"""The step loop's account of its own time (``LoopAccount``): starved time by
host phase, its slack, no-work time, the device thread's off-CPU time and the
parts of a phase, on an injected clock with injected readiness (hand-computed
sums); then counts and invariants on a tiny real engine.  No time is compared
with another run's."""

import pytest

from dynamo_tpu.engine.engine import PHASE_PARTS, STEP_PHASES
from dynamo_tpu.observability import SpanRecorder, get_recorder, set_recorder
from dynamo_tpu.observability.step_metrics import LoopAccount, StepRecord, StepTelemetry

from tests.engine.test_jax_engine import collect, make_engine, request
from tests.engine.test_step_accounting import idle_engine, settled_stats

MS = 1e-3

FLAT_KEYS = (
    "device_starved_time_total_s", "device_starved_slack_time_total_s",
    "device_starved_dispatches_total", "engine_no_work_time_total_s",
    "engine_host_offcpu_time_total_s", "engine_post_time_total_s",
    "engine_post_emit_time_total_s", "engine_post_publish_time_total_s",
    "kv_publish_blocks_hashed_total", "kv_publish_blocks_stored_total",
)


class Clocks:
    """Wall and thread-CPU time the test moves by hand."""

    def __init__(self):
        self.wall = self.cpu = 0.0
        self.newest = None                # the newest result the loop dispatched

    def run(self, wall_ms: float, cpu_ms: float | None = None) -> None:
        self.wall += wall_ms * MS
        self.cpu += (wall_ms if cpu_ms is None else cpu_ms) * MS


class Window:
    """Stands for a dispatched window's result."""

    def __init__(self, deleted: bool = False):
        self.done, self.deleted, self.asked = False, deleted, 0

    def is_ready(self) -> bool:
        self.asked += 1
        if self.deleted:
            raise RuntimeError("Array has been deleted.")
        return self.done


def account():
    clocks, episodes = Clocks(), []
    acct = LoopAccount(STEP_PHASES, PHASE_PARTS, observe=episodes.append,
                       newest=lambda: clocks.newest,
                       clock=lambda: clocks.wall, cpu_clock=lambda: clocks.cpu)
    return acct, clocks, episodes


def step_of(acct) -> dict:
    return {"starved": acct.step_starved_s, "slack": acct.step_slack_s,
            "dispatches": acct.step_starved_dispatches, "no_work": acct.step_no_work_s,
            "offcpu": acct.step_offcpu_s, "readback": acct.step_readback_s}


def starved_by_phase(acct) -> dict:
    return {name: pytest.approx(row[3]) for name, row in acct.rows.items() if row[3]}


def overlapped_step(acct, clocks, new, *, before_dispatch=(), readback_ms=10.0):
    """schedule 1, upload 1, dispatch 1 (``new`` goes out), post 1, readback
    of the window before, post 1.  ``before_dispatch`` finish while `upload`
    runs."""
    t0 = acct.begin_step()
    acct.phase("schedule")
    clocks.run(1)
    acct.phase("upload")
    clocks.run(1)
    for w in before_dispatch:
        w.done = True
    acct.phase("dispatch")
    clocks.run(1)
    clocks.newest = new
    acct.phase("post")
    clocks.run(1)
    acct.phase("readback")
    clocks.run(readback_ms)
    acct.phase("post")
    clocks.run(1)
    acct.phase(None)
    return acct.end_step() - t0


def test_overlapped_the_first_dispatch_from_an_empty_chip_is_starved_the_next_is_not():
    acct, clocks, episodes = account()
    acct.loop_started()
    w1, w2 = Window(), Window()
    took = overlapped_step(acct, clocks, w1, readback_ms=0.0)
    # nothing was queued at engine start: the chip stood empty from the
    # step's start until its dispatch phase closed
    assert step_of(acct) == {"starved": pytest.approx(3 * MS), "slack": 0.0, "dispatches": 1,
                             "no_work": 0.0, "offcpu": 0.0, "readback": 0.0}
    assert starved_by_phase(acct) == {"schedule": 1 * MS, "upload": 1 * MS, "dispatch": 1 * MS}
    assert episodes == [pytest.approx(3 * MS)] and took == pytest.approx(5 * MS)
    # device-bound: w1 still runs at every boundary of the next step
    overlapped_step(acct, clocks, w2)
    assert step_of(acct) == {"starved": 0.0, "slack": 0.0, "dispatches": 0, "no_work": 0.0,
                             "offcpu": 0.0, "readback": pytest.approx(10 * MS)}
    assert len(episodes) == 1
    # one is_ready() a boundary while w1 was the newest (post, readback,
    # post, None, schedule, upload, dispatch), then w2 took its place
    assert w1.asked == 7 and w1.done is False


def test_overlapped_a_window_that_ends_during_upload_starves_the_dispatch_with_upload_as_slack():
    acct, clocks, episodes = account()
    acct.loop_started()
    w1, w2 = Window(), Window()
    overlapped_step(acct, clocks, w1, readback_ms=0.0)
    overlapped_step(acct, clocks, w2, before_dispatch=[w1], readback_ms=0.0)
    # seen finished at the boundary upload -> dispatch: `dispatch` is
    # starved (the lower bound), `upload` is the one phase of slack
    assert step_of(acct) == {"starved": pytest.approx(1 * MS), "slack": pytest.approx(1 * MS),
                             "dispatches": 1, "no_work": 0.0, "offcpu": 0.0, "readback": 0.0}
    assert starved_by_phase(acct) == {"schedule": 1 * MS, "upload": 1 * MS, "dispatch": 2 * MS}
    assert {name: row[5] for name, row in acct.rows.items() if row[5]} == {"upload": pytest.approx(1 * MS)}
    assert acct.snapshot()["upload"]["slack_ms"] == 1.0
    assert episodes == [pytest.approx(3 * MS), pytest.approx(1 * MS)]
    # once seen finished nothing was polled until the next dispatch
    asked = w1.asked
    acct.phase("schedule"), acct.phase(None)
    assert w1.asked == asked


def sync_step(acct, clocks, new):
    """schedule 1, upload 1, dispatch 1, readback 5 of the SAME window (it
    returns when the device is done), post 2."""
    t0 = acct.begin_step()
    acct.phase("schedule")
    clocks.run(1)
    acct.phase("upload")
    clocks.run(1)
    acct.phase("dispatch")
    clocks.run(1)
    clocks.newest = new
    acct.phase("readback")
    clocks.run(5)
    new.done = True
    acct.phase("post")
    clocks.run(2)
    acct.phase(None)
    return acct.end_step() - t0


def test_synchronous_every_phase_but_readback_is_starved_and_an_episode_spans_two_steps():
    acct, clocks, episodes = account()
    acct.loop_started()
    sync_step(acct, clocks, Window())
    first = step_of(acct)
    assert first["starved"] == pytest.approx(5 * MS) and first["dispatches"] == 1
    assert first["slack"] == 0.0          # a readback's wait ends when the device does
    clocks.run(0.5)                       # the loop's housekeeping between two steps
    took = sync_step(acct, clocks, Window())
    second = step_of(acct)
    assert second == {"starved": pytest.approx(5 * MS), "slack": 0.0, "dispatches": 1,
                      "no_work": pytest.approx(0.5 * MS), "offcpu": 0.0,
                      "readback": pytest.approx(5 * MS)}
    assert second["starved"] + second["slack"] <= took
    # engine start -> first dispatch; then post of step 1 + schedule, upload,
    # dispatch of step 2, the half millisecond between the steps left out
    assert episodes == [pytest.approx(3 * MS), pytest.approx(5 * MS)]
    assert starved_by_phase(acct) == {"schedule": 2 * MS, "upload": 2 * MS,
                                      "dispatch": 2 * MS, "post": 4 * MS}


def test_no_work_is_not_starvation_and_with_the_step_time_it_is_the_loops_wall_time():
    acct, clocks, episodes = account()
    acct.loop_started()
    clocks.run(100)                       # waiting for the first request
    steps = sync_step(acct, clocks, Window())
    assert step_of(acct)["no_work"] == pytest.approx(100 * MS)
    assert step_of(acct)["starved"] == pytest.approx(5 * MS)
    acct.idle()                           # the loop found nothing more to do
    assert episodes == [pytest.approx(3 * MS), pytest.approx(2 * MS)]
    clocks.run(50)
    no_work = 100 * MS
    steps += sync_step(acct, clocks, Window())
    no_work += acct.step_no_work_s
    # the wait is the next step's no-work time; its episode starts with it
    assert acct.step_no_work_s == pytest.approx(50 * MS)
    assert step_of(acct)["starved"] == pytest.approx(5 * MS)
    assert episodes[2:] == [pytest.approx(3 * MS)]
    assert steps + no_work == pytest.approx(clocks.wall)


def test_phases_outside_a_step_book_their_wall_time_and_nothing_else():
    acct, clocks, episodes = account()
    sync_step(acct, clocks, Window())
    before = step_of(acct)
    acct.phase("readback")                # an abort retires a window between steps
    clocks.run(1)
    acct.phase("post")
    clocks.run(3, cpu_ms=1)
    acct.phase(None)
    assert acct.rows["post"][0] == pytest.approx(5 * MS) and acct.rows["post"][2] == pytest.approx(3 * MS)
    assert acct.rows["post"][3] == pytest.approx(2 * MS) and step_of(acct) == before


def test_off_cpu_is_wall_less_thread_cpu_of_every_phase_of_a_step_but_readback():
    acct, clocks, _ = account()
    acct.begin_step()
    acct.phase("schedule")
    clocks.run(2, cpu_ms=0.5)             # 1.5 ms without the CPU
    acct.phase("readback")
    clocks.run(10, cpu_ms=0.1)            # the wait for the chip: not off-CPU time
    acct.phase("post")
    clocks.run(3, cpu_ms=3)
    acct.phase(None)
    assert acct.step_offcpu_s == pytest.approx(1.5 * MS)
    assert acct.step_readback_s == pytest.approx(10 * MS)
    snap = acct.snapshot()
    assert snap["schedule"]["total_ms"] == 2.0 and snap["schedule"]["cpu_ms"] == 0.5
    assert snap["readback"]["cpu_ms"] == 0.1 and snap["post"]["cpu_ms"] == 3.0


def test_a_result_donated_out_of_sight_is_dropped_not_taken_for_finished():
    acct, clocks, episodes = account()
    overlapped_step(acct, clocks, Window(deleted=True), readback_ms=0.0)
    assert acct._probe is None and acct._idle_since is None
    assert step_of(acct)["starved"] == pytest.approx(3 * MS)   # engine start only


def test_parts_of_a_phase_and_what_a_caller_measured_itself_inside_one():
    acct, clocks, _ = account()
    emit = acct.part_row("post", "emit")  # a part its caller measures, a token
    acct.begin_step()
    acct.phase("post")
    acct.part("release")
    clocks.run(1)
    acct.part("tokens")                   # closes `release`
    for _ in range(4):                    # a token: 0.25 ms of rules, 0.5 ms of emit
        clocks.run(0.25)
        clocks.run(0.5)
        emit[0] += 0.5 * MS
        emit[1] += 1
    acct.part(None)
    clocks.run(0.5)                       # the phase's own bookkeeping
    acct.part("slots")                    # not a part of `post`: opens nothing
    clocks.run(0.5)
    acct.phase(None)                      # would have closed an open part too
    parts = acct.snapshot()["post"]["parts"]
    assert parts == {"release": {"total_ms": 1.0, "n": 1}, "tokens": {"total_ms": 1.0, "n": 1},
                     "emit": {"total_ms": 2.0, "n": 4}, "publish": {"total_ms": 0.0, "n": 0}}
    assert acct.snapshot()["post"]["total_ms"] == 5.0
    # a part opened with no phase open books nothing
    acct.part("tokens")
    clocks.run(1)
    acct.part(None)
    assert acct.snapshot()["post"]["parts"]["tokens"] == {"total_ms": 1.0, "n": 1}


class Shown:
    """Stands for jax.profiler.TraceAnnotation: what was open, in order."""

    log: list = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        Shown.log.append(("open", self.name, self.attrs))

    def __exit__(self, *exc):
        Shown.log.append(("close", self.name))


def test_phases_and_parts_are_shown_nested_and_a_part_closes_before_its_phase():
    Shown.log = []
    acct = LoopAccount(STEP_PHASES, PHASE_PARTS, annotate=Shown)
    acct.phase("schedule")
    acct.part("admit")
    acct.part("slots")
    acct.phase("dispatch", kind="decode", tokens=3)
    acct.phase(None)
    assert Shown.log == [
        ("open", "dyn.schedule", {}), ("open", "dyn.schedule.admit", {}),
        ("close", "dyn.schedule.admit"), ("open", "dyn.schedule.slots", {}),
        ("close", "dyn.schedule.slots"), ("close", "dyn.schedule"),
        ("open", "dyn.dispatch", {"kind": "decode", "tokens": 3}), ("close", "dyn.dispatch")]


def test_the_step_record_carries_the_account_and_the_telemetry_sums_it():
    tele = StepTelemetry(max_batch_size=4)
    for _ in range(3):
        tele.observe(StepRecord(duration_s=0.01, num_running=1, starved_s=0.002,
                                starved_slack_s=0.001, starved_dispatches=1,
                                offcpu_s=0.0005, no_work_s=0.1))
    stats = tele.stats()
    assert stats["device_starved_time_total_s"] == pytest.approx(0.006)
    assert stats["device_starved_slack_time_total_s"] == pytest.approx(0.003)
    assert stats["device_starved_dispatches_total"] == 3
    assert stats["engine_host_offcpu_time_total_s"] == pytest.approx(0.0015)
    assert stats["engine_no_work_time_total_s"] == pytest.approx(0.3)


# -- a tiny real engine ---------------------------------------------------------

@pytest.mark.parametrize("key", FLAT_KEYS)
def test_every_new_key_is_in_stats_from_engine_start_at_zero(key):
    assert idle_engine().stats()[key] == 0


@pytest.mark.parametrize("phase", STEP_PHASES)
def test_every_phase_carries_cpu_starved_and_its_parts_from_engine_start(phase):
    row = idle_engine().stats()["phase_ms"][phase]
    assert (row["total_ms"], row["n"], row["mean_ms"], row["cpu_ms"], row["starved_ms"],
            row["slack_ms"]) == (0, 0, 0, 0, 0, 0)
    assert set(row.get("parts", {})) == set(PHASE_PARTS.get(phase, ()))
    assert all(part == {"total_ms": 0.0, "n": 0} for part in row.get("parts", {}).values())


@pytest.fixture
def fresh_recorder():
    old = get_recorder()
    set_recorder(SpanRecorder(max_spans=64))
    yield get_recorder()
    set_recorder(old)


async def served(**kwargs) -> dict:
    engine = make_engine(**kwargs)
    try:
        for prompt in (range(3, 9), range(20, 31)):
            await collect(engine, request(list(prompt), max_tokens=6, ignore_eos=True))
        return await settled_stats(engine)
    finally:
        engine.stop()


@pytest.mark.parametrize("overlap", [True, False], ids=["overlapped", "synchronous"])
async def test_a_served_window_keeps_the_accounts_invariants(overlap, fresh_recorder):
    stats = await served(decode_overlap=overlap)
    phases = stats["phase_ms"]
    # the six names and their totals as before, with the new columns beside
    assert set(phases) == set(STEP_PHASES)
    for name, row in phases.items():
        # the two clocks are read one after the other, a boundary: a phase
        # wholly on the CPU may read a clock's read more than its wall time
        assert row["cpu_ms"] <= row["total_ms"] + 0.05 * max(row["n"], 1), (name, row)
        assert row["starved_ms"] <= row["total_ms"] + 0.01, (name, row)
    step_s = stats["engine_step_time_total_s"]
    starved, slack = stats["device_starved_time_total_s"], stats["device_starved_slack_time_total_s"]
    assert 0 < starved and starved + slack <= step_s
    assert starved == pytest.approx(sum(row["starved_ms"] for row in phases.values()) / 1e3, abs=1e-4)
    assert slack == pytest.approx(sum(row["slack_ms"] for row in phases.values()) / 1e3, abs=1e-4)
    assert 1 <= stats["device_starved_dispatches_total"] <= phases["dispatch"]["n"]
    # signed, a phase: two clocks read one after the other, CPU time charged by the tick
    host = stats["engine_host_time_total_s"]
    assert -0.2 * host - 1e-3 <= stats["engine_host_offcpu_time_total_s"] <= host + 1e-6
    assert stats["engine_no_work_time_total_s"] > 0       # it waited for each request
    # the parts of `post` lie inside `post`; what is left is its bookkeeping
    post = phases["post"]
    parts = sum(part["total_ms"] for part in post["parts"].values())
    assert 0 < parts <= post["total_ms"] + 0.01
    assert post["parts"]["emit"]["n"] == stats["tokens_emitted_total"] == 12
    assert post["parts"]["tokens"]["n"] >= 2
    assert stats["engine_post_time_total_s"] == pytest.approx(post["total_ms"] / 1e3, abs=1e-5)
    assert stats["engine_post_emit_time_total_s"] == pytest.approx(
        post["parts"]["emit"]["total_ms"] / 1e3, abs=1e-5)
    assert stats["engine_post_publish_time_total_s"] == pytest.approx(
        post["parts"]["publish"]["total_ms"] / 1e3, abs=1e-5)
    # the duration series: a busy step a sample, an episode a sample
    series = stats["spans"]["series"]["engine"]
    busy = series["engine.step.decode"]["count"] + series["engine.step.prompt"]["count"]
    assert busy == stats["engine_busy_steps_total"]
    assert series["engine.step.prompt"]["count"] >= 2
    assert series["engine.starved"]["count"] >= stats["device_starved_dispatches_total"]
    assert series["engine.starved"]["total_s"] == pytest.approx(starved, abs=1e-4)


async def test_without_overlap_every_phase_after_a_window_but_readback_is_starved(
        fresh_recorder, monkeypatch):
    """DYN_DECODE_OVERLAP=0: each window is read back before the next is
    built, so the chip stands empty through every other phase."""
    monkeypatch.setenv("DYN_DECODE_OVERLAP", "0")
    stats = await served()
    assert stats["decode_windows_overlapped_total"] == 0
    phases = stats["phase_ms"]
    # every dispatch ended an episode, and was itself wholly starved
    assert stats["device_starved_dispatches_total"] == phases["dispatch"]["n"]
    assert phases["dispatch"]["starved_ms"] == pytest.approx(
        phases["dispatch"]["total_ms"], abs=0.02 * phases["dispatch"]["n"])
    # (on the CPU a program's results turn ready one after another: a window
    # whose tokens were read may be seen finished a boundary late, and that
    # phase is then the episode's slack, not its starved time)
    host_ms = sum(phases[name]["total_ms"] for name in ("schedule", "upload", "dispatch", "post"))
    starved = stats["device_starved_time_total_s"] * 1e3
    slack = stats["device_starved_slack_time_total_s"] * 1e3
    assert all(phases[name]["starved_ms"] > 0 for name in ("schedule", "upload", "dispatch", "post"))
    assert starved >= 0.9 * host_ms and starved + slack >= 0.98 * host_ms - 0.5
    assert starved + slack <= stats["engine_step_time_total_s"] * 1e3
