"""Engine step telemetry.

The engine's device loop builds one :class:`StepRecord` per scheduler
iteration and hands the same object to :meth:`StepTelemetry.observe`, to
``UtilizationTracker.observe`` and to the flight ring (plain Python
assignments under the GIL — safe to read from the asyncio thread).  The
snapshot rides the existing telemetry path: ``JaxLlmEngine.stats()`` merges
it, ``WorkerMetricsPublisher`` ships it as ``ForwardPassMetrics``, and
``components/metrics_service.py`` exports it as ``dyn_worker_*`` Prometheus
gauges — no new registry, one coherent pipeline.

Every iteration is booked by KIND: ``prompt`` when the window it is booked
to carried at least one prompt token, ``decode`` when it carried none.  The
window an iteration is booked to is the one the DEVICE was executing while
the iteration ran: under the overlapped pipeline iteration k schedules and
dispatches window k and then waits for window k-1, so its time belongs to
window k-1 (the engine passes that window's kind; see
``JaxLlmEngine._device_loop``).

:class:`LoopAccount` is the loop's account of its own wall time, always on:
the host phases (wall and thread-CPU seconds, and the named parts of the
large ones), how long the chip stood empty while the loop had work
(``starved``), and the time the loop had none (``no work``).  It reads two
clocks and one ``is_ready()`` a phase boundary; the engine hands the step's
share of it to the step's one :class:`StepRecord`.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

KIND_DECODE, KIND_PROMPT = "decode", "prompt"


@dataclass(slots=True)
class StepRecord:
    """Everything one engine iteration knows about itself, built once."""

    iteration: int = 0
    kind: str = KIND_DECODE             # of the window this time is booked to
    duration_s: float = 0.0
    readback_wait_s: float = 0.0        # host blocked on the device
    num_running: int = 0
    num_waiting: int = 0
    kv_active_blocks: int = 0
    kv_total_blocks: int = 0
    prefill_tokens: int = 0             # prompt tokens computed this step
    decode_tokens: int = 0              # decode positions computed this step
    decode_lane_steps: int = 0          # running decode lanes x device steps
    attn_ctx_tokens: int = 0            # attended context positions
    weight_streams: float = 0.0         # full weight passes dispatched
    emitted_tokens: int = 0
    # no lane of the window this time is booked to samples, so its program
    # took ``sample_tokens``' empty branch: no sort over the vocabulary
    sample_sort_skipped: bool = False
    # the loop's account of this step (LoopAccount): the chip empty while
    # the loop had work (a lower bound, and the one phase of slack above
    # it), the dispatches that ended such an episode, the device thread
    # without the CPU outside `readback`, and the loop's time with no work
    # BEFORE this step
    starved_s: float = 0.0
    starved_slack_s: float = 0.0
    starved_dispatches: int = 0
    offcpu_s: float = 0.0
    no_work_s: float = 0.0
    # filled by UtilizationTracker.observe (the cost model lives there)
    flops: float = 0.0


@dataclass
class StepSnapshot:
    """State of the most recent engine step."""

    iteration: int = 0
    num_running: int = 0
    num_waiting: int = 0
    batch_occupancy_perc: float = 0.0   # running lanes / max_batch_size
    kv_usage_perc: float = 0.0          # used blocks / pool blocks
    kv_active_blocks: int = 0
    prefill_tokens: int = 0             # prompt tokens computed this step
    decode_tokens: int = 0              # decode positions computed this step


class StepTelemetry:
    """Latest-step snapshot + monotone counters, cheap enough for every step."""

    def __init__(self, max_batch_size: int):
        self.max_batch_size = max(max_batch_size, 1)
        self.snapshot = StepSnapshot()
        self.steps_total = 0
        self.busy_steps_total = 0        # steps with at least one running lane
        self.sample_sort_skipped_steps_total = 0  # those of them that sorted no vocabulary
        self.step_time_total_s = 0.0
        # by kind of the window the time is booked to
        self.kind_steps_total = {KIND_DECODE: 0, KIND_PROMPT: 0}
        self.kind_time_total_s = {KIND_DECODE: 0.0, KIND_PROMPT: 0.0}
        self.host_time_total_s = 0.0     # step time less readback wait
        self.decode_lane_steps_total = 0
        # the loop's account of its time (LoopAccount), summed over steps
        self.starved_time_total_s = 0.0
        self.starved_slack_time_total_s = 0.0
        self.starved_dispatches_total = 0
        self.host_offcpu_time_total_s = 0.0
        self.no_work_time_total_s = 0.0

    def observe(self, rec: StepRecord) -> None:
        self.snapshot = StepSnapshot(
            iteration=rec.iteration,
            num_running=rec.num_running,
            num_waiting=rec.num_waiting,
            batch_occupancy_perc=rec.num_running / self.max_batch_size,
            kv_usage_perc=(
                rec.kv_active_blocks / rec.kv_total_blocks
                if rec.kv_total_blocks else 0.0
            ),
            kv_active_blocks=rec.kv_active_blocks,
            prefill_tokens=rec.prefill_tokens,
            decode_tokens=rec.decode_tokens,
        )
        self.steps_total += 1
        if rec.num_running:
            self.busy_steps_total += 1
            self.sample_sort_skipped_steps_total += rec.sample_sort_skipped
        self.step_time_total_s += rec.duration_s
        self.kind_steps_total[rec.kind] += 1
        self.kind_time_total_s[rec.kind] += rec.duration_s
        self.host_time_total_s += max(0.0, rec.duration_s - rec.readback_wait_s)
        self.decode_lane_steps_total += rec.decode_lane_steps
        self.starved_time_total_s += rec.starved_s
        self.starved_slack_time_total_s += rec.starved_slack_s
        self.starved_dispatches_total += rec.starved_dispatches
        self.host_offcpu_time_total_s += rec.offcpu_s
        self.no_work_time_total_s += rec.no_work_s

    def stats(self) -> dict:
        """Merged into ``JaxLlmEngine.stats()`` (names stable: the wire
        protocol and the Prometheus exporter key off them).  The ``step_*``
        names are the state AT the latest step — a coherent point-in-time
        view, unlike the live scheduler/allocator reads the engine's other
        stats fields take mid-drain."""
        s = self.snapshot
        return {
            "batch_occupancy_perc": s.batch_occupancy_perc,
            "step_num_running": s.num_running,
            "step_num_waiting": s.num_waiting,
            "step_kv_usage_perc": s.kv_usage_perc,
            "step_kv_active_blocks": s.kv_active_blocks,
            "engine_steps_total": self.steps_total,
            "engine_busy_steps_total": self.busy_steps_total,
            "sample_sort_skipped_steps_total": self.sample_sort_skipped_steps_total,
            "engine_step_time_total_s": self.step_time_total_s,
            "engine_decode_steps_total": self.kind_steps_total[KIND_DECODE],
            "engine_decode_step_time_total_s": self.kind_time_total_s[KIND_DECODE],
            "engine_prompt_steps_total": self.kind_steps_total[KIND_PROMPT],
            "engine_prompt_step_time_total_s": self.kind_time_total_s[KIND_PROMPT],
            "engine_host_time_total_s": self.host_time_total_s,
            "decode_lane_steps_total": self.decode_lane_steps_total,
            "device_starved_time_total_s": self.starved_time_total_s,
            "device_starved_slack_time_total_s": self.starved_slack_time_total_s,
            "device_starved_dispatches_total": self.starved_dispatches_total,
            "engine_host_offcpu_time_total_s": self.host_offcpu_time_total_s,
            "engine_no_work_time_total_s": self.no_work_time_total_s,
        }


class LoopAccount:
    """Where the step loop's wall time goes, kept by the loop itself.

    **Phases.**  ``phase(name)`` closes the open host phase and opens
    ``name``: a row a phase of wall seconds, count, thread-CPU seconds
    (``time.thread_time``) and starved seconds, and under it the named
    ``part`` s of a phase that was split.  Wall less CPU of every phase of a
    step but ``readback`` is the step's ``offcpu_s``: the device thread held
    no CPU while it was supposed to be working (the GIL in another thread's
    hands, a page fault, a descheduled process; it does not say which).  In
    ``readback`` the same difference is the wait for the chip.  The
    difference is kept signed and read over a window: a kernel that charges
    CPU time by the tick makes one step's value noise (the chip's host does,
    and a read of its thread clock is a system call of about 6 us).

    **Starved.**  When a ``dispatch`` phase closes the account takes the
    newest result the loop dispatched.  At every boundary after that, until
    the result is seen finished, it is asked ``is_ready()``.  From the first
    boundary that sees it finished until the next ``dispatch`` phase closes
    the device has nothing queued: that wall time, inside a step, is
    ``starved``, booked to the phases it spans; the dispatch that ends it
    is counted and the episode's length handed to ``observe``.  It is a
    LOWER bound: the device finished somewhere inside the phase before that
    boundary, and that phase's length is the ``slack``, booked to that
    phase (none after a ``readback``: that wait ended when the device did),
    so starved + slack is the upper bound.  Once a result is seen finished nothing is polled
    until the next dispatch: a device-bound step pays one ``is_ready()`` a
    boundary, all false.  Only the step programs are watched; blocks
    injected, restored or offloaded between them are not.

    **No work.**  The loop's time outside a step (waiting for a request,
    paging hinted blocks, its housekeeping between two steps) is the next
    step's ``no_work_s``, never starvation: an episode is booked no earlier
    than its step's start, and one still open when the loop runs out of
    work ends there (``idle``).  Step time + no-work time is the loop's
    wall time.
    """

    def __init__(self, phases, parts=None, *, observe=None, newest=None, annotate=None,
                 clock=time.perf_counter, cpu_clock=time.thread_time):
        """``observe(seconds)`` takes each starved episode's length;
        ``newest()`` answers the newest result the loop dispatched (asked
        once a ``dispatch`` phase, when it closes); ``annotate(name,
        **attrs)`` makes the context manager that shows a phase or a part
        on a profiler's clock (``jax.profiler.TraceAnnotation``)."""
        self._clock, self._cpu_clock = clock, cpu_clock
        self._observe, self._newest, self._annotate = observe, newest, annotate
        # phase -> [wall s, count, thread-CPU s, starved s, {part: [wall s, count]}, slack s]
        self.rows = {
            name: [0.0, 0, 0.0, 0.0, {p: [0.0, 0] for p in (parts or {}).get(name, ())}, 0.0]
            for name in phases
        }
        self.name: str | None = None    # the open phase
        self._row: list | None = None   # its row
        self._t0 = self._cpu0 = 0.0
        self._ann = None                # its annotation
        self._part: list | None = None  # the open part's row
        self._part_t0 = self._part_inner0 = 0.0
        self._part_ann = None
        # rows a caller adds to itself (``part_row``): what they gain while
        # a part is open is not that part's own time
        self._inner_rows: list[list] = []
        self._probe = None              # newest result dispatched, until seen finished
        # the device has had nothing queued since (None: a window is out);
        # at engine start nothing is
        self._idle_since: float | None = 0.0
        self._episode_s = 0.0
        self._in_step = False
        self._mark: float | None = None  # the last step's end (the loop's start)
        # the open step's share, for its StepRecord
        self.step_readback_s = self.step_starved_s = self.step_slack_s = 0.0
        self.step_offcpu_s = self.step_no_work_s = 0.0
        self.step_starved_dispatches = 0

    # -- the loop and its steps --------------------------------------------
    def loop_started(self) -> None:
        self._mark = self._clock()

    def idle(self) -> None:
        """The loop found no work: an episode left open ends here."""
        self._end_episode()

    def begin_step(self) -> float:
        now = self._clock()
        self.step_no_work_s = 0.0 if self._mark is None else now - self._mark
        self.step_readback_s = self.step_starved_s = self.step_slack_s = 0.0
        self.step_offcpu_s = 0.0
        self.step_starved_dispatches = 0
        self._in_step = True
        return now

    def end_step(self) -> float:
        """The step's end on the account's clock (phases closed first)."""
        self._in_step = False
        self._mark = self._clock()
        return self._mark

    def abandon_step(self) -> None:
        """A step that raised books nothing; its time falls to no-work."""
        self._in_step = False

    # -- phases -------------------------------------------------------------
    def phase(self, name: str | None, **attrs) -> None:
        """Close the open phase and open ``name`` (None: just close).
        ``attrs`` ride on the phase's annotation."""
        now = self._clock()
        cpu = self._cpu_clock()
        cur = self.name
        dt = starved = 0.0
        if cur is not None:
            if self._part is not None:
                self._close_part(now)
            t0 = self._t0
            dt = now - t0
            on_cpu = cpu - self._cpu0
            row = self._row
            row[0] += dt
            row[1] += 1
            row[2] += on_cpu
            if self._in_step:
                if cur == "readback":
                    self.step_readback_s += dt
                else:
                    # signed: where the kernel charges CPU time by the
                    # scheduler's tick a short phase reads 0 or a whole
                    # tick, and only the sum over many is a reading
                    self.step_offcpu_s += dt - on_cpu
                idle = self._idle_since
                if idle is not None:
                    starved = now - (idle if idle > t0 else t0)
                    row[3] += starved
                    self.step_starved_s += starved
                    self._episode_s += starved
            if cur == "dispatch":
                if self._idle_since is not None:
                    self._idle_since = None
                    if self._in_step:
                        self.step_starved_dispatches += 1
                    self._end_episode()
                if self._newest is not None:
                    self._probe = self._newest()
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
        probe = self._probe
        if probe is not None:
            try:
                done = probe.is_ready()
            except RuntimeError:
                # donated to a program outside the step's phases (a lane's
                # row set, a verify warm-up): lost from sight, not finished
                self._probe, done = None, False
            if done:
                self._probe = None
                self._idle_since = now
                if self._in_step and cur is not None and cur != "readback":
                    # less what the phase already booked as starved, before
                    # its own dispatch: never more than the phase in all
                    self.step_slack_s += dt - starved
                    self._row[5] += dt - starved
        self.name = name
        if name is not None:
            self._t0, self._cpu0 = now, cpu
            row = self.rows.get(name)
            if row is None:         # a phase of no step (`prefetch.page`)
                row = self.rows[name] = [0.0, 0, 0.0, 0.0, {}, 0.0]
            self._row = row
            if self._annotate is not None:
                self._ann = self._annotate("dyn." + name, **attrs)
                self._ann.__enter__()

    def _end_episode(self) -> None:
        if self._observe is not None and self._episode_s > 0.0:
            self._observe(self._episode_s)
        self._episode_s = 0.0

    # -- parts of a phase ---------------------------------------------------
    def part(self, name: str | None) -> None:
        """Inside the open phase: close the open part and open ``name``
        (None: just close; the phase's close and the next part's opening
        close it too), shown as ``dyn.<phase>.<part>``.  For parts that run
        once a step."""
        now = self._clock()
        if self._part is not None:
            self._close_part(now)
        phase = self.name
        if name is None or phase is None:
            return
        # a name the open phase does not declare opens nothing (the split
        # step prepares its decode window while `post` is still open)
        row = self._row[4].get(name)
        if row is not None:
            self._part = row
            self._part_t0 = now
            self._part_inner0 = self._inner()
            if self._annotate is not None:
                self._part_ann = self._annotate(f"dyn.{phase}.{name}")
                self._part_ann.__enter__()

    def _inner(self) -> float:
        total = 0.0
        for row in self._inner_rows:
            total += row[0]
        return total

    def _close_part(self, now: float) -> None:
        row = self._part
        row[0] += now - self._part_t0 - (self._inner() - self._part_inner0)
        row[1] += 1
        self._part = None
        if self._part_ann is not None:
            self._part_ann.__exit__(None, None, None)
            self._part_ann = None

    def part_row(self, phase: str, name: str) -> list:
        """``[wall s, count]`` of a part its caller measures itself (one
        that runs once a token: two clock reads, no boundary, no
        annotation) and adds to in place."""
        row = self.rows[phase][4].setdefault(name, [0.0, 0])
        if not any(row is held for held in self._inner_rows):
            self._inner_rows.append(row)
        return row

    # -- reading ------------------------------------------------------------
    def reset(self) -> None:
        for row in self.rows.values():
            row[:4] = [0.0, 0, 0.0, 0.0]
            row[5] = 0.0
            for part in row[4].values():
                part[:] = [0.0, 0]

    def snapshot(self) -> dict:
        """``{phase: {total_ms, n, mean_ms, cpu_ms, starved_ms, slack_ms[,
        parts]}}``, safe to take from another thread while the loop books."""
        out = {}
        for name, row in list(self.rows.items()):
            tot, n, cpu, starved, parts, slack = tuple(row)
            out[name] = {
                "total_ms": round(tot * 1e3, 2), "n": n,
                "mean_ms": round(tot / n * 1e3, 3) if n else 0.0,
                "cpu_ms": round(cpu * 1e3, 2),
                "starved_ms": round(starved * 1e3, 2),
                "slack_ms": round(slack * 1e3, 2),
            }
            if parts:
                out[name]["parts"] = {
                    p: {"total_ms": round(s * 1e3, 2), "n": k}
                    for p, (s, k) in list(parts.items())
                }
        return out
