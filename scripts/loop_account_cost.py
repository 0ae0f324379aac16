#!/usr/bin/env python3
"""What the step loop's accounting costs a step, alone (builder's tool).

Plays the boundaries of ONE overlapped decode step of a model with a window
pool and expert counters (the longest list of boundaries a step has) through
``LoopAccount`` as the engine binds it (``_phase``, ``_part``): 7 phase
boundaries (two clock reads, one ``is_ready()`` on a real device array that
answers "not yet", one TraceMe each), 9 part boundaries, ``--lanes`` tokens'
worth of the two clock reads around ``emit``, the step's begin and end and the
recorder's ``engine.step.decode`` sample.  No model, no request: the work
between the boundaries is left out, so the time is the accounting's own.
Beside it the same step through the accounting of the tree before ISSUE 44
(seven ``_phase`` calls: two ``perf_counter`` reads and a TraceMe each).

    python scripts/loop_account_cost.py [--lanes 24] [--steps 20000]

Prints one JSON line.  Run it on the machine whose host serves (through the
chip tool): a time from another host is not the cell's.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class NotYet:
    """A dispatched window's result that is still running: the real
    array's ``is_ready()`` is paid, its answer is not used."""

    def __init__(self, array):
        self._array = array

    def is_ready(self) -> bool:
        self._array.is_ready()
        return False


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--lanes", type=int, default=24)
    p.add_argument("--steps", type=int, default=20000)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.engine import PHASE_PARTS, STEP_PHASES
    from dynamo_tpu.observability import get_recorder
    from dynamo_tpu.observability.step_metrics import LoopAccount

    class Loop:
        """The little of the engine its boundaries touch."""

        def __init__(self):
            self._gen_counts = NotYet(jax.block_until_ready(jnp.zeros((args.lanes, 8), jnp.int32)))
            self.loop_account = LoopAccount(
                STEP_PHASES, PHASE_PARTS,
                observe=lambda s: get_recorder().observe("engine.starved", s, component="engine"),
                newest=lambda: self._gen_counts, annotate=jax.profiler.TraceAnnotation)
            self._phase, self._part = self.loop_account.phase, self.loop_account.part
            self._emit_row = self.loop_account.part_row("post", "emit")

    loop = Loop()
    lanes = range(args.lanes)

    def step_now():
        acct = loop.loop_account
        t_step = acct.begin_step()
        loop._phase("schedule")
        loop._part("admit")
        loop._part("slots")
        loop._part("build")
        loop._part("tables")
        loop._phase("upload")
        loop._part("sampling")
        loop._part("arrays")
        loop._phase("dispatch", kind="decode", tokens=args.lanes)
        loop._phase("post")
        loop._part("release")
        loop._part(None)
        loop._phase("readback", kind="decode")
        loop._phase("post")
        loop._part("tokens")
        row = loop._emit_row
        for _ in lanes:
            t0 = time.perf_counter()
            row[0] += time.perf_counter() - t0
            row[1] += 1
        loop._phase(None)
        get_recorder().observe("engine.step.decode", acct.end_step() - t_step, component="engine")

    state = {"name": None, "t0": 0.0, "ann": None, "rows": {n: [0.0, 0] for n in STEP_PHASES}}

    def phase_before(name, **attrs):
        now = time.perf_counter()
        cur = state["name"]
        if cur is not None:
            row = state["rows"][cur]
            row[0] += now - state["t0"]
            row[1] += 1
            state["ann"].__exit__(None, None, None)
        state["name"] = name
        if name is not None:
            state["t0"] = now
            state["ann"] = jax.profiler.TraceAnnotation("dyn." + name, **attrs)
            state["ann"].__enter__()

    def step_before():
        phase_before("schedule")
        phase_before("upload")
        phase_before("dispatch", kind="decode", tokens=args.lanes)
        phase_before("post")
        phase_before("readback", kind="decode")
        phase_before("post")
        phase_before(None)

    def us_a_step(fn) -> dict:
        for _ in range(2000):
            fn()
        rounds = []
        for _ in range(9):
            t0 = time.perf_counter()
            for _ in range(args.steps):
                fn()
            rounds.append((time.perf_counter() - t0) / args.steps * 1e6)
        return {"median": statistics.median(rounds), "min": min(rounds), "max": max(rounds)}

    now, before = us_a_step(step_now), us_a_step(step_before)
    clock = us_a_step(time.perf_counter)
    cpu_clock = us_a_step(time.thread_time)
    ready = us_a_step(loop._gen_counts.is_ready)
    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "lanes": args.lanes, "steps_a_round": args.steps, "rounds": 9,
        "accounting_us_a_step": now, "before_issue_44_us_a_step": before,
        "added_us_a_step": now["median"] - before["median"],
        "perf_counter_us": clock["median"], "thread_time_us": cpu_clock["median"],
        "is_ready_us": ready["median"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
