"""The reduction from a profiler trace (``.xplane.pb``) to device busy time,
per-operation time and idle gaps.

``reduce_planes`` is pure: it takes ``{plane name: {line name: [(event name,
start ns, duration ns), …]}}`` and is tested on a hand-built trace.
``load_planes`` reads a real file through ``jax.profiler.ProfileData`` and
keeps only the device planes.

On a TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds
one event per executed operation (kernels included) and ``XLA Modules`` one
per launched program.  Busy time is the union of the ``XLA Ops`` intervals,
averaged over the chips; the window is the span from the first operation's
start to the last one's end.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
NOT_OPS = {MODULES_LINE, "Steps", "XLA TraceMe", "Framework Name Scope", "Framework Ops",
           "Source code"}


def start_options():
    """Device events only: the Python tracer would record every call of the
    server's host code and slow it."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def find_xplane(trace_dir: str) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_planes(path) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    planes = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        planes[plane.name] = {
            line.name: [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
            for line in plane.lines
        }
    return planes


def _ops_of(lines: dict) -> list:
    if OPS_LINE in lines:
        return lines[OPS_LINE]
    return [e for name, evs in lines.items() if name not in NOT_OPS for e in evs]


def _union(events) -> tuple[float, list]:
    """Busy nanoseconds and the idle gaps ``(start, end, before, after)``
    between merged intervals."""
    busy, gaps = 0.0, []
    cur_start = cur_end = None
    last_name = None
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if cur_end is None:
            cur_start, cur_end = start, end
        elif start > cur_end:
            busy += cur_end - cur_start
            gaps.append((cur_end, start, last_name, name))
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
        if end >= cur_end:
            last_name = name
    if cur_end is not None:
        busy += cur_end - cur_start
    return busy, gaps


def _module_at(modules: list, t: float) -> str | None:
    for name, start, dur in modules:
        if start <= t <= start + dur:
            return name
    return None


def short(name: str, limit: int = 80) -> str:
    return name if len(name) <= limit else name[: limit - 1] + "~"


def reduce_planes(planes: dict, top: int = 10) -> dict:
    """``busy_s`` and ``window_s`` averaged over the device planes that ran
    anything, time per operation name (``ops``: seconds, summed over chips)
    and how many events that time is of (``op_events``: launches of a
    kernel), and the longest idle gaps named by the programs on either side."""
    per_op: dict[str, float] = {}
    events: dict[str, int] = {}
    busy_ns, window_ns, n = 0.0, 0.0, 0
    all_gaps = []
    line_names = {}
    for pname, lines in planes.items():
        line_names[pname] = {k: len(v) for k, v in lines.items()}
        ops = _ops_of(lines)
        if not ops:
            continue
        n += 1
        b, gaps = _union(ops)
        busy_ns += b
        window_ns += max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)
        for name, _, dur in ops:
            per_op[name] = per_op.get(name, 0.0) + dur
            events[name] = events.get(name, 0) + 1
        modules = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        for g0, g1, before, after in gaps:
            a = _module_at(modules, g0 - 1.0) or before
            z = _module_at(modules, g1 + 1.0) or after
            all_gaps.append((f"{short(a or '?', 36)}->{short(z or '?', 36)}", (g1 - g0) / 1e9))
    if n == 0:
        return {"busy_s": 0.0, "window_s": 0.0, "chips": 0, "ops": {}, "op_events": {}, "device_ops": [],
                "idle_gaps": [], "gap_kinds": [], "lines": line_names}
    kinds: dict[str, float] = {}
    for name, sec in all_gaps:
        kinds[name] = kinds.get(name, 0.0) + sec
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])
    # a loop's event spans its body's events: the ranking lists leaves only
    leaves = [(k, v) for k, v in ranked if not k.startswith(("%while", "%conditional", "%call"))]
    return {
        "busy_s": busy_ns / n / 1e9, "window_s": window_ns / n / 1e9, "chips": n,
        "ops": {k: v / 1e9 for k, v in ranked},
        "op_events": {k: events[k] for k, _ in ranked},
        "device_ops": [[short(k), v / 1e9] for k, v in leaves[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(all_gaps, key=lambda g: -g[1])[:top]],
        "gap_kinds": [[k, v] for k, v in sorted(kinds.items(), key=lambda kv: -kv[1])[:top]],
        "lines": line_names,
    }


def reduce_dir(trace_dir: str, keep=(), largest: int = 200) -> dict:
    """The reduction of the trace under ``trace_dir``, its per-operation
    tables cut to the ``largest`` operations by time and every operation
    whose name one of the ``keep`` patterns matches: what a metric's reader
    looks for is there however little time it took."""
    out = reduce_planes(load_planes(find_xplane(trace_dir)))
    wanted = [re.compile(p) for p in keep]
    names = [name for rank, name in enumerate(out["ops"])
             if rank < largest or any(rx.search(name) for rx in wanted)]
    for table in ("ops", "op_events"):
        out[table] = {name: out[table][name] for name in names}
    return out
