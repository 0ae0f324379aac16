"""The program's host phases set beside the device's programs, on the
profiler's one clock (builder's tool; not wired into ``/trace/reduce`` yet).

The engine wraps every host phase of its step loop in a
``jax.profiler.TraceAnnotation("dyn.<phase>")``.  ``launcher.py`` traces with
``host_tracer_level = 1``, so those land in the same ``.xplane.pb`` as the
device planes: host events on ``/host:CPU`` (one line per thread), programs on
``/device:TPU:<n>`` / ``XLA Modules``, operations on ``XLA Ops``.

``attribute`` is pure: it takes ``{plane: {line: [(name, start ns, duration
ns), …]}}`` like ``trace.reduce_planes`` (host planes included) and answers,
for every launch on ``XLA Modules`` and every idle gap of the device, which
``dyn.*`` annotation covers that instant or, if none does, ended last before
it; and for every launch how long before its start on the device the host
had opened the ``dyn.dispatch`` that sent it (the lead: how far the host
runs ahead of the chip).

    JAX_PLATFORMS=cpu python3 benchmark/host_spans.py <trace dir or .xplane.pb>
"""

from __future__ import annotations

import bisect
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import trace  # noqa: E402

PREFIX = "dyn."
DISPATCH = "dyn.dispatch"


def load_planes(path) -> dict:
    """Every plane of the file, host planes included."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    return {plane.name: {line.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                                     for e in line.events]
                         for line in plane.lines}
            for plane in data.planes}


def annotations(planes: dict) -> list:
    """The ``dyn.*`` host events of every non-device plane, by start."""
    out = [e for pname, lines in planes.items() if not trace.DEVICE_PLANE.match(pname)
           for events in lines.values() for e in events if e[0].startswith(PREFIX)]
    return sorted(out, key=lambda e: e[1])


def phase_at(anns: list, starts: list, t: float):
    """``(name, covers)``: the annotation open at ``t``, else the one that
    ended last before it; ``(None, False)`` before the first."""
    i = bisect.bisect_right(starts, t)
    best = None
    for name, start, dur in reversed(anns[max(0, i - 8):i]):
        if start + dur >= t:
            return name, True
        if best is None or start + dur > best[1]:
            best = (name, start + dur)
    return (best[0], False) if best else (None, False)


def attribute(planes: dict, top: int = 5) -> dict:
    anns = annotations(planes)
    starts = [a[1] for a in anns]
    dispatch_starts = [a[1] for a in anns if a[0] == DISPATCH]
    launches, leads_ms = {}, []
    gap_s: dict[str, float] = {}
    longest = []
    for pname, lines in planes.items():
        if not trace.DEVICE_PLANE.match(pname):
            continue
        for name, start, _ in lines.get(trace.MODULES_LINE, []):
            phase, covers = phase_at(anns, starts, start)
            key = f"{phase or 'none'}{'' if covers else ' (ended)'}"
            launches[key] = launches.get(key, 0) + 1
            i = bisect.bisect_right(dispatch_starts, start)
            if i:
                leads_ms.append((start - dispatch_starts[i - 1]) / 1e6)
        _, gaps = trace._union(trace._ops_of(lines))
        for g0, g1, _, _ in gaps:
            phase, covers = phase_at(anns, starts, g0 + (g1 - g0) / 2)
            key = f"{phase or 'none'}{'' if covers else ' (ended)'}"
            gap_s[key] = gap_s.get(key, 0.0) + (g1 - g0) / 1e9
            longest.append(((g1 - g0) / 1e9, key))
    names: dict[str, int] = {}
    for name, _, _ in anns:
        names[name] = names.get(name, 0) + 1
    return {
        "annotations": names,
        "launches_by_host_phase": launches,
        "dispatch_lead_ms": ({"n": len(leads_ms), "median": statistics.median(leads_ms),
                              "min": min(leads_ms), "max": max(leads_ms)} if leads_ms else None),
        "idle_gap_s_by_host_phase": dict(sorted(gap_s.items(), key=lambda kv: -kv[1])),
        "longest_gaps": [[sec, key] for sec, key in sorted(longest, reverse=True)[:top]],
    }


def main(argv) -> int:
    path = Path(argv[0])
    if path.is_dir():
        path = trace.find_xplane(str(path))
    print(json.dumps(attribute(load_planes(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
