"""Reduction of a profiler trace to device busy time, idle gaps and ops.

The benchmark wraps its calls into the program with host annotations
(``bench.submit``, ``bench.run_all``, ``bench.cleanup``).  The traced
window runs from the first of them to the end of the last.  Inside it:

* busy: the union of the intervals of every op on each device plane's
  ``XLA Ops`` line, whatever program the op belongs to, averaged over
  the device planes;
* idle gaps: the complement of that union, each labelled with the
  innermost benchmark annotation that covers the middle of the gap;
* ops: the device time of each op name (without its HLO text), summed.

A trace with no device plane reduces to ``None``.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench."

Interval = Tuple[float, float]


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return max(found, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def annotations(profile) -> List[Tuple[str, float, float]]:
    """(name, start_ns, end_ns) of the benchmark's host annotations."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def op_name(hlo: str) -> str:
    """An op's name without its HLO text: ``%fusion.3 = f32[...] ...``
    becomes ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def device_ops(profile) -> List[List[Tuple[str, float, float]]]:
    """Per device plane, its ops as (name, start_ns, end_ns)."""
    planes = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = [(op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
               for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        if ops:
            planes.append(ops)
    return planes


def reduce(profile, top: int = 10) -> Optional[Dict]:
    """Busy and window seconds, the ops that took most device time and
    the longest idle gaps, or ``None`` where the trace holds no device
    op or no benchmark annotation."""
    marks = annotations(profile)
    planes = device_ops(profile)
    if not marks or not planes:
        return None
    lo = min(s for _, s, _ in marks)
    hi = max(e for _, _, e in marks)
    busy_ns = 0.0
    op_ns: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for ops in planes:
        spans = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_ns += sum(e - s for s, e in spans)
        for name, s, e in ops:
            for cs, ce in clip([(s, e)], lo, hi):
                op_ns[name] = op_ns.get(name, 0.0) + (ce - cs)
        edges = [lo] + [x for span in spans for x in span] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps.append((_label(marks, (gs + ge) / 2), (ge - gs) / 1e9))
    n = len(planes)
    ops_sorted = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[name, ns / n / 1e9] for name, ns in ops_sorted],
        "idle_gaps": [list(g) for g in sorted(gaps, key=lambda g: -g[1])[:top]],
    }


def _label(marks, t: float) -> str:
    inside = [(e - s, name) for name, s, e in marks if s <= t <= e]
    return min(inside)[1] if inside else "outside annotations"
