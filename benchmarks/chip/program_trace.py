"""The program's own spans: per job, and on the profiler's host plane.

The program sums each merge job's spans and counters as its run stats'
``trace`` (``repro.core.trace``; the names are in docs/EXECUTION.md).
A job record carries that sum where the harness copies it into the
record; otherwise it is found by the job's sid among the program's last
finished jobs (``repro.core.trace.recent``).  A program without spans
gives none, and the readers of ``metrics/`` then return None.

Under the profiler the same spans are annotations on the host plane.
:func:`reduce` is ``devtrace.reduce`` with each idle gap named by the
innermost program span that covers its middle, leaving out the
background threads' ``mergepipe.bg.`` spans, which overlap the driving
thread's; a gap that no program span covers keeps its benchmark
annotation.  Busy time, the window and the ops are ``devtrace``'s own.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import devtrace

PROGRAM_PREFIX = "mergepipe."
BACKGROUND_PREFIX = "mergepipe.bg."


# ----------------------------------------------------------- run stats
def _recent(sid: Optional[str]) -> Optional[Dict]:
    if sid is None:
        return None
    try:
        from repro.core import trace
    except ImportError:  # a program without spans
        return None
    return trace.recent(sid)


def job_traces(run) -> Optional[List[Dict]]:
    """The trace of every job of the window, or None unless every job
    has one."""
    jobs = [j for b in run["batches"] for j in b["jobs"]]
    traces = [j.get("trace") or _recent(j.get("sid")) for j in jobs]
    if not traces or any(t is None for t in traces):
        return None
    return traces


def span_seconds(run, name: str) -> Optional[float]:
    """Seconds of span ``name`` per job, averaged over the window's jobs
    (0 for a job that never entered it)."""
    traces = job_traces(run)
    if traces is None:
        return None
    return sum(t["spans"].get(name, {}).get("seconds", 0.0)
               for t in traces) / len(traces)


def counter_mean(run, key: str) -> Optional[float]:
    """Counter ``key`` per job, averaged over the window's jobs."""
    traces = job_traces(run)
    if traces is None:
        return None
    return sum(t["counters"].get(key, 0) for t in traces) / len(traces)


# -------------------------------------------------------- device trace
def program_spans(profile):
    """(name, start_ns, end_ns) of the program's spans on the host planes,
    without the background threads' ones."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith(devtrace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if (ev.name.startswith(PROGRAM_PREFIX)
                        and not ev.name.startswith(BACKGROUND_PREFIX)):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def label(marks, spans, t: float) -> str:
    """The innermost program span over ``t``, else the benchmark's."""
    inside = [(e - s, name) for name, s, e in spans if s <= t <= e]
    return min(inside)[1] if inside else devtrace._label(marks, t)


def reduce(profile, top: int = 10) -> Optional[Dict]:
    out = devtrace.reduce(profile, top)
    if out is None:
        return None
    marks = devtrace.annotations(profile)
    spans = program_spans(profile)
    lo = min(s for _, s, _ in marks)
    hi = max(e for _, _, e in marks)
    gaps = []
    for ops in devtrace.device_ops(profile):
        busy = devtrace.union(devtrace.clip([(s, e) for _, s, e in ops],
                                            lo, hi))
        edges = [lo] + [x for span in busy for x in span] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps.append((label(marks, spans, (gs + ge) / 2),
                             (ge - gs) / 1e9))
    out["idle_gaps"] = [list(g) for g in
                        sorted(gaps, key=lambda g: -g[1])[:top]]
    return out
