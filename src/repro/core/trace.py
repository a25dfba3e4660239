"""Spans and counters of one merge job: where its time goes.

A :class:`Recorder` belongs to one job, named by the executor's sid.
``rec.span(name)`` opens a span timed by :func:`now`
(``time.perf_counter_ns``, the clock of every run-stats duration); its
parent is the innermost span open on the same thread, and closing it
(``close()`` or the end of a ``with`` block) adds its duration to that
parent's children.  :func:`count` adds to a counter of the job whose
span is innermost on the calling thread, and does nothing outside
spans; counters are kept per thread, so adding to one takes no lock.

Spans are coarse by rule: a job, a tensor, a window, a whole stage,
never a block.  Per-block work (preads, verify hashing, delta pulls,
single writes) adds nanoseconds and bytes to counters instead.

Under a running profiler every span is also a
``jax.profiler.TraceAnnotation`` of the same name on the host plane,
on the device trace's clock.  JAX is used only where the process has
already imported it: a host-only merge never imports it.

:meth:`Recorder.finish` sums the spans by name (count, seconds, self
seconds: the duration less its same-thread children) with the job's
counters; that sum is ``MergeResult.stats["trace"]``, and
:func:`recent` keeps the last ones by job for monitors that do not
hold the result.  docs/EXECUTION.md lists the span and counter names.
"""
from __future__ import annotations

import collections
import sys
import threading
import time
from typing import Dict, List, Optional

now = time.perf_counter_ns

# the thread that drives a job; at each level the children cover the parent
JOB = "mergepipe.job"
PLAN = "mergepipe.plan"
EXECUTE = "mergepipe.execute"
OPEN = "mergepipe.exec.open"
WAIT_INPUT = "mergepipe.exec.wait_input"
HOST_PREP = "mergepipe.exec.host_prep"
DEVICE = "mergepipe.exec.device"
NUMPY = "mergepipe.exec.numpy"
WAIT_WRITE = "mergepipe.exec.wait_write"
COMMIT = "mergepipe.commit"
VALIDATE = "mergepipe.commit.validate"
# background threads: one span per staged window, one per written tensor,
# and inside the latter the tensor's begin and finish (file create, the
# journal's synced records, close)
BG_STAGE = "mergepipe.bg.stage"
BG_WRITE = "mergepipe.bg.write"
BG_TENSOR_SYNC = "mergepipe.bg.tensor_sync"

#: finished jobs whose sums :func:`recent` keeps
RECENT_JOBS = 256

_tls = threading.local()
_recent: "collections.OrderedDict[str, Dict]" = collections.OrderedDict()
_recent_lock = threading.Lock()


_annotation_cls = None


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` of ``name`` where the
    process has imported JAX and a profiler runs, else None."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        profiler = sys.modules.get("jax.profiler")
        if profiler is None:
            return None
        cls = _annotation_cls = profiler.TraceAnnotation
    if not cls.is_enabled():
        return None
    ann = cls(name)
    ann.__enter__()
    return ann


def _stack() -> List["Span"]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


class Span:
    """One timed stage of a job; a context manager that closes it."""

    __slots__ = ("name", "start", "end", "parent", "child_ns", "share",
                 "recorder", "_counters", "_annotation")

    def __init__(self, recorder: "Recorder", name: str,
                 parent: Optional["Span"], counters: Dict[str, int],
                 start: Optional[int] = None):
        self.recorder = recorder
        self.name = name
        self.parent = parent
        self.child_ns = 0
        self.share = 1.0
        self.end: Optional[int] = None
        self._counters = counters
        self._annotation = _annotation(name)
        self.start = now() if start is None else start

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9

    def close(self) -> None:
        self.end = now()
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            # spans an error left open above this one end with it
            del stack[stack.index(self):]
        if self.parent is not None:
            self.parent.child_ns += self.end - self.start

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class Recorder:
    """The spans and counters of one job (``job`` is its sid)."""

    def __init__(self, job: Optional[str] = None):
        self.job = job
        self._local = threading.local()
        self._lock = threading.Lock()
        self._threads: List[tuple] = []  # guarded-by: _lock

    def _state(self) -> tuple:
        state = getattr(self._local, "state", None)
        if state is None:
            state = self._local.state = ([], {})
            with self._lock:
                self._threads.append(state)
        return state

    def span(self, name: str, start: Optional[int] = None) -> Span:
        """Open a span on the calling thread; ``start`` (a :func:`now`
        reading) dates it back, where the profiler annotation cannot
        follow."""
        spans, counters = self._state()
        stack = _stack()
        parent = stack[-1] if stack and stack[-1].recorder is self else None
        sp = Span(self, name, parent, counters, start)
        stack.append(sp)
        spans.append(sp)
        return sp

    def adopt(self, span: Span, parent: Span, share: float = 1.0) -> None:
        """Record a finished span of another recorder as a child of
        ``parent``, counting ``share`` of its time: a level's planning
        serves each of its jobs in equal parts."""
        copy = Span.__new__(Span)
        copy.recorder, copy.name, copy.parent = self, span.name, parent
        copy.start, copy.end, copy.child_ns = span.start, span.end, 0
        copy.share, copy._counters, copy._annotation = share, {}, None
        parent.child_ns += int((span.end - span.start) * share)
        self._state()[0].append(copy)

    def spans(self) -> List[Span]:
        """Every finished span, from every thread."""
        with self._lock:
            threads = list(self._threads)
        return [sp for spans, _ in threads for sp in spans
                if sp.end is not None]

    def summary(self) -> Dict:
        out: Dict[str, Dict] = {}
        for sp in self.spans():
            s = out.setdefault(sp.name, {"count": 0, "seconds": 0.0,
                                         "self_seconds": 0.0})
            dur = sp.end - sp.start
            s["count"] += 1
            s["seconds"] += dur * sp.share / 1e9
            s["self_seconds"] += (dur * sp.share - sp.child_ns) / 1e9
        counters: Dict[str, int] = {}
        with self._lock:
            threads = list(self._threads)
        for _, c in threads:
            for k, v in list(c.items()):
                counters[k] = counters.get(k, 0) + v
        return {"job": self.job, "spans": out, "counters": counters}

    def finish(self) -> Dict:
        """The summary, also kept as the job's :func:`recent` trace."""
        s = self.summary()
        if self.job is not None:
            with _recent_lock:
                _recent.pop(self.job, None)
                _recent[self.job] = s
                while len(_recent) > RECENT_JOBS:
                    _recent.popitem(last=False)
        return s


def current() -> Optional[Recorder]:
    """The recorder of the innermost span open on this thread."""
    stack = getattr(_tls, "stack", None)
    return stack[-1].recorder if stack else None


def count(key: str, n: int) -> None:
    """Add ``n`` to counter ``key`` of the innermost open span's job."""
    stack = getattr(_tls, "stack", None)
    if stack:
        c = stack[-1]._counters
        c[key] = c.get(key, 0) + n


def recent(job: str) -> Optional[Dict]:
    """The trace of one of the last :data:`RECENT_JOBS` jobs this process
    finished, by sid, or None."""
    with _recent_lock:
        return _recent.get(job)
