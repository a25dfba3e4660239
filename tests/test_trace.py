"""Spans and counters of a merge job (repro.core.trace): the names a
pipelined merge records, their nesting and coverage, the transfer
counters, no span per block, and the spans under the profiler."""
import glob
import os
import threading

import numpy as np
import pytest

from repro.api import MergeSpec, Session
from repro.core import trace
from repro.core.executor import PipelineConfig

FIRST_GROUP = {trace.JOB, trace.PLAN, trace.EXECUTE, trace.OPEN,
               trace.WAIT_INPUT, trace.WAIT_WRITE, trace.COMMIT,
               trace.VALIDATE}
KERNEL_SPANS = {"numpy": {trace.NUMPY},
                "jax": {trace.HOST_PREP, trace.DEVICE}}
# the children of mergepipe.execute on the driving thread
EXEC_CHILDREN = {trace.OPEN, trace.WAIT_INPUT, trace.HOST_PREP, trace.DEVICE,
                 trace.NUMPY, trace.WAIT_WRITE, trace.COMMIT}


def _fleet(path, n_tensors=4, rows=256, cols=256, k=3, block_size=16384):
    rng = np.random.default_rng(7)
    base = {f"l{i}/w": rng.normal(size=(rows, cols)).astype(np.float32)
            for i in range(n_tensors)}
    sess = Session(str(path), block_size=block_size)
    sess.register_model("base", base)
    ids = []
    for e in range(k):
        sess.register_model(f"e{e}", {
            n: v + 0.02 * rng.normal(size=v.shape).astype(np.float32)
            for n, v in base.items()})
        ids.append(f"e{e}")
    # analyzed ahead, as a deployment's fleet is: a job's window then
    # holds planning and execution only
    sess.ensure_analyzed("base", ids)
    return sess, ids


@pytest.fixture
def recorders(monkeypatch):
    """Every recorder whose job finished, in order."""
    seen = []
    finish = trace.Recorder.finish

    def keep(self):
        seen.append(self)
        return finish(self)

    monkeypatch.setattr(trace.Recorder, "finish", keep)
    return seen


def _merge(sess, ids, kernel, op="ta", theta=None, window_blocks=4):
    sess.submit(MergeSpec.build("base", ids, op=op,
                                theta=theta or {"lam": 0.5}))
    (res,) = sess.run_all(pipeline=PipelineConfig(
        kernel=kernel, window_blocks=window_blocks))
    return res


@pytest.mark.parametrize("kernel", ["numpy", "jax"])
def test_pipelined_merge_records_every_stage(tmp_path, recorders, kernel):
    sess, ids = _fleet(tmp_path)
    try:
        res = _merge(sess, ids, kernel)
    finally:
        sess.close()
    tr = res.stats["trace"]
    assert tr["job"] == res.sid
    names = set(tr["spans"])
    assert FIRST_GROUP | KERNEL_SPANS[kernel] <= names
    other = "jax" if kernel == "numpy" else "numpy"
    assert not KERNEL_SPANS[other] & names
    assert {trace.BG_STAGE, trace.BG_WRITE} <= names
    # run stats seconds is the executor span's duration
    assert res.stats["seconds"] == pytest.approx(
        tr["spans"][trace.EXECUTE]["seconds"])
    assert res.stats["plan"]["plan_seconds"] > 0
    for key in ("read_bytes.base", "read_bytes.expert", "verify_bytes",
                "pull_ns", "write_ns", "hash_ns", "hash_wait_ns",
                "fsync_ns"):
        assert tr["counters"][key] > 0, key
    assert ("kernel_calls" in tr["counters"]) == (kernel == "jax")

    # nesting: every child lies inside its parent, on the parent's thread
    spans = recorders[-1].spans()
    parents = {sp.name: (sp.parent.name if sp.parent else None)
               for sp in spans}
    assert parents[trace.JOB] is None
    assert parents[trace.PLAN] == parents[trace.EXECUTE] == trace.JOB
    for name in EXEC_CHILDREN & names:
        assert parents[name] == trace.EXECUTE, name
    assert parents[trace.VALIDATE] == trace.COMMIT
    assert parents[trace.BG_STAGE] is None and parents[trace.BG_WRITE] is None
    for sp in spans:
        if sp.parent is not None and sp.name != trace.PLAN:
            assert sp.parent.start <= sp.start <= sp.end <= sp.parent.end


def test_first_group_covers_the_executor_and_the_job(tmp_path):
    # 32 MiB of operands in 64 KiB blocks: what the spans leave out is a
    # few tens of microseconds per window, under 3% at this size
    sess, ids = _fleet(tmp_path, n_tensors=8, rows=1024, cols=1024, k=4,
                       block_size=65536)
    try:
        res = _merge(sess, ids, "numpy", window_blocks=8)
    finally:
        sess.close()
    spans = res.stats["trace"]["spans"]
    execute = spans[trace.EXECUTE]["seconds"]
    children = sum(spans[n]["seconds"] for n in EXEC_CHILDREN if n in spans)
    assert children <= execute
    assert children >= 0.95 * execute
    job = spans[trace.JOB]["seconds"]
    assert spans[trace.PLAN]["seconds"] + execute >= 0.95 * job
    assert spans[trace.JOB]["self_seconds"] == pytest.approx(
        job - spans[trace.PLAN]["seconds"] - execute, abs=1e-6)


@pytest.mark.parametrize("op,theta", [("ta", {"lam": 0.5}),
                                      ("dare", {"density": 0.5, "seed": 3})])
def test_h2d_bytes_are_the_operands_handed_to_the_kernel(
        tmp_path, monkeypatch, op, theta):
    from repro.kernels import ops

    moved = {"in": 0, "out": 0, "calls": 0}
    merge_blocks = ops.merge_blocks

    def counting(op_, x0s, Ds, theta_, masks=None):
        # the operands as stored, their full-weights flags, DARE's masks
        moved["in"] += x0s.nbytes + Ds.nbytes + np.asarray(
            theta_["_subtract"]).nbytes + (
            masks.nbytes if masks is not None else 0)
        out = merge_blocks(op_, x0s, Ds, theta_, masks=masks)
        moved["out"] += out.nbytes
        moved["calls"] += 1
        return out

    monkeypatch.setattr(ops, "merge_blocks", counting)
    sess, ids = _fleet(tmp_path)
    try:
        res = _merge(sess, ids, "jax", op=op, theta=theta)
    finally:
        sess.close()
    counters = res.stats["trace"]["counters"]
    assert moved["calls"] > 0
    assert counters["h2d_bytes"] == moved["in"]
    assert counters["d2h_bytes"] == moved["out"]
    assert counters["kernel_calls"] == moved["calls"]


@pytest.mark.parametrize("window_blocks", [2, 8])
def test_no_span_per_block(tmp_path, window_blocks):
    # 4 tensors of 16 blocks each, all blocks merged in one width
    n_tensors, blocks = 4, 16
    sess, ids = _fleet(tmp_path, n_tensors=n_tensors, rows=256, cols=256,
                       block_size=16384)
    try:
        res = _merge(sess, ids, "jax", window_blocks=window_blocks)
    finally:
        sess.close()
    windows = res.stats["pipeline"]["windows"]
    assert windows == n_tensors * blocks // window_blocks
    spans = res.stats["trace"]["spans"]
    counts = {n: s["count"] for n, s in spans.items()}
    assert counts[trace.BG_STAGE] == windows
    assert counts[trace.BG_WRITE] == n_tensors
    # one item per window and per tensor, and the end marker
    assert counts[trace.WAIT_INPUT] == windows + n_tensors + 1
    assert counts[trace.DEVICE] == windows
    assert counts[trace.HOST_PREP] == 2 * windows
    # one per window and per tensor command, and the last flush
    assert counts[trace.WAIT_WRITE] == windows + 2 * n_tensors + 1
    for name in (trace.JOB, trace.PLAN, trace.EXECUTE, trace.OPEN,
                 trace.COMMIT, trace.VALIDATE):
        assert counts[name] == 1, name


def test_tensor_files_opened_and_tensor_syncs(tmp_path, recorders):
    n_tensors, k = 4, 3
    sess, ids = _fleet(tmp_path, n_tensors=n_tensors, k=k)
    try:
        res = _merge(sess, ids, "jax")
    finally:
        sess.close()
    tr = res.stats["trace"]
    # each tensor file of the base and of every expert, opened once
    assert tr["counters"]["fd_opens"] == n_tensors * (k + 1)
    # on the writer thread, each tensor's begin and its finish
    syncs = [sp for sp in recorders[-1].spans()
             if sp.name == trace.BG_TENSOR_SYNC]
    assert len(syncs) == tr["spans"][trace.BG_TENSOR_SYNC]["count"] == (
        2 * n_tensors)
    for sp in syncs:
        assert sp.parent.name == trace.BG_WRITE
        assert sp.parent.start <= sp.start <= sp.end <= sp.parent.end


def test_self_time_counters_and_recent():
    rec = trace.Recorder("job-a")
    with rec.span(trace.EXECUTE) as outer:
        with rec.span(trace.WAIT_INPUT) as inner:
            trace.count("read_bytes.base", 10)
        trace.count("read_bytes.base", 5)

        def worker():
            with rec.span(trace.BG_STAGE):
                trace.count("read_bytes.base", 7)

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    trace.count("read_bytes.base", 1000)  # outside every span: dropped
    s = rec.finish()
    assert s["counters"] == {"read_bytes.base": 22}
    ex = s["spans"][trace.EXECUTE]
    assert ex["self_seconds"] == pytest.approx(outer.seconds - inner.seconds)
    # a span on another thread is no child of the one that was open here
    assert s["spans"][trace.BG_STAGE]["count"] == 1
    assert trace.recent("job-a") is s
    assert trace.recent("no-such-job") is None
    assert trace.current() is None


def test_counters_from_many_threads_lose_no_update():
    import sys

    rec = trace.Recorder("job-threads")
    n_threads, n_adds = 16, 2000
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            with rec.span(trace.BG_STAGE):
                for _ in range(n_adds):
                    trace.count("pull_ns", 1)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    s = rec.summary()
    assert s["counters"]["pull_ns"] == n_threads * n_adds
    assert s["spans"][trace.BG_STAGE]["count"] == n_threads


def test_an_error_closes_the_spans_it_left_open():
    rec = trace.Recorder()
    with pytest.raises(RuntimeError):
        with rec.span(trace.EXECUTE):
            rec.span(trace.WAIT_INPUT)  # never closed
            raise RuntimeError("boom")
    assert trace.current() is None
    assert [sp.name for sp in rec.spans()] == [trace.EXECUTE]


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    sess, ids = _fleet(tmp_path / "ws", n_tensors=2, rows=128, cols=128)
    tdir = str(tmp_path / "trace")
    try:
        jax.profiler.start_trace(tdir)
        try:
            _merge(sess, ids, "jax")
        finally:
            jax.profiler.stop_trace()
    finally:
        sess.close()
    (path,) = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            names.update(ev.name for ev in line.events
                         if ev.name.startswith("mergepipe."))
    assert {trace.JOB, trace.PLAN, trace.EXECUTE, trace.DEVICE,
            trace.BG_STAGE, trace.BG_WRITE} <= names
