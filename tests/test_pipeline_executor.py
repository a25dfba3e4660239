"""Overlapped pipelined execution engine (prefetch → windowed compute →
write-behind): bit-identity with the stream path, identical I/O
accounting, budget soundness, crash-safety mid-pipeline, and the
bounded-memory invariant (no whole-tensor buffering)."""
import time

import numpy as np
import pytest

from repro.core.api import MergePipe
from repro.core.executor import PipelineConfig
from repro.core.operators import dare_mask, dare_mask_batch
from repro.store.iostats import IOStats, measure
from repro.store.snapshot import StagingWriter

from conftest import make_models

OPS = [
    ("avg", {}),
    ("ta", {"lam": 0.7}),
    ("ties", {"trim_frac": 0.3}),
    ("dare", {"density": 0.5, "seed": 3}),
]

SMALL_PIPE = PipelineConfig(
    window_blocks=4, prefetch_windows=2, read_threads=3, write_queue_blocks=8
)


def _tensor_hashes(mp, sid):
    with mp.snapshots.models.open_model(sid) as r:
        return {t: r.spec(t)["hash"] for t in r.tensor_names()}


# ---------------------------------------------------------------- golden
@pytest.mark.parametrize("op,theta", OPS)
def test_pipelined_bit_identical_and_same_io(populated, stats, op, theta):
    """The hard invariant: pipelined produces a bit-identical snapshot and
    moves exactly the same tagged bytes per category as stream."""
    mp, base, ids, *_ = populated
    with measure(stats) as io_s:
        mp.merge(base, ids, op, theta=theta, budget=0.5,
                 compute="stream", sid=f"s-{op}")
    with measure(stats) as io_p:
        res = mp.merge(base, ids, op, theta=theta, budget=0.5,
                       compute="pipelined", sid=f"p-{op}", pipeline=SMALL_PIPE)
    a, b = mp.load(f"s-{op}"), mp.load(f"p-{op}")
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    # file-level bit-identity: streaming blake2b content hashes match
    assert _tensor_hashes(mp, f"s-{op}") == _tensor_hashes(mp, f"p-{op}")
    for cat in ("base_read", "expert_read", "out_written"):
        assert io_s[cat] == io_p[cat], cat
    assert res.stats["pipeline"]["windows"] > 0


@pytest.mark.parametrize("op,theta", OPS)
def test_pipelined_matches_batched_within_tolerance(populated, op, theta):
    """The jitted-kernel path reassociates float math (XLA), so batched is
    equivalent at tolerance, not bitwise — same contract as before."""
    mp, base, ids, *_ = populated
    mp.merge(base, ids, op, theta=theta, budget=0.5,
             compute="batched", sid=f"bt-{op}")
    mp.merge(base, ids, op, theta=theta, budget=0.5,
             compute="pipelined", sid=f"pl-{op}", pipeline=SMALL_PIPE)
    a, b = mp.load(f"bt-{op}"), mp.load(f"pl-{op}")
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=2e-6, atol=2e-6)


def test_pipelined_jax_kernel_matches_stream_within_tolerance(populated):
    mp, base, ids, *_ = populated
    cfg = PipelineConfig(window_blocks=4, kernel="jax")
    mp.merge(base, ids, "ties", theta={"trim_frac": 0.3}, budget=0.5,
             compute="stream", sid="jk-s")
    res = mp.merge(base, ids, "ties", theta={"trim_frac": 0.3}, budget=0.5,
                   compute="pipelined", sid="jk-p", pipeline=cfg)
    import jax

    # the run stats name the implementation the kernel dispatched to
    assert res.stats["pipeline"]["backend"] == "jnp-" + jax.default_backend()
    a, b = mp.load("jk-s"), mp.load("jk-p")
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("op,theta", [("ta", {"lam": 0.9}),
                                      ("dare", {"density": 0.6, "seed": 7})])
def test_pipelined_expert_kinds(workspace, op, theta):
    """full / delta / adapter expert kinds through the pipeline are
    bit-identical to the stream path."""
    mp = workspace
    rng = np.random.default_rng(0)
    base = {"w": rng.normal(size=(96, 64)).astype(np.float32),
            "v": rng.normal(size=(4000,)).astype(np.float32)}
    delta = {k: 0.05 * rng.normal(size=v.shape).astype(np.float32)
             for k, v in base.items()}
    A = rng.normal(size=(4, 64)).astype(np.float32)
    B = rng.normal(size=(96, 4)).astype(np.float32)
    mp.register_model("base", base)
    mp.register_model("full", {k: base[k] + delta[k] for k in base})
    mp.register_model("delta", delta, kind="delta")
    mp.register_model("adapter", {"w::lora_A": A, "w::lora_B": B},
                      kind="adapter", scale=0.1)
    ids = ["full", "delta", "adapter"]
    mp.merge("base", ids, op, theta=theta, budget=None,
             compute="stream", sid="kinds-s")
    mp.merge("base", ids, op, theta=theta, budget=None,
             compute="pipelined", sid="kinds-p", pipeline=SMALL_PIPE)
    a, b = mp.load("kinds-s"), mp.load("kinds-p")
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert _tensor_hashes(mp, "kinds-s") == _tensor_hashes(mp, "kinds-p")


def _kinds_fleet(mp, dtype):
    """Base and experts of every kind in ``dtype``: ``full`` and
    ``frozen`` store weights (``frozen``'s "w" equals the base), ``delta``
    and ``zero`` store deltas (``zero``'s "v" is all zeros), ``adapter``
    LoRA factors.  "v" ends in a ragged block."""
    rng = np.random.default_rng(4)
    base = {"w": rng.normal(size=(96, 64)), "v": rng.normal(size=(3000,))}
    delta = {k: 0.05 * rng.normal(size=v.shape) for k, v in base.items()}
    cast = lambda d: {k: np.asarray(v).astype(dtype) for k, v in d.items()}
    mp.register_model("base", cast(base))
    mp.register_model("full", cast({k: base[k] + delta[k] for k in base}))
    mp.register_model("frozen", cast({"w": base["w"],
                                      "v": base["v"] - delta["v"]}))
    mp.register_model("delta", cast(delta), kind="delta")
    mp.register_model("zero", cast({"w": -delta["w"],
                                    "v": np.zeros_like(base["v"])}),
                      kind="delta")
    mp.register_model("adapter", {
        "w::lora_A": rng.normal(size=(4, 64)).astype(np.float32),
        "w::lora_B": rng.normal(size=(96, 4)).astype(np.float32)},
        kind="adapter", scale=0.1)


#: expert sets: one kind each; elided packed rows among full and delta
#: rows; every kind in one window
EXPERT_SETS = {
    "full": ["full"], "delta": ["delta"], "adapter": ["adapter"],
    "elided": ["frozen", "zero", "full"],
    "mixed": ["full", "delta", "adapter", "frozen"],
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "float32"])
@pytest.mark.parametrize("experts", sorted(EXPERT_SETS))
@pytest.mark.parametrize("op,theta", OPS)
def test_stored_operands_bit_identical_to_float32_path(tmp_path, op, theta,
                                                       experts, dtype):
    """The device path ships stored-dtype blocks and forms the deltas on
    the device; its snapshot equals, byte for byte, the float32 path's
    (``compute="batched"``: deltas widened on the host, float32 operands,
    the result rounded on the host)."""
    from repro.store.dtypes import to_np_dtype

    mp = MergePipe(str(tmp_path), block_size=4096, stats=IOStats())
    _kinds_fleet(mp, to_np_dtype(dtype))
    ids = EXPERT_SETS[experts]
    if experts == "elided":
        rep = mp.repack(ids, "base", layout_id="L")
        assert rep["elided_blocks"] > 0
    mp.merge("base", ids, op, theta=theta, budget=None, compute="batched",
             sid="f32")
    res = mp.merge("base", ids, op, theta=theta, budget=None,
                   compute="pipelined", sid="stored",
                   pipeline=PipelineConfig(window_blocks=4, kernel="jax"))
    if experts == "elided":
        assert res.manifest["layout_id"] == "L"
    assert res.stats["pipeline"]["operand_dtype"] == dtype
    a, b = mp.load("f32"), mp.load("stored")
    for k in a:
        assert b[k].dtype == a[k].dtype == to_np_dtype(dtype)
        assert b[k].tobytes() == a[k].tobytes(), k
    assert _tensor_hashes(mp, "f32") == _tensor_hashes(mp, "stored")
    mp.close()


def test_device_path_counts_stored_operands(tmp_path):
    """h2d_bytes counts the stored-dtype operands shipped, and
    device_delta_rows the expert rows whose deltas the device formed;
    the residency bound holds; the numpy kernel counts neither."""
    from repro.store.dtypes import bfloat16

    mp = MergePipe(str(tmp_path), block_size=4096, stats=IOStats())
    base, experts = make_models(shapes={"w": (96, 64), "v": (3000,)})
    mp.register_model("base", {k: v.astype(bfloat16) for k, v in base.items()})
    ids = []
    for i, e in enumerate(experts):
        mp.register_model(f"e{i}", {k: v.astype(bfloat16) for k, v in e.items()})
        ids.append(f"e{i}")
    runs = {}
    for kernel in ("jax", "numpy"):
        runs[kernel] = mp.merge(
            "base", ids, "ta", budget=None, compute="pipelined",
            pipeline=PipelineConfig(window_blocks=4, prefetch_windows=1,
                                    read_threads=2, kernel=kernel))
    res = runs["jax"]
    counters = res.stats["trace"]["counters"]
    pipe = res.stats["pipeline"]
    model_bytes = sum(v.size * 2 for v in base.values())
    # every block merged: base + 3 experts in bf16, one flag per call
    assert counters["h2d_bytes"] == (
        4 * model_bytes + counters["kernel_calls"])
    assert counters["d2h_bytes"] == model_bytes
    assert counters["device_delta_rows"] == res.stats[
        "realized_expert_blocks"] > 0
    assert pipe["operand_dtype"] == "bfloat16"
    assert pipe["peak_resident_blocks"] <= pipe["resident_bound"]
    numpy_run = runs["numpy"]
    for key in ("h2d_bytes", "device_delta_rows", "kernel_calls"):
        assert key not in numpy_run.stats["trace"]["counters"]
    assert numpy_run.stats["pipeline"]["operand_dtype"] is None
    assert numpy_run.stats["realized_expert_blocks"] == res.stats[
        "realized_expert_blocks"]
    mp.close()


def test_pipelined_int_passthrough_and_coalesce_off(workspace):
    mp = workspace
    base = {"w": np.ones((2048,), np.float32),
            "ids": np.arange(512, dtype=np.int32)}
    mp.register_model("base", base)
    mp.register_model("e0", {"w": np.full((2048,), 2.0, np.float32),
                             "ids": np.arange(512, dtype=np.int32) + 5})
    res = mp.merge("base", ["e0"], "ta", budget=None, compute="pipelined",
                   coalesce=False, pipeline=SMALL_PIPE)
    out = mp.load(res.sid)
    np.testing.assert_array_equal(out["ids"], base["ids"])
    assert not np.allclose(out["w"], base["w"])


# --------------------------------------------------------- budget + memory
def test_budget_soundness_under_pipelining(populated, stats):
    mp, base, ids, *_ = populated
    mp.ensure_analyzed(base, ids)
    budget_b = mp.resolve_budget(ids, 0.4)
    with measure(stats) as io:
        res = mp.merge(base, ids, "ties", budget=budget_b,
                       compute="pipelined", pipeline=SMALL_PIPE)
    assert io["expert_read"] <= budget_b
    assert res.stats["c_expert_run"] <= res.stats["c_expert_hat"] <= budget_b


def test_bounded_memory_no_whole_tensor_buffering(tmp_path):
    """Peak resident input blocks stay within the configured window bound
    even when single tensors span many times the window."""
    stats = IOStats()
    mp = MergePipe(str(tmp_path), block_size=1024, stats=stats)
    base, experts = make_models(shapes={"big": (512, 96), "b2": (256, 96)})
    mp.register_model("base", base)
    ids = []
    for i, e in enumerate(experts):
        mp.register_model(f"e{i}", e)
        ids.append(f"e{i}")
    cfg = PipelineConfig(window_blocks=4, prefetch_windows=2,
                         read_threads=3, write_queue_blocks=8)
    res = mp.merge("base", ids, "ta", budget=None,
                   compute="pipelined", pipeline=cfg)
    pipe = res.stats["pipeline"]
    n_blocks_big = -(-512 * 96 * 4 // 1024)  # 192 blocks in one tensor
    assert pipe["peak_resident_blocks"] <= pipe["resident_bound"]
    # decisively below whole-tensor buffering (base + K experts resident)
    assert pipe["peak_resident_blocks"] < n_blocks_big
    assert pipe["peak_write_queue_blocks"] <= pipe["write_queue_bound"]
    assert 1 <= pipe["peak_hash_lane_blocks"] <= pipe["hash_lane_bound"]
    mp.close()


@pytest.mark.parametrize("slow", ["disk", "hash"])
def test_bounded_output_under_a_slow_writer(tmp_path, monkeypatch, slow):
    """Resident output stays within ``write_queue_blocks`` queued plus
    ``HASH_LANE_BLOCKS`` on the writer's hash lanes, whether the disk
    (the writer thread) or the hashing (the lanes) is the slow side;
    the slow side fills its own bound and no more."""
    from repro.store import snapshot

    if slow == "disk":
        real = StagingWriter.write_block

        def slow_write(self, *args, **kw):
            time.sleep(0.001)
            return real(self, *args, **kw)
        monkeypatch.setattr(StagingWriter, "write_block", slow_write)
    else:
        real_digest = snapshot.block_digest

        def slow_digest(raw):
            time.sleep(0.001)
            return real_digest(raw)
        monkeypatch.setattr(snapshot, "block_digest", slow_digest)
    mp = MergePipe(str(tmp_path), block_size=1024, stats=IOStats())
    base, experts = make_models(shapes={"big": (512, 96), "b2": (256, 96)})
    mp.register_model("base", base)
    ids = []
    for i, e in enumerate(experts):
        mp.register_model(f"e{i}", e)
        ids.append(f"e{i}")
    res = mp.merge("base", ids, "ta", budget=None, compute="pipelined",
                   pipeline=PipelineConfig(window_blocks=4, read_threads=3,
                                           write_queue_blocks=8))
    pipe = res.stats["pipeline"]
    assert pipe["write_queue_bound"] == 8
    assert pipe["hash_lane_bound"] == snapshot.HASH_LANE_BLOCKS
    assert pipe["peak_write_queue_blocks"] == pipe["write_queue_bound"]
    assert pipe["peak_hash_lane_blocks"] <= pipe["hash_lane_bound"]
    if slow == "hash":
        assert pipe["peak_hash_lane_blocks"] == pipe["hash_lane_bound"]
    assert (pipe["peak_write_queue_blocks"] + pipe["peak_hash_lane_blocks"]
            <= pipe["write_queue_bound"] + pipe["hash_lane_bound"])
    # the bytes are those of an unhurried stream run
    ref = mp.merge("base", ids, "ta", budget=None, compute="stream")
    assert _tensor_hashes(mp, res.sid) == _tensor_hashes(mp, ref.sid)
    mp.close()


# ------------------------------------------------------------ crash safety
def test_crash_mid_pipeline_leaves_no_partial_snapshot(populated, monkeypatch):
    """A persistent failure on the write-behind thread exhausts the
    retry budget (transient I/O errors are retried — docs/RECOVERY.md)
    and quarantines: nothing published, staging cleaned, and the
    workspace still works."""
    mp, base, ids, *_ = populated
    before = set(mp.list_snapshots())

    real = StagingWriter.write_block
    calls = {"n": 0}

    def flaky(self, tensor_id, block_idx, block, experts=None):
        calls["n"] += 1
        if calls["n"] >= 7:
            raise IOError("injected disk failure mid-pipeline")
        return real(self, tensor_id, block_idx, block, experts=experts)

    monkeypatch.setattr(StagingWriter, "write_block", flaky)
    with pytest.raises(RuntimeError, match="injected disk failure"):
        mp.merge(base, ids, "ties", budget=0.5, compute="pipelined",
                 sid="doomed", pipeline=SMALL_PIPE)
    monkeypatch.setattr(StagingWriter, "write_block", real)

    assert set(mp.list_snapshots()) == before
    assert not mp.snapshots.is_published("doomed")
    import os
    assert os.listdir(mp.snapshots.staging_root) == []
    # the engine shut down cleanly: the same workspace keeps working
    res = mp.merge(base, ids, "ties", budget=0.5, compute="pipelined",
                   sid="after-crash", pipeline=SMALL_PIPE)
    assert res.sid == "after-crash"


def test_prefetch_error_propagates_and_aborts(populated, monkeypatch):
    """A persistent failure on the prefetch pool (expert read) surfaces
    on the caller thread — after the transient-error retries exhaust —
    and aborts with no partial state."""
    from repro.store import tensorstore

    mp, base, ids, *_ = populated
    real = tensorstore.ModelReader.read_range

    def flaky(self, tensor_id, offset, nbytes, category):
        if category == "expert":
            raise IOError("injected expert read failure")
        return real(self, tensor_id, offset, nbytes, category)

    monkeypatch.setattr(tensorstore.ModelReader, "read_range", flaky)
    with pytest.raises(RuntimeError, match="injected expert read"):
        mp.merge(base, ids, "ties", budget=0.5, compute="pipelined",
                 sid="doomed2", pipeline=SMALL_PIPE)
    monkeypatch.setattr(tensorstore.ModelReader, "read_range", real)
    assert not mp.snapshots.is_published("doomed2")
    import os
    assert os.listdir(mp.snapshots.staging_root) == []


# -------------------------------------------------------------- session v2
def test_session_default_pipelined_batch_matches_stream(tmp_path):
    """run_all's new default engine (pipelined + shared reads) is
    bit-identical to an explicit stream run of the same specs."""
    from repro.api import MergeSpec, Session

    base, experts = make_models()
    results = {}
    for mode, ws in [(None, "wsA"), ("stream", "wsB")]:
        with Session(str(tmp_path / ws), block_size=4096) as sess:
            sess.register_model("base", base)
            ids = []
            for i, e in enumerate(experts):
                sess.register_model(f"e{i}", e)
                ids.append(f"e{i}")
            specs = [
                MergeSpec.build("base", ids, op="ties",
                                theta={"trim_frac": 0.3}, budget="60%",
                                name="j-ties"),
                MergeSpec.build("base", ids[:2], op="dare",
                                theta={"density": 0.5, "seed": 5},
                                budget="60%", name="j-dare"),
            ]
            for s in specs:
                sess.submit(s, sid=s.name)
            if mode is None:
                res = sess.run_all(pipeline=SMALL_PIPE)  # default compute
                assert all(r.stats["compute"] == "pipelined" for r in res)
            else:
                res = sess.run_all(compute=mode)
            results[ws] = {r.sid: {k: v.copy() for k, v in
                                   _load(sess, r.sid).items()} for r in res}
    for sid in results["wsA"]:
        a, b = results["wsA"][sid], results["wsB"][sid]
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


def _load(sess, sid):
    return sess.load(sid)


# ------------------------------------------------------- satellite units
def test_dare_mask_batch_bit_identical_to_scalar():
    for eidxs in ([0], [2, 0, 5], []):
        got = dare_mask_batch(9, eidxs, "layer0/w", 3, 257, 0.35)
        assert got.shape == (len(eidxs), 257)
        for j, ei in enumerate(eidxs):
            np.testing.assert_array_equal(
                got[j], dare_mask(9, ei, "layer0/w", 3, 257, 0.35)
            )


def test_adapter_residency_retired_per_tensor(workspace):
    """Adapter Δ-tensors are charged once per tensor and retired when the
    tensor finishes — the residency gauge balances instead of accumulating
    one unit per (adapter, tensor) across the whole merge."""
    mp = workspace
    rng = np.random.default_rng(2)
    base = {f"t{i}/w": rng.normal(size=(64, 48)).astype(np.float32)
            for i in range(12)}
    mp.register_model("base", base)
    arrays = {}
    for name in base:
        arrays[f"{name}::lora_A"] = rng.normal(size=(4, 48)).astype(np.float32)
        arrays[f"{name}::lora_B"] = rng.normal(size=(64, 4)).astype(np.float32)
    mp.register_model("ad", arrays, kind="adapter", scale=0.1)
    cfg = PipelineConfig(window_blocks=2, prefetch_windows=1, read_threads=2,
                         write_queue_blocks=4)
    res = mp.merge("base", ["ad"], "ta", budget=None,
                   compute="pipelined", pipeline=cfg)
    pipe = res.stats["pipeline"]
    assert pipe["peak_resident_blocks"] <= pipe["resident_bound"]
    # stream equivalence for the same adapter-only merge
    res_s = mp.merge("base", ["ad"], "ta", budget=None, compute="stream")
    a, b = mp.load(res.sid), mp.load(res_s.sid)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_pipeline_config_validation(populated):
    with pytest.raises(ValueError):
        PipelineConfig(window_blocks=0).validate()
    with pytest.raises(ValueError):
        PipelineConfig(kernel="tpu").validate()
    mp, base, ids, *_ = populated
    with pytest.raises(ValueError):  # surfaced through the execute path
        mp.merge(base, ids, "ta", budget=None, compute="pipelined",
                 pipeline=PipelineConfig(prefetch_windows=0))
