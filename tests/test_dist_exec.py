"""Sharded coordinator/worker execution: bit-identity with the local
pipelined engine across operators, model kinds and stores; per-category
IOStats roll-up parity; per-worker budget bounds (docs/DISTRIBUTED.md)."""
import os

import numpy as np
import pytest

from repro.api import MergeSpec, Session
from repro.dist.lease import DistOptions
from repro.store.iostats import IOStats, measure

from conftest import make_models

BS = 4096


def _workspace(tmp_path, tag, kind="full", n_experts=3, stats=None):
    sess = Session(str(tmp_path / tag), block_size=BS, stats=stats)
    base, experts = make_models(n_experts=n_experts)
    sess.register_model("base", base)
    ids = []
    for i, e in enumerate(experts):
        if kind == "delta":
            e = {k: v - base[k] for k, v in e.items()}
        sess.register_model(f"ex{i}", e, kind=kind)
        ids.append(f"ex{i}")
    return sess, ids


def _run(sess, ids, sid, op="ties", theta=None, budget="60%", **kw):
    theta = theta if theta is not None else {"trim_frac": 0.3}
    sess.submit(MergeSpec.build("base", ids, op=op, theta=dict(theta),
                                budget=budget), sid=sid)
    return sess.run_all(**kw)[0]


def _assert_identical(sess, sid_a, sid_b):
    a, b = sess.load(sid_a), sess.load(sid_b)
    assert set(a) == set(b)
    for t in a:
        assert np.array_equal(a[t], b[t]), t


# ------------------------------------------------ operators x model kinds
@pytest.mark.parametrize("kind", ["full", "delta"])
@pytest.mark.parametrize("op,theta", [
    ("avg", {}),
    ("ta", {"lam": 0.5}),
    ("ties", {"trim_frac": 0.3}),
    ("dare", {"density": 0.5, "seed": 7}),
])
def test_sharded_bit_identical_flat(tmp_path, op, theta, kind):
    sess, ids = _workspace(tmp_path, "ws", kind=kind)
    # anchor to the paper-faithful synchronous engine, not pipelined
    _run(sess, ids, "local", op=op, theta=theta, compute="stream")
    _run(sess, ids, "shard", op=op, theta=theta, n_workers=2)
    _assert_identical(sess, "local", "shard")
    sess.close()


# --------------------------------------------------- stores x worker counts
@pytest.mark.parametrize("op,theta", [
    ("avg", {}),
    ("ta", {"lam": 0.5}),
    ("ties", {"trim_frac": 0.3}),
    ("dare", {"density": 0.5, "seed": 7}),
])
@pytest.mark.parametrize("n_workers", [2, 4])
def test_sharded_bit_identical_packed(tmp_path, n_workers, op, theta):
    sess, ids = _workspace(tmp_path, "ws")
    sess.repack(ids, "base")
    r_local = _run(sess, ids, "local", op=op, theta=theta)
    r_shard = _run(sess, ids, "shard", op=op, theta=theta,
                   n_workers=n_workers)
    # both executions planned from the packed layout, not flat reads
    assert r_local.manifest["layout_id"] == r_shard.manifest["layout_id"]
    assert r_shard.manifest["layout_id"] is not None
    _assert_identical(sess, "local", "shard")
    assert r_shard.stats["n_workers"] == n_workers
    sess.close()


@pytest.mark.parametrize("op,theta", [
    ("avg", {}),
    ("ta", {"lam": 0.5}),
    ("ties", {"trim_frac": 0.3}),
    ("dare", {"density": 0.5, "seed": 7}),
])
@pytest.mark.parametrize("n_workers", [2, 4])
def test_sharded_bit_identical_tiered_remote(tmp_path, n_workers, op, theta):
    sess, ids = _workspace(tmp_path, "ws")
    bucket = str(tmp_path / "bucket")
    for mid in ids:
        sess.publish_model_remote(mid, bucket,
                                  profile={"latency_s": 1e-4, "mbps": 500})
    r_local = _run(sess, ids, "local", op=op, theta=theta)
    r_shard = _run(sess, ids, "shard", op=op, theta=theta,
                   n_workers=n_workers)
    _assert_identical(sess, "local", "shard")
    # remote bytes flowed through the tier hierarchy on both paths
    assert r_local.stats["c_expert_run"] == r_shard.stats["c_expert_run"]
    sess.close()


# ----------------------------------------------------------- IOStats parity
def test_sharded_iostats_category_parity(tmp_path):
    """Rolled-up per-category worker stats match local execution exactly
    on the parameter-byte categories; coordination overhead is confined
    to its documented categories (region+splice in 'other', shard
    journals in 'journal', lease/result docs in 'meta')."""
    s1 = IOStats()
    sess_a, ids_a = _workspace(tmp_path, "wsA", stats=s1)
    with measure(s1) as io_local:
        _run(sess_a, ids_a, "out")
    sess_a.close()

    s2 = IOStats()
    sess_b, ids_b = _workspace(tmp_path, "wsB", stats=s2)
    with measure(s2) as io_shard:
        r = _run(sess_b, ids_b, "out", n_workers=2)

    # parameter-byte categories are exactly equal: same realized read
    # set, and output bytes are billed once at the coordinator splice
    for cat in ("base_read", "expert_read", "out_written"):
        assert io_local[cat] == io_shard[cat], cat
    # coordination overhead exists but never leaks into parameter
    # categories: regions are written+spliced through 'other' (inside
    # the historical "meta" total alongside lease/result docs)
    assert io_shard["meta"] > io_local["meta"]
    assert io_shard["waste_read"] > io_local["waste_read"]

    # the per-shard roll-up partitions the workers' expert bytes
    rollup = s2.shard_rollup()
    assert set(rollup) == {"0", "1"}
    shard_expert = sum(
        sh["read"].get("expert", 0) + sh["read"].get("expert_packed", 0)
        + sh["read"].get("expert_remote", 0) + sh["read"].get("expert_disk", 0)
        for sh in rollup.values()
    )
    assert shard_expert == r.stats["c_expert_run"] == io_shard["expert_read"]
    sess_b.close()


# ------------------------------------------------------- per-worker budgets
@pytest.mark.parametrize("n_workers", [2, 4])
def test_per_worker_expert_bytes_bounded(tmp_path, n_workers):
    """Every worker's realized expert bytes stay under
    ceil(C_hat_physical / n_workers) plus one output block of imbalance
    slack.  The indivisible unit a prefix cut cannot split is one output
    block *with all of its expert reads* — up to K expert blocks — so
    the slack is K * block_size, one block per expert."""
    sess, ids = _workspace(tmp_path, "ws")
    r = _run(sess, ids, "shard", budget="100%", n_workers=n_workers)
    total = r.stats["partition"]["total_expert_bytes"]
    assert total == r.stats["c_expert_run"]  # flat store: no re-reads
    cap = -(-total // n_workers) + len(ids) * BS
    for sh in r.stats["shards"]:
        assert sh["realized_expert_bytes"] <= cap, sh
    # shard budgets cover exactly what each shard realizes
    by_shard = {s["shard"]: s for s in r.stats["partition"]["shards"]}
    for sh in r.stats["shards"]:
        assert sh["realized_expert_bytes"] <= by_shard[sh["shard"]]["budget"]
    sess.close()


def test_sharded_run_stats_shape(tmp_path):
    """The run stats document the distributed execution: partition,
    per-shard attempts/bytes, transport and kernel."""
    sess, ids = _workspace(tmp_path, "ws")
    r = _run(sess, ids, "shard",
             dist=DistOptions(n_workers=2, transport="process"))
    st = r.stats
    assert st["execution"] == "sharded" and st["n_workers"] == 2
    assert st["transport"] == "process" and st["kernel"] == "numpy"
    assert st["reissued"] == 0
    assert len(st["shards"]) == len(st["partition"]["shards"]) == 2
    assert all(s["attempts"] == 1 for s in st["shards"])
    assert r.manifest["execution"] == "sharded"
    # zero staging residue after a clean commit
    shards = os.path.join(sess.snapshots.staging_root, "shards")
    assert not os.path.isdir(shards) or not os.listdir(shards)
    sess.close()


def test_sharded_single_worker_degenerates_to_local(tmp_path):
    """n_workers=1 is a valid degenerate deployment: one lease covering
    the whole plan, still bit-identical."""
    sess, ids = _workspace(tmp_path, "ws")
    _run(sess, ids, "local")
    r = _run(sess, ids, "shard", n_workers=1)
    _assert_identical(sess, "local", "shard")
    assert len(r.stats["shards"]) == 1
    sess.close()


# ------------------------------------------------- device kernels on workers
def _bf16_workspace(tmp_path):
    """Experts stored in bf16, as fine-tune fleets ship: a mesh row holds
    block_size / 2 elements, not block_size / 4."""
    from repro.store.dtypes import bfloat16

    sess = Session(str(tmp_path / "ws"), block_size=BS)
    base, experts = make_models(n_experts=3)
    sess.register_model("base", {k: v.astype(bfloat16) for k, v in base.items()})
    ids = []
    for i, e in enumerate(experts):
        sess.register_model(f"ex{i}", {k: v.astype(bfloat16) for k, v in e.items()})
        ids.append(f"ex{i}")
    return sess, ids


@pytest.mark.parametrize("op,theta", [
    ("ta", {"lam": 0.5}),
    ("ties", {"trim_frac": 0.3}),
])
def test_mesh_kernel_bf16_matches_stream(tmp_path, op, theta):
    sess, ids = _bf16_workspace(tmp_path)
    _run(sess, ids, "ref", op=op, theta=theta, compute="stream")
    r = _run(sess, ids, "mesh", op=op, theta=theta,
             dist=DistOptions(n_workers=1, kernel="mesh", transport="inline"))
    (shard,) = r.stats["shards"]
    assert shard["attempts"] == 1
    assert shard["pipeline"]["mesh_devices"] == len(__import__("jax").devices())
    assert shard["pipeline"]["backend"].startswith("xla-")
    ref, got = sess.load("ref"), sess.load("mesh")
    # float32 math in another order: results agree to one bf16 step
    w = BS // 2
    for t in ref:
        a = np.asarray(ref[t], np.float32)
        b = np.asarray(got[t], np.float32)
        assert got[t].dtype == ref[t].dtype
        if op == "ties" and a.size % w:
            # the mesh trims TIES over the zero-padded tail block (see
            # test_distributed.py::test_ties_tail_block_deviation_bounded)
            continue
        np.testing.assert_allclose(b, a, rtol=2 ** -7, atol=2 ** -16)
    sess.close()


@pytest.mark.parametrize("kernel", ["jax", "mesh"])
def test_device_kernel_workers_never_share_a_chip(tmp_path, monkeypatch, kernel):
    """Two device-kernel worker processes would contend for one chip: the
    configuration is refused before any lease is issued."""
    from repro.dist import transport

    def no_launch(*a, **kw):
        raise AssertionError("a lease was issued")

    monkeypatch.setattr(transport.LocalProcessTransport, "launch", no_launch)
    sess, ids = _workspace(tmp_path, "ws")
    with pytest.raises(ValueError, match="n_workers=1"):
        _run(sess, ids, "shard",
             dist=DistOptions(n_workers=2, kernel=kernel, transport="process"))
    sess.close()


def test_coordinator_holding_the_chip_spawns_no_device_worker(
        tmp_path, monkeypatch):
    from repro.dist import coordinator, transport

    def no_launch(*a, **kw):
        raise AssertionError("a lease was issued")

    monkeypatch.setattr(transport.LocalProcessTransport, "launch", no_launch)
    monkeypatch.setattr(coordinator, "holds_accelerator", lambda: True)
    sess, ids = _workspace(tmp_path, "ws")
    with pytest.raises(RuntimeError, match="holds the accelerator"):
        _run(sess, ids, "shard",
             dist=DistOptions(n_workers=1, kernel="jax", transport="process"))
    sess.close()


def test_jax_kernel_worker_process(tmp_path):
    """One device-kernel worker process, spawned by a coordinator that
    holds no accelerator, merges within float tolerance of the stream
    engine and reports the backend it dispatched to."""
    from repro.dist.transport import holds_accelerator

    assert not holds_accelerator()  # CPU-only test host
    sess, ids = _workspace(tmp_path, "ws")
    _run(sess, ids, "ref", compute="stream")
    r = _run(sess, ids, "shard",
             dist=DistOptions(n_workers=1, kernel="jax", transport="process"))
    (shard,) = r.stats["shards"]
    assert shard["attempts"] == 1
    assert shard["pipeline"]["backend"].startswith("jnp-")
    ref, got = sess.load("ref"), sess.load("shard")
    for t in ref:
        np.testing.assert_allclose(got[t], ref[t], rtol=2e-6, atol=2e-6)
    sess.close()
