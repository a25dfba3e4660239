"""The plain reference against the program, at a tiny size on the CPU.

A whole run of the harness is driven here with the look for a chip left
out: the fleet is made, registered and ANALYZEd, merged in a closed-loop
window through ``Session.run_all`` and compared with the reference.  The
run is correct as the program stands, and not correct where the timed
path is broken underneath: merged in bfloat16 (the control), returning
the base unchanged, leaving half the experts out, or altering one value.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import bench  # noqa: E402
import reference  # noqa: E402

TINY = {"name": "tiny", "dtype": "bfloat16", "compute_dtype": "float32",
        "block_size": 4096,
        "base_std": 0.02, "delta_std": 0.0004,
        # full blocks, a ragged tail, a tensor smaller than one block
        "tensors": [["a.weight", [300, 40]], ["b.weight", [64, 64]],
                    ["c.bias", [100]], ["d.weight", [8192]]]}
SEED = 2 ** 33 + 5


def _limits(cell):
    return bench.load_json(HERE, "limits", cell + ".json")


TRAFFIC = {
    "ta": ({"experts": 3, "batch": [{"op": "ta", "theta": {"lam": 0.3},
                                     "budget": None}]},
           "qwen2-1.5b.ta-k8"),
    "ties-budget": ({"experts": 4, "batch": [
        {"op": "ties", "theta": {"trim_frac": 0.2, "lam": 1.0},
         "budget": "50%"}]}, "granite-3-8b.ties-k4-b50"),
    "avg-budget": ({"experts": 3, "batch": [{"op": "avg", "theta": {},
                                             "budget": "60%"}]},
                   "qwen2-1.5b.ta-k8"),
    "dare": ({"experts": 3, "batch": [
        {"op": "dare", "theta": {"density": 0.5, "lam": 1.0, "seed": 7},
         "budget": None}]}, "qwen2-1.5b.ta-k8"),
}


def _run(tmp_path, traffic, cell, seed=SEED):
    t_start = time.time()
    workdir = str(tmp_path / "ws")
    bench.make_fleet(TINY, traffic, seed, workdir)
    return bench.run_cell({"name": "tiny", "chips": 1}, TINY, traffic,
                          _limits(cell), [], seed, 0.05, False,
                          platform="cpu", expect_backend="jnp-cpu",
                          workdir=workdir, t_start=t_start)


@pytest.mark.parametrize("kind", sorted(TRAFFIC))
def test_reference_agrees_with_the_program(tmp_path, kind):
    traffic, cell = TRAFFIC[kind]
    result = _run(tmp_path, traffic, cell)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0


def _bf16_merge(op, x0s, Ds, theta, masks=None, interpret=False):
    """The merge with every operation in bfloat16 (the control)."""
    import jax.numpy as jnp

    b = jnp.bfloat16
    x0 = jnp.asarray(x0s, b)
    D = jnp.asarray(Ds, b)
    lam = jnp.asarray(theta.get("lam", 1.0), b)
    if op == "ta":
        out = x0 + lam * D.sum(axis=1)
    else:
        keep = max(1, int(round(theta["trim_frac"] * D.shape[-1])))
        a = jnp.abs(D)
        thr = jnp.sort(a, axis=-1)[..., D.shape[-1] - keep][..., None]
        Dt = jnp.where(a >= thr, D, 0)
        el = jnp.sign(Dt.sum(axis=1))[:, None, :]
        agree = (jnp.sign(Dt) == el) & (a >= thr) & (el != 0)
        num = jnp.where(agree, Dt, 0).sum(axis=1)
        cnt = agree.sum(axis=1).astype(b)
        out = x0 + lam * num / jnp.maximum(cnt, 1)
    return np.asarray(out.astype(jnp.float32))


def _unchanged(op, x0s, Ds, theta, masks=None, interpret=False):
    return np.asarray(x0s, np.float32)


def _half_the_experts(op, x0s, Ds, theta, masks=None, interpret=False):
    keep = max(1, Ds.shape[1] // 2)
    return _ORIG(op, x0s, Ds[:, :keep] * (Ds.shape[1] / keep), theta, masks)


def _one_value_altered(op, x0s, Ds, theta, masks=None, interpret=False):
    out = np.array(_ORIG(op, x0s, Ds, theta, masks))
    out.flat[0] = -out.flat[0] + 0.5
    return out


_ORIG = None


@pytest.mark.parametrize("fault", ["bf16", "unchanged", "half", "altered"])
@pytest.mark.parametrize("kind", ["ta", "ties-budget"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, kind,
                                            fault):
    global _ORIG
    from repro.kernels import ops

    _ORIG = ops.merge_blocks
    fake = {"bf16": _bf16_merge, "unchanged": _unchanged,
            "half": _half_the_experts, "altered": _one_value_altered}[fault]
    monkeypatch.setattr(ops, "merge_blocks", fake)
    traffic, cell = TRAFFIC[kind]
    result = _run(tmp_path, traffic, cell)
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("kind", ["ta", "ties-budget"])
def test_the_merge_in_bfloat16_fails_the_comparison(kind):
    import control

    traffic, cell = TRAFFIC[kind]
    limits = _limits(cell)
    (numbers,) = control.control_numbers(TINY, traffic, SEED)
    assert any(numbers[k] > limits[k]["limit"] for k in limits), numbers


def test_the_plan_keeps_the_budget():
    host = None
    traffic, _ = TRAFFIC["ties-budget"]
    entry = traffic["batch"][0]
    import jax

    import fleet

    names = [n for n, _ in fleet.inventory(TINY)]
    models = fleet.fleet_arrays(TINY, SEED, range(5))
    host = [dict(zip(names, jax.device_get(m))) for m in models]
    sel, theta = reference.plan(TINY, 4, "ties", entry["theta"], "50%", host)
    itemsize = 2
    sizes = {t: int(np.prod(s)) * itemsize for t, s in fleet.inventory(TINY)}
    naive = 4 * sum(sizes.values())
    spent = 0
    for t, s in sel.items():
        for b, e in zip(*np.nonzero(s)):
            spent += min(4096, sizes[t] - b * 4096)
    assert 0 < spent <= naive // 2
    assert 0.8 * 0.2 <= theta["trim_frac"] < 0.2


def test_a_failed_fleet_child_ends_the_run(monkeypatch):
    calls = []

    def child(cmd, **kw):
        calls.append(cmd)
        return subprocess.CompletedProcess(cmd, 1)

    monkeypatch.setattr(bench.subprocess, "run", child)
    with pytest.raises(SystemExit) as exc:
        bench.make_fleet_in_child("qwen2-1.5b.ta-k8", 2 ** 31 + 9)
    assert "failed" in str(exc.value.code)
    assert "fleet_child('qwen2-1.5b.ta-k8', 2147483657)" in calls[0][-1]


def _no_result(proc):
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_a_run_without_a_tpu_exits_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "bench.py"), "--workload",
         "qwen2-1.5b.ta-k8", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "needs 1 tpu chip" in proc.stderr


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".work*", ".cache",
                                                  "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         "qwen2-1.5b.ta-k8", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)
