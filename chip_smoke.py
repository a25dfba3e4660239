#!/usr/bin/env python3
"""Chip smoke test: MergePipe's merge path, end to end, on a TPU.

A seeded fleet — a base and K=4 fine-tunes of qwen2-1.5b at its published
widths (d_model 1536, d_ff 8960, 12 query / 2 KV heads of 128, QKV bias,
the full 151,936-row embedding), stored in bf16, with only the depth cut —
is registered through ``Session`` and merged through ``Session.run_all``.
Every merged tensor is checked against the same spec run through
``compute="stream"``, the numpy reference engine.

    python chip_smoke.py               # one chip: ties, dare, avg and ta
                                       # on the pipelined engine's Pallas
                                       # kernels (PipelineConfig(kernel="jax"))
    python chip_smoke.py --four-chips  # only the mesh path: ta and ties
                                       # through DistOptions(kernel="mesh"),
                                       # one worker process driving every chip

Earlier lines report set-up time, each merge's wall time, compilations,
I/O bytes by category, the largest difference from the reference and the
kernel backend.  The last line of standard output is one JSON object
naming the device.  Any failure, or a run where JAX finds no TPU, exits
non-zero without that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.api import MergeSpec, Session  # noqa: E402
from repro.core.executor import PipelineConfig  # noqa: E402
from repro.dist.lease import DistOptions  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.store.dtypes import bfloat16  # noqa: E402
from repro.store.iostats import CATEGORIES  # noqa: E402

ARCH = "qwen2-1.5b"
#: the platform every phase must run on
PLATFORM = "tpu"
N_EXPERTS = 4
#: decoder layers kept of the architecture's 28: the depth cut that fits
#: a five-model fleet and its merges in the run's time limit
N_LAYERS = 4
BLOCK_SIZE = 128 * 1024
WORKSPACE = os.path.join(ROOT, ".smoke_ws")
#: weight and fine-tune delta scales of the generated fleet
BASE_STD = 0.02
DELTA_STD = 0.02 * BASE_STD

#: (op, theta, budget) merged on one chip, in this order
ONE_CHIP_SPECS = [
    ("ties", {"trim_frac": 0.3}, "50%"),
    ("dare", {"density": 0.5}, None),
    ("avg", {}, None),
    ("ta", {}, None),
]
FOUR_CHIP_SPECS = [
    ("ta", {}, None),
    ("ties", {"trim_frac": 0.3}, "50%"),
]

# Tolerance against the stream engine, per element:
#     |got - ref| <= RTOL * |ref| + ATOL
# Both engines compute in float32 and round once to the stored bf16.  The
# kernels sum the K deltas in another order (and the TPU divides in its
# own way), so float32 results may differ by a few float32 ulps, about
# 1e-8 at this fleet's magnitudes.  Rounding can carry that to the
# neighbouring bf16 value, one bf16 step, which is at most 2**-7 of |ref|;
# ATOL covers float32 rounding where x0 and the deltas cancel to near 0.
# Anything larger is a wrong merge.
RTOL = 2.0 ** -7
ATOL = 2.0 ** -24


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    pass


# ---------------------------------------------------------------- device
def require_tpu():
    """The first JAX device, which must be a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != PLATFORM:
        raise SmokeFailure(
            "JAX found no TPU (platform %r): this smoke test runs on the "
            "chip only" % dev.platform)
    return dev


def device_line(dev, count: int) -> str:
    return json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}})


class CompileCounter:
    """Counts XLA/Mosaic backend compiles (and persistent-cache hits)
    in this process through ``jax.monitoring``."""

    def __init__(self) -> None:
        import jax

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self) -> str:
        return ("compiles=%d compile_seconds=%.3f persistent_cache_hits=%d"
                % (self.n, self.seconds, self.cache_hits))


# ----------------------------------------------------------------- fleet
def fleet_shapes(n_layers: int):
    """{tensor name: shape} of the architecture's parameter tree, depth
    cut to ``n_layers``.  Shapes only: nothing is allocated and no JAX
    backend is started."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=n_layers)
    tree = jax.eval_shape(build_model(cfg).init,
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    shapes = {
        "/".join(str(getattr(k, "key", k)) for k in path): tuple(leaf.shape)
        for path, leaf in leaves
    }
    log("fleet: %s, %d tensors, n_layers %d of %d (depth cut), "
        "d_model %d, d_ff %d, heads %d/%d x %d, vocab %d, qkv_bias %s"
        % (ARCH, len(shapes), n_layers, full.n_layers, cfg.d_model,
           cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
           cfg.vocab_size, cfg.qkv_bias))
    return shapes


def _draw(seed: int, model: int, tensor: int, shape, std: float):
    rng = np.random.default_rng([seed, model, tensor])
    return rng.standard_normal(shape, dtype=np.float32) * np.float32(std)


def register_fleet(sess: Session, shapes, seed: int):
    """Register a bf16 base and N_EXPERTS fine-tunes (base + small seeded
    noise), then ANALYZE them.  Returns the expert ids."""
    names = sorted(shapes)
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        base32 = dict(zip(names, pool.map(
            lambda i: _draw(seed, 0, i, shapes[names[i]], BASE_STD),
            range(len(names)))))
        sess.register_model(
            "base", {k: v.astype(bfloat16) for k, v in base32.items()})
        ids = []
        for e in range(N_EXPERTS):
            deltas = pool.map(
                lambda i: _draw(seed, e + 1, i, shapes[names[i]], DELTA_STD),
                range(len(names)))
            sess.register_model("ft%d" % e, {
                k: (base32[k] + d).astype(bfloat16)
                for k, d in zip(names, deltas)})
            ids.append("ft%d" % e)
    del base32
    sess.analyze("base")
    for e in ids:
        sess.analyze(e, base_id="base")
    nbytes = sum(int(np.prod(s)) for s in shapes.values()) * 2
    log("fleet: %d models x %.3f GB bf16 = %.3f GB"
        % (len(ids) + 1, nbytes / 1e9, (len(ids) + 1) * nbytes / 1e9))
    return ids


def _spec(ids, op, theta, budget) -> MergeSpec:
    return MergeSpec.build("base", ids, op=op, theta=dict(theta),
                           budget=budget)


# ---------------------------------------------------------------- checks
def compare(sess: Session, got_sid: str, ref_sid: str, tail_w: int = 0):
    """Max |got - ref| over every tensor; raises on any element outside
    the stated tolerance.  ``tail_w`` > 0 exempts the zero-padded tail
    block of tensors whose size is not a multiple of ``tail_w`` (the mesh
    path trims TIES over the padded width there)."""
    got, ref = sess.load(got_sid), sess.load(ref_sid)
    if set(got) != set(ref):
        raise SmokeFailure("%s and %s hold different tensors" % (got_sid, ref_sid))
    max_abs, n_diff, n_tail = 0.0, 0, 0
    for t in sorted(ref):
        if got[t].dtype != ref[t].dtype or got[t].shape != ref[t].shape:
            raise SmokeFailure("%s: %s %s vs reference %s %s" % (
                t, got[t].dtype, got[t].shape, ref[t].dtype, ref[t].shape))
        a = np.asarray(ref[t], np.float32).reshape(-1)
        b = np.asarray(got[t], np.float32).reshape(-1)
        err = np.abs(b - a)
        max_abs = max(max_abs, float(err.max()))
        n_diff += int(np.count_nonzero(err))
        bad = err > RTOL * np.abs(a) + ATOL
        if tail_w and a.size % tail_w:
            tail = np.arange(a.size) >= (a.size // tail_w) * tail_w
            n_tail += int(np.count_nonzero(bad & tail))
            bad &= ~tail
        if bad.any():
            i = int(np.argmax(bad))
            raise SmokeFailure(
                "%s vs %s: %d elements of %s outside tolerance; first at "
                "%d: %r vs reference %r" % (got_sid, ref_sid,
                                            int(bad.sum()), t, i, b[i], a[i]))
    return max_abs, n_diff, n_tail


def drop(sess: Session, *sids: str) -> None:
    for sid in sids:
        sess.snapshots.models.delete_model(sid, force=True)


def io_report(sess: Session) -> str:
    st = sess.stats
    read = {c: st.bytes_read(c) for c in CATEGORIES if st.bytes_read(c)}
    written = {c: st.bytes_written(c) for c in CATEGORIES
               if st.bytes_written(c)}
    return "io bytes: read %s written %s" % (
        json.dumps(read, sort_keys=True), json.dumps(written, sort_keys=True))


# ---------------------------------------------------------------- phases
def one_chip(sess: Session, ids) -> None:
    """The four operators through the pipelined engine's device kernels,
    each checked against the stream engine."""
    counter = CompileCounter()
    sess.stats.reset()
    for op, theta, budget in ONE_CHIP_SPECS:
        sess.submit(_spec(ids, op, theta, budget), sid="chip-" + op)
    t0 = time.perf_counter()
    results = sess.run_all(pipeline=PipelineConfig(kernel="jax"))
    wall = time.perf_counter() - t0
    for (op, _theta, _budget), res in zip(ONE_CHIP_SPECS, results):
        pipe = res.stats["pipeline"]
        log("merge %s: seconds %.3f, windows %d, expert bytes %d of "
            "planned %d, backend %s" % (
                op, res.stats["seconds"], pipe["windows"],
                res.stats["c_expert_run"], res.stats["c_expert_hat"],
                pipe["backend"]))
        if pipe["backend"] != "pallas-" + PLATFORM:
            raise SmokeFailure("merge %s dispatched to %r, not compiled "
                               "Pallas on the TPU" % (op, pipe["backend"]))
    log("run_all (4 merges, pipelined, kernel=jax): %.3f s" % wall)
    log(counter.report())
    log(io_report(sess))

    for op, theta, budget in ONE_CHIP_SPECS:
        sess.submit(_spec(ids, op, theta, budget), sid="ref-" + op)
        t0 = time.perf_counter()
        sess.run_all(compute="stream")
        ref_s = time.perf_counter() - t0
        max_abs, n_diff, _ = compare(sess, "chip-" + op, "ref-" + op)
        log("check %s: max_abs_diff %.9g, elements differing %d, stream "
            "reference %.3f s, within tolerance" % (op, max_abs, n_diff, ref_s))
        drop(sess, "chip-" + op, "ref-" + op)


def four_chips(sess: Session, ids) -> None:
    """The mesh path only: one worker process drives every chip."""
    sess.stats.reset()
    for op, theta, budget in FOUR_CHIP_SPECS:
        sess.submit(_spec(ids, op, theta, budget), sid="mesh-" + op)
    t0 = time.perf_counter()
    results = sess.run_all(dist=DistOptions(n_workers=1, kernel="mesh"))
    log("run_all (%d merges, sharded, kernel=mesh): %.3f s"
        % (len(results), time.perf_counter() - t0))
    for (op, _theta, _budget), res in zip(FOUR_CHIP_SPECS, results):
        (shard,) = res.stats["shards"]
        pipe = shard["pipeline"]
        log("merge %s: seconds %.3f, mesh_devices %d, attempts %d, "
            "packed_blocks %d, backend %s" % (
                op, res.stats["seconds"], pipe["mesh_devices"],
                shard["attempts"], pipe["packed_blocks"], pipe["backend"]))
        if (pipe["mesh_devices"] != 4 or shard["attempts"] != 1
                or pipe["backend"] != "xla-" + PLATFORM):
            raise SmokeFailure("merge %s: expected 4 TPU mesh devices and "
                               "1 attempt" % op)
    log(io_report(sess))
    for op, theta, budget in FOUR_CHIP_SPECS:
        sess.submit(_spec(ids, op, theta, budget), sid="ref-" + op)
        t0 = time.perf_counter()
        sess.run_all(compute="stream")
        ref_s = time.perf_counter() - t0
        max_abs, n_diff, n_tail = compare(
            sess, "mesh-" + op, "ref-" + op,
            tail_w=BLOCK_SIZE // 2 if op == "ties" else 0)
        log("check %s: max_abs_diff %.9g, elements differing %d, outside "
            "tolerance in TIES tail blocks %d, stream reference %.3f s, "
            "within tolerance" % (op, max_abs, n_diff, n_tail, ref_s))
        drop(sess, "mesh-" + op, "ref-" + op)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path, over four chips")
    args = ap.parse_args(argv)

    log("compile cache: %s" % enable_compile_cache())
    dev = None
    if not args.four_chips:
        # the mesh path's worker process needs the chips: this process
        # may look at the device only after that worker has exited
        dev = require_tpu()
        log("device: %s %s x%d" % (dev.platform, dev.device_kind,
                                   len(__import__("jax").devices())))
    shutil.rmtree(WORKSPACE, ignore_errors=True)
    try:
        t0 = time.perf_counter()
        with Session(WORKSPACE, block_size=BLOCK_SIZE) as sess:
            ids = register_fleet(sess, fleet_shapes(N_LAYERS), args.seed)
            log("setup: generate + register + analyze %.3f s"
                % (time.perf_counter() - t0))
            if args.four_chips:
                four_chips(sess, ids)
            else:
                one_chip(sess, ids)
    finally:
        shutil.rmtree(WORKSPACE, ignore_errors=True)
    import jax

    if dev is None:
        dev = require_tpu()
    print(device_line(dev, len(jax.devices())), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print("chip_smoke: FAILED: %s" % e, file=sys.stderr)
        sys.exit(1)
