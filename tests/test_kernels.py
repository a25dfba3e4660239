"""Pallas kernels vs pure-jnp oracles: shape/dtype/K sweeps in
interpret=True (kernel body executed on CPU; TPU is the target)."""
import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from repro.kernels import merge_block as mb  # noqa: E402
from repro.kernels import ref  # noqa: E402

SHAPES = [(3, 257), (8, 1024), (5, 700), (16, 2048), (1, 64)]
DTYPES = ["float32", "bfloat16"]
KS = [1, 2, 5]


def _mk(nb, k, w, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x0 = jnp.asarray(rng.normal(size=(nb, w)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(nb, k, w)), jnp.float32)
    if dtype == "bfloat16":
        x0 = x0.astype(jnp.bfloat16).astype(jnp.float32)
        D = D.astype(jnp.bfloat16).astype(jnp.float32)
    return x0, D


def _as_stored(x0, D, seed=0):
    """Expert rows E and flags for deltas D: a flagged row holds full
    weights x0 + Δ, the others the delta; and the float32 deltas the
    kernels form from them."""
    from repro.kernels.ops import _deltas

    full = jnp.asarray(np.random.default_rng(seed).random(D.shape[:2]) < 0.5)
    E = jnp.where(full[:, :, None], x0[:, None, :] + D, D)
    return E, full, _deltas(x0, E, full)


def _pad_run(fn, x0, E, full, *extras, **kw):
    from repro.kernels.ops import _pallas_padded

    return _pallas_padded(fn, x0, E, full.astype(jnp.float32), *extras,
                          interpret=True, **kw)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_linear_kernel_sweep(shape, k, dtype):
    nb, w = shape
    x0, D = _mk(nb, k, w, dtype)
    E, full, D = _as_stored(x0, D)
    got = _pad_run(mb.linear_merge_pallas, x0, E, full, coeff=0.37)
    want = x0 + 0.37 * D.sum(axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("trim", [0.1, 0.5, 1.0])
def test_ties_kernel_sweep(shape, k, trim):
    nb, w = shape
    x0, D = _mk(nb, k, w, "float32", seed=k)
    E, full, D = _as_stored(x0, D, seed=k)
    thresh = ref.ties_thresholds(D, trim)
    got = _pad_run(mb.ties_merge_pallas, x0, E, full, thresh, lam=0.9)
    want = ref.ties_apply_ref(x0, D, thresh, 0.9)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("trim", [0.001, 0.3, 0.5, 0.999])
@pytest.mark.parametrize("dtype", DTYPES)
def test_ties_thresholds_equal_sorted_rank(trim, dtype):
    """The bisection threshold is exactly the keep-th largest |Δ|, as a
    sort finds it — also with repeated values and zeros (bf16 deltas)."""
    nb, k, w = 3, 4, 1000
    _, D = _mk(nb, k, w, dtype, seed=9)
    D = D.at[0, 0, :500].set(0.0).at[1, 2].set(0.25)
    keep = max(1, int(round(trim * w)))
    want = np.sort(np.abs(np.asarray(D)), axis=-1)[..., w - keep]
    np.testing.assert_array_equal(np.asarray(ref.ties_thresholds(D, trim)), want)


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("density", [0.25, 0.75])
def test_dare_kernel_sweep(shape, k, density):
    nb, w = shape
    x0, D = _mk(nb, k, w, "float32", seed=k + 1)
    rng = np.random.default_rng(7)
    masks = jnp.asarray(rng.random((nb, k, w)) < density)
    E, full, D = _as_stored(x0, D, seed=k + 1)
    got = _pad_run(mb.dare_merge_pallas, x0, E, full, masks,
                   density=density, lam=1.1)
    want = ref.dare_ref(x0, D, masks, density, 1.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
def test_sketch_kernel_sweep(shape):
    from repro.kernels.ops import sketch_blocks

    nb, w = shape
    rng = np.random.default_rng(3)
    x = rng.normal(size=(nb, w)).astype(np.float32)
    s = sketch_blocks(x)
    np.testing.assert_allclose(s[:, 0], np.linalg.norm(x, axis=1), rtol=1e-4)
    np.testing.assert_allclose(s[:, 1], np.abs(x).max(axis=1), rtol=1e-6)
    np.testing.assert_allclose(s[:, 2], x.mean(axis=1), rtol=1e-3, atol=1e-6)


def test_ops_dispatch_forced_pallas():
    """merge_blocks through the interpret-mode Pallas path == jnp path."""
    from repro.kernels import ops as kops

    nb, k, w = 4, 3, 300
    x0, D = _mk(nb, k, w, "float32")
    masks = np.random.default_rng(0).random((nb, k, w)) < 0.5
    assert kops.backend(interpret=True) == "pallas-interpret"
    assert kops.backend() == "jnp-" + jax.default_backend()
    for op, theta, extra in [
        ("avg", {}, {}),
        ("ta", {"lam": 0.3}, {}),
        ("ties", {"trim_frac": 0.4}, {}),
        ("dare", {"density": 0.5}, {"masks": masks}),
    ]:
        a = kops.merge_blocks(op, x0, D, theta, **extra)
        b = kops.merge_blocks(op, x0, D, theta, interpret=True, **extra)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("flags", ["per_row", "one"])
@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "float32",
                                   "float64"])
@pytest.mark.parametrize("op,theta", [("avg", {}), ("ta", {"lam": 0.3}),
                                      ("ties", {"trim_frac": 0.2}),
                                      ("dare", {"density": 0.5})])
def test_stored_operands_give_the_float32_path_bytes(op, theta, dtype,
                                                     interpret, flags):
    """Stored-dtype operands with full-weights flags give the bytes of the
    float32 path: deltas formed and widened on the host, float32 operands,
    the result rounded on the host."""
    from repro.kernels import ops as kops
    from repro.store.dtypes import to_np_dtype

    dt = to_np_dtype(dtype)
    nb, k, w = 5, 3, 700  # ragged in both tile dimensions
    rng = np.random.default_rng(11)
    x0 = (0.02 * rng.normal(size=(nb, w))).astype(dt)
    E = (x0.astype(np.float32)[:, None, :]
         + 4e-4 * rng.normal(size=(nb, k, w))).astype(dt)
    full = rng.random((nb, k)) < 0.5 if flags == "per_row" else np.True_
    full_rows = np.broadcast_to(full, (nb, k))
    E[~full_rows] = (4e-4 * rng.normal(size=(int((~full_rows).sum()), w))
                     ).astype(dt)
    x0f = x0.astype(np.float32)
    D = np.where(full_rows[:, :, None], E.astype(np.float32) - x0f[:, None, :],
                 E.astype(np.float32))
    extra = ({"masks": rng.random((nb, k, w)) < 0.5} if op == "dare" else {})
    want = kops.merge_blocks(op, x0f, D, theta, interpret=interpret,
                             **extra).astype(dt)
    got = kops.merge_blocks(op, x0, E, dict(theta, _subtract=full),
                            interpret=interpret, **extra)
    assert got.dtype == dt
    assert got.tobytes() == want.tobytes()


# ------------------------------------------------------ flash attention
FA_CASES = [
    # (B, Sq, Sk, H, Hkv, hd, causal, window, q_offset)
    (2, 64, 64, 4, 2, 16, True, 0, 0),    # GQA causal
    (1, 50, 50, 4, 1, 8, True, 13, 0),    # MQA local window
    (2, 33, 70, 6, 6, 16, False, 0, 0),   # cross (ragged, MHA)
    (1, 1, 40, 4, 2, 16, True, 0, 39),    # decode-style single query
]


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_kernel_vs_jax(case):
    """Pallas flash kernel (interpret) == chunked JAX attention."""
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.attention import flash_attention

    b, sq, sk, h, hkv, hd, causal, window, qoff = case
    rng = np.random.default_rng(hash(case) % 2**31)
    q = jnp.asarray(rng.normal(size=(b, sq, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sk, hkv, hd)), jnp.float32)
    want = flash_attention(q, k, v, causal=causal, window=window,
                           q_offset=qoff, cq=16, ck=16)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=qoff, cq=16, ck=16,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_kernel_bf16():
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.attention import flash_attention

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 32, 4, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 32, 2, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 32, 2, 16)), jnp.bfloat16)
    want = flash_attention(q, k, v, causal=True, cq=16, ck=16)
    got = flash_attention_pallas(q, k, v, causal=True, cq=16, ck=16,
                                 interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )
