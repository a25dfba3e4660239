"""Pallas TPU kernels for blockwise merge operators.

TPU-native adaptation (DESIGN.md §6): the paper's hot loop is
``ApplyOperator(x0, {Δi})`` over streamed blocks on a CPU; on TPU the same
work is a VPU elementwise-fusion problem.  We tile the *block batch*
``(NB, W)`` into VMEM tiles and keep all K expert delta tiles resident,
fusing trim-mask -> sign-election -> disjoint-mean -> λ-scale (TIES),
mask -> rescale -> sum (DARE), and the linear ops (AVG / TA) into single
kernels — one HBM round-trip per operand instead of one per arithmetic op.

Operands arrive in the checkpoint's stored dtype: base blocks x0 (NB, W),
expert blocks E (NB, K, W) and a per-row flag ``full`` (NB, K), nonzero
where E's row holds full weights rather than a delta.  Each kernel widens
its tiles to float32 in VMEM, forms Δ = E − x0 on flagged rows and E
elsewhere, merges in float32 and rounds the result once to x0's dtype —
the same float32 arithmetic as widening on the host, at the stored
dtype's bytes over HBM.

Tiling: grid is (NB/TB, W/TW) with TB=8 sublanes for 4-byte operands (16
for 2-byte ones, their minimum tile) and TW=1024 (8×128 lanes), K
resident in VMEM.  VMEM per grid step ≈ (K+2)·TB·TW·itemsize ≈ (K+2)·32
KiB, plus the float32 Δ tile — comfortably inside the ~16 MiB VMEM for
K ≤ 64.  Merging has arithmetic intensity < 1 FLOP/byte, so the kernels
are HBM-bandwidth-bound by construction; the win is the fusion, not FLOPs.

TIES trim thresholds (a per-row top-k) are computed *outside* the kernel
(``ref.ties_thresholds``, an exact bisection) and passed in as a (NB, K)
operand — selecting inside a VPU kernel would waste the fused pass.

Tests validate the kernels with ``interpret=True`` (kernel body executed
in Python on the CPU) against :mod:`repro.kernels.ref`, and
``tests/test_tpu_compile.py`` compiles them for a described v5e chip;
``benchmarks/chip`` runs them compiled on the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# TPU-aligned tile: 8 sublanes × 128 lanes; TW a multiple of 128.
TILE_NB = 8
TILE_W = 1024


def _grid(nb: int, w: int, tb: int, tw: int):
    return (pl.cdiv(nb, tb), pl.cdiv(w, tw))


# ------------------------------------------------------------------ deltas
def _deltas(x0_ref, e_ref, f_ref):
    """float32 base tile (TB, TW) and deltas (TB, K, TW): a row flagged
    full weights has the base subtracted, any other row is the delta."""
    x0 = x0_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    full = f_ref[...][:, :, None] != 0   # (TB, K, 1)
    return x0, jnp.where(full, e - x0[:, None, :], e)


def _row_spec(tb: int, k: int):
    """(TB, K): one value per (block, expert) row."""
    return pl.BlockSpec((tb, k), lambda i, j: (i, 0))


def _element_spec(tb: int, k: int, tw: int):
    """(TB, K, TW): one value per element of every expert row."""
    return pl.BlockSpec((tb, k, tw), lambda i, j: (i, 0, j))


def _specs(tb: int, tw: int, k: int):
    """BlockSpecs of x0, E and the flags."""
    return [pl.BlockSpec((tb, tw), lambda i, j: (i, j)),
            _element_spec(tb, k, tw), _row_spec(tb, k)]


# ----------------------------------------------------------------- AVG / TA
def _linear_kernel(x0_ref, e_ref, f_ref, o_ref, *, coeff: float):
    """out = x0 + coeff * Σ_k Δ_k   (AVG: coeff=1/(K+1), TA: coeff=λ)."""
    x0, d = _deltas(x0_ref, e_ref, f_ref)
    acc = jnp.sum(d, axis=1)  # (TB, TW), K reduced in VMEM
    o_ref[...] = (x0 + coeff * acc).astype(o_ref.dtype)


def linear_merge_pallas(
    x0: jnp.ndarray,
    E: jnp.ndarray,
    full: jnp.ndarray,
    coeff: float,
    tb: int = TILE_NB,
    tw: int = TILE_W,
    interpret: bool = False,
) -> jnp.ndarray:
    nb, k, w = E.shape
    return pl.pallas_call(
        functools.partial(_linear_kernel, coeff=coeff),
        grid=_grid(nb, w, tb, tw),
        in_specs=_specs(tb, tw, k),
        out_specs=pl.BlockSpec((tb, tw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, w), x0.dtype),
        interpret=interpret,
    )(x0, E, full)


# ----------------------------------------------------------------------- TIES
def _ties_kernel(x0_ref, e_ref, f_ref, t_ref, o_ref, *, lam: float):
    x0, d = _deltas(x0_ref, e_ref, f_ref)   # (TB, TW), (TB, K, TW)
    thresh = t_ref[...][:, :, None]      # (TB, K, 1)
    mask = jnp.abs(d) >= thresh
    dt = jnp.where(mask, d, 0.0)
    elected = jnp.sign(jnp.sum(dt, axis=1))              # (TB, TW)
    agree = (jnp.sign(dt) == elected[:, None, :]) & mask
    agree = agree & (elected != 0)[:, None, :]
    num = jnp.sum(jnp.where(agree, dt, 0.0), axis=1)
    cnt = jnp.sum(agree.astype(jnp.float32), axis=1)
    o_ref[...] = (x0 + lam * num / jnp.maximum(cnt, 1.0)).astype(o_ref.dtype)


def ties_merge_pallas(
    x0: jnp.ndarray,
    E: jnp.ndarray,
    full: jnp.ndarray,
    thresh: jnp.ndarray,
    lam: float = 1.0,
    tb: int = TILE_NB,
    tw: int = TILE_W,
    interpret: bool = False,
) -> jnp.ndarray:
    nb, k, w = E.shape
    return pl.pallas_call(
        functools.partial(_ties_kernel, lam=lam),
        grid=_grid(nb, w, tb, tw),
        in_specs=_specs(tb, tw, k) + [_row_spec(tb, k)],
        out_specs=pl.BlockSpec((tb, tw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, w), x0.dtype),
        interpret=interpret,
    )(x0, E, full, thresh)


# ----------------------------------------------------------------------- DARE
def _dare_kernel(x0_ref, e_ref, f_ref, m_ref, o_ref, *, inv_density: float,
                 lam: float):
    x0, d = _deltas(x0_ref, e_ref, f_ref)
    m = m_ref[...].astype(jnp.float32)   # (TB, K, TW) keep mask
    acc = jnp.sum(d * m, axis=1) * inv_density
    o_ref[...] = (x0 + lam * acc).astype(o_ref.dtype)


def dare_merge_pallas(
    x0: jnp.ndarray,
    E: jnp.ndarray,
    full: jnp.ndarray,
    masks: jnp.ndarray,
    density: float = 0.5,
    lam: float = 1.0,
    tb: int = TILE_NB,
    tw: int = TILE_W,
    interpret: bool = False,
) -> jnp.ndarray:
    nb, k, w = E.shape
    return pl.pallas_call(
        functools.partial(_dare_kernel, inv_density=1.0 / density, lam=lam),
        grid=_grid(nb, w, tb, tw),
        in_specs=_specs(tb, tw, k) + [_element_spec(tb, k, tw)],
        out_specs=pl.BlockSpec((tb, tw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, w), x0.dtype),
        interpret=interpret,
    )(x0, E, full, masks.astype(jnp.int8))


# ------------------------------------------------------------ ANALYZE sketch
def _sketch_kernel(x_ref, o_ref):
    """Per-block partial stats: Σx², max|x|, Σx over the width tile.
    Width-tile partials are accumulated by the caller (associative)."""
    x = x_ref[...]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    sq = jnp.sum(x * x, axis=1)
    mx = jnp.max(jnp.abs(x), axis=1)
    sm = jnp.sum(x, axis=1)
    prev = o_ref[...]
    o_ref[...] = jnp.stack(
        [prev[:, 0] + sq, jnp.maximum(prev[:, 1], mx), prev[:, 2] + sm], axis=1
    )


def sketch_blocks_pallas(
    x: jnp.ndarray,
    tb: int = TILE_NB,
    tw: int = TILE_W,
    interpret: bool = False,
) -> jnp.ndarray:
    """(NB, W) -> (NB, 3) stats [Σx², max|x|, Σx] for ANALYZE on-device."""
    nb, w = x.shape
    return pl.pallas_call(
        _sketch_kernel,
        grid=_grid(nb, w, tb, tw),
        in_specs=[pl.BlockSpec((tb, tw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tb, 3), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 3), jnp.float32),
        interpret=interpret,
    )(x)
