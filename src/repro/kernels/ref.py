"""Pure-jnp oracles for the merge kernels.

Shapes (the executor's batched layout):
    x0     (NB, W)        base blocks, float32
    D      (NB, K, W)     stacked expert deltas, float32
    masks  (NB, K, W)     DARE keep masks (bool)
    thresh (NB, K)        TIES per-(block, expert) trim thresholds

These mirror :mod:`repro.core.operators` bit-for-bit (same trim rule,
same election rule) and serve as the allclose oracle for the Pallas
kernels in :mod:`repro.kernels.merge_block`.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def ties_thresholds(D: jnp.ndarray, trim_frac: float) -> jnp.ndarray:
    """keep-th largest |Δ| per (block, expert) row; keep = round(ρ·W).

    Found exactly by bisection on the float32 bit pattern (for
    non-negative floats it orders like the value): the result is the
    largest ``t`` with at least ``keep`` elements ``>= t``, which is the
    keep-th largest element itself.  A sort gives the same value, but on
    the TPU a sort of a 65,536-wide row takes ~20 s to compile for each
    window shape; the 31 counting passes take ~0.3 s and no sorted copy.
    """
    nb, k, w = D.shape
    keep = max(1, int(round(trim_frac * w)))
    if keep >= w:
        return jnp.full((nb, k), -jnp.inf, dtype=jnp.float32)
    bits = lax.bitcast_convert_type(jnp.abs(D.astype(jnp.float32)), jnp.int32)
    # invariant: count(bits >= lo) >= keep > count(bits >= hi)
    lo = jnp.zeros((nb, k), jnp.int32)
    hi = jnp.full((nb, k), jnp.iinfo(jnp.int32).max, jnp.int32)

    def halve(_, bounds):
        lo, hi = bounds
        mid = lo + (hi - lo) // 2
        ok = jnp.sum(bits >= mid[..., None], axis=-1) >= keep
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = lax.fori_loop(0, 31, halve, (lo, hi))
    return lax.bitcast_convert_type(lo, jnp.float32)


def avg_ref(x0: jnp.ndarray, D: jnp.ndarray) -> jnp.ndarray:
    k = D.shape[1]
    return x0 + D.sum(axis=1) / (k + 1)


def ta_ref(x0: jnp.ndarray, D: jnp.ndarray, lam: float = 1.0) -> jnp.ndarray:
    return x0 + lam * D.sum(axis=1)


def ties_apply_ref(
    x0: jnp.ndarray, D: jnp.ndarray, thresh: jnp.ndarray, lam: float = 1.0
) -> jnp.ndarray:
    """Trim (by precomputed thresholds) -> elect sign -> sign-matched mean."""
    mask = jnp.abs(D) >= thresh[..., None]
    Dt = jnp.where(mask, D, 0.0)
    elected = jnp.sign(Dt.sum(axis=1))  # (NB, W)
    agree = (jnp.sign(Dt) == elected[:, None, :]) & mask & (elected != 0)[:, None, :]
    num = jnp.where(agree, Dt, 0.0).sum(axis=1)
    cnt = agree.sum(axis=1)
    return x0 + lam * num / jnp.maximum(cnt, 1)


def ties_ref(
    x0: jnp.ndarray, D: jnp.ndarray, trim_frac: float = 0.2, lam: float = 1.0
) -> jnp.ndarray:
    return ties_apply_ref(x0, D, ties_thresholds(D, trim_frac), lam)


def dare_ref(
    x0: jnp.ndarray,
    D: jnp.ndarray,
    masks: jnp.ndarray,
    density: float = 0.5,
    lam: float = 1.0,
) -> jnp.ndarray:
    rescaled = jnp.where(masks, D, 0.0) / density
    return x0 + lam * rescaled.sum(axis=1)
