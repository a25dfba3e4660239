"""Jitted wrappers + dispatch for the merge kernels.

``merge_blocks(op, x0s, Ds, theta, masks=None)`` is the single entry used
by the executor's batched and pipelined paths.  The implementation is
picked from the platform JAX runs on, never from a fallback:

    * TPU          -> Pallas kernels, compiled
    * CPU/other    -> pure-jnp reference (XLA-fused)
    * interpret=True (tests only) -> Pallas kernels in interpret mode

:func:`backend` names the implementation a call dispatches to; the
pipelined engine records it in its run stats.  A backend that fails to
start raises here — it is never read as "not a TPU".

Inputs may be any float dtype; math runs in float32 and the result is
cast back (matching the streaming executor's numpy semantics).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import merge_block as mb
from repro.kernels import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def backend(interpret: bool = False) -> str:
    """The implementation :func:`merge_blocks` dispatches to:
    ``"pallas-tpu"`` (compiled Pallas), ``"pallas-interpret"`` or
    ``"jnp-<platform>"``."""
    if interpret:
        return "pallas-interpret"
    platform = jax.default_backend()
    return "pallas-tpu" if platform == "tpu" else "jnp-" + platform


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pallas_padded(fn, x0, D, *extras, interpret: bool,
                   tb=mb.TILE_NB, tw=mb.TILE_W, **kw):
    """Pad (NB, W) to tile multiples, run the kernel, slice back."""
    nb, w = x0.shape
    tw = min(tw, max(128, ((w + 127) // 128) * 128))
    x0p = _pad_to(_pad_to(x0, tb, 0), tw, 1)
    Dp = _pad_to(_pad_to(D, tb, 0), tw, 2)
    extras_p = []
    for e in extras:
        e = _pad_to(e, tb, 0)
        if e.ndim == 3:
            e = _pad_to(e, tw, 2)
        extras_p.append(e)
    out = fn(x0p, Dp, *extras_p, tb=tb, tw=tw, interpret=interpret, **kw)
    return out[:nb, :w]


# pad + kernel + slice as one program: one compile per input shape
@functools.partial(jax.jit, static_argnames=("coeff", "interpret"))
def _linear_pallas(x0, D, coeff, interpret):
    return _pallas_padded(mb.linear_merge_pallas, x0, D, coeff=coeff,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("lam", "interpret"))
def _ties_pallas(x0, D, thresh, lam, interpret):
    return _pallas_padded(mb.ties_merge_pallas, x0, D, thresh, lam=lam,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("density", "lam", "interpret"))
def _dare_pallas(x0, D, m, density, lam, interpret):
    return _pallas_padded(mb.dare_merge_pallas, x0, D, m, density=density,
                          lam=lam, interpret=interpret)


# --------------------------------------------------------------- public API
def merge_blocks(
    op: str,
    x0s,
    Ds,
    theta: Dict,
    masks=None,
    interpret: bool = False,
) -> np.ndarray:
    """Apply operator ``op`` to a batch of blocks.

    x0s (NB, W) float; Ds (NB, K, W); masks (NB, K, W) for DARE.
    Returns float32 ndarray (NB, W).  ``interpret=True`` runs the Pallas
    kernels in interpret mode on any platform (tests only).
    """
    x0 = jnp.asarray(x0s, jnp.float32)
    D = jnp.asarray(Ds, jnp.float32)
    lam = float(theta.get("lam", 1.0))
    op = op.lower()
    pallas = interpret or _on_tpu()

    if op == "avg":
        k = D.shape[1]
        if pallas:
            out = _linear_pallas(x0, D, 1.0 / (k + 1), interpret)
        else:
            out = _avg_jit(x0, D)
    elif op == "ta":
        if pallas:
            out = _linear_pallas(x0, D, lam, interpret)
        else:
            out = _ta_jit(x0, D, lam)
    elif op == "ties":
        trim = float(theta.get("trim_frac", 0.2))
        thresh = _ties_thresh_jit(D, trim)
        if pallas:
            out = _ties_pallas(x0, D, thresh, lam, interpret)
        else:
            out = _ties_apply_jit(x0, D, thresh, lam)
    elif op == "dare":
        if masks is None:
            raise ValueError("dare requires masks")
        m = jnp.asarray(masks)
        density = float(theta.get("density", 0.5))
        if pallas:
            out = _dare_pallas(x0, D, m, density, lam, interpret)
        else:
            out = _dare_jit(x0, D, m, density, lam)
    else:
        raise KeyError(f"unknown operator {op!r}")
    return np.asarray(out)


# ------------------------------------------------------------ jitted refs
@jax.jit
def _avg_jit(x0, D):
    return ref.avg_ref(x0, D)


@functools.partial(jax.jit, static_argnums=(2,))
def _ta_jit(x0, D, lam):
    return ref.ta_ref(x0, D, lam)


@functools.partial(jax.jit, static_argnums=(1,))
def _ties_thresh_jit(D, trim):
    return ref.ties_thresholds(D, trim)


@functools.partial(jax.jit, static_argnums=(3,))
def _ties_apply_jit(x0, D, thresh, lam):
    return ref.ties_apply_ref(x0, D, thresh, lam)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _dare_jit(x0, D, m, density, lam):
    return ref.dare_ref(x0, D, m, density, lam)


def sketch_blocks(x, interpret: bool = False) -> np.ndarray:
    """(NB, W) -> (NB, 3) [l2, absmax, mean] (ANALYZE on-device path)."""
    xj = jnp.asarray(x, jnp.float32)
    if interpret or _on_tpu():
        nb, w = xj.shape
        tw = min(mb.TILE_W, max(128, ((w + 127) // 128) * 128))
        xp = _pad_to(_pad_to(xj, mb.TILE_NB, 0), tw, 1)
        stats = mb.sketch_blocks_pallas(
            xp, tb=mb.TILE_NB, tw=tw, interpret=interpret
        )[:nb]
    else:
        stats = _sketch_jit(xj)
    sq, mx, sm = stats[:, 0], stats[:, 1], stats[:, 2]
    w = x.shape[1]
    return np.stack(
        [np.sqrt(np.asarray(sq)), np.asarray(mx), np.asarray(sm) / w], axis=1
    )


@jax.jit
def _sketch_jit(x):
    return jnp.stack(
        [jnp.sum(x * x, axis=1), jnp.max(jnp.abs(x), axis=1), jnp.sum(x, axis=1)],
        axis=1,
    )
