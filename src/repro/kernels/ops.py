"""Jitted wrappers + dispatch for the merge kernels.

``merge_blocks(op, x0s, Es, theta, masks=None)`` is the single entry used
by the executor's batched and pipelined paths.  The implementation is
picked from the platform JAX runs on, never from a fallback:

    * TPU          -> Pallas kernels, compiled
    * CPU/other    -> pure-jnp reference (XLA-fused)
    * interpret=True (tests only) -> Pallas kernels in interpret mode

:func:`backend` names the implementation a call dispatches to; the
pipelined engine records it in its run stats.  A backend that fails to
start raises here — it is never read as "not a TPU".

Operand contract: operands cross to the device in the dtype they are
stored in (:func:`operand_dtype`: a float wider than four bytes is cast
to float32 on the host).  Inside one jitted program each operand is
widened to float32, the delta of a full-weights row is formed there
(expert − base), the operator runs in float32, and the result is
rounded once to the base's dtype before it leaves the device — the same
float32 arithmetic, in the same order, as widening on the host.
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


def operand_dtype(dtype) -> np.dtype:
    """The dtype an operand stored as ``dtype`` crosses to the device in:
    itself, or float32 for a float wider than four bytes."""
    dtype = np.dtype(dtype)
    if dtype.itemsize > 4 and np.issubdtype(dtype, np.floating):
        return np.dtype(np.float32)
    return dtype


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pallas_padded(fn, x0, E, *extras, interpret: bool,
                   tb=None, tw=mb.TILE_W, **kw):
    """Pad (NB, W) to tile multiples, run the kernel, slice back.  The
    default TB is the minimum tile of the narrowest operand: 8 rows of
    4-byte values, 16 of 2-byte ones."""
    nb, w = x0.shape
    if tb is None:
        tb = mb.TILE_NB * 4 // min(x0.dtype.itemsize, E.dtype.itemsize)
    tw = min(tw, max(128, ((w + 127) // 128) * 128))
    x0p = _pad_to(_pad_to(x0, tb, 0), tw, 1)
    Ep = _pad_to(_pad_to(E, tb, 0), tw, 2)
    extras_p = []
    for e in extras:
        e = _pad_to(e, tb, 0)
        if e.ndim == 3:
            e = _pad_to(e, tw, 2)
        extras_p.append(e)
    out = fn(x0p, Ep, *extras_p, tb=tb, tw=tw, interpret=interpret, **kw)
    return out[:nb, :w]


#: operand dtypes the Pallas kernels load on a TPU (Mosaic has no float16)
_KERNEL_DTYPES = (jnp.float32, jnp.bfloat16)


def _deltas(x0, E, full):
    """float32 deltas (NB, K, W) of stored-dtype operands."""
    e = E.astype(jnp.float32)
    return jnp.where(full[:, :, None], e - x0.astype(jnp.float32)[:, None, :],
                     e)


# one program per call: widening, deltas, thresholds, the kernel and the
# rounding to the stored dtype; compiled once per shape, dtype and θ
@functools.partial(jax.jit,
                   static_argnames=("op", "params", "pallas", "interpret"))
def _merge(x0, E, full, masks, op, params, pallas, interpret):
    nb, k, _ = E.shape
    full = jnp.broadcast_to(full, (nb, k))
    stored = x0.dtype
    if pallas:
        # a dtype the kernels cannot load is widened on the device first
        x0, E = (a if a.dtype in _KERNEL_DTYPES else a.astype(jnp.float32)
                 for a in (x0, E))
        flags = full.astype(jnp.float32)
        if op in ("avg", "ta"):
            (coeff,) = params
            out = _pallas_padded(mb.linear_merge_pallas, x0, E, flags,
                                 coeff=coeff, interpret=interpret)
        elif op == "ties":
            trim, lam = params
            thresh = ref.ties_thresholds(_deltas(x0, E, full), trim)
            out = _pallas_padded(mb.ties_merge_pallas, x0, E, flags, thresh,
                                 lam=lam, interpret=interpret)
        else:
            density, lam = params
            out = _pallas_padded(mb.dare_merge_pallas, x0, E, flags, masks,
                                 density=density, lam=lam,
                                 interpret=interpret)
        return out.astype(stored)
    x = x0.astype(jnp.float32)
    D = _deltas(x0, E, full)
    if op == "avg":
        out = ref.avg_ref(x, D)  # divides by K + 1 where Pallas multiplies
    elif op == "ta":
        out = ref.ta_ref(x, D, *params)
    elif op == "ties":
        trim, lam = params
        out = ref.ties_apply_ref(x, D, ref.ties_thresholds(D, trim), lam)
    else:
        out = ref.dare_ref(x, D, masks, *params)
    return out.astype(stored)


def _operand(a) -> jnp.ndarray:
    """``a`` as it crosses to the device (see :func:`operand_dtype`)."""
    dtype = operand_dtype(a.dtype)
    if dtype != a.dtype:
        a = np.asarray(a, dtype)  # float64: cast on the host
    return jnp.asarray(a)


# --------------------------------------------------------------- public API
def merge_blocks(
    op: str,
    x0s,
    Es,
    theta: Dict,
    masks=None,
    interpret: bool = False,
) -> np.ndarray:
    """Apply operator ``op`` to a batch of blocks.

    x0s (NB, W) base blocks and Es (NB, K, W) expert rows, each in its
    stored float dtype; masks (NB, K, W) bool for DARE.  ``theta`` holds
    the operator's parameters and, under the key ``"_subtract"`` (per-call
    data like the host operator's ``"_masks"``), a bool broadcastable to
    (NB, K): True where Es's row holds an expert's full weights, whose
    delta is the row less the base; False or absent where the row holds
    the delta itself.  Math runs in float32 on the device (see the module
    docstring).  Returns an (NB, W) ndarray in x0s's dtype.
    ``interpret=True`` runs the Pallas kernels in interpret mode on any
    platform (tests only).
    """
    x0 = _operand(x0s)
    E = _operand(Es)
    full = jnp.asarray(np.asarray(theta.get("_subtract", False), np.bool_))
    lam = float(theta.get("lam", 1.0))
    op = op.lower()
    m = None
    if op == "avg":
        params = (1.0 / (E.shape[1] + 1),)
    elif op == "ta":
        params = (lam,)
    elif op == "ties":
        params = (float(theta.get("trim_frac", 0.2)), lam)
    elif op == "dare":
        if masks is None:
            raise ValueError("dare requires masks")
        m = jnp.asarray(masks)
        params = (float(theta.get("density", 0.5)), lam)
    else:
        raise KeyError(f"unknown operator {op!r}")
    pallas = interpret or _on_tpu()
    out = np.asarray(_merge(x0, E, full, m, op, params, pallas, interpret))
    return out.astype(x0s.dtype, copy=False)


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
