"""The plain reference a cell's committed merges are compared with.

It imports nothing of the program and reads nothing the program made: the
fleet comes from the seed (``fleet.py``), the plan and the operators are
written out here from their published definitions.

* Plan (budget-aware greedy selection, paper Algorithm 1).  Every expert
  block is a candidate, scored by its task vector's L2 norm per byte
  (float32 norm of expert - base, as ANALYZE sketches it), times
  ``0.5 + 0.5 * agreement`` for TIES, where agreement is one minus the
  share of 64 evenly spaced sign bits that differ from the experts'
  bitwise majority.  Candidates are admitted by score, highest first
  (ties by expert, tensor, block), while they fit the budget; one that
  does not fit is skipped.  A budget that skipped blocks moves TIES'
  ``trim_frac`` (DARE's ``density``) to ``old * (0.8 + 0.4 * share)``,
  kept within ``[0.8 * old, old]``, where share is the admitted share of
  all expert bytes.
* Operators, per block of the blocks an expert was selected for, with
  ``D_k = expert_k - base``:  AVG ``x0 + sum(D) / (k + 1)``, TA
  ``x0 + lam * sum(D)``, TIES (trim each expert to its ``trim_frac`` share
  of largest ``|D|`` in the block, elect the sign of the sum, average the
  agreeing entries, scale by ``lam``), DARE (keep each entry with
  probability ``density`` from a Philox stream per (seed, expert,
  tensor, block), rescale by ``1 / density``, sum, scale by ``lam``).
  Blocks with no selected expert keep the base.

The merge runs on the device in blocks of rows, in float32 and rounded
once to the stored dtype; ``dtype="bfloat16"`` runs every operation in
bfloat16 instead, which is the control that the comparison has to fail.
"""
from __future__ import annotations

import functools
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import fleet

#: rows (blocks) the device merges per call
CHUNK_ROWS = 64
SIGN_BITS = 64
GOLDEN = 0x9E3779B97F4A7C15


def block_elems(cfg: Dict) -> int:
    return int(cfg["block_size"]) // np.dtype(fleet.stored_dtype(cfg)).itemsize


def resolve_budget(budget, naive_bytes: int) -> Optional[int]:
    """``None`` (unbounded), ``"<p>%"`` of all expert bytes, or bytes."""
    if budget is None:
        return None
    if isinstance(budget, str) and budget.endswith("%"):
        return int(float(budget[:-1]) / 100.0 * naive_bytes)
    return int(budget)


# ------------------------------------------------------------------ plan
def _sign_signature(d: np.ndarray) -> int:
    idx = np.linspace(0, d.size - 1, num=SIGN_BITS, dtype=np.int64)
    out = 0
    for j, neg in enumerate(d[idx] < 0):
        out |= int(neg) << j
    return out


def _deltas(host: Sequence[Dict[str, np.ndarray]], tensor: str, lo: int,
            hi: int, e: int) -> np.ndarray:
    """float32 delta of fine-tune ``e`` (1-based model index) on
    elements [lo, hi) of a tensor."""
    return (np.asarray(host[e][tensor][lo:hi], np.float32)
            - np.asarray(host[0][tensor][lo:hi], np.float32))


def plan(cfg: Dict, k: int, op: str, theta: Dict, budget,
         host=None) -> Tuple[Dict[str, np.ndarray], Dict]:
    """Selection ``{tensor: bool array (n_blocks, k)}`` and the θ the
    merge runs with.  ``host`` holds the fleet on the host as
    ``[{tensor: flat array}]``, base first; it is needed only where a
    budget is set."""
    itemsize = np.dtype(fleet.stored_dtype(cfg)).itemsize
    w = block_elems(cfg)
    theta = dict(theta)
    sizes = {t: int(np.prod(s)) for t, s in fleet.inventory(cfg)}
    n_blocks = {t: -(-n // w) for t, n in sizes.items()}
    sel = {t: np.zeros((n, k), bool) for t, n in n_blocks.items()}
    naive = k * sum(sizes.values()) * itemsize
    cap = resolve_budget(budget, naive)
    if cap is None or cap >= naive:
        for t in sel:
            sel[t][:] = True
        return sel, theta

    cands = []  # (score, expert, tensor, block, nbytes)
    conflict = op.lower() == "ties" and k > 1
    for t in sorted(sizes):
        for b in range(n_blocks[t]):
            lo, hi = b * w, min(sizes[t], (b + 1) * w)
            nbytes = (hi - lo) * itemsize
            l2, sigs = [], []
            for e in range(k):
                d = _deltas(host, t, lo, hi, e + 1)
                l2.append(float(np.linalg.norm(d)))
                sigs.append(_sign_signature(d))
            agree = [1.0] * k
            if conflict:
                maj = 0
                for j in range(SIGN_BITS):
                    ones = sum((s >> j) & 1 for s in sigs)
                    maj |= int(ones * 2 >= k) << j
                agree = [1.0 - bin(s ^ maj).count("1") / 64.0 for s in sigs]
            for e in range(k):
                score = np.float64(l2[e]) / np.float64(nbytes)
                if conflict:
                    score = score * (0.5 + 0.5 * np.float64(agree[e]))
                cands.append((-float(score), e, t, b, nbytes))
    cands.sort()
    cost = skipped = 0
    for _neg, e, t, b, nbytes in cands:
        if cost + nbytes > cap:
            skipped += 1
            continue
        sel[t][b, e] = True
        cost += nbytes
    key = {"ties": "trim_frac", "dare": "density"}.get(op.lower())
    if skipped and key in theta:
        old = theta[key]
        theta[key] = float(np.clip(old * (0.8 + 0.4 * (cost / naive)),
                                   0.8 * old, old))
    return sel, theta


def ties_thresholds(cfg: Dict, sel: Dict[str, np.ndarray], trim: float,
                    host) -> Dict[str, np.ndarray]:
    """{tensor: (n_blocks, k) float32}: the keep-th largest |D| of each
    selected (block, expert), keep = round(trim * block elements)."""
    w = block_elems(cfg)
    sizes = {t: int(np.prod(s)) for t, s in fleet.inventory(cfg)}
    out = {}
    for t, s in sel.items():
        thr = np.full(s.shape, np.inf, np.float32)
        for b, e in zip(*np.nonzero(s)):
            lo, hi = b * w, min(sizes[t], (b + 1) * w)
            n = hi - lo
            keep = max(1, int(round(trim * n)))
            if keep >= n:
                thr[b, e] = -np.inf
                continue
            a = np.abs(_deltas(host, t, lo, hi, e + 1))
            thr[b, e] = np.partition(a, n - keep)[n - keep]
        out[t] = thr
    return out


def _tensor_counter(tensor: str) -> int:
    return int.from_bytes(
        hashlib.blake2b(tensor.encode(), digest_size=8).digest(), "little")


def dare_masks(seed: int, tensor: str, block: int, n: int, k: int,
               density: float) -> np.ndarray:
    """(k, n) keep masks of one block, one Philox stream per expert."""
    out = np.empty((k, n), bool)
    for e in range(k):
        gen = np.random.Generator(np.random.Philox(
            key=(seed & 0xFFFFFFFFFFFFFFFF) ^ (e * GOLDEN),
            counter=[0, 0, block, _tensor_counter(tensor)]))
        out[e] = gen.random(n) < density
    return out


# ----------------------------------------------------------------- merge
@functools.partial(jax.jit, static_argnames=(
    "rows", "width", "op", "lam", "density", "dtype"))
def merge_rows(x0, experts, sel, thr, masks, start, *, rows, width, op,
               lam, density, dtype):
    """Merge ``rows`` blocks of ``width`` elements starting at element
    ``start`` of one tensor.  ``x0`` and each of ``experts`` are the flat
    tensor; ``sel`` (rows, k) marks the selected experts; ``thr`` (rows,
    k) are TIES thresholds and ``masks`` (k, rows, width) DARE keep masks.
    Returns the merged rows in the stored dtype."""
    f = jnp.dtype(dtype)
    n = rows * width
    x = lax.dynamic_slice(x0, (start,), (n,)).reshape(rows, width)
    xf = x.astype(f)
    deltas = [lax.dynamic_slice(e, (start,), (n,)).reshape(rows, width)
              .astype(f) - xf for e in experts]
    on = [sel[:, i:i + 1] for i in range(len(experts))]
    zero = jnp.zeros((), f)
    if op == "ties":
        kept = [o & (jnp.abs(d) >= thr[:, i:i + 1].astype(f))
                for i, (o, d) in enumerate(zip(on, deltas))]
        trimmed = [jnp.where(m, d, zero) for m, d in zip(kept, deltas)]
        total = trimmed[0]
        for d in trimmed[1:]:
            total = total + d
        elected = jnp.sign(total)
        agree = [m & (jnp.sign(d) == elected) & (elected != 0)
                 for m, d in zip(kept, trimmed)]
        num, cnt = zero, jnp.zeros((), jnp.int32)
        for a, d in zip(agree, trimmed):
            num = num + jnp.where(a, d, zero)
            cnt = cnt + a.astype(jnp.int32)
        out = xf + jnp.asarray(lam, f) * (num / jnp.maximum(cnt, 1).astype(f))
    else:
        if op == "dare":
            on = [o & m for o, m in zip(on, masks)]
            deltas = [d / jnp.asarray(density, f) for d in deltas]
        total = zero
        for o, d in zip(on, deltas):
            total = total + jnp.where(o, d, zero)
        if op == "avg":
            k_sel = sum(o.astype(jnp.int32) for o in on)
            out = xf + total / (k_sel + 1).astype(f)
        elif op in ("ta", "dare"):
            out = xf + jnp.asarray(lam, f) * total
        else:
            raise KeyError("the reference has no operator %r" % op)
    return out.astype(x0.dtype)


#: absolute slack of the tolerance, as a share of the block's largest
#: base magnitude: float32 rounding of the same sums in another order
#: stays some 2**-24 of the operands, far below it; bfloat16 arithmetic
#: errs by some 2**-9 of them near cancellation, far above it
ATOL_SHARE = 2.0 ** -21


@functools.partial(jax.jit, static_argnames=("rows", "width"))
def compare_rows(ref, got, x0, start, *, rows, width):
    """(elements that differ, elements outside tolerance) of ``got``
    against ``ref``, both (rows, width) in the stored dtype.  The
    tolerance of an element is one step of the stored dtype at the
    reference value, plus ``ATOL_SHARE`` of the largest base magnitude
    of its block (``x0`` is the flat base tensor, the rows start at
    element ``start``)."""
    r = ref.astype(jnp.float32)
    g = got.astype(jnp.float32)
    x = lax.dynamic_slice(x0, (start,), (rows * width,)).reshape(rows, width)
    atol = ATOL_SHARE * jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1,
                                keepdims=True)
    nmant = jnp.finfo(ref.dtype).nmant
    exp = (lax.bitcast_convert_type(jnp.abs(r), jnp.uint32) >> 23) & 0xFF
    step_exp = jnp.maximum(exp.astype(jnp.int32) - nmant, 1)
    step = lax.bitcast_convert_type(step_exp.astype(jnp.uint32) << 23,
                                    jnp.float32)
    return jnp.sum(r != g), jnp.sum(jnp.abs(g - r) > step + atol)


class Tally:
    """Running comparison of one merge against the reference."""

    def __init__(self):
        self.elements = 0
        self.mismatches = 0
        self.outside = 0

    def add(self, n: int, counts) -> None:
        mism, outside = counts
        self.elements += n
        self.mismatches += int(mism)
        self.outside += int(outside)

    def numbers(self) -> Dict[str, float]:
        return {"mismatch_share": self.mismatches / max(self.elements, 1),
                "outside_tol": self.outside}


def _rows(n_elems: int, w: int):
    """(start element, rows, width) pieces covering a flat tensor: chunks
    of whole blocks, then the ragged tail block."""
    full, tail = divmod(n_elems, w)
    for r0 in range(0, full, CHUNK_ROWS):
        yield r0 * w, min(CHUNK_ROWS, full - r0), w
    if tail:
        yield full * w, 1, tail


def merge_job(cfg: Dict, models, job: Dict, seed_theta: Dict, sel, thr,
              dtype: str = "float32", compare_to=None, tally=None,
              merged_is_reference: bool = True):
    """Run one job's merge over every tensor.  ``models`` are the fleet's
    flat device arrays (base first, then the job's experts).  With
    ``compare_to`` (``{tensor: flat host array}``) each block of rows is
    compared as it is made and counted into ``tally``, the merged rows
    standing for the reference unless ``merged_is_reference`` is false;
    otherwise the merged tensors are returned as flat device arrays."""
    w = block_elems(cfg)
    op = job["op"].lower()
    lam = float(seed_theta.get("lam", 1.0))
    density = float(seed_theta.get("density", 0.5))
    dseed = int(seed_theta.get("seed", 0))
    out = {}
    for i, (t, shape) in enumerate(fleet.inventory(cfg)):
        n = int(np.prod(shape))
        x0 = models[0][i]
        experts = tuple(m[i] for m in models[1:])
        k = len(experts)
        pieces = []
        for start, rows, width in _rows(n, w):
            b0 = start // w
            s = sel[t][b0:b0 + rows]
            th = (thr[t][b0:b0 + rows] if thr is not None
                  else np.zeros(s.shape, np.float32))
            masks = None
            if op == "dare":
                masks = np.stack([dare_masks(dseed, t, b0 + r, width, k,
                                             density) for r in range(rows)],
                                 axis=1)
            merged = merge_rows(x0, experts, s, th, masks, np.int32(start),
                                rows=rows, width=width, op=op, lam=lam,
                                density=density, dtype=dtype)
            if compare_to is None:
                pieces.append(merged.reshape(-1))
                continue
            other = np.reshape(compare_to[t][start:start + rows * width],
                               (rows, width))
            pair = (merged, other) if merged_is_reference else (other, merged)
            tally.add(rows * width, compare_rows(
                *pair, x0, np.int32(start), rows=rows, width=width))
        if compare_to is None:
            out[t] = jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]
    return out
