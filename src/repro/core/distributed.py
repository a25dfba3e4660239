"""Sharded merge execution — MergePipe across a TPU mesh (beyond-paper).

The paper executes merges on a single host.  At pod scale the same plan
can be *partitioned*: the block space is range-sharded across devices, and
each device merges only its shard.  Merging is embarrassingly parallel
over blocks, so the lowered HLO contains **zero collectives** in the
steady state — verified by the dry-run (EXPERIMENTS.md §Dry-run) — and
per-host expert I/O is bounded by ``B / n_hosts``.

Layout: model parameters are flattened, padded, and viewed as a block
matrix ``(NB, W)`` with ``W = block_size / itemsize`` elements per block
(the stored dtype's itemsize; the math runs in float32).
The plan's selection becomes a dense ``(K, NB)`` mask that gates expert
deltas; zeroed (unselected) deltas are mathematically inert for every
operator (TA/DARE: zero contribution; AVG: per-block count divisor;
TIES: zero rows can never win the sign election) so the sharded result
matches the streaming executor block-for-block.

``build_merge_step`` returns a jit-compiled function with explicit
in/out shardings over the production mesh — the same artifact the
roofline analysis lowers.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.plan import MergePlan
from repro.kernels import ref as kref


# ----------------------------------------------------------- param packing
def pack_arrays(
    arrays: Dict[str, np.ndarray], block_elems: int
) -> Tuple[np.ndarray, List[Tuple[str, Tuple[int, ...], int, int]]]:
    """Flatten float tensors into a padded (NB, W) block matrix.

    Each tensor is padded *individually* to a block multiple, so packed
    blocks map 1:1 onto the per-tensor block grid used by plans (exact
    selection, no boundary straddling).  Returns (blocks, meta) with
    meta = [(name, shape, size, block_offset)].  Non-float tensors are
    excluded (they pass through unmerged).

    Tail-block note: the last block of a ragged tensor carries zero
    padding; for TIES the trim count is computed over the padded width,
    which can deviate from the streaming engine on that one block per
    tensor (bounded, measured in tests; <1e-4 of params at LLM scale).
    """
    metas: List[Tuple[str, Tuple[int, ...], int, int]] = []
    chunks: List[np.ndarray] = []
    block_off = 0
    for name in sorted(arrays):
        a = arrays[name]
        if not np.issubdtype(np.asarray(a).dtype, np.floating):
            continue
        flat = np.asarray(a, np.float32).reshape(-1)
        pad = (-flat.size) % block_elems
        padded = np.pad(flat, (0, pad))
        chunks.append(padded)
        metas.append((name, tuple(a.shape), flat.size, block_off))
        block_off += padded.size // block_elems
    if not chunks:
        return np.zeros((0, block_elems), np.float32), metas
    return np.concatenate(chunks).reshape(-1, block_elems), metas


def unpack_arrays(
    blocks: np.ndarray, metas: List[Tuple[str, Tuple[int, ...], int, int]]
) -> Dict[str, np.ndarray]:
    flat = np.asarray(blocks)
    w = flat.shape[1]
    flat = flat.reshape(-1)
    out: Dict[str, np.ndarray] = {}
    for name, shape, size, block_off in metas:
        lo = block_off * w
        out[name] = flat[lo : lo + size].reshape(shape)
    return out


def selection_mask(
    plan: MergePlan,
    metas: List[Tuple[str, Tuple[int, ...], int, int]],
    block_elems: int,
    n_blocks: int,
) -> np.ndarray:
    """Dense (K, NB) mask over the packed block space from plan.selection.

    With per-tensor aligned packing, per-tensor block ``tb`` of tensor
    ``t`` is exactly packed block ``block_offset(t) + tb`` — selection is
    exact, and budget accounting matches the plan."""
    sel = np.zeros((len(plan.expert_ids), n_blocks), dtype=bool)
    offsets = {name: block_off for name, _s, _n, block_off in metas}
    for ei, e in enumerate(plan.expert_ids):
        for tensor_id, t_blocks in plan.selection.get(e, {}).items():
            if tensor_id not in offsets:
                continue
            base = offsets[tensor_id]
            for tb in t_blocks:
                sel[ei, base + tb] = True
    return sel


def dare_masks_packed(
    plan: MergePlan,
    metas: List[Tuple[str, Tuple[int, ...], int, int]],
    block_elems: int,
    n_blocks: int,
) -> np.ndarray:
    """(K, NB, W) keep-masks matching the streaming engine's Philox masks.

    The Philox stream has the prefix property (first n draws are identical
    regardless of how many are requested), so padded-width masks agree
    with the streaming engine on every real element."""
    from repro.core.operators import dare_mask

    seed = int(plan.theta.get("seed", 0))
    density = float(plan.theta.get("density", 0.5))
    offsets = {name: block_off for name, _s, _n, block_off in metas}
    masks = np.zeros((len(plan.expert_ids), n_blocks, block_elems), dtype=bool)
    for ei, e in enumerate(plan.expert_ids):
        for tensor_id, t_blocks in plan.selection.get(e, {}).items():
            if tensor_id not in offsets:
                continue
            base = offsets[tensor_id]
            for tb in t_blocks:
                masks[ei, base + tb] = dare_mask(
                    seed, ei, tensor_id, tb, block_elems, density
                )
    return masks


# ----------------------------------------------------------- sharded step
def _merge_blocks_masked(
    base: jnp.ndarray,      # (NB, W)
    experts: jnp.ndarray,   # (K, NB, W)  deltas (kind="delta") or weights
    select: jnp.ndarray,    # (K, NB) bool
    op: str,
    theta: Dict,
    kind: str,
    dare_masks: Optional[jnp.ndarray],
) -> jnp.ndarray:
    D = experts - base[None] if kind == "full" else experts
    D = D * select[:, :, None]
    Dt = jnp.transpose(D, (1, 0, 2))  # (NB, K, W)
    lam = float(theta.get("lam", 1.0))
    if op == "avg":
        k_sel = jnp.sum(select, axis=0)  # (NB,)
        return base + jnp.sum(Dt, axis=1) / (k_sel + 1.0)[:, None]
    if op == "ta":
        return kref.ta_ref(base, Dt, lam)
    if op == "ties":
        thresh = kref.ties_thresholds(Dt, float(theta.get("trim_frac", 0.2)))
        return kref.ties_apply_ref(base, Dt, thresh, lam)
    if op == "dare":
        if dare_masks is None:
            raise ValueError("dare requires masks")
        Mt = jnp.transpose(dare_masks, (1, 0, 2))  # (K, NB, W) -> (NB, K, W)
        return kref.dare_ref(
            base, Dt, Mt, float(theta.get("density", 0.5)), lam
        )
    raise KeyError(op)


def build_merge_step(
    mesh: Mesh,
    op: str,
    theta: Dict,
    kind: str = "delta",
    donate: bool = True,
):
    """jit-compiled sharded merge step over the full mesh.

    Block axis (NB) is sharded across *all* mesh axes; W is replicated
    within a block.  in_shardings are explicit so .lower()/.compile()
    reflects the production layout (dry-run artifact).
    """
    axes = tuple(mesh.axis_names)
    block_sharding = NamedSharding(mesh, P(axes))          # (NB, W) on axis 0
    expert_sharding = NamedSharding(mesh, P(None, axes))   # (K, NB, W) axis 1
    sel_sharding = NamedSharding(mesh, P(None, axes))      # (K, NB)

    is_dare = op == "dare"

    def step(base, experts, select, dare_masks=None):
        return _merge_blocks_masked(
            base, experts, select, op, theta, kind, dare_masks
        )

    in_shardings = [block_sharding, expert_sharding, sel_sharding]
    if is_dare:
        in_shardings.append(expert_sharding)

    return jax.jit(
        step,
        in_shardings=tuple(in_shardings),
        out_shardings=block_sharding,
        donate_argnums=(0,) if donate else (),
    )


def shard_plan_by_host(
    plan: MergePlan, n_hosts: int, catalog=None
) -> List[Dict]:
    """Partition a plan's selected (expert, tensor, block) triples across
    hosts so each host reads <= ceil(Ĉ_expert / n_hosts) bytes (per-host
    budget).  Deterministic greedy (LPT) over size-sorted units.

    With ``catalog`` the cost model matches the planner's marginal-byte
    accounting (``planner._selection_bytes``): ragged tail blocks are
    billed at their physical size, elided packed blocks at zero, and the
    triples that share one packed extent form a single atomic unit so
    the shared extent is charged — and read — once per host.  Without a
    catalog every block falls back to the legacy ``plan.block_size``
    estimate (an upper bound that overcounts tails and dedup)."""
    # unit = [(bytes, expert, tensor, blk), ...] scheduled atomically;
    # multi-item units are the triples sharing one packed extent
    units: List[List[Tuple[int, str, str, int]]] = []
    if catalog is not None:
        from repro.core.planner import _selection_bytes

        costs = _selection_bytes(catalog, plan, {})
        by_extent: Dict[str, List[Tuple[int, str, str, int]]] = {}
        for e, per_t in plan.selection.items():
            for t, bs in per_t.items():
                for b in bs:
                    nbytes, extent_key = costs.get(
                        (e, t, b), (plan.block_size, None))
                    if extent_key is None:
                        units.append([(nbytes, e, t, b)])
                    else:
                        by_extent.setdefault(extent_key, []).append(
                            (nbytes, e, t, b))
        for key in sorted(by_extent):
            grp = sorted(by_extent[key], key=lambda it: (it[1], it[2], it[3]))
            # the extent moves once per host no matter how many triples
            # it serves: bill its physical size on the first item only
            units.append([grp[0]] + [(0, e, t, b) for _n, e, t, b in grp[1:]])
    else:
        for e, per_t in plan.selection.items():
            for t, bs in per_t.items():
                for b in bs:
                    units.append([(plan.block_size, e, t, b)])
    units.sort(
        key=lambda u: (-sum(it[0] for it in u), u[0][1], u[0][2], u[0][3])
    )
    buckets: List[Dict] = [
        {"host": h, "bytes": 0, "items": []} for h in range(n_hosts)
    ]
    for unit in units:
        tgt = min(buckets, key=lambda bkt: (bkt["bytes"], bkt["host"]))
        for nbytes, e, t, b in unit:
            tgt["items"].append((e, t, b))
            tgt["bytes"] += nbytes
    return buckets
