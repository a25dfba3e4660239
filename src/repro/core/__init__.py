"""MergePipe core — the paper's contribution as a composable library.

Layers (paper section in parens):
    blocks          Partition(T; s), block ids                 (§2.2, §3.3)
    catalog         BlockMeta/TouchMap/Coverage/Plan/Manifest  (§2.2, T.1)
    sketch          ANALYZE block statistics                   (§2.3)
    cost            C_merge decomposition + budget objective   (§3)
    plan, planner   MergePlan π, greedy budget-aware PlanGen   (§4, Alg.1)
    delta_iterator  unified full/delta/adapter streaming       (§5.2)
    operators       AVG / TA / TIES / DARE registry            (§4.1)
    executor        ExecuteMerge streaming engine              (§5, Alg.2)
    trace           spans and counters of a merge job
    transactions    staging + atomic publish + recovery        (§5.3)
    lineage         explain / audit / verify                   (§2.2)
    naive           stateless O(K) baseline pipeline           (§6.1)
    api             MergePipe facade (legacy v1 shim)
    distributed     shard_map sharded merge (beyond-paper)

The declarative v2 surface (typed budgets, composable merge graphs,
batched multi-merge sessions with cross-job shared expert reads) lives
in :mod:`repro.api`; the v1 facade delegates to it.
"""
from repro.core.blocks import DEFAULT_BLOCK_SIZE, BlockId

__all__ = [
    "MergePipe",
    "MergePlan",
    "MergeResult",
    "BlockId",
    "DEFAULT_BLOCK_SIZE",
    "plan_merge",
    "execute_merge",
    "naive_merge",
]

# Lazy exports: the storage layer imports repro.core.blocks, and the rest
# of core imports the storage layer — eager re-exports here would close an
# import cycle, so resolve the facade symbols on first attribute access.
_LAZY = {
    "MergePipe": ("repro.core.api", "MergePipe"),
    "MergePlan": ("repro.core.plan", "MergePlan"),
    "MergeResult": ("repro.core.executor", "MergeResult"),
    "plan_merge": ("repro.core.planner", "plan_merge"),
    "execute_merge": ("repro.core.executor", "execute_merge"),
    "naive_merge": ("repro.core.naive", "naive_merge"),
}


def __getattr__(name: str):
    if name in _LAZY:
        import importlib

        module, attr = _LAZY[name]
        return getattr(importlib.import_module(module), attr)
    raise AttributeError(f"module 'repro.core' has no attribute {name!r}")
