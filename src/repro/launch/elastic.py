"""Elastic scaling & straggler policy.

Design (1000+ node deployments):

* **Checkpoint-elastic resume.**  Checkpoints are mesh-agnostic (logical
  tensors, no device layout baked in — store/checkpoint.py), so a job
  that loses a pod restarts on ANY mesh whose axes divide the logical
  dims: the launcher re-resolves shardings against the new mesh and the
  first jitted step re-shards the restored state.  ``replan_mesh`` picks
  the largest valid (data, model) grid for the surviving chip count.

* **Straggler mitigation.**  The train loop stamps a per-step deadline
  (p99 of a rolling window × slack).  On real multi-host topologies the
  controller responds to repeated deadline misses from one host by
  (1) excluding it from the next mesh epoch and (2) triggering the
  checkpoint-elastic path above.  In this single-host container the
  deadline bookkeeping runs (TrainLoop.straggler_steps) and the remap is
  exercised by tests via ``replan_mesh``.

* **Failure domains.**  The pod axis is the failure domain: batch is
  sharded over ("pod", "data") so losing a pod halves global batch but
  never splits a model shard across a failure boundary (model axis stays
  inside one pod's ICI domain — DCI only carries data-parallel traffic).
"""
from __future__ import annotations

from typing import Optional, Tuple

from repro.launch.mesh import auto_mesh


def replan_mesh(
    n_chips: int,
    model_parallel: int = 16,
    want_pods: Optional[int] = None,
):
    """Largest valid mesh for a (possibly reduced) chip count.

    Keeps the model axis fixed (re-sharding weights across a different TP
    degree would change per-op layouts); absorbs chip loss on the
    data/pod axes.
    """
    if n_chips % model_parallel:
        raise ValueError(
            f"{n_chips} chips not divisible by model_parallel={model_parallel}"
        )
    data = n_chips // model_parallel
    if want_pods and want_pods > 1:
        if data % want_pods:
            raise ValueError(f"data axis {data} not divisible by {want_pods} pods")
        return auto_mesh(
            (want_pods, data // want_pods, model_parallel),
            ("pod", "data", "model"),
        )
    return auto_mesh((data, model_parallel), ("data", "model"))


def degraded_batch(global_batch: int, lost_fraction: float) -> int:
    """Keep per-chip batch constant when chips are lost (linear scaling
    rule); callers rescale LR accordingly."""
    b = int(global_batch * (1 - lost_fraction))
    return max(1, b)
