"""DeltaIterator — unified streaming access to heterogeneous experts (§5.2).

For tensor ``t``, ``InitDeltaIterator(t, π, M0, {Mi})`` builds an iterator
whose ``pull(b)`` returns exactly the selected expert contributions
{Δ_i} for block ``b`` — and performs expert I/O *iff* (i, t, b) is in the
plan's realized read set (budget soundness, §5.1).

Supported expert kinds (checkpoint meta ``kind``):
    full     — expert stores full weights;        Δ = expert_block - base_block
    delta    — expert stores task vectors;        Δ = expert_block
    adapter  — expert stores LoRA factors         Δ = scale · (B @ A), sliced
               ``<tensor>::lora_A`` (r, in) and    blockwise from the
               ``<tensor>::lora_B`` (out, r);      materialized product

Two ways to pull a block:

* ``pull`` returns the float32 deltas, formed on the host (the stream,
  batched and numpy-kernel paths, and shard workers);
* ``pull_stored`` returns the blocks as stored, with a flag per row that
  says whether the row holds full weights (its delta is the row less the
  base) or the delta itself — the operands of the device path
  (``kernels.ops.merge_blocks``), which widens them to float32 and forms
  the deltas on the device.  Both read the same experts in the same order
  and give the same float32 deltas.

Physical reads go through the coalescing path by default (adjacent
selected blocks become one sequential read — beyond-paper optimization;
set ``coalesce=False`` for the paper-faithful per-block I/O pattern).
Both paths move exactly the same expert bytes; only the syscall pattern
differs, so budget accounting is identical.

Two materialization modes:

* **lazy** (default) — the first ``pull`` reads the tensor's whole
  realized selection per expert (the stream/batched executor paths);
* **windowed** — the pipelined executor calls ``prefetch(blocks)`` ahead
  of compute (from its reader pool) and ``release_blocks(blocks)`` /
  ``release_adapters()`` behind it, so resident expert blocks stay
  bounded by the pipeline window instead of the tensor's full selection.
  ``pull`` then serves from the window cache only and performs **no I/O
  on the compute thread**.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import blocks as blk
from repro.core import trace
from repro.core.plan import MergePlan
from repro.store.tensorstore import ModelReader

#: sentinel returned by a source for an elided packed block: the delta is
#: exactly zero, synthesized with no expert I/O at all (the packed layout
#: stores such blocks as metadata-only entries — store/packed)
ELIDED = object()


class _ExpertTensorSource:
    """Per (expert, tensor) block source implementing the three kinds."""

    def __init__(
        self,
        reader: ModelReader,
        tensor_id: str,
        base_spec,
        selected: Sequence[int],
        block_size: int,
        coalesce: bool,
        windowed: bool = False,
        coalesce_gap: int = 0,
    ):
        self.reader = reader
        self.tensor_id = tensor_id
        self.base_spec = base_spec
        self.block_size = block_size
        self.kind = reader.meta.get("kind", "full")
        self.scale = float(reader.meta.get("scale", 1.0))
        self.selected = list(selected)
        self._selected_set = frozenset(self.selected)
        self.coalesce = coalesce
        self.coalesce_gap = coalesce_gap
        self.windowed = windowed
        # packed-layout readers mark (near-)zero-delta blocks as elided:
        # those selected blocks cost zero reads — pull() synthesizes them
        elided = getattr(reader, "elided_blocks", None)
        self._elided = (
            frozenset(elided(tensor_id)) & self._selected_set
            if elided is not None else frozenset()
        )
        self._read_list = [b for b in self.selected if b not in self._elided]
        self._cache: Dict[int, np.ndarray] = {}
        self._adapter_delta: Optional[np.ndarray] = None
        self._prefetched = False
        #: serializes adapter materialization when the pipelined engine
        #: stages several windows of this tensor concurrently (the block
        #: sets are disjoint, but the factor read must happen once)
        self._adapter_lock = threading.Lock()

    # ---------------------------------------------------------------- kinds
    def _prefetch_direct(self) -> None:
        """full/delta kinds: read the selected blocks (coalesced or not)."""
        if self.coalesce:
            self._cache = self.reader.read_blocks_coalesced(
                self.tensor_id, self._read_list, self.block_size, "expert",
                gap_bytes=self.coalesce_gap,
            )
        else:
            for b in self._read_list:
                self._cache[b] = self.reader.read_block(
                    self.tensor_id, b, self.block_size, "expert"
                )
        self._prefetched = True

    def _materialize_adapter(self) -> None:
        """adapter kind: Δ-tensor = scale · (B @ A); factors are tiny and
        read in full (counted as expert reads), then sliced blockwise."""
        a_name = f"{self.tensor_id}::lora_A"
        b_name = f"{self.tensor_id}::lora_B"
        A = self.reader.read_tensor(a_name, "expert")
        B = self.reader.read_tensor(b_name, "expert")
        delta = (
            np.asarray(B, dtype=np.float32) @ np.asarray(A, dtype=np.float32)
        ) * self.scale
        self._adapter_delta = delta.reshape(-1).astype(self.base_spec.dtype)
        self._prefetched = True

    # ------------------------------------------------- windowed prefetch
    def prefetch(self, blocks: Sequence[int]) -> int:
        """Read the plan-selected subset of ``blocks`` ahead of compute.

        Called from the pipelined executor's reader pool (never from the
        compute thread).  Returns the number of expert blocks now newly
        resident, so the engine can account in-flight memory.  Adapter
        experts materialize their (tiny-factor) Δ-tensor on first touch
        and count as one resident unit thereafter.
        """
        want = [
            b for b in blocks
            if b in self._selected_set
            and b not in self._elided  # elided: synthesized, never read
            and b not in self._cache
        ]
        if not want:
            return 0
        if self.kind == "adapter":
            with self._adapter_lock:
                if self._prefetched:
                    return 0
                self._materialize_adapter()
            return 1
        if self.coalesce:
            self._cache.update(
                self.reader.read_blocks_coalesced(
                    self.tensor_id, want, self.block_size, "expert",
                    gap_bytes=self.coalesce_gap,
                )
            )
        else:
            for b in want:
                self._cache[b] = self.reader.read_block(
                    self.tensor_id, b, self.block_size, "expert"
                )
        self._prefetched = True
        return len(want)

    def release_blocks(self, blocks: Sequence[int]) -> int:
        """Drop exactly these cached blocks (one retired window; windows
        are disjoint, so concurrent staging of other windows is unaffected).
        The adapter Δ-tensor is kept until the tensor finishes — it is
        materialized once per tensor and sliced by every window — and is
        retired via :meth:`release_adapter`."""
        if self.kind == "adapter":
            return 0
        n = 0
        for b in blocks:
            if self._cache.pop(b, None) is not None:
                n += 1
        return n

    def release_adapter(self) -> int:
        """Drop the materialized adapter Δ-tensor (tensor complete).
        Returns the resident units retired (matching what ``prefetch``
        charged), so the engine's residency gauge balances."""
        if self._adapter_delta is None:
            return 0
        self._adapter_delta = None
        return 1

    def resident_blocks(self) -> int:
        return len(self._cache) + (1 if self._adapter_delta is not None else 0)

    def has_tensor(self) -> bool:
        if self.kind == "adapter":
            return f"{self.tensor_id}::lora_A" in self.reader.specs
        return self.tensor_id in self.reader.specs

    def pull(self, block_idx: int) -> Optional[np.ndarray]:
        if block_idx not in self._selected_set:
            return None
        if block_idx in self._elided:
            return ELIDED  # zero delta, zero I/O — caller synthesizes
        if not self._prefetched:
            if self.windowed:
                raise RuntimeError(
                    f"windowed source for {self.tensor_id}: block {block_idx} "
                    f"pulled before prefetch (pipeline ordering bug)"
                )
            if self.kind == "adapter":
                self._materialize_adapter()
            else:
                self._prefetch_direct()
        if self.kind == "adapter":
            rng = blk.block_range(
                self.base_spec.nbytes, block_idx, self.block_size
            )
            itemsize = self.base_spec.dtype.itemsize
            lo = rng.offset // itemsize
            hi = rng.end // itemsize
            return self._adapter_delta[lo:hi]
        arr = self._cache.get(block_idx)
        if arr is None and self.windowed:
            raise RuntimeError(
                f"windowed source for {self.tensor_id}: selected block "
                f"{block_idx} not resident (released early or never prefetched)"
            )
        return arr


class DeltaIterator:
    """Algorithm 2's ``D`` for one tensor: pull(b) -> stacked Δ (K_sel, n)."""

    def __init__(
        self,
        tensor_id: str,
        plan: MergePlan,
        base_reader: ModelReader,
        expert_readers: Dict[str, ModelReader],
        coalesce: bool = True,
        windowed: bool = False,
        coalesce_gap: int = 0,
        read_from: int = 0,
    ):
        """``read_from`` restricts the realized read set to blocks at or
        above that index — the resume path: blocks below the journaled
        high-water mark are already staged, so their expert bytes must
        never be read (or charged) again."""
        self.tensor_id = tensor_id
        self.plan = plan
        self.base_spec = base_reader.spec(tensor_id)
        self.block_size = plan.block_size
        self._used_experts: List[str] = []
        self._sources: List[Tuple[int, str, _ExpertTensorSource]] = []
        for ei, e in enumerate(plan.expert_ids):
            sel = plan.blocks_for(e, tensor_id)
            if read_from > 0:
                sel = [b for b in sel if b >= read_from]
            if not sel:
                continue
            src = _ExpertTensorSource(
                expert_readers[e],
                tensor_id,
                self.base_spec,
                sel,
                self.block_size,
                coalesce,
                windowed=windowed,
                coalesce_gap=coalesce_gap,
            )
            if src.has_tensor():
                self._sources.append((ei, e, src))

    # ------------------------------------------------- windowed prefetch
    def prefetch_source(self, source_pos: int, blocks: Sequence[int]) -> int:
        """Prefetch one expert source's share of a window (the pipelined
        engine fans sources out over its reader pool as separate tasks)."""
        return self._sources[source_pos][2].prefetch(blocks)

    @property
    def n_sources(self) -> int:
        return len(self._sources)

    def release_blocks(self, blocks: Sequence[int]) -> int:
        """Retire a completed window: drop exactly its resident blocks."""
        return sum(src.release_blocks(blocks) for _, _, src in self._sources)

    def release_adapters(self) -> int:
        """Retire materialized adapter Δ-tensors (tensor complete)."""
        return sum(src.release_adapter() for _, _, src in self._sources)

    def resident_blocks(self) -> int:
        return sum(src.resident_blocks() for _, _, src in self._sources)

    def pull(
        self, block_idx: int, base_block: np.ndarray
    ) -> Tuple[np.ndarray, List[int], List[str]]:
        """Returns (stacked deltas (K_sel, n) float32, expert indexes,
        expert ids).  Performs expert I/O iff the plan selected the block."""
        t0 = trace.now()
        deltas: List[np.ndarray] = []
        idxs: List[int] = []
        ids: List[str] = []
        base_f = None
        for ei, e, src in self._sources:
            x = src.pull(block_idx)
            if x is None:
                continue
            if x is ELIDED:
                # packed-layout elision: the stored block equals the base
                # (full kind) or zero (delta kind) bit-exactly, so its
                # delta row is exactly what the flat path would compute —
                # all zeros — at zero expert I/O.
                deltas.append(np.zeros(base_block.size, dtype=np.float32))
                idxs.append(ei)
                ids.append(e)
                continue
            xf = np.asarray(x, dtype=np.float32)
            if src.kind == "full":
                if base_f is None:
                    base_f = np.asarray(base_block, dtype=np.float32)
                xf = xf - base_f
            deltas.append(xf)
            idxs.append(ei)
            ids.append(e)
        self._used_experts = ids
        stacked = (np.stack(deltas) if deltas
                   else np.zeros((0, base_block.size), dtype=np.float32))
        trace.count("pull_ns", trace.now() - t0)
        return stacked, idxs, ids

    def pull_stored(
        self, block_idx: int
    ) -> Tuple[List[Optional[np.ndarray]], List[bool], List[int], List[str]]:
        """The selected experts' blocks as stored, for the device path:
        (rows, full, expert indexes, expert ids) in ``pull``'s order.  A
        full-kind row is the expert's weights (``full`` True); a delta
        row, and an adapter row in the base's dtype, is the delta itself;
        an elided packed row is None, a delta of exactly zero.  Performs
        the same expert I/O as ``pull``."""
        t0 = trace.now()
        rows: List[Optional[np.ndarray]] = []
        full: List[bool] = []
        idxs: List[int] = []
        ids: List[str] = []
        for ei, e, src in self._sources:
            x = src.pull(block_idx)
            if x is None:
                continue
            elided = x is ELIDED
            rows.append(None if elided else x)
            full.append(not elided and src.kind == "full")
            idxs.append(ei)
            ids.append(e)
        self._used_experts = ids
        trace.count("pull_ns", trace.now() - t0)
        return rows, full, idxs, ids

    def used_experts(self) -> List[str]:
        """Experts that contributed to the most recent block (coverage)."""
        return self._used_experts
