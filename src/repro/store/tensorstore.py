"""File-backed block-granular tensor storage.

Physical layout of one stored model (base, expert, or merged snapshot):

    <root>/<model_id>/
        MODEL.json            # tensor specs: name -> {shape, dtype, file, nbytes}
        tensors/00000.bin     # raw little-endian row-major bytes, one per tensor

Blocks are *logical* views over the flat tensor bytes (core.blocks); reads
use positional ``os.pread`` on a per-tensor file descriptor so expert
access is genuinely partial — reading 3 of 40 blocks of a tensor moves
only those bytes — and **concurrent readers never race**: ``pread`` takes
an explicit offset and does not touch the shared file position, so the
pipelined executor's prefetch pool (and v2 batch sessions sharing a
``CachingModelReader``) can read the same tensor from many threads.
A reader keeps at most :data:`MAX_OPEN_TENSOR_FILES` tensor files open,
least recently used closed first, so a merge of a many-tensor
checkpoint (a mixture of experts holds hundreds of tensors per layer)
holds O(models) descriptors, not O(models x tensors).
Every physical read/write is tagged into :mod:`repro.store.iostats` with
the paper's cost category.
"""
from __future__ import annotations

import collections
import hashlib
import json
import os
import threading
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core import blocks as blk
from repro.core import trace
from repro.store import dtypes
from repro.store.iostats import GLOBAL_STATS, IOStats

MODEL_MANIFEST = "MODEL.json"
TENSOR_DIR = "tensors"
#: presence of this stub (instead of MODEL.json) marks a model whose
#: bytes live in a remote object store (see repro.store.remote)
REMOTE_STUB = "REMOTE.json"
#: tensor files one ModelReader keeps open; more may be open for a moment
#: while preads pin them.  Windows stage in tensor order and at most a few
#: tensors at once (``PipelineConfig.prefetch_windows`` + 1 on the read
#: pool), so a merge opens each tensor file about once per model.
MAX_OPEN_TENSOR_FILES = 8


def _hash_bytes(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=16).hexdigest()


class TensorSpec(dict):
    """Lightweight spec record: shape, dtype name, file, nbytes."""

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self["shape"])

    @property
    def dtype(self) -> np.dtype:
        return dtypes.to_np_dtype(self["dtype"])

    @property
    def nbytes(self) -> int:
        return int(self["nbytes"])

    @property
    def file(self) -> str:
        return self["file"]


class BlockReaderMixin:
    """Block-granular read surface over any ``read_range`` provider.

    Everything here is derived purely from ``self.specs`` (a
    ``{tensor_id: TensorSpec}`` map) plus the subclass's ``read_range`` —
    the local :class:`ModelReader` and the remote-backed
    :class:`repro.store.tiered.TieredReader` share it, so the executor,
    delta iterator, and block cache see one reader interface regardless
    of which storage backend serves the bytes.
    """

    specs: Dict[str, "TensorSpec"]

    #: verify-on-read hook (repro.store.integrity.BlockVerifier or None).
    #: When attached, every derived block read is checked against the
    #: cataloged block hash before the bytes reach compute — duck-typed
    #: so this module stays import-free of the integrity layer.
    verifier = None

    # -- structure -------------------------------------------------------
    def tensor_names(self) -> List[str]:
        return list(self.specs.keys())

    def spec(self, tensor_id: str) -> "TensorSpec":
        return self.specs[tensor_id]

    def total_nbytes(self) -> int:
        return sum(s.nbytes for s in self.specs.values())

    def num_blocks(self, tensor_id: str, block_size: int) -> int:
        return blk.num_blocks(self.specs[tensor_id].nbytes, block_size)

    # -- derived reads ---------------------------------------------------
    def read_block(
        self, tensor_id: str, block_idx: int, block_size: int, category: str
    ) -> np.ndarray:
        spec = self.specs[tensor_id]
        rng = blk.block_range(spec.nbytes, block_idx, block_size)
        t0 = trace.now()
        data = self.read_range(tensor_id, rng.offset, rng.nbytes, category)
        t1 = trace.now()
        trace.count("read_ns." + category, t1 - t0)
        trace.count("read_bytes." + category, rng.nbytes)
        v = self.verifier
        if v is not None and block_size == v.block_size:
            data = v.check(
                self, tensor_id, block_idx, rng.offset, rng.nbytes, data,
                category,
            )
            trace.count("verify_ns", trace.now() - t1)
            trace.count("verify_bytes", rng.nbytes)
        return np.frombuffer(data, dtype=spec.dtype)

    def read_blocks_coalesced(
        self,
        tensor_id: str,
        block_idxs: Sequence[int],
        block_size: int,
        category: str,
        gap_bytes: int = 0,
    ) -> Dict[int, np.ndarray]:
        """Read a set of blocks with adjacent ranges coalesced into large
        sequential reads (beyond-paper batched streaming; planning remains
        block-granular, physical I/O becomes run-granular).

        ``gap_bytes`` tolerates up to that many unrequested bytes between
        two selected ranges before splitting the run (one larger
        sequential read instead of two round trips — pays off on
        high-latency shared storage).  Gap bytes are tagged ``other``,
        never ``category``, so budgeted categories count exactly the
        requested payload.

        Runs and ranges are both offset-sorted, so slicing runs back into
        blocks is a single linear sweep — O(R) total over R requested
        blocks, not O(R²) (one rescan of every range per run).
        """
        spec = self.specs[tensor_id]
        ranges = sorted(
            (blk.block_range(spec.nbytes, i, block_size) for i in block_idxs),
            key=lambda r: r.offset,
        )
        out: Dict[int, np.ndarray] = {}
        ri = 0
        v = self.verifier
        verifying = v is not None and block_size == v.block_size
        # summed over the runs, counted once per call: not per block
        read_ns = read_bytes = verify_ns = 0
        for offset, nbytes in blk.coalesce_ranges(ranges, gap=gap_bytes):
            end = offset + nbytes
            run_ranges = []
            payload = 0
            while ri < len(ranges) and ranges[ri].end <= end:
                run_ranges.append(ranges[ri])
                payload += ranges[ri].nbytes
                ri += 1
            waste = max(0, nbytes - payload)
            # pass waste only when present: gap=0 keeps the historical
            # 4-arg call shape (tests/benches wrap read_range to emulate
            # storage profiles and must see an unchanged surface)
            t0 = trace.now()
            data = (
                self.read_range(tensor_id, offset, nbytes, category)
                if waste == 0
                else self.read_range(
                    tensor_id, offset, nbytes, category, waste_nbytes=waste
                )
            )
            t1 = trace.now()
            read_ns += t1 - t0
            read_bytes += nbytes
            for r in run_ranges:
                lo = r.offset - offset
                chunk = data[lo : lo + r.nbytes]
                if verifying:
                    # verified per logical block, not per physical run:
                    # the contract hashes live on the block grid, and a
                    # repair refetches only the corrupt block's range
                    chunk = v.check(
                        self, tensor_id, r.block_idx, r.offset, r.nbytes,
                        chunk, category,
                    )
                out[r.block_idx] = np.frombuffer(chunk, dtype=spec.dtype)
            verify_ns += trace.now() - t1
        if read_bytes:
            trace.count("read_ns." + category, read_ns)
            trace.count("read_bytes." + category, read_bytes)
        if verifying and read_bytes:
            trace.count("verify_ns", verify_ns)
            trace.count("verify_bytes", sum(r.nbytes for r in ranges))
        return out

    def read_tensor(self, tensor_id: str, category: str) -> np.ndarray:
        spec = self.specs[tensor_id]
        t0 = trace.now()
        data = self.read_range(tensor_id, 0, spec.nbytes, category)
        trace.count("read_ns." + category, trace.now() - t0)
        trace.count("read_bytes." + category, spec.nbytes)
        return np.frombuffer(data, dtype=spec.dtype).reshape(spec.shape)

    def close(self) -> None:  # pragma: no cover — overridden where needed
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class ModelReader(BlockReaderMixin):
    """Read-only, block-granular view over one stored model."""

    def __init__(self, root: str, model_id: str, stats: IOStats):
        self.root = root
        self.model_id = model_id
        self.stats = stats
        self.dir = os.path.join(root, model_id)
        manifest_path = os.path.join(self.dir, MODEL_MANIFEST)
        with open(manifest_path, "rb") as f:
            raw = f.read()
        stats.record_read("meta", len(raw))
        doc = json.loads(raw)
        self.meta: Dict = doc.get("meta", {})
        self.specs: Dict[str, TensorSpec] = {
            name: TensorSpec(spec) for name, spec in doc["tensors"].items()
        }
        #: tensor -> [fd, preads using it], least recently used first
        self._fds = collections.OrderedDict()  # guarded-by: _fd_lock
        self._fd_lock = threading.Lock()

    # -- physical reads ----------------------------------------------------
    def _pin(self, tensor_id: str) -> List[int]:
        """The tensor's open ``[fd, pins]``, pinned against closing until
        :meth:`_unpin`: a descriptor closed under a running ``pread`` could
        be reused by the next open and serve another tensor's bytes."""
        with self._fd_lock:
            ent = self._fds.get(tensor_id)
            if ent is None:
                path = os.path.join(self.dir, self.specs[tensor_id].file)
                ent = self._fds[tensor_id] = [os.open(path, os.O_RDONLY), 0]
                trace.count("fd_opens", 1)
            else:
                self._fds.move_to_end(tensor_id)
            ent[1] += 1
            self._close_idle()
            return ent

    def _unpin(self, ent: List[int]) -> None:
        with self._fd_lock:
            ent[1] -= 1
            self._close_idle()

    def _close_idle(self) -> None:  # unguarded-ok: caller holds _fd_lock
        """Close least recently used unpinned files beyond the cap."""
        excess = len(self._fds) - MAX_OPEN_TENSOR_FILES
        if excess <= 0:
            return
        for tensor_id, (fd, pins) in list(self._fds.items()):
            if not pins:
                del self._fds[tensor_id]
                os.close(fd)
                excess -= 1
                if not excess:
                    return

    def read_range(
        self,
        tensor_id: str,
        offset: int,
        nbytes: int,
        category: str,
        waste_nbytes: int = 0,
    ) -> bytes:
        """Positional read — safe under arbitrary thread concurrency
        (``pread`` never moves a shared file offset).

        ``waste_nbytes`` marks bytes inside the range that no caller
        requested (gap-tolerant coalescing reads them to save a round
        trip); they are tagged ``other`` instead of ``category`` so
        budget categories count payload bytes only while total physical
        volume stays fully accounted.
        """
        ent = self._pin(tensor_id)
        chunks = []
        got = 0
        try:
            while got < nbytes:  # pread may return short on signals / EOF
                chunk = os.pread(ent[0], nbytes - got, offset + got)
                if not chunk:
                    break
                chunks.append(chunk)
                got += len(chunk)
        finally:
            self._unpin(ent)
        data = chunks[0] if len(chunks) == 1 else b"".join(chunks)
        if len(data) != nbytes:
            raise IOError(
                f"short read on {self.model_id}/{tensor_id} "
                f"[{offset}:{offset+nbytes}]: got {len(data)}"
            )
        self.stats.record_read(category, nbytes - waste_nbytes)
        if waste_nbytes:
            self.stats.record_read("other", waste_nbytes)
        return data

    def close(self) -> None:
        with self._fd_lock:
            for fd, _ in self._fds.values():
                os.close(fd)
            self._fds.clear()


class CheckpointStore:
    """Directory of stored models with tagged-I/O read/write access."""

    def __init__(self, root: str, stats: Optional[IOStats] = None):
        self.root = root
        self.stats = stats or GLOBAL_STATS
        os.makedirs(root, exist_ok=True)
        #: callables ``model_id -> List[str]`` naming live references that
        #: make deletion unsafe (catalog lineage, packed layouts, ...).
        #: Wired by MergePipe/Session; a bare store has no guards.
        self._delete_guards: List = []
        #: shared local-disk extent cache for remote-backed models
        #: (repro.store.tiered.DiskExtentCache); wired by SnapshotStore so
        #: every tenant on the box shares one warm tier.  None => tiered
        #: readers skip the disk tier and fetch straight from remote.
        self.disk_cache = None
        # one RemoteObjectStore per remote root, shared across readers so
        # fault-injection / request counters are coherent per endpoint
        self._remote_stores: Dict[str, object] = {}
        self._remote_lock = threading.Lock()

    def add_delete_guard(self, guard) -> None:
        """Register a referential-integrity check consulted by
        :meth:`delete_model` (``guard(model_id) -> List[str]`` of
        human-readable references; empty list = safe to delete)."""
        self._delete_guards.append(guard)

    # -- write -------------------------------------------------------------
    def write_model(
        self,
        model_id: str,
        tensors: Mapping[str, np.ndarray],
        meta: Optional[Dict] = None,
        category: str = "out",
        fsync: bool = False,
    ) -> str:
        """Materialize a full model. Returns the model directory."""
        mdir = os.path.join(self.root, model_id)
        tdir = os.path.join(mdir, TENSOR_DIR)
        os.makedirs(tdir, exist_ok=True)
        specs: Dict[str, Dict] = {}
        for idx, (name, arr) in enumerate(tensors.items()):
            arr = np.ascontiguousarray(arr)
            fname = os.path.join(TENSOR_DIR, f"{idx:05d}.bin")
            raw = arr.tobytes()
            with open(os.path.join(mdir, fname), "wb") as f:
                f.write(raw)
                if fsync:
                    f.flush()
                    os.fsync(f.fileno())
            self.stats.record_write(category, len(raw))
            specs[name] = {
                "shape": list(arr.shape),
                "dtype": dtypes.dtype_name(arr.dtype),
                "file": fname,
                "nbytes": len(raw),
                "hash": _hash_bytes(raw),
            }
        doc = {"model_id": model_id, "meta": meta or {}, "tensors": specs}
        raw_manifest = json.dumps(doc, indent=1).encode()
        tmp = os.path.join(mdir, MODEL_MANIFEST + ".tmp")
        with open(tmp, "wb") as f:
            f.write(raw_manifest)
            if fsync:
                f.flush()
                os.fsync(f.fileno())
        # chaos-ok: input-model ingest, upstream of merge transactions —
        # crash points cover the merge publish/journal edges only
        os.replace(tmp, os.path.join(mdir, MODEL_MANIFEST))
        self.stats.record_write("meta", len(raw_manifest))
        return mdir

    # -- remote-backed models (repro.store.remote / .tiered) -----------------
    def register_remote(
        self,
        model_id: str,
        remote_root: str,
        profile: Optional[Dict] = None,
        disk_cache: bool = True,
    ) -> str:
        """Register a model whose bytes live in a remote object store.

        Writes a ``REMOTE.json`` stub in place of a local ``MODEL.json``;
        ``open_model`` then returns a :class:`repro.store.tiered.
        TieredReader` serving reads RAM -> disk cache -> remote.
        ``disk_cache=False`` opts this model out of the shared disk tier
        (every miss pays the remote round trip — benchmark baseline).
        """
        if self.exists(model_id):
            raise ValueError(f"model {model_id!r} already registered")
        # validate now, not at first read: a typo'd id would otherwise
        # plant a stub that only fails deep inside a merge (HEAD is a
        # cheap control-plane request, never fault-injected)
        self.remote_store(remote_root).head(f"{model_id}/{MODEL_MANIFEST}")
        mdir = os.path.join(self.root, model_id)
        os.makedirs(mdir, exist_ok=True)
        stub = {
            "model_id": model_id,
            "remote_root": os.path.abspath(remote_root),
            "profile": dict(profile or {}),
            "disk_cache": bool(disk_cache),
        }
        raw = json.dumps(stub, indent=1).encode()
        tmp = os.path.join(mdir, REMOTE_STUB + ".tmp")
        with open(tmp, "wb") as f:
            f.write(raw)
            f.flush()
            os.fsync(f.fileno())
        # chaos-ok: model registration at ingest, upstream of merge
        # transactions; a crash here is re-run by the operator
        os.replace(tmp, os.path.join(mdir, REMOTE_STUB))
        self.stats.record_write("meta", len(raw))
        return mdir

    def is_remote(self, model_id: str) -> bool:
        return not os.path.exists(
            os.path.join(self.root, model_id, MODEL_MANIFEST)
        ) and os.path.exists(os.path.join(self.root, model_id, REMOTE_STUB))

    def remote_stub(self, model_id: str) -> Dict:
        path = os.path.join(self.root, model_id, REMOTE_STUB)
        with open(path, "rb") as f:
            raw = f.read()
        self.stats.record_read("meta", len(raw))
        return json.loads(raw)

    def remote_store(self, remote_root: str):
        """Shared :class:`repro.store.remote.RemoteObjectStore` per remote
        root (so request/fault counters are per-endpoint, not per-reader)."""
        from repro.store.remote import RemoteObjectStore

        key = os.path.abspath(remote_root)
        with self._remote_lock:
            store = self._remote_stores.get(key)
            if store is None:
                store = RemoteObjectStore(key)
                self._remote_stores[key] = store
            return store

    def publish_remote(
        self,
        model_id: str,
        remote_root: str,
        profile: Optional[Dict] = None,
        keep_local: bool = False,
        disk_cache: bool = True,
    ) -> str:
        """Upload a locally stored model to a remote object store and
        replace its local copy with a ``REMOTE.json`` stub (unless
        ``keep_local``).  Subsequent reads go through the tiered path."""
        from repro.store.remote import publish_model

        if not os.path.exists(os.path.join(self.root, model_id, MODEL_MANIFEST)):
            raise ValueError(f"model {model_id!r} has no local copy to publish")
        remote = self.remote_store(remote_root)
        publish_model(self, model_id, remote)
        if not keep_local:
            import shutil

            mdir = os.path.join(self.root, model_id)
            shutil.rmtree(os.path.join(mdir, TENSOR_DIR), ignore_errors=True)
            os.remove(os.path.join(mdir, MODEL_MANIFEST))
            stub = {
                "model_id": model_id,
                "remote_root": os.path.abspath(remote_root),
                "profile": dict(profile or {}),
                "disk_cache": bool(disk_cache),
            }
            raw = json.dumps(stub, indent=1).encode()
            tmp = os.path.join(mdir, REMOTE_STUB + ".tmp")
            # the local tensors are already gone at this point: a torn
            # stub after a crash would orphan the model, so the stub
            # must be durable before it becomes visible (mergelint:
            # durability caught the missing fsync here)
            with open(tmp, "wb") as f:
                f.write(raw)
                f.flush()
                os.fsync(f.fileno())
            # chaos-ok: operator-driven republish, upstream of merge
            # transactions — re-run publish_remote after a crash
            os.replace(tmp, os.path.join(mdir, REMOTE_STUB))
            self.stats.record_write("meta", len(raw))
        return remote.root

    # -- read ----------------------------------------------------------------
    def open_model(self, model_id: str):
        if os.path.exists(os.path.join(self.root, model_id, MODEL_MANIFEST)):
            return ModelReader(self.root, model_id, self.stats)
        if self.is_remote(model_id):
            from repro.store.tiered import open_tiered_reader

            return open_tiered_reader(self, model_id)
        # fall through to ModelReader's "no such manifest" error
        return ModelReader(self.root, model_id, self.stats)

    def exists(self, model_id: str) -> bool:
        mdir = os.path.join(self.root, model_id)
        return os.path.exists(os.path.join(mdir, MODEL_MANIFEST)) or os.path.exists(
            os.path.join(mdir, REMOTE_STUB)
        )

    def list_models(self) -> List[str]:
        if not os.path.isdir(self.root):
            return []
        return sorted(
            d
            for d in os.listdir(self.root)
            if os.path.exists(os.path.join(self.root, d, MODEL_MANIFEST))
            or os.path.exists(os.path.join(self.root, d, REMOTE_STUB))
        )

    def delete_model(self, model_id: str, force: bool = False) -> None:
        """Delete a stored model, refusing while anything still references
        it (snapshot lineage, merge-graph edges, packed layouts that
        synthesize or attribute blocks from it) — deleting such a model
        would silently corrupt committed snapshots' audit trail or packed
        reads.  ``force=True`` is the explicit escape hatch.
        """
        import shutil

        if not force:
            refs = [r for g in self._delete_guards for r in g(model_id)]
            if refs:
                raise ValueError(
                    f"refusing to delete model {model_id!r}: still "
                    f"referenced by {refs} (pass force=True / --force to "
                    f"delete anyway)"
                )
        mdir = os.path.join(self.root, model_id)
        if os.path.isdir(mdir):
            shutil.rmtree(mdir)


def load_model_arrays(
    store: CheckpointStore, model_id: str, category: str = "base"
) -> Dict[str, np.ndarray]:
    """Convenience full load (used by tests / naive baseline)."""
    with store.open_model(model_id) as reader:
        return {t: reader.read_tensor(t, category) for t in reader.tensor_names()}
