"""Hash lanes of the staging writer: the tensor and block digests are
computed on two lane threads behind the thread that writes, and every
staged byte, spec, journal record and published manifest equals what
inline hashlib gives over the same bytes — through ``StagingWriter``,
``WriteBehindWriter`` and ``ShardRegionWriter`` alike.  Also: resume
mid-tensor, lane failures, and a write-behind crash with blocks still on
the lanes (docs/EXECUTION.md, docs/RECOVERY.md)."""
import hashlib
import json
import os
import threading
import time
import types

import numpy as np
import pytest

from repro.core.executor import execute_merge
from repro.dist.region import ShardRegionWriter
from repro.store import snapshot
from repro.store.iostats import IOStats
from repro.store.journal import ProgressJournal, build_resume_state, parse_journal
from repro.store.snapshot import (
    CHUNK_BLOCKS,
    HASH_LANE_BLOCKS,
    SnapshotStore,
    StagingWriter,
    WriteBehindWriter,
)
from repro.store.tensorstore import TENSOR_DIR
from repro.testing import chaos

from conftest import make_models

BS = 4096

# (name, nbytes): several blocks with a ragged tail, a single short block,
# whole blocks only, and more blocks than the lanes may hold
TENSORS = [
    ("multi", 5 * BS + 1000),
    ("single", 3000),
    ("exact", 3 * BS),
    ("long", (3 * HASH_LANE_BLOCKS + 1) * BS + 17),
]
#: global block offset of each tensor's span in the shard-region case
SHARD_LO = {"multi": 3, "single": 0, "exact": 1, "long": 2}


def _fleet(seed=0):
    rng = np.random.default_rng(seed)
    return {name: rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            for name, n in TENSORS}


def _blocks(raw):
    return [raw[o:o + BS] for o in range(0, len(raw), BS)]


def _experts(name, b):
    return None if b % 3 == 2 else "e%d,e%d" % (b % 2, len(name) % 4)


def _expected_records(fleet, start_file=0):
    """The journal's records after its begin record, as inline hashlib
    gives them over the same bytes."""
    recs = []
    for i, (name, raw) in enumerate(fleet.items()):
        recs.append({"k": "tensor", "t": name,
                     "file": os.path.join(TENSOR_DIR,
                                          "%05d.bin" % (start_file + i)),
                     "shape": [len(raw)], "dtype": "uint8"})
        for b, blk in enumerate(_blocks(raw)):
            rec = {"k": "block", "t": name, "i": b, "n": len(blk),
                   "h": hashlib.blake2b(blk, digest_size=8).hexdigest()}
            if _experts(name, b):
                rec["e"] = _experts(name, b)
            recs.append(rec)
        recs.append({"k": "finish", "t": name, "n": len(raw),
                     "h": hashlib.blake2b(raw, digest_size=16).hexdigest()})
    return recs


def _journal_records(path):
    with open(path, "rb") as f:
        lines = [json.loads(line) for line in f.read().splitlines() if line]
    assert lines[0]["k"] == "begin"
    return lines[1:]


def _lane_threads():
    return {t for t in threading.enumerate()
            if t.name.startswith("mergepipe-hash")}


def _begin_journal(path, stats, staging_dir, attempt=1):
    journal = ProgressJournal(path, stats, sync_every=3)
    journal.begin("sid", "plan", "digest", staging_dir, BS, attempt=attempt)
    return journal


def _stream(w, fleet, lo=None):
    for name, raw in fleet.items():
        off = (lo or {}).get(name, 0)
        w.begin_tensor(name, (len(raw),), np.uint8)
        for b, blk in enumerate(_blocks(raw)):
            w.write_block(name, off + b, np.frombuffer(blk, np.uint8),
                          experts=_experts(name, b))
        w.finish_tensor(name)


def _check_specs(specs, staged_dir, fleet):
    assert list(specs) == list(fleet)
    for name, raw in fleet.items():
        spec = specs[name]
        assert spec["nbytes"] == len(raw)
        assert spec["hash"] == hashlib.blake2b(raw, digest_size=16).hexdigest()
        assert spec["block_hashes"] == [
            hashlib.blake2b(blk, digest_size=8).hexdigest()
            for blk in _blocks(raw)]
        with open(os.path.join(staged_dir, spec["file"]), "rb") as f:
            assert f.read() == raw


# ------------------------------------------------------------ byte identity
@pytest.mark.parametrize("through", ["staging", "write_behind",
                                     "shard_region"])
def test_staged_bytes_digests_and_journal_match_inline_hashlib(
        tmp_path, through):
    fleet = _fleet()
    store = SnapshotStore(str(tmp_path / "ws"), IOStats())
    staging_dir = os.path.join(store.staging_root, "txn-lanes")
    jpath = str(tmp_path / "lanes.journal")
    journal = _begin_journal(jpath, store.stats, staging_dir)
    before = _lane_threads()
    if through == "shard_region":
        spans = {name: (SHARD_LO[name], SHARD_LO[name] + len(_blocks(raw)))
                 for name, raw in fleet.items()}
        w = ShardRegionWriter(staging_dir, spans, store.stats,
                              journal=journal)
        _stream(w, fleet, lo=SHARD_LO)
        inner = w.inner
    else:
        inner = StagingWriter(staging_dir, store.stats, journal=journal)
        if through == "write_behind":
            wb = WriteBehindWriter(inner, max_queued_blocks=4)
            _stream(wb, fleet)
            wb.flush()
            wb.close()
            assert wb.peak_queued <= 4
        else:
            _stream(inner, fleet)
    assert _lane_threads() == before  # every tensor's lanes have ended
    assert 1 <= inner.peak_hash_lane_blocks <= HASH_LANE_BLOCKS
    _check_specs(inner.specs, staging_dir, fleet)
    journal.close()
    assert _journal_records(jpath) == _expected_records(fleet)
    inner.validate_hashes()

    if through == "shard_region":
        for region in w.region_manifest():
            raw = fleet[region["tensor"]]
            assert region["hash"] == hashlib.blake2b(
                raw, digest_size=16).hexdigest()
            assert region["nbytes"] == len(raw)
            assert region["lo"] == SHARD_LO[region["tensor"]]
        return
    store.atomic_publish(inner, {"sid": "lanes"})
    with store.models.open_model("lanes") as r:
        for name, raw in fleet.items():
            spec = r.spec(name)
            assert spec["hash"] == hashlib.blake2b(
                raw, digest_size=16).hexdigest()
            assert spec["nbytes"] == len(raw)
            assert r.read_tensor(name, "meta").tobytes() == raw


def test_many_writers_under_fast_thread_switching(tmp_path):
    """More writers (three threads each) than cores, switching threads
    every microsecond: a lost update to a lane's counters or digests
    would show as a wrong digest, a missing record or a hang."""
    import sys

    fleet = _fleet(seed=2)
    want = _expected_records(fleet)
    n = 2 * (os.cpu_count() or 4)
    results = [None] * n

    def one(i):
        stats = IOStats()
        staging_dir = str(tmp_path / ("w%d" % i))
        jpath = str(tmp_path / ("w%d.journal" % i))
        w = StagingWriter(staging_dir, stats,
                          journal=_begin_journal(jpath, stats, staging_dir))
        _stream(w, fleet)
        w.journal.close()
        _check_specs(w.specs, staging_dir, fleet)
        results[i] = _journal_records(jpath)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert all(r == want for r in results)


@pytest.mark.parametrize("n_done", [1, HASH_LANE_BLOCKS + 3])
def test_resume_mid_tensor_reaches_the_same_digest(tmp_path, n_done):
    """A writer that dies mid-tensor and a successor seeded from the
    journal's validation pass give the tensor the digest of its bytes."""
    fleet = _fleet(seed=1)
    stats = IOStats()
    staging_dir = str(tmp_path / "staging")
    jpath = str(tmp_path / "resume.journal")
    w = StagingWriter(staging_dir, stats,
                      journal=_begin_journal(jpath, stats, staging_dir))
    first = dict(list(fleet.items())[:1])
    _stream(w, first)
    w.begin_tensor("long", (len(fleet["long"]),), np.uint8)
    blocks = _blocks(fleet["long"])
    for b in range(n_done):
        w.write_block("long", b, np.frombuffer(blocks[b], np.uint8),
                      experts=_experts("long", b))
    w.detach()

    state = build_resume_state(parse_journal(jpath, stats), stats)
    assert state.completed["long"] == n_done
    w2 = StagingWriter(staging_dir, stats,
                       journal=_begin_journal(jpath, stats, staging_dir, 2),
                       resume=state)
    w2.begin_tensor("long", (len(fleet["long"]),), np.uint8)
    for b in range(n_done, len(blocks)):
        w2.write_block("long", b, np.frombuffer(blocks[b], np.uint8),
                       experts=_experts("long", b))
    w2.finish_tensor("long")
    w2.journal.close()
    _check_specs({"long": w2.specs["long"]}, staging_dir,
                 {"long": fleet["long"]})
    recs = _journal_records(jpath)
    second = recs.index(next(r for r in recs if r.get("attempt") == 2))
    want = _expected_records({"long": fleet["long"]})
    assert recs[second + 1:] == (
        [want[0] | {"file": w2.specs["long"]["file"]}]
        + want[1 + n_done:])


# ----------------------------------------------------------------- failures
def _fail_lane(monkeypatch, lane):
    """Make one lane raise OSError on its third block."""
    calls = {"n": 0}

    def third(raw):
        calls["n"] += 1
        if calls["n"] >= 3:
            raise OSError("injected %s lane failure" % lane)
    if lane == "block":
        real = snapshot.block_digest

        def failing_digest(raw):
            third(raw)
            return real(raw)
        monkeypatch.setattr(snapshot, "block_digest", failing_digest)
        return

    class Streaming:
        def __init__(self):
            self._h = hashlib.blake2b(digest_size=16)

        def update(self, raw):
            third(raw)
            self._h.update(raw)

    def blake2b(data=b"", digest_size=64):
        if digest_size == 16:
            return Streaming()
        return hashlib.blake2b(data, digest_size=digest_size)
    monkeypatch.setattr(snapshot, "hashlib",
                        types.SimpleNamespace(blake2b=blake2b))


@pytest.mark.parametrize("lane", ["tensor", "block"])
def test_lane_failure_sets_failed_and_reraises_on_producer(
        tmp_path, monkeypatch, lane):
    _fail_lane(monkeypatch, lane)
    stats = IOStats()
    staging_dir = str(tmp_path / "staging")
    w = StagingWriter(staging_dir, stats,
                      journal=_begin_journal(str(tmp_path / "j"), stats,
                                             staging_dir))
    wb = WriteBehindWriter(w, max_queued_blocks=4)
    before = _lane_threads()
    blocks = _blocks(_fleet()["long"])
    with pytest.raises(OSError, match="injected %s lane" % lane):
        wb.begin_tensor("long", (len(blocks) * BS,), np.uint8)
        for b, blk in enumerate(blocks):
            wb.write_block("long", b, np.frombuffer(blk, np.uint8))
        wb.finish_tensor("long")
        wb.flush()
    assert wb.failed.is_set()
    wb.close(discard=True)
    lanes = w._lanes._threads
    w.abort()
    assert not os.path.exists(staging_dir)
    assert not any(t.is_alive() for t in lanes)
    assert _lane_threads() == before


@pytest.mark.parametrize("lane", ["tensor", "block"])
def test_lane_failure_raises_on_the_synchronous_writer(
        tmp_path, monkeypatch, lane):
    _fail_lane(monkeypatch, lane)
    staging_dir = str(tmp_path / "staging")
    w = StagingWriter(staging_dir, IOStats())
    before = _lane_threads()
    with pytest.raises(OSError, match="injected %s lane" % lane):
        _stream(w, {"long": _fleet()["long"]})
    w.abort()
    assert not os.path.exists(staging_dir)
    assert _lane_threads() == before


def test_detach_and_abort_end_the_lanes(tmp_path):
    for end in ("detach", "abort"):
        staging_dir = str(tmp_path / end)
        w = StagingWriter(staging_dir, IOStats())
        w.begin_tensor("t", (2 * BS,), np.uint8)
        w.write_block("t", 0, np.zeros(BS, np.uint8))
        threads = w._lanes._threads
        assert all(t.is_alive() for t in threads)
        getattr(w, end)()
        assert not any(t.is_alive() for t in threads)
        assert os.path.exists(staging_dir) == (end == "detach")


# ------------------------------------------------ crash with lanes behind
def test_write_behind_crash_with_blocks_on_the_lanes_resumes(
        workspace, monkeypatch):
    """Kill the write-behind thread while a written chunk is still on the
    (slowed) block lane and more blocks are gathered: the journal then
    lags the blocks handed in, yet every record it holds matches the
    bytes on disk, and the resumed merge is bit-identical to an
    uninterrupted one."""
    mp = workspace
    mp.snapshots.journal_sync_every = 1
    # one tensor of 40 blocks, so a whole chunk is written before the kill
    base, experts = make_models(shapes={"big": (64, 640)})
    mp.register_model("base", base)
    ids = []
    for i, e in enumerate(experts):
        mp.register_model("ex%d" % i, e)
        ids.append("ex%d" % i)
    mp.ensure_analyzed("base", ids)
    plan = mp.plan("base", ids, "ties", theta={"trim_frac": 0.2},
                   budget=0.5).plan
    execute_merge(plan, mp.snapshots, mp.catalog, sid="ref", txn=mp.txn,
                  compute="pipelined")
    ref = mp.load("ref")

    real_digest, real_write = snapshot.block_digest, StagingWriter.write_block
    handed = {"n": 0}

    def slow_digest(raw):
        time.sleep(0.03)
        return real_digest(raw)

    def counted_write(self, *args, **kw):
        real_write(self, *args, **kw)
        handed["n"] += 1
    monkeypatch.setattr(snapshot, "block_digest", slow_digest)
    monkeypatch.setattr(StagingWriter, "write_block", counted_write)
    jpath = mp.snapshots.journal_path("crash")
    n_blocks = CHUNK_BLOCKS + CHUNK_BLOCKS // 2
    # the kill comes before the drain applies its next command: the
    # tensor's begin and n_blocks blocks are in, one chunk of them written
    with pytest.raises(chaos.SimulatedCrash):
        with chaos.inject("writer:drain", skip=1 + n_blocks):
            execute_merge(plan, mp.snapshots, mp.catalog, sid="crash",
                          txn=mp.txn, compute="pipelined")
    n_handed = handed["n"]
    lagging = [r for r in _journal_records(jpath) if r["k"] == "block"]
    assert n_handed == n_blocks and len(lagging) < CHUNK_BLOCKS

    mp.txn.forsake()  # detach: the last chunk is written, hashed, recorded
    parsed = parse_journal(jpath)
    assert sum(len(recs) for recs in parsed.blocks.values()) == n_handed
    for tensor_id, recs in parsed.blocks.items():
        fname = parsed.tensors[tensor_id][0]
        with open(os.path.join(parsed.staging_dir, fname), "rb") as f:
            for i in range(len(recs)):
                n, h, _experts_csv = recs[i]
                assert real_digest(f.read(n)) == h, (tensor_id, i)

    monkeypatch.setattr(snapshot, "block_digest", real_digest)
    state = mp.txn.prepare_resume("crash")
    assert sum(state.completed.values()) == n_handed
    res = execute_merge(plan, mp.snapshots, mp.catalog, sid="crash",
                        txn=mp.txn, compute="pipelined", resume=state)
    assert res.stats["resumed_blocks"] == n_handed
    got = mp.load("crash")
    for k in ref:
        assert np.array_equal(ref[k], got[k]), k
    assert mp.verify("crash")
