#!/usr/bin/env python3
"""One run of one benchmark cell of MergePipe on the chip.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration, found
as ``configs/<config>.json``, and a traffic mix, found as
``workloads/<traffic>.json``; the limits of its correctness check are in
``limits/<cell>.json``, and each metric is read by ``metrics/<name>.py``.

Set-up makes the fleet on the device from the seed, registers and
ANALYZEs it through ``Session``, all in a child process that has ended
before this one touches the chip; then this process merges one whole
batch of the traffic's specs, so that every program the window runs is
compiled, and flushes its writes to the disk.
The window is a closed loop: one client submits a batch through
``Session.submit`` and ``Session.run_all(pipeline=PipelineConfig(
kernel="jax"))``, waits for it to commit, reads the outputs back and
deletes them outside the timed interval, and repeats until the timed
batches add up to ``--seconds``.  With ``--trace 1`` one more batch runs
under the profiler after the window.  Then the last batch's outputs are
compared with the plain reference (``reference.py``), and every other
job's output with the last batch's.

Earlier lines of standard output report set-up, compiles, each batch and
the I/O; the last line is one JSON object.  The numbers compared are
printed with their limits as the last lines of standard error.  A run
that finds no TPU, or fewer chips than the cell asks for, exits non-zero
without a result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import zlib  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WORK = os.path.join(HERE, ".work")
COMPILE_CACHE = os.path.join(HERE, ".cache", "jax")
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.api import MergeSpec, Session  # noqa: E402
from repro.core.executor import PipelineConfig  # noqa: E402
from repro.store.iostats import CATEGORIES, EXPERT_CATEGORIES  # noqa: E402

import fleet  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import devtrace  # noqa: E402


class NoDevice(RuntimeError):
    """JAX found no accelerator of the platform, or too few chips."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ the cell
def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(manifest: Dict, name: str) -> Dict:
    for wl in manifest["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError("no workload %r in BENCHMARK.json" % name)


def cell_metrics(manifest: Dict, traced: bool) -> List[Dict]:
    """The metrics a run reports: the end-to-end metrics, or with the
    trace the per-layer ones.  A reader that finds nothing to read in a
    run returns None, and the metric is left out of the line."""
    return manifest["per_layer" if traced else "end_to_end"]


def reader(name: str):
    """``read(run)`` of ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------- the device
def require_device(platform: str, chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        raise NoDevice("JAX found %d %s device(s); this cell needs %d %s "
                       "chip(s)" % (len(devs), devs[0].platform, chips,
                                    platform))
    return devs


class CompileCounter:
    """Backend compiles (and persistent-cache hits) in this process,
    counted through ``jax.monitoring``."""

    def __init__(self) -> None:
        import jax

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self) -> str:
        return ("compiles=%d compile_seconds=%.3f persistent_cache_hits=%d"
                % (self.n, self.seconds, self.cache_hits))


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ------------------------------------------------------------ batches
def io_bytes(stats) -> Dict[str, int]:
    """Bytes by ``read:<category>`` and ``written:<category>``."""
    out = {"read:" + c: stats.bytes_read(c) for c in CATEGORIES}
    out.update({"written:" + c: stats.bytes_written(c) for c in CATEGORIES})
    return out


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_batch(sess, specs, tag: str, expect_backend: str):
    """Submit the specs, run them as one window and wait for the commit.
    Returns the batch record and the committed sids."""
    before = io_bytes(sess.stats)
    t0 = time.perf_counter()
    with annotate("bench.submit"):
        sids = ["%s-j%d" % (tag, j) for j in range(len(specs))]
        for spec, sid in zip(specs, sids):
            sess.submit(spec, sid=sid)
    with annotate("bench.run_all"):
        results = sess.run_all(pipeline=PipelineConfig(kernel="jax"))
    wall = time.perf_counter() - t0
    after = io_bytes(sess.stats)
    io = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    jobs = []
    for res in results:
        backend = res.stats["pipeline"]["backend"]
        if backend != expect_backend:
            raise RuntimeError("job %s ran on %r, not %r"
                               % (res.sid, backend, expect_backend))
        jobs.append({"sid": res.sid, "seconds": res.stats["seconds"],
                     "c_expert_run": res.stats["c_expert_run"],
                     "plan_id": res.manifest["plan_id"]})
    return {"tag": tag, "wall_s": wall, "jobs": jobs, "io": io,
            "io_bytes": sum(io.values()),
            "expert_bytes": sum(io.get("read:" + c, 0)
                                for c in EXPERT_CATEGORIES)}, sids


def fingerprint(arrays: Dict) -> int:
    crc = 0
    for name in sorted(arrays):
        crc = zlib.crc32(name.encode(), crc)
        crc = zlib.crc32(arrays[name].tobytes(), crc)
    return crc


def cleanup(sess, sids, keep: bool = False) -> List[int]:
    """Read each output back for its fingerprint, then delete it unless
    ``keep``."""
    with annotate("bench.cleanup"):
        out = [fingerprint(sess.load(sid)) for sid in sids]
        if not keep:
            for sid in sids:
                sess.snapshots.models.delete_model(sid, force=True)
    return out


# ---------------------------------------------------------- reference
def check(sess, cfg: Dict, traffic: Dict, seed: int, sids: List[str]):
    """Compare each job of the last batch with the plain reference.
    Returns (numbers, problems)."""
    import jax

    k = int(traffic["experts"])
    inv = fleet.inventory(cfg)
    names = [n for n, _ in inv]
    models = [list(m) for m in fleet.fleet_arrays(cfg, seed, range(k + 1))]
    host = None
    worst = {"mismatch_share": 0.0, "outside_tol": 0}
    problems = []
    for entry, sid in zip(traffic["batch"], sids):
        op = entry["op"].lower()
        if host is None and (entry.get("budget") is not None or op == "ties"):
            host = [dict(zip(names, jax.device_get(m))) for m in models]
        sel, theta = reference.plan(cfg, k, op, entry.get("theta", {}),
                                    entry.get("budget"), host)
        thr = (reference.ties_thresholds(
            cfg, sel, float(theta.get("trim_frac", 0.2)), host)
            if op == "ties" else None)
        got = sess.load(sid)
        want = {n: (s, np.dtype(fleet.stored_dtype(cfg))) for n, s in inv}
        have = {n: (a.shape, a.dtype) for n, a in got.items()}
        if have != want:
            problems.append("%s holds %d tensors unlike the inventory"
                            % (sid, len(have)))
            continue
        tally = reference.Tally()
        reference.merge_job(cfg, models, entry, theta, sel, thr,
                            dtype=cfg["compute_dtype"],
                            compare_to={n: a.reshape(-1) for n, a in got.items()},
                            tally=tally)
        for key, value in tally.numbers().items():
            worst[key] = max(worst[key], value)
        log("check %s (%s): %d elements, %d differ, %d outside tolerance, "
            "theta %s" % (sid, op, tally.elements, tally.mismatches,
                          tally.outside, json.dumps(theta, sort_keys=True)))
    return worst, problems


# ---------------------------------------------------------------- run
def make_fleet(cfg: Dict, traffic: Dict, seed: int, workdir: str) -> None:
    """Make the fleet from the seed into a fresh workspace: registered and
    ANALYZEd through ``Session``."""
    shutil.rmtree(workdir, ignore_errors=True)
    sess = Session(workdir, block_size=int(cfg["block_size"]))
    try:
        t0 = time.time()
        ids, split = fleet.register_fleet(sess, cfg, int(traffic["experts"]),
                                          seed)
    finally:
        sess.close()
    log("fleet: %s, %d tensors, base + %d fine-tunes x %.6f GB in "
        "%.3f s: %s" % (cfg["name"], len(cfg["tensors"]), len(ids),
                        fleet.model_nbytes(cfg) / 1e9, time.time() - t0,
                        ", ".join("%s %.3f s" % kv for kv in split.items())))


def make_fleet_in_child(cell: str, seed: int) -> None:
    """Make the fleet in a child process, which has ended when this
    returns.  On a TPU v5e a process that had compiled the fleet
    generator, or run it over chunks, merged 20-25% slower afterwards
    (its host copies 1.8x slower), and its device memory peak was one
    whole model; so the measured process never runs the generator before
    its window.  A child that fails ends the run."""
    code = "import bench; bench.fleet_child(%r, %d)" % (cell, seed)
    rc = subprocess.run([sys.executable, "-c", code], cwd=HERE).returncode
    if rc:
        sys.exit("bench: making the fleet in a child process failed "
                 "(exit %d)" % rc)


def fleet_child(cell: str, seed: int) -> None:
    configure_jax()
    wl = find_cell(load_json(ROOT, "BENCHMARK.json"), cell)
    try:
        require_device("tpu", int(wl["chips"]))
    except NoDevice as e:
        print("bench: %s" % e, file=sys.stderr)
        sys.exit(3)
    make_fleet(load_json(HERE, "configs", wl["config"] + ".json"),
               load_json(HERE, "workloads", wl["traffic"] + ".json"), seed,
               WORK)


def run_cell(wl: Dict, cfg: Dict, traffic: Dict, limits: Dict,
             metrics: List[Dict], seed: int, seconds: float, traced: bool,
             *, platform: str = "tpu", expect_backend: str = "pallas-tpu",
             workdir: str = WORK, t_start: Optional[float] = None) -> Dict:
    """One run of a cell on the fleet that ``make_fleet`` left in
    ``workdir``; returns the result line as a dict."""
    t_start = T_START if t_start is None else t_start
    devs = require_device(platform, int(wl["chips"]))
    log("device: %s %s x%d" % (devs[0].platform, devs[0].device_kind,
                               len(devs)))
    counter = CompileCounter()
    ids = fleet.expert_ids(int(traffic["experts"]))
    sess = Session(workdir, block_size=int(cfg["block_size"]))
    try:
        specs = [MergeSpec.build("base", ids, op=e["op"],
                                 theta=e.get("theta", {}),
                                 budget=e.get("budget"))
                 for e in traffic["batch"]]
        warm, sids = run_batch(sess, specs, "warm", expect_backend)
        cleanup(sess, sids)
        # set-up's writes (the fleet) reach the disk before the window, so
        # that the window's fsyncs do not wait on them
        t0 = time.time()
        os.sync()
        synced = time.time() - t0
        setup_s = time.time() - t_start
        log("warm-up batch %.3f s; sync %.3f s; set-up %.3f s; %s"
            % (warm["wall_s"], synced, setup_s, counter.report()))

        compiles0 = counter.n
        batches, prints = [], []
        timed = 0.0
        while timed < seconds or not batches:
            rec, sids = run_batch(sess, specs, "b%d" % len(batches),
                                  expect_backend)
            timed += rec["wall_s"]
            batches.append(rec)
            last = timed >= seconds
            prints.append(cleanup(sess, sids, keep=last))
            log("batch %d: %.3f s, jobs %s, io %d B, expert %d B"
                % (len(batches) - 1, rec["wall_s"],
                   ["%.3f" % j["seconds"] for j in rec["jobs"]],
                   rec["io_bytes"], rec["expert_bytes"]))
        window_compiles = counter.n - compiles0
        log("window: %d batches, %.3f s timed; window %s" % (
            len(batches), timed, "compiles=%d" % window_compiles))
        io = {}
        for rec in batches:
            for k, v in rec["io"].items():
                io[k] = io.get(k, 0) + v
        log("window io bytes: %s" % json.dumps(io, sort_keys=True))
        if window_compiles:
            raise RuntimeError("%d compiles inside the window" % window_compiles)

        reduced, traced_bytes = None, None
        if traced:
            reduced, traced_bytes = traced_batch(sess, cfg, specs,
                                                 expect_backend, prints)
        peak = memory_peak(devs)

        t0 = time.time()
        numbers, problems = check(sess, cfg, traffic, seed, sids)
        if any(p != prints[-1] for p in prints):
            problems.append("outputs of one spec differ between batches")
        log("reference check: %.3f s" % (time.time() - t0))
    finally:
        sess.close()
        shutil.rmtree(workdir, ignore_errors=True)

    run = {"setup_s": setup_s, "batches": batches, "trace": reduced,
           "traced_merge_bytes": traced_bytes,
           "peak": roofline.peak(devs[0].device_kind) if traced else None}
    values = {}
    for m in metrics:
        v = reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    compared = {k: {"value": v, "limit": limits[k]["limit"]}
                for k, v in numbers.items()}
    for p in problems:
        print("problem: " + p, file=sys.stderr)
    for k, c in compared.items():
        print("%s %r limit %r" % (k, c["value"], c["limit"]), file=sys.stderr)
    jobs = sum(len(b["jobs"]) for b in batches)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {
        "correct": not problems and all(c["value"] <= c["limit"]
                                        for c in compared.values()),
        "attempted": jobs, "failed": 0, "metrics": values, "device": device,
    }
    if traced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared
    return result


def traced_batch(sess, cfg, specs, expect_backend, prints):
    """One more batch under the profiler: its trace reduction and the
    bytes its merges need."""
    import jax

    tdir = os.path.join(WORK + "-trace")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        rec, sids = run_batch(sess, specs, "traced", expect_backend)
    finally:
        jax.profiler.stop_trace()
    try:
        reduced = devtrace.reduce(devtrace.load(devtrace.newest_xplane(tdir)))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    if reduced is None:
        raise RuntimeError("the trace holds no device op")
    sizes = {n: int(np.prod(s)) * fleet.stored_dtype(cfg).itemsize
             for n, s in fleet.inventory(cfg)}
    needed = 0
    for job in rec["jobs"]:
        plan = sess.catalog.get_plan(job["plan_id"])
        needed += roofline.merge_bytes(plan["payload"]["selection"], sizes,
                                       int(cfg["block_size"]))
    prints.append(cleanup(sess, sids))
    log("traced batch: %.3f s, busy %.6f s of %.6f s, merge bytes %d"
        % (rec["wall_s"], reduced["busy_s"], reduced["window_s"], needed))
    return reduced, needed


def configure_jax() -> None:
    # the TPU runtime's logs stay inside the checkout too
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(HERE, ".cache",
                                                      "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    wl = find_cell(manifest, args.workload)
    cfg = load_json(HERE, "configs", wl["config"] + ".json")
    traffic = load_json(HERE, "workloads", wl["traffic"] + ".json")
    limits = load_json(HERE, "limits", wl["name"] + ".json")
    metrics = cell_metrics(manifest, bool(args.trace))

    make_fleet_in_child(wl["name"], args.seed)
    configure_jax()
    log("compile cache: %s" % COMPILE_CACHE)
    result = run_cell(wl, cfg, traffic, limits, metrics, args.seed,
                      args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoDevice as e:
        print("bench: %s" % e, file=sys.stderr)
        sys.exit(3)
