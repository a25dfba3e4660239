#!/usr/bin/env python3
"""Readings of the control that a cell's correctness check has to fail.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3

For each seed the cell's fleet is made on the device and each job of its
traffic is merged by the plain reference twice: in the configuration's
``compute_dtype``, float32 (the reference), and with every operation in
bfloat16 (the control, the next precision below, which a later change
might be tempted to compute in).  The control
is compared with the reference by the numbers the benchmark compares,
at the cell's own size.  One JSON line per seed.  The program is not
run: its readings are the benchmark's own runs.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench  # noqa: E402
import fleet  # noqa: E402
import reference  # noqa: E402


def control_numbers(cfg, traffic, seed):
    import jax

    k = int(traffic["experts"])
    names = [n for n, _ in fleet.inventory(cfg)]
    models = [list(m) for m in fleet.fleet_arrays(cfg, seed, range(k + 1))]
    host = [dict(zip(names, jax.device_get(m))) for m in models]
    out = []
    for entry in traffic["batch"]:
        op = entry["op"].lower()
        sel, theta = reference.plan(cfg, k, op, entry.get("theta", {}),
                                    entry.get("budget"), host)
        thr = (reference.ties_thresholds(
            cfg, sel, float(theta.get("trim_frac", 0.2)), host)
            if op == "ties" else None)
        ref = jax.device_get(reference.merge_job(
            cfg, models, entry, theta, sel, thr, dtype=cfg["compute_dtype"]))
        tally = reference.Tally()
        reference.merge_job(cfg, models, entry, theta, sel, thr,
                            dtype="bfloat16", compare_to=ref, tally=tally,
                            merged_is_reference=False)
        out.append(tally.numbers())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    manifest = bench.load_json(bench.ROOT, "BENCHMARK.json")
    wl = bench.find_cell(manifest, args.workload)
    cfg = bench.load_json(HERE, "configs", wl["config"] + ".json")
    traffic = bench.load_json(HERE, "workloads", wl["traffic"] + ".json")
    bench.require_device("tpu", int(wl["chips"]))
    for seed in args.seeds:
        t0 = time.time()
        nums = control_numbers(cfg, traffic, seed)
        print(json.dumps({"workload": wl["name"], "seed": seed,
                          "control": nums,
                          "seconds": time.time() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
