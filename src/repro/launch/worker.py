"""Shard worker process entrypoint — ``python -m repro.launch.worker``.

Launched by :class:`repro.dist.transport.LocalProcessTransport` (and by
``merge_cli worker`` for manual runs): reads a :class:`ShardLease` JSON
document, executes it against the shared workspace, and writes the
result doc the coordinator splices from.

Exit codes:

* ``0`` — lease completed; the result doc exists;
* ``3`` — :class:`~repro.testing.chaos.SimulatedCrash` (armed via the
  lease's chaos field): the staged region and shard journal survive on
  disk for lease re-issue, exactly like a kill -9;
* anything else — a real error (traceback on stderr); the coordinator
  aborts the window.
"""
from __future__ import annotations

import argparse
import sys
from typing import Optional

from repro.dist.lease import ShardLease
from repro.dist.worker import run_worker
from repro.testing.chaos import SimulatedCrash

CRASH_EXIT = 3


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.worker",
        description="execute one shard lease against a MergePipe workspace",
    )
    ap.add_argument("--workspace", required=True)
    ap.add_argument("--lease", required=True, help="ShardLease JSON path")
    ap.add_argument("--result", required=True,
                    help="where to write the result doc")
    args = ap.parse_args(argv)
    lease = ShardLease.read(args.lease)
    if lease.kernel != "numpy":
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    try:
        run_worker(args.workspace, lease, result_path=args.result)
    except SimulatedCrash as e:
        print("simulated crash: %s" % e, file=sys.stderr)
        return CRASH_EXIT
    return 0


if __name__ == "__main__":
    sys.exit(main())
