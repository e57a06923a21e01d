#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload granite-3-2b.train --seed 7 \
        --seconds 51 --trace 0

Loads the cell named in BENCHMARK.json, sets up (weights and inputs from
``--seed``, every program compiled and warmed), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line as the last line of standard output.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles the window and reports its per-layer metrics.  The numbers
compared for ``correct`` are printed with their limits as the last lines
of standard error and under ``checks`` in the result line.

Without an accelerator, or with fewer chips than the cell asks for, it
exits with status 2 and prints no result.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.devices(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    trace_dir = None
    if args.trace:
        trace_dir = ROOT / ".bench_traces" / f"{args.workload}-{args.seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    outcome = harness.runner(cell).run(cell, args.seed, args.seconds, trace_dir,
                                       devs=devs)
    line = harness.result_line(cell, outcome, devs, traced=bool(args.trace))
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    harness.print_checks(outcome)
    import json
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
