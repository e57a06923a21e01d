#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; not part of a
benchmark run.

    python3 bench/calibrate.py --workload granite-3-2b.train \
        --variant program --seeds 11,12,13 [--seconds 51]

Variants:
  program     the program as the configuration states (the lower reading)
  control     the program's own lower-cost path in its place: ``surrogate``
              numerics with bf16, operands rounded to bfloat16 and multiplied
              exactly on the MXU, which is what stopping the simulation of
              the multiplier would compute (the upper reading)
  half_batch  training only: the loss of the checked steps taken over the
              first half of each batch's tokens (a fault the numbers
              compared must catch)

Training readings need no window (``--seconds`` is ignored).  Serving
readings take the cell's window, and the control serves the whole run in
the program's place: its served tokens go through the same comparison.
One JSON line per seed; all seeds in one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONTROL = "surrogate:bf16"


def half_batch_feed(cell, seed):
    from bench.drive.train_steps import batch_maker
    import jax.numpy as jnp
    make = batch_maker(cell, seed)
    seq = cell.traffic["seq"]

    def feed(i):
        b = make(i)
        return dict(b, labels=jnp.where(jnp.arange(seq) < seq // 2, b["labels"], -1))
    return feed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", choices=("program", "control", "half_batch"),
                    required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.devices(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    drv = harness.runner(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = {"workload": args.workload, "variant": args.variant, "seed": seed}
        kw = {}
        if args.variant == "control":
            kw["numerics"] = CONTROL
        elif args.variant == "half_batch":
            kw["feed"] = half_batch_feed(cell, seed)
        if cell.traffic["kind"] == "train_steps":
            o = drv.run(cell, seed, 0, devs=devs, **kw)
        else:
            o = drv.run(cell, seed, args.seconds, devs=devs, **kw)
            out["served_tokens"] = sum(len(s[1]) for s in o.counts["sample"])
        out["checks"] = {n: v for n, v, _ in o.checks}
        out["correct"] = o.correct
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
