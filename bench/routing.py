#!/usr/bin/env python3
"""How many of the router's token-expert choices differ between the
program and the plain reference in a sparse-expert training cell's
checked steps; not part of a benchmark run.

    python3 bench/routing.py --workload granite-moe-3b-a800m.train --seeds 11,12

For each seed both sides start from the seed's weights and take the
cell's ``checked_steps`` steps on its batches, as a run does; before each
step each side's own forward pass picks, in every layer, the experts of
each token (``routes`` of the configuration's system and reference
modules).  A choice differs where an expert in one side's set of a
token is not in the other's.  Step 1's choices come from the same
weights, so they differ only where the two sides' sums of the same
simulated products round apart across a near tie; later steps' also
carry the weights' drift.  One JSON line per seed; all seeds in one
process.
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


def differing(a, b) -> list:
    """Per layer, the choices of ``a`` (layers, T, k) not in ``b``'s set
    of the same token."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    kept = (a[..., :, None] == b[..., None, :]).any(-1)
    return [int(n) for n in (~kept).sum(axis=(1, 2))]


def count(cell, seed: int) -> dict:
    """The choices of ``cell`` that differ between program and reference
    before each checked step of ``seed``."""
    import jax
    import numpy as np
    from bench import harness
    from bench.drive.train_steps import batch_maker
    from bench.reference import optim
    sizes, t = cell.sizes, cell.traffic
    ref, system = cell.reference, cell.system
    make, wkey = batch_maker(cell, seed), harness.key(seed, "weights")
    batches = [make(i) for i in range(t["checked_steps"])]
    step, opt_init = system.train_step(sizes, t, sizes["numerics"])
    params = ref.make_weights(sizes, wkey)
    state = jax.jit(opt_init)(params)
    mine = []
    for b in batches:
        mine.append(np.asarray(system.routes(sizes, sizes["numerics"],
                                             params, b["tokens"])))
        params, state, _ = step(params, state, b)
    del params, state, step
    harness.free()
    rstep = ref.train_step(sizes, t)
    w = ref.make_weights(sizes, wkey)
    rstate = optim.init_state(t, w)
    theirs = []
    for b in batches:
        theirs.append(np.asarray(ref.routes(sizes, w, b["tokens"])))
        w, rstate, _ = rstep(w, rstate, b)
    del w, rstate
    harness.free()
    return {"workload": cell.name, "seed": seed,
            "choices_per_step": int(mine[0].size),
            "differing_by_step_and_layer": [differing(a, b) for a, b in
                                            zip(mine, theirs)]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        harness.devices(cell.chips)
    except harness.NoChip as e:
        print(f"routing: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(count(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
