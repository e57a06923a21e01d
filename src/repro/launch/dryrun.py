import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST precede every other import (jax locks the device
# count at first init) — deliverable (e), multi-pod dry-run.
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.analysis.roofline import (
    V5E, analytic_memory_bytes, analyze, collective_traffic, model_flops_for,
)
from repro.configs import ARCH_REGISTRY, SHAPES, get_arch
from repro.core.policy import NumericsPolicy
from repro.launch.cells import build_cell, cell_skip_reason
from repro.launch.mesh import make_production_mesh

ALL_ARCHS = [
    "whisper-base", "stablelm-12b", "qwen2.5-32b", "granite-3-2b",
    "qwen1.5-110b", "zamba2-1.2b", "granite-moe-3b-a800m",
    "llama4-maverick-400b-a17b", "llava-next-34b", "mamba2-780m",
]
ALL_SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

# Archs whose full unrolled stack compiles quickly enough to cost directly;
# deeper stacks use the exact two-point per-layer extrapolation below.
UNROLL_LAYER_BUDGET = 16


def _extrapolation_step(cfg) -> int:
    """Layer-granularity at which the stack is homogeneous."""
    if cfg.family == "hybrid" and cfg.attn_every:
        return cfg.attn_every
    if cfg.family == "moe" and cfg.moe and cfg.moe.interleave > 1:
        return cfg.moe.interleave
    return 1


def _compile_costs(cfg, shape, mesh, policy, microbatches, chips):
    """lower+compile one cell config; return (compiled, costs dict)."""
    kw = {"microbatches": microbatches} if shape.kind == "train" else {}
    cell = build_cell(cfg, shape, mesh, policy, **kw)
    with jax.set_mesh(mesh):
        lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                          out_shardings=cell.out_shardings).lower(*cell.args)
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    traffic = collective_traffic(compiled.as_text(), default_group=chips)
    return compiled, {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": {k: float(v) for k, v in traffic["bytes"].items()},
        "coll_counts": traffic["counts"],
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             policy: NumericsPolicy, microbatches: int = 1,
             unroll: bool = True, verbose: bool = True, opts: str = "",
             config_overrides: dict | None = None) -> dict:
    import dataclasses as _dc
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    # §Perf optimisation toggles (baseline = none)
    opt_over = {}
    for o in filter(None, opts.split(",")):
        if o == "attn":
            opt_over["shard_attn_heads"] = True
        elif o == "logits":
            opt_over["constrain_logits"] = True
        elif o == "cache16":
            opt_over["cache_dtype"] = "bfloat16"
        elif o == "fsdpgather":
            opt_over["unshard_weights"] = True
        else:
            raise ValueError(f"unknown opt {o!r}")
    if multi_pod:
        opt_over["mesh_data_axes"] = ("pod", "data")
    if unroll:
        # cost_analysis counts lax.scan bodies ONCE — unroll the layer
        # stack (and, for prefill, the attention q-chunk loop) so the
        # roofline sees every layer's and every chunk's FLOPs/bytes.
        over = {"scan_layers": False}
        if shape.kind == "train":
            over["q_chunk"] = max(shape.seq_len, 1024)  # 4k: no chunking
        elif shape.kind == "prefill":
            over["q_chunk"] = 4096
            over["unroll_attn_chunks"] = True
        cfg = _dc.replace(cfg, **over)
    if opt_over:
        cfg = _dc.replace(cfg, **opt_over)
    if config_overrides:
        cfg = _dc.replace(cfg, **config_overrides)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    skip = cell_skip_reason(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    model_axis = mesh.shape["model"]
    t0 = time.time()
    try:
        step = _extrapolation_step(cfg)
        total_layers = cfg.n_layers + cfg.n_enc_layers
        extrapolate = (unroll and not cfg.scan_layers
                       and total_layers > UNROLL_LAYER_BUDGET
                       and cfg.family != "encdec")
        if extrapolate:
            # (1) full-depth compile (scanned): the lower+compile PROOF and
            #     the true per-device argument/memory sizes;
            # (2) L=step and L=2*step unrolled compiles: EXACT per-layer
            #     flops/bytes/collective costs from cost_analysis —
            #     cost(L) = cost(step) + (L/step - 1) * delta.
            cfg_scan = _dc.replace(cfg, scan_layers=True)
            compiled, _ = _compile_costs(cfg_scan, shape, mesh, policy,
                                         microbatches, chips)
            mem = compiled.memory_analysis()
            c1cfg = _dc.replace(cfg, n_layers=step)
            c2cfg = _dc.replace(cfg, n_layers=2 * step)
            _, c1 = _compile_costs(c1cfg, shape, mesh, policy,
                                   microbatches, chips)
            _, c2 = _compile_costs(c2cfg, shape, mesh, policy,
                                   microbatches, chips)
            blocks = cfg.n_layers / step
            lin = lambda a, b: a + (blocks - 1) * (b - a)
            flops = lin(c1["flops"], c2["flops"])
            bytes_ub = lin(c1["bytes"], c2["bytes"])
            coll = {k: lin(c1["coll"].get(k, 0.0), c2["coll"].get(k, 0.0))
                    for k in set(c1["coll"]) | set(c2["coll"])}
            coll_detail = {"bytes": coll, "counts": c2["coll_counts"],
                           "extrapolated": True}
            cbytes = coll["total"]
        else:
            compiled, costs = _compile_costs(cfg, shape, mesh, policy,
                                             microbatches, chips)
            mem = compiled.memory_analysis()
            flops, bytes_ub = costs["flops"], costs["bytes"]
            coll_detail = {"bytes": costs["coll"],
                           "counts": costs["coll_counts"]}
            cbytes = costs["coll"]["total"]

        arg_bytes = float(getattr(mem, "argument_size_in_bytes", 0))
        out_bytes = float(getattr(mem, "output_size_in_bytes", 0))
        mem_bytes = analytic_memory_bytes(cfg, shape, chips, model_axis,
                                          arg_bytes, out_bytes)
        model_flops = model_flops_for(cfg, shape)
        compute_s = flops / V5E.peak_flops
        memory_s = mem_bytes / V5E.hbm_bw
        memory_ub_s = bytes_ub / V5E.hbm_bw
        collective_s = cbytes / V5E.ici_bw
        terms = {"compute": compute_s, "memory": memory_s,
                 "collective": collective_s}
        dominant = max(terms, key=terms.get)
        bound_s = max(terms.values())
        ideal = model_flops / (chips * V5E.peak_flops)
        dt = time.time() - t0
        result = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "ok", "chips": chips, "compile_s": round(dt, 1),
            "extrapolated": bool(extrapolate),
            "bytes_per_device": int(getattr(mem, "temp_size_in_bytes", 0)
                                    + arg_bytes + out_bytes),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "arg_bytes": int(arg_bytes),
            "hlo_flops_per_dev": flops,
            "hlo_bytes_per_dev": bytes_ub,
            "memory_bytes_per_dev": mem_bytes,
            "collective_bytes_per_dev": cbytes,
            "collective_detail": coll_detail,
            "model_flops": model_flops,
            "compute_s": compute_s,
            "memory_s": memory_s,
            "memory_ub_s": memory_ub_s,
            "collective_s": collective_s,
            "dominant": dominant,
            "useful_flops_frac": model_flops / max(flops * chips, 1.0),
            "roofline_frac": ideal / bound_s if bound_s else 0.0,
        }
        if verbose:
            print(f"[ok] {cfg.name} x {shape_name} mesh={mesh_name} "
                  f"compile={dt:.1f}s "
                  f"args/dev={arg_bytes/2**30:.2f}GiB "
                  f"terms(ms): C={compute_s*1e3:.2f} "
                  f"M={memory_s*1e3:.2f} X={collective_s*1e3:.2f} "
                  f"dom={dominant} roofline={result['roofline_frac']:.1%}")
        return result
    except Exception as e:
        if verbose:
            traceback.print_exc()
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "error", "error": f"{type(e).__name__}: {e}"}


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run (deliverable e)")
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--numerics", default="surrogate",
                    help="policy mode (surrogate|native|amsim_jnp|direct)")
    ap.add_argument("--multiplier", default="bf16")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--opts", default="",
                    help="comma list of §Perf toggles: attn,logits,cache16")
    ap.add_argument("--no-unroll", action="store_true",
                    help="keep lax.scan over layers (fast compile; use for "
                         "the multi-pod shard-proof where no roofline is "
                         "read from cost_analysis)")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    args = ap.parse_args()

    policy = (NumericsPolicy() if args.numerics == "native"
              else NumericsPolicy(mode=args.numerics,
                                  multiplier=args.multiplier))
    archs = ALL_ARCHS if args.arch == "all" else [args.arch]
    shapes = ALL_SHAPES if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = []
    with out_path.open("a") as fh:
        for arch in archs:
            for shape in shapes:
                for mp in meshes:
                    r = run_cell(arch, shape, multi_pod=mp, policy=policy,
                                 microbatches=args.microbatches,
                                 unroll=not args.no_unroll, opts=args.opts)
                    r["numerics"] = f"{args.numerics}/{args.multiplier}"
                    r["opts"] = args.opts
                    results.append(r)
                    fh.write(json.dumps(r) + "\n")
                    fh.flush()

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        for r in results:
            if r["status"] == "error":
                print("  ERROR", r["arch"], r["shape"], r["mesh"], r["error"])
        raise SystemExit(1)


if __name__ == "__main__":
    main()
