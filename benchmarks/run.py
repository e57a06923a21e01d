"""Benchmark driver: one section per paper table/figure.

CSV format: name,us_per_call,derived

Flags:
  --smoke       kernel-engine sections only (batched GEMM + fused conv)
                at smoke size — the CI bench-regression workload
  --suite NAME  "kernels" / "serving" / "all" (default): section subset,
                matching the parallel CI bench lanes — each lane dumps
                its own JSON and compares it against the one committed
                baseline (compare_bench skips metrics the subset didn't
                produce; both subsets carry gated rows, so neither
                lane's gate is vacuous)
  --json PATH   dump the metrics registry as JSON (consumed by
                benchmarks/compare_bench.py)
"""
from __future__ import annotations

import argparse
import sys
import traceback
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "src"))


def _sections(smoke: bool):
    """(title, fn, suite) triples — ``suite`` tags the CI bench lane
    ("kernels" / "serving") each section belongs to."""
    # Smoke (the CI gate) imports only the engine benches; an
    # import-time error in an unused full-run module must not brick it.
    from benchmarks import (bench_attention, bench_batched_gemm,
                            bench_conv2d, bench_crossformat, bench_faults,
                            bench_policy_table, bench_serving)

    if smoke:
        return [
            ("Batched approx-GEMM engine (smoke)",
             lambda: bench_batched_gemm.main(smoke=True), "kernels"),
            ("Cross-format generated LUTs (smoke)",
             lambda: bench_crossformat.main(smoke=True), "kernels"),
            ("Fused approx-conv2d engine (smoke)",
             lambda: bench_conv2d.main(smoke=True), "kernels"),
            ("Fused approx-attention engine (smoke)",
             lambda: bench_attention.main(smoke=True), "kernels"),
            ("Policy-table overhead (smoke)",
             lambda: bench_policy_table.main(smoke=True), "kernels"),
            ("Fault-injection seam overhead (smoke)",
             lambda: bench_faults.main(smoke=True), "kernels"),
            ("Continuous-batching serving (smoke)",
             lambda: bench_serving.main(smoke=True), "serving"),
        ]
    from benchmarks import (
        bench_convergence,
        bench_crossformat,
        bench_gemm_sim,
        bench_infer_time,
        bench_pruning,
        bench_roofline,
        bench_train_time,
    )

    return [
        ("Fig.6 GEMM simulation perf", bench_gemm_sim.main, "kernels"),
        ("Batched approx-GEMM engine", bench_batched_gemm.main, "kernels"),
        ("Fused approx-conv2d engine", bench_conv2d.main, "kernels"),
        ("Fused approx-attention engine", bench_attention.main, "kernels"),
        ("Policy-table overhead", bench_policy_table.main, "kernels"),
        ("Fault-injection seam overhead", bench_faults.main, "kernels"),
        ("Continuous-batching serving", bench_serving.main, "serving"),
        ("Fig.10/Table III convergence & accuracy", bench_convergence.main,
         "kernels"),
        ("Table IV cross-format matrix", bench_crossformat.main, "kernels"),
        ("Fig.11 pruning x multipliers", bench_pruning.main, "kernels"),
        ("Table V training time", bench_train_time.main, "kernels"),
        ("Table VI inference time", bench_infer_time.main, "serving"),
        ("Roofline table (from dry-run)", bench_roofline.main, "kernels"),
    ]


def main(smoke: bool = False, json_path: str | None = None,
         suite: str = "all") -> None:
    from benchmarks import common
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    common.reset_metrics()
    failures = 0
    ran = 0
    for title, fn, sec_suite in _sections(smoke):
        if suite != "all" and sec_suite != suite:
            continue
        ran += 1
        print(f"\n# === {title} ===")
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
    if not ran:
        print(f"# no sections in suite {suite!r}", file=sys.stderr)
        sys.exit(2)
    if json_path:
        common.dump_metrics(json_path)
        print(f"\n# wrote {len(common.METRICS)} metrics -> {json_path}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="kernel-engine sections only, smoke sizes (CI)")
    ap.add_argument("--suite", choices=("kernels", "serving", "all"),
                    default="all",
                    help="section subset (parallel CI bench lanes)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="dump metrics registry as JSON")
    args = ap.parse_args()
    main(smoke=args.smoke, json_path=args.json, suite=args.suite)
