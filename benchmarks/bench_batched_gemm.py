"""Batched approximate-GEMM engine throughput (tentpole measurement).

Per batch shape, times four executions of (B, m, k) @ (B, k, n):

  native          jnp batched matmul (MXU / XLA dot)        — "TFnG" floor
  surrogate       mantissa-quantised operands + native dot  — fast path
  amsim_batched   the 4-D-grid ``approx_gemm_batched`` kernel (packed LUT
                  when available), block sizes from the autotune cache
  amsim_vmapped   the pre-engine fallback: jax.vmap over the 2-D
                  ``approx_gemm`` at its 2-D default tiling

so the batched engine's win over the vmapped fallback — and its remaining
gap to native — stays measurable as the speedup trajectory evolves.

CSV columns (benchmarks/common.emit): name,us_per_call,derived.

Flags:
  --smoke      acceptance shape only, best-of-5 timing (feeds the CI
               bench-regression gate)
  --autotune   sweep the autotuner per shape first (writes the JSON cache)
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from functools import partial

from benchmarks.common import emit, time_fn
from repro.core.float_bits import jnp_truncate_mantissa
from repro.core.lutgen import get_lut, get_packed_lut
from repro.core.multipliers import get_multiplier
from repro.kernels import autotune
from repro.kernels.approx_gemm import approx_gemm, approx_gemm_batched

# Best-of-N timing: the least-interference estimator, so the gated
# batched-vs-vmapped ratio is reproducible across CI runs.
time_fn_best = partial(time_fn, best=True)

SHAPES = [
    (8, 256, 256, 256),   # acceptance shape: batched must beat vmapped 2-D
    (4, 128, 512, 128),   # deep contraction (weight-grad-like)
    (16, 64, 256, 64),    # many small heads (attention-score-like)
]
# Smoke = the acceptance shape: compute-dominated, so the gated
# batched-vs-vmapped ratio is reproducible across CI runs (tiny shapes
# are dispatch-overhead noise and flipped between 0.6x and 2.7x).
SMOKE_SHAPES = [(8, 256, 256, 256)]


def bench_shape(B, m, k, n, *, mult, lut, plut, iters, do_autotune):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((B, m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, k, n)), jnp.float32)
    M = mult.mantissa_bits
    tag = f"B{B}_m{m}_k{k}_n{n}"
    flops = 2.0 * B * m * k * n

    def gflops(t):
        return f"{flops / t / 1e9:.2f}GFLOP/s"

    if do_autotune:
        won = autotune.autotune("gemm3d", a, b, plut if plut is not None
                                else lut, M, iters=max(1, iters - 1))
        emit(f"autotune_{tag}", 0.0,
             f"bm{won.bm}_bn{won.bn}_bk{won.bk}_c{won.chunk}")

    native = jax.jit(lambda a, b: jnp.matmul(
        a, b, preferred_element_type=jnp.float32))
    t_native = time_fn_best(native, a, b, iters=iters)
    emit(f"native_{tag}", t_native, gflops(t_native))

    surrogate = jax.jit(lambda a, b: jnp.matmul(
        jnp_truncate_mantissa(a, M), jnp_truncate_mantissa(b, M),
        preferred_element_type=jnp.float32))
    t_sur = time_fn_best(surrogate, a, b, iters=iters)
    emit(f"surrogate_{tag}", t_sur, gflops(t_sur))

    klut = plut if plut is not None else lut
    batched = jax.jit(lambda a, b: approx_gemm_batched(a, b, klut, M))
    t_bat = time_fn_best(batched, a, b, iters=iters)
    emit(f"amsim_batched_{tag}", t_bat,
         f"{gflops(t_bat)}_x{t_bat / t_native:.1f}_vs_native",
         norm=t_bat / t_native)

    # The pre-engine fallback: vmap of the 2-D kernel at its 2-D defaults.
    cfg2d = autotune.get_block_config("gemm2d", *a.shape[1:], b.shape[-1], M)
    vmapped = jax.jit(jax.vmap(lambda a, b: approx_gemm(
        a, b, lut, M, bm=cfg2d.bm, bn=cfg2d.bn, bk=cfg2d.bk,
        chunk=cfg2d.chunk)))
    t_vm = time_fn_best(vmapped, a, b, iters=iters)
    emit(f"amsim_vmapped2d_{tag}", t_vm,
         f"{gflops(t_vm)}_x{t_vm / t_native:.1f}_vs_native",
         norm=t_vm / t_native)

    emit(f"batched_vs_vmapped_speedup_{tag}", 0.0,
         f"{t_vm / t_bat:.2f}x_batched_over_vmapped", norm=t_bat / t_vm,
         gate=True)
    return t_bat, t_vm


def main(smoke: bool = False, do_autotune: bool = False) -> None:
    mult = get_multiplier("afm16")
    lut = jnp.asarray(get_lut(mult))
    packed = get_packed_lut(mult)
    plut = jnp.asarray(packed) if packed is not None else None
    shapes = SMOKE_SHAPES if smoke else SHAPES
    iters = 5 if smoke else 3  # smoke feeds the CI gate: best-of-5
    for B, m, k, n in shapes:
        bench_shape(B, m, k, n, mult=mult, lut=lut, plut=plut,
                    iters=iters, do_autotune=do_autotune)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="acceptance shape only, best-of-5 timing (CI)")
    ap.add_argument("--autotune", action="store_true",
                    help="run the block-size sweep per shape first")
    args = ap.parse_args()
    main(smoke=args.smoke, do_autotune=args.autotune)
