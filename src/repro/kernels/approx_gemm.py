"""Pallas TPU kernels: LUT-simulated approximate GEMM (paper §V-B + §VI-D).

TPU adaptation of the paper's custom CUDA GEMM with AMSim device function:

  * the mantissa-product LUT lives in **VMEM** as a pallas_call operand
    (the TPU analogue of the paper's texture-memory placement — small,
    read-only, heavily reused: 64 KiB for M=7 vs ~16 MiB VMEM).  With the
    packed uint16 layout (``lutgen.pack_lut``) the footprint halves again,
    freeing VMEM for larger operand tiles;
  * HBM->VMEM movement is expressed with explicit BlockSpec tiling
    (bm x bk and bk x bn operand tiles, bm x bn f32 accumulator scratch),
    the TPU analogue of the paper's 16x16 shared-memory tiles;
  * the inner product is computed on the **VPU** (vector unit): a table
    gather + integer sign/exponent arithmetic per element, accumulated in
    FP32.  A lookup-based multiply cannot enter the MXU (systolic array
    of fused multipliers) — this is the structural cost of *simulating*
    non-native hardware, identical in kind to the paper's GEMM running
    ~2x slower than cuBLAS (Fig. 6).  The point preserved from the paper
    is that the cost is **independent of the multiplier design** — any
    model compiles to the same gather.

Two entry points:

``approx_gemm``          (m, k) @ (k, n).  Grid (m/bm, n/bn, k/bk), the
                         contraction dimension innermost ("arbitrary"
                         semantics) so the accumulator tile stays resident
                         in VMEM across k-steps.
``approx_gemm_batched``  (B, m, k) @ (B, k, n).  Grid (B, m/bm, n/bn,
                         k/bk): the batch dimension is the outermost
                         ("parallel") grid axis and the LUT block index
                         is constant, so the one table is broadcast to
                         every batch element instead of being re-staged
                         per element as the vmap-over-pallas_call
                         fallback does.

Block sizes default to the autotuner's cached winner for the (shape
bucket, M, backend) — see ``kernels/autotune.py``; explicit bm/bn/bk/chunk
arguments override.  Operand tiles are multiples of 128 to align MXU/VPU
lanes and HBM burst transfers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune
from repro.kernels.common import (_ceil128, _gather_gemm_tile, _pad_to,
                                  best_chunk, kernel_lut, lut_spec,
                                  resolve_interpret, tagged_pallas_call)


def _amsim_kernel(a_ref, b_ref, lut_ref, o_ref, acc_ref, *,
                  M: int, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = _gather_gemm_tile(
        a_ref[...], b_ref[...], lut_ref[...], acc_ref[...],
        M=M, chunk=chunk)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _amsim_kernel_batched(a_ref, b_ref, lut_ref, o_ref, acc_ref, *,
                          M: int, chunk: int):
    # Block shapes carry a leading singleton batch axis; k is grid dim 3.
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = _gather_gemm_tile(
        a_ref[0], b_ref[0], lut_ref[...], acc_ref[...],
        M=M, chunk=chunk)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _flush():
        o_ref[0] = acc_ref[...]


def _resolve(kind, m, k, n, M, batch, bm, bn, bk, chunk, interpret,
             mult=None):
    """Fill unset tiling params from the autotune cache.

    Autotuned/default block sizes are clamped to the 128-rounded problem
    dims (a cache entry covers a pow2 bucket, so e.g. bk=256 must not pad
    a k=32 call out to 256 — 8x wasted gathers); explicit arguments are
    taken as-is.  chunk is snapped to the nearest divisor of bk
    (``best_chunk``: the gather fori_loop drops tail k-elements
    otherwise, and a cached chunk must never silently degrade toward
    chunk=1 when bk has no smaller divisor nearby).
    """
    interpret = resolve_interpret(interpret)
    if None in (bm, bn, bk, chunk):
        cfg = autotune.get_block_config(kind, m, k, n, M, batch=batch,
                                        mult=mult)
        bm = min(cfg.bm, _ceil128(m)) if bm is None else bm
        bn = min(cfg.bn, _ceil128(n)) if bn is None else bn
        bk = min(cfg.bk, _ceil128(k)) if bk is None else bk
        chunk = cfg.chunk if chunk is None else chunk
    return bm, bn, bk, best_chunk(chunk, bk), interpret


@functools.partial(
    jax.jit,
    static_argnames=("M", "bm", "bn", "bk", "chunk", "interpret", "tag"))
def _approx_gemm_impl(a, b, lut, M, *, bm, bn, bk, chunk, interpret,
                      tag=None):
    m, k = a.shape
    n = b.shape[1]
    a = _pad_to(a.astype(jnp.float32), bm, bk)
    b = _pad_to(b.astype(jnp.float32), bk, bn)
    mp, kp = a.shape
    np_ = b.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)
    out = tagged_pallas_call(
        "_approx_gemm_impl", tag,
        functools.partial(_amsim_kernel, M=M, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            lut_spec(lut),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b, lut)
    return out[:m, :n]


def approx_gemm(
    a,
    b,
    lut,
    M: int,
    *,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    chunk: int | None = None,
    interpret: bool | None = None,
    mult: str | None = None,
    tag: tuple[str, str] | None = None,
):
    """LUT-simulated GEMM: (m, k) @ (k, n) -> (m, n), FP32 accumulate.

    ``mult`` is the resolved multiplier name, used only to key the
    autotune cache (per-multiplier tilings under mixed policy tables).
    ``tag`` is the launch's ``(site, pass)`` (``common.tagged_pallas_call``):
    a static argument, so same-shaped calls at two sites trace apart and
    each keeps its own tag.

    ``lut`` may be the canonical uint32 table or the packed uint16 one
    (detected by dtype).  Zero padding is safe: AMSim flushes
    zero-exponent operands to zero (Alg. 2 line 13), so padded rows/cols
    contribute exactly 0.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk, chunk, interpret = _resolve(
        "gemm2d", m, k, n, M, 0, bm, bn, bk, chunk, interpret, mult)
    lut = kernel_lut(lut, M, interpret)
    return _approx_gemm_impl(a, b, lut, M, bm=bm, bn=bn, bk=bk,
                             chunk=chunk, interpret=interpret, tag=tag)


@functools.partial(
    jax.jit,
    static_argnames=("M", "bm", "bn", "bk", "chunk", "interpret", "tag"))
def _approx_gemm_batched_impl(a, b, lut, M, *, bm, bn, bk, chunk, interpret,
                              tag=None):
    B, m, k = a.shape
    n = b.shape[2]
    a = _pad_to(a.astype(jnp.float32), bm, bk)
    b = _pad_to(b.astype(jnp.float32), bk, bn)
    mp, kp = a.shape[1:]
    np_ = b.shape[2]
    grid = (B, mp // bm, np_ // bn, kp // bk)
    out = tagged_pallas_call(
        "_approx_gemm_batched_impl", tag,
        functools.partial(_amsim_kernel_batched, M=M, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda bb, i, j, kk: (bb, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda bb, i, j, kk: (bb, kk, j)),
            # LUT block index is constant: one VMEM-resident table is
            # broadcast across the whole batch grid axis.
            lut_spec(lut),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(a, b, lut)
    return out[:, :m, :n]


def approx_gemm_batched(
    a,
    b,
    lut,
    M: int,
    *,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    chunk: int | None = None,
    interpret: bool | None = None,
    mult: str | None = None,
    tag: tuple[str, str] | None = None,
):
    """Batched LUT-simulated GEMM: (B, m, k) @ (B, k, n) -> (B, m, n).

    One 4-D-grid pallas_call — the batch axis is a parallel grid
    dimension with the LUT broadcast across it, replacing the
    vmap-over-pallas_call / lax.map fallbacks.  Accepts uint32 or packed
    uint16 LUTs (dtype-detected); accumulation is FP32 (paper §VII).
    ``tag`` as for :func:`approx_gemm`.
    """
    assert a.ndim == 3 and b.ndim == 3, (a.shape, b.shape)
    B, m, k = a.shape
    B2, k2, n = b.shape
    assert B == B2 and k == k2, (a.shape, b.shape)
    bm, bn, bk, chunk, interpret = _resolve(
        "gemm3d", m, k, n, M, B, bm, bn, bk, chunk, interpret, mult)
    lut = kernel_lut(lut, M, interpret)
    return _approx_gemm_batched_impl(a, b, lut, M, bm=bm, bn=bn, bk=bk,
                                     chunk=chunk, interpret=interpret,
                                     tag=tag)
