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
    gather and two multiplies per element (``common.factored_product``),
    accumulated in FP32.  A lookup-based multiply cannot enter the MXU (systolic array
    of fused multipliers) — this is the structural cost of *simulating*
    non-native hardware, identical in kind to the paper's GEMM running
    ~2x slower than cuBLAS (Fig. 6).  The point preserved from the paper
    is that the cost is **independent of the multiplier design** — any
    model compiles to the same gather.

Entry points:

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
``approx_gemm_grouped``  rows sorted by expert (``grouped_layout``), each
                         expert's group padded to ``GROUP_TILE`` rows,
                         times that expert's (k, n) weight block: a
                         scalar-prefetched tile -> expert map picks each
                         row tile's block, and tiles past the last group
                         do no work.  ``approx_gemm_grouped_dw`` is its
                         weight gradient, each expert's x^T @ dy folded
                         over that expert's row tiles only.  Both launch
                         as ``_approx_gemm_grouped_impl``.

Block sizes default to the autotuner's cached winner for the (shape
bucket, M, backend), and for the 2-D and grouped kernels' output tile on a
miss to the shape rule ``autotune.tile_2d`` — see ``kernels/autotune.py``;
explicit bm/bn/bk/chunk arguments override.  Lane extents are multiples of
128 and row extents of 16 (the brick's bf16 one-hot operand).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels import autotune
from repro.kernels.common import (_ceil128, _ceil_to, _gather_gemm_tile,
                                  _pad_to, best_chunk, kernel_lut, lut_spec,
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
    obs.route("gemm.tile.gemm2d", f"{bm}x{bn}")
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
    obs.route("gemm.tile.gemm3d", f"{bm}x{bn}")
    lut = kernel_lut(lut, M, interpret)
    return _approx_gemm_batched_impl(a, b, lut, M, bm=bm, bn=bn, bk=bk,
                                     chunk=chunk, interpret=interpret,
                                     tag=tag)


# =====================================================================
# Grouped GEMM over rows sorted by expert (the dropless MoE layer)
# =====================================================================

# Rows of one tile of the grouped GEMM.  Each expert's group of sorted
# rows is padded to a multiple of it, so every row tile belongs to one
# expert; it is also the contraction block of the weight gradient.
GROUP_TILE = 128


class GroupedRows(NamedTuple):
    """Where each routed row sits in the sorted, padded layout that
    :func:`approx_gemm_grouped` reads (built by :func:`grouped_layout`).

    ``rows`` (T*k,): the sorted row of each (token, choice) assignment, in
    assignment order.  ``sizes`` (E,): each expert's row count padded to
    ``GROUP_TILE``.  ``tile_expert`` (n_tiles,): the expert of each row
    tile; a tile past the last group carries the last group's expert, so
    its blocks are the ones already in VMEM.  ``live_tiles`` (1,): how
    many tiles hold a group."""
    rows: jax.Array
    sizes: jax.Array
    tile_expert: jax.Array
    live_tiles: jax.Array


def grouped_rows_bound(n_rows: int, n_experts: int) -> int:
    """Static row count of the sorted layout: every routed row, plus each
    group's padding to the tile, rounded up to the tile."""
    return _ceil_to(n_rows + n_experts * (GROUP_TILE - 1), GROUP_TILE)


def grouped_layout(experts, n_experts: int) -> GroupedRows:
    """The sorted layout of assignments whose experts are ``experts``
    (T*k,) int32: stable by expert, so within a group rows keep their
    assignment order; groups in expert order, each padded to the tile."""
    n = experts.shape[0]
    order = jnp.argsort(experts, stable=True)
    counts = jnp.zeros((n_experts,), jnp.int32).at[experts].add(1)
    sizes = (counts + GROUP_TILE - 1) // GROUP_TILE * GROUP_TILE
    ends = jnp.cumsum(sizes)
    starts, firsts = ends - sizes, jnp.cumsum(counts) - counts
    pos = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    rows = starts[experts] + pos - firsts[experts]
    live = ends[-1] // GROUP_TILE
    tile = jnp.arange(grouped_rows_bound(n, n_experts) // GROUP_TILE,
                      dtype=jnp.int32)
    tile = jnp.minimum(tile, live - 1) * GROUP_TILE
    tile_expert = jnp.searchsorted(ends, tile, side="right").astype(jnp.int32)
    return GroupedRows(rows.astype(jnp.int32), sizes, tile_expert,
                       live.reshape(1).astype(jnp.int32))


def _grouped_kernel(te_ref, live_ref, rows_ref, a_ref, b_ref, lut_ref, o_ref,
                    acc_ref, *, M: int, chunk: int, strips: int):
    # Grid (row strip, n/bn, k/bk), ``strips`` strips to a tile; a strip
    # of a tile past the last group writes zeros.
    del te_ref, rows_ref
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(pl.program_id(0) // strips < live_ref[0])
    def _step():
        acc_ref[...] = _gather_gemm_tile(
            a_ref[...], b_ref[0], lut_ref[...], acc_ref[...],
            M=M, chunk=chunk)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _grouped_dw_kernel(te_ref, live_ref, rows_ref, a_ref, b_ref, lut_ref,
                       o_ref, *, M: int, chunk: int):
    # Grid (k/bm, n/bn, tile): an expert's row tiles are consecutive, so
    # its output block stays resident across them and is folded in place,
    # from +0.0 at its first tile.
    del rows_ref
    t = pl.program_id(2)
    first = (t == 0) | (te_ref[t] != te_ref[jnp.maximum(t - 1, 0)])

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(t < live_ref[0])
    def _step():
        o_ref[0] = _gather_gemm_tile(a_ref[...], b_ref[...], lut_ref[...],
                                     o_ref[0], M=M, chunk=chunk)


@functools.partial(
    jax.jit,
    static_argnames=("M", "dw", "n_experts", "bm", "bn", "bk", "chunk",
                     "interpret", "tag"))
def _approx_gemm_grouped_impl(a, b, groups, lut, M, *, dw, n_experts, bm, bn,
                              bk, chunk, interpret, tag=None):
    """``dw`` False: a (R, k) sorted rows @ b (E, k, n) -> (R, n), in row
    strips of ``bm`` rows, each inside one row tile.
    ``dw`` True: a (k, R) the sorted rows transposed, b (R, n) their
    output gradient -> (E, k, n), zero for an expert with no rows."""
    tm = GROUP_TILE
    te, live = groups.tile_expert, groups.live_tiles
    n_tiles = te.shape[0]
    if dw:
        a = _pad_to(a.astype(jnp.float32), bm, 1)
        b = _pad_to(b.astype(jnp.float32), 1, bn)
        kp, np_ = a.shape[0], b.shape[1]
        grid = (kp // bm, np_ // bn, n_tiles)
        row = lambda t, live: jnp.where(t < live[0], t, live[0] - 1)
        in_specs = [
            pl.BlockSpec((bm, tm), lambda i, j, t, te, live: (i, row(t, live))),
            pl.BlockSpec((tm, bn), lambda i, j, t, te, live: (row(t, live), j)),
        ]
        out_spec = pl.BlockSpec((1, bm, bn),
                                lambda i, j, t, te, live: (te[t], i, j))
        out_shape = (n_experts, kp, np_)
        kernel, scratch = _grouped_dw_kernel, []
    else:
        a = _pad_to(a.astype(jnp.float32), 1, bk)
        b = _pad_to(b.astype(jnp.float32), bk, bn)
        kp, np_ = b.shape[1:]
        nj, nk = np_ // bn, kp // bk
        strips = tm // bm
        grid = (n_tiles * strips, nj, nk)
        # A strip of a tile past the last group keeps the blocks of the
        # last live step, so the pipeline fetches nothing for it.
        live_ = lambda s, live, x, last: jnp.where(s // strips < live[0], x,
                                                   last)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda s, j, kk, te, live: (
                live_(s, live, s, live[0] * strips - 1),
                live_(s, live, kk, nk - 1))),
            pl.BlockSpec((1, bk, bn), lambda s, j, kk, te, live: (
                te[s // strips], live_(s, live, kk, nk - 1),
                live_(s, live, j, nj - 1))),
        ]
        out_spec = pl.BlockSpec((bm, bn), lambda s, j, kk, te, live: (s, j))
        out_shape = (a.shape[0], np_)
        kernel = functools.partial(_grouped_kernel, strips=strips)
        scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    # ``rows`` stays in HBM, untouched: it carries the routed-row count
    # in the launch's operand shapes (bench/work/approx_gemm_grouped_impl).
    out = tagged_pallas_call(
        "_approx_gemm_grouped_impl", tag,
        functools.partial(kernel, M=M, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + in_specs
            + [lut_spec(lut)],
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(te, live, groups.rows, a, b, lut)
    return out


def approx_gemm_grouped(
    a,
    b,
    groups: GroupedRows,
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
    """LUT-simulated grouped GEMM: row tile t of a (R, k) times expert
    ``groups.tile_expert[t]``'s block of b (E, k, n) -> (R, n), FP32
    accumulate; rows of tiles past the last group come back zero.

    Each output row folds its k products exactly as the E-batched kernel
    folds that expert's rows (``bk`` and ``chunk`` from the same ``gemm3d``
    tiling bucket), so a row's bits do not depend on how the rows were
    grouped.  The output tile, ``bm`` rows (a divisor of the row tile) by
    ``bn`` lanes, is the 2-D kernel's (``autotune.tile_2d``) and moves no
    bit.  ``tag`` as for :func:`approx_gemm`."""
    R, k = a.shape
    E, k2, n = b.shape
    assert k == k2 and R % GROUP_TILE == 0, (a.shape, b.shape)
    rule_bm, rule_bn = autotune.tile_2d(R, n)
    bm = min(rule_bm, GROUP_TILE) if bm is None else bm
    bn = rule_bn if bn is None else bn
    assert GROUP_TILE % bm == 0, bm
    _, _, bk, chunk, interpret = _resolve(
        "gemm3d", GROUP_TILE, k, n, M, E, bm, bn, bk, chunk, interpret, mult)
    obs.route("gemm.tile.grouped", f"{bm}x{bn}")
    lut = kernel_lut(lut, M, interpret)
    out = _approx_gemm_grouped_impl(
        a, b, groups, lut, M, dw=False, n_experts=E, bm=bm, bn=bn,
        bk=bk, chunk=chunk, interpret=interpret, tag=tag)
    return out[:, :n]


def approx_gemm_grouped_dw(
    a,
    g,
    groups: GroupedRows,
    lut,
    M: int,
    n_experts: int,
    *,
    bm: int | None = None,
    bn: int | None = None,
    chunk: int | None = None,
    interpret: bool | None = None,
    mult: str | None = None,
    tag: tuple[str, str] | None = None,
):
    """Weight gradient of :func:`approx_gemm_grouped`: for each expert e,
    a[rows of e]^T @ g[rows of e] -> (E, k, n), with a (R, k) the sorted
    rows and g (R, n) their output gradient.

    The contraction over an expert's rows folds as the 2-D brick folds k:
    chunks of ``chunk`` consecutive rows, each summed in order from +0.0,
    added to the accumulator in row order, from the expert's first tile
    to its last; an expert with no rows gets zeros.  The output tile is
    the 2-D kernel's (``autotune.tile_2d``)."""
    R, k = a.shape
    assert g.shape[0] == R and R % GROUP_TILE == 0, (a.shape, g.shape)
    n = g.shape[1]
    rule_bm, rule_bn = autotune.tile_2d(k, n)
    bm = rule_bm if bm is None else bm
    bn = rule_bn if bn is None else bn
    _, _, _, chunk, interpret = _resolve(
        "gemm3d", k, GROUP_TILE, n, M, n_experts, bm, bn, GROUP_TILE, chunk,
        interpret, mult)
    obs.route("gemm.tile.grouped_dw", f"{bm}x{bn}")
    lut = kernel_lut(lut, M, interpret)
    out = _approx_gemm_grouped_impl(
        a.T, g, groups, lut, M, dw=True, n_experts=n_experts, bm=bm, bn=bn,
        bk=GROUP_TILE, chunk=chunk, interpret=interpret, tag=tag)
    return jnp.where((groups.sizes > 0)[:, None, None], out[:, :k, :n], 0.0)
