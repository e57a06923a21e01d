"""Shared bricks for every LUT approx-kernel family (GEMM / conv / attention).

The fused engines (``approx_gemm``, ``approx_conv``, ``approx_attention``,
``decode_chain``) all reduce to the same inner operation: look up the
mantissa-product LUT for every (A, B) operand pair of a rank-``chunk``
brick and accumulate in FP32 (the paper's AMSim device function inlined
into the consuming GEMM, §V-B).  This module holds that brick, the LUT
operand it reads, and the small layout helpers every family needs, so a
numerics fix lands in one place.

The brick is written in the form Mosaic lowers for the TPU (docs/kernels.md,
"The TPU-legal brick"), and the CPU tests run the same code in Pallas
interpret mode:

  * the contraction walks one k at a time.  A's column k is broadcast
    across lanes by a lane gather of its aligned 128-lane block; B's row
    k is a dynamic sublane load.  Both operands sit in scoped VMEM
    scratch, so every dynamic slice is a ref slice;
  * for tables with M <= 7 the 2^M x 2^M LUT is a (128, 128) matrix.
    A one-hot matmul on the MXU selects the rows for A's top-M bits (exact:
    every output sums one non-zero entry, and each entry is a byte held
    exactly in bf16), then a lane gather by B's top-M bits picks the
    columns.  Canonical uint32 tables carry three byte planes, packed
    uint16 tables one;
  * wider tables (the M > 7 cross-format families) keep the 1-D table
    and ``jnp.take``, which only interpret mode lowers: ``kernel_lut``
    raises for them when compiling for the chip;
  * the FP32 fold is explicit: each chunk sums its k terms in order from
    +0.0, and the chunk sum is added to the accumulator — the same order
    ``kernels/ref.py`` uses, on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.amsim import amsim_from_entry, mantissa_top
from repro.core.float_bits import jnp_float

LANES = 128
SUBLANES = 8
# Widest table whose 2^M x 2^M matrix fits one 128-lane row per A value.
MATRIX_MAX_M = 7


def resolve_interpret(interpret: bool | None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    Unset means: compiled on a TPU backend, interpreted elsewhere (the
    CPU test path).  Interpret mode on a TPU backend would hide the chip
    behind the interpreter, so it is refused.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: the kernels "
                         "compile for the chip there")
    return bool(interpret)


def lut_matrix(lut, M: int):
    """The (128, planes*128) bf16 byte-plane matrix of a 1-D LUT.

    Row = A's top-M mantissa bits, column = B's (``amsim._amsim``'s
    index, reshaped), zero-padded to 128 x 128.  Packed uint16 entries
    are below 2^(M+1) <= 256 and need one plane; canonical uint32
    entries need bits 0-23 (carry and mantissa), three planes.
    """
    n = 1 << M
    packed = lut.dtype == jnp.uint16
    t = jnp.asarray(lut).astype(jnp.int32).reshape(n, n)
    t = jnp.pad(t, ((0, LANES - n), (0, LANES - n)))
    planes = [(t >> (8 * p)) & 0xFF for p in range(1 if packed else 3)]
    return jnp.concatenate(planes, axis=1).astype(jnp.bfloat16)


def kernel_lut(lut, M: int, interpret: bool):
    """The LUT operand a kernel hands its brick.

    M <= 7: the ``lut_matrix`` form, which lowers on the chip.  Wider
    tables stay 1-D for the interpret-mode gather and raise when the
    kernel compiles for the chip — never a quiet detour to the oracle.
    """
    lut = jnp.asarray(lut)
    lut = lut if lut.dtype == jnp.uint16 else lut.astype(jnp.uint32)
    if M <= MATRIX_MAX_M:
        return lut_matrix(lut, M)
    if not interpret:
        raise NotImplementedError(
            f"M={M} LUTs have no TPU lowering (the LUT brick takes "
            f"M <= {MATRIX_MAX_M}); run this multiplier in interpret mode "
            f"or under mode='amsim_jnp'")
    return lut


def lut_spec(lut) -> pl.BlockSpec:
    """Whole-LUT BlockSpec with a constant index map: one VMEM-resident
    table broadcast across any grid."""
    return pl.BlockSpec(lut.shape, lambda *_: (0,) * lut.ndim)


def _lut_is_packed(lut) -> bool:
    if lut.ndim == 2:
        return lut.shape[1] == LANES
    return lut.dtype == jnp.uint16


def _lookup(lut, ua, ub, M: int, bmp: int, bnp: int):
    """LUT entries (bmp, bnp) for A words ``ua`` (bmp, 128; every row
    constant) against B words ``ub`` (bmp, bnp; every column constant)."""
    at = mantissa_top(ua, M, jnp).astype(jnp.int32)
    bt = mantissa_top(ub, M, jnp).astype(jnp.int32)
    if lut.ndim == 1:  # wide table: interpret mode only (kernel_lut)
        idx = (_tile_lanes(at, bnp) << M) | bt
        return jnp.take(lut, idx).astype(jnp.uint32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bmp, LANES), 1)
    onehot = (at == lane).astype(jnp.bfloat16)
    rows = jnp.dot(onehot, lut, preferred_element_type=jnp.float32)
    rows = rows.astype(jnp.int32)             # exact: bytes < 256
    entry = None
    for p in range(lut.shape[1] // LANES):
        plane = rows[:, p * LANES:(p + 1) * LANES]
        cols = [jnp.take_along_axis(plane, bt[:, q:q + LANES], axis=1,
                                    mode="promise_in_bounds")
                for q in range(0, bnp, LANES)]
        e = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
        entry = e if entry is None else entry | (e << (8 * p))
    return entry.astype(jnp.uint32)


def _tile_lanes(x, width: int):
    """(r, 128) -> (r, width) by repeating the 128-lane block."""
    if width == LANES:
        return x
    return jnp.concatenate([x] * (width // LANES), axis=1)


def _gather_gemm_tile(a, b, lut, acc, *, M: int, chunk: int):
    """Rank-``chunk`` gather-GEMM update of the f32 accumulator tile.

    a (bm, bk) @ b (bk, bn) with the product simulated per element by
    the LUT operand of ``kernel_lut``; ``chunk`` must divide bk (see
    :func:`best_chunk`).  Fold: acc + (((+0 + p_0) + p_1) + ...) per
    chunk of ``chunk`` consecutive k, chunks in order.
    """
    bm, bk = a.shape
    bn = b.shape[1]
    bmp, bnp = _ceil_to(bm, SUBLANES), _ceil128(bn)
    packed = _lut_is_packed(lut)

    def run(a_scr, b_scr):
        a_scr[:bm, :bk] = jax.lax.bitcast_convert_type(a, jnp.uint32)
        b_scr[:bk, :bn] = jax.lax.bitcast_convert_type(b, jnp.uint32)

        def product(k):
            blk = a_scr[:, pl.ds(pl.multiple_of(k // LANES * LANES, LANES),
                                 LANES)]
            ua = jnp.take_along_axis(
                blk, jnp.full((bmp, LANES), k % LANES, jnp.int32), axis=1,
                mode="promise_in_bounds")
            # Broadcast B's row before slicing it: Mosaic lowers a
            # sublane broadcast of the whole row, not of its lane slices.
            ub = jnp.broadcast_to(b_scr[pl.ds(k, 1), :], (bmp, bnp))
            entry = _lookup(lut, ua, ub, M, bmp, bnp)
            return jnp_float(amsim_from_entry(
                _tile_lanes(ua, bnp), ub, entry, M, jnp, packed))

        def chunk_sum(i, acc):
            s = jax.lax.fori_loop(
                0, chunk, lambda j, s: s + product(i * chunk + j),
                jnp.zeros((bmp, bnp), jnp.float32))
            return acc + s[:bm, :bn]

        return jax.lax.fori_loop(0, bk // chunk, chunk_sum, acc)

    return pl.run_scoped(run,
                         pltpu.VMEM((bmp, _ceil128(bk)), jnp.uint32),
                         pltpu.VMEM((_ceil_to(bk, SUBLANES), bnp), jnp.uint32))


def _rms_scale(x, eps: float):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return jax.lax.rsqrt(var + eps)


def rmsnorm_expr(x, g, eps: float):
    """``(x * rsqrt(mean(x^2) + eps)) * g`` — THE RMSNorm forward, shared
    verbatim by the decode-chain kernels, their oracles and
    models/layers.rmsnorm (bit-for-bit on one backend)."""
    return (x * _rms_scale(x, eps)) * g


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, g, eps: float):
    """Differentiable :func:`rmsnorm_expr` whose backward gives the same
    bits in every program it is compiled into.

    Autodiff's own backward leaves two choices to the compiler, and
    XLA:CPU makes them per fusion: it contracts a multiply feeding an add
    into one FMA, and it reassociates a fused reduction.  The same
    gradient then differed in its last bits between the decode chain's
    recompute-through-oracle VJP and plain autodiff of the oracle.  Here
    every product is materialised (``optimization_barrier``) before it
    meets an add or a reduction, so neither choice exists.
    """
    return rmsnorm_expr(x, g, eps)


def _rms_norm_fwd(x, g, eps):
    r = _rms_scale(x, eps)
    return (x * r) * g, (x, g, r)


def _rms_norm_bwd(eps, res, dy):
    # y = x r g, r = (mean(x^2) + eps)^-1/2:
    #   dx = (dy g) r - x (sum(dy g x) r^3 / d),  dg = sum_rows(dy x r).
    x, g, r = res
    bar = jax.lax.optimization_barrier
    xf = x.astype(jnp.float32)
    t = bar(dy * g)
    s = bar(jnp.sum(bar(t * xf), axis=-1, keepdims=True))
    dx = bar(t * r) - bar(xf * (s * (r * r * r) / x.shape[-1]))
    dg = jnp.sum(bar(dy * bar(xf * r)), axis=tuple(range(dy.ndim - 1)))
    return dx.astype(x.dtype), dg.astype(g.dtype)


rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def attention_mask(q_pos, k_pos, *, causal: bool, window: int):
    """(..., S, T) bool validity mask — THE attention mask.

    One definition shared by the fused kernel, the einsum reference and
    the full-head einsum path: the fused/einsum bit-compatibility
    contract requires all lowerings to mask identically, so none may
    carry its own copy.  A key is valid iff its absolute position is
    non-negative (negative = unwritten ring-buffer slot), not after the
    query (``causal``) and inside the sliding ``window`` (0 = off).

    Positions may be 1-D (``(S,)``/``(T,)`` -> ``(S, T)``, the ring
    buffer's shared layout) or carry a leading batch dim (``(B, S)`` /
    ``(B, T)`` -> ``(B, S, T)``) for the paged serving cache, where
    every slot sits at its own decode position (docs/serving.md).
    """
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    shape = jnp.broadcast_shapes(qp.shape, kp.shape)
    mask = jnp.broadcast_to(kp >= 0, shape)
    if causal:
        mask = mask & (kp <= qp)
    if window:
        mask = mask & (kp > qp - window)
    return mask


def _pad_to(x, *mults):
    """Zero-pad the trailing len(mults) dims of x up to the given multiples."""
    lead = x.ndim - len(mults)
    pads = [(0, 0)] * lead + [
        (0, (-x.shape[lead + i]) % m) for i, m in enumerate(mults)
    ]
    if any(p for _, p in pads):
        x = jnp.pad(x, pads)
    return x


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _ceil128(x: int) -> int:
    return _ceil_to(x, 128)


def best_chunk(chunk: int, total: int) -> int:
    """The divisor of ``total`` closest to ``chunk`` in log-space,
    capped at ``2 * chunk``.

    The gather fori_loop runs ``total // chunk`` steps, so chunk MUST
    divide total or tail elements are silently dropped.  The old policy
    ("largest value <= chunk that divides total") degrades to chunk=1 —
    a per-element loop, catastrophic — whenever total has no divisor
    just below chunk (e.g. total=96 has none in (48, 96)).  Selecting
    from the full divisor set instead may round *up* to a slightly
    larger brick; the 2x cap bounds the chunk a snapped-up request can
    reach (a prime total still falls back to 1 — there is no divisor to
    rescue it).  Ties prefer the larger divisor.  Static at trace time.
    """
    total = max(1, int(total))
    chunk = max(1, int(chunk))
    best, best_cost = 1, float("inf")
    for d in range(1, int(total ** 0.5) + 1):
        if total % d:
            continue
        for cand in (d, total // d):
            if cand > 2 * chunk:
                continue
            big, small = max(cand, chunk), min(cand, chunk)
            cost = big / small  # log-distance monotone; >= 1, 1 == exact
            if cost < best_cost or (cost == best_cost and cand > best):
                best, best_cost = cand, cost
    return best
