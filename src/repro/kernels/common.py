"""Shared bricks for every LUT approx-kernel family (GEMM / conv / attention).

The fused engines (``approx_gemm``, ``approx_conv``, ``approx_attention``)
all reduce to the same inner operation: look up the
mantissa-product LUT for every (A, B) operand pair of a rank-``chunk``
brick and accumulate in FP32 (the paper's AMSim device function inlined
into the consuming GEMM, §V-B).  This module holds that brick, the LUT
operand it reads, and the small layout helpers every family needs, so a
numerics fix lands in one place.

The brick is written in the form Mosaic lowers for the TPU (docs/kernels.md,
"The TPU-legal brick"), and the CPU tests run the same code in Pallas
interpret mode:

  * the operands are decoded once per tile, into VMEM scratch: each
    word keeps its sign and exponent, with its table index (top M
    mantissa bits) in the cleared mantissa field.  The contraction walks
    one k at a time: A's column k is broadcast across lanes by a lane
    gather of its aligned 128-lane block, once per tile row for every
    128-lane block of the output tile; each lane block of B's row k is a
    dynamic sublane load, so every dynamic slice is a ref slice;
  * for tables with M <= 7 the 2^M x 2^M LUT is a (128, 128) matrix.
    A one-hot matmul on the MXU selects the rows for A's indices (exact:
    every output sums one non-zero entry, held exactly in bf16), then a
    lane gather by B's indices picks the columns.  A packed uint16 table
    becomes one plane of float values V = 2^carry * (1 + mnt / 2^M), and
    each product is ``factored_product``: the operands' signed powers of
    two times V, flushed below 2^-126 — Alg. 2 bit for bit on finite
    words.  A tile holding an inf or NaN word, and a canonical uint32
    table (three byte planes), take the integer form instead,
    ``amsim_from_entry``;
  * wider tables (the M > 7 cross-format families) keep the 1-D table
    and ``jnp.take``, which only interpret mode lowers: ``kernel_lut``
    raises for them when compiling for the chip;
  * the FP32 fold is explicit: each chunk sums its k terms in order from
    +0.0, and the chunk sum is added to the accumulator — the same order
    ``kernels/ref.py`` uses, on every backend.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.amsim import amsim_from_entry, mantissa_top
from repro.core.float_bits import EXP_MASK, SIGN_MASK, jnp_float

LANES = 128
SUBLANES = 8
_SIGN_EXP = SIGN_MASK | EXP_MASK
# k-steps of a chunk the factored brick runs as one straight-line block.
_UNROLL = 8
_MIN_NORMAL = float(np.finfo(np.float32).tiny)     # 2^-126
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
    """The (128, planes*128) bf16 matrix of a 1-D LUT.

    Row = A's top-M mantissa bits, column = B's (``amsim._amsim``'s
    index, reshaped), zero-padded to 128 x 128.  A packed uint16 table
    becomes one plane of values ``V = 2^carry * (1 + mnt / 2^M)``, carry
    and mantissa decoded as ``amsim_from_entry`` decodes a packed entry:
    at most 8 significant bits, exact in bf16.  A canonical uint32 table
    becomes three byte planes of its entries' bits 0-23.
    """
    n = 1 << M
    t = jnp.asarray(lut).astype(jnp.int32).reshape(n, n)
    if lut.dtype == jnp.uint16:
        mnt = (t & (n - 1)).astype(jnp.float32)
        planes = [(1.0 + mnt / n) * (1 + ((t >> M) & 1)).astype(jnp.float32)]
    else:
        planes = [((t >> (8 * p)) & 0xFF).astype(jnp.float32)
                  for p in range(3)]
    planes = [jnp.pad(v, ((0, LANES - n), (0, LANES - n))) for v in planes]
    return jnp.concatenate(planes, axis=1).astype(jnp.bfloat16)


def table_form(lut) -> str:
    """How the brick multiplies with a ``kernel_lut`` operand:
    "factored" (the float-valued table of a packed LUT),
    "integer.canonical" (byte planes) or "integer.wide" (1-D)."""
    if lut.ndim == 1:
        return "integer.wide"
    return "factored" if lut.shape[1] == LANES else "integer.canonical"


def kernel_lut(lut, M: int, interpret: bool):
    """The LUT operand a kernel hands its brick, one per launch; records
    the brick's path for it (``obs.routes["lut_brick.<form>"]``).

    M <= 7: the ``lut_matrix`` form, which lowers on the chip.  Wider
    tables stay 1-D for the interpret-mode gather and raise when the
    kernel compiles for the chip — never a quiet detour to the oracle.
    """
    lut = jnp.asarray(lut)
    lut = lut if lut.dtype == jnp.uint16 else lut.astype(jnp.uint32)
    if M <= MATRIX_MAX_M:
        lut = lut_matrix(lut, M)
    elif not interpret:
        raise NotImplementedError(
            f"M={M} LUTs have no TPU lowering (the LUT brick takes "
            f"M <= {MATRIX_MAX_M}); run this multiplier in interpret mode "
            f"or under mode='amsim_jnp'")
    obs.route("lut_brick", table_form(lut))
    return lut


def tagged_pallas_call(name: str, tag, kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` for a launch tagged
    ``(site, pass)``: the GEMM site it computes ("qkv", "head", "attn",
    ...) and its pass ("fwd", "dx" or "dw"); ``name`` is the jitted impl
    that launches it.  ``tag`` None launches untagged.

    The tag travels two ways.  ``metadata`` puts it in the custom call's
    ``frontend_attributes={kernel_metadata={...}}``, which the profiler's
    op text carries.  The call runs in the name scope
    ``<name>.<site>.<pass>``: XLA names the custom call after its
    innermost scope, and names a ``kind=kCustom`` fusion built around the
    call (a scanned layer's weight gradient written in place into the
    stacked gradient) after the call, and such a fusion keeps no
    ``frontend_attributes``.  The name's base, what a trace names the
    kernel by, stays ``name``."""
    if tag is None:
        return pl.pallas_call(kernel, **kwargs)
    site, pass_ = tag
    call = pl.pallas_call(kernel, metadata={"site": site, "pass": pass_},
                          **kwargs)

    def launch(*args):
        with jax.named_scope(f"{name}.{site}.{pass_}"):
            return call(*args)
    return launch


def lut_spec(lut) -> pl.BlockSpec:
    """Whole-LUT BlockSpec with a constant index map: one VMEM-resident
    table broadcast across any grid."""
    return pl.BlockSpec(lut.shape, lambda *_: (0,) * lut.ndim)


def _decode(x, M: int):
    """Operand words as the brick keeps them: sign and exponent of each
    f32 word, with its table index (the top M mantissa bits) in the
    cleared mantissa field's low bits."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return (u & _SIGN_EXP) | mantissa_top(u, M, jnp)


def _index(w, M: int):
    """A decoded word's table index."""
    return (w & np.uint32((1 << M) - 1)).astype(jnp.int32)


def _scale(w):
    """A decoded word's signed power of two, 2^(e-127), or ±0 where its
    exponent field is 0."""
    return jax.lax.bitcast_convert_type(w & _SIGN_EXP, jnp.float32)


def factored_product(fa, fb, v, xp=jnp):
    """Alg. 2's product of two finite words from their ``_scale`` values
    and the table value ``V``; ``xp`` is jnp or numpy.  Bitwise
    ``amsim._amsim`` but for the sign of a zero, which no fold from +0.0
    shows: ``t`` is an exact power of two, 0 or inf; ``t * V`` is exact
    and overflows where Alg. 2 saturates; ``|t| < 2^-126`` is Alg. 2's
    flush, with or without hardware flush-to-zero (docs/kernels.md)."""
    t = fa * fb
    return xp.where(xp.abs(t) < _MIN_NORMAL, xp.float32(0.0), t * v)


def _any_nonfinite(x):
    """Whether any word of ``x`` has exponent field 255 (inf or NaN)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.any((u & EXP_MASK) == EXP_MASK)


def _rows(lut, at):
    """The table rows of A indices ``at`` (bmp, 128; every row constant),
    selected by a one-hot matmul (exact: each output sums one non-zero
    entry): f32 values of the float-valued table, int32 byte planes of a
    canonical one."""
    lane = jax.lax.broadcasted_iota(jnp.int32, at.shape, 1)
    onehot = (at == lane).astype(jnp.bfloat16)
    rows = jnp.dot(onehot, lut, preferred_element_type=jnp.float32)
    return rows if lut.shape[1] == LANES else rows.astype(jnp.int32)


def _pick(rows, bt):
    """Each row's entry at B indices ``bt`` (bmp, 128; every column
    constant): a lane gather per plane, planes OR-ed back together."""
    entry = None
    for p in range(rows.shape[1] // LANES):
        e = jnp.take_along_axis(rows[:, p * LANES:(p + 1) * LANES], bt,
                                axis=1, mode="promise_in_bounds")
        entry = e if entry is None else entry | (e << (8 * p))
    return entry


def _gather_gemm_tile(a, b, lut, acc, *, M: int, chunk: int):
    """Rank-``chunk`` gather-GEMM update of the f32 accumulator tile.

    a (bm, bk) @ b (bk, bn) with the product simulated per element by
    the LUT operand of ``kernel_lut``; ``chunk`` must divide bk (see
    :func:`best_chunk`).  Fold: acc + (((+0 + p_0) + p_1) + ...) per
    chunk of ``chunk`` consecutive k, chunks in order.

    The operands are decoded once, into the scratch (``_decode``).  With
    the float-valued table each product is :func:`factored_product`,
    exact on finite words; a tile holding an inf or NaN word instead runs
    the integer form, ``amsim_from_entry``, as canonical and wide tables
    always do.
    """
    bm, bk = a.shape
    bn = b.shape[1]
    bmp, bnp = _ceil_to(bm, SUBLANES), _ceil128(bn)
    form = table_form(lut)
    packed = lut.ndim == 1 and lut.dtype == jnp.uint16
    nq = bnp // LANES

    def run(a_scr, b_scr):
        a_scr[:bm, :bk] = _decode(a, M)
        # B by 128-lane blocks, so a k-step loads each block's row whole.
        for q in range(nq):
            width = min(LANES, bn - q * LANES)
            b_scr[q, :bk, :width] = _decode(
                b[:, q * LANES:q * LANES + width], M)

        def a_col(k):
            blk = a_scr[:, pl.ds(pl.multiple_of(k // LANES * LANES, LANES),
                                 LANES)]
            return jnp.take_along_axis(
                blk, jnp.full((bmp, LANES), k % LANES, jnp.int32), axis=1,
                mode="promise_in_bounds")

        def b_row(k, q):
            # Lane block q of B's row k, decoded on one vreg of sublanes
            # and not on bmp.  Mosaic lowers a sublane broadcast of a
            # whole scratch row, not a lane slice of one.
            return jnp.broadcast_to(b_scr[q, pl.ds(k, 1), :],
                                    (SUBLANES, LANES))

        def tile_rows(x):
            """(8, 128) -> (bmp, 128) by repeating the vreg."""
            return jnp.concatenate([x] * (bmp // SUBLANES), axis=0)

        def factored(k):
            wa = a_col(k)
            rows, fa = _rows(lut, _index(wa, M)), _scale(wa)
            out = []
            for q in range(nq):
                wb = b_row(k, q)
                out.append(factored_product(
                    fa, tile_rows(_scale(wb)),
                    _pick(rows, tile_rows(_index(wb, M)))))
            return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)

        def integer(k):
            wa = a_col(k)
            at = _index(wa, M)
            rows = None if form == "integer.wide" else _rows(lut, at)
            out = []
            for q in range(nq):
                wb = b_row(k, q)
                bt = tile_rows(_index(wb, M))
                if rows is None:  # interpret mode only (kernel_lut)
                    entry = jnp.take(lut, (at << M) | bt)
                else:
                    entry = _pick(rows, bt)
                    if form == "factored":  # V's f32 bits less 1.0's
                        entry = (jax.lax.bitcast_convert_type(
                            entry, jnp.uint32) - np.uint32(0x3F80_0000))
                out.append(jnp_float(amsim_from_entry(
                    wa, tile_rows(wb), entry.astype(jnp.uint32), M, jnp,
                    packed)))
            return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)

        def fold(product, unroll=1):
            def steps(i, acc):
                # ``unroll`` consecutive k run as one straight-line block,
                # in order: the compiler overlaps their loads, gathers and
                # matmuls, and pushes the table into the MXU once a block.
                def step(g, s):
                    for j in range(unroll):
                        s = s + product(i * chunk + g * unroll + j)
                    return s
                s = jax.lax.fori_loop(0, chunk // unroll, step,
                                      jnp.zeros((bmp, bnp), jnp.float32))
                return acc + s[:bm, :bn]
            return lambda acc: jax.lax.fori_loop(0, bk // chunk, steps, acc)

        if form != "factored":
            return fold(integer)(acc)
        return jax.lax.cond(_any_nonfinite(a) | _any_nonfinite(b),
                            fold(integer),
                            fold(factored, math.gcd(chunk, _UNROLL)), acc)

    return pl.run_scoped(run,
                         pltpu.VMEM((bmp, _ceil128(bk)), jnp.uint32),
                         pltpu.VMEM((nq, _ceil_to(bk, SUBLANES), LANES),
                                    jnp.uint32))


def _rms_scale(x, eps: float):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return jax.lax.rsqrt(var + eps)


def rmsnorm_expr(x, g, eps: float):
    """``(x * rsqrt(mean(x^2) + eps)) * g`` — THE RMSNorm forward of
    models/layers.rmsnorm (bit-for-bit on one backend)."""
    return (x * _rms_scale(x, eps)) * g


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, g, eps: float):
    """Differentiable :func:`rmsnorm_expr` whose backward gives the same
    bits in every program it is compiled into.

    Autodiff's own backward leaves two choices to the compiler, and
    XLA:CPU makes them per fusion: it contracts a multiply feeding an add
    into one FMA, and it reassociates a fused reduction.  The same
    gradient then differed in its last bits between a VJP that recomputes
    through the forward and plain autodiff of it.  Here
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
