"""Fused LUT approx-conv2d Pallas kernels — the AMCONV2D analogue (paper §VI).

AMCONV2D mapping.  The paper's second custom op routes convolution
forward *and* backward multiplies through the LUT-based AMSim device
function, restructuring conv as IM2COL + GEMM on the GPU (§VI-B,
Fig. 8a-c).  This module is the TPU/Pallas twin, with one structural
upgrade: the im2col patch matrix is never materialised in HBM.  Instead
an **implicit-GEMM** kernel tiles the output over (batch, output-row
block, out-channel block) and performs the im2col gather per block
inside the kernel — a `dynamic_slice` + static strided restride of the
VMEM-resident padded image per kernel position — feeding the same
VPU gather-GEMM brick (`_gather_gemm_tile`) as the AMDENSE kernels.
The three AMCONV2D GEMMs map as:

  Fig. 8a (forward)           ``approx_conv2d_fused``   out[n,oh,ow,o] =
      sum_{ki,kj,c} amsim(x[n, oh*s+ki, ow*s+kj, c], w[ki,kj,c,o])
  Fig. 8b (weight gradient)   ``approx_conv2d_dw``      patch outer
      product: dw[ki,kj,c,o] = sum_{n,p} amsim(patch, g) with the batch
      as the innermost "arbitrary" accumulation grid axis
  Fig. 8c (data gradient)     ``approx_conv2d_fused`` again, applied to
      the stride-dilated error with the spatially-flipped, IO-transposed
      weights (the paper's fused dilation becomes explicit zero
      insertion + index-equivalent padding)

As in the GEMM kernels the mantissa-product LUT (canonical uint32 or
packed uint16, dtype-detected) is a pallas_call operand whose BlockSpec
index map is constant — one VMEM-resident table broadcast across the
whole grid.  Zero padding is free: AMSim flushes zero-exponent operands
to zero, so padded rows/columns/channels contribute exactly 0.

Block sizes come from the autotuner's ``conv2d`` cache namespace
(``kernels/autotune.py``), keyed on backend | N/H/W/C/KHxKW/O/stride/
padding | M; explicit ``br``/``bo``/``chunk`` arguments override.  The
whole padded image of one batch element is staged per grid point, which
bounds the fused path to paper-scale feature maps (LeNet/ResNet-CIFAR);
``fused_supported`` guards the dispatch in ``kernels/ops.py`` and
oversize shapes fall back to the materialised im2col + GEMM path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune
from repro.kernels.common import (_ceil_to,
                                  _gather_gemm_tile, best_chunk, kernel_lut,
                                  lut_spec, resolve_interpret,
                                  tagged_pallas_call)

# Static-unroll / VMEM guards for the fused path (see fused_supported).
MAX_TAPS = 64                      # kh*kw positions unrolled in-kernel
MAX_IMAGE_BYTES = 8 * 1024 * 1024  # padded image of one batch element
MAX_BR = 16                        # largest row tile any config may pick


# ------------------------------------------------------------------ padding
def conv_pads(h: int, w: int, kh: int, kw: int, stride: int,
              padding) -> tuple[int, int, int, int]:
    """(top, bottom, left, right) pads, aligned with XLA conv semantics.

    Delegates to ``lax.padtype_to_pads`` for "SAME"/"VALID" so the
    asymmetric split for even kernel sizes (extra pad goes low=floor,
    high=remainder) can never drift from ``lax.conv_general_dilated``.
    An explicit 4-tuple is passed through unchanged.
    """
    if not isinstance(padding, str):
        pt, pb, pl_, pr = padding
        return (int(pt), int(pb), int(pl_), int(pr))
    (ph, pb), (pw, pr) = jax.lax.padtype_to_pads(
        (h, w), (kh, kw), (stride, stride), padding.upper())
    return (int(ph), int(pb), int(pw), int(pr))


def conv_out_shape(h: int, w: int, kh: int, kw: int, stride: int,
                   pads: tuple[int, int, int, int]) -> tuple[int, int]:
    pt, pb, pl_, pr = pads
    return ((h + pt + pb - kh) // stride + 1,
            (w + pl_ + pr - kw) // stride + 1)


def fused_supported(x_shape, w_shape, stride: int = 1) -> bool:
    """Whether the implicit-GEMM kernel can take this conv (VMEM/unroll
    guards) — callers fall back to the im2col + GEMM path otherwise."""
    n, h, wid, c = x_shape
    kh, kw, _, o = w_shape
    if kh * kw > MAX_TAPS:
        return False
    # Upper bound on the padded image staged per grid point: SAME pads
    # plus the worst-case row-block ceil padding ((MAX_BR - 1) * stride
    # extra rows when OH is rounded up to the tile) — the guard must
    # hold for ANY tiling the autotuner may pick.
    hp = h + kh + stride * MAX_BR
    wp = wid + kw + stride
    return hp * wp * c * 4 <= MAX_IMAGE_BYTES


# Chunk snapping is shared with the GEMM/attention resolvers: the gather
# fori_loop drops tail elements unless chunk divides the total, and
# ``best_chunk`` picks the nearest divisor instead of degrading to 1.


# ------------------------------------------------------------------ forward
def _window(x_ref, r0, c0, rows: int, cols: int, stride: int):
    """The strided (rows, cols, C) input window of one kernel position,
    read straight from the VMEM-resident padded image ref."""
    return x_ref[0, pl.ds(r0, rows, stride=stride),
                 pl.ds(c0, cols, stride=stride), :]


def _amconv_kernel(x_ref, w_ref, lut_ref, o_ref, *,
                   M: int, stride: int, kh: int, kw: int, chunk: int):
    """One (batch, row-block, out-channel-block) output tile.

    The full contraction (kh*kw taps x C channels) runs inside a single
    grid point: a static loop over kernel positions, each gathering its
    strided input window from the VMEM-resident padded image and feeding
    the (br*ow, C) x (C, bo) gather-GEMM brick.
    """
    lut = lut_ref[...]
    br, ow, bo = o_ref.shape[1], o_ref.shape[2], o_ref.shape[3]
    c = x_ref.shape[-1]
    r0 = pl.program_id(1) * (br * stride)
    acc = jnp.zeros((br * ow, bo), jnp.float32)
    for ki in range(kh):
        for kj in range(kw):
            patch = _window(x_ref, r0 + ki, kj, br, ow, stride)
            acc = _gather_gemm_tile(
                patch.reshape(br * ow, c), w_ref[ki, kj], lut, acc,
                M=M, chunk=chunk)
    o_ref[0] = acc.reshape(br, ow, bo)


@functools.partial(jax.jit, static_argnames=(
    "M", "stride", "pads", "br", "bo", "chunk", "interpret", "pass_"))
def _fused_impl(x, w, lut, M, *, stride, pads, br, bo, chunk, interpret,
                pass_="fwd"):
    n, h, wid, c = x.shape
    kh, kw, _, o = w.shape
    pt, pb, pl_, pr = pads
    oh, ow = conv_out_shape(h, wid, kh, kw, stride, pads)
    assert oh > 0 and ow > 0, (x.shape, w.shape, stride, pads)
    ohp = _ceil_to(oh, br)
    op = _ceil_to(o, bo)
    # Rows the padded grid needs: row-block padding may extend past pb,
    # VALID overhang may need fewer rows than h — pad then crop.
    hp = (ohp - 1) * stride + kh
    wp = (ow - 1) * stride + kw
    xpad = jnp.pad(x.astype(jnp.float32),
                   ((0, 0), (pt, max(0, hp - h - pt)),
                    (pl_, max(0, wp - wid - pl_)), (0, 0)))
    xpad = xpad[:, :hp, :wp, :]
    wpad = jnp.pad(w.astype(jnp.float32),
                   ((0, 0), (0, 0), (0, 0), (0, op - o)))
    grid = (n, ohp // br, op // bo)
    out = tagged_pallas_call(
        "_fused_impl", ("conv", pass_),
        functools.partial(_amconv_kernel, M=M, stride=stride, kh=kh, kw=kw,
                          chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda nn, rr, oo: (nn, 0, 0, 0)),
            pl.BlockSpec((kh, kw, c, bo), lambda nn, rr, oo: (0, 0, 0, oo)),
            lut_spec(lut),
        ],
        out_specs=pl.BlockSpec((1, br, ow, bo),
                               lambda nn, rr, oo: (nn, rr, 0, oo)),
        out_shape=jax.ShapeDtypeStruct((n, ohp, ow, op), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(xpad, wpad, lut)
    return out[:, :oh, :, :o]


def approx_conv2d_fused(
    x,
    w,
    lut,
    M: int,
    *,
    stride: int = 1,
    padding="SAME",
    br: int | None = None,
    bo: int | None = None,
    chunk: int | None = None,
    interpret: bool | None = None,
    mult: str | None = None,
    pass_: str = "fwd",
):
    """Implicit-GEMM LUT-simulated conv2d: x (N,H,W,C), w (KH,KW,C,O) ->
    (N,OH,OW,O), NHWC, FP32 accumulate.

    ``padding`` is "SAME"/"VALID" or an explicit (top, bottom, left,
    right) tuple (the data-gradient pass uses the latter).  ``lut`` may
    be canonical uint32 or packed uint16 (dtype-detected).  Unset
    br/bo/chunk come from the autotuner's conv2d namespace.  ``pass_``
    ("fwd" or "dx") tags the launch (``common.tagged_pallas_call``).
    """
    n, h, wid, c = x.shape
    kh, kw, cw, o = w.shape
    assert c == cw, (x.shape, w.shape)
    pads = conv_pads(h, wid, kh, kw, stride, padding)
    oh, _ = conv_out_shape(h, wid, kh, kw, stride, pads)
    interpret = resolve_interpret(interpret)
    lut = kernel_lut(lut, M, interpret)
    if None in (br, bo, chunk):
        cfg = autotune.get_conv_config(n, h, wid, c, kh, kw, o, stride,
                                       padding, M, mult=mult)
        # Cache-derived row tiles are capped at MAX_BR so the
        # fused_supported VMEM bound holds for any tuned entry
        # (explicit br arguments are taken as-is).
        br = min(cfg.br, MAX_BR) if br is None else br
        bo = cfg.bo if bo is None else bo
        chunk = cfg.chunk if chunk is None else chunk
    br = max(1, min(br, oh))
    bo = max(1, min(bo, o))
    chunk = best_chunk(chunk, c)
    return _fused_impl(x, w, lut, M, stride=stride, pads=pads, br=br,
                       bo=bo, chunk=chunk, interpret=interpret, pass_=pass_)


# ----------------------------------------------------------- weight gradient
def _amconv_dw_kernel(x_ref, g_ref, lut_ref, o_ref, acc_ref, *,
                      M: int, stride: int, kw: int, chunk: int):
    """One kernel-position (ki, kj) slice of dw, accumulated over the
    batch (grid axis 1, "arbitrary"): dw[ki,kj] += patch^T @ g."""
    g = g_ref[0]                       # (OH, OW, O) upstream error
    lut = lut_ref[...]
    c = x_ref.shape[-1]
    oh, ow, o = g.shape
    kp = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    patch = _window(x_ref, kp // kw, kp % kw, oh, ow, stride)
    cols_t = jnp.transpose(patch.reshape(oh * ow, c))    # (C, P)
    acc_ref[...] = _gather_gemm_tile(
        cols_t, g.reshape(oh * ow, o), lut, acc_ref[...],
        M=M, chunk=chunk)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "M", "stride", "pads", "kh", "kw", "chunk", "interpret"))
def _dw_impl(x, g, lut, M, *, stride, pads, kh, kw, chunk, interpret):
    n, h, wid, c = x.shape
    _, oh, ow, o = g.shape
    pt, _, pl_, _ = pads
    hp = (oh - 1) * stride + kh
    wp = (ow - 1) * stride + kw
    xpad = jnp.pad(x.astype(jnp.float32),
                   ((0, 0), (pt, max(0, hp - h - pt)),
                    (pl_, max(0, wp - wid - pl_)), (0, 0)))
    xpad = xpad[:, :hp, :wp, :]
    g = g.astype(jnp.float32)
    grid = (kh * kw, n)
    out = tagged_pallas_call(
        "_dw_impl", ("conv", "dw"),
        functools.partial(_amconv_dw_kernel, M=M, stride=stride, kw=kw,
                          chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda kp, nn: (nn, 0, 0, 0)),
            pl.BlockSpec((1, oh, ow, o), lambda kp, nn: (nn, 0, 0, 0)),
            lut_spec(lut),
        ],
        out_specs=pl.BlockSpec((1, c, o), lambda kp, nn: (kp, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((kh * kw, c, o), jnp.float32),
        scratch_shapes=[pltpu.VMEM((c, o), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xpad, g, lut)
    return out.reshape(kh, kw, c, o)


def approx_conv2d_dw(
    x,
    g,
    lut,
    M: int,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    padding="SAME",
    chunk: int | None = None,
    interpret: bool | None = None,
    mult: str | None = None,
):
    """Fused weight gradient (paper Fig. 8b): dw[ki,kj,c,o] =
    sum_{n,oh,ow} amsim(x_patch, g) — the patch outer product, with the
    batch as the innermost accumulation grid axis.

    ``g`` is the upstream error (N, OH, OW, O); ``chunk`` tiles the
    patch axis (OH*OW) of the gather brick.
    """
    n, h, wid, c = x.shape
    assert g.shape[0] == n, (x.shape, g.shape)
    pads = conv_pads(h, wid, kh, kw, stride, padding)
    interpret = resolve_interpret(interpret)
    lut = kernel_lut(lut, M, interpret)
    if chunk is None:
        o = g.shape[-1]
        cfg = autotune.get_conv_config(n, h, wid, c, kh, kw, o, stride,
                                       padding, M, mult=mult)
        chunk = cfg.dw_chunk
    chunk = best_chunk(chunk, g.shape[1] * g.shape[2])
    return _dw_impl(x, g, lut, M, stride=stride, pads=pads, kh=kh, kw=kw,
                    chunk=chunk, interpret=interpret)
