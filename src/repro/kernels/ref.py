"""Pure-jnp oracles for every kernel in this package.

These are the ground truth the Pallas kernels are asserted against
(tests sweep shapes/dtypes and assert_allclose).  They are also usable
execution modes in their own right (``amsim_jnp`` / ``direct`` in
NumericsPolicy) — portable to any backend, no Pallas required.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.amsim import amsim_multiply
from repro.core.multipliers import Multiplier

# Contraction-chunk size of the oracle's FP32 fold (see _elementwise_gemm).
_K_CHUNK = 128


def _elementwise_gemm(a, b, mul):
    """Shared oracle body: (..., m, k) @ (..., k, n) with ``mul`` as the
    scalar product.  Equal leading batch dims.

    The FP32 fold is explicit, the same on every backend and the same as
    the Pallas brick's (kernels/common.py): each chunk of ``_K_CHUNK``
    consecutive k (all of k when it is not a multiple above one chunk)
    sums its products in order from +0.0, and the chunk sums are added
    to the accumulator in order.  A ``jnp.sum`` would leave the order to
    the compiler, which reassociates it differently per backend and per
    fusion.
    """
    k = a.shape[-1]
    assert b.shape[-2] == k and a.shape[:-2] == b.shape[:-2], (a.shape, b.shape)
    m, n = a.shape[-2], b.shape[-1]
    zero = jnp.zeros(a.shape[:-2] + (m, n), jnp.float32)

    def fold(lo, size):
        def step(j, s):
            aj = jax.lax.dynamic_slice_in_dim(a, lo + j, 1, axis=a.ndim - 1)
            bj = jax.lax.dynamic_slice_in_dim(b, lo + j, 1, axis=b.ndim - 2)
            return s + mul(aj, bj)
        return jax.lax.fori_loop(0, size, step, zero)

    if k % _K_CHUNK == 0 and k > _K_CHUNK:
        return jax.lax.fori_loop(
            0, k // _K_CHUNK,
            lambda i, acc: acc + fold(i * _K_CHUNK, _K_CHUNK), zero)
    return fold(0, k)


def ref_amsim_gemm(a, b, lut, M: int):
    """LUT-simulated GEMM oracle: out[i,j] = sum_k amsim(a[i,k], b[k,j]).

    Accumulation in FP32 (paper §VII).  a: (..., m, k), b: (..., k, n)
    f32 with equal leading batch dims — the portable (``amsim_jnp``)
    twin of ``approx_gemm`` / ``approx_gemm_batched``.
    """
    lut = jnp.asarray(lut, jnp.uint32)
    return _elementwise_gemm(a, b, lambda x, y: amsim_multiply(x, y, lut, M))


def ref_direct_gemm(a, b, multiplier: Multiplier):
    """Direct bit-manipulation GEMM oracle (the paper's 'direct C sim').

    Batched like ``ref_amsim_gemm``: (..., m, k) @ (..., k, n).
    """
    return _elementwise_gemm(a, b, multiplier.jnp_mul)


# --------------------------------------------------------------- conv oracle
def ref_conv2d(x, w, stride: int = 1, padding: str = "SAME"):
    """Exact NHWC conv oracle via lax.conv_general_dilated (f32 accum)."""
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        w.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )


def ref_im2col(x, kh: int, kw: int, stride: int, pad: tuple[int, int, int, int]):
    """Reference im2col: x (N,H,W,C) -> (N*OH*OW, KH*KW*C) patch matrix."""
    n, h, w, c = x.shape
    pt, pb, pl_, pr = pad
    xp = jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    oh = (h + pt + pb - kh) // stride + 1
    ow = (w + pl_ + pr - kw) // stride + 1
    cols = []
    for i in range(kh):
        for j in range(kw):
            patch = jax.lax.slice(
                xp,
                (0, i, j, 0),
                (n, i + (oh - 1) * stride + 1, j + (ow - 1) * stride + 1, c),
                (1, stride, stride, 1),
            )
            cols.append(patch.reshape(n * oh * ow, c))
    # (N*OH*OW, KH*KW, C) -> (N*OH*OW, KH*KW*C)
    return jnp.stack(cols, axis=1).reshape(n * oh * ow, kh * kw * c)
