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
from repro.kernels.approx_gemm import GROUP_TILE
from repro.kernels.ssm_state import decayed

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


def _chunked_gemm(a, b, mul, chunk: int):
    """(..., m, k) @ (..., k, n) folded as the Pallas brick folds k: k
    zero-padded to a multiple of ``chunk``, each chunk of ``chunk``
    consecutive k summed in order from +0.0 and added to the accumulator
    in order.  Equal leading batch dims."""
    k = a.shape[-1]
    pad = -k % chunk
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    b = jnp.pad(b, [(0, 0)] * (b.ndim - 2) + [(0, pad), (0, 0)])
    zero = jnp.zeros(a.shape[:-1] + b.shape[-1:], jnp.float32)

    def add_chunk(i, acc):
        def step(j, s):
            aj = jax.lax.dynamic_slice_in_dim(a, i * chunk + j, 1, a.ndim - 1)
            bj = jax.lax.dynamic_slice_in_dim(b, i * chunk + j, 1, b.ndim - 2)
            return s + mul(aj, bj)
        return acc + jax.lax.fori_loop(0, chunk, step, zero)

    return jax.lax.fori_loop(0, (k + pad) // chunk, add_chunk, zero)


def ref_grouped_gemm(a, b, groups, mul, chunk: int = _K_CHUNK):
    """Oracle of ``approx_gemm_grouped``: row tile t of a (R, k) times
    b[groups.tile_expert[t]] (k, n), ``mul`` as the scalar product, k
    folded in chunks of ``chunk`` as the kernel folds it; rows of tiles
    past the last group are zero."""
    R, k = a.shape
    tm = GROUP_TILE
    out = _chunked_gemm(a.reshape(R // tm, tm, k), b[groups.tile_expert],
                        mul, chunk)
    live = jnp.arange(R // tm) < groups.live_tiles[0]
    return jnp.where(live[:, None, None], out, 0.0).reshape(R, -1)


def ref_grouped_gemm_dw(a, g, groups, n_experts: int, mul,
                        chunk: int = _K_CHUNK):
    """Oracle of ``approx_gemm_grouped_dw``: for each expert, a^T @ g over
    the rows of its tiles (the others masked to zero), the rows folded in
    chunks of ``chunk`` from the first row: (E, k, n)."""
    R = a.shape[0]
    tm = GROUP_TILE
    live = jnp.arange(R // tm) < groups.live_tiles[0]
    owner = jnp.repeat(jnp.where(live, groups.tile_expert, -1), tm)
    mine = owner[None, :] == jnp.arange(n_experts)[:, None]        # (E, R)
    at = jnp.where(mine[:, None, :], a.T[None], 0.0)                # (E, k, R)
    return _chunked_gemm(at, jnp.broadcast_to(g, (n_experts,) + g.shape),
                         mul, chunk)


def ref_direct_gemm(a, b, multiplier: Multiplier):
    """Direct bit-manipulation GEMM oracle (the paper's 'direct C sim').

    Batched like ``ref_amsim_gemm``: (..., m, k) @ (..., k, n).
    """
    return _elementwise_gemm(a, b, multiplier.jnp_mul)


def ref_ssm_state(state, layer, xdt, dec, b, c, mul):
    """Oracle of ``kernels/ssm_state.ssm_state_update``: one recurrent
    step of layer ``layer`` of the stacked state (L, S, N, H*p) with
    ``mul`` as the scalar product,

        new[s, n, j] = state[layer, s, n, j] * dec[s, j] + mul(b[s, n], xdt[s, j])
        (the decay a product of its own, ``ssm_state.decayed``)
        y[s, j]      = sum_n mul(c[s, n], new[s, n, j]),

    the sum folded as the kernel folds it: n in groups of 8, each of the
    8 partial sums (n mod 8) over the groups in order from +0.0, then the
    8 partials in order from +0.0.  Returns (y (S, H*p), state)."""
    st = jax.lax.dynamic_index_in_dim(state, layer, axis=0, keepdims=False)
    new = decayed(st, dec[:, None, :]) + mul(b[:, :, None], xdt[:, None, :])
    p = mul(c[:, :, None], new)
    S, N, HP = p.shape
    acc = jnp.zeros((S, 8, HP), jnp.float32)
    for g in range(N // 8):
        acc = acc + p[:, 8 * g:8 * g + 8]
    y = jnp.zeros((S, HP), jnp.float32)
    for r in range(8):
        y = y + acc[:, r]
    return y, jax.lax.dynamic_update_index_in_dim(state, new, layer, axis=0)


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
