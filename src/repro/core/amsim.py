"""Algorithm 2: AMSim — LUT-based approximate FP multiplication (paper §V-B).

Elementwise simulator: given FP32 operands and the mantissa-product LUT
from Algorithm 1, produce the approximate product.  Three steps (paper):
  1. fetch mantissa product (+carry) from the LUT,
  2. compute sign (XOR) and exponent (ea + eb - 127 + carry) exactly,
  3. concatenate; flush-to-zero on underflow/zero input, inf on overflow.

``amsim_multiply``  — jnp version (jit/vmap-able; also the body used by
                      the Pallas GEMM kernel in interpret and TPU mode).
``np_amsim_multiply`` — numpy version (the CPU "ATxC" baseline of
                      Tables V/VI and the LUT-correctness oracle).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .float_bits import MNT_BITS, jnp_bits, jnp_float, np_bits, np_float


def mantissa_top(u, M: int, xp):
    """Top-M mantissa bits of uint32 words: a LUT row (A) or column (B)
    index (paper line 8)."""
    return (u & xp.uint32(0x007F_FFFF)) >> xp.uint32(MNT_BITS - M)


def _amsim(ua, ub, lut, M: int, xp, packed: bool = False):
    """Shared Alg. 2 body over uint32 words; xp is numpy or jnp.

    ``packed=True`` reads the uint16 packed-LUT layout of
    ``lutgen.pack_lut``: entry = (carry << M) | top-M mantissa bits.
    The unpack is two shifts after the gather, so the gather itself moves
    half the bytes (the VMEM-footprint win for the Pallas kernels).
    """
    # Index = concat(top-M bits of A mantissa, top-M bits of B mantissa)
    # (paper line 8; written shift-then-or so it also works for M=12).
    idx = (mantissa_top(ua, M, xp) << xp.uint32(M)) | mantissa_top(ub, M, xp)
    if xp is np:
        entry = lut[idx]
    else:
        entry = jnp.take(lut, idx.astype(jnp.int32), indices_are_sorted=False)
    return amsim_from_entry(ua, ub, entry, M, xp, packed)


def amsim_from_entry(ua, ub, entry, M: int, xp, packed: bool = False):
    """Alg. 2 lines 9-19: the product word from the fetched LUT entry.

    Split from the fetch so the Pallas brick can look the entry up in
    whatever form the backend lowers (kernels/common.py) and still share
    every bit of the sign/exponent/flush arithmetic with the oracles.
    """
    mnt_mask = xp.uint32(0x007F_FFFF)
    if packed:
        entry = entry.astype(xp.uint32)
        entry = ((entry >> xp.uint32(M)) << xp.uint32(MNT_BITS)) | (
            (entry & xp.uint32((1 << M) - 1)) << xp.uint32(MNT_BITS - M)
        )
    carry = (entry >> xp.uint32(MNT_BITS)) & xp.uint32(1)  # line 9
    mnt = entry & mnt_mask  # line 10
    sign = ((ua ^ ub) >> xp.uint32(31)).astype(xp.uint32)  # line 11
    ea = (ua >> xp.uint32(MNT_BITS)) & xp.uint32(0xFF)
    eb = (ub >> xp.uint32(MNT_BITS)) & xp.uint32(0xFF)
    e = ea.astype(xp.int32) + eb.astype(xp.int32) - 127  # line 12
    zero = (e <= 0) | (ea == 0) | (eb == 0)  # line 13
    e = e + carry.astype(xp.int32)  # line 18
    inf = (e >= 255) & ~zero  # line 15
    e = xp.clip(e, 0, 255).astype(xp.uint32)
    out = (sign << xp.uint32(31)) | (e << xp.uint32(MNT_BITS)) | mnt  # line 19
    out = xp.where(inf, (sign << xp.uint32(31)) | xp.uint32(0x7F80_0000), out)
    out = xp.where(zero, sign << xp.uint32(31), out)  # signed zero
    return out


def amsim_multiply(a, b, lut, M: int, packed: bool = False):
    """Approximate product of broadcastable f32 arrays ``a``, ``b`` (jnp)."""
    a, b = jnp.broadcast_arrays(jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32))
    lut = jnp.asarray(lut, jnp.uint16 if packed else jnp.uint32)
    return jnp_float(_amsim(jnp_bits(a), jnp_bits(b), lut, M, jnp, packed=packed))


def np_amsim_multiply(a, b, lut, M: int, packed: bool = False):
    """numpy twin of ``amsim_multiply`` (CPU simulation baseline)."""
    a, b = np.broadcast_arrays(np.asarray(a, np.float32), np.asarray(b, np.float32))
    lut = np.asarray(lut, np.uint16 if packed else np.uint32)
    return np_float(_amsim(np_bits(a), np_bits(b), lut, M, np, packed=packed))
