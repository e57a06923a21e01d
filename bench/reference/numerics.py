"""The afm16 product and the GEMM built on it, written from the
multiplier's definition in plain jax.numpy.

afm16 multiplies two float32 numbers as the bfloat16-format minimally
biased logarithmic multiplier does: each operand's mantissa is cut to its
top 7 bits (xa, xb in steps of 1/128), the mantissa product
(1 + xa)(1 + xb) is replaced by 1 + s with s = xa + xb + 1/12 (Mitchell's
sum plus the bias compensation), s is cut to 7 bits, a carry moves into
the exponent when s >= 1, and s saturates below 2.  Sign and exponent are
exact; a zero or subnormal operand gives zero.

With na = 128 xa and nb = 128 xb (integers in [0, 127]), the cut sum is
t = na + nb + 10 in steps of 1/128 (10 = floor(128 * round(2^23/12) / 2^23)),
so the product is

    sa * sb * g(na + nb + 10),   g(t) = (128 + t) / 128   for t < 128
                                      = min(t, 255) / 64   otherwise,

where sa, sb are the operands with their mantissas cleared (signed powers
of two).  Every such product is exact in float32; products below about
2^-125 may flush to zero where the multiplier keeps them.

The GEMM sums these products over the contraction in float32.  It groups
the terms by A's mantissa index v:

    C = sum_v (sa * [na == v]) @ (sb * g(v + nb + 10)),

and both factors of every group are exact in bfloat16 (a signed power of
two or zero; a power of two times an integer of at most 8 bits), so each
group is one bfloat16 matrix product with float32 accumulation, whose
products are exact.  Only the order of the float32 sums differs from the
kernels': the two agree to float32 rounding, not bit for bit.

Nothing here reads a table or imports the program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def split(x):
    """x as (sign * 2^E, n): the operand with its mantissa cleared, and
    the top 7 mantissa bits as an int32 in [0, 127]."""
    u = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    scale = lax.bitcast_convert_type(u & jnp.uint32(0xFF800000), jnp.float32)
    n = ((u >> 16) & jnp.uint32(0x7F)).astype(jnp.int32)
    return scale, n


def g(t):
    """The cut mantissa product for the cut sum t (int32), as a float32."""
    t = t.astype(jnp.float32)
    return jnp.where(t < 128.0, (128.0 + t) * (1.0 / 128.0),
                     jnp.minimum(t, 255.0) * (1.0 / 64.0))


def afm16(a, b):
    """Elementwise afm16 product of broadcastable float32 arrays."""
    sa, na = split(a)
    sb, nb = split(b)
    return (sa * sb) * g(na + nb + 10)


def _gemm(a, b):
    """(..., m, k) @ (..., k, n), equal leading dims, every product afm16."""
    sa, na = split(a)
    sb, nb = split(b)
    nd = a.ndim
    batch = tuple(range(nd - 2))
    dims = (((nd - 1,), (nd - 2,)), (batch, batch))
    out = jax.eval_shape(lambda x, y: lax.dot_general(x, y, dims), a, b).shape

    def group(v, acc):
        av = jnp.where(na == v, sa, 0.0).astype(jnp.bfloat16)
        bv = (sb * g(nb + (v + 10))).astype(jnp.bfloat16)
        return acc + lax.dot_general(av, bv, dims,
                                     preferred_element_type=jnp.float32)

    return lax.fori_loop(0, 128, group, jnp.zeros(out, jnp.float32))


@jax.custom_vjp
def matmul(a, b):
    """Differentiable afm16 matmul whose backward GEMMs are afm16 too, as
    the program's approximate backward pass computes them:
    da = g @ b^T and db = a^T @ g."""
    return _gemm(a, b)


def _fwd(a, b):
    return _gemm(a, b), (a, b)


def _bwd(res, ct):
    a, b = res
    t = lambda x: jnp.swapaxes(x, -1, -2)
    return _gemm(ct, t(b)), _gemm(t(a), ct)


matmul.defvjp(_fwd, _bwd)
