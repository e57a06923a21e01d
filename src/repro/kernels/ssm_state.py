"""Pallas TPU kernel: one decode tick of the Mamba-2 recurrent state.

A Mamba-2 layer carries, per sequence, a state h of (heads, head_dim,
d_state) = (H, p, N).  One decode token with inputs x (H, p), dt (H,),
B (N,) and C (N,) (one group: B and C are shared by every head) updates
it and reads it out:

    h[h, i, n] <- h[h, i, n] * exp(dt[h] A[h]) + B[n] (x[h, i] dt[h])
    y[h, i]     = sum_n C[n] h[h, i, n]

``models/ssm.py``'s chunked prefill forms the same two products through
the LUT GEMMs (``B (x dt)`` in its chunk states, ``C h`` in its read-out
of the state entering a chunk).  Here, a tick's contraction is one row
against one state, so a GEMM tile would pad it 128-fold; this kernel
instead forms each product once, with the LUT brick's decode
(``kernels/common.py``), and keeps the decays and ``x dt`` exact float32
(they are inputs, computed outside).

State layout: ``(layers, slots, N, H * p)`` float32, d_state on
sublanes and (head, head_dim) on lanes.  Both products then select one
table row per sublane: B[n]'s row for ``B (x dt)``, C[n]'s row for
``C h``, each by a one-hot matmul (``common._rows``), and a lane gather
by the other operand's index picks the entry (``common._pick``).  One
launch updates one layer of every slot in place: the stacked state is
aliased to the output (``input_output_aliases``), the layer comes in as a
scalar-prefetched index, and only that layer's blocks are read and
written.

The fold of ``y`` over n is explicit, the same in ``kernels/ref.py``'s
oracle: n in groups of 8 consecutive sublanes, each of the 8 partial
sums (one per n mod 8) summed over the groups in order from +0.0, then
the 8 partials added in order to +0.0.

A table with M <= 7 takes the factored form (``common.factored_product``)
while every word of the block is finite, and the integer form
(``amsim.amsim_from_entry``) otherwise, as the brick does; wider tables
take the 1-D gather, which only interpret mode lowers (``kernel_lut``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.amsim import amsim_from_entry
from repro.core.float_bits import jnp_float
from repro.kernels.common import (LANES, SUBLANES, _any_nonfinite, _decode,
                                  _index, _pick, _rows, _scale,
                                  factored_product, kernel_lut, lut_spec,
                                  resolve_interpret, table_form,
                                  tagged_pallas_call)

# Lanes of state one grid step holds: 1024 x 128 sublanes of float32 is
# 512 KiB in and 512 KiB out, double-buffered.
_BLOCK_LANES = 1024


def decayed(st, dec):
    """``st * dec``, kept a product of its own: the select (a no-op, since
    a NaN ``dec`` makes the product NaN anyway) stands between it and the
    add it feeds, so no compiler fuses the two into one rounding (a
    multiply-add), here or in the oracle."""
    return jnp.where(jnp.isnan(dec), dec, st * dec)


def _column(row):
    """(1, N) -> (N, LANES): sublane n holds row[0, n] in every lane."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))


def _product(wa, wb, rows, lut, *, M: int, factored: bool):
    """afm(a, b) elementwise for decoded words ``wa``, ``wb`` (N, LANES),
    ``rows`` the table rows of ``wa``'s indices (None for a 1-D table)."""
    form = table_form(lut)
    bt = _index(wb, M)
    if rows is None:  # interpret mode only (kernel_lut)
        entry = jnp.take(lut, (_index(wa, M) << M) | bt)
        return jnp_float(amsim_from_entry(
            wa, wb, entry.astype(jnp.uint32), M, jnp,
            lut.dtype == jnp.uint16))
    entry = _pick(rows, bt)
    if factored:
        return factored_product(_scale(wa), _scale(wb), entry)
    if form == "factored":  # V's f32 bits less 1.0's
        entry = (jax.lax.bitcast_convert_type(entry, jnp.uint32)
                 - np.uint32(0x3F80_0000))
    return jnp_float(amsim_from_entry(wa, wb, entry.astype(jnp.uint32), M,
                                      jnp, False))


def _fold_rows(p):
    """sum_n p[n, :] in the kernel's order: (N, L) -> (1, L)."""
    acc = jnp.zeros((SUBLANES, p.shape[1]), jnp.float32)
    for g in range(p.shape[0] // SUBLANES):
        acc = acc + p[g * SUBLANES:(g + 1) * SUBLANES]
    y = jnp.zeros((1, p.shape[1]), jnp.float32)
    for r in range(SUBLANES):
        y = y + acc[r:r + 1]
    return y


def _state_kernel(layer_ref, s_ref, xdt_ref, dec_ref, b_ref, c_ref, lut_ref,
                  s_out_ref, y_ref, *, M: int):
    del layer_ref
    lut = lut_ref[...]
    wide = table_form(lut) == "integer.wide"
    wb, wc = _decode(_column(b_ref[0]), M), _decode(_column(c_ref[0]), M)
    rows_b = None if wide else _rows(lut, _index(wb, M))
    rows_c = None if wide else _rows(lut, _index(wc, M))
    n = wb.shape[0]
    for q in range(s_ref.shape[3] // LANES):
        lanes = pl.ds(q * LANES, LANES)
        st = s_ref[0, 0, :, lanes]
        xdt = xdt_ref[0, :, lanes]
        wx = _decode(jnp.broadcast_to(xdt, (n, LANES)), M)
        dec = jnp.broadcast_to(dec_ref[0, :, lanes], (n, LANES))

        def update(factored):
            return decayed(st, dec) + _product(wb, wx, rows_b, lut, M=M,
                                       factored=factored)

        def read(new, factored):
            return _fold_rows(_product(wc, _decode(new, M), rows_c, lut, M=M,
                                       factored=factored))

        if wide or table_form(lut) != "factored":
            new = update(False)
            y = read(new, False)
        else:
            new = jax.lax.cond(_any_nonfinite(xdt) | _any_nonfinite(b_ref[0]),
                               lambda: update(False),
                               lambda: update(True))
            y = jax.lax.cond(_any_nonfinite(new) | _any_nonfinite(c_ref[0]),
                             lambda: read(new, False),
                             lambda: read(new, True))
        s_out_ref[0, 0, :, lanes] = new
        y_ref[0, :, lanes] = y


@functools.partial(jax.jit, static_argnames=("M", "interpret", "tag"))
def _ssm_state_impl(state, layer, xdt, dec, b, c, lut, M, *, interpret,
                    tag=None):
    L, S, N, HP = state.shape
    w = min(HP, _BLOCK_LANES)
    state_spec = pl.BlockSpec((1, 1, N, w), lambda s, j, l: (l[0], s, 0, j))
    lane_spec = pl.BlockSpec((1, 1, w), lambda s, j, l: (s, 0, j))
    row_spec = pl.BlockSpec((1, 1, N), lambda s, j, l: (s, 0, 0))
    new, y = tagged_pallas_call(
        "_ssm_state_impl", tag,
        functools.partial(_state_kernel, M=M),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, HP // w),
            in_specs=[state_spec, lane_spec, lane_spec, row_spec, row_spec,
                      lut_spec(lut)],
            out_specs=[state_spec, lane_spec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((S, 1, HP), jnp.float32)],
        # Operand 1 (after the prefetched layer) is the stacked state.
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(layer.reshape(1).astype(jnp.int32), state, xdt[:, None], dec[:, None],
      b[:, None], c[:, None], lut)
    return y[:, 0], new


def ssm_state_update(state, layer, xdt, dec, b, c, lut, M: int, *,
                     interpret: bool | None = None,
                     tag: tuple[str, str] | None = None):
    """One recurrent step of layer ``layer`` (int32 scalar, traced) of the
    stacked state (L, S, N, H*p), every product simulated by ``lut``.

    xdt (S, H*p): x dt, exact; dec (S, H*p): each head's decay
    exp(dt A) repeated over its head_dim lanes; b, c (S, N).
    Returns (y (S, H*p), state): the layer's rows of ``state`` updated in
    place (in a program that donates ``state``, no copy of it is made),
    the other layers untouched.
    """
    L, S, N, HP = state.shape
    if N % SUBLANES or HP % LANES:
        raise ValueError(f"state (d_state {N}, heads x head_dim {HP}) must "
                         f"tile by ({SUBLANES}, {LANES})")
    interpret = resolve_interpret(interpret)
    obs.route("ssm.state", "kernel")
    lut = kernel_lut(lut, M, interpret)
    f32 = lambda x: x.astype(jnp.float32)
    return _ssm_state_impl(f32(state), jnp.asarray(layer, jnp.int32),
                           f32(xdt), f32(dec), f32(b), f32(c), lut, M,
                           interpret=interpret, tag=tag)
