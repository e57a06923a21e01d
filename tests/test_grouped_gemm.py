"""The grouped LUT GEMM over rows sorted by expert (kernels/approx_gemm.py)
against its oracles (kernels/ref.py), bit for bit in interpret mode:
forward, dx and dw, for routings with an empty expert, one expert taking
every row, groups that are not a multiple of the row tile, and tiles past
the last group; and the custom VJP that wires them (kernels/ops.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.amsim import amsim_multiply
from repro.core.lutgen import get_lut, get_packed_lut
from repro.core.multipliers import get_multiplier
from repro.core.policy import NumericsPolicy
from repro.kernels.approx_gemm import (GROUP_TILE, approx_gemm_grouped,
                                       approx_gemm_grouped_dw, grouped_layout,
                                       grouped_rows_bound)
from repro.kernels.ops import grouped_matmul
from repro.kernels.ref import (ref_amsim_gemm, ref_grouped_gemm,
                               ref_grouped_gemm_dw)

MULT = get_multiplier("afm16")
M = MULT.mantissa_bits
E, D, F = 4, 96, 40
TILES = dict(bk=128, chunk=32, interpret=True)


def _mul():
    lut = jnp.asarray(get_lut(MULT), jnp.uint32)
    return lambda x, y: amsim_multiply(x, y, lut, M)


def _routing(kind, rng):
    """Each row's expert, (rows,) int32."""
    if kind == "one_expert_takes_all":     # 300 rows: 3 tiles, 4 idle past
        return np.full(300, 2, np.int32)
    if kind == "ragged_with_an_empty_expert":
        return rng.choice([0, 1, 3], 200).astype(np.int32)
    return np.repeat(np.arange(E, dtype=np.int32), GROUP_TILE)  # whole tiles


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("kind", ["one_expert_takes_all",
                                  "ragged_with_an_empty_expert",
                                  "whole_tiles"])
def test_grouped_kernel_is_bitwise_its_oracle(kind, rng):
    experts = jnp.asarray(_routing(kind, rng))
    n = experts.shape[0]
    groups = grouped_layout(experts, E)
    R = grouped_rows_bound(n, E)
    counts = np.bincount(np.asarray(experts), minlength=E)
    sizes = -(-counts // GROUP_TILE) * GROUP_TILE
    assert np.array_equal(np.asarray(groups.sizes), sizes)
    assert int(groups.live_tiles[0]) == sizes.sum() // GROUP_TILE < R // GROUP_TILE
    x = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    xs = jnp.zeros((R, D), jnp.float32).at[groups.rows].set(x)
    w = jnp.asarray(rng.standard_normal((E, D, F)), jnp.float32)
    g = jnp.zeros((R, F), jnp.float32).at[groups.rows].set(
        jnp.asarray(rng.standard_normal((n, F)), jnp.float32))
    lut, mul = get_packed_lut(MULT), _mul()

    out = approx_gemm_grouped(xs, w, groups, lut, M, **TILES)
    np.testing.assert_array_equal(
        _bits(out), _bits(ref_grouped_gemm(xs, w, groups, mul, chunk=32)))
    # Each routed row is its expert's product, in the order of assignment
    # (the 2-D oracle folds k in other chunks: equal to rounding).
    lut32 = jnp.asarray(get_lut(MULT), jnp.uint32)
    for e in np.unique(np.asarray(experts)):
        mine = np.asarray(experts) == e
        np.testing.assert_allclose(
            np.asarray(out[groups.rows])[mine],
            np.asarray(ref_amsim_gemm(x[mine], w[e], lut32, M)),
            rtol=1e-5, atol=1e-5)
    live = np.arange(R) < int(groups.live_tiles[0]) * GROUP_TILE
    assert not np.asarray(out)[~live].any()

    dx = approx_gemm_grouped(g, jnp.swapaxes(w, 1, 2), groups, lut, M, **TILES)
    np.testing.assert_array_equal(
        _bits(dx), _bits(ref_grouped_gemm(g, jnp.swapaxes(w, 1, 2), groups,
                                          mul, chunk=32)))

    dw = approx_gemm_grouped_dw(xs, g, groups, lut, M, E, chunk=32,
                                interpret=True)
    np.testing.assert_array_equal(
        _bits(dw), _bits(ref_grouped_gemm_dw(xs, g, groups, E, mul, chunk=32)))
    assert not np.asarray(dw)[counts == 0].any()


def test_grouped_matmul_vjp_runs_the_grouped_kernels(rng):
    """``ops.grouped_matmul`` under amsim: its forward, dx and dw are the
    grouped kernels' (the same tiling resolved), and under native numerics
    the same layout gives each row its expert's exact product."""
    experts = jnp.asarray(rng.choice([0, 1, 3], 150).astype(np.int32))
    groups = grouped_layout(experts, E)
    R = grouped_rows_bound(150, E)
    xs = jnp.zeros((R, D), jnp.float32).at[groups.rows].set(
        jnp.asarray(rng.standard_normal((150, D)), jnp.float32))
    w = jnp.asarray(rng.standard_normal((E, D, F)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((R, F)), jnp.float32)
    pol = NumericsPolicy(mode="amsim", multiplier="afm16")
    out, vjp = jax.vjp(lambda x, w: grouped_matmul(x, w, groups, pol, "wu"),
                       xs, w)
    dx, dw = vjp(g)
    lut = get_packed_lut(MULT)
    np.testing.assert_array_equal(
        _bits(out), _bits(approx_gemm_grouped(xs, w, groups, lut, M)))
    np.testing.assert_array_equal(
        _bits(dx), _bits(approx_gemm_grouped(g, jnp.swapaxes(w, 1, 2),
                                             groups, lut, M)))
    np.testing.assert_array_equal(
        _bits(dw), _bits(approx_gemm_grouped_dw(xs, g, groups, lut, M, E)))

    exact = grouped_matmul(xs, w, groups, NumericsPolicy(), "wu")
    rows = np.asarray(groups.rows)
    want = np.einsum("rd,rdf->rf", np.asarray(xs)[rows],
                     np.asarray(w)[np.asarray(experts)])
    np.testing.assert_allclose(np.asarray(exact)[rows], want, rtol=1e-5,
                               atol=1e-4)
