"""Pallas approx_gemm vs pure-jnp oracle: shape/dtype/M sweeps (deliverable c)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.amsim import _amsim, amsim_multiply
from repro.core.faults import FaultSpec, apply_faults
from repro.core.lutgen import generate_lut, get_lut, get_packed_lut
from repro.core.multipliers import REGISTRY, get_multiplier
from repro.core.policy import NumericsPolicy
from repro.kernels import common
from repro.kernels.approx_attention import approx_attention_fused
from repro.kernels.approx_gemm import (approx_gemm, approx_gemm_batched,
                                       approx_gemm_grouped,
                                       approx_gemm_grouped_dw, grouped_layout,
                                       grouped_rows_bound)
from repro.kernels.ops import attend_einsum
from repro.kernels.ref import (_chunked_gemm, ref_amsim_gemm, ref_conv2d,
                               ref_direct_gemm, ref_grouped_gemm,
                               ref_grouped_gemm_dw, ref_im2col)
from test_multiplier_properties import _EDGE_BITS

MULT = get_multiplier("afm16")
LUT = get_lut(MULT)


@pytest.mark.parametrize("m,k,n", [
    (8, 16, 8),          # tiny, heavy padding
    (128, 128, 128),     # exactly one tile
    (96, 200, 130),      # ragged everything
    (256, 384, 128),     # multi-tile
    (1, 7, 1),           # degenerate
])
def test_pallas_gemm_matches_oracle(m, k, n, rng):
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    out = approx_gemm(a, b, LUT, 7, interpret=True)
    ref = ref_amsim_gemm(a, b, jnp.asarray(LUT), 7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_gemm_dtypes(dtype, rng):
    a = jnp.asarray(rng.standard_normal((64, 96)), dtype)
    b = jnp.asarray(rng.standard_normal((96, 32)), dtype)
    out = approx_gemm(a, b, LUT, 7, interpret=True)
    ref = ref_amsim_gemm(a.astype(jnp.float32), b.astype(jnp.float32),
                         jnp.asarray(LUT), 7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name,M", [("trunc4", 4), ("mitchell11", 11),
                                    ("bf16", 7)])
def test_pallas_gemm_other_multipliers(name, M, rng):
    mult = get_multiplier(name)
    lut = get_lut(mult, M)
    a = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    out = approx_gemm(a, b, lut, M, interpret=True)
    ref = ref_amsim_gemm(a, b, jnp.asarray(lut), M)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bm,bn,bk,chunk", [
    (128, 128, 128, 8), (64, 128, 64, 4), (128, 64, 128, 16)])
def test_pallas_gemm_block_shapes(bm, bn, bk, chunk, rng):
    a = jnp.asarray(rng.standard_normal((160, 200)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((200, 96)), jnp.float32)
    out = approx_gemm(a, b, LUT, 7, bm=bm, bn=bn, bk=bk, chunk=chunk,
                      interpret=True)
    ref = ref_amsim_gemm(a, b, jnp.asarray(LUT), 7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_amsim_gemm_equals_direct_gemm(rng):
    """LUT-kernel GEMM == direct bit-manipulation GEMM (Fig. 6 cross-check)."""
    a = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((128, 48)), jnp.float32)
    lutted = ref_amsim_gemm(a, b, jnp.asarray(LUT), 7)
    direct = ref_direct_gemm(a, b, MULT)
    np.testing.assert_allclose(np.asarray(lutted), np.asarray(direct),
                               rtol=1e-5, atol=1e-5)


def test_im2col_matches_conv(rng):
    x = jnp.asarray(rng.standard_normal((2, 9, 9, 3)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 3, 5)), jnp.float32)
    cols = ref_im2col(x, 3, 3, 1, (1, 1, 1, 1))
    out = (cols @ w.reshape(-1, 5)).reshape(2, 9, 9, 5)
    ref = ref_conv2d(x, w, 1, "SAME")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------- the brick's product, bit for bit
BRICK_TABLES = sorted(n for n, m in REGISTRY.items() if m.mantissa_bits <= 7)
BRICK_TABLES += ["faulted", "canonical"]


def _brick_table(name):
    """(LUT, M): a registered multiplier's packed table; afm16's packed
    table with 5% of its bits flipped, carry bits among them; or an
    unpackable canonical table (exact products of 8-bit significands
    keep mantissa bits below the top 7)."""
    if name == "faulted":
        spec = FaultSpec(kind="bitflip", rate=0.05, seed=3)
        return apply_faults(get_packed_lut("afm16"), 7, spec, packed=True,
                            mult="afm16"), 7
    if name == "canonical":
        lut = generate_lut(get_multiplier("exact23"), 7)
        assert np.any(lut & np.uint32((1 << 16) - 1))
        return lut, 7
    return get_packed_lut(name), REGISTRY[name].mantissa_bits


def _as_f32(u):
    return np.asarray(u, np.uint32).view(np.float32)


def _bits(x):
    """uint32 words of f32 ``x``, a zero's sign dropped (+0.0 + x): the
    brick folds every product into a sum that starts at +0.0."""
    return (np.float32(0.0) + np.asarray(x, np.float32)).view(np.uint32)


@pytest.mark.parametrize("table", BRICK_TABLES)
def test_brick_product_bitwise(table, rng):
    """Every pair of words from the multiplier-property edge battery, NaN
    encodings and random words: the LUT brick's product is Alg. 2's
    (``core.amsim._amsim``) bit for bit.  Finite tiles take the factored
    product, exact on every pair of finite words; a tile holding an
    exponent-255 word, where the factored product differs, falls back to
    the integer form."""
    lut, M = _brick_table(table)
    words = np.concatenate([
        _EDGE_BITS, np.array([0x7FC00000, 0xFFC00001, 0x7F800001], np.uint32),
        rng.integers(0, 1 << 32, 400, dtype=np.uint64).astype(np.uint32)])
    special = ((words >> 23) & 0xFF) == 255
    finite, nonfinite = words[~special][:256], words[special]
    # Two tiles of finite words, then one tile of every exponent-255 word
    # (filled up with finite ones), on both sides of an outer product.
    w = np.concatenate([finite, nonfinite, finite[:128 - nonfinite.size]])
    ua, ub = w[:, None], w[None, :]
    ref = _amsim(ua, ub, lut, M, np, packed=lut.dtype == np.uint16)

    klut = common.kernel_lut(lut, M, interpret=True)
    assert common.table_form(klut) == (
        "integer.canonical" if table == "canonical" else "factored")
    if table != "canonical":
        wa = common._decode(jnp.asarray(_as_f32(ua)), M)
        wb = common._decode(jnp.asarray(_as_f32(ub)), M)
        args = (common._scale(wa), common._scale(wb),
                klut.astype(jnp.float32)[common._index(wa, M),
                                         common._index(wb, M)])
        # This backend's arithmetic, and IEEE's without flush-to-zero
        # (inf and NaN words overflow and make NaN: no warning wanted).
        with np.errstate(over="ignore", invalid="ignore"):
            ieee = common.factored_product(*map(np.asarray, args), xp=np)
        for fac in (common.factored_product(*args), ieee):
            fac = np.asarray(fac)
            np.testing.assert_array_equal(_bits(fac[:256, :256]),
                                          _bits(_as_f32(ref[:256, :256])))
            assert np.any(_bits(fac[256:]) != _bits(_as_f32(ref[256:])))

    out = approx_gemm(jnp.asarray(_as_f32(ua)), jnp.asarray(_as_f32(ub)),
                      lut, M, bm=128, bn=128, bk=8, chunk=8, interpret=True)
    np.testing.assert_array_equal(_bits(out), _bits(_as_f32(ref)))


def _plant(x, at, value):
    x = np.array(x)
    x[at] = value
    return jnp.asarray(x)


@pytest.mark.parametrize("kernel", ["gemm", "batched", "attention"])
def test_nonfinite_tile_falls_back_bitwise(kernel, rng):
    """Tiles holding inf, NaN, denormal and largest-exponent operands give
    the oracle's bits.  A zero times an inf is 0 under Alg. 2 and NaN in
    the factored product, so a finite output where the planted inf meets
    zeros shows the tile fell back to the integer form."""
    inf, nan, den, big = np.inf, np.nan, np.float32(1e-40), np.float32(2**127)
    policy = NumericsPolicy(mode="amsim_jnp", multiplier="afm16")
    lut = get_packed_lut("afm16")
    if kernel == "attention":
        q = rng.standard_normal((1, 8, 4, 8)).astype(np.float32)
        k = rng.standard_normal((1, 8, 2, 8)).astype(np.float32)
        k[..., 1] *= np.float32(2.0 ** -120)    # big q[.., 1] stays finite
        q = _plant(_plant(q, (0, 2, 0, 1), big), (0, 3, 1, 4), den)
        v = rng.standard_normal((1, 8, 2, 8)).astype(np.float32)
        v = _plant(_plant(v, (0, 5, 0, 3), inf), (0, 6, 1, 2), nan)
        k = jnp.asarray(k)
        pos = jnp.arange(8, dtype=jnp.int32)
        out = approx_attention_fused(q, k, v, pos, pos, lut, 7, causal=True,
                                     bq=8, bkv=8, chunk=8, interpret=True)
        ref = attend_einsum(q, k, v, pos, pos, policy, causal=True, window=0)
        # Queries before key 5 give it probability 0: 0 * inf reads 0.
        zero_times_inf = np.asarray(out)[0, :5, :2, 3]
    else:
        a = rng.standard_normal((2, 256, 64)).astype(np.float32)
        a[:, 3] = 0.0
        a = _plant(_plant(a, (0, 5, 7), den), (1, 6, 2), big)
        b = rng.standard_normal((2, 64, 256)).astype(np.float32)
        b = _plant(_plant(_plant(b, (0, 7, 9), inf), (1, 2, 11), nan),
                   (0, 4, 4), big)
        tiles = dict(bm=128, bn=128, bk=64, chunk=64, interpret=True)
        if kernel == "gemm":
            a, b = a[0], b[0]
            out = approx_gemm(a, b, lut, 7, **tiles)
        else:
            out = approx_gemm_batched(a, b, lut, 7, **tiles)
        ref = ref_amsim_gemm(a, b, jnp.asarray(get_lut("afm16")), 7)
        zero_times_inf = np.asarray(out).reshape(-1, 256, 256)[0, 3, 9]
    assert np.all(np.isfinite(zero_times_inf))
    np.testing.assert_array_equal(np.asarray(out).view(np.uint32),
                                  np.asarray(ref).view(np.uint32))


@pytest.mark.parametrize("kernel", ["gemm", "grouped"])
@pytest.mark.parametrize("bm,bn", [(128, 128), (64, 256), (32, 512),
                                   (16, 1024)])
def test_output_tile_moves_no_bit(kernel, bm, bn, rng):
    """The output tile is free of the numerics: at one fold (bk 64, chunk
    8) every tile gives the oracle's bits, for a short m (48 rows), an n
    that no wide tile divides (640), a B tile holding inf and NaN (the
    integer form), and the grouped expert kernel in row strips of ``bm``
    and its weight gradient."""
    fold = dict(bk=64, chunk=8, interpret=True)
    lut = get_packed_lut("afm16")
    mul = lambda x, y: amsim_multiply(x, y, jnp.asarray(LUT, jnp.uint32), 7)
    if kernel == "gemm":
        a = jnp.asarray(rng.standard_normal((48, 64)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((64, 640)), jnp.float32)
        for b in (b, _plant(_plant(b, (3, 600), np.inf), (5, 130), np.nan)):
            out = approx_gemm(a, b, lut, 7, bm=bm, bn=bn, **fold)
            np.testing.assert_array_equal(_bits(out),
                                          _bits(_chunked_gemm(a, b, mul, 8)))
        assert not np.isfinite(np.asarray(out)[:, [130, 600]]).all()
        return
    E = 4
    experts = jnp.asarray(rng.choice([0, 1, 3], 200).astype(np.int32))
    groups = grouped_layout(experts, E)
    R = grouped_rows_bound(200, E)
    x = jnp.zeros((R, 64), jnp.float32).at[groups.rows].set(
        jnp.asarray(rng.standard_normal((200, 64)), jnp.float32))
    w = jnp.asarray(rng.standard_normal((E, 64, 640)), jnp.float32)
    g = jnp.zeros((R, 640), jnp.float32).at[groups.rows].set(
        jnp.asarray(rng.standard_normal((200, 640)), jnp.float32))
    out = approx_gemm_grouped(x, w, groups, lut, 7, bm=bm, bn=bn, **fold)
    np.testing.assert_array_equal(
        _bits(out), _bits(ref_grouped_gemm(x, w, groups, mul, chunk=8)))
    dw = approx_gemm_grouped_dw(x, g, groups, lut, 7, E, bm=bm, bn=bn,
                                chunk=8, interpret=True)
    np.testing.assert_array_equal(
        _bits(dw), _bits(ref_grouped_gemm_dw(x, g, groups, E, mul, chunk=8)))
