"""Batched approximate-GEMM engine: golden oracle, packed LUT, autotuner.

Covers the three tentpole pieces:
  * ``approx_gemm_batched`` == stacked ``np_amsim_multiply`` oracle GEMMs
    per batch element — bit-exact in interpret mode with chunk=1 (fully
    sequential FP32 accumulation on both sides), allclose at the default
    chunked tiling;
  * packed uint16 LUT bitwise-equivalent to the canonical uint32 table,
    elementwise for every registered M<=7 multiplier and end-to-end
    through the kernel;
  * autotuner cache: write -> reload -> same config; corrupt file ->
    safe defaults + successful re-tune.
"""
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.amsim import np_amsim_multiply
from repro.core.lutgen import get_lut, get_packed_lut, pack_lut, unpack_lut
from repro.core.multipliers import REGISTRY, get_multiplier
from repro.kernels import autotune
from repro.kernels.approx_gemm import approx_gemm, approx_gemm_batched
from repro.kernels.ref import ref_amsim_gemm


def _np_stacked_oracle(a, b, lut, M):
    """Per-batch-element numpy AMSim GEMM, sequential FP32 accumulation
    over k — the exact order the kernel uses with chunk=1."""
    B, m, k = a.shape
    n = b.shape[2]
    acc = np.zeros((B, m, n), np.float32)
    for kk in range(k):
        acc = acc + np_amsim_multiply(
            a[:, :, kk, None], b[:, None, kk, :], lut, M)
    return acc


# ------------------------------------------------------------ golden oracle
@pytest.mark.parametrize("name", ["trunc7", "bf16", "mitchell12"])
@pytest.mark.parametrize("B,m,k,n", [
    (3, 33, 70, 17),     # ragged everything
    (2, 1, 129, 5),      # k crosses a block boundary, degenerate m
])
def test_batched_kernel_bitexact_vs_numpy_oracle(name, B, m, k, n, rng):
    mult = get_multiplier(name)
    M = mult.mantissa_bits
    lut = get_lut(mult)
    a = jnp.asarray(rng.standard_normal((B, m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, k, n)), jnp.float32)
    out = approx_gemm_batched(a, b, lut, M, bm=128, bn=128, bk=128,
                              chunk=1, interpret=True)
    ref = _np_stacked_oracle(np.asarray(a), np.asarray(b), lut, M)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_batched_kernel_default_tiling_matches_oracle(rng):
    """At the default (autotuned/fallback) tiling the chunk-axis reduction
    order may differ from sequential — allclose, and chunk=1 bit-exact."""
    mult = get_multiplier("afm16")
    lut = get_lut(mult)
    a = jnp.asarray(rng.standard_normal((3, 64, 150)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((3, 150, 60)), jnp.float32)
    out = approx_gemm_batched(a, b, lut, 7, interpret=True)
    ref = ref_amsim_gemm(a, b, jnp.asarray(lut), 7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_chunk_not_dividing_bk_is_snapped(rng):
    """Regression: chunk must divide bk or the kernel's fori_loop drops
    the tail k-elements of every block; the wrapper snaps it down."""
    mult = get_multiplier("afm16")
    lut = get_lut(mult)
    a = jnp.asarray(rng.standard_normal((2, 16, 96)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((2, 96, 16)), jnp.float32)
    out = approx_gemm_batched(a, b, lut, 7, bm=96, bn=96, bk=96, chunk=64,
                              interpret=True)
    ref = ref_amsim_gemm(a, b, jnp.asarray(lut), 7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_batched_equals_per_element_2d_kernel(rng):
    mult = get_multiplier("afm16")
    lut = get_lut(mult)
    a = jnp.asarray(rng.standard_normal((3, 40, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((3, 64, 24)), jnp.float32)
    kw = dict(bm=128, bn=128, bk=128, chunk=8, interpret=True)
    out = approx_gemm_batched(a, b, lut, 7, **kw)
    per = jnp.stack([approx_gemm(a[i], b[i], lut, 7, **kw)
                     for i in range(3)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(per))


# ------------------------------------------------------------- packed LUT
_M7 = sorted({m.name for m in REGISTRY.values() if m.mantissa_bits <= 7})


@pytest.mark.parametrize("name", _M7)
def test_packed_lut_bitwise_equivalent(name, rng):
    mult = get_multiplier(name)
    M = mult.mantissa_bits
    lut = get_lut(mult)
    packed = get_packed_lut(mult)
    assert packed is not None and packed.dtype == np.uint16
    np.testing.assert_array_equal(unpack_lut(packed, M), lut)
    a = np.concatenate([
        (rng.standard_normal(20000) * 10).astype(np.float32),
        np.array([0.0, -0.0, 1e38, -1e38, 1e-38, 2**-126, 1.0], np.float32),
    ])
    b = np.concatenate([
        (rng.standard_normal(20000) * 0.1).astype(np.float32),
        np.array([5.0, 3.0, 1e38, 1e38, 1e-38, 1.0, -0.0], np.float32),
    ])
    np.testing.assert_array_equal(
        np_amsim_multiply(a, b, lut, M),
        np_amsim_multiply(a, b, packed, M, packed=True))


def test_packed_lut_kernel_bitwise_equivalent(rng):
    mult = get_multiplier("realm16")
    lut = get_lut(mult)
    packed = get_packed_lut(mult)
    a = jnp.asarray(rng.standard_normal((2, 50, 33)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((2, 33, 20)), jnp.float32)
    kw = dict(bm=128, bn=128, bk=128, chunk=8, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(approx_gemm_batched(a, b, lut, 7, **kw)),
        np.asarray(approx_gemm_batched(a, b, packed, 7, **kw)))


def test_pack_lut_rejects_unpackable_tables():
    lut = get_lut(get_multiplier("afm16")).copy()
    lut[3] |= 1  # a mantissa bit below the top 7
    with pytest.raises(ValueError):
        pack_lut(lut, 7)


# -------------------------------------------------------------- autotuner
@pytest.fixture
def tuned_env(tmp_path, monkeypatch, rng):
    """Isolated autotune cache + tiny representative operands."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "gemm_blocks.json"))
    autotune.reload_cache()
    yield {
        "path": tmp_path / "gemm_blocks.json",
        "a": jnp.asarray(rng.standard_normal((2, 32, 32)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal((2, 32, 32)), jnp.float32),
        "lut": get_lut(get_multiplier("afm16")),
    }
    autotune.reload_cache()


_TINY_CANDIDATES = [autotune.BlockConfig(32, 32, 32, 8),
                    autotune.BlockConfig(32, 32, 32, 32)]


def test_autotune_cache_roundtrip(tuned_env):
    won = autotune.autotune("gemm3d", tuned_env["a"], tuned_env["b"],
                            tuned_env["lut"], 7,
                            candidates=_TINY_CANDIDATES, iters=1,
                            interpret=True)
    assert won in _TINY_CANDIDATES
    raw = json.loads(tuned_env["path"].read_text())
    assert raw["version"] == autotune.SCHEMA_VERSION
    (key, entry), = raw["entries"].items()
    assert key == autotune.cache_key("gemm3d", 32, 32, 32, 7, batch=2)
    assert (entry["bm"], entry["bn"], entry["bk"], entry["chunk"]) == won.astuple()
    # Fresh process simulation: drop the in-memory mirror, reload from disk.
    autotune.reload_cache()
    assert autotune.get_block_config("gemm3d", 32, 32, 32, 7, batch=2) == won
    # The winner is what the kernel wrapper now consults at trace time.
    out = approx_gemm_batched(tuned_env["a"], tuned_env["b"],
                              tuned_env["lut"], 7, interpret=True)
    ref = ref_amsim_gemm(tuned_env["a"], tuned_env["b"],
                         jnp.asarray(tuned_env["lut"]), 7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_autotune_raises_when_every_candidate_fails(tuned_env,
                                                   monkeypatch):
    """A sweep that times nothing raises its first failure instead of
    handing back a default as if it had won; nothing is cached."""
    errors = iter([RuntimeError("first"), ValueError("second")])

    def fail(fn, iters):
        raise next(errors)

    monkeypatch.setattr(autotune, "_time_call", fail)
    with pytest.raises(RuntimeError, match="first"):
        autotune.autotune("gemm3d", tuned_env["a"], tuned_env["b"],
                          tuned_env["lut"], 7, candidates=_TINY_CANDIDATES,
                          iters=1, interpret=True)
    assert not tuned_env["path"].exists()


def test_autotune_corrupt_cache_is_safe(tuned_env):
    tuned_env["path"].write_text("{ not json !!")
    autotune.reload_cache()
    # Corrupt file degrades to defaults, never raises.
    assert autotune.get_block_config("gemm3d", 32, 32, 32, 7, batch=2) == \
        autotune.DEFAULT_BATCHED
    assert autotune.get_block_config("gemm2d", 32, 32, 32, 7) == \
        autotune.BlockConfig(*autotune.tile_2d(32, 32), *autotune.FOLD_2D)
    # Re-tune overwrites the corrupt file with a valid cache.
    won = autotune.autotune("gemm3d", tuned_env["a"], tuned_env["b"],
                            tuned_env["lut"], 7,
                            candidates=_TINY_CANDIDATES, iters=1,
                            interpret=True)
    raw = json.loads(tuned_env["path"].read_text())
    assert raw["entries"]
    autotune.reload_cache()
    assert autotune.get_block_config("gemm3d", 32, 32, 32, 7, batch=2) == won


def test_autotune_cache_drops_an_entry_of_a_retired_kind(tuned_env):
    """A cache written when the fused decode chain was still tuned holds
    entries of its (bn, bko, bf, overlap) form.  They carry no ``bm``, so
    they parse as no tiling and are dropped, while the valid gemm2d entry
    beside them still loads."""
    stale = "cpu|retired|r128_d2048_k2048_f8192|M7"
    key = autotune.cache_key("gemm2d", 32, 32, 32, 7)
    tuned_env["path"].write_text(json.dumps({
        "version": autotune.SCHEMA_VERSION,
        "entries": {
            stale: {"bn": 128, "bko": 128, "bf": 128, "overlap": 1,
                    "us": 1.0},
            key: {"bm": 32, "bn": 32, "bk": 32, "chunk": 8, "us": 1.0}}}))
    autotune.reload_cache()
    assert autotune.get_block_config("gemm2d", 32, 32, 32, 7) == \
        autotune.BlockConfig(32, 32, 32, 8)
    assert list(autotune._entries()) == [key]


def test_shape_bucket_is_pow2_and_batch_aware():
    assert autotune.shape_bucket(256, 256, 256, batch=8) == "b8_m256_k256_n256"
    assert autotune.shape_bucket(200, 129, 96) == "m256_k256_n128"
    assert autotune.shape_bucket(1, 1, 1) == "m1_k1_n1"
