"""Compile the main-path LUT kernels for a TPU v5e that is described, not
attached, at granite-3-2b's published widths (d=2048, 32 heads, 8 KV
heads, head_dim 64, d_ff=8192) and resnet-mini's conv shapes.

Nothing runs: each test lowers and compiles with ``interpret=False``, so
the chip's compiler (Mosaic) refuses here what it would refuse on the
chip — an op with no TPU lowering, an unaligned slice, more VMEM than a
kernel may use.  The topology is described inside a module fixture,
never at import: only one process may hold the TPU library, and the
tests run under several workers.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lutgen import get_packed_lut
from repro.core.multipliers import get_multiplier

MULT = get_multiplier("afm16")
M = MULT.mantissa_bits
D, H, KV, DH, FF = 2048, 32, 8, 64, 8192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Executables compiled for a described chip cannot be read back from
    # the persistent cache without one: keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Lower and compile ``fn`` for the described chip; returns the HLO
    text, which must hold the Pallas kernel as a TPU custom call."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _lut_shape():
    lut = get_packed_lut(MULT)
    return lut.shape, jnp.uint16


f32, i32 = jnp.float32, jnp.int32


def test_gemm_brick(one_chip):
    from repro.kernels.approx_gemm import approx_gemm
    _compile(lambda a, b, lut: approx_gemm(a, b, lut, M, interpret=False),
             one_chip, ((256, D), f32), ((D, FF), f32), _lut_shape())


def test_gemm_batched(one_chip):
    from repro.kernels.approx_gemm import approx_gemm_batched
    _compile(lambda a, b, lut: approx_gemm_batched(a, b, lut, M,
                                                   interpret=False),
             one_chip, ((H, 128, DH), f32), ((H, DH, 512), f32),
             _lut_shape())


def test_prefill_attention(one_chip):
    from repro.kernels.approx_attention import approx_attention_fused
    S = 512
    _compile(lambda q, k, v, lut: approx_attention_fused(
                 q, k, v, jnp.arange(S), jnp.arange(S), lut, M,
                 causal=True, interpret=False),
             one_chip, ((1, S, H, DH), f32), ((1, S, KV, DH), f32),
             ((1, S, KV, DH), f32), _lut_shape())


def test_decode_attention_paged(one_chip):
    """Decode tick of the paged serving cache: per-row positions."""
    from repro.kernels.approx_attention import approx_attention_fused
    B, T = 4, 128
    _compile(lambda q, k, v, qp, kp, lut: approx_attention_fused(
                 q, k, v, qp, kp, lut, M, causal=True, interpret=False),
             one_chip, ((B, 1, H, DH), f32), ((B, T, KV, DH), f32),
             ((B, T, KV, DH), f32), ((B, 1), i32), ((B, T), i32),
             _lut_shape())


def test_decode_chain_qkv(one_chip):
    from repro.kernels.decode_chain import fused_qkv_norm
    _compile(lambda x, g, wq, wk, wv, lut: fused_qkv_norm(
                 x, g, wq, wk, wv, lut, M, eps=1e-5, interpret=False),
             one_chip, ((4, D), f32), ((D,), f32), ((D, H * DH), f32),
             ((D, KV * DH), f32), ((D, KV * DH), f32), _lut_shape())


def test_decode_chain_out_mlp(one_chip):
    from repro.kernels.decode_chain import fused_out_mlp
    _compile(lambda x, at, g, wo, wg, wu, wd, lut: fused_out_mlp(
                 x, at, g, wo, wg, wu, wd, lut, M, eps=1e-5,
                 interpret=False),
             one_chip, ((4, D), f32), ((4, H * DH), f32), ((D,), f32),
             ((H * DH, D), f32), ((D, FF), f32), ((D, FF), f32),
             ((FF, D), f32), _lut_shape())


@pytest.mark.parametrize("cin,cout,stride", [(16, 16, 1), (16, 32, 2)])
def test_conv_forward(one_chip, cin, cout, stride):
    from repro.kernels.approx_conv import approx_conv2d_fused
    _compile(lambda x, w, lut: approx_conv2d_fused(
                 x, w, lut, M, stride=stride, interpret=False),
             one_chip, ((8, 32, 32, cin), f32), ((3, 3, cin, cout), f32),
             _lut_shape())


def test_conv_weight_gradient(one_chip):
    from repro.kernels.approx_conv import approx_conv2d_dw
    _compile(lambda x, g, lut: approx_conv2d_dw(
                 x, g, lut, M, kh=3, kw=3, interpret=False),
             one_chip, ((8, 32, 32, 16), f32), ((8, 32, 32, 16), f32),
             _lut_shape())


def test_wide_table_raises_on_chip(one_chip):
    """M > 7 tables have no chip form: compiling one raises, it never
    routes to the oracle."""
    from repro.core.lutgen import get_lut
    from repro.kernels.approx_gemm import approx_gemm
    wide = get_multiplier("fp16xbf16")
    assert wide.mantissa_bits > 7
    with pytest.raises(NotImplementedError, match="no TPU lowering"):
        _compile(lambda a, b: approx_gemm(a, b, get_lut(wide),
                                          wide.mantissa_bits,
                                          interpret=False),
                 one_chip, ((128, 128), f32), ((128, 128), f32))


def test_decode_chain_attn_out_mlp(one_chip):
    """The 2-launch form: attention core folded into the back half."""
    from repro.kernels.decode_chain import fused_attn_out_mlp
    B, T = 4, 128
    _compile(lambda x, q, k, v, qp, kp, g, wo, wg, wu, wd, lut:
             fused_attn_out_mlp(x, q, k, v, qp, kp, g, wo, wg, wu, wd, lut,
                                M, eps=1e-5, interpret=False),
             one_chip, ((B, D), f32), ((B, 1, H, DH), f32),
             ((B, T, KV, DH), f32), ((B, T, KV, DH), f32), ((B, 1), i32),
             ((B, T), i32), ((D,), f32), ((H * DH, D), f32), ((D, FF), f32),
             ((D, FF), f32), ((FF, D), f32), _lut_shape())


def test_decode_chain_moe_launches(one_chip):
    """The MoE back half at granite-moe-3b-a800m's widths (d=1536, 24
    heads of 64, 40 experts of d_ff 512): wo -> norm, and the stacked
    expert-bank FFN."""
    from repro.kernels.decode_chain import fused_moe_ffn, fused_wo_norm
    d, k, experts, cap, ff = 1536, 24 * 64, 40, 8, 512
    _compile(lambda x, a, g, wo, lut: fused_wo_norm(
                 x, a, g, wo, lut, M, eps=1e-5, interpret=False),
             one_chip, ((4, d), f32), ((4, k), f32), ((d,), f32),
             ((k, d), f32), _lut_shape())
    _compile(lambda h, wg, wu, wd, lut: fused_moe_ffn(
                 h, wg, wu, wd, lut, M, interpret=False),
             one_chip, ((experts, cap, d), f32), ((experts, d, ff), f32),
             ((experts, d, ff), f32), ((experts, ff, d), f32), _lut_shape())
