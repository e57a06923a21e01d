"""Compile the main-path LUT kernels for a TPU v5e that is described, not
attached, at granite-3-2b's published widths (d=2048, 32 heads, 8 KV
heads, head_dim 64, d_ff=8192), granite-moe-3b-a800m's expert GEMMs and
resnet-mini's conv shapes.

Nothing runs: each test lowers and compiles with ``interpret=False``, so
the chip's compiler (Mosaic) refuses here what it would refuse on the
chip — an op with no TPU lowering, an unaligned slice, more VMEM than a
kernel may use.  The topology is described inside a module fixture,
never at import: only one process may hold the TPU library, and the
tests run under several workers.

Every launch carries its ``(site, pass)`` tag, in the custom call's
``kernel_metadata`` and in its instruction name, and keeps the base name
the benchmark's trace reduction (bench/trace.py) knows the kernel by.
"""
import collections
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lutgen import get_packed_lut
from repro.core.multipliers import get_multiplier
from repro.core.policy import NumericsPolicy

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import trace as T  # noqa: E402
from bench.program_trace import kernel_tag  # noqa: E402

MULT = get_multiplier("afm16")
M = MULT.mantissa_bits
D, H, KV, DH, FF = 2048, 32, 8, 64, 8192
VOCAB = 49155


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


def _launches(text):
    """Every Pallas launch in compiled HLO text: each tpu_custom_call, and
    each ``kind=kCustom`` fusion XLA built around one.  A tag's JSON
    spreads over several lines, so continuation lines are joined to the
    instruction they belong to."""
    instrs = []
    for line in text.splitlines():
        line = line.strip().removeprefix("ROOT ")
        if line.startswith("%") or not instrs:
            instrs.append(line)
        else:
            instrs[-1] += "\n" + line
    return [i for i in instrs if T.is_kernel(i)]


def _kernels(text):
    """(name in a trace, tag) of every Pallas launch, sorted."""
    return sorted((T.kernel_name(i), kernel_tag(i)) for i in _launches(text))


def _lut_shape():
    lut = get_packed_lut(MULT)
    return lut.shape, jnp.uint16


f32, i32 = jnp.float32, jnp.int32


def test_gemm_brick(one_chip):
    from repro.kernels.approx_gemm import approx_gemm
    _compile(lambda a, b, lut: approx_gemm(a, b, lut, M, interpret=False),
             one_chip, ((256, D), f32), ((D, FF), f32), _lut_shape())


@pytest.mark.parametrize("m,k,n", [
    (256, D, 3 * D // 2),   # qkv forward: q, k and v of 32 + 2 x 8 heads
    (256, FF, D),           # wd forward
    (256, D, VOCAB),        # the tied head: forward,
    (256, VOCAB, D),        # dx
    (D, 256, VOCAB),        # and dw
    (48, D, 3 * D // 2),    # qkv of a 48-row prefill
    (1, D, VOCAB),          # the head of an admission prefill: one row
], ids=["qkv", "wd", "head.fwd", "head.dx", "head.dw", "prefill48.qkv",
        "prefill.head"])
def test_gemm_at_the_rule_tile(one_chip, m, k, n):
    """The 2-D kernel at the output tile ``autotune.tile_2d`` picks for
    granite-3-2b's sites, the one each launch records: Mosaic takes every
    such tile (a bf16 one-hot of 16 rows or more, whole 128-lane blocks
    of B's scratch)."""
    from repro import obs
    from repro.kernels import autotune
    from repro.kernels.approx_gemm import approx_gemm
    before = collections.Counter(obs.routes)
    _compile(lambda a, b, lut: approx_gemm(a, b, lut, M, interpret=False),
             one_chip, ((m, k), f32), ((k, n), f32), _lut_shape())
    bm, bn = autotune.tile_2d(m, n)
    assert obs.routes - before == collections.Counter({
        f"gemm.tile.gemm2d.{bm}x{bn}": 1, "lut_brick.factored": 1})


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


@pytest.mark.parametrize("cin,cout,stride", [(16, 16, 1), (16, 32, 2)])
def test_conv_forward(one_chip, cin, cout, stride):
    from repro.kernels.approx_conv import approx_conv2d_fused
    text = _compile(lambda x, w, lut: approx_conv2d_fused(
                        x, w, lut, M, stride=stride, interpret=False),
                    one_chip, ((8, 32, 32, cin), f32),
                    ((3, 3, cin, cout), f32), _lut_shape())
    assert _kernels(text) == [("fused_impl", ("conv", "fwd"))]


def test_conv_weight_gradient(one_chip):
    from repro.kernels.approx_conv import approx_conv2d_dw
    text = _compile(lambda x, g, lut: approx_conv2d_dw(
                        x, g, lut, M, kh=3, kw=3, interpret=False),
                    one_chip, ((8, 32, 32, 16), f32), ((8, 32, 32, 16), f32),
                    _lut_shape())
    assert _kernels(text) == [("dw_impl", ("conv", "dw"))]


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


AFM16 = NumericsPolicy(mode="amsim", multiplier="afm16")


@pytest.mark.parametrize("site,k,n", [("wg", 1536, 512), ("wd", 512, 1536)])
def test_grouped_gemm_forward_dx_dw(one_chip, compiled_kernels, site, k, n):
    """The dropless MoE layer's expert GEMMs at granite-moe-3b-a800m's
    widths (d 1536, 40 experts of width 512, a 2048-token batch's 16384
    routed rows): forward, dx and dw, three launches of the one grouped
    impl, each tagged; the benchmark's work function reads the routed
    rows' products from each launch's operands."""
    from bench.kernels import work_of
    from repro.kernels.approx_gemm import GroupedRows, grouped_rows_bound
    from repro.kernels.ops import grouped_matmul
    E, routed = 40, 16384
    R = grouped_rows_bound(routed, E)

    def step(x, w, rows, sizes, tile_expert, live):
        g = GroupedRows(rows, sizes, tile_expert, live)
        return jax.value_and_grad(
            lambda x, w: grouped_matmul(x, w, g, AFM16, site).sum(),
            argnums=(0, 1))(x, w)
    text = _compile(step, one_chip, ((R, k), f32), ((E, k, n), f32),
                    ((routed,), i32), ((E,), i32), ((R // 128,), i32),
                    ((1,), i32))
    assert _kernels(text) == [("approx_gemm_grouped_impl", (site, p))
                              for p in ("dw", "dx", "fwd")]
    work = work_of("approx_gemm_grouped_impl")
    for launch in _launches(text):
        # Compiled text names the operands; their shapes are in the
        # layout constraints (a trace's op text carries them inline).
        parse = lambda t: [(d, tuple(int(x) for x in dims.split(",") if x))
                           for d, dims in T._SHAPE.findall(t)]
        operands = parse(launch.split("operand_layout_constraints={")[1]
                         .split("}}")[0])
        results = parse(launch.split("=", 1)[1].split("custom-call")[0])
        assert work(operands, results)[0] == 2.0 * routed * k * n


@pytest.mark.parametrize("arch", ["granite-3-2b", "granite-moe-3b-a800m"],
                         ids=["dense", "moe"])
def test_decode_tick_compiles(one_chip, compiled_kernels, arch):
    """One layer at its published widths on a 128-slot paged decode tick
    (one token a slot, pages of 16, 24 pages a slot): the per-op path the
    serving engine runs.  The MoE layer routes 1,024 rows to 40 experts
    through the grouped kernel."""
    from repro.configs import get_arch
    from repro.models.transformer import _dense_block, _init_dense_layer
    cfg = get_arch(arch)
    moe = cfg.moe is not None
    p = jax.eval_shape(lambda k: _init_dense_layer(k, cfg, moe),
                       jax.random.PRNGKey(0))
    slots, page, per_slot = 128, 16, 24
    pool = (slots * per_slot + 1, page, cfg.n_kv_heads, cfg.head_dim)

    leaves, tree = jax.tree.flatten(p)

    def tick(x, pool_k, pool_v, ptab, live, start, *leaves):
        cache = dict(pool_k=pool_k, pool_v=pool_v, ptab=ptab, live=live,
                     start=start)
        return _dense_block(jax.tree.unflatten(tree, leaves), x, cfg, AFM16,
                            cache, 0)
    text = _compile(tick, one_chip, ((slots, 1, cfg.d_model), f32),
                    (pool, f32), (pool, f32), ((slots, per_slot), i32),
                    ((slots,), jnp.bool_), ((slots,), i32),
                    *[(a.shape, a.dtype) for a in leaves])
    experts = [("approx_gemm_grouped_impl", (s, "fwd"))
               for s in ("wd", "wg", "wu")]
    mlp = [("approx_gemm_impl", (s, "fwd")) for s in ("wd", "wg", "wu")]
    want = sorted([("approx_gemm_impl", ("qkv", "fwd"))] * 3
                  + [("approx_gemm_impl", ("wo", "fwd")),
                     ("attn_impl", ("attn", "fwd"))]
                  + (experts + [("approx_gemm_impl", ("router", "fwd"))]
                     if moe else mlp))
    assert _kernels(text) == want


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels reached through the policy seam compile for the chip, not
    in interpret mode, while the host's backend is the CPU."""
    import repro.kernels.approx_attention as attention
    import repro.kernels.approx_gemm as gemm
    for module in (gemm, attention):
        monkeypatch.setattr(module, "resolve_interpret",
                            lambda interpret: False)


def test_gemm_launches_carry_site_and_pass(one_chip, compiled_kernels):
    """The tied head's forward, dx and dw GEMMs: three launches of the one
    jitted impl, each tagged with its own pass."""
    from repro.kernels.ops import policy_matmul

    def step(x, w):
        return jax.value_and_grad(
            lambda x, w: policy_matmul(x, w, AFM16, "head").sum(),
            argnums=(0, 1))(x, w)
    text = _compile(step, one_chip, ((128, D), f32), ((D, 512), f32))
    assert _kernels(text) == [("approx_gemm_impl", ("head", p))
                              for p in ("dw", "dx", "fwd")]


def test_batched_gemm_launches_carry_site_and_pass(one_chip,
                                                   compiled_kernels):
    """An attention score einsum and its VJP: both operands' gradients of
    an activation-activation product are dx."""
    from repro.kernels.ops import policy_einsum

    def step(q, k):
        return jax.value_and_grad(
            lambda q, k: policy_einsum("bqd,btd->bqt", q, k, AFM16,
                                       "attn_score").sum(),
            argnums=(0, 1))(q, k)
    text = _compile(step, one_chip, ((8, 128, DH), f32), ((8, 128, DH), f32))
    assert _kernels(text) == [("approx_gemm_batched_impl", ("attn_score", p))
                              for p in ("dx", "dx", "fwd")]


def test_attention_launch_is_tagged(one_chip):
    from repro.kernels.approx_attention import approx_attention_fused
    S = 256
    text = _compile(lambda q, k, v, lut: approx_attention_fused(
                        q, k, v, jnp.arange(S), jnp.arange(S), lut, M,
                        causal=True, interpret=False),
                    one_chip, ((1, S, H, DH), f32), ((1, S, KV, DH), f32),
                    ((1, S, KV, DH), f32), _lut_shape())
    assert _kernels(text) == [("attn_impl", ("attn", "fwd"))]


def test_fused_weight_gradient_keeps_its_tag(one_chip, compiled_kernels):
    """A scanned layer's weight gradient is written in place into the
    stacked gradient: XLA builds a ``kind=kCustom`` fusion around the
    kernel, which keeps no ``frontend_attributes``.  Its name carries the
    tag."""
    from repro.kernels.ops import policy_matmul

    def loss(ws, x):
        layer = lambda h, w: (policy_matmul(h, w, AFM16, "wo"), None)
        return jax.lax.scan(layer, x, ws)[0].sum()
    text = _compile(jax.grad(loss), one_chip, ((2, 256, 256), f32),
                    ((128, 256), f32))
    fused = [i for i in _launches(text) if "kind=kCustom" in i]
    assert fused and all("kernel_metadata" not in i for i in fused)
    assert {kernel_tag(i) for i in fused} == {("wo", "dw")}
    kernels = _kernels(text)
    assert {name for name, _ in kernels} == {"approx_gemm_impl"}
    assert {tag for _, tag in kernels} == {("wo", p) for p in ("fwd", "dx", "dw")}
