"""PolicyTable (core/policy): resolution precedence, construction-time
validation, uniform-table ≡ flat-policy bit-identity across all three
kernel families (fwd + VJP, single-device and on the 2x2 mesh), dx/dw
split resolution, no-retrace contract, and the multiplier-qualified
autotune cache keys.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests run when hypothesis is installed (requirements-dev);
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # the deterministic twin below covers the law
    HAVE_HYPOTHESIS = False

from repro.core.policy import (FAMILIES, PASSES, SITES, NumericsPolicy,
                               PolicyRule, PolicyTable, load_numerics,
                               site_family, table_from_assignments,
                               table_from_json)
from repro.kernels.ops import (approx_conv2d, attend_einsum,
                               fused_attention_enabled, policy_attention,
                               policy_einsum, policy_matmul)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

bitwise = lambda a, b: bool(jnp.all(jnp.asarray(a) == jnp.asarray(b)))


# =====================================================================
# Construction-time validation
# =====================================================================

def test_invalid_tables_raise_at_construction():
    # uncovered site (no wildcard default)
    with pytest.raises(ValueError, match="does not cover"):
        PolicyTable((PolicyRule("amsim", "mitchell8", site="conv"),))
    # surrogate + log-family multiplier: per-rule check
    with pytest.raises(ValueError, match="surrogate"):
        PolicyRule("surrogate", "mitchell8", site="wd")
    # unknown mode / multiplier / site / family / pass
    with pytest.raises(ValueError, match="mode"):
        PolicyRule("quantum", "fp32")
    with pytest.raises(ValueError, match="multiplier"):
        PolicyRule("amsim", "notamult")
    with pytest.raises(ValueError, match="site"):
        PolicyRule("native", site="wx")
    with pytest.raises(ValueError, match="family"):
        PolicyRule("native", family="fft")
    with pytest.raises(ValueError, match="pass"):
        PolicyRule("native", pass_="sideways")
    # contradictory site+family pairing can never match
    with pytest.raises(ValueError, match="never match"):
        PolicyRule("native", site="conv", family="gemm")
    # duplicate patterns would make resolution order-dependent
    with pytest.raises(ValueError, match="conflicting"):
        PolicyTable((PolicyRule("amsim", "mitchell8"), PolicyRule("native")))
    with pytest.raises(ValueError, match="at least one rule"):
        PolicyTable(())


def test_assignment_and_json_round_trip(tmp_path):
    spec = "conv=mitchell8,attn_score=bf16,dw=native,default=afm10"
    t = table_from_assignments(spec)
    assert t.resolve("conv").multiplier == "mitchell8"
    assert t.resolve("attn_score").multiplier == "bf16"
    assert t.resolve("wg", pass_="dw").mode == "native"
    assert t.resolve("wg").multiplier == "afm10"
    # JSON round trip preserves resolution cell-for-cell
    import json
    path = tmp_path / "table.json"
    path.write_text(json.dumps(t.to_json()))
    t2 = table_from_json(str(path))
    for s in list(SITES) + [None]:
        for p in PASSES:
            assert t.resolve(s, pass_=p) == t2.resolve(s, pass_=p)
    # load_numerics: mode name -> flat, .json path -> table
    assert isinstance(load_numerics("amsim_jnp", "afm16"), NumericsPolicy)
    assert isinstance(load_numerics(str(path)), PolicyTable)
    # bad shorthand
    with pytest.raises(ValueError, match="unknown assignment key"):
        table_from_assignments("wx=bf16")
    with pytest.raises(ValueError, match="key=value"):
        table_from_assignments("conv")
    with pytest.raises(ValueError, match="unknown pass"):
        table_from_assignments("qkv.up=native")
    with pytest.raises(ValueError, match="unknown site/family"):
        table_from_assignments("wx.dw=native")


def test_combined_site_pass_shorthand():
    """`qkv.dw=native` pins a specific site's pass (specificity 5),
    which the plain `dw=` rule cannot reach past a site rule — the
    documented precedence caveat (docs/policies.md)."""
    t = table_from_assignments("qkv=mitchell8,dw=native,"
                               "default=amsim_jnp:afm16")
    # site rule outranks the pass rule at its own site...
    assert t.resolve("qkv", pass_="dw").multiplier == "mitchell8"
    assert t.resolve("wd", pass_="dw").mode == "native"
    # ...and the combined key overrides it
    t2 = table_from_assignments("qkv=mitchell8,qkv.dw=native,dw=native,"
                                "default=amsim_jnp:afm16")
    assert t2.resolve("qkv", pass_="dw").mode == "native"
    assert t2.resolve("qkv").multiplier == "mitchell8"
    # family.pass works too
    t3 = table_from_assignments("attention.dx=native,"
                                "default=amsim_jnp:afm16")
    assert t3.resolve("attn_score", pass_="dx").mode == "native"
    assert t3.resolve("attn_score").multiplier == "afm16"


# =====================================================================
# Resolution precedence: deterministic, total, most-specific-wins
# =====================================================================

_MULTS = ("bf16", "mitchell8", "afm10", "exact7", "trunc7")


def _random_table(rng) -> PolicyTable:
    """A random valid table: wildcard default + distinct random rules."""
    rules = [PolicyRule("amsim_jnp", "afm16")]
    seen = {(None, None, None)}
    for _ in range(int(rng.integers(0, 8))):
        site = rng.choice([None, *SITES])
        site = None if site is None else str(site)
        fam = site_family(site) if site is not None else \
            (None if rng.random() < 0.5 else str(rng.choice(FAMILIES)))
        if site is not None and rng.random() < 0.5:
            fam = None
        pas = None if rng.random() < 0.5 else str(rng.choice(PASSES))
        if (site, fam, pas) in seen:
            continue
        seen.add((site, fam, pas))
        rules.append(PolicyRule("amsim_jnp", str(rng.choice(_MULTS)),
                                site=site, family=fam, pass_=pas))
    return PolicyTable(tuple(rules))


def _check_precedence_laws(table: PolicyTable):
    """Totality + determinism + most-specific-wins on every query."""
    for site in list(SITES) + [None]:
        fams = [site_family(site)] if site is not None else list(FAMILIES)
        for fam in fams:
            for pas in PASSES:
                leaf = table.resolve(site, fam, pas)      # total: no raise
                assert leaf == table.resolve(site, fam, pas)  # deterministic
                win = table.winning_rule(site, fam, pas)
                assert (leaf.mode, leaf.multiplier) == (win.mode,
                                                        win.multiplier)
                matches = [r for r in table.rules
                           if r.matches(site, fam, pas)]
                assert win in matches
                # strictly most specific: no other match outranks it, and
                # equal rank never happens (duplicate patterns rejected)
                for r in matches:
                    if r is not win:
                        assert r.specificity < win.specificity
                # site-match dominance: any site-specific match beats
                # every site-wildcard match
                if any(r.site is not None for r in matches):
                    assert win.site is not None


def test_precedence_deterministic_total_seeded():
    rng = np.random.default_rng(0)
    for _ in range(50):
        _check_precedence_laws(_random_table(rng))


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**32 - 1))
    def test_precedence_deterministic_total_property(seed):
        _check_precedence_laws(_random_table(np.random.default_rng(seed)))


def test_specificity_ordering_site_over_family_over_pass():
    t = PolicyTable((
        PolicyRule("amsim_jnp", "afm16"),                        # spec 0
        PolicyRule("amsim_jnp", "bf16", pass_="dw"),             # spec 1
        PolicyRule("amsim_jnp", "mitchell8", family="attention"),  # spec 2
        PolicyRule("amsim_jnp", "exact7", site="attn_score"),    # spec 4
        PolicyRule("native", site="attn_score", pass_="dw"),     # spec 5
    ))
    assert t.resolve("wg").multiplier == "afm16"
    assert t.resolve("wg", pass_="dw").multiplier == "bf16"
    assert t.resolve("attn_value").multiplier == "mitchell8"      # family
    assert t.resolve("attn_score").multiplier == "exact7"         # site wins
    assert t.resolve("attn_score", pass_="dw").mode == "native"   # site+pass
    # family rule beats pass rule at a family site
    assert t.resolve("attn_value", pass_="dw").multiplier == "mitchell8"


def test_flat_policy_flags_equal_compiled_in_rules():
    """NumericsPolicy.resolve (the legacy flags) agrees cell-for-cell
    with its as_table() explicit-rule translation."""
    for aa in (True, False):
        for ab in (True, False):
            flat = NumericsPolicy("amsim_jnp", "afm16", aa, ab)
            table = flat.as_table()
            for s in list(SITES) + [None]:
                for p in PASSES:
                    lf, lt = flat.resolve(s, pass_=p), table.resolve(s, pass_=p)
                    assert (lf.mode, lf.multiplier) == (lt.mode, lt.multiplier), \
                        (aa, ab, s, p)


def test_tables_are_hashable_static_args():
    t1 = table_from_assignments("conv=mitchell8,default=afm10")
    t2 = table_from_assignments("conv=mitchell8,default=afm10")
    assert hash(t1) == hash(t2) and t1 == t2
    assert jax.jit(lambda x, p: x * 0 + p.resolve("wg").mantissa_bits,
                   static_argnums=1)(jnp.ones(()), t1) == 10


# =====================================================================
# Uniform table ≡ flat policy: bit-identity, all three families
# =====================================================================

def _uniform(mode, mult):
    return PolicyTable((PolicyRule(mode, mult),))


@pytest.mark.parametrize("mult", ["exact7", "mitchell8"])
@pytest.mark.parametrize("mode", ["amsim", "amsim_jnp"])
def test_uniform_table_bit_identical_gemm(rng, mode, mult):
    flat = NumericsPolicy(mode=mode, multiplier=mult)
    uni = _uniform(mode, mult)
    a = jnp.asarray(rng.standard_normal((3, 16, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32)
    assert bitwise(policy_matmul(a, w, flat), policy_matmul(a, w, uni, "wg"))
    lf = lambda w_: jnp.sum(policy_matmul(a, w_, flat) ** 2)
    lu = lambda w_: jnp.sum(policy_matmul(a, w_, uni, "wg") ** 2)
    gf, gu = jax.grad(lf)(w), jax.grad(lu)(w)
    assert bitwise(gf, gu)
    # einsum path too (the batched engine)
    e = jnp.asarray(rng.standard_normal((3, 16, 32)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((3, 32, 8)), jnp.float32)
    assert bitwise(policy_einsum("bmk,bkn->bmn", e, b, flat),
                   policy_einsum("bmk,bkn->bmn", e, b, uni, "ssm"))


@pytest.mark.parametrize("mult", ["exact7", "mitchell8"])
def test_uniform_table_bit_identical_conv(rng, mult):
    flat = NumericsPolicy(mode="amsim", multiplier=mult)
    uni = _uniform("amsim", mult)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 16, 32)) * 0.1, jnp.float32)
    assert bitwise(approx_conv2d(x, w, 1, "SAME", flat),
                   approx_conv2d(x, w, 1, "SAME", uni))
    gf = jax.grad(lambda t: jnp.sum(
        approx_conv2d(*t, 1, "SAME", flat) ** 2))((x, w))
    gu = jax.grad(lambda t: jnp.sum(
        approx_conv2d(*t, 1, "SAME", uni) ** 2))((x, w))
    assert bitwise(gf[0], gu[0]) and bitwise(gf[1], gu[1])


@pytest.mark.parametrize("mult", ["exact7", "mitchell8"])
def test_uniform_table_bit_identical_attention(rng, mult):
    flat = NumericsPolicy(mode="amsim", multiplier=mult)
    uni = _uniform("amsim", mult)
    B, S, H, KV, dh = 2, 16, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    assert fused_attention_enabled(uni, q.shape, k.shape)
    assert bitwise(policy_attention(q, k, v, pos, pos, flat, True, 0),
                   policy_attention(q, k, v, pos, pos, uni, True, 0))
    gf = jax.grad(lambda t: jnp.sum(
        policy_attention(*t, pos, pos, flat, True, 0) ** 2))((q, k, v))
    gu = jax.grad(lambda t: jnp.sum(
        policy_attention(*t, pos, pos, uni, True, 0) ** 2))((q, k, v))
    assert all(bitwise(a, b) for a, b in zip(gf, gu))
    # einsum lowering as well (amsim_jnp)
    flatj = NumericsPolicy(mode="amsim_jnp", multiplier=mult)
    unij = _uniform("amsim_jnp", mult)
    assert bitwise(
        attend_einsum(q, k, v, pos, pos, flatj, causal=True, window=0),
        attend_einsum(q, k, v, pos, pos, unij, causal=True, window=0))


def test_uniform_table_bit_identical_on_mesh():
    """Acceptance: uniform-table ≡ flat for the shard_fused paths on a
    2x2 debug mesh — column/row matmul fwd + VJP and sharded attention
    fwd + VJP, for exact7 and mitchell8 (subprocess with forced host
    devices + hermetic autotune cache, as in test_sharded_fused)."""
    code = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.policy import NumericsPolicy, PolicyRule, PolicyTable
    from repro.distributed import shard_fused as sf

    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh()
    rng = np.random.default_rng(0)
    bitwise = lambda a, b: bool(jnp.all(a == b))

    for mult in ("exact7", "mitchell8"):
        flat = NumericsPolicy(mode="amsim", multiplier=mult)
        uni = PolicyTable((PolicyRule("amsim", mult),))
        x = jnp.asarray(rng.standard_normal((8, 16, 128)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((128, 256)) * 0.1, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((256, 128)) * 0.1, jnp.float32)
        with jax.set_mesh(mesh):
            of = jax.jit(lambda a, b: sf.column_parallel_matmul(
                a, b, flat, mesh))(x, w1)
            ou = jax.jit(lambda a, b: sf.column_parallel_matmul(
                a, b, uni, mesh, "qkv"))(x, w1)
            assert bitwise(of, ou), f"{mult}: col fwd"
            rf = jax.jit(lambda a, b: sf.row_parallel_matmul(
                a, b, flat, mesh))(of, w2)
            ru = jax.jit(lambda a, b: sf.row_parallel_matmul(
                a, b, uni, mesh, "wo"))(of, w2)
            assert bitwise(rf, ru), f"{mult}: row fwd"
            def pair(pol, site1, site2):
                def f(t):
                    h = sf.column_parallel_matmul(t[0], t[1], pol, mesh,
                                                  site1)
                    return jnp.sum(sf.row_parallel_matmul(
                        h, t[2], pol, mesh, site2) ** 2)
                return jax.jit(jax.grad(f))((x, w1, w2))
            gf = pair(flat, None, None)
            gu = pair(uni, "qkv", "wo")
            for name, a, b in zip("xw1w2", gf, gu):
                assert bitwise(a, b), f"{mult}: pair d{name}"

            B, S, H, KV, dh = 4, 16, 4, 2, 32
            q = jnp.asarray(rng.standard_normal((B, S, H, dh)), jnp.float32)
            k = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
            v = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
            pos = jnp.arange(S, dtype=jnp.int32)
            af = jax.jit(lambda a, b, c: sf.sharded_attention(
                a, b, c, pos, pos, flat, causal=True, window=0,
                mesh=mesh))(q, k, v)
            au = jax.jit(lambda a, b, c: sf.sharded_attention(
                a, b, c, pos, pos, uni, causal=True, window=0,
                mesh=mesh))(q, k, v)
            assert bitwise(af, au), f"{mult}: attn fwd"
            gaf = jax.jit(jax.grad(lambda t: jnp.sum(sf.sharded_attention(
                *t, pos, pos, flat, causal=True, window=0,
                mesh=mesh) ** 2)))((q, k, v))
            gau = jax.jit(jax.grad(lambda t: jnp.sum(sf.sharded_attention(
                *t, pos, pos, uni, causal=True, window=0,
                mesh=mesh) ** 2)))((q, k, v))
            assert all(bitwise(a, b) for a, b in zip(gaf, gau)), \\
                f"{mult}: attn vjp"
        print("OK", mult)
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"),
               REPRO_AUTOTUNE_CACHE="/tmp/repro_ptbl_test_noexist/x.json")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK exact7" in out.stdout and "OK mitchell8" in out.stdout


# =====================================================================
# Per-pass splits: dx and dw can now differ
# =====================================================================

def test_dx_dw_split_resolution(rng):
    """Weight matmul with dw=native: dW is bitwise the exact-backward
    reference (same approximate forward, native backward GEMMs) while
    dA stays bitwise the fully-approximate one — and vice versa for
    dx=native.  This is the new capability: the two backward passes can
    differ, which the flat approx_backward flag could never express."""
    a = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32)
    approx = NumericsPolicy(mode="amsim_jnp", multiplier="mitchell8")
    exact_bwd = NumericsPolicy(mode="amsim_jnp", multiplier="mitchell8",
                               approx_backward=False)

    def grads(policy, site=None):
        return jax.grad(lambda t: jnp.sum(
            policy_matmul(*t, policy, site) ** 2), argnums=0)((a, w))

    ga_app, gw_app = grads(approx)          # dx, dw both approximate
    ga_eb, gw_eb = grads(exact_bwd)         # dx, dw both native
    assert not bitwise(gw_app, gw_eb)       # the split must be observable
    assert not bitwise(ga_app, ga_eb)

    t_dw_nat = table_from_assignments(
        "dw=native,default=amsim_jnp:mitchell8")
    ga, gw = grads(t_dw_nat, "wg")
    assert bitwise(gw, gw_eb) and bitwise(ga, ga_app)

    t_dx_nat = table_from_assignments(
        "dx=native,default=amsim_jnp:mitchell8")
    ga, gw = grads(t_dx_nat, "wg")
    assert bitwise(ga, ga_eb) and bitwise(gw, gw_app)


def test_stacked_expert_weights_resolve_dw(rng):
    """MoE expert banks stack their FFN weights 3-D, taking the
    equal-batch matmul layout — their weight gradients must still
    resolve under the dw pass at the wg/wu/wd sites (regression: the
    rank-based rule alone would misroute them to dx)."""
    E, C, d, ff = 2, 8, 16, 24
    x = jnp.asarray(rng.standard_normal((E, C, d)), jnp.float32)
    wbank = jnp.asarray(rng.standard_normal((E, d, ff)) * 0.1, jnp.float32)
    approx = NumericsPolicy(mode="amsim_jnp", multiplier="mitchell8")
    exact_bwd = NumericsPolicy(mode="amsim_jnp", multiplier="mitchell8",
                               approx_backward=False)
    t_dw_nat = table_from_assignments("dw=native,default=amsim_jnp:mitchell8")

    def gw(policy, site=None):
        return jax.grad(lambda w_: jnp.sum(
            policy_matmul(x, w_, policy, site) ** 2))(wbank)

    assert not bitwise(gw(approx), gw(exact_bwd))
    assert bitwise(gw(t_dw_nat, "wg"), gw(exact_bwd))     # dw rule applies
    # ...while an activation-style site keeps the dx resolution
    t_dx_nat = table_from_assignments("dx=native,default=amsim_jnp:mitchell8")
    assert bitwise(gw(t_dx_nat, "ssm"), gw(exact_bwd))


def test_attention_site_split_forces_einsum(rng):
    """A table that resolves attn_score and attn_value to different
    multipliers cannot take the one-LUT fused kernel: the guard refuses
    and the einsum lowering honours the split."""
    t = table_from_assignments("attn_score=bf16,attn_value=mitchell8,"
                               "default=amsim:mitchell8")
    assert not fused_attention_enabled(t, (2, 16, 4, 32), (2, 16, 2, 32))
    B, S, H, KV, dh = 1, 8, 2, 1, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    tj = table_from_assignments("attn_score=amsim_jnp:bf16,"
                                "attn_value=amsim_jnp:mitchell8,"
                                "default=amsim_jnp:afm16")
    out = attend_einsum(q, k, v, pos, pos, tj, causal=True, window=0)
    # reference: hand-computed split lowering
    from repro.kernels.common import attention_mask
    from repro.kernels.ops import NEG_INF
    qg = q.reshape(B, S, KV, H // KV, dh)
    sc = policy_einsum("bqkgd,btkd->bkgqt", qg, k,
                       NumericsPolicy("amsim_jnp", "bf16")) \
        / jnp.sqrt(float(dh))
    mask = attention_mask(pos, pos, causal=True, window=0)
    probs = jax.nn.softmax(jnp.where(mask[None, None, None], sc, NEG_INF), -1)
    ref = policy_einsum("bkgqt,btkd->bqkgd", probs, v,
                        NumericsPolicy("amsim_jnp", "mitchell8"))
    assert bitwise(out, ref.reshape(B, S, H, dh))


# =====================================================================
# No-retrace contract + autotune keying
# =====================================================================

def test_mixed_table_no_retrace(rng):
    """A many-rule table is a static arg: training-style fwd+bwd steps
    trace exactly once, and re-running with an equal table instance hits
    the same jit cache entry."""
    t = table_from_assignments("qkv=trunc7,wd=bf16,dw=native,"
                               "default=amsim_jnp:afm16")
    traces = [0]

    def loss(a, w1, w2):
        traces[0] += 1
        h = policy_matmul(a, w1, t, "qkv")
        return jnp.sum(policy_matmul(jax.nn.silu(h), w2, t, "wd") ** 2)

    f = jax.jit(jax.grad(loss, argnums=(1, 2)))
    a = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((32, 32)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((32, 16)) * 0.1, jnp.float32)
    for _ in range(4):
        jax.block_until_ready(f(a, w1, w2))
    assert traces[0] == 1, f"retraced: {traces[0]}"
    # an equal (but distinct) table object must not retrace either
    t2 = table_from_assignments("qkv=trunc7,wd=bf16,dw=native,"
                                "default=amsim_jnp:afm16")
    assert t2 == t

    def loss2(a, w1, w2):
        traces[0] += 1
        h = policy_matmul(a, w1, t2, "qkv")
        return jnp.sum(policy_matmul(jax.nn.silu(h), w2, t2, "wd") ** 2)

    jax.block_until_ready(jax.jit(jax.grad(loss2, argnums=(1, 2)))(a, w1, w2))
    assert traces[0] == 2  # distinct closure traces once, never per call


def test_autotune_keys_multiplier_qualified(tmp_path, monkeypatch):
    """Cache keys gain the resolved multiplier name; lookups fall back
    to the bare-M key so legacy entries still serve."""
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "blocks.json"))
    autotune.reload_cache()
    k_bare = autotune.cache_key("gemm3d", 256, 256, 256, 7, 8, "cpu")
    k_mit = autotune.cache_key("gemm3d", 256, 256, 256, 7, 8, "cpu",
                               mult="mitchell8")
    assert k_bare.endswith("|M7") and k_mit.endswith("|M7-mitchell8")
    assert k_bare != k_mit
    cfg_bare = autotune.BlockConfig(128, 128, 256, 32)
    cfg_mit = autotune.BlockConfig(256, 128, 256, 32)
    autotune._save_entry(k_bare, cfg_bare, 1.0)
    # fallback: multiplier-qualified lookup serves the bare entry
    got = autotune.get_block_config("gemm3d", 256, 256, 256, 7, batch=8,
                                    backend="cpu", mult="mitchell8")
    assert got == cfg_bare
    # a per-multiplier entry then takes precedence for its multiplier only
    autotune._save_entry(k_mit, cfg_mit, 1.0)
    assert autotune.get_block_config("gemm3d", 256, 256, 256, 7, batch=8,
                                     backend="cpu",
                                     mult="mitchell8") == cfg_mit
    assert autotune.get_block_config("gemm3d", 256, 256, 256, 7, batch=8,
                                     backend="cpu", mult="bf167") == cfg_bare
    assert autotune.get_block_config("gemm3d", 256, 256, 256, 7, batch=8,
                                     backend="cpu") == cfg_bare
    autotune.reload_cache()
