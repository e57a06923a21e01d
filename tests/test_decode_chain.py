"""Fused decode chain (kernels/decode_chain.py + ops.decode_qkv /
ops.decode_out_mlp): end-to-end bit identity against the per-op
lowering for exact, log-based, and packed-LUT multipliers — single
device and 2x2 debug mesh — plus kill-switch nesting semantics, psum
overlap settings, and the zero-retrace contract through the
continuous-batching scheduler's decode ticks.

The bit contract requires both sides to resolve identical kernel block
configs, so the in-process tests pin REPRO_AUTOTUNE_CACHE to an empty
path (module fixture) and the mesh tests run in subprocesses with the
same pin — the idiom of tests/test_sharded_fused.py.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HERMETIC = {
    "REPRO_AUTOTUNE_CACHE": "/tmp/repro_decode_chain_test_no_such/x.json",
}

_MULTS = ("exact7", "mitchell8", "bf16")  # exact / log-based / packed-u16
_B, _PLEN, _MAX_LEN = 2, 8, 32


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def hermetic():
    """Pin the autotune cache to an empty path for every in-process test:
    a tuned entry that differs between the q/k/v shape buckets would
    change the shared-fold derivation and void the bit comparisons."""
    from repro.kernels import autotune
    old = os.environ.get("REPRO_AUTOTUNE_CACHE")
    os.environ["REPRO_AUTOTUNE_CACHE"] = _HERMETIC["REPRO_AUTOTUNE_CACHE"]
    autotune.reload_cache()
    yield
    if old is None:
        os.environ.pop("REPRO_AUTOTUNE_CACHE", None)
    else:
        os.environ["REPRO_AUTOTUNE_CACHE"] = old
    autotune.reload_cache()


@pytest.fixture(scope="module")
def setup(hermetic):
    from repro.configs import get_arch, reduced
    from repro.models.transformer import init_lm
    cfg = reduced(get_arch("granite-3-2b"), n_layers=2)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _with_env(env: dict):
    """(saved, apply) helper: set/unset env vars, return restore map."""
    saved = {k: os.environ.get(k) for k in env}
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    return saved


def _decode_logits(cfg, pol, params, env: dict, n_steps: int = 3):
    """Shared prefill + ``n_steps`` greedy decode steps under the given
    REPRO_* env; returns the per-step logits (numpy)."""
    from repro.models.transformer import init_lm_caches
    from repro.serve.engine import make_prefill, make_serve_step
    saved = _with_env(env)
    try:
        toks = jax.random.randint(jax.random.PRNGKey(1), (_B, _PLEN), 1,
                                  cfg.vocab)
        caches = init_lm_caches(cfg, _B, _MAX_LEN)
        nxt, caches = jax.jit(make_prefill(cfg, pol, _MAX_LEN))(
            params, toks, caches)
        step = jax.jit(make_serve_step(cfg, pol))
        outs = []
        for _ in range(n_steps):
            logits, nxt, caches = step(params, nxt, caches)
            outs.append(np.asarray(logits))
        return outs
    finally:
        _with_env(saved)


# ------------------------------------------------- single-device identity
@pytest.mark.parametrize("mult", _MULTS)
def test_fused_decode_bit_exact_single_device(setup, mult):
    """The whole point of the chain: REPRO_DECODE_FUSED on vs off must
    be bitwise-invisible in the decode logits, every step, with the
    kernel trace counter proving the fused path actually engaged (and
    that the kill switch actually disengaged it)."""
    from repro.core.policy import NumericsPolicy
    from repro.kernels import decode_chain
    cfg, params = setup
    pol = NumericsPolicy(mode="amsim", multiplier=mult)

    t0 = decode_chain.trace_count()
    fused = _decode_logits(cfg, pol, params, {"REPRO_DECODE_FUSED": "1"})
    assert decode_chain.trace_count() > t0, \
        f"{mult}: fused chain never engaged"

    t1 = decode_chain.trace_count()
    perop = _decode_logits(cfg, pol, params, {"REPRO_DECODE_FUSED": "0"})
    assert decode_chain.trace_count() == t1, \
        f"{mult}: REPRO_DECODE_FUSED=0 did not disable the chain"

    for i, (a, b) in enumerate(zip(fused, perop)):
        np.testing.assert_array_equal(a, b, err_msg=f"{mult} step {i}")


def test_decode_chain_vjp_matches_oracle(hermetic):
    """ops.decode_qkv / ops.decode_out_mlp custom VJPs recompute through
    the per-op oracle, so forward AND gradients are bitwise-identical to
    the unfused lowering (the property the training path relies on if a
    chain op ever appears under grad)."""
    from repro.core.policy import NumericsPolicy
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    rows, d, K, KVd, F = 2, 128, 128, 64, 256
    x = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    attn = jnp.asarray(rng.standard_normal((rows, K)), jnp.float32)
    g1 = jnp.asarray(rng.standard_normal((d,)) * 0.1 + 1.0, jnp.float32)
    g2 = jnp.asarray(rng.standard_normal((d,)) * 0.1 + 1.0, jnp.float32)
    wq = jnp.asarray(rng.standard_normal((d, K)) * 0.1, jnp.float32)
    wk = jnp.asarray(rng.standard_normal((d, KVd)) * 0.1, jnp.float32)
    wv = jnp.asarray(rng.standard_normal((d, KVd)) * 0.1, jnp.float32)
    wo = jnp.asarray(rng.standard_normal((K, d)) * 0.1, jnp.float32)
    wg = jnp.asarray(rng.standard_normal((d, F)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((d, F)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((F, d)) * 0.1, jnp.float32)
    for mult in ("exact7", "mitchell8"):
        pol = NumericsPolicy(mode="amsim", multiplier=mult)

        def qkv_loss(fn, args):
            q, k, v = fn(*args, pol, 1e-5)
            return jnp.sum(q ** 2) + jnp.sum(k ** 2) + jnp.sum(v ** 2)

        args = (x, g1, wq, wk, wv)
        f = jax.jit(lambda a: qkv_loss(ops.decode_qkv, a))(args)
        r = jax.jit(lambda a: qkv_loss(ops.decode_qkv_oracle, a))(args)
        assert bool(f == r), f"{mult}: qkv fwd loss not bitwise"
        gf = jax.jit(jax.grad(lambda a: qkv_loss(ops.decode_qkv, a)))(args)
        gr = jax.jit(jax.grad(
            lambda a: qkv_loss(ops.decode_qkv_oracle, a)))(args)
        for name, a, b in zip("x g1 wq wk wv".split(), gf, gr):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{mult}: qkv d{name}")

        margs = (x, attn, g2, wo, wg, wu, wd)
        mf = jax.jit(lambda a: jnp.sum(
            ops.decode_out_mlp(*a, pol, 1e-5) ** 2))(margs)
        mr = jax.jit(lambda a: jnp.sum(
            ops.decode_out_mlp_oracle(*a, pol, 1e-5) ** 2))(margs)
        assert bool(mf == mr), f"{mult}: out_mlp fwd loss not bitwise"
        gmf = jax.jit(jax.grad(lambda a: jnp.sum(
            ops.decode_out_mlp(*a, pol, 1e-5) ** 2)))(margs)
        gmr = jax.jit(jax.grad(lambda a: jnp.sum(
            ops.decode_out_mlp_oracle(*a, pol, 1e-5) ** 2)))(margs)
        for name, a, b in zip("x attn g2 wo wg wu wd".split(), gmf, gmr):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{mult}: out_mlp d{name}")


def test_bias_fold_bitwise(hermetic):
    """wo/wd epilogue biases fold into the back-half launch epilogues as
    statically-gated operands: with biases the fused op must match the
    per-op oracle bitwise (fwd + grads), and the bias-free call of the
    bias-capable op must stay bitwise against the historical bias-free
    kernel (no unconditional +0.0 sneaking into the fold)."""
    from repro.core.policy import NumericsPolicy
    from repro.kernels import ops
    rng = np.random.default_rng(1)
    rows, d, K, F = 2, 128, 128, 256
    arr = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
    x, attn, g2 = arr(rows, d), arr(rows, K), arr(d)
    wo, wg, wu, wd = arr(K, d), arr(d, F), arr(d, F), arr(F, d)
    bo, bd = arr(d), arr(d)
    pol = NumericsPolicy(mode="amsim", multiplier="mitchell8")

    for bo_, bd_ in ((bo, bd), (bo, None), (None, bd)):
        args = (x, attn, g2, wo, wg, wu, wd, bo_, bd_)
        fused = jax.jit(lambda a: ops.decode_out_mlp_b(*a, pol, 1e-5))(args)
        oracle = ops.decode_out_mlp_oracle(x, attn, g2, wo, wg, wu, wd,
                                           pol, 1e-5, bo=bo_, bd=bd_)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(oracle),
                                      err_msg=f"bias fwd {bo_ is None},"
                                              f"{bd_ is None}")
    gl = jax.grad(lambda a: jnp.sum(
        ops.decode_out_mlp_b(*a, pol, 1e-5) ** 2))(
        (x, attn, g2, wo, wg, wu, wd, bo, bd))
    go = jax.grad(lambda a: jnp.sum(
        ops.decode_out_mlp_oracle(*a[:7], pol, 1e-5, bo=a[7],
                                  bd=a[8]) ** 2))(
        (x, attn, g2, wo, wg, wu, wd, bo, bd))
    for name, a, b in zip("x attn g2 wo wg wu wd bo bd".split(), gl, go):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"bias d{name}")
    # Bias-free through the bias-capable op == the historical kernel.
    nb = ops.decode_out_mlp_b(x, attn, g2, wo, wg, wu, wd, None, None,
                              pol, 1e-5)
    legacy = ops.decode_out_mlp(x, attn, g2, wo, wg, wu, wd, pol, 1e-5)
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(legacy))


def test_attn_fused_two_launch(setup):
    """The VMEM budget model collapses attention INTO the back-half
    launch (3 launches -> 2) on shapes in the single-KV-block regime:
    the 2-launch decode must be bitwise-identical to the 3-launch chain
    (REPRO_DECODE_FUSE_ATTN=0) and the per-op path, and the standalone
    attention kernel's trace counter must show decode attention moved
    in-kernel (fewer standalone traces with the fusion on)."""
    from repro.core.policy import NumericsPolicy
    from repro.kernels import approx_attention, decode_chain
    cfg, params = setup
    pol = NumericsPolicy(mode="amsim", multiplier="mitchell8")

    a0 = approx_attention.trace_count()
    two = _decode_logits(cfg, pol, params,
                         {"REPRO_DECODE_FUSED": "1",
                          "REPRO_DECODE_FUSE_ATTN": "1"})
    attn_two = approx_attention.trace_count() - a0

    a1 = approx_attention.trace_count()
    t1 = decode_chain.trace_count()
    three = _decode_logits(cfg, pol, params,
                           {"REPRO_DECODE_FUSED": "1",
                            "REPRO_DECODE_FUSE_ATTN": "0"})
    attn_three = approx_attention.trace_count() - a1
    assert decode_chain.trace_count() > t1, "chain disengaged entirely"

    perop = _decode_logits(cfg, pol, params, {"REPRO_DECODE_FUSED": "0"})

    assert attn_two < attn_three, \
        "2-launch mode still traced the standalone attention kernel on " \
        "decode ticks"
    for i, (a, b, c) in enumerate(zip(two, three, perop)):
        np.testing.assert_array_equal(a, b,
                                      err_msg=f"step {i}: 2- vs 3-launch")
        np.testing.assert_array_equal(a, c,
                                      err_msg=f"step {i}: 2-launch vs per-op")


def test_moe_decode_chain_bitwise(hermetic):
    """The MoE decode back half (fused wo->norm + stacked expert-bank
    launch, router per-op) must be bitwise-invisible in serve-path
    decode logits, with the chain trace counter proving engagement."""
    from repro.configs import get_arch, reduced
    from repro.core.policy import NumericsPolicy
    from repro.kernels import decode_chain
    from repro.models.transformer import init_lm
    cfg = reduced(get_arch("granite-moe-3b-a800m"), n_layers=1)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    pol = NumericsPolicy(mode="amsim", multiplier="mitchell8")

    t0 = decode_chain.trace_count()
    fused = _decode_logits(cfg, pol, params, {"REPRO_DECODE_FUSED": "1"})
    assert decode_chain.trace_count() > t0, "MoE chain never engaged"
    t1 = decode_chain.trace_count()
    perop = _decode_logits(cfg, pol, params, {"REPRO_DECODE_FUSED": "0"})
    assert decode_chain.trace_count() == t1
    for i, (a, b) in enumerate(zip(fused, perop)):
        np.testing.assert_array_equal(a, b, err_msg=f"moe step {i}")


def test_cbe_paged_moe_chain(hermetic):
    """MoE decode through the continuous-batching engine's paged-KV
    ticks: the chain engages (trace counter) and the generated tokens
    are identical to a chain-off engine — the end-to-end statement that
    paged serving + MoE now run the persistent decode chain."""
    from repro.configs import get_arch, reduced
    from repro.core.policy import NumericsPolicy
    from repro.kernels import decode_chain
    from repro.models.transformer import init_lm
    from repro.serve.scheduler import ContinuousBatchingEngine
    cfg = reduced(get_arch("granite-moe-3b-a800m"), n_layers=1)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    pol = NumericsPolicy(mode="amsim", multiplier="exact7")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab, size=n).tolist() for n in (5, 3)]

    def run(env):
        saved = _with_env(env)
        try:
            cbe = ContinuousBatchingEngine(cfg, {"t": pol}, params,
                                           max_len=32, capacity=2,
                                           page_size=4)
            rids = [cbe.submit(p, 5, tier="t") for p in prompts]
            out = cbe.drain()
            return [out[r] for r in rids]
        finally:
            _with_env(saved)

    t0 = decode_chain.trace_count()
    fused = run({"REPRO_DECODE_FUSED": "1"})
    assert decode_chain.trace_count() > t0, \
        "paged MoE decode tick did not engage the chain"
    t1 = decode_chain.trace_count()
    perop = run({"REPRO_DECODE_FUSED": "0"})
    assert decode_chain.trace_count() == t1
    assert fused == perop, "paged MoE chain changed generated tokens"


def test_vmem_budget_model(hermetic):
    """Unit contract of the kernels/vmem.py estimators: the dispatch
    guard delegates to chain_fits; fuse_attention_ok enforces the
    bitwise regime (T <= 128) and the row bound; filter_candidates never
    returns empty and keeps only in-budget configs otherwise."""
    from repro.kernels import vmem
    from repro.kernels.autotune import CANDIDATES_DECODE_CHAIN
    from repro.kernels.decode_chain import decode_chain_supported
    M = 8
    for shape in ((2, 128, 128, 256), (4, 256, 256, 1024)):
        assert decode_chain_supported(*shape, M) == \
            vmem.chain_fits(*shape, M)
    assert vmem.chain_fits(2, 128, 128, 256, M)
    assert not vmem.chain_fits(vmem.MAX_ROWS + 1, 128, 128, 256, M)
    assert not vmem.chain_fits(0, 128, 128, 256, M)

    # fuse_attention_ok: in-regime shape passes, T > 128 (outside the
    # single-chunk einsum-bitwise regime) and rows != B never do.
    ok = vmem.fuse_attention_ok(2, 128, 128, 256, 2, 32, 2, 32, M)
    assert ok, "small decode shape should admit the 2-launch form"
    assert not vmem.fuse_attention_ok(2, 128, 128, 256, 2, 256, 2, 32, M)
    assert not vmem.fuse_attention_ok(4, 128, 128, 256, 2, 32, 2, 32, M)

    # moe_ffn_fits: the capacity bound keeps it a decode-only path.
    assert vmem.moe_ffn_fits(8, 8, 128, 64, M)
    assert not vmem.moe_ffn_fits(8, vmem.MAX_ROWS + 8, 128, 64, M)

    cands = [(c.bn, c.bko, c.bf, c.overlap)
             for c in CANDIDATES_DECODE_CHAIN]
    kept = vmem.filter_candidates(cands, 2, 128, 128, 256, M)
    assert kept and set(kept) <= set(cands)
    for c in kept:
        assert vmem.chain_bytes(2, 128, 128, 256, M, bn=c[0],
                                bf=c[2]) <= vmem.VMEM_BUDGET
    # A shape no candidate fits still yields the smallest-footprint one.
    huge = vmem.filter_candidates(cands, vmem.MAX_ROWS, 8192, 8192,
                                  32768, M)
    assert len(huge) >= 1


# ---------------------------------------------------- kill-switch nesting
def test_kill_switch_nests_with_attn_fused(setup):
    """REPRO_ATTN_FUSED=0 swaps the attention *core* to the einsum
    lowering on BOTH sides of the comparison but must not disturb the
    chain: the fused front/back halves still engage and the decode
    logits stay bitwise-identical to the per-op run under the same
    attention setting (docs/configuration.md nesting table)."""
    from repro.core.policy import NumericsPolicy
    from repro.kernels import decode_chain
    cfg, params = setup
    pol = NumericsPolicy(mode="amsim", multiplier="exact7")

    t0 = decode_chain.trace_count()
    fused = _decode_logits(cfg, pol, params,
                           {"REPRO_DECODE_FUSED": "1",
                            "REPRO_ATTN_FUSED": "0"})
    assert decode_chain.trace_count() > t0, \
        "chain must engage independently of the attention dispatch"
    perop = _decode_logits(cfg, pol, params,
                           {"REPRO_DECODE_FUSED": "0",
                            "REPRO_ATTN_FUSED": "0"})
    for i, (a, b) in enumerate(zip(fused, perop)):
        np.testing.assert_array_equal(a, b, err_msg=f"step {i}")


# --------------------------------------------------------- mesh (2x2) sub
def run_in_subprocess(code: str, devices: int = 4, env=None) -> str:
    env_full = dict(os.environ,
                    XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
                    PYTHONPATH=os.path.join(REPO, "src"),
                    **_HERMETIC, **(env or {}))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env_full,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_decode_chain_under_mesh():
    """Mesh semantics of the dispatch guard, on a 2x2 debug mesh:

    * with the sharded per-op dispatch active, the chain must yield
      (Megatron partitioning owns decode) — guard returns False and a
      full decode adds zero chain traces;
    * with REPRO_SHARD_FUSED=0 (shard dispatch killed) the chain engages
      with GSPMD-replicated lowering, bitwise-identical to both the
      per-op run under the same mesh and the single-device fused run —
      for the exact, log-based, and packed multiplier families.
    """
    code = textwrap.dedent("""
    import contextlib, os
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch, reduced
    from repro.core.policy import NumericsPolicy
    from repro.kernels import decode_chain, ops
    from repro.models.transformer import init_lm, init_lm_caches
    from repro.serve.engine import make_prefill, make_serve_step

    cfg = reduced(get_arch("granite-3-2b"), n_layers=1)
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    K = cfg.n_heads * cfg.head_dim

    def decode(pol, mesh_ctx=None, n=2):
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 1,
                                  cfg.vocab)
        caches = init_lm_caches(cfg, 2, 32)
        ctx = (jax.set_mesh(mesh_ctx) if mesh_ctx is not None
               else contextlib.nullcontext())
        outs = []
        with ctx:
            nxt, caches = jax.jit(make_prefill(cfg, pol, 32))(
                params, toks, caches)
            step = jax.jit(make_serve_step(cfg, pol))
            for _ in range(n):
                logits, nxt, caches = step(params, nxt, caches)
                outs.append(np.asarray(logits))
        return outs

    pol = NumericsPolicy(mode="amsim", multiplier="mitchell8")
    # guard: under the mesh the sharded per-op dispatch wins...
    with jax.set_mesh(mesh):
        assert not ops.decode_chain_enabled(pol, 2, cfg.d_model, K,
                                            cfg.d_ff)
        # ...until the shard dispatch is killed, then the chain engages.
        os.environ["REPRO_SHARD_FUSED"] = "0"
        assert ops.decode_chain_enabled(pol, 2, cfg.d_model, K, cfg.d_ff)
        del os.environ["REPRO_SHARD_FUSED"]
    # end to end: a sharded decode run adds zero chain traces.
    t0 = decode_chain.trace_count()
    decode(pol, mesh_ctx=mesh)
    assert decode_chain.trace_count() == t0, "chain engaged under mesh"
    print("OK guard")

    for mult in ("exact7", "mitchell8", "bf16"):
        p = NumericsPolicy(mode="amsim", multiplier=mult)
        ref_single = decode(p)          # single-device fused (no mesh)
        os.environ["REPRO_SHARD_FUSED"] = "0"
        t0 = decode_chain.trace_count()
        fused_mesh = decode(p, mesh_ctx=mesh)
        assert decode_chain.trace_count() > t0, \\
            f"{mult}: chain did not engage with shard dispatch killed"
        os.environ["REPRO_DECODE_FUSED"] = "0"
        perop_mesh = decode(p, mesh_ctx=mesh)
        del os.environ["REPRO_SHARD_FUSED"], os.environ["REPRO_DECODE_FUSED"]
        for i, (a, b, c) in enumerate(zip(fused_mesh, perop_mesh,
                                          ref_single)):
            np.testing.assert_array_equal(a, b,
                err_msg=f"{mult} step {i}: fused vs per-op under mesh")
            np.testing.assert_array_equal(a, c,
                err_msg=f"{mult} step {i}: mesh-replicated vs single")
        print("OK", mult)
    """)
    out = run_in_subprocess(code)
    assert "OK guard" in out
    for mult in _MULTS:
        assert f"OK {mult}" in out


def test_overlap_psum_settings():
    """REPRO_OVERLAP_PSUM on the row-parallel reduce: 1 (single psum),
    explicit chunk counts, and auto must all be bitwise-identical (the
    chunking splits OUTPUT columns, never the fold); the ring variant
    accumulates in fixed shard-index order — on the two-device model
    axis that is bitwise-identical to the single psum too (FP add is
    commutative), so it is held to the same standard."""
    code = textwrap.dedent("""
    import os
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.policy import NumericsPolicy
    from repro.distributed import shard_fused as sf

    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh()
    rng = np.random.default_rng(0)
    pol = NumericsPolicy(mode="amsim", multiplier="mitchell8")
    x = jnp.asarray(rng.standard_normal((4, 8, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 512)) * 0.1, jnp.float32)

    def run():
        # fresh closure per call: the overlap setting is read at trace
        # time, so a cached jit would mask the env change.
        with jax.set_mesh(mesh):
            return jax.jit(lambda a, b: sf.row_parallel_matmul(
                a, b, pol, mesh))(x, w)

    os.environ["REPRO_OVERLAP_PSUM"] = "1"
    base = run()
    for setting in ("auto", "2", "4"):
        os.environ["REPRO_OVERLAP_PSUM"] = setting
        out = run()
        assert bool(jnp.all(out == base)), f"overlap={setting} not bitwise"
    os.environ["REPRO_OVERLAP_PSUM"] = "ring"
    ring = run()
    assert bool(jnp.all(ring == base)), "ring not bitwise on 2-dev axis"
    del os.environ["REPRO_OVERLAP_PSUM"]
    print("OK overlap")
    """)
    assert "OK overlap" in run_in_subprocess(code)


# ------------------------------------------------------ scheduler retrace
def test_cbe_decode_ticks_zero_added_retraces(setup):
    """The chain must not break the scheduler's one-decode-trace-per-tier
    contract: an amsim tier engages the fused chain on its decode ticks,
    and a second wave of requests through the SAME engine adds zero new
    decode traces and zero new chain kernel traces."""
    from repro.core.policy import NumericsPolicy
    from repro.kernels import decode_chain
    from repro.serve.scheduler import ContinuousBatchingEngine
    cfg, params = setup
    pol = NumericsPolicy(mode="amsim", multiplier="exact7")
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab, size=n).tolist()
               for n in (5, 3, 6, 4)]

    cbe = ContinuousBatchingEngine(cfg, {"cheap": pol}, params,
                                   max_len=32, capacity=2, page_size=4)
    t0 = decode_chain.trace_count()
    rids = [cbe.submit(p, 5, tier="cheap") for p in prompts[:2]]
    out = cbe.drain()
    assert all(len(out[r]) == 5 for r in rids)
    assert decode_chain.trace_count() > t0, \
        "amsim tier decode tick did not engage the fused chain"
    assert cbe.decode_trace_counts == {"cheap": 1}

    t1 = decode_chain.trace_count()
    rids2 = [cbe.submit(p, 4, tier="cheap") for p in prompts[2:]]
    out2 = cbe.drain()
    assert all(len(out2[r]) == 4 for r in rids2)
    assert cbe.decode_trace_counts == {"cheap": 1}, \
        "second wave retraced the decode step"
    assert decode_chain.trace_count() == t1, \
        "second wave added fused-chain kernel traces"
