"""Sharded fused-LUT execution (distributed/shard_fused): bit-identity
against the single-device fused kernels on a 2x2 debug mesh, VJP
identity through a column+row-parallel pair, kill-switch fallback, and
mesh-vs-unsharded training-loss parity.

All mesh tests run in subprocesses with forced host devices (the main
pytest process must keep seeing 1 device), with REPRO_AUTOTUNE_CACHE
pinned to an empty path so both runs resolve identical kernel block
configs — the precondition of the bit contract (docs/numerics.md).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HERMETIC = {
    # hermetic block configs: a tuned cache entry that differs between
    # the local and global shape buckets would change accumulation
    # order and void the bitwise comparisons below.
    "REPRO_AUTOTUNE_CACHE": "/tmp/repro_sharded_test_does_not_exist/x.json",
}


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


_PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core.policy import NumericsPolicy
from repro.distributed import shard_fused as sf
from repro.kernels.ops import policy_matmul, policy_attention, approx_conv2d

from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh()
rng = np.random.default_rng(0)

def bitwise(a, b):
    return bool(jnp.all(a == b))
"""


def test_sharded_ops_bit_identity_and_pair_vjp():
    """The core contract (docs/numerics.md): per-op sharded-vs-single-
    device comparisons for an exact and a log-based multiplier family.

    * column-parallel GEMM forward: bitwise
    * row-parallel GEMM forward: bitwise vs the k-split oracle
    * attention (heads over model, batch over data): forward AND full
      VJP bitwise
    * conv (batch over data): forward + dx bitwise, dw bitwise vs the
      batch-split oracle
    * column+row layer pair with replicated batch (pure TP): both
      weight gradients bitwise, dx tight-allclose
    """
    code = _PRELUDE + textwrap.dedent("""
    for mult in ("exact7", "mitchell8"):
        pol = NumericsPolicy(mode="amsim", multiplier=mult)
        x = jnp.asarray(rng.standard_normal((8, 16, 128)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((128, 256)) * 0.1, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((256, 128)) * 0.1, jnp.float32)

        # ---- column-parallel forward: bitwise
        ref = policy_matmul(x, w1, pol)
        with jax.set_mesh(mesh):
            out = jax.jit(
                lambda a, b: sf.column_parallel_matmul(a, b, pol, mesh))(x, w1)
        assert bitwise(out, ref), f"{mult}: column fwd not bitwise"

        # ---- row-parallel forward: bitwise vs the k-split oracle
        y = policy_matmul(x, w1, pol)
        with jax.set_mesh(mesh):
            out2 = jax.jit(
                lambda a, b: sf.row_parallel_matmul(a, b, pol, mesh))(y, w2)
        half = y.shape[-1] // 2
        oracle = (policy_matmul(y[..., :half], w2[:half], pol)
                  + policy_matmul(y[..., half:], w2[half:], pol))
        assert bitwise(out2, oracle), f"{mult}: row fwd != k-split oracle"
        ref2 = policy_matmul(y, w2, pol)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                                   rtol=1e-5, atol=1e-5)

        # ---- attention: forward and full VJP bitwise
        B, S, H, KV, dh = 4, 16, 4, 2, 32
        q = jnp.asarray(rng.standard_normal((B, S, H, dh)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
        pos = jnp.arange(S, dtype=jnp.int32)
        aref = policy_attention(q, k, v, pos, pos, pol, True, 0)
        with jax.set_mesh(mesh):
            assert sf.attention_supported(pol, mesh, q.shape, k.shape,
                                          causal=True, window=0)
            aout = jax.jit(lambda a, b, c: sf.sharded_attention(
                a, b, c, pos, pos, pol, causal=True, window=0,
                mesh=mesh))(q, k, v)
        assert bitwise(aout, aref), f"{mult}: attn fwd not bitwise"
        loss_r = lambda t: jnp.sum(
            policy_attention(*t, pos, pos, pol, True, 0) ** 2)
        gref = jax.jit(jax.grad(loss_r))((q, k, v))
        with jax.set_mesh(mesh):
            gsh = jax.jit(jax.grad(lambda t: jnp.sum(sf.sharded_attention(
                *t, pos, pos, pol, causal=True, window=0,
                mesh=mesh) ** 2)))((q, k, v))
        for name, a, b in zip("qkv", gref, gsh):
            assert bitwise(a, b), f"{mult}: attn d{name} not bitwise"

        # ---- conv: fwd + dx bitwise; dw bitwise vs batch-split oracle
        xc = jnp.asarray(rng.standard_normal((4, 8, 8, 16)), jnp.float32)
        wc = jnp.asarray(rng.standard_normal((3, 3, 16, 32)) * 0.1,
                         jnp.float32)
        cref = approx_conv2d(xc, wc, 1, "SAME", pol)
        with jax.set_mesh(mesh):
            cout = jax.jit(lambda a, b: sf.sharded_conv2d(
                a, b, 1, "SAME", pol, mesh))(xc, wc)
        assert bitwise(cout, cref), f"{mult}: conv fwd not bitwise"
        closs = lambda t: jnp.sum(approx_conv2d(*t, 1, "SAME", pol) ** 2)
        gcr = jax.jit(jax.grad(closs))((xc, wc))
        with jax.set_mesh(mesh):
            gcs = jax.jit(jax.grad(lambda t: jnp.sum(sf.sharded_conv2d(
                *t, 1, "SAME", pol, mesh) ** 2)))((xc, wc))
        assert bitwise(gcr[0], gcs[0]), f"{mult}: conv dx not bitwise"
        # batch-split oracle for dw: per-half fused dw + ordered sum.
        # The cotangent g = 2*conv(x, w) is bitwise-identical between
        # the two lowerings (fwd is), so dw differs only by the psum.
        g = 2.0 * cref
        from repro.kernels.ops import _conv_bwd
        dws = [_conv_bwd(1, "SAME", pol, (xc[i:i+2], wc), g[i:i+2])[1]
               for i in (0, 2)]
        assert bitwise(gcs[1], dws[0] + dws[1]), \
            f"{mult}: conv dw != batch-split oracle"

        # ---- column+row pair, batch replicated (pure TP): weight
        # grads bitwise (every dW chain is shard-local), dx close.
        xs = jnp.asarray(rng.standard_normal((3, 8, 128)), jnp.float32)
        def pair_sh(x_, w1_, w2_):
            h = sf.column_parallel_matmul(x_, w1_, pol, mesh)
            return jnp.sum(sf.row_parallel_matmul(h, w2_, pol, mesh) ** 2)
        def pair_ref(x_, w1_, w2_):
            h = policy_matmul(x_, w1_, pol)
            return jnp.sum(policy_matmul(h, w2_, pol) ** 2)
        with jax.set_mesh(mesh):
            gx, g1, g2 = jax.jit(
                jax.grad(pair_sh, argnums=(0, 1, 2)))(xs, w1, w2)
        rx, r1, r2 = jax.jit(
            jax.grad(pair_ref, argnums=(0, 1, 2)))(xs, w1, w2)
        assert bitwise(g1, r1), f"{mult}: pair dW1 not bitwise"
        assert bitwise(g2, r2), f"{mult}: pair dW2 not bitwise"
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-4, atol=1e-5)
        print("OK", mult)
    """)
    out = run_in_subprocess(code)
    assert "OK exact7" in out and "OK mitchell8" in out


def test_kill_switch_and_dispatch_fallback():
    """REPRO_SHARD_FUSED=0 deactivates the mesh dispatch (attention falls
    back to the GSPMD einsum path, matmuls to policy_matmul), unsupported
    shapes fall back per-op, and the KV-cache specs store the layout the
    sharded kernel consumes (KV heads over "model")."""
    code = _PRELUDE + textwrap.dedent("""
    import os
    from repro.models.attention import _derive_dispatch
    from repro.distributed.sharding import cache_pspecs
    from jax.sharding import PartitionSpec as P

    pol = NumericsPolicy(mode="amsim", multiplier="mitchell8")
    q_s, k_s = (8, 16, 4, 32), (8, 16, 2, 32)
    assert sf.active_mesh(pol) is None  # no ambient mesh
    with jax.set_mesh(mesh):
        assert sf.active_mesh(pol) is not None
        assert _derive_dispatch(pol, q_s, k_s, causal=True, window=0) \\
            == "sharded"
        # indivisible KV heads -> einsum fallback, never an error
        assert _derive_dispatch(pol, (8, 16, 3, 32), (8, 16, 3, 32),
                                causal=True, window=0) == "einsum"
        # non-amsim modes never shard-dispatch
        assert sf.active_mesh(NumericsPolicy(mode="amsim_jnp",
                                             multiplier="mitchell8")) is None
        # kill switches nest (docs/configuration.md): SHARD off ->
        # GSPMD-replicated fused kernel; + ATTN off -> einsum oracle.
        os.environ["REPRO_SHARD_FUSED"] = "0"
        assert sf.active_mesh(pol) is None
        assert _derive_dispatch(pol, q_s, k_s, causal=True, window=0) \\
            == "fused"
        os.environ["REPRO_ATTN_FUSED"] = "0"
        assert _derive_dispatch(pol, q_s, k_s, causal=True, window=0) \\
            == "einsum"
        del os.environ["REPRO_SHARD_FUSED"], os.environ["REPRO_ATTN_FUSED"]

        # cache layout invariant: KV-head axis over "model"
        caches = {"k": jnp.zeros((8, 32, 2, 64)),
                  "v": jnp.zeros((8, 32, 2, 64))}
        spec = jax.tree.leaves(cache_pspecs(caches, mesh, 8),
                               is_leaf=lambda s: isinstance(s, P))[0]
        assert tuple(spec)[2] == "model", spec

    # killed switch end-to-end: the model still runs under the mesh
    # (GSPMD replicated kernels) and stays close to the sharded result.
    from repro.configs import get_arch, reduced
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import lm_batch
    from repro.distributed.sharding import lm_param_pspecs, to_shardings
    from repro.models.transformer import init_lm, lm_loss
    from jax.sharding import NamedSharding

    cfg = reduced(get_arch("granite-3-2b"))
    params = init_lm(jax.random.PRNGKey(0), cfg)
    batch = lm_batch(cfg, ShapeConfig("t", 32, 8, "train"), 0)
    loss = lambda p, b: lm_loss(p, b, cfg, pol)[0]
    params_d = jax.device_put(params, to_shardings(
        lm_param_pspecs(params, cfg, mesh), mesh))
    batch_d = jax.device_put(batch, NamedSharding(mesh, P("data")))
    with jax.set_mesh(mesh):
        l_sharded = float(jax.jit(loss)(params_d, batch_d))
    os.environ["REPRO_SHARD_FUSED"] = "0"
    with jax.set_mesh(mesh):
        l_killed = float(jax.jit(loss)(params_d, batch_d))
    assert abs(l_sharded - l_killed) / abs(l_sharded) < 1e-5, \\
        (l_sharded, l_killed)
    print("OK", l_sharded, l_killed)
    """)
    assert "OK" in run_in_subprocess(code)


def test_train_steps_mesh_loss_parity():
    """Two optimizer steps of the reduced granite arch under
    mode="amsim": the 2x2-mesh run's per-step loss must match the
    unsharded fused run to FP32-reassociation tolerance (the satellite
    smoke; the 20-step CLI variant is the slow tier's
    test_launch_train_cli_20step_parity)."""
    code = """
    import contextlib
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_arch, reduced
    from repro.configs.base import ShapeConfig
    from repro.core.policy import NumericsPolicy
    from repro.data.pipeline import lm_batch
    from repro.distributed.sharding import (lm_param_pspecs,
                                            opt_state_pspecs, to_shardings)
    from repro.models.transformer import init_lm, lm_loss
    from repro.optim.optimizers import cosine_schedule, make_optimizer
    from repro.train.step import make_train_step

    cfg = reduced(get_arch("granite-3-2b"))
    pol = NumericsPolicy(mode="amsim", multiplier="mitchell8")
    shape = ShapeConfig("t", 32, 8, "train")
    opt = make_optimizer(cfg.optimizer, cosine_schedule(3e-4, 2, 4))
    step = make_train_step(lambda p, b: lm_loss(p, b, cfg, pol), opt)

    def run(steps, mesh=None):
        params = init_lm(jax.random.PRNGKey(0), cfg)
        opt_state = opt.init(params)
        if mesh is not None:
            pspecs = lm_param_pspecs(params, cfg, mesh)
            params = jax.device_put(params, to_shardings(pspecs, mesh))
            opt_state = jax.device_put(opt_state, to_shardings(
                opt_state_pspecs(cfg.optimizer, pspecs), mesh))
        fn = jax.jit(step)
        losses = []
        ctx = (jax.set_mesh(mesh) if mesh is not None
               else contextlib.nullcontext())
        with ctx:
            for s in range(steps):
                batch = lm_batch(cfg, shape, s)
                if mesh is not None:
                    batch = jax.device_put(
                        batch, NamedSharding(mesh, P("data")))
                params, opt_state, m = fn(params, opt_state, batch)
                losses.append(float(m["loss"]))
        return losses

    l1 = run(2)
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh()
    l2 = run(2, mesh)
    print("unsharded", l1)
    print("sharded  ", l2)
    # step-0 loss agrees at pure-reassociation level (~1e-7); one Adam
    # update (rsqrt amplifies float noise near zero — see
    # test_distributed) pushes step-1 to ~1e-5.  Same tolerance as the
    # existing DP+TP equivalence test.
    np.testing.assert_allclose(l1, l2, rtol=5e-5)
    print("OK")
    """
    assert "OK" in run_in_subprocess(code)


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


@pytest.mark.slow
def test_launch_train_cli_20step_parity():
    """launch/train.py --numerics amsim on the debug mesh: reports the
    sharded dispatch, completes 20 steps, and every logged loss matches
    a single-device run of the same CLI to reassociation tolerance."""
    import re

    def run_cli(devices):
        env = dict(os.environ,
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
                   PYTHONPATH=os.path.join(REPO, "src"), **_HERMETIC)
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.train", "--arch",
             "granite-3-2b", "--reduced", "--steps", "20", "--batch", "8",
             "--seq", "64", "--numerics", "amsim", "--multiplier",
             "mitchell8"],
            capture_output=True, text=True, env=env, timeout=900)
        assert out.returncode == 0, out.stderr[-4000:]
        return out.stdout

    sharded = run_cli(4)
    single = run_cli(1)
    assert "sharded fused LUT kernels" in sharded, sharded
    assert "single-device fused LUT kernels" in single, single
    assert "done at step 20" in sharded and "done at step 20" in single

    def losses(text):
        return [float(m) for m in re.findall(r"loss[=:]\s*([0-9.]+)", text)]

    ls, lu = losses(sharded), losses(single)
    assert ls and len(ls) == len(lu), (sharded, single)
    import numpy as np
    # per-step reassociation noise compounds through 20 Adam updates;
    # 1e-3 still distinguishes "same trajectory" from any real bug.
    np.testing.assert_allclose(ls, lu, rtol=1e-3)


@pytest.mark.slow
def test_serving_engine_mesh_matches_single():
    """ServingEngine(mesh=...) under mode="amsim" generates the same
    greedy tokens as the single-device engine (params sharded by the
    Megatron rules, caches in the KV-heads-over-model layout, decode
    through the sharded fused kernels)."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch, reduced
    from repro.core.policy import NumericsPolicy
    from repro.launch.mesh import make_debug_mesh
    from repro.models.transformer import init_lm
    from repro.serve.engine import ServingEngine

    cfg = reduced(get_arch("granite-3-2b"))
    pol = NumericsPolicy(mode="amsim", multiplier="mitchell8")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                 cfg.vocab, jnp.int32)
    single = ServingEngine(cfg, pol, params, max_len=48)
    toks1 = np.asarray(single.generate(prompts, max_new_tokens=12))
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(2, 2)
    sharded = ServingEngine(cfg, pol, params, max_len=48, mesh=mesh)
    toks2 = np.asarray(sharded.generate(prompts, max_new_tokens=12))
    assert (toks1 == toks2).all(), (toks1, toks2)
    print("OK", toks1[0, :6])
    """
    assert "OK" in run_in_subprocess(code)


@pytest.mark.slow
def test_sharded_bit_identity_packed_and_afm():
    """Acceptance sweep for the remaining multiplier families: bf16
    (packed uint16 LUT) and afm10 (canonical uint32) — sharded
    attention forward/VJP and column-parallel GEMM stay bitwise."""
    code = _PRELUDE + textwrap.dedent("""
    for mult in ("bf16", "afm10"):
        pol = NumericsPolicy(mode="amsim", multiplier=mult)
        x = jnp.asarray(rng.standard_normal((8, 16, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((128, 256)) * 0.1, jnp.float32)
        ref = policy_matmul(x, w, pol)
        with jax.set_mesh(mesh):
            out = jax.jit(
                lambda a, b: sf.column_parallel_matmul(a, b, pol, mesh))(x, w)
        assert bitwise(out, ref), mult
        B, S, H, KV, dh = 4, 16, 4, 2, 32
        q = jnp.asarray(rng.standard_normal((B, S, H, dh)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
        pos = jnp.arange(S, dtype=jnp.int32)
        aref = policy_attention(q, k, v, pos, pos, pol, True, 0)
        with jax.set_mesh(mesh):
            aout = jax.jit(lambda a, b, c: sf.sharded_attention(
                a, b, c, pos, pos, pol, causal=True, window=0,
                mesh=mesh))(q, k, v)
        assert bitwise(aout, aref), mult
        gref = jax.jit(jax.grad(lambda t: jnp.sum(
            policy_attention(*t, pos, pos, pol, True, 0) ** 2)))((q, k, v))
        with jax.set_mesh(mesh):
            gsh = jax.jit(jax.grad(lambda t: jnp.sum(sf.sharded_attention(
                *t, pos, pos, pol, causal=True, window=0,
                mesh=mesh) ** 2)))((q, k, v))
        assert all(bitwise(a, b) for a, b in zip(gref, gsh)), mult
        print("OK", mult)
    """)
    out = run_in_subprocess(code)
    assert "OK bf16" in out and "OK afm10" in out
