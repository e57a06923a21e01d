"""Distributed: sharding specs, DP+TP numerical equivalence, grad
compression, dry-run cell — run in subprocesses with 8 forced host devices
(the main pytest process must keep seeing 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.distributed.compression import dequantize_int8, quantize_int8


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_int8_quantize_roundtrip_error_bound(rng):
    x = rng.standard_normal(1000).astype(np.float32) * 5
    import jax.numpy as jnp
    q, scale, pad = quantize_int8(jnp.asarray(x))
    back = np.asarray(dequantize_int8(q, scale, pad, x.shape))
    err = np.abs(back - x)
    # error bounded by half a quantization step of the global max
    assert err.max() <= np.abs(x).max() / 127.0 + 1e-6


def test_param_pspecs_divisibility_all_archs():
    """Every assigned spec must divide its dim on the production mesh."""
    code = """
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_arch
    from repro.distributed.sharding import lm_param_pspecs
    from repro.launch.cells import _params_shapes
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(2, 4)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for name in ["granite-3-2b", "qwen1.5-110b", "granite-moe-3b-a800m",
                 "mamba2-780m", "whisper-base", "zamba2-1.2b"]:
        cfg = get_arch(name)
        params = _params_shapes(cfg)
        specs = lm_param_pspecs(params, cfg, mesh)
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for p, s in zip(flat_p, flat_s):
            for dim, ax in enumerate(tuple(s)):
                if ax is None: continue
                n = sizes[ax] if isinstance(ax, str) else 1
                assert p.shape[dim] % n == 0, (name, p.shape, s)
    print("OK")
    """
    assert "OK" in run_in_subprocess(code)


@pytest.mark.slow
def test_dp_tp_training_matches_single_device():
    """Loss and gradients on a 2x2 (data, model) mesh must match the
    single-device values: the distribution layer cannot change numerics.
    (Gradients, not post-Adam params — Adam's rsqrt amplifies float noise
    near zero and would make the comparison ill-conditioned.)"""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_arch, reduced
    from repro.core.policy import NumericsPolicy
    from repro.data.pipeline import lm_batch
    from repro.configs.base import ShapeConfig
    from repro.distributed.sharding import lm_param_pspecs
    from repro.models.transformer import init_lm, lm_loss
    from repro.optim.optimizers import global_norm

    cfg = reduced(get_arch("granite-3-2b"))
    pol = NumericsPolicy(mode="surrogate", multiplier="bf16")
    key = jax.random.PRNGKey(0)
    params = init_lm(key, cfg)
    shape = ShapeConfig("t", 32, 8, "train")
    batch = lm_batch(cfg, shape, 0)
    vg = jax.value_and_grad(lambda p, b: lm_loss(p, b, cfg, pol)[0])

    (l1, g1) = jax.jit(vg)(params, batch)

    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh()
    pspecs = lm_param_pspecs(params, cfg, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    params_d = jax.device_put(params, psh)
    batch_d = jax.device_put(batch, NamedSharding(mesh, P("data")))
    with jax.set_mesh(mesh):
        (l2, g2) = jax.jit(vg)(params_d, batch_d)
    np.testing.assert_allclose(float(l1), float(l2), rtol=5e-5)
    # gradient direction identical: normed difference tiny
    num = 0.0; den = 0.0
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        num += float(jnp.sum((a - b) ** 2)); den += float(jnp.sum(a ** 2))
    # f32 reassociation across shards (+ surrogate quantized products)
    # gives ~0.5% on attention grads; semantics preserved
    assert num / den < 1e-3, (num, den)
    print("OK")
    """
    assert "OK" in run_in_subprocess(code)


@pytest.mark.slow
def test_paged_pool_sharding_token_parity():
    """Paged serving pools under a 2x2 mesh: KV heads shard over "model",
    pages stay replicated over data (any slot's page table may name any
    page), and the sharded ContinuousBatchingEngine emits exactly the
    tokens of the unsharded one."""
    code = """
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_arch, reduced
    from repro.core.policy import NumericsPolicy
    from repro.distributed.sharding import cache_pspecs
    from repro.models.transformer import init_lm, init_paged_lm_caches
    from repro.serve.scheduler import ContinuousBatchingEngine

    cfg = reduced(get_arch("granite-3-2b"), n_layers=1)
    params = init_lm(jax.random.PRNGKey(7), cfg)
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh()

    caches = init_paged_lm_caches(cfg, n_pages=9, page_size=4)
    specs = cache_pspecs(caches, mesh, 2)
    for name in ("pool_k", "pool_v"):
        s = specs[name]
        # (L, n_pages, page_size, KV, dh): KV over "model", rest replicated
        assert s[3] == "model", (name, s)
        assert all(x is None for i, x in enumerate(s) if i != 3), (name, s)

    tiers = {"default": NumericsPolicy(mode="native")}
    stream = [(0, [3, 1, 4, 1, 5], 6, "default"),
              (1, [2, 7, 1], 5, "default")]

    def run(mesh_arg):
        eng = ContinuousBatchingEngine(cfg, tiers, params, max_len=32,
                                       capacity=2, page_size=4, mesh=mesh_arg)
        return eng.run(stream)

    ref = run(None)
    shd = run(mesh)
    assert ref == shd, (ref, shd)
    print("OK")
    """
    assert "OK" in run_in_subprocess(code, devices=4)


@pytest.mark.slow
def test_compressed_psum_error_feedback():
    """int8+EF all-reduce: per-step error bounded; mean over repeated
    steps converges to the true mean (EF kills the bias)."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import compressed_psum, init_ef_state

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 1024)) * 0.1
    true_mean = jnp.mean(g, 0)

    @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
             out_specs=(P("data"), P("data")))
    def reduce_once(gs, ef):
        m, ef = compressed_psum({"g": gs[0]}, {"g": ef[0]}, "data")
        return m["g"][None], ef["g"][None]

    ef = jnp.zeros_like(g)
    acc = jnp.zeros_like(true_mean)
    steps = 20
    for _ in range(steps):
        mean, ef = reduce_once(g, ef)
        acc = acc + mean[0]
    # single-shot error small
    one, _ = reduce_once(g, jnp.zeros_like(g))
    err1 = float(jnp.max(jnp.abs(one[0] - true_mean)))
    # with EF, the *time-average* of reduced grads converges to the truth
    err_avg = float(jnp.max(jnp.abs(acc / steps - true_mean)))
    assert err1 < 0.05, err1
    assert err_avg < err1 * 0.5 + 1e-4, (err_avg, err1)
    print("OK", err1, err_avg)
    """
    assert "OK" in run_in_subprocess(code)


@pytest.mark.slow
def test_dryrun_single_cell_and_multipod():
    """The dry-run machinery itself: one small arch, both meshes, scanned
    layers for speed.  Proves lower+compile on 256 and 512 fake chips."""
    code = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    from repro.core.policy import NumericsPolicy
    from repro.launch.dryrun import run_cell
    pol = NumericsPolicy(mode="surrogate", multiplier="bf16")
    r1 = run_cell("whisper-base", "train_4k", multi_pod=False, policy=pol,
                  unroll=False, verbose=False)
    assert r1["status"] == "ok", r1
    r2 = run_cell("whisper-base", "train_4k", multi_pod=True, policy=pol,
                  unroll=False, verbose=False)
    assert r2["status"] == "ok", r2
    assert r2["chips"] == 512
    print("OK")
    """
    assert "OK" in run_in_subprocess(code, devices=512)
