"""Optimizers, trainer fault tolerance, checkpointing, data pipeline, serving."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointManager, load_pytree, save_pytree
from repro.configs import get_arch, reduced
from repro.configs.base import ShapeConfig
from repro.core.policy import NumericsPolicy
from repro.data.pipeline import lm_batch, vision_batches, vision_dataset
from repro.models.transformer import init_lm, lm_loss
from repro.optim.optimizers import (
    adafactor, adamw, apply_updates, clip_by_global_norm, cosine_schedule,
    global_norm, make_optimizer, sgdm,
)
from repro.serve.engine import ServingEngine
from repro.train.step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig, TrainerState

POL = NumericsPolicy()


# ------------------------------------------------------------- optimizers
@pytest.mark.parametrize("name", ["sgdm", "adamw", "adafactor"])
def test_optimizer_reduces_quadratic(name):
    opt = make_optimizer(name, lr=0.1)
    params = {"w": jnp.asarray([3.0, -2.0, 5.0])}
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(200):
        g = jax.grad(loss)(params)
        upd, state = opt.update(g, state, params)
        params = apply_updates(params, upd)
    assert float(loss(params)) < 0.2


def test_adafactor_state_is_factored():
    opt = adafactor(1e-2)
    params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
    st = opt.init(params)
    assert st["f"]["w"]["r"].shape == (64,)
    assert st["f"]["w"]["c"].shape == (32,)
    assert st["f"]["b"]["v"].shape == (32,)


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5
    assert float(norm) == pytest.approx(20.0)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1.0)
    assert float(lr(100)) == pytest.approx(0.1, abs=0.02)


# ----------------------------------------------------------- checkpointing
def test_checkpoint_roundtrip_exact():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray([1, 2, 3], jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.npz")
        save_pytree(path, tree, extra={"step": 7})
        got, meta = load_pytree(path, tree)
        assert meta["step"] == 7
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_manager_keep_k():
    tree = {"w": jnp.zeros(3)}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for s in [1, 2, 3, 4]:
            mgr.save(s, tree)
        assert mgr.latest_step() == 4
        steps = sorted(int(f.name[5:13]) for f in mgr.dir.glob("step-*.npz"))
        assert steps == [3, 4]


def test_trainer_recovers_from_injected_failure():
    """Node-failure model: the step function raises once; the supervisor
    restores from checkpoint and continues to completion."""
    cfg = reduced(get_arch("granite-3-2b"), n_layers=1)
    key = jax.random.PRNGKey(0)
    params = init_lm(key, cfg)
    opt = make_optimizer("adamw", 1e-3)
    opt_state = opt.init(params)
    shape = ShapeConfig("t", 16, 4, "train")
    base_step = jax.jit(make_train_step(
        lambda p, b: lm_loss(p, b, cfg, POL), opt))
    boom = {"armed": True}

    def flaky_step(params, opt_state, batch):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected node failure")
        return base_step(params, opt_state, batch)

    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(flaky_step, lambda s: lm_batch(cfg, shape, s),
                     TrainerConfig(total_steps=6, ckpt_dir=d, ckpt_every=2,
                                   log_every=100, log_fn=lambda *a: None))
        st = tr.run(TrainerState(params, opt_state))
        assert st.step == 6


def test_trainer_resume_continues_from_checkpoint():
    cfg = reduced(get_arch("granite-3-2b"), n_layers=1)
    key = jax.random.PRNGKey(0)
    params = init_lm(key, cfg)
    opt = make_optimizer("adamw", 1e-3)
    opt_state = opt.init(params)
    shape = ShapeConfig("t", 16, 4, "train")
    step = jax.jit(make_train_step(lambda p, b: lm_loss(p, b, cfg, POL), opt))
    batch_fn = lambda s: lm_batch(cfg, shape, s)
    with tempfile.TemporaryDirectory() as d:
        cfg1 = TrainerConfig(total_steps=4, ckpt_dir=d, ckpt_every=2,
                             log_every=100, log_fn=lambda *a: None)
        st1 = Trainer(step, batch_fn, cfg1).run(TrainerState(params, opt_state))
        cfg2 = TrainerConfig(total_steps=8, ckpt_dir=d, ckpt_every=2,
                             log_every=100, log_fn=lambda *a: None)
        st2 = Trainer(step, batch_fn, cfg2).run(
            TrainerState(params, opt_state))
        assert st1.step == 4 and st2.step == 8


# -------------------------------------------------------------------- data
def test_lm_batch_step_indexed_deterministic():
    cfg = reduced(get_arch("granite-3-2b"))
    shape = ShapeConfig("t", 32, 4, "train")
    b1 = lm_batch(cfg, shape, 5)
    b2 = lm_batch(cfg, shape, 5)
    b3 = lm_batch(cfg, shape, 6)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_lm_batch_overrides_respect_explicit_values():
    """batch/seq overrides must be `is not None` checks: an explicit
    override (including one that happens to be falsy in a refactor) may
    never silently fall back to the shape defaults."""
    cfg = reduced(get_arch("granite-3-2b"))
    shape = ShapeConfig("t", 8, 32, "train")  # seq_len=8, global_batch=32
    b = lm_batch(cfg, shape, 0, batch_override=4, seq_override=6)
    assert b["tokens"].shape == (4, 6)
    # Only one side overridden: the other keeps the shape default.
    b = lm_batch(cfg, shape, 0, batch_override=4)
    assert b["tokens"].shape == (4, 8)
    b = lm_batch(cfg, shape, 0, seq_override=6)
    assert b["tokens"].shape == (32, 6)


def test_vision_dataset_learnable_and_deterministic():
    d1 = vision_dataset("t", 256, 64, 8, 1, 4)
    d2 = vision_dataset("t", 256, 64, 8, 1, 4)
    np.testing.assert_array_equal(d1["x_train"], d2["x_train"])
    batches = list(vision_batches(d1, 32, epoch=0))
    assert len(batches) == 8 and batches[0]["x"].shape == (32, 8, 8, 1)


# ------------------------------------------------------------------ serving
@pytest.mark.slow  # tier-1 runs the stronger token-for-token tests/test_serve.py
def test_serving_engine_greedy_matches_full_forward():
    from repro.models.transformer import lm_forward
    cfg = reduced(get_arch("granite-3-2b"), n_layers=2)
    key = jax.random.PRNGKey(0)
    params = init_lm(key, cfg)
    engine = ServingEngine(cfg, POL, params, max_len=24)
    prompts = jax.random.randint(key, (2, 6), 0, cfg.vocab, jnp.int32)
    out = engine.generate(prompts, max_new_tokens=4)
    assert out.shape == (2, 4)
    # cross-check first generated token against non-cached forward
    logits, _, _ = lm_forward(params, prompts, cfg, POL)
    first = jnp.argmax(logits[:, -1], -1)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(first))


# ------------------------------------------------------- compile cache
def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache is the fixed in-checkout ``.jax_cache``."""
    from repro.checkout import ROOT
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
    assert compile_cache.enable_compile_cache() == "/elsewhere/jax"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = str(ROOT / ".jax_cache")
    assert compile_cache.cache_dir() == want
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
