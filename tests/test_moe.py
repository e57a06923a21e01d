"""MoE dispatch invariants (property-style)."""
import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced
from repro.core.policy import NumericsPolicy
from repro.models.mlp import ffn
from repro.models.moe import init_moe, moe_ffn

POL = NumericsPolicy()


def _cfg():
    return reduced(get_arch("granite-moe-3b-a800m"))


def _manual(p, xf, cfg, policy):
    """sum_k gate_k * expert_k(x): every expert on every token, combined
    by hand in choice order."""
    logits = policy.matmul(xf, p["router"]["w"], site="router")
    top, sel = jax.lax.top_k(logits, cfg.moe.top_k)
    gate = jax.nn.softmax(top, -1)
    all_out = jax.vmap(lambda ep: ffn(ep, xf, policy, cfg.act))(
        p["experts"])  # (E, T, d)
    rows = jnp.arange(xf.shape[0])
    want = jnp.zeros_like(xf)
    for kk in range(cfg.moe.top_k):
        want = want + gate[:, kk, None] * all_out[sel[:, kk], rows]
    return want, sel


def test_moe_matches_manual_expert_combination():
    """MoE output == sum_k gate_k * expert_k(x): no token is dropped."""
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    p = init_moe(key, cfg)
    x = jax.random.normal(key, (2, 6, cfg.d_model))
    y, _ = moe_ffn(p, x, cfg, POL)
    want, _ = _manual(p, x.reshape(-1, cfg.d_model), cfg, POL)
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("mode", ["native", "amsim"])
def test_moe_dropless_under_a_fully_skewed_router(mode):
    """Every token routed to the same top-k experts: those experts take
    all T tokens (more than a tile's worth, so their groups span several
    tiles), the others none, and the output is still sum_k gate_k *
    expert_k(x) for every token, under the LUT kernels too."""
    cfg = _cfg()
    pol = NumericsPolicy(mode=mode, multiplier="afm16")
    key = jax.random.PRNGKey(4)
    p = init_moe(key, cfg)
    k = cfg.moe.top_k
    # Router logits dominated by a per-expert bias through a constant input
    # lane: experts 3 and 5 win for every token.
    w = p["router"]["w"] * 1e-3
    w = w.at[0].set(jnp.zeros(cfg.moe.n_experts).at[3].set(8.).at[5].set(6.))
    p["router"]["w"] = w
    x = jax.random.normal(key, (3, 50, cfg.d_model)).at[..., 0].set(1.0)
    y, _ = moe_ffn(p, x, cfg, pol)
    want, sel = _manual(p, x.reshape(-1, cfg.d_model), cfg, pol)
    assert k == 2 and np.all(np.asarray(sel) == [3, 5])
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.slow
def test_moe_aux_loss_balanced_router_is_one():
    """Perfectly uniform router -> Switch aux loss ~= 1."""
    cfg = _cfg()
    key = jax.random.PRNGKey(2)
    p = init_moe(key, cfg)
    p["router"]["w"] = jnp.zeros_like(p["router"]["w"])  # uniform logits
    x = jax.random.normal(key, (2, 32, cfg.d_model))
    _, aux = moe_ffn(p, x, cfg, POL)
    assert 0.9 < float(aux) < 1.1


def test_moe_grads_flow_to_router_and_experts():
    cfg = _cfg()
    key = jax.random.PRNGKey(3)
    p = init_moe(key, cfg)
    x = jax.random.normal(key, (2, 8, cfg.d_model))

    def loss(p):
        y, aux = moe_ffn(p, x, cfg, POL)
        return jnp.sum(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    assert float(jnp.sum(jnp.abs(g["router"]["w"]))) > 0
    assert float(jnp.sum(jnp.abs(g["experts"]["wd"]["w"]))) > 0


def test_prefill_then_paged_decode_matches_the_reference_forward():
    """granite-moe-3b-a800m at a tiny width (the published 40 experts,
    top 8): a prefill and four greedy decode ticks through the paged
    serving cache give the logits the benchmark's plain reference
    (bench/configs/granite-moe-3b-a800m.ref.py) gives in one full forward
    pass over the same tokens, both with exact float32 products."""
    import json
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from bench import harness
    from repro.models.transformer import init_paged_lm_caches, lm_forward
    from repro.serve.scheduler import _merge_control
    ref = harness.load_module(root / "bench/configs/granite-moe-3b-a800m.ref.py")
    system = harness.load_module(root / "bench/configs/granite-moe-3b-a800m.py")
    sizes = dict(json.loads((root / "bench/configs/granite-moe-3b-a800m.json")
                            .read_text()),
                 hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
                 intermediate_size=64, num_hidden_layers=2, vocab_size=512,
                 initializer_range=0.08)
    cfg = system.arch(sizes)
    w = ref.make_weights(sizes, jax.random.PRNGKey(5))
    B, m, ps, steps = 2, 6, 4, 4
    prompt = jax.random.randint(jax.random.PRNGKey(6), (B, m), 0, cfg.vocab)
    pages = 16 // ps
    ptab = 1 + jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
    pool = init_paged_lm_caches(cfg, B * pages + 1, ps)
    live = jnp.ones((B,), bool)

    def tick(toks, pool, start):
        merged = _merge_control(pool, ptab, live, jnp.full((B,), start))
        lg, merged, _ = lm_forward(w, toks, cfg, POL, caches=merged)
        return lg[:, -1], {"pool_k": merged["pool_k"],
                           "pool_v": merged["pool_v"]}

    got, toks, seq = [], prompt, prompt
    for i in range(steps + 1):
        lg, pool = tick(toks, pool, 0 if i == 0 else m + i - 1)
        got.append(lg)
        toks = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
        seq = jnp.concatenate([seq, toks], axis=1)

    exact = lambda a, b: jnp.matmul(a, b, precision="highest")
    z = ref.dims(sizes)
    h, _, _ = ref.forward(exact, w, seq[:, :-1], z)
    want = ref.dense.logits_at(exact, w, h)[:, m - 1:]
    # Float32 sums in another order, through 2 layers: 1e-4 of logits
    # whose spread is about 1 leaves room for that and for no more.
    np.testing.assert_allclose(np.asarray(jnp.stack(got, 1)),
                               np.asarray(want), rtol=0, atol=1e-4)
