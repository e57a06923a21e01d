"""A decode tick under simulated products, on the paged serving cache.

A prefill and four greedy ticks through ``lm_forward`` with a paged cache
(the way the continuous-batching engine drives it) must give, at every
tick, the logits the same program gives in one forward pass over the
whole sequence under the same amsim policy.  The cases cross four
multiplier families (an exact table, a log-based one, the packed bf16
table and afm16) with four blocks: dense, dense with qkv/wo/wd biases, a
sliding window shorter than the sequence, and the dropless MoE layer.

The prefill pads its prompt to the whole sequence's length, as the
engine pads a prompt to its bucket, so it and the full pass share one
compiled program and each case compiles two: interpret mode spends
seconds lowering each LUT kernel.  A tick writes its key and value over
the padding's before it attends, and masks every later position.

The comparison is bitwise.  It holds because no simulated bit depends on
how many rows a launch has: the 2-D and grouped LUT kernels fold k in
the fixed order ``FOLD_2D`` names whatever their row tile, the attention
kernel folds one KV block in both the full pass and the tick (both
extents fit its 128-position block), and masked positions weigh exact
zeros.  The autotune cache is pinned empty, so a tuned entry cannot give
two row counts two folds.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.policy import NumericsPolicy
from repro.kernels import autotune
from repro.models.transformer import init_lm, init_paged_lm_caches, lm_forward
from repro.serve.scheduler import _merge_control

MULTS = ("exact7", "mitchell8", "bf16", "afm16")
B, PROMPT, TICKS, PAGE = 2, 6, 4, 4


def _biased(cfg, params):
    """Nonzero qkv, wo and wd biases in every layer."""
    layers = params["layers"]
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 5))
    bias = lambda w: 0.1 * jax.random.normal(
        next(keys), (w.shape[0], w.shape[-1]), jnp.float32)
    attn = {k: dict(v, b=bias(v["w"])) if k in ("wq", "wk", "wv", "wo")
            else v for k, v in layers["attn"].items()}
    ffn = dict(layers["ffn"], wd=dict(layers["ffn"]["wd"],
                                      b=bias(layers["ffn"]["wd"]["w"])))
    return dict(params, layers=dict(layers, attn=attn, ffn=ffn))


def _block(name):
    dense = reduced(get_arch("granite-3-2b"), n_layers=2)
    if name == "dense":
        return dense, lambda cfg, p: p
    if name == "biases":
        return dataclasses.replace(dense, qkv_bias=True), _biased
    if name == "window":
        return dataclasses.replace(dense, sliding_window=4), lambda cfg, p: p
    return (reduced(get_arch("granite-moe-3b-a800m"), n_layers=2),
            lambda cfg, p: p)


@pytest.fixture(scope="module", autouse=True)
def hermetic(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("REPRO_AUTOTUNE_CACHE",
              str(tmp_path_factory.mktemp("autotune") / "none.json"))
    autotune.reload_cache()
    yield
    mp.undo()
    autotune.reload_cache()


@pytest.mark.parametrize("block", ["dense", "biases", "window", "moe"])
@pytest.mark.parametrize("mult", MULTS)
def test_paged_decode_matches_full_forward(mult, block):
    cfg, fill = _block(block)
    params = fill(cfg, init_lm(jax.random.PRNGKey(0), cfg))
    pol = NumericsPolicy(mode="amsim", multiplier=mult)
    pages = -(-(PROMPT + TICKS) // PAGE)
    ptab = 1 + jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
    live = jnp.ones((B,), bool)

    @jax.jit
    def forward(params, toks, pool, start):
        merged = _merge_control(pool, ptab, live, jnp.full((B,), start))
        lg, merged, _ = lm_forward(params, toks, cfg, pol, caches=merged)
        return lg, {"pool_k": merged["pool_k"], "pool_v": merged["pool_v"]}

    fresh = init_paged_lm_caches(cfg, B * pages + 1, PAGE)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 1,
                                cfg.vocab)
    lg, pool = forward(params, jnp.pad(prompt, ((0, 0), (0, TICKS))),
                       fresh, 0)
    got, seq = [lg[:, PROMPT - 1]], prompt
    for i in range(TICKS):
        toks = jnp.argmax(got[-1], axis=-1).astype(jnp.int32)[:, None]
        seq = jnp.concatenate([seq, toks], axis=1)
        lg, pool = forward(params, toks, pool, PROMPT + i)
        got.append(lg[:, 0])

    full, _ = forward(params, seq, fresh, 0)
    np.testing.assert_array_equal(np.asarray(jnp.stack(got, 1)),
                                  np.asarray(full[:, PROMPT - 1:]))
