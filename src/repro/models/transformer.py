"""Decoder-only LM covering the dense / moe / ssm / hybrid families.

Homogeneous stacks (dense, moe, ssm, and llama4-style interleave via
scan_block=2 pairs) run under ``jax.lax.scan`` over stacked layer params
(with optional remat) — one compiled block regardless of depth.
Heterogeneous stacks (zamba2 hybrid with a weight-shared attention block
every k layers) unroll: the arch is small, and unrolling keeps the shared
block's 6 distinct KV caches exact.

All matmuls (projections, attention score/value, MoE experts, SSD
einsums, LM head) route through the NumericsPolicy — the paper's
technique as a first-class framework feature.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.policy import NumericsPolicy
from repro.models.attention import attention, init_attention, init_cache
from repro.models.layers import (
    embed,
    init_embedding,
    init_linear,
    init_rmsnorm,
    linear,
    rmsnorm,
    unembed,
)
from repro.models.mlp import ffn, init_ffn
from repro.models.moe import init_moe, moe_ffn
from repro.models.ssm import init_mamba2, init_ssm_cache, mamba2


# ---------------------------------------------------------------- blocks
def _init_dense_layer(key, cfg: ArchConfig, use_moe: bool):
    ks = jax.random.split(key, 2)
    p = {
        "attn": init_attention(ks[0], cfg),
        "n1": init_rmsnorm(cfg.d_model),
        "n2": init_rmsnorm(cfg.d_model),
    }
    if use_moe:
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.act)
    return p


def _dense_block(p, x, cfg, policy, cache, window):
    a, cache = attention(p["attn"], rmsnorm(p["n1"], x, cfg.norm_eps), cfg,
                         policy, cache=cache, window=window)
    x = x + a
    h = rmsnorm(p["n2"], x, cfg.norm_eps)
    if "moe" in p:
        y, aux = moe_ffn(p["moe"], h, cfg, policy)
    else:
        y, aux = ffn(p["ffn"], h, policy, cfg.act), 0.0
    return x + y, cache, aux


def _ssm_block(p, x, cfg, policy, cache):
    y, cache = mamba2(p["mamba"], rmsnorm(p["n1"], x, cfg.norm_eps), cfg,
                      policy, cache=cache)
    return x + y, cache


# ---------------------------------------------------------------- init
def init_lm(key, cfg: ArchConfig):
    ks = jax.random.split(key, 8)
    params = {"embed": init_embedding(ks[0], cfg.vocab, cfg.d_model),
              "final_norm": init_rmsnorm(cfg.d_model)}
    if not cfg.tie_embeddings:
        params["head"] = init_linear(ks[1], cfg.d_model, cfg.vocab)

    fam = cfg.family
    lkeys = jax.random.split(ks[2], cfg.n_layers)
    if fam == "dense":
        params["layers"] = jax.vmap(
            lambda k: _init_dense_layer(k, cfg, False))(lkeys)
    elif fam == "moe":
        il = cfg.moe.interleave
        if il == 1:
            params["layers"] = jax.vmap(
                lambda k: _init_dense_layer(k, cfg, True))(lkeys)
        else:
            # scan over blocks of `il` layers: dense x (il-1), then MoE
            assert cfg.n_layers % il == 0
            bkeys = lkeys.reshape(cfg.n_layers // il, il, -1)
            def init_block(kk):
                sub = [_init_dense_layer(kk[i], cfg, False) for i in range(il - 1)]
                return {"dense": jax.tree.map(lambda *a: jnp.stack(a), *sub)
                        if il > 2 else sub[0],
                        "moe_layer": _init_dense_layer(kk[il - 1], cfg, True)}
            params["layers"] = jax.vmap(init_block)(bkeys)
    elif fam == "ssm":
        params["layers"] = jax.vmap(
            lambda k: {"mamba": init_mamba2(k, cfg),
                       "n1": init_rmsnorm(cfg.d_model)})(lkeys)
    elif fam == "hybrid":
        params["layers"] = jax.vmap(
            lambda k: {"mamba": init_mamba2(k, cfg),
                       "n1": init_rmsnorm(cfg.d_model)})(lkeys)
        params["shared_attn"] = _init_dense_layer(ks[3], cfg, False)
    else:
        raise ValueError(f"init_lm does not handle family {fam!r}")
    return params


# ---------------------------------------------------------------- forward
def lm_forward(params, tokens, cfg: ArchConfig, policy: NumericsPolicy, *,
               embeds=None, caches=None, window: int | None = None,
               train: bool = False, logits_at=None):
    """tokens (B, S) [+ optional frontend embeds (B, F, d) prepended].

    Returns (logits (B, S_total, vocab), new_caches, aux_loss).  With
    ``logits_at`` (B,) int, positions into the S_total outputs, only
    row ``logits_at[b]`` of each sequence is unembedded: logits are
    (B, 1, vocab).  The row is gathered after the final norm, so its
    bits are the full head's row at that position.
    """
    window = cfg.sliding_window if window is None else window
    if cfg.fsdp and cfg.unshard_weights:
        # §Perf: ZeRO-3 unshard-at-use.  Constraining each weight to its
        # fsdp-stripped spec makes XLA all-gather parameters over "data"
        # before the matmuls; without this GSPMD contracts against the
        # data-sharded dim and all-reduces batch-REPLICATED activations
        # (orders of magnitude more wire bytes).
        import dataclasses as _dc
        from jax.sharding import PartitionSpec as _P
        from repro.distributed.sharding import lm_param_pspecs
        specs = lm_param_pspecs(params, _dc.replace(cfg, fsdp=False))
        params = jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            params, specs, is_leaf=lambda v: isinstance(v, _P))
    x = embed(params["embed"], tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)

    fam = cfg.family
    aux_total = jnp.zeros((), jnp.float32)

    if fam == "hybrid":
        x, new_caches, aux_total = _hybrid_stack(
            params, x, cfg, policy, caches, window)
    elif not cfg.scan_layers:
        # Unrolled stack: one HLO block per layer.  Used by the dry-run so
        # compiled.cost_analysis() counts every layer (scan bodies are
        # costed once), and by small archs where scan buys nothing.
        block = _make_scan_block(cfg, policy, window, train)
        n_blocks = jax.tree.leaves(params["layers"])[0].shape[0]
        new_caches_list = []
        for i in range(n_blocks):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            c = (jax.tree.map(lambda a: a[i], caches)
                 if caches is not None else None)
            x, nc, aux_t = block(lp, x, c)
            aux_total = aux_total + aux_t
            new_caches_list.append(nc)
        new_caches = (jax.tree.map(lambda *a: jnp.stack(a), *new_caches_list)
                      if caches is not None else None)
    else:
        block = _make_scan_block(cfg, policy, window, train)
        xs = (params["layers"],) + ((caches,) if caches is not None else ())
        def scan_fn(carry, xs_t):
            x, aux = carry
            lp = xs_t[0]
            cache = xs_t[1] if len(xs) > 1 else None
            x, new_cache, aux_t = block(lp, x, cache)
            return (x, aux + aux_t), new_cache
        (x, aux_total), new_caches = jax.lax.scan(
            scan_fn, (x, aux_total), xs)
        if caches is None:
            new_caches = None

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x, policy)
    else:
        # Vocab-parallel head (sharding._RULES: head/w -> ("F", "model")).
        logits = linear(params["head"], x, policy, kind="column", site="head")
    if cfg.constrain_logits:
        # §Perf: vocab-parallel cross-entropy — keep logits sharded over
        # "model" through the loss (logsumexp reduces locally + tiny AR)
        # instead of all-gathering the (B, S, vocab) tensor.
        from jax.sharding import PartitionSpec as P
        daxes = (cfg.mesh_data_axes if len(cfg.mesh_data_axes) > 1
                 else cfg.mesh_data_axes[0])
        logits = jax.lax.with_sharding_constraint(
            logits, P(daxes, None, "model"))
    return logits, new_caches, aux_total


def _make_scan_block(cfg, policy, window, train):
    fam = cfg.family
    il = cfg.moe.interleave if (fam == "moe" and cfg.moe) else 1

    def block(lp, x, cache):
        aux = jnp.zeros((), jnp.float32)
        if fam == "ssm":
            x, cache = _ssm_block(lp, x, cfg, policy, cache)
        elif fam == "moe" and il > 1:
            c0 = cache[0] if cache is not None else None
            c1 = cache[1] if cache is not None else None
            x, c0, a0 = _dense_block(lp["dense"], x, cfg, policy, c0, window)
            x, c1, a1 = _dense_block(lp["moe_layer"], x, cfg, policy, c1, window)
            aux = aux + a0 + a1
            cache = (c0, c1) if cache is not None else None
        else:
            x, cache, a = _dense_block(lp, x, cfg, policy, cache, window)
            aux = aux + a
        return x, cache, aux

    if train and cfg.remat:
        return jax.checkpoint(block)
    return block


def _hybrid_stack(params, x, cfg, policy, caches, window):
    """zamba2: unrolled mamba layers + weight-shared attn every k layers."""
    aux = jnp.zeros((), jnp.float32)
    mcaches, acaches = (caches if caches is not None else (None, None))
    new_m, new_a = [], []
    ai = 0
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        c = jax.tree.map(lambda a: a[i], mcaches) if mcaches is not None else None
        x, nc = _ssm_block(lp, x, cfg, policy, c)
        new_m.append(nc)
        if cfg.attn_every and (i + 1) % cfg.attn_every == 0:
            c = (jax.tree.map(lambda a: a[ai], acaches)
                 if acaches is not None else None)
            x, nc, a = _dense_block(params["shared_attn"], x, cfg, policy,
                                    c, window)
            new_a.append(nc)
            aux = aux + a
            ai += 1
    if caches is None:
        return x, None, aux
    stack = lambda cs: jax.tree.map(lambda *a: jnp.stack(a), *cs)
    return x, (stack(new_m), stack(new_a)), aux


# ---------------------------------------------------------------- caches
def init_lm_caches(cfg: ArchConfig, batch: int, max_len: int):
    """Decode caches for the whole stack (layout matches lm_forward)."""
    fam = cfg.family

    def stacked(make, n):
        return jax.tree.map(lambda *a: jnp.stack(a), *[make() for _ in range(n)])

    if fam == "dense":
        return stacked(lambda: init_cache(cfg, batch, max_len), cfg.n_layers)
    if fam == "moe":
        il = cfg.moe.interleave
        if il == 1:
            return stacked(lambda: init_cache(cfg, batch, max_len), cfg.n_layers)
        nb = cfg.n_layers // il
        return (stacked(lambda: init_cache(cfg, batch, max_len), nb),
                stacked(lambda: init_cache(cfg, batch, max_len), nb))
    if fam == "ssm":
        return stacked(lambda: init_ssm_cache(cfg, batch), cfg.n_layers)
    if fam == "hybrid":
        n_attn = cfg.n_layers // cfg.attn_every
        attn_len = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
        return (stacked(lambda: init_ssm_cache(cfg, batch), cfg.n_layers),
                stacked(lambda: init_cache(cfg, batch, attn_len), n_attn))
    raise ValueError(fam)


def init_paged_lm_caches(cfg: ArchConfig, n_pages: int, page_size: int):
    """Persistent device state of the paged serving cache: one K and one
    V page pool per layer, stacked over the layer dim so the scan in
    ``lm_forward`` threads them exactly like ring caches.

    Everything else the paged attention path consumes (page table,
    per-slot lengths, liveness) is host-authoritative control state the
    scheduler merges in per step (serve/scheduler.py), so it is NOT part
    of this tree.  Page 0 is the reserved trash page
    (models/attention._paged_cache_update).  Paged serving covers the
    families whose decode state is attention KV (dense, and moe with
    interleave=1); SSM/hybrid recurrent state is O(1) per slot and needs
    no paging — unsupported here until a scheduler lane carries it.
    """
    fam = cfg.family
    if not (fam == "dense" or (fam == "moe" and cfg.moe.interleave == 1)):
        raise NotImplementedError(
            f"paged serving caches support dense/moe(interleave=1) "
            f"stacks; {cfg.name} is family {fam!r}")
    dt = jnp.dtype(cfg.cache_dtype)
    shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return {"pool_k": jnp.zeros(shape, dt), "pool_v": jnp.zeros(shape, dt)}


# ---------------------------------------------------------------- loss
def lm_loss(params, batch, cfg: ArchConfig, policy: NumericsPolicy,
            aux_weight: float = 0.01):
    """batch: {"tokens": (B,S) int32, "labels": (B,S) int32 (-1 = ignore),
    optional "embeds": (B,F,d)}.  Mean token cross-entropy + MoE aux."""
    logits, _, aux = lm_forward(
        params, batch["tokens"], cfg, policy,
        embeds=batch.get("embeds"), train=True)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:  # frontend positions carry no loss
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    valid = labels >= 0
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    # Label gather as mask-and-sum, NOT take_along_axis: the gather's
    # backward is a scatter into the (B, S, V) logits, and under a
    # vocab-sharded LM head GSPMD lowers that scatter with the batch dim
    # REPLICATED — batch-replicated all-reduces contaminate the whole
    # backward pass (§Perf iteration 2).  The masked reduce has an
    # elementwise backward and keeps every sharding intact.
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    ll = jnp.sum(jnp.where(iota == jnp.maximum(labels, 0)[..., None],
                           logits.astype(jnp.float32), 0.0), axis=-1)
    xent = jnp.where(valid, lse - ll, 0.0)
    loss = jnp.sum(xent) / jnp.maximum(jnp.sum(valid), 1)
    return loss + aux_weight * aux, {"xent": loss, "aux": aux}
