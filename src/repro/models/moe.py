"""Mixture-of-Experts FFN: top-k routing, dropless grouped dispatch.

The router's logits are a policy GEMM (site "router"), so its top-k
choices are made from simulated products.  Each token takes the k
experts of its largest logits, weighted by a softmax over those k
logits; no token is ever dropped.

Dispatch sorts the (token, choice) rows by expert and pads each expert's
group to the grouped kernel's row tile (``approx_gemm.grouped_layout``):
T*k + E*(tile-1) rows at most, so the expert work is the routed rows plus
at most a tile of padding per expert, whatever the routing.  The expert
FFN is three grouped GEMMs over those rows (``ops.grouped_matmul``, sites
"wg", "wu", "wd"), one LUT kernel launch each, whose row tiles each pick
their expert's weights.  The results are gathered back to assignment
order and summed per token in choice order.

Decode ticks (one token per sequence) take the grouped kernel too.
Each call counts "moe.grouped.taken" in ``obs.routes`` at trace time.

An auxiliary load-balance loss (Switch-style) is returned for training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ArchConfig
from repro.core.policy import NumericsPolicy
from repro.kernels import ops
from repro.kernels.approx_gemm import grouped_layout, grouped_rows_bound
from repro.models.layers import init_linear
from repro.models.mlp import ffn, init_ffn


def init_moe(key, cfg: ArchConfig):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 3 + m.n_shared_experts)
    ek = jax.random.split(ks[1], m.n_experts)
    experts = jax.vmap(lambda k: init_ffn(k, d, m.d_ff, cfg.act))(ek)
    p = {"router": init_linear(ks[0], d, m.n_experts), "experts": experts}
    if m.n_shared_experts:
        p["shared"] = init_ffn(ks[2], d, m.d_ff * m.n_shared_experts, cfg.act)
    return p


def route(router, x, cfg: ArchConfig, policy: NumericsPolicy):
    """x (T, d) -> (gate (T, k), sel (T, k), probs (T, E)): the experts of
    each token's k largest router logits, in descending order, their
    softmax over those k logits, and the softmax over all E (for the
    load-balance loss)."""
    logits = policy.matmul(x, router["w"], site="router").astype(jnp.float32)
    top, sel = jax.lax.top_k(logits, cfg.moe.top_k)
    return jax.nn.softmax(top, axis=-1), sel, jax.nn.softmax(logits, axis=-1)


def _grouped_ffn(ew, xs, groups, policy: NumericsPolicy, act: str):
    mm = lambda h, s: ops.grouped_matmul(h, ew[s]["w"], groups, policy, s)
    if act == "swiglu":
        h = jax.nn.silu(mm(xs, "wg")) * mm(xs, "wu")
    else:
        h = jax.nn.gelu(mm(xs, "wu"))
    return mm(h, "wd")


def moe_ffn(p, x, cfg: ArchConfig, policy: NumericsPolicy):
    """x (B, S, d) -> (y (B, S, d), aux_loss scalar)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    xf = x.reshape(T, d)

    with jax.named_scope("moe.router"):
        gate, sel, probs = route(p["router"], xf, cfg, policy)

    obs.route("moe.grouped", "taken")
    with jax.named_scope("moe.dispatch"):
        e_flat = sel.reshape(-1)                          # (T*k,) token-major
        tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        groups = grouped_layout(e_flat, E)
        R = grouped_rows_bound(T * k, E)
        src = jnp.full((R,), T, jnp.int32).at[groups.rows].set(tok)
        xs = jnp.take(xf, src, axis=0, mode="fill", fill_value=0)

    with jax.named_scope("moe.experts"):
        out = _grouped_ffn(p["experts"], xs, groups, policy, cfg.act)

    with jax.named_scope("moe.combine"):
        got = out[groups.rows].reshape(T, k, d)
        y = gate[:, 0, None] * got[:, 0]
        for j in range(1, k):
            y = y + gate[:, j, None] * got[:, j]

    if m.n_shared_experts and "shared" in p:
        y = y + ffn(p["shared"], xf, policy, cfg.act)

    # Switch-style load-balance loss: E * sum_e f_e * P_e / k
    assign_frac = jnp.zeros((E,), jnp.float32).at[e_flat].add(1.0) / T
    router_frac = jnp.mean(probs, axis=0)                          # P_e
    aux = E * jnp.sum(assign_frac * router_frac) / k
    return y.reshape(B, S, d), aux
