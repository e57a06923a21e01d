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

On a decode tick (one token per sequence) under a homogeneous amsim
wg/wu/wd leaf the experts can instead run in one persistent stacked-bank
launch (kernels/decode_chain.fused_moe_ffn) over an (E, C, d) buffer
whose capacity C is the tick's row count, so it cannot drop either.
Each choice is recorded in ``obs.routes`` ("moe.grouped.taken" or
"moe.grouped.refused.decode").  The two give the same bits: the grouped
kernel folds every row as the E-batched kernel the launch is slaved to.

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


def _decode_bank(ew, cfg: ArchConfig, policy: NumericsPolicy, S: int,
                 C: int) -> bool:
    """Whether this call's experts run in the stacked-bank launch over a
    capacity-``C`` buffer."""
    m = cfg.moe
    return (S == 1 and cfg.act == "swiglu" and "wg" in ew
            and not any("b" in ew[s] for s in ("wg", "wu", "wd"))
            and ops.decode_moe_ffn_enabled(policy, m.n_experts, C,
                                           cfg.d_model, m.d_ff))


def moe_ffn(p, x, cfg: ArchConfig, policy: NumericsPolicy):
    """x (B, S, d) -> (y (B, S, d), aux_loss scalar)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    xf = x.reshape(T, d)

    with jax.named_scope("moe.router"):
        gate, sel, probs = route(p["router"], xf, cfg, policy)

    ew = p["experts"]
    C = -(-T // 8) * 8                # a bank's capacity: every row fits
    bank = _decode_bank(ew, cfg, policy, S, C)
    obs.route("moe.grouped", "refused.decode" if bank else "taken")
    with jax.named_scope("moe.dispatch"):
        e_flat = sel.reshape(-1)                          # (T*k,) token-major
        tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        groups = grouped_layout(e_flat, E)
        if bank:
            slot = groups.rows - (jnp.cumsum(groups.sizes)
                                  - groups.sizes)[e_flat]
            xs = jnp.zeros((E, C, d), xf.dtype).at[e_flat, slot].set(xf[tok])
        else:
            R = grouped_rows_bound(T * k, E)
            src = jnp.full((R,), T, jnp.int32).at[groups.rows].set(tok)
            xs = jnp.take(xf, src, axis=0, mode="fill", fill_value=0)

    with jax.named_scope("moe.experts"):
        if bank:
            out = ops.decode_moe_ffn(xs, ew["wg"]["w"], ew["wu"]["w"],
                                     ew["wd"]["w"], policy)
        else:
            out = _grouped_ffn(ew, xs, groups, policy, cfg.act)

    with jax.named_scope("moe.combine"):
        got = out[e_flat, slot] if bank else out[groups.rows]
        got = got.reshape(T, k, d)
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
