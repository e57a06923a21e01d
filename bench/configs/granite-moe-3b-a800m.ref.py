"""Plain reference of granite-moe-3b-a800m as the benchmark runs it.

A decoder written out in jax.numpy from the sizes in
``granite-moe-3b-a800m.json``: the attention half of each layer is the
dense reference's (``granite-3-2b.ref.py``: RMSNorm, grouped-query
attention with rotary positions, a residual); the other half is RMSNorm,
a sparse-expert FFN and a residual.  In it each token's router logits
x . W_r pick its ``num_experts_per_tok`` largest, weighted by a softmax
over those logits; every expert, a SwiGLU MLP of width
``intermediate_size``, is applied to every token in turn and added with
its weight, which is zero where the token did not pick it, so no token
is dropped.  The loss adds 0.01 times the Switch load-balance loss,
E * sum_e f_e * P_e / k summed over layers (f_e: the share of choices
that went to e; P_e: the mean softmax over all E logits).  The
departures from the published model are the configuration's own and are
kept here (see ``departures`` in the JSON).

Every GEMM, the router's too, and every attention score and value
product goes through ``bench/reference/numerics.py`` (the afm16 product
from its definition), forward and backward; norms, activations, rotary
products and the expert weights' sum are exact float32.  This file
imports nothing of the program: it also makes the weights, from the
seed, in the layout that both it and the program read.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import harness
from bench.reference import numerics, optim

dense = harness.load_module(harness.BENCH / "configs" / "granite-3-2b.ref.py")
AUX_WEIGHT = 0.01


def dims(sizes: dict) -> dict:
    return dict(dense.dims(sizes), experts=sizes["num_local_experts"],
                top_k=sizes["num_experts_per_tok"])


@functools.lru_cache(maxsize=None)
def _maker(frozen):
    z = dims(dict(frozen))

    def make(key):
        ks = jax.random.split(key, 9)
        n = lambda k, shape, scale: jax.random.normal(k, shape, jnp.float32) * scale
        L, d, dh, E, f = z["layers"], z["d"], z["dh"], z["experts"], z["ff"]
        lin = lambda k, i, o: {"w": n(k, (L, i, o), 1.0 / math.sqrt(i))}
        bank = lambda k, i, o: {"w": n(k, (L, E, i, o), 1.0 / math.sqrt(i))}
        return {
            "embed": {"emb": n(ks[0], (z["vocab"], d), z["init"])},
            "final_norm": {"g": jnp.ones((d,), jnp.float32)},
            "layers": {
                "attn": {"wq": lin(ks[1], d, z["h"] * dh),
                         "wk": lin(ks[2], d, z["kv"] * dh),
                         "wv": lin(ks[3], d, z["kv"] * dh),
                         "wo": lin(ks[4], z["h"] * dh, d)},
                "n1": {"g": jnp.ones((L, d), jnp.float32)},
                "n2": {"g": jnp.ones((L, d), jnp.float32)},
                "moe": {"router": lin(ks[5], d, E),
                        "experts": {"wg": bank(ks[6], d, f),
                                    "wu": bank(ks[7], d, f),
                                    "wd": bank(ks[8], f, d)}},
            },
        }
    return jax.jit(make)


def _frozen(sizes: dict):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float, str))))


def make_weights(sizes: dict, key):
    """Float32 weights from ``key`` in one jitted call on the device:
    normal projections scaled by 1/sqrt(fan-in), embeddings by
    ``initializer_range``, unit norm gains.  Layer weights are stacked over
    a leading layer axis, expert weights over an expert axis after it."""
    return _maker(_frozen(sizes))(key)


# ------------------------------------------------------------------ model
def choices(mm, router, x, z):
    """x (T, d) -> (weights (T, k), experts (T, k), logits (T, E))."""
    logits = mm(x, router["w"])
    top, sel = jax.lax.top_k(logits, z["top_k"])
    return jax.nn.softmax(top, axis=-1), sel, logits


def _experts(mm, p, x, z):
    """The sparse-expert FFN of x (T, d): (y (T, d), load-balance loss,
    experts picked (T, k))."""
    T, E, k = x.shape[0], z["experts"], z["top_k"]
    gate, sel, logits = choices(mm, p["router"], x, z)
    weight = jnp.zeros((T, E), jnp.float32)
    for j in range(k):
        weight = weight + jax.nn.one_hot(sel[:, j], E) * gate[:, j:j + 1]

    # One expert at a time, recomputed in the backward pass, so that what
    # the gradient keeps of a layer is its inputs and not E expert outputs.
    @jax.checkpoint
    def expert(e):
        w, we = e
        out = mm(jax.nn.silu(mm(x, w["wg"]["w"])) * mm(x, w["wu"]["w"]),
                 w["wd"]["w"])
        return we[:, None] * out

    y = jnp.sum(jax.lax.map(expert, (p["experts"], weight.T)), axis=0)
    share = jnp.sum(jax.nn.one_hot(sel, E), axis=(0, 1)) / T
    aux = E * jnp.sum(share * jnp.mean(jax.nn.softmax(logits, axis=-1), 0)) / k
    return y, aux, sel


def forward(mm, w, tokens, z):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S), the
    summed load-balance loss and the experts picked in each layer
    (layers, B*S, k)."""
    x = jnp.take(w["embed"]["emb"], tokens, axis=0)
    aux, sels = jnp.zeros((), jnp.float32), []
    for i in range(z["layers"]):
        lp = jax.tree.map(lambda a: a[i], w["layers"])
        x = x + dense._attention(mm, lp["attn"],
                                 dense._rmsnorm(x, lp["n1"]["g"], z["eps"]), z)
        h = dense._rmsnorm(x, lp["n2"]["g"], z["eps"])
        y, a, sel = _experts(mm, lp["moe"], h.reshape(-1, h.shape[-1]), z)
        x, aux = x + y.reshape(x.shape), aux + a
        sels.append(sel)
    return dense._rmsnorm(x, w["final_norm"]["g"], z["eps"]), aux, jnp.stack(sels)


def loss(mm, w, batch, z):
    h, aux, _ = forward(mm, w, batch["tokens"], z)
    lg = dense.logits_at(mm, w, h)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll) + AUX_WEIGHT * aux


# ------------------------------------------------------------------ checks
@functools.lru_cache(maxsize=None)
def _train_step(frozen, traffic_items):
    z = dims(dict(frozen))
    traffic = dict(traffic_items)
    mm = numerics.matmul

    def step(w, state, batch):
        value, grads = jax.value_and_grad(lambda p: loss(mm, p, batch, z))(w)
        grads = optim.clip(grads, traffic["clip_norm"])
        w, state = optim.update(traffic, w, grads, state)
        return w, state, value
    return jax.jit(step, donate_argnums=(0, 1))


def train_step(sizes: dict, traffic: dict):
    """The jitted reference step: (weights, state, batch) -> (weights,
    state, loss)."""
    items = tuple(sorted((k, v) for k, v in traffic.items()
                         if isinstance(v, (int, float, str))))
    return _train_step(_frozen(sizes), items)


def train_reference(sizes: dict, traffic: dict, key, batches):
    """The first ``len(batches)`` training steps from the weights of
    ``key``: the loss of each step, the first clipped gradient and the
    parameters after the last step."""
    step = train_step(sizes, traffic)
    w = make_weights(sizes, key)
    state = optim.init_state(traffic, w)
    losses, grad1 = [], None
    for i, batch in enumerate(batches):
        w, state, value = step(w, state, batch)
        losses.append(float(value))
        if i == 0:
            grad1 = optim.first_gradient(traffic, state)
    return {"losses": losses, "grad1": grad1, "params": w}


@functools.lru_cache(maxsize=None)
def _routes(frozen):
    z = dims(dict(frozen))
    return jax.jit(lambda w, tokens: forward(numerics.matmul, w, tokens, z)[2])


def routes(sizes: dict, w, tokens):
    """The experts the reference picks for ``tokens`` (B, S) in each layer:
    (layers, B*S, k)."""
    return _routes(_frozen(sizes))(w, tokens)
