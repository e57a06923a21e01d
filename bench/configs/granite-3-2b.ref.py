"""Plain reference of granite-3-2b as the benchmark runs it.

A dense decoder written out in jax.numpy from the sizes in
``granite-3-2b.json``: token embedding, then per layer RMSNorm, grouped-
query attention with rotary positions (half-split), a residual, RMSNorm,
a SwiGLU MLP and a residual; a final RMSNorm and the tied head.  The
departures from the published model that the program makes are the
configuration's own and are kept here (see ``departures`` in the JSON).

Every GEMM, attention score and attention value product goes through
``bench/reference/numerics.py`` (the afm16 product from its definition),
forward and backward; norms, activations and rotary products are exact
float32.  This file imports nothing of the program: it also makes the
weights, from the seed, in the layout that both it and the program read.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import numerics, optim

NEG = -1e30


def dims(sizes: dict) -> dict:
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    return {"d": d, "h": h, "kv": sizes["num_key_value_heads"], "dh": d // h,
            "ff": sizes["intermediate_size"], "vocab": sizes["vocab_size"],
            "layers": sizes["num_hidden_layers"], "eps": sizes["rms_norm_eps"],
            "theta": sizes["rope_theta"], "init": sizes["initializer_range"]}


@functools.lru_cache(maxsize=None)
def _maker(frozen):
    z = dims(dict(frozen))

    def make(key):
        ks = jax.random.split(key, 8)
        n = lambda k, shape, scale: jax.random.normal(k, shape, jnp.float32) * scale
        L, d, dh = z["layers"], z["d"], z["dh"]
        lin = lambda k, i, o: {"w": n(k, (L, i, o), 1.0 / math.sqrt(i))}
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
                "ffn": {"wg": lin(ks[5], d, z["ff"]),
                        "wu": lin(ks[6], d, z["ff"]),
                        "wd": lin(ks[7], z["ff"], d)},
            },
        }
    return jax.jit(make)


def make_weights(sizes: dict, key):
    """Float32 weights from ``key`` in one jitted call on the device:
    normal projections scaled by 1/sqrt(fan-in), embeddings by
    ``initializer_range``, unit norm gains.  Layer weights are stacked over
    a leading layer axis."""
    frozen = tuple(sorted((k, v) for k, v in sizes.items()
                          if isinstance(v, (int, float, str))))
    return _maker(frozen)(key)


# ------------------------------------------------------------------ model
def _linear(mm, x, w):
    return mm(x.reshape(-1, x.shape[-1]), w).reshape(*x.shape[:-1], w.shape[-1])


def _rmsnorm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = (pos[:, None].astype(jnp.float32) * freqs[None, :])[None, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(mm, p, x, z):
    B, S, _ = x.shape
    H, KV, dh = z["h"], z["kv"], z["dh"]
    G = H // KV
    pos = jnp.arange(S, dtype=jnp.int32)
    q = _rope(_linear(mm, x, p["wq"]["w"]).reshape(B, S, H, dh), pos, z["theta"])
    k = _rope(_linear(mm, x, p["wk"]["w"]).reshape(B, S, KV, dh), pos, z["theta"])
    v = _linear(mm, x, p["wv"]["w"]).reshape(B, S, KV, dh)
    # Queries of one KV head's G query heads stacked as rows: (B, KV, G*S, dh).
    qg = q.reshape(B, S, KV, G, dh).transpose(0, 2, 3, 1, 4).reshape(B, KV, G * S, dh)
    kt = k.transpose(0, 2, 3, 1)                                   # (B, KV, dh, S)
    scores = mm(qg, kt).reshape(B, KV, G, S, S) / math.sqrt(dh)
    causal = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, NEG), axis=-1)
    out = mm(probs.reshape(B, KV, G * S, S), v.transpose(0, 2, 1, 3))
    out = out.reshape(B, KV, G, S, dh).transpose(0, 3, 1, 2, 4).reshape(B, S, H * dh)
    return _linear(mm, out, p["wo"]["w"])


def hidden(mm, w, tokens, z):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S)."""
    x = jnp.take(w["embed"]["emb"], tokens, axis=0)
    for i in range(z["layers"]):
        lp = jax.tree.map(lambda a: a[i], w["layers"])
        x = x + _attention(mm, lp["attn"], _rmsnorm(x, lp["n1"]["g"], z["eps"]), z)
        h = _rmsnorm(x, lp["n2"]["g"], z["eps"])
        f = lp["ffn"]
        y = jax.nn.silu(_linear(mm, h, f["wg"]["w"])) * _linear(mm, h, f["wu"]["w"])
        x = x + _linear(mm, y, f["wd"]["w"])
    return _rmsnorm(x, w["final_norm"]["g"], z["eps"])


def logits_at(mm, w, h):
    """Tied head: h (..., d) @ emb^T."""
    return _linear(mm, h, w["embed"]["emb"].T)


def loss(mm, w, batch, z):
    lg = logits_at(mm, w, hidden(mm, w, batch["tokens"], z))
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


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
    return jax.jit(step)


def train_reference(sizes: dict, traffic: dict, key, batches):
    """The first ``len(batches)`` training steps from the weights of
    ``key``: the loss of each step, the first clipped gradient and the
    parameters after the last step, with the starting weights."""
    frozen = tuple(sorted((k, v) for k, v in sizes.items()
                          if isinstance(v, (int, float, str))))
    items = tuple(sorted((k, v) for k, v in traffic.items()
                         if isinstance(v, (int, float, str))))
    step = _train_step(frozen, items)
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
def _served_logits(frozen):
    z = dims(dict(frozen))
    mm = numerics.matmul

    def run(w, tokens, positions):
        h = hidden(mm, w, tokens, z)                                 # (B, L, d)
        h = jnp.take_along_axis(h, positions[..., None], axis=1)     # (B, P, d)
        return logits_at(mm, w, h)
    return jax.jit(run)


def served_logits(sizes: dict, w, tokens, positions):
    """Logits (B, P, vocab) at ``positions`` (B, P) of ``tokens`` (B, L):
    row p predicts the token at p + 1."""
    frozen = tuple(sorted((k, v) for k, v in sizes.items()
                          if isinstance(v, (int, float, str))))
    return _served_logits(frozen)(w, tokens, positions)
