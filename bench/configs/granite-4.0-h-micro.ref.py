"""Plain reference of granite-4.0-h-micro as the benchmark runs it.

Written out in jax.numpy from the sizes in ``granite-4.0-h-micro.json``:
token embedding, then per layer an RMSNorm, a mixer, a residual, an
RMSNorm, a SwiGLU MLP and a residual; a final RMSNorm and the tied head.
The mixer of layer i is ``layer_types[i]``:

  * "attention": grouped-query attention with no position embedding
    ("nope"), causal;
  * "mamba": Mamba-2 with one group.  in_proj gives z, x, B, C and dt;
    x, B and C pass a depthwise causal conv (kernel 4, with bias) and
    SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log); then, position by
    position from a zero state h (heads, head_dim, d_state),

        h <- h * exp(dt A) + B (x dt),    y = C . h + D x,

    and y * silu(z) passes an RMSNorm over the whole inner width and
    out_proj.

The recurrence runs one position at a time, never in chunks, so that a
fault in the program's chunked form or in its padding shows.  The
departures from the published model that the program makes are the
configuration's own and are kept here (``departures`` in the JSON).

Every GEMM and attention score and value product goes through
``bench/reference/numerics.py`` (the afm16 product from its definition),
and so do the recurrence's products B (x dt) and C h, elementwise; the
conv's products, the decays, x dt, D x, the gate, the activations and the
norms are exact float32.  This file imports nothing of the program: it
also makes the weights, from the seed, in the layout that both it and the
program read.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import numerics

NEG = -1e30


def dims(sizes: dict) -> dict:
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    kinds = tuple(sizes["layer_types"][:sizes["num_hidden_layers"]])
    return {"d": d, "h": h, "kv": sizes["num_key_value_heads"], "dh": d // h,
            "ff": sizes["shared_intermediate_size"], "vocab": sizes["vocab_size"],
            "kinds": kinds, "eps": sizes["rms_norm_eps"],
            "init": sizes["initializer_range"],
            "mh": sizes["mamba_n_heads"], "mp": sizes["mamba_d_head"],
            "n": sizes["mamba_d_state"], "k": sizes["mamba_d_conv"],
            "d_in": sizes["mamba_n_heads"] * sizes["mamba_d_head"]}


def _frozen(sizes: dict):
    items = [(k, v) for k, v in sizes.items() if isinstance(v, (int, float, str))]
    return tuple(sorted(items)) + (("layer_types", tuple(sizes["layer_types"])),)


@functools.lru_cache(maxsize=None)
def _maker(frozen):
    z = dims(dict(frozen))

    def make(key):
        ks = iter(jax.random.split(key, 24))
        n = lambda shape, scale: jax.random.normal(next(ks), shape, jnp.float32) * scale
        d, dh, ff, d_in = z["d"], z["dh"], z["ff"], z["d_in"]
        lin = lambda L, i, o: {"w": n((L, i, o), 1.0 / math.sqrt(i))}

        def mlp(L):
            return {"n1": {"g": jnp.ones((L, d), jnp.float32)},
                    "n2": {"g": jnp.ones((L, d), jnp.float32)},
                    "ffn": {"wg": lin(L, d, ff), "wu": lin(L, d, ff),
                            "wd": lin(L, ff, d)}}
        layers = {}
        na, nm = z["kinds"].count("attention"), z["kinds"].count("mamba")
        if na:
            layers["attention"] = dict(mlp(na), attn={
                "wq": lin(na, d, z["h"] * dh), "wk": lin(na, d, z["kv"] * dh),
                "wv": lin(na, d, z["kv"] * dh), "wo": lin(na, z["h"] * dh, d)})
        if nm:
            ch = d_in + 2 * z["n"]
            A = jax.random.uniform(next(ks), (nm, z["mh"]), jnp.float32, 1.0, 16.0)
            dt = jnp.exp(jax.random.uniform(next(ks), (nm, z["mh"]), jnp.float32,
                                            math.log(1e-3), math.log(1e-1)))
            layers["mamba"] = dict(mlp(nm), mixer={
                "in_proj": lin(nm, d, 2 * d_in + 2 * z["n"] + z["mh"]),
                "conv_w": n((nm, z["k"], ch), 1.0 / math.sqrt(z["k"])),
                "conv_b": n((nm, ch), 0.1),
                "A_log": jnp.log(A),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
                "D": jnp.ones((nm, z["mh"]), jnp.float32),
                "norm": {"g": jnp.ones((nm, d_in), jnp.float32)},
                "out_proj": lin(nm, d_in, d)})
        return {"embed": {"emb": n((z["vocab"], d), z["init"])},
                "final_norm": {"g": jnp.ones((d,), jnp.float32)},
                "layers": layers}
    return jax.jit(make)


def make_weights(sizes: dict, key):
    """Float32 weights from ``key`` in one jitted call on the device.
    Layer weights are stacked over the layers of each kind, in layer
    order."""
    return _maker(_frozen(sizes))(key)


# ------------------------------------------------------------------ model
def _linear(mm, x, w):
    return mm(x.reshape(-1, x.shape[-1]), w).reshape(*x.shape[:-1], w.shape[-1])


def _rmsnorm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * g


def _attention(mm, p, x, z):
    B, S, _ = x.shape
    H, KV, dh = z["h"], z["kv"], z["dh"]
    G = H // KV
    pos = jnp.arange(S, dtype=jnp.int32)
    q = _linear(mm, x, p["wq"]["w"]).reshape(B, S, H, dh)
    k = _linear(mm, x, p["wk"]["w"]).reshape(B, S, KV, dh)
    v = _linear(mm, x, p["wv"]["w"]).reshape(B, S, KV, dh)
    qg = q.reshape(B, S, KV, G, dh).transpose(0, 2, 3, 1, 4).reshape(B, KV, G * S, dh)
    kt = k.transpose(0, 2, 3, 1)                                   # (B, KV, dh, S)
    scores = mm(qg, kt).reshape(B, KV, G, S, S) / math.sqrt(dh)
    causal = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, NEG), axis=-1)
    out = mm(probs.reshape(B, KV, G * S, S), v.transpose(0, 2, 1, 3))
    out = out.reshape(B, KV, G, S, dh).transpose(0, 3, 1, 2, 4).reshape(B, S, H * dh)
    return _linear(mm, out, p["wo"]["w"])


def _mamba(mm, mul, p, x, z):
    B, S, _ = x.shape
    d_in, N, H, P, K = z["d_in"], z["n"], z["mh"], z["mp"], z["k"]
    proj = _linear(mm, x, p["in_proj"]["w"])
    gate, xbc, dt = (proj[..., :d_in], proj[..., d_in:2 * d_in + 2 * N],
                     proj[..., 2 * d_in + 2 * N:])
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :d_in].reshape(B, S, H, P)
    Bm, Cm = xbc[..., d_in:d_in + N], xbc[..., d_in + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                        # (B, S, H)
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]))
    xdt = xs * dt[..., None]

    def step(h, t):
        b, c = Bm[:, t][:, None, None, :], Cm[:, t][:, None, None, :]
        h = h * decay[:, t][:, :, None, None] + mul(b, xdt[:, t][..., None])
        return h, jnp.sum(mul(c, h), axis=-1)                      # (B, H, P)

    h0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0, jnp.arange(S))
    y = jnp.moveaxis(ys, 0, 1) + p["D"][:, None] * xs              # (B, S, H, P)
    y = y.reshape(B, S, d_in) * jax.nn.silu(gate)
    return _linear(mm, _rmsnorm(y, p["norm"]["g"], z["eps"]), p["out_proj"]["w"])


def hidden(mm, mul, w, tokens, z):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S)."""
    x = jnp.take(w["embed"]["emb"], tokens, axis=0)
    seen = {"mamba": 0, "attention": 0}
    for kind in z["kinds"]:
        lp = jax.tree.map(lambda a: a[seen[kind]], w["layers"][kind])
        seen[kind] += 1
        h = _rmsnorm(x, lp["n1"]["g"], z["eps"])
        x = x + (_attention(mm, lp["attn"], h, z) if kind == "attention"
                 else _mamba(mm, mul, lp["mixer"], h, z))
        h = _rmsnorm(x, lp["n2"]["g"], z["eps"])
        f = lp["ffn"]
        y = jax.nn.silu(_linear(mm, h, f["wg"]["w"])) * _linear(mm, h, f["wu"]["w"])
        x = x + _linear(mm, y, f["wd"]["w"])
    return _rmsnorm(x, w["final_norm"]["g"], z["eps"])


def logits_at(mm, w, h):
    """Tied head: h (..., d) @ emb^T."""
    return _linear(mm, h, w["embed"]["emb"].T)


@functools.lru_cache(maxsize=None)
def _served_logits(frozen, exact: bool):
    z = dims(dict(frozen))
    if exact:
        mm = lambda a, b: jnp.matmul(a, b, precision="highest")
        mul = jnp.multiply
    else:
        mm, mul = numerics.matmul, numerics.afm16

    def run(w, tokens, positions):
        h = hidden(mm, mul, w, tokens, z)                            # (B, L, d)
        h = jnp.take_along_axis(h, positions[..., None], axis=1)     # (B, P, d)
        return logits_at(mm, w, h)
    return jax.jit(run)


def served_logits(sizes: dict, w, tokens, positions, exact: bool = False):
    """Logits (B, P, vocab) at ``positions`` (B, P) of ``tokens`` (B, L):
    row p predicts the token at p + 1.  Every product afm16, or, with
    ``exact``, exact float32 (the program's native numerics)."""
    return _served_logits(_frozen(sizes), exact)(w, tokens, positions)
