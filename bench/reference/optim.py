"""Optimizers of the training traffic, written out plainly: global-norm
clipping, then AdamW (or SGD with momentum) under a warmup-cosine rate."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def learning_rate(traffic: dict, step):
    """Linear warmup to ``lr`` over ``warmup_steps``, then a cosine decay
    to a tenth of it at ``total_steps``.  ``step`` counts from 1."""
    base, warm = traffic["lr"], traffic.get("warmup_steps", 0)
    total = traffic.get("total_steps", 0)
    step = jnp.asarray(step, jnp.float32)
    if not total:
        return jnp.asarray(base, jnp.float32)
    t = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = 0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(math.pi * t))
    return jnp.where(step < warm, base * step / max(warm, 1), base * cos)


def clip(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def init_state(traffic: dict, params):
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    if traffic["optimizer"] == "adamw":
        return {"m": zeros(), "v": zeros(), "step": jnp.zeros((), jnp.int32)}
    if traffic["optimizer"] == "sgdm":
        return {"mu": zeros(), "step": jnp.zeros((), jnp.int32)}
    raise ValueError(f"unknown optimizer {traffic['optimizer']!r}")


def update(traffic: dict, params, grads, state):
    """One step: returns (new params, new state)."""
    step = state["step"] + 1
    lr = learning_rate(traffic, step)
    if traffic["optimizer"] == "adamw":
        b1, b2, eps = 0.9, 0.999, 1e-8
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)
        new = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)),
            params, m, v)
        return new, {"m": m, "v": v, "step": step}
    mu = jax.tree.map(lambda m, g: 0.9 * m + g, state["mu"], grads)
    new = jax.tree.map(lambda p, m: p - lr * m, params, mu)
    return new, {"mu": mu, "step": step}


def first_gradient(traffic: dict, state):
    """The first (clipped) gradient, as the optimizer's state after one
    step holds it."""
    if traffic["optimizer"] == "adamw":
        return jax.tree.map(lambda m: m / 0.1, state["m"])
    return state["mu"]
