"""Primitive layers, every multiplication routed through NumericsPolicy.

Functional style: ``init_*`` builds a param pytree (dict of jnp arrays),
the apply function takes (params, inputs, ..., policy).  This is the
AMDENSE analogue (paper §VI-C) generalised to the whole model zoo.

Elementwise products (norm scales, activations) stay native: the paper's
AMDENSE/AMCONV2D replace *GEMM* multiplies; norm/act multiplies are a
vanishing fraction of FLOPs and are not in the paper's scope.

``linear`` takes the layer's Megatron role (``kind`` = "column"/"row",
mirroring ``distributed/sharding._RULES``) so that under an active mesh
``mode="amsim"`` lowers to the per-shard fused LUT kernels via
``distributed/shard_fused`` instead of GSPMD's replicated-kernel
fallback (kill switch and knobs: docs/configuration.md) — and the
layer's numerics ``site`` label (``core.policy.SITES``), which a
:class:`~repro.core.policy.PolicyTable` resolves to per-site,
per-pass ``(mode, multiplier)`` leaves (docs/policies.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.policy import Numerics, NumericsPolicy
from repro.distributed.shard_fused import parallel_matmul
from repro.kernels.common import rms_norm


def init_linear(key, d_in: int, d_out: int, bias: bool = False, scale=None):
    scale = (1.0 / d_in) ** 0.5 if scale is None else scale
    p = {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def linear(p, x, policy: Numerics, kind: str | None = None,
           site: str | None = None):
    y = parallel_matmul(x, p["w"], policy, kind, site)
    if "b" in p:
        y = y + p["b"]
    return y


def init_embedding(key, vocab: int, d: int):
    return {"emb": jax.random.normal(key, (vocab, d), jnp.float32) * 0.02}


def embed(p, ids):
    return jnp.take(p["emb"], ids, axis=0)


def unembed(p, x, policy: Numerics):
    """Tied LM head: x @ emb^T (a GEMM -> routed through the policy).
    Vocab-parallel under the sharded fused path: emb^T's output dim is
    the "model"-sharded vocab, i.e. a column-parallel matmul.  Numerics
    site "unembed" (distinct from the untied "head")."""
    return parallel_matmul(x, p["emb"].T, policy, "column", "unembed")


def init_rmsnorm(d: int):
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps: float = 1e-5):
    return rms_norm(x, p["g"], eps)


def init_layernorm(d: int):
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def layernorm(p, x, eps: float = 1e-5):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]
