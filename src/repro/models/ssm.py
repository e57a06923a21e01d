"""Mamba2 (SSD — state-space duality) block, policy-routed einsums.

Chunked SSD algorithm (arXiv:2405.21060): within a chunk of length Q the
output is an attention-like masked matmul (quadratic in Q only); across
chunks a (heads, p, N) state is carried by a linear recurrence.  Both the
intra-chunk score/value matmuls and the state contraction/expansion
einsums route through ``policy.einsum`` — the SSD form makes the paper's
approximate-GEMM technique directly applicable to an attention-free arch.

Decode is a constant-size recurrent state update: the "KV cache" of an
SSM is O(1) in sequence length (noted in the roofline table for the
decode_32k / long_500k cells).  A decode step forms the same two products
the chunked form does, ``B (x dt)`` and ``C h``, under the same policy,
through the state-update kernel (``kernels/ssm_state.py``); decays,
``dt x``, ``D x``, the gate and the norms stay exact float32.

n_groups=1 (Mamba2 default): B and C are shared across heads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ArchConfig
from repro.core.policy import NumericsPolicy
from repro.kernels.ops import policy_ssm_state
from repro.models.layers import init_linear, init_rmsnorm, linear, rmsnorm


def _dims(cfg: ArchConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nheads = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.d_state
    return s, d_in, nheads, conv_ch


def init_mamba2(key, cfg: ArchConfig):
    s, d_in, nheads, conv_ch = _dims(cfg)
    ks = jax.random.split(key, 4)
    d_proj = 2 * d_in + 2 * s.n_groups * s.d_state + nheads  # z,x,B,C,dt
    return {
        "in_proj": init_linear(ks[0], cfg.d_model, d_proj),
        "conv_w": jax.random.normal(ks[1], (s.conv_kernel, conv_ch), jnp.float32)
        * (1.0 / s.conv_kernel) ** 0.5,
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads).astype(jnp.float32)),
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.full((nheads,), -2.0, jnp.float32),
        "norm": init_rmsnorm(d_in),
        "out_proj": init_linear(ks[2], d_in, cfg.d_model),
    }


def _conv_with_state(xbc, c0, w, b):
    """Depthwise causal conv1d of xbc (B, L, ch) continuing from the K-1
    inputs before it, c0 (B, K-1, ch), w (K, ch).  Products are exact
    float32."""
    K, L = w.shape[0], xbc.shape[1]
    full = jnp.concatenate([c0.astype(xbc.dtype), xbc], axis=1)
    return sum(full[:, i:i + L, :] * w[i] for i in range(K)) + b, full


def _split_proj(cfg, zxbcdt):
    s, d_in, nheads, _ = _dims(cfg)
    gn = s.n_groups * s.d_state
    z, xs, Bc, Cc, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + gn, 2 * d_in + 2 * gn], axis=-1)
    return z, xs, Bc, Cc, dt


def mamba2(p, u, cfg: ArchConfig, policy: NumericsPolicy, *, cache=None,
           true_len=None):
    """u (B, L, d) -> (y (B, L, d), new_cache).

    The recurrent state is kept as ``ssm`` (B, N, nh * p), d_state first
    (``kernels/ssm_state.py``), and the conv input history as ``conv``
    (B, K-1, conv_ch).  ``cache`` holds both for decoding, or, in the
    serving engine's slot state, both stacked over layers and slots,
    (layers, slots, ...), with the layer's index under ``"layer"``; it is
    returned in the form it came in.  A stacked cache with ``"slot"``
    (B,) is an admission prefill: the block starts from zero and its
    final state is written to those slots.

    * One token with a cache: a recurrent step, the products
      ``B (x dt)`` and ``C h`` under ``policy`` (``kernels/ops.policy_ssm_state``).
    * Otherwise the chunked SSD over the whole block, from the cache's
      state or from zero, with chunk ``min(chunk, L)``.  ``true_len``
      (B,) marks each row's real length in a right-padded block: ``dt``
      is 0 at and past it, so padding neither decays nor feeds the state,
      whose value after the last real position, with the conv history of
      its K-1 inputs, is the new cache.
    """
    s, d_in, nheads, conv_ch = _dims(cfg)
    B_, L, _ = u.shape
    hp, N, K = s.head_dim, s.d_state, s.conv_kernel

    zxbcdt = linear(p["in_proj"], u, policy, site="ssm")
    z, xs, Bc, Cc, dt = _split_proj(cfg, zxbcdt)
    xbc = jnp.concatenate([xs, Bc, Cc], axis=-1)

    fresh = cache is None or "slot" in cache
    stacked = cache is not None and "layer" in cache
    step = cache is not None and not fresh and L == 1
    if fresh:
        c0 = jnp.zeros((B_, K - 1, conv_ch), jnp.float32)
    elif stacked:
        c0 = jax.lax.dynamic_index_in_dim(cache["conv"], cache["layer"], 0,
                                          keepdims=False)
    else:
        c0 = cache["conv"]
    xbc, hist = _conv_with_state(xbc, c0, p["conv_w"], p["conv_b"])
    end = jnp.full((B_,), L, jnp.int32) if true_len is None else true_len
    new_conv = jax.vmap(lambda h, e: jax.lax.dynamic_slice_in_dim(
        h, e, K - 1, axis=0))(hist, end.astype(jnp.int32))
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(B_, L, nheads, hp)
    Bc = xbc[..., d_in : d_in + N]                      # (B, L, N)  G=1
    Cc = xbc[..., d_in + N :]                           # (B, L, N)

    dt = jax.nn.softplus(dt + p["dt_bias"])             # (B, L, nh)
    if true_len is not None:
        real = jnp.arange(L)[None, :] < true_len[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"])                            # (nh,)
    dA = dt * A                                         # (B, L, nh)  log-decay
    xdt = xs * dt[..., None]                            # (B, L, nh, p)

    if step:
        state = cache["ssm"] if stacked else cache["ssm"][None]
        layer = cache["layer"] if stacked else 0
        dec = jnp.repeat(jnp.exp(dA[:, 0]), hp, axis=-1)   # (B, nh * p)
        y, state = policy_ssm_state(state, layer, xdt.reshape(B_, d_in), dec,
                                    Bc[:, 0], Cc[:, 0], policy)
        y = y.reshape(B_, 1, nheads, hp)
    else:
        h0 = None if fresh else cache["ssm"]
        Q = min(s.chunk, L)
        obs.route("ssm.chunk", str(Q))
        if h0 is not None:
            h0 = h0.reshape(B_, N, nheads, hp).transpose(0, 2, 3, 1)
        y, h = _ssd_chunked(xdt, Bc, Cc, dA, Q, policy, h0)
        state = h.transpose(0, 3, 1, 2).reshape(B_, N, d_in)

    if cache is None:
        new_cache = None
    elif stacked and step:
        new_cache = dict(cache, ssm=state,
                         conv=jax.lax.dynamic_update_index_in_dim(
                             cache["conv"], new_conv, cache["layer"], 0))
    elif stacked:
        at = (cache["layer"], cache["slot"])
        new_cache = dict(cache, ssm=cache["ssm"].at[at].set(state),
                         conv=cache["conv"].at[at].set(new_conv))
    else:
        new_cache = {"ssm": state[0] if step else state, "conv": new_conv}

    y = y + p["D"][None, None, :, None] * xs            # skip connection
    y = y.reshape(B_, L, d_in) * jax.nn.silu(z)
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    return linear(p["out_proj"], y, policy, site="ssm"), new_cache


def _ssd_chunked(xdt, Bc, Cc, dA, Q: int, policy: NumericsPolicy, h0=None):
    """SSD scan from state h0 (B,nh,p,N), zero when None.  xdt (B,L,nh,p),
    Bc/Cc (B,L,N), dA (B,L,nh) -> (y (B,L,nh,p), final state (B,nh,p,N))."""
    B_, L, nh, hp = xdt.shape
    N = Bc.shape[-1]
    pad = -L % Q
    if pad:
        # Zero inputs and zero log-decay: the padding leaves the state as
        # the last position left it, and its outputs are dropped.
        grow = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        y, h = _ssd_chunked(grow(xdt), grow(Bc), grow(Cc), grow(dA), Q,
                            policy, h0)
        return y[:, :L], h
    c = L // Q
    xc = xdt.reshape(B_, c, Q, nh, hp)
    Bcc = Bc.reshape(B_, c, Q, N)
    Ccc = Cc.reshape(B_, c, Q, N)
    dAc = dA.reshape(B_, c, Q, nh)
    cum = jnp.cumsum(dAc, axis=2)                       # (B,c,Q,nh)

    # --- intra-chunk: attention-like masked matmul (all SSD einsums
    # resolve under the single "ssm" site — gemm family)
    scores = policy.einsum("bcln,bcsn->bcls", Ccc, Bcc, site="ssm")
    decay = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # l,s -> (B,c,Q,Q,nh)
    li = jnp.arange(Q)
    mask = (li[:, None] >= li[None, :])[None, None, :, :, None]
    Tm = jnp.where(mask, jnp.exp(decay), 0.0) * scores[..., None]  # (B,c,Q,Q,nh)
    y_intra = policy.einsum("bclsh,bcshp->bclhp", Tm, xc, site="ssm")

    # --- chunk states: S_c = sum_s exp(cum_last - cum_s) B_s x_s^T
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)           # (B,c,Q,nh)
    Sc = policy.einsum("bcsn,bcshp->bchpn", Bcc, xc * to_end[..., None],
                       site="ssm")

    # --- inter-chunk recurrence over c (sequential scan)
    seg = jnp.exp(cum[:, :, -1, :])                     # (B,c,nh) chunk decay

    def step(h, t):
        y = h                                           # state entering chunk t
        h = h * seg[:, t][:, :, None, None] + Sc[:, t]
        return h, y

    if h0 is None:
        h0 = jnp.zeros((B_, nh, hp, N), jnp.float32)
    h, hs = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(c))
    hs = jnp.moveaxis(hs, 0, 1)                         # (B,c,nh,hp,N) entering
    y_inter = policy.einsum("bcln,bchpn->bclhp", Ccc, hs, site="ssm")
    y_inter = y_inter * jnp.exp(cum)[..., None]
    return (y_intra + y_inter).reshape(B_, L, nh, hp), h


def init_ssm_cache(cfg: ArchConfig, batch: int):
    s, d_in, nheads, conv_ch = _dims(cfg)
    return {
        "ssm": jnp.zeros((batch, s.d_state, d_in), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_kernel - 1, conv_ch), jnp.float32),
    }
