"""GQA attention with RoPE, KV cache, sliding window, cross-attention.

Score and value matmuls route through policy numerics (the paper's
observation that MultiHeadAttention "involves matrix multiplication under
the hood" — Table I); QKV/O projections route through ``policy.matmul``
with their Megatron roles (QKV column-parallel, O row-parallel).
Three attention lowerings, dispatched per call (``_derive_dispatch``):

  * **sharded** (``mode="amsim"`` under an active mesh): the fused
    one-launch kernel wrapped in shard_map — KV heads shard over
    "model", batch over the data axes, each shard runs the kernel on
    its block (``distributed/shard_fused``; REPRO_SHARD_FUSED=0 kills
    it, docs/distributed.md has the routing table).
  * **fused** (``mode="amsim"``, no ambient mesh, shape within the VMEM
    guards): the
    one-launch Pallas kernel ``kernels/approx_attention.py`` — score ->
    mask -> softmax -> value in a single grid sweep, scores never
    materialised in HBM, fully-masked KV blocks skipped so
    sliding-window decode cost scales with ``window`` not the cache
    capacity.  The q-chunk scan below collapses into the kernel's
    q-block grid axis.  ``REPRO_ATTN_FUSED=0`` kills the dispatch.
  * **einsum** (every other mode, oversize shapes, kill switch): the
    grouped-query einsum chain ``kernels/ops.attend_einsum`` — the
    KV-head axis stays a batch axis and the contractions lower to the
    4-D-grid ``approx_gemm_batched`` kernel in the amsim modes.  Long
    sequences are processed in q-chunks (scan) so the score matrix
    never exceeds (B, KV, G, q_chunk, T) — the memory-side requirement
    for the 32k-prefill dry-run cells.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.policy import Numerics
from repro.distributed import shard_fused
# NEG_INF is shared with the fused kernel and the einsum reference (one
# constant — the fused/einsum bit-compatibility contract depends on it).
from repro.kernels.common import attention_mask
from repro.kernels.ops import (NEG_INF, attend_einsum, attention_fused_leaf,
                               fused_attention_enabled, policy_attention)
from repro.models.layers import init_linear, linear


def init_attention(key, cfg: ArchConfig):
    d, dh = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d, cfg.n_heads * dh, bias=cfg.qkv_bias),
        "wk": init_linear(ks[1], d, cfg.n_kv_heads * dh, bias=cfg.qkv_bias),
        "wv": init_linear(ks[2], d, cfg.n_kv_heads * dh, bias=cfg.qkv_bias),
        "wo": init_linear(ks[3], cfg.n_heads * dh, d),
    }


@functools.lru_cache(maxsize=None)
def _rope_freqs(half: int, theta: float):
    """Per-(head_dim, theta) inverse-frequency table, computed once per
    process instead of per rope() call (it is shape/config-, not data-,
    dependent; under jit the cached concrete array embeds as a constant,
    and eager callers skip the recompute entirely).
    ensure_compile_time_eval keeps the computation eager even when the
    first call happens under a jit trace — caching a tracer here would
    leak it out of its trace."""
    with jax.ensure_compile_time_eval():
        return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def rope(x, positions, theta: float):
    """Rotary embedding. x: (B, S, H, dh), positions: (S,) or (B, S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = _rope_freqs(half, float(theta))
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, half)
        ang = ang[None, :, None, :]
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _wsc(x, *spec):
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _derive_dispatch(ap: Numerics, q_shape, k_shape, *, causal: bool,
                     window: int) -> str:
    """The three-way attention dispatch, decided once per call:

      * "sharded" — an active mesh (``shard_fused.active_mesh``: both
        attention sites resolve to one amsim leaf under a ``with
        mesh:`` context, REPRO_SHARD_FUSED not killed) whose axes
        divide batch/KV-heads and whose per-shard shape passes the
        kernel guards: the one-launch kernel runs per shard via
        shard_map (KV heads over "model", batch over the data axes).
      * "fused"   — no ambient mesh: the single-device one-launch
        kernel (shape permitting, REPRO_ATTN_FUSED to kill).
      * "einsum"  — everything else: policies whose score/value sites
        resolve differently (the kernel bakes one LUT), mesh-active
        shapes the sharded path cannot take, oversize shapes, kill
        switches — the grouped-query einsum chain, which GSPMD
        partitions natively and which honours per-site splits.
    """
    leaf = attention_fused_leaf(ap)
    mesh = shard_fused.active_mesh(leaf) if leaf is not None else None
    if mesh is not None:
        if shard_fused.attention_supported(ap, mesh, q_shape, k_shape,
                                           causal=causal, window=window):
            return "sharded"
        return "einsum"
    if fused_attention_enabled(ap, q_shape, k_shape, causal=causal,
                               window=window):
        return "fused"
    return "einsum"


def _attend_fullhead(q, k, v, q_pos, k_pos, policy: Numerics, *,
                     causal: bool, window: int, daxes,
                     dispatch: str | None = None):
    """§Perf optimisation: repeat KV to full head count and shard the head
    axis over "model" with explicit constraints — keeps score/prob tensors
    sharded 1/TP instead of replicated (GSPMD often fails to propagate
    sharding through the grouped-query reshape)."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    if dispatch is None:  # direct callers: derive the dispatch locally
        dispatch = _derive_dispatch(policy, q.shape, k.shape, causal=causal,
                                    window=window)
    if dispatch == "sharded":
        # Head sharding is native to the sharded fused kernel (KV heads
        # over "model"), on the original *grouped* K/V — the explicit
        # repeat+constraint dance below exists only for the einsum path.
        return shard_fused.sharded_attention(
            q, k, v, q_pos, k_pos, policy, causal=causal, window=window,
            mesh=shard_fused.active_mesh(attention_fused_leaf(policy)))
    if dispatch == "fused":
        # Single device: sharding constraints are no-ops, so the fused
        # one-launch kernel takes the call — on the original *grouped*
        # K/V (it folds G into its gather rows), skipping the G-fold
        # repeat below that the einsum layout needs.
        return policy_attention(q, k, v, q_pos, k_pos, policy, causal, window)
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    q = _wsc(q, daxes, None, "model", None)
    k = _wsc(k, daxes, None, "model", None)
    v = _wsc(v, daxes, None, "model", None)
    scores = policy.einsum("bqhd,bthd->bhqt", q, k,
                           site="attn_score") / jnp.sqrt(float(dh))
    scores = _wsc(scores, daxes, "model", None, None)
    mask = attention_mask(q_pos, k_pos, causal=causal, window=window)
    # Shared (S, T) mask broadcasts over (B, H); a per-row (B, S, T)
    # mask (paged cache, per-slot positions) broadcasts over H only.
    mask = mask[None, None] if mask.ndim == 2 else mask[:, None]
    probs = jax.nn.softmax(
        jnp.where(mask, scores.astype(jnp.float32), NEG_INF), -1)
    out = policy.einsum("bhqt,bthd->bqhd", probs, v, site="attn_value")
    return _wsc(out, daxes, None, "model", None)


def _attend(q, k, v, q_pos, k_pos, policy: Numerics, *,
            causal: bool, window: int, dispatch: str | None = None):
    """q (B,S,H,dh), k/v (B,T,KV,dh) -> (B,S,H,dh).

    Dispatch (see ``_derive_dispatch``): the shard_map-wrapped fused
    kernel under an active mesh, the single-device one-launch kernel,
    or the grouped-query einsum chain.  ``attention()`` passes the
    decision in (``dispatch``) so the q-chunk-scan skip and the inner
    dispatch can never disagree; direct callers may leave it None to
    self-derive.  k_pos holds the *absolute* position of every KV slot;
    negative means unwritten (ring-buffer cache) and is masked out.
    The "attn_score"/"attn_value" sites resolve inside each lowering.
    """
    if dispatch is None:
        dispatch = _derive_dispatch(policy, q.shape, k.shape, causal=causal,
                                    window=window)
    if dispatch == "sharded":
        return shard_fused.sharded_attention(
            q, k, v, q_pos, k_pos, policy, causal=causal, window=window,
            mesh=shard_fused.active_mesh(attention_fused_leaf(policy)))
    if dispatch == "fused":
        return policy_attention(q, k, v, q_pos, k_pos, policy, causal, window)
    return attend_einsum(q, k, v, q_pos, k_pos, policy, causal=causal,
                         window=window)


def _paged_cache_update(cache, k, v, q_pos):
    """Slot-granular paged KV cache: write the fresh K/V through the page
    table, gather the per-slot contiguous views.

    ``cache`` keys (serve/paged_cache.py; docs/serving.md):

      * ``pool_k``/``pool_v`` — (n_pages, page, KV, dh) shared page
        pools (page 0 is the reserved trash page: never allocated, the
        sink for every masked write).
      * ``ptab``  — (B, n_ptab) int32 page table per slot; entry 0 =
        unallocated (reads gather trash, masked by position validity).
      * ``start`` — (B,) int32 tokens already resident per slot.
      * ``live``  — (B,) bool slot liveness.  Dead rows write to the
        trash page and report every key position unwritten, so a dead
        slot can neither corrupt live pages nor attend to stale ones —
        eviction is pure host-side bookkeeping, no device reset.

    The page table / start / live arrays are HOST-authoritative: the
    scheduler passes fresh ones into every step and ignores the copies
    that ride along in the returned cache tree.  Token index t of a slot
    always holds absolute position t (a paged cache never wraps — the
    scheduler rejects requests longer than the table covers), so key
    positions are derived, not stored: t is valid iff t < start + S.
    Positions written past a slot's true length (padded prefill) are
    simply never valid and get overwritten as decode advances.

    Returns (k_view (B, T, KV, dh), v_view, k_pos (B, T), new_cache).
    """
    pool_k, pool_v = cache["pool_k"], cache["pool_v"]
    ptab, live, start = cache["ptab"], cache["live"], cache["start"]
    B, S = q_pos.shape
    page_size, n_ptab = pool_k.shape[1], ptab.shape[1]
    Tcap = n_ptab * page_size
    ok = live[:, None] & (q_pos >= 0) & (q_pos < Tcap)
    page = jnp.take_along_axis(
        ptab, jnp.clip(q_pos // page_size, 0, n_ptab - 1), axis=1)
    page = jnp.where(ok, page, 0)                     # masked -> trash page
    off = jnp.where(ok, q_pos % page_size, 0)
    cdt = pool_k.dtype
    pool_k = pool_k.at[page, off].set(k.astype(cdt))
    pool_v = pool_v.at[page, off].set(v.astype(cdt))
    k_view = pool_k[ptab].reshape(B, Tcap, *pool_k.shape[2:])
    v_view = pool_v[ptab].reshape(B, Tcap, *pool_v.shape[2:])
    t = jnp.arange(Tcap, dtype=jnp.int32)[None]
    valid = live[:, None] & (t < (start + S)[:, None])
    k_pos = jnp.where(valid, t, jnp.int32(-(2 ** 30)))
    return k_view, v_view, k_pos, dict(cache, pool_k=pool_k, pool_v=pool_v)


def attention(p, x, cfg: ArchConfig, policy: Numerics, *,
              kv_src=None, causal=True, q_offset=0, cache=None,
              window: int = 0, q_chunk: int | None = None,
              use_rope: bool = True):
    """Full attention block.  Returns (out, new_cache).

    kv_src: encoder states for cross-attention (no rope, no cache update
            semantics beyond plain K/V projection, causal=False expected).
    cache:  {"k","v": (B, Tmax, KV, dh), "len": int32} for decode (ring
            buffer), or a paged-cache dict carrying a ``ptab`` page
            table (``_paged_cache_update``; serve/paged_cache.py).
    """
    B, S, d = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # QKV projections are column-parallel, the output projection below is
    # row-parallel (sharding._RULES) — under an active mesh in amsim mode
    # each runs the fused LUT kernel per shard (distributed/shard_fused).
    # Numerics sites: projections are "qkv"/"wo"; the score/value
    # contractions below resolve "attn_score"/"attn_value".
    q = linear(p["wq"], x, policy, kind="column",
               site="qkv").reshape(B, S, H, dh)
    src = x if kv_src is None else kv_src
    Tsrc = src.shape[1]
    k = linear(p["wk"], src, policy, kind="column",
               site="qkv").reshape(B, Tsrc, KV, dh)
    v = linear(p["wv"], src, policy, kind="column",
               site="qkv").reshape(B, Tsrc, KV, dh)

    paged = cache is not None and "ptab" in cache
    if paged:
        # Paged serving cache (serve/paged_cache.py): every batch row is
        # a scheduler slot sitting at its own decode position, so the
        # position vector carries a batch dim and masking is per row.
        if kv_src is not None:
            raise ValueError("paged KV caches are decoder-self-attention "
                             "only (no cross-attention)")
        start = cache["start"]                                   # (B,)
        q_pos = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    else:
        start = cache["len"] if cache is not None else q_offset
        q_pos = start + jnp.arange(S, dtype=jnp.int32)
    if use_rope and kv_src is None:
        q = rope(q, q_pos, cfg.rope_theta)
        k = rope(k, q_pos, cfg.rope_theta)  # fresh K written at the same offsets

    if paged:
        k, v, k_pos, cache = _paged_cache_update(cache, k, v, q_pos)
    elif cache is not None:
        # Ring-buffer cache: write the S new KVs starting at slot
        # len % Tmax and record their absolute positions (sliding-window
        # decode keeps a cache of only `window` slots; masking is
        # position-based).  A write that reaches the end of the buffer
        # WRAPS: the single-token decode step keeps the contiguous
        # dynamic_update_slice fast path (slot + 1 <= Tmax always), any
        # larger write goes through a modular scatter so the boundary
        # can never silently clamp and corrupt the newest entries.  A
        # block longer than the buffer keeps only its last Tmax tokens
        # (the earlier ones would be overwritten by the wrap anyway) —
        # queries whose own keys were evicted that way see no valid key
        # and emit garbage (zeros fused / uniform V-average einsum);
        # only the surviving tail rows carry meaning, which is what
        # decode consumes.
        Tmax = cache["k"].shape[1]
        cdt = cache["k"].dtype
        kw_, vw_, pw_ = k.astype(cdt), v.astype(cdt), q_pos
        if S > Tmax:
            kw_, vw_, pw_ = kw_[:, -Tmax:], vw_[:, -Tmax:], pw_[-Tmax:]
        slot = (cache["len"] + max(0, S - Tmax)) % Tmax
        if kw_.shape[1] == 1:
            k = jax.lax.dynamic_update_slice(cache["k"], kw_, (0, slot, 0, 0))
            v = jax.lax.dynamic_update_slice(cache["v"], vw_, (0, slot, 0, 0))
            pos = jax.lax.dynamic_update_slice(cache["pos"], pw_, (slot,))
        else:
            idx = (slot + jnp.arange(kw_.shape[1], dtype=jnp.int32)) % Tmax
            k = cache["k"].at[:, idx].set(kw_, unique_indices=True)
            v = cache["v"].at[:, idx].set(vw_, unique_indices=True)
            pos = cache["pos"].at[idx].set(pw_, unique_indices=True)
        cache = {"k": k, "v": v, "pos": pos, "len": cache["len"] + S}
        k_pos = pos
    else:
        k_pos = jnp.arange(Tsrc, dtype=jnp.int32) if kv_src is not None else q_pos

    # Dispatch decision, made ONCE here and passed down: both kernel
    # lowerings ("fused" single-device, "sharded" per-shard) block q
    # internally (the q-block grid axis), so the memory-side motivation
    # for the q-chunk scan — bounding the materialised
    # (B, KV, G, q_chunk, T) score tensor — vanishes and the scan
    # collapses into the kernel.  Sharing one decision with
    # _attend/_attend_fullhead means the scan skip and the inner
    # dispatch can never drift apart (skipping the scan while the inner
    # call fell back to einsum would rematerialise the full score
    # tensor the scan exists to bound).
    if q_pos.ndim > 1:
        # Per-slot positions (paged serving cache): the sharded kernel
        # lowering consumes ONE position vector shared across the
        # batch, so mesh-active batched-position calls keep the einsum
        # chain (GSPMD partitions it natively, it masks per row, and
        # the amsim contractions still lower to the batched LUT GEMM
        # kernel).  Off-mesh, the single-device one-launch kernel
        # accepts per-row positions directly (its mask/liveness
        # operands grow a leading batch axis), so paged serving decode
        # ticks run the same fused attention core as the ring layout.
        leaf = attention_fused_leaf(policy)
        mesh = shard_fused.active_mesh(leaf) if leaf is not None else None
        if mesh is None and fused_attention_enabled(
                policy, q.shape, k.shape, causal=causal, window=window,
                per_row=True):
            dispatch = "fused"
        else:
            dispatch = "einsum"
    else:
        dispatch = _derive_dispatch(policy, q.shape, k.shape,
                                    causal=causal, window=window)
    if dispatch == "fused" and cfg.shard_attn_heads \
            and jax.device_count() > 1:
        # Meshless multi-device + explicit head-sharding constraints:
        # keep the einsum path (the constraints are the optimisation).
        dispatch = "einsum"
    in_kernel = dispatch != "einsum"
    if cfg.shard_attn_heads:
        attend = lambda qi, pi: _attend_fullhead(
            qi, k, v, pi, k_pos, policy, causal=causal, window=window,
            dispatch=dispatch if qi.shape == q.shape else "einsum",
            daxes=(cfg.mesh_data_axes if len(cfg.mesh_data_axes) > 1
                   else cfg.mesh_data_axes[0]))
    else:
        attend = lambda qi, pi: _attend(
            qi, k, v, pi, k_pos, policy, causal=causal, window=window,
            dispatch=dispatch if qi.shape == q.shape else "einsum")
    q_chunk = cfg.q_chunk if q_chunk is None else q_chunk
    if S > q_chunk and S % q_chunk == 0 and not in_kernel:
        nc = S // q_chunk
        if cfg.unroll_attn_chunks:
            # Python-unrolled chunks: used by the dry-run so cost_analysis
            # counts every chunk's score FLOPs (lax.map bodies cost once).
            outs = [
                attend(q[:, i * q_chunk:(i + 1) * q_chunk],
                       q_pos[..., i * q_chunk:(i + 1) * q_chunk])
                for i in range(nc)
            ]
            out = jnp.concatenate(outs, axis=1)
        else:
            qc = q.reshape(B, nc, q_chunk, H, dh).transpose(1, 0, 2, 3, 4)
            pc = (q_pos.reshape(nc, q_chunk) if q_pos.ndim == 1
                  else q_pos.reshape(B, nc, q_chunk).transpose(1, 0, 2))
            out = jax.lax.map(lambda args: attend(*args), (qc, pc))
            out = out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, dh)
    else:
        out = attend(q, q_pos)
    out = out.reshape(B, S, H * dh)
    return linear(p["wo"], out, policy, kind="row", site="wo"), cache


def init_cache(cfg: ArchConfig, batch: int, max_len: int):
    dh, KV = cfg.head_dim, cfg.n_kv_heads
    dt = jnp.dtype(cfg.cache_dtype)
    return {
        "k": jnp.zeros((batch, max_len, KV, dh), dt),
        "v": jnp.zeros((batch, max_len, KV, dh), dt),
        "pos": jnp.full((max_len,), -(2**30), jnp.int32),  # -ve = unwritten
        "len": jnp.zeros((), jnp.int32),
    }
