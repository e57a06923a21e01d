"""Fused LUT approx-attention Pallas kernel — one launch for the whole
score -> mask -> softmax -> value chain (paper §V-B / §VI-D applied to
attention).

The paper's AMDENSE argument is that simulating an approximate
multiplier is only fast when the AMSim device function is inlined into
the consuming GEMM instead of round-tripping intermediates through
memory.  PR 1/2 applied that to matmul and conv2d, but attention still
lowered to *two* ``approx_gemm_batched`` launches with the full
``(B*KV*G, S, T)`` score tensor materialised in HBM between them, plus a
third full pass for mask + softmax.  This kernel is the attention leg of
the same fusion: per ``(batch*kv-head, q-block)`` grid cell it

  1. streams KV blocks through the shared LUT gather-GEMM brick
     (``kernels/common._gather_gemm_tile`` — the same VPU brick the
     AMDENSE/AMCONV2D kernels use) to fill a VMEM score scratch,
     applying the causal / sliding-window / ring-buffer-position mask
     in-kernel;
  2. runs the row softmax (max / denominator) entirely in VMEM;
  3. accumulates ``probs @ V`` through the LUT, streaming the same KV
     blocks again.

Scores never touch HBM: only ``q``, ``k``, ``v`` and the output do.

Design note — why a score scratch instead of classic online softmax:
flash-attention's running-max/denominator rescaling multiplies the
*accumulator* by a correction factor, which is only valid when
``probs @ V`` is an exact linear contraction.  Here the value GEMM runs
through the approximate multiplier (``amsim(p, v)`` quantises ``p``
before multiplying — Alg. 2 line 8), so post-hoc rescaling would change
the simulated numerics and break bit-compatibility with the einsum
oracle.  Instead the masked score tile for one q-block row lives in VMEM
scratch (``(bq*G, Tp)`` f32 — bounded by ``attention_fused_supported``),
the softmax normalises *before* the LUT multiply, and the value pass
re-streams KV blocks.  The running max/denominator still exist, but as a
whole-row VMEM reduction rather than a streamed rescale.

Masking / decode scaling: the mask is position-based (``k_pos`` holds
the absolute position of every KV slot, negative = unwritten ring-buffer
slot) and precomputed vectorised per call, together with per-KV-block
liveness flags (does the block intersect any valid (q, k) pair?).  Both
in-kernel LUT passes guard each block on its flag with ``lax.cond``: a
block that is entirely outside the sliding window, beyond the causal
frontier, or an unwritten ring region skips both gather sweeps, so
decode cost scales with ``window``, not the cache capacity ``Tmax``.

Bit-compatibility with the ``amsim_jnp`` einsum oracle
(`ops.attend_einsum`): exact when the KV streaming structure matches the
oracle's reduction structure — i.e. ``T <= 128`` with ``bkv >= T``, or
``T % 128 == 0`` with ``bkv = chunk = 128`` (the oracle's ``_K_CHUNK``)
— up to the sign of exact-zero outputs.  Other tilings regroup the FP32
accumulation and agree to ulps (tests assert both regimes).

Block sizes come from the autotuner's ``attention`` namespace
(``kernels/autotune.py``), keyed backend | B*KV / S / T / G / head_dim |
M; explicit ``bq``/``bkv``/``chunk`` arguments override.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune
from repro.kernels.common import (_ceil_to,
                                  _gather_gemm_tile, _pad_to,
                                  attention_mask, best_chunk, kernel_lut,
                                  lut_spec, resolve_interpret)

NEG_INF = -1e30          # matches models/attention.py's mask fill
POS_PAD = -(2 ** 30)     # padding sentinel: same "unwritten" marker as
                         # init_cache; any negative position is masked

# VMEM guard for the fused path (see attention_fused_supported).
MAX_ATTN_BYTES = 8 * 1024 * 1024
MAX_BQ = 256             # largest q tile any cached config may pick
MAX_BKV = 256            # largest kv tile any cached config may pick
MAX_DH = 256             # score-GEMM depth bound

# Incremented once per *trace* of the fused wrapper (never per step):
# tests assert the fused core engages on paged serving decode ticks.
_TRACES = [0]


def trace_count() -> int:
    return _TRACES[0]


def attention_fused_supported(q_shape, k_shape, *, causal: bool = True,
                              window: int = 0,
                              per_row: bool = False) -> bool:
    """Whether the fused kernel can take this attention shape (VMEM
    guard on the per-grid-cell resident arrays: K/V of one batch*kv-head,
    the (bq*G, Tp) score scratch, q/out tiles) — callers fall back to
    the einsum + ``approx_gemm_batched`` path otherwise.  The bound must
    hold for ANY tiling the autotuner may pick, so it assumes the
    MAX_BQ/MAX_BKV caps the wrapper clamps cached configs to.  Under a
    causal sliding window the wrapper compacts the KV axis to the static
    ``window + S`` live budget first, so a huge ring-buffer capacity
    does not disqualify windowed decode.  ``per_row`` positions (the
    paged serving cache: every batch row at its own decode offset)
    disable that compaction — there is no single shared live set — so
    the bound is taken on the full KV extent.
    """
    B, S, H, dh = q_shape
    T, KV = k_shape[1], k_shape[2]
    if H % KV or dh > MAX_DH or S < 1 or T < 1:
        return False
    if causal and window and not per_row:
        T = min(T, window + S)  # wrapper's window compaction
    rows = min(MAX_BQ, S) * (H // KV)
    tp = T + MAX_BKV  # worst-case block padding
    resident = 4 * (2 * tp * dh        # K and V of one batch*kv-head
                    + rows * tp        # score scratch
                    + 2 * rows * dh)   # q block + output block
    return resident <= MAX_ATTN_BYTES


def _attn_kernel(q_ref, kt_ref, v_ref, mask_ref, live_ref, lut_ref, o_ref,
                 s_scr, *, M: int, bkv: int, chunk_d: int, chunk_t: int,
                 kv_heads: int, per_row: bool):
    """One (batch*kv-head, q-block) output tile.

    Grid cell layout: the q block arrives as (bq*G, dh) gather rows
    (q-position major, group-head minor — the einsum oracle's score row
    order); K arrives transposed, (dh, Tp), so a KV block of the score
    GEMM is a lane slice; the whole padded K/V of this batch*kv-head is
    VMEM resident and streamed in bkv-sized blocks by both LUT passes.

    The (bq*G, Tp) mask (int32, already expanded to gather rows) and the
    per-KV-block liveness flags (SMEM, the whole (Bm, nq, nkv) array)
    arrive precomputed — vectorised once per call by the wrapper.  Both
    LUT passes are static fori_loops whose body is guarded by
    ``lax.cond`` on the block's flag, so a fully-masked KV block costs a
    flag test instead of a gather sweep — this is what makes
    sliding-window decode cost scale with ``window`` instead of the
    ring-buffer capacity.  (A dynamic-trip-count while_loop over just
    the live blocks measured strictly worse under interpret-mode state
    discharge; static bounds keep the loop on the fast scan path.)
    """
    rows, dh = q_ref.shape[1], q_ref.shape[2]
    Tp = kt_ref.shape[2]
    nkv = Tp // bkv
    mrow = pl.program_id(0) // kv_heads if per_row else 0
    iq = pl.program_id(1)
    q = q_ref[0]
    lut = lut_ref[...]

    def block(j):
        # A single KV block starts at the static offset 0; several start
        # at lane offsets the chip needs proven 128-aligned (bkv % 128).
        return pl.ds(pl.multiple_of(j * bkv, bkv) if nkv > 1 else 0, bkv)

    # ---- pass 1: masked score tiles -> VMEM scratch (NEG_INF elsewhere)
    def score_step(j, carry):
        def live_tile():
            s = _gather_gemm_tile(
                q, kt_ref[0, :, block(j)], lut,
                jnp.zeros((rows, bkv), jnp.float32), M=M, chunk=chunk_d)
            s = s / jnp.sqrt(float(dh))
            return jnp.where(mask_ref[0, :, block(j)] != 0, s, NEG_INF)

        def dead_tile():
            return jnp.full((rows, bkv), NEG_INF, jnp.float32)

        s_scr[:, block(j)] = jax.lax.cond(live_ref[mrow, iq, j] != 0,
                                          live_tile, dead_tile)
        return carry

    jax.lax.fori_loop(0, nkv, score_step, 0)

    # ---- row softmax in VMEM (same op sequence as jax.nn.softmax, so
    # probs match the oracle bitwise when reduction spans line up).
    # Fully-masked rows are NaN-free (max = NEG_INF, exp(0) = 1 ->
    # uniform probs) but their value pass below only visits live blocks,
    # so such a row returns zeros/partial sums rather than the oracle's
    # uniform V-average.  A causal query normally attends at least
    # itself; the one reachable exception is a prefill longer than the
    # ring-buffer capacity, which evicts the earliest queries' own keys
    # — those rows are context-less garbage under every lowering (see
    # the cache-write comment in models/attention.py).  Padding rows
    # that hit this are cropped by the wrapper.  The probs overwrite the
    # scores in the scratch, where the value pass slices them.
    s = s_scr[...]
    m = jnp.max(s, axis=-1, keepdims=True)
    unnorm = jnp.exp(s - m)
    s_scr[...] = unnorm / jnp.sum(unnorm, axis=-1, keepdims=True)

    # ---- pass 2: probs @ V through the LUT over the same live blocks.
    # For any row with at least one valid key, a dead block's probs are
    # exactly 0 and AMSim flushes zero operands to zero, so skipping it
    # contributes nothing — up to the sign of a zero sum.
    def value_step(j, acc):
        def live_acc(acc):
            return _gather_gemm_tile(s_scr[:, block(j)],
                                     v_ref[0, block(j), :], lut, acc,
                                     M=M, chunk=chunk_t)

        return jax.lax.cond(live_ref[mrow, iq, j] != 0, live_acc,
                            lambda a: a, acc)

    o_ref[0] = jax.lax.fori_loop(0, nkv, value_step,
                                 jnp.zeros((rows, dh), jnp.float32))


@functools.partial(jax.jit, static_argnames=(
    "M", "causal", "window", "bq", "bkv", "chunk_d", "chunk_t",
    "contiguous_q", "interpret"))
def _attn_impl(q, k, v, q_pos, k_pos, lut, M, *, causal, window, bq, bkv,
               chunk_d, chunk_t, contiguous_q, interpret):
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    BH = B * KV
    # Grouped layouts: one grid row per (batch, kv-head), G folded into
    # the gather rows — the same batch flattening the einsum path feeds
    # approx_gemm_batched.
    qg = (q.astype(jnp.float32).reshape(B, S, KV, G, dh)
          .transpose(0, 2, 1, 3, 4).reshape(BH, S, G, dh))
    kt = k.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(BH, T, dh)
    vt = v.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(BH, T, dh)
    # Window compaction: under a causal sliding window with CONTIGUOUS
    # query positions at most window + S - 1 KV positions can ever be
    # live ((min_q - window, max_q]), a *static* budget.  When the cache
    # capacity exceeds it, gather just the live slots (stable slot
    # order, so the FP32 accumulation order — and hence
    # bit-compatibility — is preserved; dead filler slots stay masked by
    # their positions) and run the kernel on the compacted length: every
    # in-kernel cost then scales with ``window``, fully independent of
    # ``Tmax``.  The gather itself is one vectorised XLA take over the
    # cache, not a LUT pass.  Gapped q_pos would make the live set
    # exceed the budget and silently truncate, hence the static
    # ``contiguous_q`` gate (contiguity is a trace-time contract the
    # caller asserts — it cannot be checked on traced positions).
    per_row = q_pos.ndim == 2
    T_budget = _ceil_to(min(window + S, T), bkv) \
        if (causal and window and contiguous_q and not per_row) else T
    if T_budget < T:
        live_slot = (k_pos >= 0) & (k_pos > jnp.min(q_pos) - window) \
            & (k_pos <= jnp.max(q_pos))
        idx = jnp.argsort(jnp.logical_not(live_slot),
                          stable=True)[:T_budget].astype(jnp.int32)
        kt = jnp.take(kt, idx, axis=1)
        vt = jnp.take(vt, idx, axis=1)
        k_pos = jnp.take(k_pos, idx)
        T = T_budget
    Sp = _ceil_to(S, bq)
    Tp = _ceil_to(T, bkv)
    # Kernel layouts: q as (BH, Sp*G, dh) gather rows, K transposed to
    # (BH, dh, Tp) so its KV blocks are lane slices.
    qg = _pad_to(qg, bq, 1, 1).reshape(BH, Sp * G, dh)
    kt = _pad_to(kt, bkv, 1).transpose(0, 2, 1)
    vt = _pad_to(vt, bkv, 1)
    # Padded positions take the "unwritten" sentinel so padded K slots
    # are masked and padded q rows never force a KV block live.
    pad_q = [(0, 0)] * (q_pos.ndim - 1) + [(0, Sp - S)]
    pad_k = [(0, 0)] * (k_pos.ndim - 1) + [(0, Tp - T)]
    qp = jnp.pad(q_pos.astype(jnp.int32), pad_q, constant_values=POS_PAD)
    kp = jnp.pad(k_pos.astype(jnp.int32), pad_k, constant_values=POS_PAD)
    # THE shared mask (kernels/common.attention_mask — one definition
    # for every lowering), AND-ed with the padded-q-row validity term
    # (negative q_pos sentinel) so pad rows can never force a KV block
    # live, together with the per-(q-block, KV-block) liveness flags
    # that let the kernel skip fully-masked blocks.  Shared (1-D)
    # positions give ONE (Sp, Tp) mask reused by every batch*kv-head
    # grid row; per-row (2-D, the paged serving cache) positions give a
    # per-batch mask the grid indexes by ``bh // KV``.  Either way the
    # kernel sees a leading size-1 block axis.
    nq, nkv = Sp // bq, Tp // bkv
    if per_row:
        mask = attention_mask(qp, kp, causal=causal, window=window) \
            & (qp >= 0)[..., :, None]                     # (B, Sp, Tp)
        blk_live = jnp.any(mask.reshape(B, nq, bq, nkv, bkv),
                           axis=(2, 4))                   # (B, nq, nkv)
        mrow = lambda bh: bh // KV                        # noqa: E731
    else:
        mask = (attention_mask(qp, kp, causal=causal, window=window)
                & (qp >= 0)[:, None])[None]               # (1, Sp, Tp)
        blk_live = jnp.any(mask[0].reshape(nq, bq, nkv, bkv),
                           axis=(1, 3))[None]             # (1, nq, nkv)
        mrow = lambda bh: 0                               # noqa: E731
    # Expanded to the kernel's gather rows (each q row G times), as int32.
    mask = jnp.repeat(mask, G, axis=1).astype(jnp.int32)  # (Bm, Sp*G, Tp)
    rows = bq * G
    out = pl.pallas_call(
        functools.partial(_attn_kernel, M=M, bkv=bkv, chunk_d=chunk_d,
                          chunk_t=chunk_t, kv_heads=KV, per_row=per_row),
        grid=(BH, nq),
        in_specs=[
            pl.BlockSpec((1, rows, dh), lambda bh, iq: (bh, iq, 0)),
            # K/V block index is constant along the q-block axis, so the
            # staged copies are reused across every q block of one
            # batch*kv-head; the LUT is broadcast across the whole grid.
            pl.BlockSpec((1, dh, Tp), lambda bh, iq: (bh, 0, 0)),
            pl.BlockSpec((1, Tp, dh), lambda bh, iq: (bh, 0, 0)),
            pl.BlockSpec((1, rows, Tp), lambda bh, iq: (mrow(bh), iq, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            lut_spec(lut),
        ],
        out_specs=pl.BlockSpec((1, rows, dh), lambda bh, iq: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp * G, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, Tp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(qg, kt, vt, mask, blk_live.astype(jnp.int32), lut)
    return (out.reshape(BH, Sp, G, dh)[:, :S].reshape(B, KV, S, G, dh)
            .transpose(0, 2, 1, 3, 4).reshape(B, S, H, dh))


def approx_attention_fused(
    q,
    k,
    v,
    q_pos,
    k_pos,
    lut,
    M: int,
    *,
    causal: bool = True,
    window: int = 0,
    bq: int | None = None,
    bkv: int | None = None,
    chunk: int | None = None,
    contiguous_q: bool = True,
    interpret: bool | None = None,
    mult: str | None = None,
):
    """One-launch LUT-simulated attention.

    q (B, S, H, dh), k/v (B, T, KV, dh) with H = KV * G, q_pos (S,) and
    k_pos (T,) absolute positions (negative k_pos = unwritten ring slot,
    masked) -> (B, S, H, dh), FP32 accumulate.  Positions may instead be
    per-row — q_pos (B, S) and k_pos (B, T), the paged serving cache's
    slot-granular layout where every batch row decodes at its own
    offset — in which case the mask/liveness operands grow a leading
    batch axis and the window-compaction fast path is disabled (there
    is no single shared live set to gather).  Semantics match
    ``ops.attend_einsum``: scores scaled by 1/sqrt(dh), causal /
    sliding-``window`` / position masks, softmax over keys, both
    contractions through the multiplier LUT (canonical uint32 or packed
    uint16, dtype-detected).  Edge case: a query row with NO valid key
    at all returns zeros, where the einsum oracle returns a uniform
    V-average — through models/attention this only happens to queries
    whose own keys were evicted by an over-capacity prefill (S > Tmax),
    which are context-less garbage either way.  ``contiguous_q`` asserts the
    trace-time contract that q_pos is a contiguous run (start +
    arange(S), true for every models/attention call) — it enables the
    window-compaction fast path, whose static live-slot budget
    truncates for gapped positions; pass False for arbitrary q_pos.
    Unset bq/bkv/chunk come from the autotuner's ``attention``
    namespace; ``chunk`` is snapped to the nearest divisor of dh (score
    GEMM) and bkv (value GEMM).
    """
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    assert k.shape == v.shape and k.shape[0] == B, (q.shape, k.shape, v.shape)
    assert H % KV == 0, (H, KV)
    assert q_pos.shape in ((S,), (B, S)) \
        and k_pos.shape == q_pos.shape[:-1] + (T,), \
        (q_pos.shape, k_pos.shape, q.shape, k.shape)
    _TRACES[0] += 1
    interpret = resolve_interpret(interpret)
    lut = kernel_lut(lut, M, interpret)
    if None in (bq, bkv, chunk):
        cfg = autotune.get_attn_config(B * KV, S, T, H // KV, dh, M,
                                       mult=mult)
        # Cache-derived tiles are capped so the attention_fused_supported
        # VMEM bound holds for any tuned entry (explicit arguments are
        # taken as-is, clamped only to the problem dims).
        bq = min(cfg.bq, MAX_BQ) if bq is None else bq
        bkv = min(cfg.bkv, MAX_BKV) if bkv is None else bkv
        chunk = cfg.chunk if chunk is None else chunk
    bq = max(1, min(bq, S))
    bkv = max(1, min(bkv, T))
    return _attn_impl(q, k, v, q_pos, k_pos, lut, M, causal=causal,
                      window=int(window), bq=bq, bkv=bkv,
                      chunk_d=best_chunk(chunk, dh),
                      chunk_t=best_chunk(chunk, bkv),
                      contiguous_q=bool(contiguous_q), interpret=interpret)
