"""Persistent fused decode-chain kernels: whole-layer Pallas launches.

The per-op engines pay one ``pallas_call`` per projection per layer per
decode step, and each launch re-stages the LUT and pads the handful of
decode rows out to a 128-row GEMM tile.  This module fuses the dense
block's per-layer chain

    rmsnorm(n1) -> wq|wk|wv          (launch 1, ``fused_qkv_norm``)
    attention                         (launch 2, kernels/approx_attention)
    wo -> +residual -> rmsnorm(n2)
       -> silu(wg)*wu -> wd -> +res   (launch 3, ``fused_out_mlp``)

into two additional persistent launches (three total per layer instead
of ~8) that keep the packed LUT and every intermediate resident in VMEM:

  * **weight streaming**: weights never sit in VMEM whole.  Each kernel
    walks an "arbitrary" (sequential) grid axis whose block index maps
    stream one (k, bn)/(bk, n) weight block per step from HBM — Pallas's
    automatic grid pipelining double-buffers the next block's HBM->VMEM
    copy under the current block's VPU gathers (the emit_pipeline
    pattern), and clamped index maps pin the small operands (x, norm
    scales, LUT) so they are copied exactly once per launch.
  * **row economy**: the unfused 2-D engine pads m up to a 128-row tile;
    a decode step has B*1 rows, so >90% of its gathers hit padding.
    These kernels keep the true row count end to end.

Bit-exactness contract (the unfused chain is the oracle,
tests/test_decode_chain.py): every sub-GEMM derives its (bk, chunk)
from the SAME autotune bucket the unfused engine would consult and pads
its contraction dim to the same multiple of bk, so the FP32
accumulation is the identical left fold over identical chunk bricks —
fusion boundaries and output-column streaming never regroup a sum.  The
q/k/v projections share the q bucket's fold (their buckets can differ
only under a tuned cache that splits them; the hermetic/default cache
keeps them equal, which is what the bit tests pin).  The in-kernel
rmsnorm/silu/residual ops are the models/layers expressions verbatim,
executed on the same backend.

Dispatch lives in kernels/ops.py (``decode_chain_enabled``, kill switch
``REPRO_DECODE_FUSED=0``); models/transformer.py routes single-token
dense decode blocks here.  Streaming block sizes come from the
``decode_chain`` autotune namespace (kernels/autotune.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune, vmem
from repro.kernels.approx_attention import NEG_INF, POS_PAD
from repro.kernels.common import (_ceil128, _ceil_to,
                                  _gather_gemm_tile, attention_mask,
                                  best_chunk, kernel_lut, lut_spec,
                                  resolve_interpret, rmsnorm_expr,
                                  tagged_pallas_call)
# The fold derivation and the VMEM budget live in kernels/vmem.py (the
# budget model also prices the MoE and attention-fused launch variants);
# re-exported here because this module defined them historically.
from repro.kernels.vmem import oracle_fold  # noqa: F401

# Incremented once per *trace* of each fused-chain wrapper (never per
# step): tests assert engagement and the zero-retrace contract with it.
_TRACES = [0]


def trace_count() -> int:
    return _TRACES[0]


def _snap_stream(want: int, total: int, chunk: int) -> int:
    """Largest divisor of ``total`` that is a multiple of ``chunk`` and
    <= max(want, chunk) — the weight-streaming block size.  ``total`` is
    an oracle-padded contraction extent (a multiple of bk, itself a
    multiple of chunk), so ``total`` is always a valid fallback."""
    best = total
    for cand in range(chunk, total + 1, chunk):
        if total % cand == 0 and cand <= max(want, chunk):
            best = cand
    return best


def _snap_cols(want: int, n: int) -> tuple[int, int]:
    """(bn, padded_n) for output-column streaming: column splits never
    touch the accumulation fold, so bn only needs to tile the padded
    width."""
    bn = max(8, min(want, _ceil128(n)))
    return bn, _ceil_to(n, bn)


# =====================================================================
# Launch 1: rmsnorm(n1) -> q|k|v projections
# =====================================================================

def _qkv_kernel(x_ref, g_ref, wq_ref, wk_ref, wv_ref, lut_ref,
                oq_ref, ok_ref, ov_ref, h_scr, *,
                M: int, eps: float, chunk: int, nq: int, nk: int, nv: int,
                dp: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _norm():
        h = rmsnorm_expr(x_ref[...], g_ref[...], eps)
        # Zero-pad to the oracle's padded contraction extent: the pad
        # chunks contribute the same exact +0.0 terms, in the same fold
        # positions, as the unfused engine's _pad_to.
        h_scr[...] = jnp.zeros_like(h_scr)
        h_scr[:, :h.shape[1]] = h

    h = h_scr[...]
    rows = h.shape[0]

    def proj(w_ref, o_ref):
        o_ref[...] = _gather_gemm_tile(
            h, w_ref[...], lut_ref[...],
            jnp.zeros((rows, w_ref.shape[1]), jnp.float32),
            M=M, chunk=chunk)

    @pl.when(j < nq)
    def _q():
        proj(wq_ref, oq_ref)

    @pl.when((j >= nq) & (j < nq + nk))
    def _k():
        proj(wk_ref, ok_ref)

    @pl.when(j >= nq + nk)
    def _v():
        proj(wv_ref, ov_ref)


@functools.partial(jax.jit, static_argnames=(
    "M", "eps", "bn", "chunk", "dp", "interpret"))
def _fused_qkv_impl(x, g1, wq, wk, wv, lut, M, *, eps, bn, chunk, dp,
                    interpret):
    rows, d = x.shape
    nq, nk, nv = (w.shape[1] // bn for w in (wq, wk, wv))
    cq = lambda j: jnp.clip(j, 0, nq - 1)
    ck = lambda j: jnp.clip(j - nq, 0, nk - 1)
    cv = lambda j: jnp.clip(j - nq - nk, 0, nv - 1)
    outs = tagged_pallas_call(
        "_fused_qkv_impl", ("qkv_norm", "fwd"),
        functools.partial(_qkv_kernel, M=M, eps=eps, chunk=chunk,
                          nq=nq, nk=nk, nv=nv, dp=dp),
        grid=(nq + nk + nv,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda j: (0, 0)),
            pl.BlockSpec((d,), lambda j: (0,)),
            # Streamed column blocks: the clamped maps revisit their last
            # block outside their phase, which Pallas serves from the
            # already-resident copy (no re-fetch).
            pl.BlockSpec((dp, bn), lambda j: (0, cq(j))),
            pl.BlockSpec((dp, bn), lambda j: (0, ck(j))),
            pl.BlockSpec((dp, bn), lambda j: (0, cv(j))),
            lut_spec(lut),
        ],
        out_specs=[
            pl.BlockSpec((rows, bn), lambda j: (0, cq(j))),
            pl.BlockSpec((rows, bn), lambda j: (0, ck(j))),
            pl.BlockSpec((rows, bn), lambda j: (0, cv(j))),
        ],
        out_shape=[jax.ShapeDtypeStruct((rows, w.shape[1]), jnp.float32)
                   for w in (wq, wk, wv)],
        scratch_shapes=[pltpu.VMEM((rows, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, g1, wq, wk, wv, lut)
    return outs


def fused_qkv_norm(x, g1, wq, wk, wv, lut, M: int, *, eps: float,
                   bn: int | None = None, interpret: bool | None = None,
                   mult: str | None = None):
    """rmsnorm(x; g1) then three column-streamed LUT projections in ONE
    launch.  x (rows, d); wq/wk/wv (d, N*); returns (q, k, v) f32.

    The normed activation, accumulators and LUT stay VMEM-resident for
    the whole launch; only weight column blocks stream from HBM.
    """
    rows, d = x.shape
    _TRACES[0] += 1
    interpret = resolve_interpret(interpret)
    if bn is None:
        bn = autotune.get_decode_chain_config(
            rows, d, wq.shape[1], 0, M, mult=mult).bn
    # One fold (the q bucket's) shared by all three projections — see
    # module docstring for the shared-bucket caveat.
    _, chunk, dp = oracle_fold(rows, d, wq.shape[1], M, mult)
    x = x.astype(jnp.float32)
    # A single bn must tile every projection: snap to the smallest.
    bn = min(_snap_cols(bn, w.shape[1])[0] for w in (wq, wk, wv))
    wp = [jnp.pad(w.astype(jnp.float32),
                  ((0, dp - d), (0, _ceil_to(w.shape[1], bn) - w.shape[1])))
          for w in (wq, wk, wv)]
    q, k, v = _fused_qkv_impl(x, g1.astype(jnp.float32), *wp,
                              kernel_lut(lut, M, interpret), M,
                              eps=float(eps), bn=bn, chunk=chunk, dp=dp,
                              interpret=interpret)
    return q[:, :wq.shape[1]], k[:, :wk.shape[1]], v[:, :wv.shape[1]]


# =====================================================================
# Launch 3: wo -> +residual -> rmsnorm(n2) -> silu(wg)*wu -> wd -> +res
# =====================================================================

def _out_mlp_kernel(*refs, M: int, eps: float, n_wo: int, n_ff: int,
                    chunk_o: int, chunk_g: int, chunk_d: int,
                    d: int, dp2: int, has_bo: bool, has_bd: bool):
    # Epilogue biases (wo / wd) are *statically* optional operands: a
    # bias-free call must not add an unconditional +0.0 (it would flip
    # the sign of exact -0.0 sums and break the bitwise contract), so
    # the ref list itself changes shape with has_bo/has_bd.
    it = iter(refs)
    xres_ref, attn_ref, g_ref = next(it), next(it), next(it)
    wo_ref, wg_ref, wu_ref, wd_ref = next(it), next(it), next(it), next(it)
    bo_ref = next(it) if has_bo else None
    bd_ref = next(it) if has_bd else None
    lut_ref, o_ref = next(it), next(it)
    y_scr, x1_scr, h_scr, acc_scr = it
    t = pl.program_id(0)
    rows = xres_ref.shape[0]
    lut = lut_ref[...]

    @pl.when(t == 0)
    def _init():
        y_scr[...] = jnp.zeros_like(y_scr)

    # -- phase A: stream wo k-blocks, accumulate y = attn @ wo ----------
    @pl.when(t < n_wo)
    def _wo():
        y_scr[...] = _gather_gemm_tile(
            attn_ref[...], wo_ref[...], lut, y_scr[...],
            M=M, chunk=chunk_o)

    # -- phase boundary: residual + rmsnorm(n2), all in VMEM ------------
    @pl.when(t == n_wo - 1)
    def _norm():
        y = y_scr[...]
        if has_bo:
            # models/layers.linear adds the bias BEFORE the residual:
            # x1 = x + ((attn @ wo) + bo) — same association here.
            y = y + bo_ref[...]
        x1 = xres_ref[...] + y
        x1_scr[...] = x1
        h = rmsnorm_expr(x1, g_ref[...], eps)
        h_scr[...] = jnp.zeros_like(h_scr)
        h_scr[:, :d] = h
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # -- phase B: stream wg/wu/wd d_ff-blocks, accumulate the FFN -------
    @pl.when(t >= n_wo)
    def _ffn():
        h = h_scr[...]
        bf = wg_ref.shape[1]
        zero = jnp.zeros((rows, bf), jnp.float32)
        g = _gather_gemm_tile(h, wg_ref[...], lut, zero,
                              M=M, chunk=chunk_g)
        u = _gather_gemm_tile(h, wu_ref[...], lut, zero,
                              M=M, chunk=chunk_g)
        a = jax.nn.silu(g) * u
        acc_scr[...] = _gather_gemm_tile(
            a, wd_ref[...], lut, acc_scr[...],
            M=M, chunk=chunk_d)

    @pl.when(t == n_wo + n_ff - 1)
    def _flush():
        y2 = acc_scr[...]
        if has_bd:
            y2 = y2 + bd_ref[...]
        o_ref[...] = x1_scr[...] + y2


@functools.partial(jax.jit, static_argnames=(
    "M", "eps", "bko", "bf", "chunk_o", "chunk_g", "chunk_d", "dp2",
    "has_bo", "has_bd", "interpret"))
def _fused_out_mlp_impl(xres, attn, g2, wo, wg, wu, wd, biases, lut, M, *,
                        eps, bko, bf, chunk_o, chunk_g, chunk_d, dp2,
                        has_bo, has_bd, interpret):
    rows, d = xres.shape
    kp = attn.shape[1]
    n_wo = kp // bko
    n_ff = wg.shape[1] // bf
    co = lambda t: jnp.clip(t, 0, n_wo - 1)
    cf = lambda t: jnp.clip(t - n_wo, 0, n_ff - 1)
    bias_specs = [pl.BlockSpec((d,), lambda t: (0,)) for _ in biases]
    out = tagged_pallas_call(
        "_fused_out_mlp_impl", ("out_mlp", "fwd"),
        functools.partial(_out_mlp_kernel, M=M, eps=eps, n_wo=n_wo,
                          n_ff=n_ff, chunk_o=chunk_o, chunk_g=chunk_g,
                          chunk_d=chunk_d, d=d, dp2=dp2, has_bo=has_bo,
                          has_bd=has_bd),
        grid=(n_wo + n_ff,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda t: (0, 0)),
            pl.BlockSpec((rows, bko), lambda t: (0, co(t))),
            pl.BlockSpec((d,), lambda t: (0,)),
            pl.BlockSpec((bko, d), lambda t: (co(t), 0)),
            pl.BlockSpec((dp2, bf), lambda t: (0, cf(t))),
            pl.BlockSpec((dp2, bf), lambda t: (0, cf(t))),
            pl.BlockSpec((bf, d), lambda t: (cf(t), 0)),
            *bias_specs,
            lut_spec(lut),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                        pltpu.VMEM((rows, d), jnp.float32),
                        pltpu.VMEM((rows, dp2), jnp.float32),
                        pltpu.VMEM((rows, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xres, attn, g2, wo, wg, wu, wd, *biases, lut)
    return out


def fused_out_mlp(xres, attn, g2, wo, wg, wu, wd, lut, M: int, *,
                  eps: float, bo=None, bd=None,
                  bko: int | None = None, bf: int | None = None,
                  interpret: bool | None = None, mult: str | None = None):
    """The back half of a dense decode block in ONE launch:

        x1 = xres + (attn @ wo [+ bo]);  h = rmsnorm(x1; g2)
        out = x1 + ((silu(h @ wg) * (h @ wu)) @ wd [+ bd])

    xres (rows, d) residual stream, attn (rows, H*dh) attention output.
    x1/h and both accumulators live in VMEM for the whole launch; wo
    streams over its k blocks, wg/wu/wd over d_ff blocks.  ``bo``/``bd``
    are the optional wo/wd epilogue biases ((d,) each), folded into the
    phase-boundary / flush epilogues with the per-op add association
    (bias before residual) — statically absent operands when None, so
    bias-free calls stay bit-identical to the historical kernel.
    """
    rows, d = xres.shape
    K = attn.shape[1]
    F = wg.shape[1]
    _TRACES[0] += 1
    interpret = resolve_interpret(interpret)
    dc = autotune.get_decode_chain_config(rows, d, K, F, M, mult=mult)
    bko = dc.bko if bko is None else bko
    bf = dc.bf if bf is None else bf
    # Oracle folds (unfused engine buckets): wo (k=K,n=d), gate/up
    # (k=d,n=F), down (k=F,n=d).
    bk_o, chunk_o, kp = oracle_fold(rows, K, d, M, mult)
    bk_g, chunk_g, dp2 = oracle_fold(rows, d, F, M, mult)
    bk_d, chunk_d, fp = oracle_fold(rows, F, d, M, mult)
    bko = _snap_stream(bko, kp, chunk_o)
    # bf splits wg/wu's OUTPUT dim but wd's contraction dim: only the wd
    # fold constrains it, so snap to chunk_d multiples.
    bf = _snap_stream(bf, fp, chunk_d)
    f32 = jnp.float32
    attn = jnp.pad(attn.astype(f32), ((0, 0), (0, kp - K)))
    wo = jnp.pad(wo.astype(f32), ((0, kp - K), (0, 0)))
    wg = jnp.pad(wg.astype(f32), ((0, dp2 - d), (0, fp - F)))
    wu = jnp.pad(wu.astype(f32), ((0, dp2 - d), (0, fp - F)))
    wd = jnp.pad(wd.astype(f32), ((0, fp - F), (0, 0)))
    biases = tuple(b.astype(f32) for b in (bo, bd) if b is not None)
    return _fused_out_mlp_impl(
        xres.astype(f32), attn, g2.astype(f32), wo, wg, wu, wd, biases,
        kernel_lut(lut, M, interpret), M, eps=float(eps), bko=bko, bf=bf,
        chunk_o=chunk_o, chunk_g=chunk_g, chunk_d=chunk_d, dp2=dp2,
        has_bo=bo is not None, has_bd=bd is not None, interpret=interpret)


# =====================================================================
# Launches 2+3 collapsed: the attention core fused INTO the back half
# (three per-layer launches -> two) when the K/V views of the decode
# batch fit next to the back half's working set (vmem.fuse_attention_ok).
# =====================================================================

def _attn_out_mlp_kernel(*refs, M: int, eps: float, n_wo: int, n_ff: int,
                         chunk_qk: int, chunk_t: int, chunk_o: int,
                         chunk_g: int, chunk_d: int, d: int, dp2: int,
                         has_bo: bool, has_bd: bool):
    """fused_out_mlp's phases prefixed by an in-kernel attention core.

    At t == 0 (program order runs before phase A's first wo block) the
    kernel replays approx_attention._attn_kernel's op sequence — score
    gather-GEMM, 1/sqrt(dh) scale, mask, row softmax, value gather-GEMM
    — one (batch, kv-head) cell at a time into the ``attn_scr`` VMEM
    scratch, which phase A then slices where the 3-launch form streamed
    the HBM attention output.  The single-KV-block regime the dispatch
    guard enforces (Tp == bkv, T <= 128) makes each cell one score tile
    and one value tile, so the fold is bit-identical to the standalone
    kernel AND to the einsum oracle.
    """
    it = iter(refs)
    xres_ref, qg_ref, kt_ref, vt_ref = next(it), next(it), next(it), next(it)
    mask_ref, live_ref, g_ref = next(it), next(it), next(it)
    wo_ref, wg_ref, wu_ref, wd_ref = next(it), next(it), next(it), next(it)
    bo_ref = next(it) if has_bo else None
    bd_ref = next(it) if has_bd else None
    lut_ref, o_ref = next(it), next(it)
    attn_scr, y_scr, x1_scr, h_scr, acc_scr = it
    t = pl.program_id(0)
    rows = xres_ref.shape[0]
    B, KV, G, dh = qg_ref.shape
    Tp = kt_ref.shape[3]
    Bm = mask_ref.shape[0]
    bko = wo_ref.shape[0]
    lut = lut_ref[...]

    @pl.when(t == 0)
    def _attn():
        # Zero fills double as the oracle's kp zero-padding of the
        # attention output (exact +0.0 fold terms in phase A).
        attn_scr[...] = jnp.zeros_like(attn_scr)
        y_scr[...] = jnp.zeros_like(y_scr)
        def cell(c, carry):
            b, kv = c // KV, c % KV
            mrow = b if Bm > 1 else 0
            lv = live_ref[mrow, 0] != 0
            qc = qg_ref[b, kv]                                  # (G, dh)

            def live_tile():
                s = _gather_gemm_tile(
                    qc, kt_ref[b, kv], lut, jnp.zeros((G, Tp), jnp.float32),
                    M=M, chunk=chunk_qk)
                s = s / jnp.sqrt(float(dh))
                mc = mask_ref[pl.ds(mrow, 1), :] != 0
                return jnp.where(jnp.broadcast_to(mc, (G, Tp)), s, NEG_INF)

            s = jax.lax.cond(
                lv, live_tile,
                lambda: jnp.full((G, Tp), NEG_INF, jnp.float32))
            m = jnp.max(s, axis=-1, keepdims=True)
            unnorm = jnp.exp(s - m)
            probs = unnorm / jnp.sum(unnorm, axis=-1, keepdims=True)
            acc = jax.lax.cond(
                lv,
                lambda: _gather_gemm_tile(
                    probs, vt_ref[b, kv], lut,
                    jnp.zeros((G, dh), jnp.float32), M=M, chunk=chunk_t),
                lambda: jnp.zeros((G, dh), jnp.float32))
            # Head-major row of this (batch, kv-head): G*dh lanes at a
            # G*dh-aligned offset.
            row = jnp.concatenate([acc[g:g + 1] for g in range(G)], axis=1)
            attn_scr[pl.ds(b, 1),
                     pl.ds(pl.multiple_of(kv * (G * dh), G * dh), G * dh)] = row
            return carry

        jax.lax.fori_loop(0, B * KV, cell, 0)

    # -- phases A/B + boundary + flush: _out_mlp_kernel verbatim, with
    # phase A reading attn blocks from the scratch instead of a stream.
    @pl.when(t < n_wo)
    def _wo():
        col = jnp.minimum(t, n_wo - 1) * bko
        ab = attn_scr[:, pl.ds(pl.multiple_of(col, bko), bko)]
        y_scr[...] = _gather_gemm_tile(
            ab, wo_ref[...], lut, y_scr[...],
            M=M, chunk=chunk_o)

    @pl.when(t == n_wo - 1)
    def _norm():
        y = y_scr[...]
        if has_bo:
            y = y + bo_ref[...]
        x1 = xres_ref[...] + y
        x1_scr[...] = x1
        h = rmsnorm_expr(x1, g_ref[...], eps)
        h_scr[...] = jnp.zeros_like(h_scr)
        h_scr[:, :d] = h
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(t >= n_wo)
    def _ffn():
        h = h_scr[...]
        bf = wg_ref.shape[1]
        zero = jnp.zeros((rows, bf), jnp.float32)
        g = _gather_gemm_tile(h, wg_ref[...], lut, zero,
                              M=M, chunk=chunk_g)
        u = _gather_gemm_tile(h, wu_ref[...], lut, zero,
                              M=M, chunk=chunk_g)
        a = jax.nn.silu(g) * u
        acc_scr[...] = _gather_gemm_tile(
            a, wd_ref[...], lut, acc_scr[...],
            M=M, chunk=chunk_d)

    @pl.when(t == n_wo + n_ff - 1)
    def _flush():
        y2 = acc_scr[...]
        if has_bd:
            y2 = y2 + bd_ref[...]
        o_ref[...] = x1_scr[...] + y2


@functools.partial(jax.jit, static_argnames=(
    "M", "eps", "bko", "bf", "chunk_o", "chunk_g", "chunk_d", "chunk_qk",
    "chunk_t", "dp2", "kp", "has_bo", "has_bd", "interpret"))
def _fused_attn_out_mlp_impl(xres, qg, kt, vt, mask, live, g2, wo, wg, wu,
                             wd, biases, lut, M, *, eps, bko, bf, chunk_o,
                             chunk_g, chunk_d, chunk_qk, chunk_t, dp2, kp,
                             has_bo, has_bd, interpret):
    rows, d = xres.shape
    B, KV, G, dh = qg.shape
    Tp = kt.shape[3]
    Bm = mask.shape[0]
    n_wo = kp // bko
    n_ff = wg.shape[1] // bf
    co = lambda t: jnp.clip(t, 0, n_wo - 1)
    cf = lambda t: jnp.clip(t - n_wo, 0, n_ff - 1)
    bias_specs = [pl.BlockSpec((d,), lambda t: (0,)) for _ in biases]
    out = tagged_pallas_call(
        "_fused_attn_out_mlp_impl", ("attn_out_mlp", "fwd"),
        functools.partial(_attn_out_mlp_kernel, M=M, eps=eps, n_wo=n_wo,
                          n_ff=n_ff, chunk_qk=chunk_qk, chunk_t=chunk_t,
                          chunk_o=chunk_o, chunk_g=chunk_g, chunk_d=chunk_d,
                          d=d, dp2=dp2, has_bo=has_bo, has_bd=has_bd),
        grid=(n_wo + n_ff,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda t: (0, 0)),
            # q and the whole padded K/V views are pinned for the launch
            # (priced by vmem.attn_view_bytes); only wo/wg/wu/wd stream.
            pl.BlockSpec((B, KV, G, dh), lambda t: (0, 0, 0, 0)),
            pl.BlockSpec((B, KV, dh, Tp), lambda t: (0, 0, 0, 0)),
            pl.BlockSpec((B, KV, Tp, dh), lambda t: (0, 0, 0, 0)),
            pl.BlockSpec((Bm, Tp), lambda t: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((d,), lambda t: (0,)),
            pl.BlockSpec((bko, d), lambda t: (co(t), 0)),
            pl.BlockSpec((dp2, bf), lambda t: (0, cf(t))),
            pl.BlockSpec((dp2, bf), lambda t: (0, cf(t))),
            pl.BlockSpec((bf, d), lambda t: (cf(t), 0)),
            *bias_specs,
            lut_spec(lut),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, kp), jnp.float32),
                        pltpu.VMEM((rows, d), jnp.float32),
                        pltpu.VMEM((rows, d), jnp.float32),
                        pltpu.VMEM((rows, dp2), jnp.float32),
                        pltpu.VMEM((rows, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xres, qg, kt, vt, mask, live, g2, wo, wg, wu, wd, *biases, lut)
    return out


def fused_attn_out_mlp(xres, q, k, v, q_pos, k_pos, g2, wo, wg, wu, wd,
                       lut, M: int, *, eps: float, causal: bool = True,
                       window: int = 0, bo=None, bd=None,
                       bko: int | None = None, bf: int | None = None,
                       interpret: bool | None = None,
                       mult: str | None = None):
    """Attention core + the whole dense back half in ONE launch:

        attn = softmax(mask(q @ k.T / sqrt(dh))) @ v      (through the LUT)
        x1   = xres + (attn @ wo [+ bo]);  h = rmsnorm(x1; g2)
        out  = x1 + ((silu(h @ wg) * (h @ wu)) @ wd [+ bd])

    q (B, 1, H, dh) RoPE'd decode queries; k/v (B, T, KV, dh) the
    post-update cache views; positions shared (1,)/(T,) or per-row
    (B, 1)/(B, T) exactly as ``approx_attention_fused``.  Callers gate on
    ``vmem.fuse_attention_ok`` — the kernel asserts its single-KV-block
    regime (Tp == bkv), where the in-kernel core is bit-identical to the
    standalone fused kernel and the einsum oracle, so this 2-launch form
    is bitwise against the 3-launch chain and the per-op path alike.
    The attention tiling derives from the SAME autotune namespace as the
    standalone wrapper; the back-half folds from ``fused_out_mlp``'s.
    """
    rows, d = xres.shape
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    K = H * dh
    F = wg.shape[1]
    assert S == 1 and rows == B, (q.shape, xres.shape)
    assert k.shape == v.shape and k.shape[0] == B, (q.shape, k.shape)
    _TRACES[0] += 1
    interpret = resolve_interpret(interpret)
    # Attention tiling: the standalone wrapper's derivation verbatim.
    acfg = autotune.get_attn_config(B * KV, S, T, G, dh, M, mult=mult)
    bkv = max(1, min(min(acfg.bkv, 256), T))
    Tp = _ceil_to(T, bkv)
    assert Tp == bkv, ("fuse_attention_ok must gate single-KV-block "
                       "shapes", T, bkv)
    chunk_qk = best_chunk(acfg.chunk, dh)
    chunk_t = best_chunk(acfg.chunk, bkv)
    f32 = jnp.float32
    qg = q.astype(f32).reshape(B, KV, G, dh)
    # K transposed, (B, KV, dh, Tp): the score GEMM's B operand.
    kt = jnp.pad(k.astype(f32).transpose(0, 2, 3, 1),
                 ((0, 0), (0, 0), (0, 0), (0, Tp - T)))
    vt = jnp.pad(v.astype(f32).transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    # Mask/liveness: _attn_impl's construction at S == 1 (Sp == bq == 1
    # squeezed away); per-row (2-D) positions give a per-batch mask row,
    # shared (1-D) positions one row broadcast by the kernel.
    qp = q_pos.astype(jnp.int32)
    kpos = jnp.pad(k_pos.astype(jnp.int32),
                   [(0, 0)] * (k_pos.ndim - 1) + [(0, Tp - T)],
                   constant_values=POS_PAD)
    if qp.ndim == 2:
        mask = (attention_mask(qp, kpos, causal=causal, window=int(window))
                & (qp >= 0)[..., :, None])[:, 0, :]         # (B, Tp)
    else:
        mask = (attention_mask(qp, kpos, causal=causal, window=int(window))
                & (qp >= 0)[:, None])                       # (1, Tp)
    live = jnp.any(mask, axis=-1, keepdims=True)            # (Bm, 1)
    mask, live = mask.astype(jnp.int32), live.astype(jnp.int32)
    # Back-half folds: fused_out_mlp's derivation verbatim.
    dc = autotune.get_decode_chain_config(rows, d, K, F, M, mult=mult)
    bko = dc.bko if bko is None else bko
    bf = dc.bf if bf is None else bf
    bk_o, chunk_o, kp = oracle_fold(rows, K, d, M, mult)
    bk_g, chunk_g, dp2 = oracle_fold(rows, d, F, M, mult)
    bk_d, chunk_d, fp = oracle_fold(rows, F, d, M, mult)
    bko = _snap_stream(bko, kp, chunk_o)
    bf = _snap_stream(bf, fp, chunk_d)
    wo = jnp.pad(wo.astype(f32), ((0, kp - K), (0, 0)))
    wg = jnp.pad(wg.astype(f32), ((0, dp2 - d), (0, fp - F)))
    wu = jnp.pad(wu.astype(f32), ((0, dp2 - d), (0, fp - F)))
    wd = jnp.pad(wd.astype(f32), ((0, fp - F), (0, 0)))
    biases = tuple(b.astype(f32) for b in (bo, bd) if b is not None)
    lut = kernel_lut(lut, M, interpret)
    return _fused_attn_out_mlp_impl(
        xres.astype(f32), qg, kt, vt, mask, live, g2.astype(f32),
        wo, wg, wu, wd, biases, lut, M, eps=float(eps), bko=bko, bf=bf,
        chunk_o=chunk_o, chunk_g=chunk_g, chunk_d=chunk_d,
        chunk_qk=chunk_qk, chunk_t=chunk_t, dp2=dp2, kp=kp,
        has_bo=bo is not None, has_bd=bd is not None, interpret=interpret)


# =====================================================================
# MoE back half: launch 3a (wo -> residual -> rmsnorm) emits x1 and h;
# the router/scatter stay per-op; launch 3b runs the stacked expert
# banks with streamed bank slices.
# =====================================================================

def _wo_norm_kernel(*refs, M: int, eps: float, n_wo: int, chunk_o: int,
                    has_bo: bool):
    it = iter(refs)
    xres_ref, attn_ref, g_ref, wo_ref = next(it), next(it), next(it), next(it)
    bo_ref = next(it) if has_bo else None
    lut_ref, x1_ref, h_ref = next(it), next(it), next(it)
    (y_scr,) = it
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        y_scr[...] = jnp.zeros_like(y_scr)

    y_scr[...] = _gather_gemm_tile(
        attn_ref[...], wo_ref[...], lut_ref[...], y_scr[...],
        M=M, chunk=chunk_o)

    @pl.when(t == n_wo - 1)
    def _norm():
        y = y_scr[...]
        if has_bo:
            y = y + bo_ref[...]
        x1 = xres_ref[...] + y
        x1_ref[...] = x1
        h_ref[...] = rmsnorm_expr(x1, g_ref[...], eps)


@functools.partial(jax.jit, static_argnames=(
    "M", "eps", "bko", "chunk_o", "has_bo", "interpret"))
def _fused_wo_norm_impl(xres, attn, g2, wo, biases, lut, M, *, eps, bko,
                        chunk_o, has_bo, interpret):
    rows, d = xres.shape
    n_wo = attn.shape[1] // bko
    co = lambda t: jnp.clip(t, 0, n_wo - 1)
    bias_specs = [pl.BlockSpec((d,), lambda t: (0,)) for _ in biases]
    x1, h = tagged_pallas_call(
        "_fused_wo_norm_impl", ("wo_norm", "fwd"),
        functools.partial(_wo_norm_kernel, M=M, eps=eps, n_wo=n_wo,
                          chunk_o=chunk_o, has_bo=has_bo),
        grid=(n_wo,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda t: (0, 0)),
            pl.BlockSpec((rows, bko), lambda t: (0, co(t))),
            pl.BlockSpec((d,), lambda t: (0,)),
            pl.BlockSpec((bko, d), lambda t: (co(t), 0)),
            *bias_specs,
            lut_spec(lut),
        ],
        out_specs=[pl.BlockSpec((rows, d), lambda t: (0, 0)),
                   pl.BlockSpec((rows, d), lambda t: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, d), jnp.float32),
                   jax.ShapeDtypeStruct((rows, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xres, attn, g2, wo, *biases, lut)
    return x1, h


def fused_wo_norm(xres, attn, g2, wo, lut, M: int, *, eps: float, bo=None,
                  bko: int | None = None, interpret: bool | None = None,
                  mult: str | None = None):
    """The MoE back half's shared prefix in ONE launch:

        x1 = xres + (attn @ wo [+ bo]);  h = rmsnorm(x1; g2)

    Identical fold and epilogue to ``fused_out_mlp``'s phase A + phase
    boundary (same oracle bucket), but x1 and h are *emitted* instead of
    consumed: the router/top-k/scatter stay per-op on h (exact per
    PolicyTable — routing is control flow, not a chain GEMM) and the
    expert FFN runs in the stacked-bank launch (``fused_moe_ffn``).
    """
    rows, d = xres.shape
    K = attn.shape[1]
    _TRACES[0] += 1
    interpret = resolve_interpret(interpret)
    if bko is None:
        bko = autotune.get_decode_chain_config(rows, d, K, 0, M,
                                               mult=mult).bko
    bk_o, chunk_o, kp = oracle_fold(rows, K, d, M, mult)
    bko = _snap_stream(bko, kp, chunk_o)
    f32 = jnp.float32
    attn = jnp.pad(attn.astype(f32), ((0, 0), (0, kp - K)))
    wo = jnp.pad(wo.astype(f32), ((0, kp - K), (0, 0)))
    biases = tuple(b.astype(f32) for b in (bo,) if b is not None)
    return _fused_wo_norm_impl(
        xres.astype(f32), attn, g2.astype(f32), wo, biases,
        kernel_lut(lut, M, interpret), M, eps=float(eps), bko=bko,
        chunk_o=chunk_o, has_bo=bo is not None, interpret=interpret)


def _moe_ffn_kernel(h_ref, wg_ref, wu_ref, wd_ref, lut_ref, o_ref, acc_scr,
                    *, M: int, n_ff: int, chunk_g: int, chunk_d: int):
    f = pl.program_id(1)
    lut = lut_ref[...]
    h = h_ref[0]
    rows = h.shape[0]
    bf = wg_ref.shape[2]

    @pl.when(f == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    zero = jnp.zeros((rows, bf), jnp.float32)
    g = _gather_gemm_tile(h, wg_ref[0], lut, zero,
                          M=M, chunk=chunk_g)
    u = _gather_gemm_tile(h, wu_ref[0], lut, zero,
                          M=M, chunk=chunk_g)
    a = jax.nn.silu(g) * u
    acc_scr[...] = _gather_gemm_tile(
        a, wd_ref[0], lut, acc_scr[...], M=M, chunk=chunk_d)

    @pl.when(f == n_ff - 1)
    def _flush():
        o_ref[0] = acc_scr[...]


@functools.partial(jax.jit, static_argnames=(
    "M", "bf", "chunk_g", "chunk_d", "interpret"))
def _fused_moe_ffn_impl(h, wg, wu, wd, lut, M, *, bf, chunk_g, chunk_d,
                        interpret):
    E, C, dgp = h.shape
    d = wd.shape[2]
    n_ff = wg.shape[2] // bf
    out = tagged_pallas_call(
        "_fused_moe_ffn_impl", ("moe_ffn", "fwd"),
        functools.partial(_moe_ffn_kernel, M=M, n_ff=n_ff, chunk_g=chunk_g,
                          chunk_d=chunk_d),
        grid=(E, n_ff),
        in_specs=[
            # One expert's capacity block is resident per outer grid
            # step; its wg/wu/wd bank slices stream along the inner axis
            # (Pallas double-buffers the next slice's HBM->VMEM copy).
            pl.BlockSpec((1, C, dgp), lambda e, f: (e, 0, 0)),
            pl.BlockSpec((1, dgp, bf), lambda e, f: (e, 0, f)),
            pl.BlockSpec((1, dgp, bf), lambda e, f: (e, 0, f)),
            pl.BlockSpec((1, bf, d), lambda e, f: (e, f, 0)),
            lut_spec(lut),
        ],
        out_specs=pl.BlockSpec((1, C, d), lambda e, f: (e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, d), jnp.float32)],
        # Both axes sequential: the accumulator scratch is re-zeroed at
        # each expert's first slice, which requires the row-major
        # (expert-outer) iteration order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(h, wg, wu, wd, lut)
    return out


def fused_moe_ffn(h, wg, wu, wd, lut, M: int, *, bf: int | None = None,
                  interpret: bool | None = None, mult: str | None = None):
    """Stacked expert-bank swiglu FFN in ONE launch: h (E, C, d) is the
    scattered capacity buffer (models/moe.py), wg/wu (E, d, F) and
    wd (E, F, d) the expert banks; returns (E, C, d).

    Bit-exactness: the folds are slaved to the **gemm3d** buckets the
    unfused path's ``approx_gemm_batched`` would use for the identical
    (E, C, d)-batched problems, so each expert's accumulation is the
    same left fold over the same chunk bricks; the bank-slice streaming
    splits wg/wu's output columns and re-slices wd's fixed fold, never
    regrouping a sum.
    """
    E, C, d = h.shape
    F = wg.shape[2]
    _TRACES[0] += 1
    interpret = resolve_interpret(interpret)
    if bf is None:
        bf = autotune.get_decode_chain_config(C, d, d, F, M, mult=mult).bf
    bk_g, chunk_g, dgp = oracle_fold(C, d, F, M, mult,
                                     kind="gemm3d", batch=E)
    bk_d, chunk_d, fp = oracle_fold(C, F, d, M, mult,
                                    kind="gemm3d", batch=E)
    bf = _snap_stream(bf, fp, chunk_d)
    f32 = jnp.float32
    h = jnp.pad(h.astype(f32), ((0, 0), (0, 0), (0, dgp - d)))
    wg = jnp.pad(wg.astype(f32), ((0, 0), (0, dgp - d), (0, fp - F)))
    wu = jnp.pad(wu.astype(f32), ((0, 0), (0, dgp - d), (0, fp - F)))
    wd = jnp.pad(wd.astype(f32), ((0, 0), (0, fp - F), (0, 0)))
    return _fused_moe_ffn_impl(h, wg, wu, wd, kernel_lut(lut, M, interpret),
                               M, bf=bf, chunk_g=chunk_g, chunk_d=chunk_d,
                               interpret=interpret)


# =====================================================================
# Guards
# =====================================================================

def decode_chain_supported(rows: int, d: int, k_attn: int, d_ff: int,
                           M: int, mult: str | None = None) -> bool:
    """Shape/VMEM guard for the two chain launches — a thin wrapper
    around the budget model (kernels/vmem.py), kept under its
    historical name for dispatch-seam compatibility."""
    return vmem.chain_fits(rows, d, k_attn, d_ff, M, mult)
