"""VMEM budget model for the persistent decode-chain launches.

The decode chain (kernels/decode_chain.py) keeps its LUT, activations
and accumulators VMEM-resident and streams weights in double-buffered
blocks, so whether a launch is *possible* — and which launch structure
is *profitable* — is a question about the resident working set, not
about flops.  This module is the one place that working set is priced:

  * every estimator returns **bytes** for one launch's resident set
    (scratches + pinned operands + double-buffered streamed blocks +
    the LUT), derived from the SAME autotune folds the kernels slave
    their accumulation order to (``oracle_fold``), so the estimate and
    the kernel can never disagree about padding;
  * ``chain_fits`` / ``moe_chain_fits`` / ``moe_ffn_fits`` are the
    engagement decisions ``ops.decode_chain_enabled`` and the MoE
    expert-bank dispatch consult (``decode_chain_supported`` in
    kernels/decode_chain.py is a thin delegating wrapper kept for
    compatibility);
  * ``fuse_attention_ok`` decides whether the attention core fuses INTO
    the back-half launch — collapsing the three per-layer launches to
    two — which additionally requires the whole padded K/V view of the
    decode batch to sit in VMEM next to the back half's working set
    (and the single-KV-block regime, where the in-kernel attention is
    bitwise against the standalone kernel);
  * ``filter_candidates`` prunes the ``decode_chain`` autotune sweep to
    candidates whose streamed blocks fit, so the tuner never times a
    config the guard would refuse at dispatch time.

Budget constants are conservative: the chip's compiler reports 128 MiB
of VMEM on a TPU v5e core (a kernel asking for 129 MiB of scratch is
refused with "would exceed memory (size=134217728)"; 100 MiB compiles),
far above the 10 MiB budget, which has not been calibrated on the chip.
The margin also covers what the budget does not itemise: the LUT brick's
scoped operand copies (kernels/common.py).  The estimators deliberately
sum both chain launches even though they run sequentially, keeping the
historical guard's conservatism.
"""
from __future__ import annotations

from repro.kernels import autotune
from repro.kernels.common import _ceil128, _ceil_to, best_chunk

VMEM_BUDGET = 10 * 2 ** 20
MAX_ROWS = 512  # decode rows (B*S); beyond this the padded per-op
                # engines are no longer wasteful and fusion buys little


def lut_bytes(M: int) -> int:
    """Canonical (unpacked uint32) LUT footprint — the worst case the
    budget must absorb; the packed uint16 layout halves it."""
    return 4 * (1 << (2 * (M + 1)))


def oracle_fold(rows: int, k: int, n: int, M: int, mult: str | None = None,
                *, kind: str = "gemm2d", batch: int = 0):
    """(bk, chunk, k_padded) of the fold the unfused engine would run
    for an (rows, k) @ (k, n) GEMM — the same autotune lookup + clamp +
    chunk snap as approx_gemm._resolve, so the fused kernels accumulate
    over the identical chunk-brick sequence.  ``kind``/``batch`` select
    the bucket namespace: "gemm2d" for the dense chain, "gemm3d" for
    the stacked expert banks (approx_gemm_batched's bucket)."""
    cfg = autotune.get_block_config(kind, rows, k, n, M, batch=batch,
                                    mult=mult)
    bk = min(cfg.bk, _ceil128(k))
    chunk = best_chunk(cfg.chunk, bk)
    return bk, chunk, _ceil_to(k, bk)


# ---------------------------------------------------------------- dense chain

def qkv_launch_bytes(rows: int, d: int, k_attn: int, M: int,
                     mult: str | None = None,
                     bn: int | None = None) -> int:
    """Launch 1 (rmsnorm + q|k|v column streaming): the (rows, dp)
    normed-activation scratch plus three double-buffered (dp, bn)
    weight column blocks."""
    if bn is None:
        bn = autotune.get_decode_chain_config(rows, d, k_attn, 0, M,
                                              mult=mult).bn
    _, _, dp = oracle_fold(rows, d, k_attn, M, mult)
    return 4 * rows * dp + 2 * 4 * (dp * bn * 3)


def out_mlp_launch_bytes(rows: int, d: int, k_attn: int, d_ff: int, M: int,
                         mult: str | None = None,
                         bf: int | None = None) -> int:
    """Launch 3 (wo -> residual -> rmsnorm -> FFN -> residual): four
    activation scratches plus the double-buffered wo k-block and
    wg/wu/wd d_ff-blocks."""
    if bf is None:
        bf = autotune.get_decode_chain_config(rows, d, k_attn, d_ff, M,
                                              mult=mult).bf
    bk_o, _, _ = oracle_fold(rows, k_attn, d, M, mult)
    _, _, dp2 = oracle_fold(rows, d, d_ff, M, mult)
    scratches = 4 * rows * (dp2 + 3 * d)
    blocks = 2 * 4 * (bk_o * d + 2 * dp2 * bf + bf * d)
    return scratches + blocks


def chain_bytes(rows: int, d: int, k_attn: int, d_ff: int, M: int,
                mult: str | None = None, bn: int | None = None,
                bf: int | None = None) -> int:
    """Both dense-chain launches' resident sets plus the LUT (summed —
    conservative; see module docstring)."""
    return (lut_bytes(M)
            + qkv_launch_bytes(rows, d, k_attn, M, mult, bn=bn)
            + out_mlp_launch_bytes(rows, d, k_attn, d_ff, M, mult, bf=bf))


def chain_fits(rows: int, d: int, k_attn: int, d_ff: int, M: int,
               mult: str | None = None) -> bool:
    """The dense-chain engagement decision (row bound + budget)."""
    if rows < 1 or rows > MAX_ROWS:
        return False
    return chain_bytes(rows, d, k_attn, d_ff, M, mult) <= VMEM_BUDGET


# ------------------------------------------------------------------ MoE chain

def wo_norm_launch_bytes(rows: int, d: int, k_attn: int, M: int,
                         mult: str | None = None) -> int:
    """The MoE back half's launch 3a (wo k-block streaming + residual +
    rmsnorm, emitting x1 and h): one (rows, d) accumulator scratch plus
    the double-buffered wo block."""
    bk_o, _, _ = oracle_fold(rows, k_attn, d, M, mult)
    return 4 * rows * d + 2 * 4 * (bk_o * d)


def moe_chain_fits(rows: int, d: int, k_attn: int, M: int,
                   mult: str | None = None) -> bool:
    """Engagement decision for the MoE decode chain's shared launches
    (qkv front half + wo->norm back half; the expert-bank FFN launch is
    gated separately by ``moe_ffn_fits`` — per-op experts behind a
    fused wo->norm is still a win)."""
    if rows < 1 or rows > MAX_ROWS:
        return False
    total = (lut_bytes(M)
             + qkv_launch_bytes(rows, d, k_attn, M, mult)
             + wo_norm_launch_bytes(rows, d, k_attn, M, mult))
    return total <= VMEM_BUDGET


def moe_ffn_launch_bytes(E: int, C: int, d: int, d_ff: int, M: int,
                         mult: str | None = None,
                         bf: int | None = None) -> int:
    """The stacked expert-bank FFN launch: one expert's padded capacity
    block and accumulator stay resident while wg/wu/wd bank slices
    stream in d_ff blocks (folds from the gemm3d bucket — the bucket
    ``approx_gemm_batched`` would use for the same (E, C, d) problem)."""
    if bf is None:
        bf = autotune.get_decode_chain_config(C, d, d, d_ff, M, mult=mult).bf
    _, _, dgp = oracle_fold(C, d, d_ff, M, mult, kind="gemm3d", batch=E)
    scratches = 4 * C * (dgp + d)           # h block + accumulator
    blocks = 2 * 4 * (2 * dgp * bf + bf * d)
    return scratches + blocks + lut_bytes(M)


def moe_ffn_fits(E: int, C: int, d: int, d_ff: int, M: int,
                 mult: str | None = None) -> bool:
    """Engagement decision for the expert-bank launch.  The capacity C
    plays the row role: a prefill-sized C blows the row bound, which is
    what keeps this a *decode* path without a separate S==1 plumb."""
    if C < 1 or C > MAX_ROWS or E < 1:
        return False
    return moe_ffn_launch_bytes(E, C, d, d_ff, M, mult) <= VMEM_BUDGET


# ------------------------------------------------- attention-into-back-half

def attn_view_bytes(B: int, T: int, KV: int, dh: int, G: int,
                    bkv: int) -> int:
    """Resident bytes the fused-attention phase adds to the back-half
    launch: the whole padded K/V views of the decode batch, the grouped
    q rows, the per-row mask/liveness operands and the attention-output
    scratch."""
    tp = _ceil_to(T, bkv)
    return 4 * (2 * B * KV * tp * dh      # K and V views
                + B * KV * G * dh         # q rows
                + B * KV * G * dh         # attention-output scratch
                + B * tp // 2             # mask (bool, padded estimate)
                + B * G * tp)             # per-cell score row


def fuse_attention_ok(rows: int, d: int, k_attn: int, d_ff: int,
                      B: int, T: int, KV: int, dh: int, M: int,
                      mult: str | None = None) -> bool:
    """Whether the attention core may fuse INTO the back-half launch
    (three launches -> two).  Requires the single-KV-block bitwise
    regime — ``T <= 128`` with ``bkv >= T`` after the standalone
    kernel's clamps, where the in-kernel core, the standalone fused
    kernel, and the einsum oracle all share one fold (so the 2-launch
    form stays bit-identical to every other lowering) — and the
    combined working set under budget."""
    if rows != B or rows < 1 or rows > MAX_ROWS:
        return False
    if KV < 1 or k_attn % KV or dh < 1 or T > 128:
        return False
    G = k_attn // (KV * dh)
    if G < 1 or G * KV * dh != k_attn:
        return False
    cfg = autotune.get_attn_config(B * KV, 1, T, G, dh, M, mult=mult)
    bkv = max(1, min(min(cfg.bkv, 256), T))
    if _ceil_to(T, bkv) != bkv:
        return False  # more than one KV block: keep the standalone core
    total = (chain_bytes(rows, d, k_attn, d_ff, M, mult)
             + attn_view_bytes(B, T, KV, dh, G, bkv))
    return total <= VMEM_BUDGET


# ------------------------------------------------------------------ autotune

def filter_candidates(candidates, rows: int, d: int, k_attn: int,
                      d_ff: int, M: int, mult: str | None = None):
    """Prune a decode_chain candidate sweep to configs whose streamed
    blocks fit the budget at this shape; always returns at least one
    candidate (the smallest-footprint one) so the sweep cannot go
    empty at shapes the dispatch guard would still engage."""
    scored = [(chain_bytes(rows, d, k_attn, d_ff, M, mult,
                           bn=c[0], bf=c[2]), c) for c in candidates]
    kept = [c for bytes_, c in scored if bytes_ <= VMEM_BUDGET]
    if not kept:
        kept = [min(scored, key=lambda sc: sc[0])[1]]
    return kept
