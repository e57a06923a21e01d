"""Block-size autotuner for the approximate-GEMM and conv kernels.

The paper's CUDA GEMM hard-codes 16x16 shared-memory tiles; on TPU (and in
interpret mode on CPU) the right (bm, bn, bk, chunk) depends on the shape,
the LUT size (M) and the backend.  This module sweeps a candidate list with
the real kernel and caches the winner in a JSON file on disk, keyed by

    <backend>|<kind>|<shape bucket>|M<M>[-<multiplier>]

where *kind* is ``gemm2d`` / ``gemm3d`` / ``conv2d`` / ``attention``.
The optional ``-<multiplier>`` suffix is the *resolved* multiplier name
(e.g. ``mitchell8``): heterogeneous policy tables can assign different
multipliers with the same M to different sites, and a per-multiplier
entry keeps their tuned tilings from colliding.  Lookups fall back to
the bare ``M<M>`` key, so multiplier-agnostic sweeps stay valid.
The GEMM bucket rounds every dimension up to a power of two (so one
sweep covers a family of nearby shapes); the conv bucket keeps
H/W/KHxKW/stride/padding exact (they fix the in-kernel slicing
structure) and pow2-buckets N/C/O; the attention bucket pow2-buckets
B*KV/S/T and keeps G/head_dim exact.  ``approx_gemm`` /
``approx_gemm_batched`` / ``approx_conv2d_fused`` /
``approx_attention_fused`` consult the cache at trace time via
:func:`get_block_config` / :func:`get_conv_config` /
:func:`get_attn_config`; a miss falls back to safe defaults (for the
2-D GEMM, the shape rule :func:`tile_2d` with the fold ``FOLD_2D``) — tuning
itself only runs when :func:`autotune` / :func:`autotune_conv` /
:func:`autotune_attention` is called explicitly
(``benchmarks/bench_batched_gemm.py --autotune``,
``benchmarks/bench_conv2d.py --autotune``,
``benchmarks/bench_attention.py --autotune``).

Cache file schema (``REPRO_AUTOTUNE_CACHE``, default
``<repo>/.cache/autotune/gemm_blocks.json`` — every REPRO_* knob is
catalogued in docs/configuration.md)::

    {
      "version": 1,
      "entries": {
        "cpu|gemm3d|b8_m256_k256_n256|M7": {
          "bm": 128, "bn": 128, "bk": 256, "chunk": 64, "us": 1234.5
        },
        "cpu|conv2d|n8_h32_w32_c64_k3x3_o64_s1_pSAME|M7": {
          "br": 8, "bo": 64, "chunk": 64, "dw_chunk": 128, "us": 9876.5
        }
      }
    }

A corrupt or unreadable file is treated as empty (and overwritten on the
next tune) — never an error.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.checkout import CACHE
from repro.kernels.common import _ceil_to

SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """One pallas_call tiling: operand tiles (bm, bk) x (bk, bn), gather
    bricks of `chunk` contraction steps."""

    bm: int = 128
    bn: int = 128
    bk: int = 128
    chunk: int = 8

    def astuple(self):
        return (self.bm, self.bn, self.bk, self.chunk)


@dataclasses.dataclass(frozen=True)
class ConvBlockConfig:
    """One fused-conv tiling: ``br`` output rows x ``bo`` out-channels
    per grid point, ``chunk`` input-channel gather brick (forward) and
    ``dw_chunk`` patch-axis gather brick (weight gradient)."""

    br: int = 8
    bo: int = 128
    chunk: int = 64
    dw_chunk: int = 128

    def astuple(self):
        return (self.br, self.bo, self.chunk, self.dw_chunk)


@dataclasses.dataclass(frozen=True)
class AttnBlockConfig:
    """One fused-attention tiling: ``bq`` query positions per grid cell
    (x G group-heads = gather rows), ``bkv`` KV positions per in-kernel
    streaming step, ``chunk`` gather brick (snapped to a divisor of dh
    for the score GEMM and of bkv for the value GEMM)."""

    bq: int = 128
    bkv: int = 128
    chunk: int = 64

    def astuple(self):
        return (self.bq, self.bkv, self.chunk)


# Fallbacks when no tuned entry exists.  The batched kernel defaults to a
# deeper k-tile / wider gather brick: one grid point per (batch, m, n) tile
# amortises kernel-dispatch overhead that the vmapped 2-D path pays per
# k-block (interpret mode) and keeps the accumulator resident longer (TPU).
# The 2-D kernel takes its tile from ``tile_2d`` and its fold from FOLD_2D.
DEFAULT_BATCHED = BlockConfig(128, 128, 256, 64)
# The 2-D kernel's fold, (bk, chunk): the only tiling parameters its bits
# depend on (docs/kernels.md, "An explicit fold").
FOLD_2D = (128, 8)
# Output vregs (8 x 128 f32 each) that one k-step of the brick folds into,
# the live accumulator of its straight-line block, and the tallest tile:
# on a TPU v5e 64 x 512 (32 vregs) took the least time a product of the
# tiles swept at granite's GEMM shapes (PERF.md §6).
ACC_VREGS = 32
MAX_ROWS = 128
# Lane extents of an output tile, widest first, and the share of n a wider
# one may pad beyond the 128-lane padding every extent pays.
LANE_WIDTHS = (512, 256, 128)
LANE_PAD = 1 / 32
# Row tiles are whole bf16 sublane tiles: the brick's one-hot operand is
# bf16, and Mosaic tiles a bf16 array 16 rows deep.
ROW_TILE = 16
# Conv default: whole output-channel extent per block (``bo`` is clamped
# to O by the wrapper, avoiding the lane padding the GEMM path pays when
# O < 128) and a full-C gather brick for the paper's C <= 128 layers.
DEFAULT_CONV = ConvBlockConfig(8, 128, 64, 128)
# Attention default: 128-query blocks (x G rows) against 128-KV streaming
# steps — bkv=128 keeps the value-GEMM brick inside one jnp.sum while
# still giving block-skip granularity for sliding-window decode.
DEFAULT_ATTN = AttnBlockConfig(128, 128, 64)

CANDIDATES_2D = [
    BlockConfig(128, 128, 128, 8),
    BlockConfig(128, 128, 128, 32),
    BlockConfig(128, 128, 256, 32),
    BlockConfig(256, 128, 128, 8),
    BlockConfig(128, 256, 128, 16),
]
CANDIDATES_BATCHED = [
    BlockConfig(128, 128, 128, 32),
    BlockConfig(128, 128, 256, 32),
    BlockConfig(128, 128, 256, 64),
    BlockConfig(128, 128, 512, 64),
    BlockConfig(256, 128, 256, 32),
]
CANDIDATES_CONV = [
    ConvBlockConfig(4, 128, 64, 128),
    ConvBlockConfig(8, 128, 64, 128),
    ConvBlockConfig(8, 128, 32, 64),
    ConvBlockConfig(16, 128, 64, 256),
    ConvBlockConfig(8, 64, 64, 128),
]
CANDIDATES_ATTN = [
    AttnBlockConfig(64, 128, 64),
    AttnBlockConfig(128, 128, 64),
    AttnBlockConfig(128, 128, 128),
    AttnBlockConfig(128, 256, 64),
    AttnBlockConfig(256, 128, 64),
]

_MEM: dict[str, BlockConfig | ConvBlockConfig] | None = None  # file mirror


# ------------------------------------------------------------------ cache IO
def cache_path() -> Path:
    return Path(os.environ.get("REPRO_AUTOTUNE_CACHE")
                or CACHE / "autotune" / "gemm_blocks.json")


def _parse_entry(e) -> BlockConfig | ConvBlockConfig | AttnBlockConfig | None:
    """One cache entry -> config; None for nonsense (dropped silently)."""
    try:
        if "br" in e:
            cfg = ConvBlockConfig(int(e["br"]), int(e["bo"]),
                                  int(e["chunk"]), int(e["dw_chunk"]))
        elif "bq" in e:
            cfg = AttnBlockConfig(int(e["bq"]), int(e["bkv"]),
                                  int(e["chunk"]))
        else:
            cfg = BlockConfig(int(e["bm"]), int(e["bn"]),
                              int(e["bk"]), int(e["chunk"]))
    except (KeyError, TypeError, ValueError):
        return None
    return cfg if all(v > 0 for v in cfg.astuple()) else None


def _load_file() -> dict[str, BlockConfig | ConvBlockConfig]:
    """Parse the on-disk cache; any corruption degrades to an empty cache."""
    try:
        with open(cache_path()) as f:
            raw = json.load(f)
        if not isinstance(raw, dict) or raw.get("version") != SCHEMA_VERSION:
            return {}
        out = {}
        for key, e in raw.get("entries", {}).items():
            cfg = _parse_entry(e)
            if cfg is not None:
                out[key] = cfg
        return out
    except (OSError, ValueError, KeyError, TypeError):
        return {}


def _entries() -> dict[str, BlockConfig]:
    global _MEM
    if _MEM is None:
        _MEM = _load_file()
    return _MEM


def reload_cache() -> None:
    """Drop the in-process mirror; next lookup re-reads the file."""
    global _MEM
    _MEM = None


def _save_entry(key: str, cfg: BlockConfig | ConvBlockConfig,
                us: float) -> None:
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    try:
        with open(path) as f:
            raw = json.load(f)
        if not isinstance(raw, dict) or raw.get("version") != SCHEMA_VERSION \
                or not isinstance(raw.get("entries"), dict):
            raw = {"version": SCHEMA_VERSION, "entries": {}}
    except (OSError, ValueError):
        raw = {"version": SCHEMA_VERSION, "entries": {}}
    raw["entries"][key] = dict(dataclasses.asdict(cfg), us=round(us, 1))
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(raw, indent=1, sort_keys=True))
    os.replace(tmp, path)  # atomic publish (mirrors lutgen's LUT cache)
    _entries()[key] = cfg


# ------------------------------------------------------------------ keying
def _pow2_ceil(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def shape_bucket(m: int, k: int, n: int, batch: int = 0) -> str:
    """Power-of-two bucket so one tuned entry covers nearby shapes."""
    parts = []
    if batch:
        parts.append(f"b{_pow2_ceil(batch)}")
    parts += [f"m{_pow2_ceil(m)}", f"k{_pow2_ceil(k)}", f"n{_pow2_ceil(n)}"]
    return "_".join(parts)


def _m_tag(M: int, mult: str | None) -> str:
    """``M7`` or, with a resolved multiplier name, ``M7-mitchell8`` —
    per-multiplier entries keep mixed-multiplier tables from colliding
    on a shared mantissa width."""
    return f"M{M}" if mult is None else f"M{M}-{mult}"


def cache_key(kind: str, m: int, k: int, n: int, M: int,
              batch: int = 0, backend: str | None = None,
              mult: str | None = None) -> str:
    backend = backend or jax.default_backend()
    return f"{backend}|{kind}|{shape_bucket(m, k, n, batch)}|{_m_tag(M, mult)}"


def _pad_tag(padding) -> str:
    if isinstance(padding, str):
        return padding.upper()
    return "p" + ".".join(str(int(p)) for p in padding)


def conv_shape_bucket(n: int, h: int, w: int, c: int, kh: int, kw: int,
                      o: int, stride: int, padding) -> str:
    """H/W/K/stride/padding exact (they fix the in-kernel slicing
    structure); N/C/O pow2-bucketed like the GEMM dims."""
    return (f"n{_pow2_ceil(n)}_h{h}_w{w}_c{_pow2_ceil(c)}"
            f"_k{kh}x{kw}_o{_pow2_ceil(o)}_s{stride}_{_pad_tag(padding)}")


def conv_cache_key(n: int, h: int, w: int, c: int, kh: int, kw: int,
                   o: int, stride: int, padding, M: int,
                   backend: str | None = None,
                   mult: str | None = None) -> str:
    backend = backend or jax.default_backend()
    bucket = conv_shape_bucket(n, h, w, c, kh, kw, o, stride, padding)
    return f"{backend}|conv2d|{bucket}|{_m_tag(M, mult)}"


def attn_shape_bucket(bh: int, s: int, t: int, g: int, dh: int) -> str:
    """``bh`` = B x KV-heads (the kernel's flattened batch grid axis),
    ``s``/``t`` query/key lengths, pow2-bucketed; G and head_dim exact
    (they fix the gather-row layout and score-GEMM depth)."""
    return (f"bh{_pow2_ceil(bh)}_s{_pow2_ceil(s)}_t{_pow2_ceil(t)}"
            f"_g{g}_d{dh}")


def attn_cache_key(bh: int, s: int, t: int, g: int, dh: int, M: int,
                   backend: str | None = None,
                   mult: str | None = None) -> str:
    backend = backend or jax.default_backend()
    return (f"{backend}|attention|{attn_shape_bucket(bh, s, t, g, dh)}"
            f"|{_m_tag(M, mult)}")


# ------------------------------------------------------------------ tile rule
def tile_2d(m: int, n: int) -> tuple[int, int]:
    """(bm, bn) of an (m, k) @ (k, n) brick launch, from its shape.

    A k-step's A-side work (broadcast A's column, select its table rows
    by a one-hot matmul) is done once per row of the tile and serves every
    128-lane block of it, so ``bn`` is the widest of LANE_WIDTHS that pads
    n by at most LANE_PAD of n beyond the 128-lane padding; ``bm`` keeps
    the accumulator at ACC_VREGS vregs, at most MAX_ROWS rows, and pads m
    only to a whole bf16 sublane tile.  Neither enters the fold, so the
    rule moves no bit.
    """
    n128 = _ceil_to(n, 128)
    bn = next(w for w in LANE_WIDTHS
              if w <= n128 and _ceil_to(n, w) - n128 <= LANE_PAD * n)
    bm = min(ACC_VREGS * 8 * 128 // bn, MAX_ROWS,
             _ceil_to(max(m, 1), ROW_TILE))
    return bm, bn


# ------------------------------------------------------------------ lookup
def _lookup(key_fn, mult):
    """Per-multiplier entry first, bare-M entry as fallback (so sweeps
    tuned without a multiplier name still serve every table)."""
    hit = _entries().get(key_fn(mult)) if mult is not None else None
    return hit if hit is not None else _entries().get(key_fn(None))


def get_block_config(kind: str, m: int, k: int, n: int, M: int,
                     batch: int = 0, backend: str | None = None,
                     mult: str | None = None) -> BlockConfig:
    """Tuned winner for this bucket; on a miss DEFAULT_BATCHED for
    ``gemm3d``, and for ``gemm2d`` the tile of :func:`tile_2d` with the
    fold FOLD_2D."""
    hit = _lookup(lambda mu: cache_key(kind, m, k, n, M, batch, backend, mu),
                  mult)
    if isinstance(hit, BlockConfig):
        return hit
    if kind == "gemm3d":
        return DEFAULT_BATCHED
    return BlockConfig(*tile_2d(m, n), *FOLD_2D)


def get_conv_config(n: int, h: int, w: int, c: int, kh: int, kw: int,
                    o: int, stride: int, padding, M: int,
                    backend: str | None = None,
                    mult: str | None = None) -> ConvBlockConfig:
    """Tuned fused-conv tiling for this bucket, or DEFAULT_CONV."""
    hit = _lookup(lambda mu: conv_cache_key(n, h, w, c, kh, kw, o, stride,
                                            padding, M, backend, mu), mult)
    return hit if isinstance(hit, ConvBlockConfig) else DEFAULT_CONV


def get_attn_config(bh: int, s: int, t: int, g: int, dh: int, M: int,
                    backend: str | None = None,
                    mult: str | None = None) -> AttnBlockConfig:
    """Tuned fused-attention tiling for this bucket, or DEFAULT_ATTN."""
    hit = _lookup(lambda mu: attn_cache_key(bh, s, t, g, dh, M, backend, mu),
                  mult)
    return hit if isinstance(hit, AttnBlockConfig) else DEFAULT_ATTN


# ------------------------------------------------------------------ tuning
def _time_call(fn, *args, iters: int = 2) -> float:
    jax.block_until_ready(fn(*args))  # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _sweep(candidates, run, iters: int):
    """Time ``run(cfg)`` for every candidate; (winner, its time).

    A candidate that fails to lower (e.g. VMEM overflow on TPU) is
    skipped.  When every candidate fails, the first failure is raised: a
    sweep that times nothing must not hand back a default as if it had
    won.
    """
    best, best_t, first_err = None, float("inf"), None
    for cfg in candidates:
        try:
            t = _time_call(lambda: run(cfg), iters=iters)
        except Exception as e:  # noqa: BLE001 — re-raised below if alone
            first_err = first_err or e
            continue
        if t < best_t:
            best, best_t = cfg, t
    if best is None:
        if first_err is None:
            raise ValueError("autotune: no candidate to time")
        raise first_err
    return best, best_t


def autotune(kind: str, a, b, lut, M: int, *, candidates=None,
             interpret: bool | None = None, iters: int = 2,
             save: bool = True, mult: str | None = None) -> BlockConfig:
    """Sweep candidate tilings with the real kernel; cache + return the winner.

    ``a``/``b`` are representative operands: (m, k)/(k, n) for ``gemm2d``,
    (B, m, k)/(B, k, n) for ``gemm3d``.  Candidates that fail to lower
    (e.g. VMEM overflow on TPU) are skipped; if every candidate fails the
    first failure is raised.
    """
    from repro.kernels.approx_gemm import approx_gemm, approx_gemm_batched

    batched = kind == "gemm3d"
    if candidates is None:
        candidates = CANDIDATES_BATCHED if batched else CANDIDATES_2D
    if batched:
        B, m, k = a.shape
        n = b.shape[-1]
        run = lambda cfg: approx_gemm_batched(
            a, b, lut, M, bm=cfg.bm, bn=cfg.bn, bk=cfg.bk, chunk=cfg.chunk,
            interpret=interpret)
    else:
        B = 0
        m, k = a.shape
        n = b.shape[-1]
        run = lambda cfg: approx_gemm(
            a, b, lut, M, bm=cfg.bm, bn=cfg.bn, bk=cfg.bk, chunk=cfg.chunk,
            interpret=interpret)

    best, best_t = _sweep(candidates, run, iters)
    if save:
        _save_entry(cache_key(kind, m, k, n, M, B, mult=mult), best,
                    best_t * 1e6)
    return best


def autotune_conv(x, w, lut, M: int, *, stride: int = 1, padding="SAME",
                  candidates=None, interpret: bool | None = None,
                  iters: int = 2, save: bool = True,
                  mult: str | None = None) -> ConvBlockConfig:
    """Sweep fused-conv tilings (forward + weight-gradient timed
    together, since one cache entry serves both); cache + return the
    winner.  Candidates that fail to lower are skipped; if every
    candidate fails the first failure is raised.
    """
    from repro.kernels.approx_conv import (approx_conv2d_dw,
                                           approx_conv2d_fused)

    if candidates is None:
        candidates = CANDIDATES_CONV
    n, h, wid, c = x.shape
    kh, kw, _, o = w.shape

    def run(cfg):
        out = approx_conv2d_fused(x, w, lut, M, stride=stride,
                                  padding=padding, br=cfg.br, bo=cfg.bo,
                                  chunk=cfg.chunk, interpret=interpret)
        return approx_conv2d_dw(x, out, lut, M, kh=kh, kw=kw, stride=stride,
                                padding=padding, chunk=cfg.dw_chunk,
                                interpret=interpret)

    best, best_t = _sweep(candidates, run, iters)
    if save:
        _save_entry(conv_cache_key(n, h, wid, c, kh, kw, o, stride,
                                   padding, M, mult=mult), best,
                    best_t * 1e6)
    return best


def autotune_attention(q, k, v, q_pos, k_pos, lut, M: int, *,
                       causal: bool = True, window: int = 0,
                       candidates=None, interpret: bool | None = None,
                       iters: int = 2, save: bool = True,
                       mult: str | None = None) -> AttnBlockConfig:
    """Sweep fused-attention tilings with the real kernel; cache + return
    the winner.  ``q`` is (B, S, H, dh), ``k``/``v`` (B, T, KV, dh) —
    representative operands for the bucket.  Candidates that fail to
    lower are skipped; if every candidate fails the first failure is
    raised.
    """
    from repro.kernels.approx_attention import approx_attention_fused

    if candidates is None:
        candidates = CANDIDATES_ATTN
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV

    def run(cfg):
        return approx_attention_fused(
            q, k, v, q_pos, k_pos, lut, M, causal=causal, window=window,
            bq=cfg.bq, bkv=cfg.bkv, chunk=cfg.chunk, interpret=interpret)

    best, best_t = _sweep(candidates, run, iters)
    if save:
        _save_entry(attn_cache_key(B * KV, S, T, G, dh, M, mult=mult), best,
                    best_t * 1e6)
    return best
