"""Algorithm 1: black-box mantissa-product LUT generation (paper §V-A).

Takes *any* functional multiplier model (the user's "C/C++ code") and
enumerates all 2^M x 2^M mantissa pairs at a fixed safe exponent,
recovering the approximate mantissa product and the carry bit from the
model's FP32 output.  The resulting table is

    mntmult_lut[k * 2^M + j] = (carry << 23) | mantissa_field(C)

with 4-byte entries (the paper stores 4 bytes to avoid shifts at lookup
time — we keep the same layout so the Pallas kernel indexes uint32
directly).  Size: 2^(2M) * 4 bytes — 64 KiB for M=7, 16 MiB for M=11.

The generator is fully vectorised (one batched call into the model) and
results are cached on disk + in process, mirroring the paper's
"generate once, load at run-time" flow.  The disk cache directory is
``REPRO_LUT_DIR`` (default ``<repo>/.cache/luts``; all REPRO_* knobs:
docs/configuration.md).
"""
from __future__ import annotations

import os
from pathlib import Path

import numpy as np

from repro.checkout import CACHE

from .float_bits import MNT_BITS, MNT_MASK, np_bits, np_float, np_pack
from .multipliers import Multiplier, get_multiplier

_CACHE: dict[tuple[str, int], np.ndarray] = {}
_PACKED_CACHE: dict[tuple[str, int], np.ndarray | None] = {}

# Widest M whose packed entry (carry bit + M mantissa bits) fits uint16.
PACK_MAX_M = 15

# Safe exponent per Alg. 1 line 4: N = K = 127 -> product exponent
# N + K - 127 = 127, well inside [1, 254] even after a carry.
_SAFE_EXP = 127


def _pipeline_generation_enabled() -> bool:
    """REPRO_PIPELINE_LUT=0 forces pipeline multipliers through the
    black-box Algorithm-1 path (np_mul probing) instead of exhaustive
    staged-integer emission.  Both paths must agree bit-for-bit (tested);
    the switch exists as a validation seam and escape hatch."""
    return os.environ.get("REPRO_PIPELINE_LUT", "1").lower() not in (
        "0", "false", "off")


def generate_lut(multiplier: Multiplier, M: int | None = None) -> np.ndarray:
    """Run Algorithm 1 against ``multiplier``; returns uint32[2^(2M)].

    Pipeline-generated multipliers (``multiplier.pipeline`` set) are
    emitted directly by the staged integer pipeline (``fpstages
    .pipeline_lut``) when the table M matches the spec — bit-identical
    to black-box probing, but with carry-overflow validation and no
    float round-trip.  Any other M (or REPRO_PIPELINE_LUT=0) falls back
    to the black-box path, which re-quantises the probe grid at M
    exactly as for hand-written models.
    """
    spec = getattr(multiplier, "pipeline", None)
    if (spec is not None and _pipeline_generation_enabled()
            and (M is None or M == spec.table_bits)):
        from .fpstages import pipeline_lut

        return pipeline_lut(spec)
    return _generate_lut_blackbox(multiplier, M)


def _generate_lut_blackbox(multiplier: Multiplier, M: int | None = None) -> np.ndarray:
    """The paper's Algorithm 1 proper: probe ``np_mul`` on the mantissa grid."""
    M = multiplier.mantissa_bits if M is None else M
    if not 1 <= M <= 12:
        raise ValueError(f"LUT mantissa bits must be in [1,12], got {M}")
    n = 1 << M
    # All mantissa-field combinations, top-M bits significant (lines 5-7).
    k = np.arange(n, dtype=np.uint32) << np.uint32(MNT_BITS - M)
    ka, kb = np.meshgrid(k, k, indexing="ij")  # A index is the row (k*2^M+j)
    A = np_float(np_pack(0, _SAFE_EXP, ka))
    B = np_float(np_pack(0, _SAFE_EXP, kb))
    C = np.asarray(multiplier.np_mul(A, B), dtype=np.float32)  # line 8
    uc = np_bits(C)
    exp_c = (uc >> np.uint32(MNT_BITS)) & np.uint32(0xFF)
    # Lines 9-13: carry detection against the unnormalised exponent.
    un_normalized_exp = _SAFE_EXP + _SAFE_EXP - 127
    carry = (exp_c > un_normalized_exp).astype(np.uint32)
    entry = (carry << np.uint32(MNT_BITS)) | (uc & MNT_MASK)  # line 14
    return entry.reshape(-1)


def pack_lut(lut: np.ndarray, M: int) -> np.ndarray:
    """Compress a uint32 LUT to uint16: entry = (carry << M) | top-M mantissa.

    Valid only when every entry's mantissa field is confined to its top-M
    bits — true for every mantissa core in ``multipliers.py`` (they all
    mask the result to M significant bits), and checked here so a future
    full-precision model fails loudly instead of silently losing bits.
    Halves the table footprint (VMEM for the Pallas kernels): 32 KiB
    instead of 64 KiB for M=7.
    """
    if not 1 <= M <= PACK_MAX_M:
        raise ValueError(f"packed LUT requires 1 <= M <= {PACK_MAX_M}, got {M}")
    lut = np.asarray(lut, np.uint32)
    carry = (lut >> np.uint32(MNT_BITS)) & np.uint32(1)
    mnt = lut & MNT_MASK
    low = np.uint32((1 << (MNT_BITS - M)) - 1)
    if np.any(mnt & low):
        raise ValueError(
            f"LUT has mantissa bits below the top {M}; not packable")
    return ((carry << np.uint32(M)) | (mnt >> np.uint32(MNT_BITS - M))).astype(
        np.uint16)


def unpack_lut(packed: np.ndarray, M: int) -> np.ndarray:
    """Inverse of ``pack_lut``: uint16 -> the canonical uint32 layout."""
    p = np.asarray(packed, np.uint32)
    carry = p >> np.uint32(M)
    mnt = (p & np.uint32((1 << M) - 1)) << np.uint32(MNT_BITS - M)
    return ((carry << np.uint32(MNT_BITS)) | mnt).astype(np.uint32)


def get_packed_lut(name_or_mult, M: int | None = None,
                   cache_dir=None) -> np.ndarray | None:
    """Packed-uint16 LUT, or None if this multiplier's table is unpackable."""
    mult = get_multiplier(name_or_mult) if isinstance(name_or_mult, str) else name_or_mult
    M = mult.mantissa_bits if M is None else M
    key = (mult.name, M)
    if key not in _PACKED_CACHE:
        try:
            _PACKED_CACHE[key] = pack_lut(get_lut(mult, M, cache_dir), M)
        except ValueError:
            _PACKED_CACHE[key] = None
    return _PACKED_CACHE[key]


def lut_path(name: str, M: int, root: str | os.PathLike | None = None) -> Path:
    root = Path(root or os.environ.get("REPRO_LUT_DIR") or CACHE / "luts")
    return root / f"{name}_m{M}.lut.npy"


def get_lut(name_or_mult, M: int | None = None, cache_dir=None) -> np.ndarray:
    """Cached LUT fetch: process cache -> disk cache -> generate."""
    mult = get_multiplier(name_or_mult) if isinstance(name_or_mult, str) else name_or_mult
    M = mult.mantissa_bits if M is None else M
    key = (mult.name, M)
    if key in _CACHE:
        return _CACHE[key]
    path = lut_path(mult.name, M, cache_dir)
    if path.exists():
        lut = np.load(path)
    else:
        lut = generate_lut(mult, M)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp.npy")
        np.save(tmp, lut)
        os.replace(tmp, path)  # atomic publish
    _CACHE[key] = lut
    return lut
