"""jit'd public wrappers around the approximate-GEMM kernels.

This module is the JAX analogue of the paper's AMDENSE/AMCONV2D custom TF
ops (§VI): differentiable matmul / einsum / conv2d primitives whose forward
*and backward* multiplications are routed through the approximate-multiplier
simulation selected by a ``NumericsPolicy``.

Execution modes (leaf policy .mode):
  native     jnp dot -> MXU, exact f32               ("TFnG" baseline)
  surrogate  mantissa-truncate operands, native dot  (beyond-paper fast path,
             numerics-equivalent for the truncation family)
  amsim      Pallas LUT-GEMM kernel                  ("ATxG" analogue)
  amsim_jnp  pure-jnp LUT simulation                 (portable oracle)
  direct     pure-jnp bit-manipulation of the model  ("direct C sim", Fig. 6)

Heterogeneous numerics: every public op takes a *policy* — a flat
``NumericsPolicy`` or a hierarchical ``PolicyTable`` — plus an optional
``site`` label (the layer role threaded down from models/: "qkv", "wd",
"conv", "attn_score", ...).  This module is the single **resolve seam**:
``policy.resolve(site, pass_=...)`` picks the leaf ``(mode, multiplier)``
for each of the three passes (``fwd``, ``dx`` — activation gradients,
``dw`` — weight gradients), so a table can e.g. run exact weight
gradients with approximate activation gradients.  The legacy flat-policy
``approx_backward`` / ``approx_attention`` switches are implemented as
compiled-in default rules inside ``NumericsPolicy.resolve`` — there are
no special cases left here.  Resolution happens at trace time (policies
are static custom_vjp args), so a fixed table never retraces.

Differentiation: ``policy_matmul`` / ``policy_einsum`` / ``approx_conv2d``
carry a ``jax.custom_vjp`` so the backward pass performs the
approximate multiplications its ``dx``/``dw`` resolutions select (paper:
approximate multipliers in both forward and backpropagation).

Accumulation is always f32 (paper §VII).

Distribution: these wrappers are single-logical-device ops.  GSPMD
cannot partition a pallas_call (on the chip Mosaic refuses outright), so
under a mesh each kernel call runs replicated inside a shard_map
(``_replicated``).  The mesh-aware dispatch lives one layer up in
``distributed/shard_fused`` (shard_map around these same kernels,
collectives outside); model layers call it with their Megatron role.
Kill switches REPRO_CONV_FUSED / REPRO_ATTN_FUSED below and
REPRO_SHARD_FUSED up there are all documented in docs/configuration.md.
"""
from __future__ import annotations

import os
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro import obs
from repro.core import faults
from repro.core.amsim import amsim_multiply
from repro.core.float_bits import jnp_truncate_mantissa, jnp_round_mantissa
from repro.core.lutgen import get_lut, get_packed_lut
from repro.core.multipliers import get_multiplier
from repro.core.policy import PASSES, Numerics, NumericsPolicy
from repro.kernels.approx_attention import (NEG_INF, approx_attention_fused,
                                            attention_fused_supported)
from repro.kernels.common import attention_mask, best_chunk, rms_norm
from repro.kernels.approx_conv import (approx_conv2d_dw, approx_conv2d_fused,
                                       conv_pads, fused_supported)
from repro.kernels.approx_gemm import (GroupedRows, approx_gemm,
                                      approx_gemm_batched, approx_gemm_grouped,
                                      approx_gemm_grouped_dw)
from repro.kernels.ref import (ref_amsim_gemm, ref_direct_gemm,
                               ref_grouped_gemm, ref_grouped_gemm_dw,
                               ref_im2col)


# =====================================================================
# GEMM dispatch (2-D and stacked-batch 3-D)
# =====================================================================

def _replicated(fn, *args, **kw):
    """``fn(*args, **kw)`` — one Pallas kernel call — run whole on every
    device of the ambient mesh, outside a shard_map body.

    Mosaic kernels cannot be partitioned automatically ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map"), so a kernel the sharded dispatch
    (distributed/shard_fused) does not take runs on replicated operands,
    bitwise as on one device.  Interpret mode lowers the same way."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size <= 1 or all(
            t == AxisType.Manual for t in mesh.axis_types):
        return fn(*args, **kw)
    return jax.shard_map(partial(fn, **kw), mesh=mesh, in_specs=P(),
                         out_specs=P(), check_vma=False)(*args)


def _amsim_lut(mult):
    """Kernel LUT for ``mult``: packed uint16 when the table allows it
    (all registered cores confine results to the top-M mantissa bits),
    halving VMEM footprint; canonical uint32 otherwise.

    This is the **fault-injection seam** (core/faults.py): when a fault
    spec is active (REPRO_FAULTS or faults.inject), the table is
    perturbed here — once, at trace time — so every kernel family that
    closes over a LUT (GEMM, conv fwd/dw/dx, fused attention, decode
    chain, and all their sharded forms) inherits the faults with zero
    kernel edits.  Off (the default) returns the cached array object
    untouched: bitwise-identical traces, zero copies.
    """
    packed = get_packed_lut(mult)
    if packed is not None:
        return faults.faulted_lut(packed, mult.mantissa_bits, packed=True,
                                  mult=mult.name)
    return faults.faulted_lut(get_lut(mult), mult.mantissa_bits,
                              packed=False, mult=mult.name)


def _oracle_lut(mult):
    """Canonical uint32 LUT for the jnp oracle mode — same fault seam as
    the kernels, so ``amsim_jnp`` reproduces injected faults bit-for-bit
    (the packed/unpacked fault equivalence is pinned in tests)."""
    return faults.faulted_lut(get_lut(mult), mult.mantissa_bits,
                              packed=False, mult=mult.name)


def gemm_tag(site: str | None, pass_: str) -> tuple[str, str]:
    """The ``(site, pass)`` a GEMM's kernel launch is tagged with
    (``common.tagged_pallas_call``); an unlabelled call resolves the
    policy's default rule and is tagged "default"."""
    return ("default" if site is None else site, pass_)


# One mode-routing table shared by the 2-D and batched engines (the two
# differ only in which Pallas kernel ``amsim`` lowers to — the jnp
# oracle modes are batch-generalised already).  Each entry maps a mode
# to ``impl(a, b, mult, kernel, tag)``; ``kernel`` is the engine's amsim
# kernel, with the resolved multiplier name keying the autotune cache
# and ``tag`` its launch's (site, pass).
_GEMM_MODES = {
    "amsim": lambda a, b, mult, kernel, tag: _replicated(
        lambda a_, b_: kernel(a_, b_, _amsim_lut(mult), mult.mantissa_bits,
                              mult=mult.name, tag=tag), a, b),
    "amsim_jnp": lambda a, b, mult, kernel, tag: ref_amsim_gemm(
        a, b, jnp.asarray(_oracle_lut(mult)), mult.mantissa_bits),
    "direct": lambda a, b, mult, kernel, tag: ref_direct_gemm(a, b, mult),
}


def _gemm_dispatch(a, b, policy: NumericsPolicy, kernel, tag):
    """Route one GEMM through the mode table under a *leaf* policy."""
    mode = policy.mode
    if mode == "native" or policy.is_native:
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)
    impl = _GEMM_MODES.get(mode)
    if impl is None:
        raise ValueError(f"unknown mode {mode!r}")
    return impl(a, b, get_multiplier(policy.multiplier), kernel, tag)


def _gemm2d(a, b, policy: NumericsPolicy, tag=None):
    """(m, k) @ (k, n) -> (m, n) under the policy's numerics. f32 accumulate."""
    return _gemm_dispatch(a, b, policy, approx_gemm, tag)


def _gemm_batched(a, b, policy: NumericsPolicy, tag=None):
    """(B, m, k) @ (B, k, n) -> (B, m, n): the batched engine.

    ``amsim`` lowers to the single 4-D-grid Pallas kernel (LUT broadcast
    across the batch axis); the jnp modes use the batch-generalised
    oracles.  This replaces the per-element ``lax.map`` fallback, so one
    kernel launch covers the whole batch in every attention score/value
    contraction, MoE expert stack, and decode step.
    """
    return _gemm_dispatch(a, b, policy, approx_gemm_batched, tag)


def _surrogate_operands(a, b, policy: NumericsPolicy):
    """The operands of a ``surrogate`` product, quantized for its native
    matmul."""
    mult = get_multiplier(policy.multiplier)
    # Cross-format pipelines truncate each operand to its own format
    # width (fp16 activations x bf16 weights); symmetric multipliers
    # see ma == mb == mantissa_bits.
    ma, mb = mult.operand_bits
    # Pipeline specs always truncate operands (DenormStage); of the
    # hand-written zoo only bf16 rounds them.
    rnd = (jnp_round_mantissa
           if mult.pipeline is None and mult.name.startswith("bf16")
           else jnp_truncate_mantissa)
    return rnd(a, ma), rnd(b, mb)


def _matmul_nograd(a, b, policy: NumericsPolicy, tag=None):
    """Batched matmul (..., m, k) @ broadcastable (..., k, n), no custom grad.
    ``tag`` is the ``(site, pass)`` its kernel launches carry (``gemm_tag``).

    Three supported layouts (covering every call site in models/):
      * b is 2-D (weight matmul): fold a's batch into m — single GEMM.
      * equal batch dims (attention-style): flatten batch, one batched
        GEMM through the 4-D-grid kernel (``_gemm_batched``).
      * scalar/no batch: single GEMM.
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if policy.is_native:
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)
    if policy.mode == "surrogate":
        # Truncation family: masking inputs + exact MXU product is
        # per-multiply identical to the model up to final-product rounding.
        # Elementwise quantize + native batched matmul — no layout
        # restructuring, so GSPMD sharding propagates exactly as in
        # native mode (no spurious all-gathers).
        return jnp.matmul(*_surrogate_operands(a, b, policy),
                          preferred_element_type=jnp.float32)
    if a.ndim == 2 and b.ndim == 2:
        return _gemm2d(a, b, policy, tag)
    if b.ndim == 2:
        batch = a.shape[:-2]
        m, k = a.shape[-2:]
        out = _gemm2d(a.reshape(-1, k), b, policy, tag)
        return out.reshape(*batch, m, b.shape[-1])
    if a.shape[:-2] == b.shape[:-2]:
        # Equal batch dims (attention scores/values, MoE expert stacks):
        # flatten the batch and run the batched engine — one kernel
        # launch, not a lax.map over per-example 2-D GEMMs.
        batch = a.shape[:-2]
        m, k = a.shape[-2:]
        n = b.shape[-1]
        af = a.reshape((-1, m, k))
        bf = b.reshape((-1, k, n))
        out = _gemm_batched(af, bf, policy, tag)
        return out.reshape(*batch, m, n)
    # General broadcasting: broadcast batch dims then recurse.
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    a = jnp.broadcast_to(a, batch + a.shape[-2:])
    b = jnp.broadcast_to(b, batch + b.shape[-2:])
    return _matmul_nograd(a, b, policy, tag)


# =====================================================================
# Differentiable matmul (paper: approx multiplies in fwd AND bwd)
# =====================================================================

@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def policy_matmul(a, b, policy: Numerics, site: str | None = None):
    """Differentiable batched matmul under the numerics ``policy``
    resolves at ``site`` (flat policy or per-site table): forward under
    the ``fwd`` leaf, backward GEMMs under the ``dx``/``dw`` leaves."""
    return _matmul_nograd(a, b, policy.resolve(site), gemm_tag(site, "fwd"))


def _mm_fwd(a, b, policy, site=None):
    return (_matmul_nograd(a, b, policy.resolve(site), gemm_tag(site, "fwd")),
            (a, b))


# Sites whose second operand is a *parameter* even when it is a stacked
# 3-D bank: the MoE expert FFN runs (E, C, d) @ (E, d, d_ff), so its
# weight matmuls take the equal-batch layout that is otherwise an
# activation-activation contraction (attention scores, SSD einsums).
# Their db is a weight gradient and must resolve under the dw pass —
# without this set, a table's dw rule would silently skip MoE experts.
_STACKED_WEIGHT_SITES = frozenset({"wg", "wu", "wd"})


def _mm_bwd(policy, site, res, g):
    a, b = res
    # dx = activation gradients, dw = weight gradients (paper Fig. 8):
    # a table can resolve them to different numerics; the flat policy's
    # approx_backward flag resolves both the same way it always did.
    leaf_dx = policy.resolve(site, pass_="dx")
    leaf_dw = policy.resolve(site, pass_="dw")
    g = g.astype(jnp.float32)
    swap = lambda x: jnp.swapaxes(x, -1, -2)
    # dA = g @ B^T  — same batch layout as forward.
    da = _matmul_nograd(g, swap(b), leaf_dx, gemm_tag(site, "dx"))
    extra = da.ndim - a.ndim
    if extra > 0:
        da = da.sum(axis=tuple(range(extra)))
    if b.ndim == 2:
        # Weight gradient: fold every batch row into the contraction —
        # dB = A_flat^T @ g_flat, one large GEMM (paper Fig. 8(b)).
        k = a.shape[-1]
        n = g.shape[-1]
        db = _matmul_nograd(a.reshape(-1, k).T, g.reshape(-1, n), leaf_dw,
                            gemm_tag(site, "dw"))
    else:
        # b is batched: an activation (attention-style contraction, dx)
        # unless the site stacks its weights 3-D (MoE expert banks, dw).
        pass_db = "dw" if site in _STACKED_WEIGHT_SITES else "dx"
        leaf_db = leaf_dw if pass_db == "dw" else leaf_dx
        db = _matmul_nograd(swap(a), g, leaf_db, gemm_tag(site, pass_db))
        # Sum over broadcasted batch dims of b.
        extra = db.ndim - b.ndim
        if extra > 0:
            db = db.sum(axis=tuple(range(extra)))
        for ax, (dbs, bs) in enumerate(zip(db.shape[:-2], b.shape[:-2])):
            if bs == 1 and dbs != 1:
                db = db.sum(axis=ax, keepdims=True)
    return da.reshape(a.shape), db.reshape(b.shape)


policy_matmul.defvjp(_mm_fwd, _mm_bwd)


# =====================================================================
# Grouped matmul over rows sorted by expert (models/moe.py)
#
# The dropless MoE layer sorts its (token, choice) rows by expert and
# pads each expert's group to the row tile (approx_gemm.grouped_layout);
# each row tile is then multiplied by its expert's weights.  The forward
# and dx run the grouped kernel (dx over each expert's transposed
# weights), dw the grouped transposed-LHS kernel.  Under a mesh the
# kernels run replicated (``_replicated``), as every kernel the sharded
# dispatch does not take.
# =====================================================================

def _oracle_mul(leaf: NumericsPolicy, mult):
    """The scalar product of the jnp oracle modes."""
    if leaf.mode == "amsim_jnp":
        lut = jnp.asarray(_oracle_lut(mult))
        return lambda x, y: amsim_multiply(x, y, lut, mult.mantissa_bits)
    if leaf.mode == "direct":
        return mult.jnp_mul
    raise ValueError(f"unknown mode {leaf.mode!r}")


def _grouped_nograd(x, w, groups: GroupedRows, leaf: NumericsPolicy, tag):
    """x (R, k) sorted rows @ w (E, k, n), each row tile times its
    expert's block, under a resolved leaf: (R, n)."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if leaf.mode == "surrogate" and not leaf.is_native:
        x, w = _surrogate_operands(x, w, leaf)
    if leaf.is_native or leaf.mode == "surrogate":
        return jax.lax.ragged_dot(x, w, groups.sizes,
                                  preferred_element_type=jnp.float32)
    mult = get_multiplier(leaf.multiplier)
    if leaf.mode == "amsim":
        return _replicated(
            lambda x_, w_, g_: approx_gemm_grouped(
                x_, w_, g_, _amsim_lut(mult), mult.mantissa_bits,
                mult=mult.name, tag=tag), x, w, groups)
    return ref_grouped_gemm(x, w, groups, _oracle_mul(leaf, mult))


def _grouped_dw_nograd(x, g, groups: GroupedRows, n_experts: int,
                       leaf: NumericsPolicy, tag):
    """Per expert, x^T @ g over its rows, under a resolved leaf:
    (E, k, n)."""
    x, g = x.astype(jnp.float32), g.astype(jnp.float32)
    if leaf.mode == "surrogate" and not leaf.is_native:
        x, g = _surrogate_operands(x, g, leaf)
    if leaf.is_native or leaf.mode == "surrogate":
        zero = jnp.zeros((n_experts, x.shape[1], g.shape[1]), jnp.float32)
        _, vjp = jax.vjp(lambda w: jax.lax.ragged_dot(
            x, w, groups.sizes, preferred_element_type=jnp.float32), zero)
        return vjp(g)[0]
    mult = get_multiplier(leaf.multiplier)
    if leaf.mode == "amsim":
        return _replicated(
            lambda x_, g_, gr_: approx_gemm_grouped_dw(
                x_, g_, gr_, _amsim_lut(mult), mult.mantissa_bits, n_experts,
                mult=mult.name, tag=tag), x, g, groups)
    return ref_grouped_gemm_dw(x, g, groups, n_experts,
                               _oracle_mul(leaf, mult))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(x, w, groups: GroupedRows, policy: Numerics,
                   site: str | None = None):
    """Differentiable grouped matmul: x (R, k), rows sorted by expert as
    ``groups`` lays them out, times w (E, k, n) -> (R, n).  Forward under
    the ``fwd`` leaf ``policy`` resolves at ``site`` (the expert sites
    "wg", "wu", "wd"), dx and dw under theirs; rows of tiles past the last
    group are zero, and so is their gradient."""
    return _grouped_nograd(x, w, groups, policy.resolve(site),
                           gemm_tag(site, "fwd"))


def _gm_fwd(x, w, groups, policy, site=None):
    return (_grouped_nograd(x, w, groups, policy.resolve(site),
                            gemm_tag(site, "fwd")), (x, w, groups))


def _gm_bwd(policy, site, res, g):
    x, w, groups = res
    g = g.astype(jnp.float32)
    dx = _grouped_nograd(g, jnp.swapaxes(w, 1, 2), groups,
                         policy.resolve(site, pass_="dx"),
                         gemm_tag(site, "dx"))
    dw = _grouped_dw_nograd(x, g, groups, w.shape[0],
                            policy.resolve(site, pass_="dw"),
                            gemm_tag(site, "dw"))
    return dx.astype(x.dtype), dw.astype(w.dtype), None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)


# =====================================================================
# Einsum -> batched-matmul rewrite
# =====================================================================

def _parse_einsum(spec: str, a_shape, b_shape):
    """Classify dims of a 2-operand einsum into (batch, contract, afree, bfree).

    Supports specs with no repeated labels within an operand and no
    lone-summed labels (every label appears in >= 2 of {a, b, out}).
    """
    lhs, out = spec.replace(" ", "").split("->")
    sa, sb = lhs.split(",")
    if len(set(sa)) != len(sa) or len(set(sb)) != len(sb):
        raise ValueError(f"repeated labels unsupported: {spec}")
    batch = [c for c in sa if c in sb and c in out]
    contract = [c for c in sa if c in sb and c not in out]
    afree = [c for c in sa if c not in sb]
    bfree = [c for c in sb if c not in sa]
    if not all(c in out for c in afree + bfree):
        raise ValueError(f"lone-summed labels unsupported: {spec}")
    dims = {}
    for c, d in zip(sa, a_shape):
        dims[c] = d
    for c, d in zip(sb, b_shape):
        if c in dims and dims[c] != d and 1 not in (dims[c], d):
            raise ValueError(f"dim mismatch for {c!r} in {spec}")
        dims[c] = max(dims.get(c, d), d)
    return sa, sb, out, batch, contract, afree, bfree, dims


def _all_passes_native(policy: Numerics, site: str | None) -> bool:
    """True when every pass at this site resolves native — the einsum
    can then stay a single jnp.einsum and use XLA's own autodiff."""
    return all(policy.resolve(site, pass_=p).is_native for p in PASSES)


def policy_einsum(spec: str, a, b, policy: Numerics, site: str | None = None):
    """2-operand einsum routed through policy numerics (differentiable)."""
    if _all_passes_native(policy, site):
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
    sa, sb, out, batch, contract, afree, bfree, dims = _parse_einsum(
        spec, a.shape, b.shape)
    # a -> (batch..., afree.., contract..), b -> (batch..., contract.., bfree..)
    aperm = [sa.index(c) for c in batch + afree + contract]
    bperm = [sb.index(c) for c in batch + contract + bfree]
    at = jnp.transpose(a, aperm)
    bt = jnp.transpose(b, bperm)
    bshape = [dims[c] for c in batch]
    at = jnp.broadcast_to(at, bshape + list(at.shape[len(batch):]))
    bt = jnp.broadcast_to(bt, bshape + list(bt.shape[len(batch):]))
    m = int(np.prod([dims[c] for c in afree], initial=1))
    k = int(np.prod([dims[c] for c in contract], initial=1))
    n = int(np.prod([dims[c] for c in bfree], initial=1))
    at = at.reshape(bshape + [m, k])
    bt = bt.reshape(bshape + [k, n])
    o = policy_matmul(at, bt, policy, site)
    o = o.reshape(bshape + [dims[c] for c in afree] + [dims[c] for c in bfree])
    # current order: batch + afree + bfree -> out order
    cur = batch + afree + bfree
    operm = [cur.index(c) for c in out]
    return jnp.transpose(o, operm)


# =====================================================================
# Conv2D (paper §VI: AMCONV2D — fwd + both bwd gradients)
#
# Two lowerings:
#   * fused implicit-GEMM Pallas kernels (kernels/approx_conv.py) when
#     policy.mode == "amsim" and the shape fits the kernel's VMEM/unroll
#     guards — the paper's AMCONV2D without materialising im2col;
#   * materialised im2col + policy GEMM otherwise (also the amsim_jnp /
#     direct reference lowering the fused kernels are tested against).
# =====================================================================

# _conv_pads is intentionally lax.padtype_to_pads-backed (see
# kernels/approx_conv.py) so SAME pads for even kernel sizes keep the
# asymmetric low=floor / high=remainder split of conv_general_dilated.
_conv_pads = conv_pads


def _say_unfused(what: str, shape) -> bool:
    """Warn (once per shape, by the warnings registry) that an amsim site
    on the chip lowers to its unfused form (per-GEMM LUT kernels), so a
    slow path never passes for the fused one in silence.  Returns False
    for the guard."""
    if jax.default_backend() == "tpu":
        warnings.warn(f"amsim {what} {shape} exceeds the fused kernel's "
                      f"VMEM guard on this TPU: unfused per-GEMM LUT "
                      f"kernels instead", stacklevel=3)
    return False


def _conv_use_fused(x_shape, w_shape, stride, leaf: NumericsPolicy) -> bool:
    """``leaf`` is an already-resolved (per-pass) policy."""
    if leaf.mode != "amsim" or leaf.is_native:
        return False
    if os.environ.get("REPRO_CONV_FUSED", "1").lower() in ("0", "false"):
        return False
    return (fused_supported(x_shape, w_shape, stride)
            or _say_unfused("conv2d", (x_shape, w_shape)))


def conv2d_im2col(x, w, stride, padding, policy):
    """x (N,H,W,C), w (KH,KW,C,O) -> (N,OH,OW,O) via materialised
    im2col + policy GEMM (the pre-fused lowering; kept as reference and
    fallback, and benchmarked against the fused kernel)."""
    n, h, wid, c = x.shape
    kh, kw, _, o = w.shape
    pad = _conv_pads(h, wid, kh, kw, stride, padding)
    cols = ref_im2col(x, kh, kw, stride, pad)      # (N*OH*OW, KH*KW*C)
    out = policy_matmul(cols, w.reshape(-1, o), policy, "conv")
    oh = (h + pad[0] + pad[1] - kh) // stride + 1
    ow = (wid + pad[2] + pad[3] - kw) // stride + 1
    return out.reshape(n, oh, ow, o)


def _conv_fwd_impl(x, w, stride, padding, policy):
    leaf = policy.resolve("conv")
    if _conv_use_fused(x.shape, w.shape, stride, leaf):
        mult = get_multiplier(leaf.multiplier)
        return _replicated(
            lambda x_, w_: approx_conv2d_fused(
                x_, w_, _amsim_lut(mult), mult.mantissa_bits,
                stride=stride, padding=padding, mult=mult.name), x, w)
    return conv2d_im2col(x, w, stride, padding, policy)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def approx_conv2d(x, w, stride: int, padding: str, policy: Numerics):
    """Differentiable NHWC conv2d with approximate multiplications.

    Forward and both backward GEMMs (weight gradient & preceding-layer
    gradient, paper Fig. 8 b/c) run under the numerics ``policy``
    resolves at site "conv" — per pass, so a table can e.g. keep dw
    exact while fwd/dx stay approximate; the paper's dilation/padding
    restructuring maps to index arithmetic here.
    """
    return _conv_fwd_impl(x, w, stride, padding, policy)


def _conv_fwd(x, w, stride, padding, policy):
    return _conv_fwd_impl(x, w, stride, padding, policy), (x, w)


def _conv_bwd(stride, padding, policy, res, g):
    x, w = res
    leaf_dx = policy.resolve("conv", pass_="dx")
    leaf_dw = policy.resolve("conv", pass_="dw")
    n, h, wid, c = x.shape
    kh, kw, _, o = w.shape
    pad = _conv_pads(h, wid, kh, kw, stride, padding)
    _, oh, ow, _ = g.shape

    # --- weight gradient (Fig. 8b): cols(x)^T @ g — the fused kernel
    # computes the patch outer product in place of the materialised
    # im2col^T GEMM; the paper's fused dilation corresponds to the
    # strided patch slicing inside either lowering.
    if _conv_use_fused(x.shape, w.shape, stride, leaf_dw):
        mw = get_multiplier(leaf_dw.multiplier)
        dw = _replicated(
            lambda x_, g_: approx_conv2d_dw(
                x_, g_, _amsim_lut(mw), mw.mantissa_bits, kh=kh, kw=kw,
                stride=stride, padding=padding, mult=mw.name), x, g)
    else:
        g2 = g.reshape(n * oh * ow, o).astype(jnp.float32)
        cols = ref_im2col(x, kh, kw, stride, pad)    # (N*OH*OW, KH*KW*C)
        dw = _matmul_nograd(cols.T, g2, leaf_dw,
                            gemm_tag("conv", "dw")).reshape(kh, kw, c, o)

    # --- preceding-layer gradient (Fig. 8c): full correlation of the
    # dilated+padded error with the reversed-transposed weights.
    if stride > 1:  # materialise dilation (paper fuses it; index-equivalent)
        gd = jnp.zeros((n, (oh - 1) * stride + 1, (ow - 1) * stride + 1, o),
                       g.dtype).at[:, ::stride, ::stride, :].set(g)
    else:
        gd = g
    # pad so that VALID conv with the flipped kernel returns H x W
    pt = kh - 1 - pad[0]
    pl_ = kw - 1 - pad[2]
    gh = gd.shape[1]
    gw = gd.shape[2]
    pb = h - (gh + pt - kh + 1)
    pr = wid - (gw + pl_ - kw + 1)
    wrev = w[::-1, ::-1, :, :]                             # reverse
    wrt4 = jnp.transpose(wrev, (0, 1, 3, 2))               # O <-> C
    if _conv_use_fused(x.shape, w.shape, stride, leaf_dx) \
            and fused_supported(gd.shape, wrt4.shape, 1):
        # Transposed conv IS a conv: the same fused forward kernel runs
        # the stride-1 correlation under the explicit asymmetric pads.
        mx = get_multiplier(leaf_dx.multiplier)
        dx = _replicated(
            lambda g_, w_: approx_conv2d_fused(
                g_, w_, _amsim_lut(mx), mx.mantissa_bits, stride=1,
                padding=(pt, pb, pl_, pr), mult=mx.name, pass_="dx"),
            gd, wrt4)
    else:
        gcols = ref_im2col(gd, kh, kw, 1, (pt, pb, pl_, pr))  # (N*H*W, KH*KW*O)
        dx = _matmul_nograd(gcols, wrt4.reshape(-1, c), leaf_dx,
                            gemm_tag("conv", "dx")).reshape(n, h, wid, c)
    return dx, dw


approx_conv2d.defvjp(_conv_fwd, _conv_bwd)


# =====================================================================
# Attention (one-launch fused kernel + einsum reference lowering)
#
# Two lowerings, mirroring the conv2d structure:
#   * ``policy_attention`` — the fused Pallas kernel
#     (kernels/approx_attention.py) when policy.mode == "amsim" and the
#     shape fits the VMEM guards: one launch for score -> mask ->
#     softmax -> value, scores never materialised in HBM;
#   * ``attend_einsum`` — the grouped-query einsum chain (two
#     policy_einsum contractions through approx_gemm_batched + a full
#     mask/softmax pass).  Every other mode uses it directly; it is also
#     the oracle the fused kernel is bit-tested against AND the path the
#     fused custom VJP recomputes through, so gradients are identical to
#     the pre-fused lowering whatever the forward took.
# =====================================================================

def attend_einsum(q, k, v, q_pos, k_pos, policy: Numerics, *,
                  causal: bool, window: int):
    """Grouped-query einsum attention under ``policy`` numerics.

    q (B,S,H,dh), k/v (B,T,KV,dh) -> (B,S,H,dh).  k_pos holds the
    *absolute* position of every KV slot; negative means unwritten
    (ring-buffer cache) and is masked out.  Positions may be 1-D
    (shared across the batch, the ring layout) or ``(B, S)``/``(B, T)``
    for the paged serving cache where every slot sits at its own
    position (docs/serving.md) — the mask then differs per batch row.
    The KV-head axis stays a batch axis so KV is never materialised at
    full head count.  The two contractions resolve under their own
    sites ("attn_score" / "attn_value"), so a table can give the score
    and value GEMMs different numerics — the einsum path is the only
    lowering that can honour a split; the fused kernel requires them
    equal.
    """
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, dh)
    scores = policy_einsum("bqkgd,btkd->bkgqt", qg, k, policy,
                           "attn_score") / jnp.sqrt(float(dh))
    mask = attention_mask(q_pos, k_pos, causal=causal, window=window)
    # (S, T) broadcasts over (B, KV, G); a per-row (B, S, T) mask slots
    # its batch dim in front and broadcasts over (KV, G) only.
    mask = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = policy_einsum("bkgqt,btkd->bqkgd", probs, v, policy, "attn_value")
    return out.reshape(B, S, H, dh)


def attention_fused_leaf(policy: Numerics) -> NumericsPolicy | None:
    """The single leaf the one-launch kernel would run BOTH attention
    contractions under, or None when the policy resolves the score and
    value sites to different numerics (the kernel bakes one LUT, so a
    split forces the einsum lowering)."""
    ls = policy.resolve("attn_score")
    lv = policy.resolve("attn_value")
    if (ls.mode, ls.multiplier) != (lv.mode, lv.multiplier):
        return None
    return ls


def fused_attention_enabled(policy: Numerics, q_shape, k_shape, *,
                            causal: bool = True, window: int = 0,
                            per_row: bool = False) -> bool:
    """Dispatch guard for the one-launch kernel: both attention sites
    must resolve to the same amsim leaf, killable via
    REPRO_ATTN_FUSED=0, and the shape must pass the VMEM bounds
    (window-compacted under a causal sliding window; ``per_row``
    positions — the paged serving cache — disable that compaction, so
    the bound is taken on the full KV extent)."""
    leaf = attention_fused_leaf(policy)
    if leaf is None or leaf.mode != "amsim" or leaf.is_native:
        return False
    if os.environ.get("REPRO_ATTN_FUSED", "1").lower() in ("0", "false"):
        return False
    return (attention_fused_supported(q_shape, k_shape, causal=causal,
                                      window=window, per_row=per_row)
            or _say_unfused("attention", (q_shape, k_shape)))


def _attention_fwd_impl(q, k, v, q_pos, k_pos, policy, causal, window):
    mult = get_multiplier(attention_fused_leaf(policy).multiplier)
    return _replicated(
        lambda q_, k_, v_, qp, kp: approx_attention_fused(
            q_, k_, v_, qp, kp, _amsim_lut(mult), mult.mantissa_bits,
            causal=causal, window=window, mult=mult.name),
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        q_pos, k_pos)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def policy_attention(q, k, v, q_pos, k_pos, policy: Numerics,
                     causal: bool, window: int):
    """Differentiable one-launch fused attention under ``policy``.

    Forward runs the fused Pallas kernel; the backward pass recomputes
    through ``attend_einsum`` (jax.vjp), so gradients take exactly the
    pre-fused einsum path — each backward GEMM under the numerics the
    policy resolves for its site's ``dx`` pass (handled inside
    policy_matmul's VJP) — bit-identical to the unfused lowering for
    S <= _BWD_Q_CHUNK, q-chunked above that to keep the recompute's
    score tensor memory-bounded (as the einsum path's forward scan
    did).  Callers must have checked :func:`fused_attention_enabled`.
    """
    return _attention_fwd_impl(q, k, v, q_pos, k_pos, policy, causal, window)


def _pattn_fwd(q, k, v, q_pos, k_pos, policy, causal, window):
    out = _attention_fwd_impl(q, k, v, q_pos, k_pos, policy, causal, window)
    return out, (q, k, v, q_pos, k_pos)


# q-chunk length for the backward recompute (= ArchConfig.q_chunk's
# default): the fused forward collapses models/attention's q-chunk scan
# into its q-block grid axis, so the VJP must restore the memory bound
# that scan provided — an unchunked attend_einsum recompute would
# materialise the full (B, KV, G, S, T) score/probs tensors plus their
# residuals in every backward pass.
_BWD_Q_CHUNK = 1024


def _pattn_bwd(policy, causal, window, res, g):
    q, k, v, q_pos, k_pos = res
    g = g.astype(jnp.float32)
    B, S, H, dh = q.shape

    def chunk_grads(q_c, qp_c, g_c):
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attend_einsum(q_, k_, v_, qp_c, k_pos, policy,
                                             causal=causal, window=window),
            q_c, k, v)
        return vjp(g_c)

    # Snap the chunk to a divisor of S near the target so a
    # non-multiple S (e.g. 1536 with target 1024 -> 768) keeps the
    # memory bound instead of silently recomputing unchunked; only a
    # degenerate divisor structure (prime-ish S, where chunking would
    # mean per-row maps) falls back to the one-shot recompute.  Per-row
    # (B, S) positions — the paged serving cache — skip the chunking
    # (its reshape assumes one shared position vector); paged calls are
    # short decode/prefill segments, so the one-shot recompute stays
    # memory-bounded.
    bqc = best_chunk(_BWD_Q_CHUNK, S)
    if S > bqc > _BWD_Q_CHUNK // 16 and q_pos.ndim == 1:
        # Attention rows are independent, so dq splits cleanly by q-chunk
        # while dk/dv sum over chunks — the same decomposition the
        # einsum path's forward scan induces on its backward.
        nc = S // bqc
        qc = q.reshape(B, nc, bqc, H, dh).swapaxes(0, 1)
        gc = g.reshape(B, nc, bqc, H, dh).swapaxes(0, 1)
        pc = q_pos.reshape(nc, bqc)
        dqc, dkc, dvc = jax.lax.map(lambda a: chunk_grads(*a), (qc, pc, gc))
        dq = dqc.swapaxes(0, 1).reshape(q.shape)
        dk = jnp.sum(dkc, axis=0)
        dv = jnp.sum(dvc, axis=0)
    else:
        dq, dk, dv = chunk_grads(q, q_pos, g)
    zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # int positions
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), \
        zero(q_pos), zero(k_pos)


policy_attention.defvjp(_pattn_fwd, _pattn_bwd)


# =====================================================================
# Fused decode chain (whole-layer persistent kernels)
#
# kernels/decode_chain.py fuses a dense block's qkv-projection front
# half and wo->rmsnorm->FFN back half into one persistent launch each
# (LUT + activations VMEM-resident, weights streamed).  This section is
# the dispatch seam, mirroring the conv/attention structure: a leaf
# resolver, an enable guard (kill switch ``REPRO_DECODE_FUSED=0``), and
# custom-VJP wrappers whose backward recomputes through the unfused
# policy_matmul chain — the oracle the fused forward is bit-tested
# against — so gradients are identical to the per-op lowering.
# models/transformer.py routes single-token dense decode blocks here.
# =====================================================================

_CHAIN_SITES = ("qkv", "wo", "wg", "wu", "wd")


def decode_chain_leaf(policy: Numerics) -> NumericsPolicy | None:
    """The single forward leaf the chain kernels would run EVERY
    projection under, or None when the policy resolves any two chain
    sites differently (the kernels bake one LUT; a heterogeneous table
    forces the per-op lowering)."""
    leaves = [policy.resolve(s) for s in _CHAIN_SITES]
    first = leaves[0]
    for leaf in leaves[1:]:
        if (leaf.mode, leaf.multiplier) != (first.mode, first.multiplier):
            return None
    return first


def decode_chain_enabled(policy: Numerics, rows: int, d: int,
                         k_attn: int, d_ff: int, *,
                         moe: bool = False) -> bool:
    """Dispatch guard for the fused decode chain: every chain site must
    resolve to the same amsim leaf, killable via REPRO_DECODE_FUSED=0,
    no active shard_fused mesh dispatch (the sharded per-op path owns
    Megatron partitioning; under a mesh with REPRO_SHARD_FUSED=0 the
    chain engages with GSPMD-replicated lowering), and the shape must
    pass the VMEM budget model (kernels/vmem.py).  ``moe=True`` prices
    the MoE back half (qkv + wo->norm launches; the expert-bank FFN
    launch has its own guard, :func:`decode_moe_ffn_enabled`) instead of
    the dense out-mlp launch.  Each decision is recorded, with its
    reason, in ``obs.routes`` under "decode_chain.*"."""
    leaf = decode_chain_leaf(policy)
    if leaf is None or leaf.mode != "amsim" or leaf.is_native:
        return obs.route("decode_chain", "refused.policy")
    if os.environ.get("REPRO_DECODE_FUSED", "1").lower() in ("0", "false"):
        return obs.route("decode_chain", "refused.env")
    from repro.distributed import shard_fused  # lazy: circular import
    if shard_fused.active_mesh(leaf) is not None:
        return obs.route("decode_chain", "refused.mesh")
    from repro.kernels import vmem
    mult = get_multiplier(leaf.multiplier)
    if moe:
        fits = vmem.moe_chain_fits(rows, d, k_attn, mult.mantissa_bits,
                                   mult=mult.name)
    else:
        fits = vmem.chain_fits(rows, d, k_attn, d_ff,
                               mult.mantissa_bits, mult=mult.name)
    return obs.route("decode_chain", "taken" if fits else "refused.vmem")


_MOE_FFN_SITES = ("wg", "wu", "wd")


def moe_ffn_leaf(policy: Numerics) -> NumericsPolicy | None:
    """The single leaf the stacked expert-bank launch would run wg/wu/wd
    under, or None when they resolve differently (the router site stays
    per-op either way, so it may differ freely)."""
    leaves = [policy.resolve(s) for s in _MOE_FFN_SITES]
    first = leaves[0]
    for leaf in leaves[1:]:
        if (leaf.mode, leaf.multiplier) != (first.mode, first.multiplier):
            return None
    return first


def decode_moe_ffn_enabled(policy: Numerics, E: int, C: int, d: int,
                           d_ff: int) -> bool:
    """Dispatch guard for the stacked expert-bank FFN launch
    (kernels/decode_chain.fused_moe_ffn).  Shares the chain's kill
    switch and mesh exclusion; the shape gate is vmem.moe_ffn_fits.
    models/moe.py asks on decode ticks only, with C the tick's row
    count, so the bank drops no token."""
    leaf = moe_ffn_leaf(policy)
    if leaf is None or leaf.mode != "amsim" or leaf.is_native:
        return False
    if os.environ.get("REPRO_DECODE_FUSED", "1").lower() in ("0", "false"):
        return False
    from repro.distributed import shard_fused  # lazy: circular import
    if shard_fused.active_mesh(leaf) is not None:
        return False
    from repro.kernels import vmem
    mult = get_multiplier(leaf.multiplier)
    return vmem.moe_ffn_fits(E, C, d, d_ff, mult.mantissa_bits,
                             mult=mult.name)


def decode_qkv_oracle(x, g1, wq, wk, wv, policy: Numerics, eps: float):
    """Unfused reference for the chain's front half: rmsnorm + three
    per-op projections, exactly what models/layers runs when the chain
    is off.  The fused forward is bit-tested against this, and the
    fused VJP recomputes through it."""
    h = rms_norm(x.astype(jnp.float32), g1, eps)
    return (policy_matmul(h, wq, policy, "qkv"),
            policy_matmul(h, wk, policy, "qkv"),
            policy_matmul(h, wv, policy, "qkv"))


def decode_out_mlp_oracle(x, attn, g2, wo, wg, wu, wd, policy: Numerics,
                          eps: float, bo=None, bd=None):
    """Unfused reference for the chain's back half: wo projection +
    residual + rmsnorm + swiglu FFN + residual, per-op.  Optional wo/wd
    epilogue biases are added before the residual, matching
    models/layers.linear's op order."""
    yo = policy_matmul(attn.astype(jnp.float32), wo, policy, "wo")
    if bo is not None:
        yo = yo + bo
    x1 = x.astype(jnp.float32) + yo
    h = rms_norm(x1, g2, eps)
    y = policy_matmul(
        jax.nn.silu(policy_matmul(h, wg, policy, "wg"))
        * policy_matmul(h, wu, policy, "wu"),
        wd, policy, "wd")
    if bd is not None:
        y = y + bd
    return x1 + y


def decode_wo_norm_oracle(x, attn, g2, wo, bo, policy: Numerics, eps: float):
    """Unfused reference for the MoE back half's shared prefix:
    x1 = x + (attn @ wo [+ bo]); h = rmsnorm(x1).  Returns (x1, h)."""
    yo = policy_matmul(attn.astype(jnp.float32), wo, policy, "wo")
    if bo is not None:
        yo = yo + bo
    x1 = x.astype(jnp.float32) + yo
    return x1, rms_norm(x1, g2, eps)


def decode_moe_ffn_oracle(buf, wg, wu, wd, policy: Numerics):
    """Unfused reference for the stacked expert-bank launch: exactly
    what models/mlp.ffn runs on the (E, C, d) capacity buffer without a
    mesh — three E-batched policy GEMMs (gemm3d bucket) under the
    wg/wu/wd sites.  Expert banks carry no biases (init_ffn default)."""
    return policy_matmul(
        jax.nn.silu(policy_matmul(buf, wg, policy, "wg"))
        * policy_matmul(buf, wu, policy, "wu"),
        wd, policy, "wd")


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def decode_qkv(x, g1, wq, wk, wv, policy: Numerics, eps: float):
    """rmsnorm(x; g1) + q/k/v projections in one persistent launch.

    x (rows, d); returns (q, k, v) f32.  Backward recomputes through
    :func:`decode_qkv_oracle` (jax.vjp), so each backward GEMM runs
    under the numerics the policy resolves for the qkv site's dx/dw
    passes — bit-identical to the per-op lowering's gradients.  Callers
    must have checked :func:`decode_chain_enabled`.
    """
    return _decode_qkv_fwd_impl(x, g1, wq, wk, wv, policy, eps)


def _decode_qkv_fwd_impl(x, g1, wq, wk, wv, policy, eps):
    from repro.kernels.decode_chain import fused_qkv_norm
    mult = get_multiplier(decode_chain_leaf(policy).multiplier)
    return _replicated(
        lambda *a: fused_qkv_norm(*a, _amsim_lut(mult), mult.mantissa_bits,
                                  eps=eps, mult=mult.name),
        x, g1, wq, wk, wv)


def _decode_qkv_fwd(x, g1, wq, wk, wv, policy, eps):
    out = _decode_qkv_fwd_impl(x, g1, wq, wk, wv, policy, eps)
    return out, (x, g1, wq, wk, wv)


def _decode_qkv_bwd(policy, eps, res, g):
    x, g1, wq, wk, wv = res
    _, vjp = jax.vjp(
        lambda *args: decode_qkv_oracle(*args, policy, eps),
        x, g1, wq, wk, wv)
    return vjp(tuple(c.astype(jnp.float32) for c in g))


decode_qkv.defvjp(_decode_qkv_fwd, _decode_qkv_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def decode_out_mlp(x, attn, g2, wo, wg, wu, wd, policy: Numerics,
                   eps: float):
    """wo projection + residual + rmsnorm + swiglu FFN + residual in one
    persistent launch.  x (rows, d) residual stream, attn (rows, H*dh).
    Backward recomputes through :func:`decode_out_mlp_oracle`.  Callers
    must have checked :func:`decode_chain_enabled`.
    """
    return _decode_out_mlp_fwd_impl(x, attn, g2, wo, wg, wu, wd, policy,
                                    eps)


def _decode_out_mlp_fwd_impl(x, attn, g2, wo, wg, wu, wd, policy, eps):
    from repro.kernels.decode_chain import fused_out_mlp
    mult = get_multiplier(decode_chain_leaf(policy).multiplier)
    return _replicated(
        lambda *a: fused_out_mlp(*a, _amsim_lut(mult), mult.mantissa_bits,
                                 eps=eps, mult=mult.name),
        x, attn, g2, wo, wg, wu, wd)


def _decode_out_mlp_fwd(x, attn, g2, wo, wg, wu, wd, policy, eps):
    out = _decode_out_mlp_fwd_impl(x, attn, g2, wo, wg, wu, wd, policy, eps)
    return out, (x, attn, g2, wo, wg, wu, wd)


def _decode_out_mlp_bwd(policy, eps, res, g):
    x, attn, g2, wo, wg, wu, wd = res
    _, vjp = jax.vjp(
        lambda *args: decode_out_mlp_oracle(*args, policy, eps),
        x, attn, g2, wo, wg, wu, wd)
    return vjp(g.astype(jnp.float32))


decode_out_mlp.defvjp(_decode_out_mlp_fwd, _decode_out_mlp_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def decode_out_mlp_b(x, attn, g2, wo, wg, wu, wd, bo, bd, policy: Numerics,
                     eps: float):
    """:func:`decode_out_mlp` with optional wo/wd epilogue biases (None
    when absent).  Biases are folded into the launch's accumulator
    epilogues — added before each residual, the per-op op order — and
    the bias-free call lowers the identical kernel (statically absent
    operands, not zero-valued ones, so no-bias outputs stay bitwise
    against the historical launch)."""
    return _decode_out_mlp_b_fwd_impl(x, attn, g2, wo, wg, wu, wd, bo, bd,
                                      policy, eps)


def _decode_out_mlp_b_fwd_impl(x, attn, g2, wo, wg, wu, wd, bo, bd,
                               policy, eps):
    from repro.kernels.decode_chain import fused_out_mlp
    mult = get_multiplier(decode_chain_leaf(policy).multiplier)
    return _replicated(
        lambda *a, bo, bd: fused_out_mlp(
            *a, _amsim_lut(mult), mult.mantissa_bits, eps=eps, bo=bo, bd=bd,
            mult=mult.name),
        x, attn, g2, wo, wg, wu, wd, bo=bo, bd=bd)


def _decode_out_mlp_b_fwd(x, attn, g2, wo, wg, wu, wd, bo, bd, policy, eps):
    out = _decode_out_mlp_b_fwd_impl(x, attn, g2, wo, wg, wu, wd, bo, bd,
                                     policy, eps)
    return out, (x, attn, g2, wo, wg, wu, wd, bo, bd)


def _decode_out_mlp_b_bwd(policy, eps, res, g):
    x, attn, g2, wo, wg, wu, wd, bo, bd = res
    _, vjp = jax.vjp(
        lambda *args: decode_out_mlp_oracle(*args[:7], policy, eps,
                                            bo=args[7], bd=args[8]),
        x, attn, g2, wo, wg, wu, wd, bo, bd)
    return vjp(g.astype(jnp.float32))


decode_out_mlp_b.defvjp(_decode_out_mlp_b_fwd, _decode_out_mlp_b_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def decode_wo_norm(x, attn, g2, wo, bo, policy: Numerics, eps: float):
    """The MoE back half's shared prefix in one persistent launch:
    x1 = x + (attn @ wo [+ bo]); h = rmsnorm(x1; g2); returns (x1, h).

    Same fold as :func:`decode_out_mlp`'s phase A (bit-tested against
    :func:`decode_wo_norm_oracle`); the router/top-k/scatter that
    consume h stay per-op in models/moe.py.  Backward recomputes through
    the oracle.  Callers must have checked
    ``decode_chain_enabled(..., moe=True)``.
    """
    return _decode_wo_norm_fwd_impl(x, attn, g2, wo, bo, policy, eps)


def _decode_wo_norm_fwd_impl(x, attn, g2, wo, bo, policy, eps):
    from repro.kernels.decode_chain import fused_wo_norm
    mult = get_multiplier(decode_chain_leaf(policy).multiplier)
    return _replicated(
        lambda *a, bo: fused_wo_norm(*a, _amsim_lut(mult), mult.mantissa_bits,
                                     eps=eps, bo=bo, mult=mult.name),
        x, attn, g2, wo, bo=bo)


def _decode_wo_norm_fwd(x, attn, g2, wo, bo, policy, eps):
    out = _decode_wo_norm_fwd_impl(x, attn, g2, wo, bo, policy, eps)
    return out, (x, attn, g2, wo, bo)


def _decode_wo_norm_bwd(policy, eps, res, g):
    x, attn, g2, wo, bo = res
    _, vjp = jax.vjp(
        lambda *args: decode_wo_norm_oracle(*args, policy, eps),
        x, attn, g2, wo, bo)
    return vjp(tuple(c.astype(jnp.float32) for c in g))


decode_wo_norm.defvjp(_decode_wo_norm_fwd, _decode_wo_norm_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def decode_moe_ffn(buf, wg, wu, wd, policy: Numerics):
    """Stacked expert-bank swiglu FFN in one persistent launch: buf is
    the scattered (E, C, d) capacity buffer, wg/wu (E, d, d_ff) and
    wd (E, d_ff, d) the expert banks.  Bit-identical to the E-batched
    per-op lowering (:func:`decode_moe_ffn_oracle` — the gemm3d folds
    are slaved to ``approx_gemm_batched``'s bucket); backward recomputes
    through the oracle.  Callers must have checked
    :func:`decode_moe_ffn_enabled`.
    """
    return _decode_moe_ffn_fwd_impl(buf, wg, wu, wd, policy)


def _decode_moe_ffn_fwd_impl(buf, wg, wu, wd, policy):
    from repro.kernels.decode_chain import fused_moe_ffn
    mult = get_multiplier(moe_ffn_leaf(policy).multiplier)
    return _replicated(
        lambda *a: fused_moe_ffn(*a, _amsim_lut(mult), mult.mantissa_bits,
                                 mult=mult.name),
        buf, wg, wu, wd)


def _decode_moe_ffn_fwd(buf, wg, wu, wd, policy):
    out = _decode_moe_ffn_fwd_impl(buf, wg, wu, wd, policy)
    return out, (buf, wg, wu, wd)


def _decode_moe_ffn_bwd(policy, res, g):
    buf, wg, wu, wd = res
    _, vjp = jax.vjp(
        lambda *args: decode_moe_ffn_oracle(*args, policy),
        buf, wg, wu, wd)
    return vjp(g.astype(jnp.float32))


decode_moe_ffn.defvjp(_decode_moe_ffn_fwd, _decode_moe_ffn_bwd)


def decode_fuse_attn_enabled(policy: Numerics, rows: int, d: int,
                             k_attn: int, d_ff: int, T: int, KV: int,
                             dh: int) -> bool:
    """Dispatch guard for collapsing the attention core INTO the
    back-half launch (three chain launches -> two,
    kernels/decode_chain.fused_attn_out_mlp).  On top of the chain's own
    guard (callers check :func:`decode_chain_enabled` first) this
    requires the attention sites to resolve to the SAME leaf as the
    chain sites (the launch bakes one LUT for all seven GEMMs), honours
    REPRO_ATTN_FUSED=0 (the attention core stays per-op / standalone)
    and its own kill switch REPRO_DECODE_FUSE_ATTN=0, and asks the VMEM
    budget model whether the K/V views fit next to the back half's
    working set in the single-KV-block bitwise regime
    (vmem.fuse_attention_ok)."""
    leaf = decode_chain_leaf(policy)
    if leaf is None or leaf.mode != "amsim" or leaf.is_native:
        return False
    aleaf = attention_fused_leaf(policy)
    if aleaf is None or (aleaf.mode, aleaf.multiplier) != \
            (leaf.mode, leaf.multiplier):
        return False
    if os.environ.get("REPRO_DECODE_FUSED", "1").lower() in ("0", "false"):
        return False
    if os.environ.get("REPRO_ATTN_FUSED", "1").lower() in ("0", "false"):
        return False
    if os.environ.get("REPRO_DECODE_FUSE_ATTN", "1").lower() in \
            ("0", "false"):
        return False
    from repro.kernels import vmem
    mult = get_multiplier(leaf.multiplier)
    return vmem.fuse_attention_ok(rows, d, k_attn, d_ff, rows, T, KV, dh,
                                  mult.mantissa_bits, mult=mult.name)


@partial(jax.custom_vjp, nondiff_argnums=(13, 14, 15, 16))
def decode_attn_out_mlp(x, q, k, v, q_pos, k_pos, g2, wo, wg, wu, wd,
                        bo, bd, policy: Numerics, eps: float,
                        causal: bool, window: int):
    """Attention core + the whole dense back half in ONE persistent
    launch (the chain's launches 2 and 3 collapsed).  x (rows, d)
    residual stream; q (B, 1, H, dh) RoPE'd queries; k/v (B, T, KV, dh)
    post-update cache views; positions shared or per-row as
    ``attend_einsum``.  Bit-identical to the 3-launch chain AND the
    per-op path in the guard's single-KV-block regime; backward
    recomputes through ``attend_einsum`` + :func:`decode_out_mlp_oracle`
    (jax.vjp), so gradients take exactly the per-op lowering.  Callers
    must have checked :func:`decode_fuse_attn_enabled`.
    """
    return _decode_attn_out_mlp_fwd_impl(x, q, k, v, q_pos, k_pos, g2, wo,
                                         wg, wu, wd, bo, bd, policy, eps,
                                         causal, window)


def _decode_attn_out_mlp_fwd_impl(x, q, k, v, q_pos, k_pos, g2, wo, wg, wu,
                                  wd, bo, bd, policy, eps, causal, window):
    from repro.kernels.decode_chain import fused_attn_out_mlp
    mult = get_multiplier(decode_chain_leaf(policy).multiplier)
    return _replicated(
        lambda *a, bo, bd: fused_attn_out_mlp(
            *a, _amsim_lut(mult), mult.mantissa_bits, eps=eps, causal=causal,
            window=int(window), bo=bo, bd=bd, mult=mult.name),
        x, q, k, v, q_pos, k_pos, g2, wo, wg, wu, wd, bo=bo, bd=bd)


def _decode_attn_out_mlp_fwd(x, q, k, v, q_pos, k_pos, g2, wo, wg, wu, wd,
                             bo, bd, policy, eps, causal, window):
    out = _decode_attn_out_mlp_fwd_impl(x, q, k, v, q_pos, k_pos, g2, wo,
                                        wg, wu, wd, bo, bd, policy, eps,
                                        causal, window)
    return out, (x, q, k, v, q_pos, k_pos, g2, wo, wg, wu, wd, bo, bd)


def _decode_attn_out_mlp_bwd(policy, eps, causal, window, res, g):
    x, q, k, v, q_pos, k_pos, g2, wo, wg, wu, wd, bo, bd = res
    B, S, H, dh = q.shape

    def f(x_, q_, k_, v_, g2_, wo_, wg_, wu_, wd_, bo_, bd_):
        a = attend_einsum(q_, k_, v_, q_pos, k_pos, policy,
                          causal=causal, window=window)
        return decode_out_mlp_oracle(x_, a.reshape(B * S, H * dh), g2_,
                                     wo_, wg_, wu_, wd_, policy, eps,
                                     bo=bo_, bd=bd_)

    _, vjp = jax.vjp(f, x, q, k, v, g2, wo, wg, wu, wd, bo, bd)
    dx, dq, dk, dv, dg2, dwo, dwg, dwu, dwd, dbo, dbd = \
        vjp(g.astype(jnp.float32))
    zero = lambda p: np.zeros(p.shape, jax.dtypes.float0)  # int positions
    return (dx, dq, dk, dv, zero(q_pos), zero(k_pos), dg2, dwo, dwg, dwu,
            dwd, dbo, dbd)


decode_attn_out_mlp.defvjp(_decode_attn_out_mlp_fwd, _decode_attn_out_mlp_bwd)
