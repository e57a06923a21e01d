"""Sharded fused-LUT execution: shard_map dispatch for the Pallas kernels.

GSPMD cannot partition a ``pallas_call`` (on the chip Mosaic refuses),
so outside this module a kernel under a mesh runs replicated: operands
all-gathered, the full kernel on every device (``ops._replicated``;
correct, but the mesh buys nothing).  This module makes ``mode="amsim"`` genuinely
parallel by wrapping the three fused kernel families in explicit
``shard_map`` dispatch driven by the Megatron/FSDP rules of
``distributed/sharding.py``:

  * **column-parallel matmul** (wq/wk/wv, wg/wu, LM head — output dim
    over "model"): every shard runs the LUT-GEMM kernel on its weight
    column block; no forward collective.  Backward: dx psums partials
    over "model", dw psums over the data axes iff the batch is sharded.
  * **row-parallel matmul** (wo, wd — input dim over "model"): per-shard
    kernel on the k-block, then one ``psum`` over "model" *outside* the
    kernel (the Megatron f/g pair).  Backward: dx is shard-local, dw
    psums over the data axes iff the batch is sharded.
  * **attention** (``approx_attention_fused``): KV heads shard over
    "model", batch over the data axes ("data" / "pod" x "data"); each
    shard runs the one-launch kernel on its head/batch block.  All
    operands mention every mesh axis, so plain autodiff through the
    shard_map is exact (the kernel's custom VJP recomputes per shard).
  * **conv2d** (``approx_conv2d_fused``): batch over the data axes,
    weights replicated; backward runs the fused dw/dx kernels per shard
    and psums dw over the data axes.

The data-parallel gradient psums placed here are the same all-reduce
``distributed/compression.py`` compresses — ``compressed_psum`` slots in
for ``jax.lax.psum`` in the backward bodies unchanged.

Numerics contract (docs/numerics.md has the full table): sharding only
ever splits *parallel* grid axes (batch, heads, output columns), so
column-parallel / attention / conv forward AND their shard-local
gradients are bit-identical to the single-device fused kernels.  The
collectives (row-parallel forward psum, column-parallel dx psum,
data-axis dw psum) reassociate the FP32 accumulation at shard
boundaries: those outputs are bit-identical to a single-device *k-split
oracle* (the same per-shard kernels + an ordered sum) and agree with the
unsplit kernel to FP32 reassociation error (tests/test_sharded_fused.py
pins both).

LUT invariant: the mantissa-product LUT is a trace-time constant closed
over by every shard_map body, i.e. replicated — ``P(None)`` — on every
device (64 KiB canonical / 32 KiB packed; sharding a table this small
would trade a broadcast for a gather per *multiply*).  Nothing in this
module ever gives the LUT a non-trivial PartitionSpec.

Per-site numerics: every wrapper takes a flat policy or a PolicyTable
plus the call's ``site`` label and resolves the per-pass leaves at
trace time — the fwd leaf inside the shard_map bodies, the dx/dw
leaves inside the custom VJPs — so heterogeneous tables survive the
sharded dispatch with the collectives unchanged (they are
pass-independent).  The sharded path engages on the *forward* leaf
being amsim; see docs/policies.md for the mixed-pass fallback rules.

Kill switch: ``REPRO_SHARD_FUSED=0`` disables the dispatch entirely —
``mode="amsim"`` then falls back to GSPMD's replicated-kernel lowering
(see docs/configuration.md for every ``REPRO_*`` knob).
"""
from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AbstractMesh, Mesh, PartitionSpec as P

from repro.core.policy import Numerics, NumericsPolicy
from repro.kernels.ops import (_conv_bwd, _conv_fwd_impl, _matmul_nograd,
                               fused_attention_enabled, gemm_tag,
                               policy_attention)

_KINDS = ("column", "row")


def env_enabled() -> bool:
    """REPRO_SHARD_FUSED kill switch (default on; docs/configuration.md)."""
    return os.environ.get("REPRO_SHARD_FUSED", "1").lower() not in ("0", "false")


def current_mesh() -> AbstractMesh | None:
    """The ambient ``jax.set_mesh`` context's (abstract) mesh, or None.

    Read at trace time: launch/train.py, launch/cells.py (via dryrun)
    and serve/engine.py all trace their step functions inside the mesh
    context, which is what routes their model code through this module.
    """
    m = jax.sharding.get_abstract_mesh()
    if m.empty or m.size <= 1:
        return None
    return m


def active_mesh(leaf: NumericsPolicy) -> Mesh | None:
    """The mesh to shard fused kernels over, or None when the dispatch
    must not engage (wrong mode, kill switch, no/trivial mesh, no
    "model" axis).  ``leaf`` is a flat policy or an already-resolved
    per-site leaf — the *forward* leaf decides whether the sharded
    dispatch engages (see docs/policies.md for the mixed-pass rules)."""
    if leaf.mode != "amsim" or leaf.is_native:
        return None
    if not env_enabled():
        return None
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    return mesh


# ---------------------------------------------------------------- helpers
def _daxes(mesh: Mesh):
    """Non-"model" axis names as a tuple (may be empty)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def _dsize(mesh: Mesh) -> int:
    return math.prod(mesh.shape[a] for a in _daxes(mesh))


def _msize(mesh: Mesh) -> int:
    return mesh.shape["model"]


def _batch_entry(mesh: Mesh, dim: int):
    """Spec entry for a leading batch dim: the data axes when they divide
    it, else None (replicate — small/indivisible batches still get TP)."""
    daxes = _daxes(mesh)
    if not daxes:
        return None
    if dim % _dsize(mesh) == 0 and dim >= _dsize(mesh):
        return daxes if len(daxes) > 1 else daxes[0]
    return None


def _lead_spec(mesh: Mesh, ndim: int, bentry, tail):
    """P(bentry, None, ..., *tail) for an ndim-rank operand."""
    return P(*((bentry,) + (None,) * (ndim - 1 - len(tail)) + tuple(tail)))


def _swap(x):
    return jnp.swapaxes(x, -1, -2)


def _dw_psum(x, g, leaf_dw, mesh, sx, so, sw, bentry, site):
    """Weight gradient shared by both matmul roles: fold every batch row
    into the contraction (dw = x_flat^T @ g_flat, ops._mm_bwd's weight
    formula) per shard under the resolved ``dw`` leaf, psum over the
    data axes iff those rows were sharded.  One definition so the
    column/row backward paths can never diverge."""
    daxes = _daxes(mesh)

    def dw_body(xs, gs):
        k, n = xs.shape[-1], gs.shape[-1]
        dws = _matmul_nograd(xs.reshape(-1, k).T, gs.reshape(-1, n), leaf_dw,
                             gemm_tag(site, "dw"))
        return jax.lax.psum(dws, daxes) if bentry is not None else dws

    return shard_map(dw_body, mesh=mesh, in_specs=(sx, so), out_specs=sw,
                     check_vma=False)(x, g)


# ================================================================= matmul
def matmul_supported(kind: str, x_shape, w_shape, mesh: Mesh) -> bool:
    """Whether the (x @ w) call can take the sharded fused path.

    Requires a 2-D weight whose parallel dim divides the "model" axis;
    x must carry at least a (m, k) matrix (leading dims are batch).
    3-D stacked weights (MoE expert banks) fall back to the GSPMD
    batched engine.
    """
    if kind not in _KINDS or len(w_shape) != 2 or len(x_shape) < 2:
        return False
    msize = _msize(mesh)
    k, n = w_shape
    if x_shape[-1] != k:
        return False
    if kind == "column":
        return n % msize == 0 and n >= msize
    return k % msize == 0 and k >= msize  # row


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def column_parallel_matmul(x, w, policy: Numerics, mesh: Mesh,
                           site: str | None = None):
    """x (..., m, k) @ w (k, n) with n sharded over "model".

    Forward is collective-free: each shard's LUT kernel computes its
    column block bit-identically to the single-device kernel (k is never
    split).  The custom VJP places the Megatron collectives explicitly —
    autodiff through a ``check_vma=False`` shard_map would silently drop
    the psum over unmentioned mesh axes (dw's data-axis reduction).
    ``site`` resolves the per-pass leaves (fwd here, dx/dw in the VJP);
    the collectives themselves are pass-independent.
    """
    return _col_fwd(x, w, policy, mesh, site)[0]


def _col_specs(mesh, xdim, bentry):
    sx = _lead_spec(mesh, xdim, bentry, (None,))
    so = _lead_spec(mesh, xdim, bentry, ("model",))
    return sx, P(None, "model"), so


def _col_fwd(x, w, policy, mesh, site=None):
    leaf = policy.resolve(site)
    bentry = _batch_entry(mesh, x.shape[0]) if x.ndim > 2 else None
    sx, sw, so = _col_specs(mesh, x.ndim, bentry)
    tag = gemm_tag(site, "fwd")
    out = shard_map(lambda xs, ws: _matmul_nograd(xs, ws, leaf, tag),
                    mesh=mesh, in_specs=(sx, sw), out_specs=so,
                    check_vma=False)(x, w)
    return out, (x, w)


def _col_bwd(policy, mesh, site, res, g):
    x, w = res
    leaf_dx = policy.resolve(site, pass_="dx")
    leaf_dw = policy.resolve(site, pass_="dw")
    g = g.astype(jnp.float32)
    bentry = _batch_entry(mesh, x.shape[0]) if x.ndim > 2 else None
    sx, sw, so = _col_specs(mesh, x.ndim, bentry)

    def dx_body(gs, ws):
        # contraction over the model-sharded n: partial per shard -> psum
        return jax.lax.psum(
            _matmul_nograd(gs, _swap(ws), leaf_dx, gemm_tag(site, "dx")),
            "model")

    dx = shard_map(dx_body, mesh=mesh, in_specs=(so, sw), out_specs=sx,
                   check_vma=False)(g, w)
    dw = _dw_psum(x, g, leaf_dw, mesh, sx, so, sw, bentry, site)
    return dx.reshape(x.shape), dw.reshape(w.shape)


column_parallel_matmul.defvjp(_col_fwd, _col_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def row_parallel_matmul(x, w, policy: Numerics, mesh: Mesh,
                        site: str | None = None):
    """x (..., m, k) @ w (k, n) with k sharded over "model".

    Each shard's kernel contracts its k block; the single ``psum`` over
    "model" happens OUTSIDE the kernel (the Megatron g collective).
    This is the one forward op whose output reassociates FP32 adds at
    shard boundaries — bit-identical to the k-split oracle, within
    reassociation error of the unsplit kernel (docs/numerics.md).
    """
    return _row_fwd(x, w, policy, mesh, site)[0]


def _row_specs(mesh, xdim, bentry):
    sx = _lead_spec(mesh, xdim, bentry, ("model",))
    so = _lead_spec(mesh, xdim, bentry, (None,))
    return sx, P("model", None), so


def _overlap_setting(n: int):
    """Parse REPRO_OVERLAP_PSUM (docs/configuration.md, runbook in
    docs/distributed.md): how the row-parallel forward psum is pipelined
    so layer *l*'s reduce overlaps the next block's compute.

      * ``auto`` (default) — chunk the psum 4 ways when the output width
        allows it (n >= 512 and divisible), else the single psum.
      * integer N — chunk N ways (falls back to 1 when N doesn't divide
        n; the ``decode_chain`` autotune namespace's ``overlap`` knob is
        applied by exporting its winner here).
      * ``ring`` — ppermute-pipelined all-reduce in fixed shard-index
        order (bitwise-deterministic; see ``_ring_psum``).

    Chunked mode splits w's OUTPUT columns, so every output element's
    model-axis sum is computed exactly as before — bit-identical to the
    single psum as long as both column widths resolve to the same GEMM
    fold (always true under the default/hermetic autotune cache; a
    tuned cache that splits the n buckets may reassociate).  Ring mode
    accumulates the cross-device sum in fixed shard-index order —
    bitwise-deterministic, and bitwise-equal to the single psum on a
    two-device model axis (FP add is commutative).
    """
    raw = os.environ.get("REPRO_OVERLAP_PSUM", "auto").strip().lower()
    if raw == "ring":
        return "ring"
    if raw in ("", "auto"):
        return 4 if n >= 512 and n % 4 == 0 else 1
    try:
        c = int(raw)
    except ValueError:
        return 1
    return c if c > 1 and n % c == 0 else 1


def _ring_psum(part, D: int, axis_name: str = "model"):
    """ppermute-pipelined all-reduce of ``part`` over the mesh axis in
    **fixed shard-index order**: the partial sums are accumulated
    0 + 1 + ... + (D-1) regardless of which device computes, so the
    result is bitwise-deterministic across runs, topologies and XLA
    collective schedules — the property REPRO_OVERLAP_PSUM=ring buys.
    (On a two-device axis the order coincides with any psum order up to
    FP-add commutativity, so ring is additionally bitwise against the
    single-psum baseline there; tests/test_shard_fused.py asserts it.)

    Reduce leg (D-1 hops): the accumulator walks the ring forward and
    each device folds its shard in AT ITS INDEX TURN via a select — no
    arithmetic happens on non-adding devices, so there is no -0.0 or
    rounding hazard from dummy adds.  Broadcast leg (D-1 hops): device
    D-1's finished sum walks the same ring.  Each hop streams the whole
    tensor (more wire bytes than a reduce-scatter ring), but every hop
    still overlaps the next block's compute; determinism, not minimal
    bandwidth, is this mode's contract (docs/configuration.md)."""
    if D <= 1:
        return part
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % D) for i in range(D)]
    acc = jnp.where(idx == 0, part, jnp.zeros_like(part))
    for s in range(1, D):
        acc = jax.lax.ppermute(acc, axis_name, fwd)
        acc = jnp.where(idx == s, acc + part, acc)
    # device D-1 now holds sum(part[0..D-1]) in shard-index order
    buf = acc
    for s in range(1, D):
        buf = jax.lax.ppermute(buf, axis_name, fwd)
        acc = jnp.where(idx == (D - 1 + s) % D, buf, acc)
    return acc


def _row_fwd(x, w, policy, mesh, site=None):
    leaf = policy.resolve(site)
    bentry = _batch_entry(mesh, x.shape[0]) if x.ndim > 2 else None
    sx, sw, so = _row_specs(mesh, x.ndim, bentry)
    overlap = _overlap_setting(w.shape[-1])
    tag = gemm_tag(site, "fwd")

    def body(xs, ws):
        if overlap == "ring":
            return _ring_psum(_matmul_nograd(xs, ws, leaf, tag), _msize(mesh))
        if overlap == 1:
            return jax.lax.psum(_matmul_nograd(xs, ws, leaf, tag), "model")
        # Chunked psum: GEMM chunk i's reduce is issued as soon as its
        # columns finish, so XLA's async collectives overlap chunk i's
        # wire time with chunk i+1's compute (and, across layers, the
        # tail chunks with the next block's kernels).
        step = ws.shape[-1] // overlap
        outs = [
            jax.lax.psum(
                _matmul_nograd(xs, ws[..., i * step:(i + 1) * step], leaf,
                               tag),
                "model")
            for i in range(overlap)
        ]
        return jnp.concatenate(outs, axis=-1)

    out = shard_map(body, mesh=mesh, in_specs=(sx, sw), out_specs=so,
                    check_vma=False)(x, w)
    return out, (x, w)


def _row_bwd(policy, mesh, site, res, g):
    x, w = res
    leaf_dx = policy.resolve(site, pass_="dx")
    leaf_dw = policy.resolve(site, pass_="dw")
    g = g.astype(jnp.float32)
    bentry = _batch_entry(mesh, x.shape[0]) if x.ndim > 2 else None
    sx, sw, so = _row_specs(mesh, x.ndim, bentry)

    def dx_body(gs, ws):
        # w's k rows live on this shard: dx block is shard-local, exact
        return _matmul_nograd(gs, _swap(ws), leaf_dx, gemm_tag(site, "dx"))

    dx = shard_map(dx_body, mesh=mesh, in_specs=(so, sw), out_specs=sx,
                   check_vma=False)(g, w)
    dw = _dw_psum(x, g, leaf_dw, mesh, sx, so, sw, bentry, site)
    return dx.reshape(x.shape), dw.reshape(w.shape)


row_parallel_matmul.defvjp(_row_fwd, _row_bwd)


def parallel_matmul(x, w, policy: Numerics, kind: str | None,
                    site: str | None = None):
    """Model-layer dispatch point: the sharded fused kernel when active
    and supported, ``policy_matmul`` (single-device kernel or GSPMD)
    otherwise.  ``kind`` is the layer's Megatron role, mirroring
    ``sharding._RULES``: "column" (wq/wk/wv, wg/wu, head) or "row"
    (wo, wd); ``site`` is the numerics site label resolved per pass.
    The sharded path engages on the *forward* leaf — a table whose fwd
    leaf is not amsim falls back to policy_matmul (its amsim backward
    leaves then lower through GSPMD's replicated kernels)."""
    from repro.kernels.ops import policy_matmul  # runtime: avoid stale ref

    if kind is not None:
        mesh = active_mesh(policy.resolve(site))
        if mesh is not None and matmul_supported(kind, x.shape, w.shape, mesh):
            fn = (column_parallel_matmul if kind == "column"
                  else row_parallel_matmul)
            return fn(x, w, policy, mesh, site)
    return policy_matmul(x, w, policy, site)


# ============================================================== attention
def attention_supported(policy: Numerics, mesh: Mesh, q_shape,
                        k_shape, *, causal: bool, window: int) -> bool:
    """Whether the fused one-launch attention kernel can run per shard:
    KV heads divide "model", batch divides the data axes (or there are
    none — with a data axis an indivisible batch falls back, because the
    plain-autodiff path needs every operand to mention every mesh axis),
    and the per-shard shape passes the kernel's own VMEM guard +
    REPRO_ATTN_FUSED gate."""
    B, S, H, dh = q_shape
    T, KV = k_shape[1], k_shape[2]
    msize, dsize = _msize(mesh), _dsize(mesh)
    if KV % msize or H % KV:
        return False
    if dsize > 1 and (B % dsize or B < dsize):
        return False
    bl = B // dsize if dsize > 1 else B
    lq = (bl, S, H // msize, dh)
    lk = (bl, T, KV // msize, dh)
    return fused_attention_enabled(policy, lq, lk, causal=causal,
                                   window=window)


def sharded_attention(q, k, v, q_pos, k_pos, policy: Numerics, *,
                      causal: bool, window: int, mesh: Mesh):
    """Fused attention with KV heads over "model", batch over the data
    axes.  Heads and batch are embarrassingly parallel in the kernel
    grid, so forward and VJP are bit-identical to the single-device
    fused kernel (no collectives at all; the VJP recompute runs the
    einsum oracle on each shard's head/batch block).  Callers must have
    checked :func:`attention_supported`."""
    bentry = _batch_entry(mesh, q.shape[0])
    sq = P(bentry, None, "model", None)

    def body(qs, ks, vs, qp, kp):
        return policy_attention(qs, ks, vs, qp, kp, policy, causal, window)

    return shard_map(body, mesh=mesh,
                     in_specs=(sq, sq, sq, P(None), P(None)),
                     out_specs=sq, check_vma=False)(q, k, v, q_pos, k_pos)


# ================================================================= conv2d
def conv_supported(policy: Numerics, mesh: Mesh, x_shape) -> bool:
    """Batch-parallel conv: N must shard over the data axes (weights are
    replicated; "model" sharding of channels is out of scope for the
    vision stack)."""
    dsize = _dsize(mesh)
    return dsize > 1 and x_shape[0] % dsize == 0 and x_shape[0] >= dsize


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def sharded_conv2d(x, w, stride: int, padding, policy: Numerics,
                   mesh: Mesh):
    """NHWC conv with N sharded over the data axes; each shard runs the
    fused implicit-GEMM kernels (fwd, dw, dx) on its batch block.  dw
    sums over batch, so the backward psums it across the data axes —
    forward and dx are bit-identical to single device, dw to the
    batch-split oracle."""
    return _sconv_fwd(x, w, stride, padding, policy, mesh)[0]


def _sconv_specs(mesh, bentry):
    return P(bentry, None, None, None), P(None, None, None, None)


def _sconv_fwd(x, w, stride, padding, policy, mesh):
    bentry = _batch_entry(mesh, x.shape[0])
    sx, sw = _sconv_specs(mesh, bentry)
    out = shard_map(lambda xs, ws: _conv_fwd_impl(xs, ws, stride, padding,
                                                  policy),
                    mesh=mesh, in_specs=(sx, sw), out_specs=sx,
                    check_vma=False)(x, w)
    return out, (x, w)


def _sconv_bwd(stride, padding, policy, mesh, res, g):
    x, w = res
    bentry = _batch_entry(mesh, x.shape[0])
    sx, sw = _sconv_specs(mesh, bentry)
    daxes = _daxes(mesh)

    def body(xs, ws, gs):
        dxs, dws = _conv_bwd(stride, padding, policy, (xs, ws), gs)
        if bentry is not None:
            dws = jax.lax.psum(dws, daxes)
        return dxs, dws

    return shard_map(body, mesh=mesh, in_specs=(sx, sw, sx),
                     out_specs=(sx, sw), check_vma=False)(x, w, g)


sharded_conv2d.defvjp(_sconv_fwd, _sconv_bwd)


def parallel_conv2d(x, w, stride: int, padding, policy: Numerics):
    """Conv dispatch point: batch-sharded fused kernels when active,
    ``ops.approx_conv2d`` otherwise.  Engages on the "conv" site's
    forward leaf; per-pass resolution happens inside the conv VJP."""
    from repro.kernels.ops import approx_conv2d

    mesh = active_mesh(policy.resolve("conv"))
    if mesh is not None and conv_supported(policy, mesh, x.shape):
        return sharded_conv2d(x, w, stride, padding, policy, mesh)
    return approx_conv2d(x, w, stride, padding, policy)
