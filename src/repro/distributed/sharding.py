"""Sharding rules: param/optimizer/batch/cache PartitionSpecs.

Megatron-style tensor parallelism over the "model" axis:
  * column-parallel (wq/wk/wv, wg/wu, in_proj): output dim over "model"
  * row-parallel   (wo, wd, out_proj):          input  dim over "model"
  * embeddings / LM head: vocab over "model"
  * MoE experts: expert dim over "model" (expert parallelism)
  * Mamba heads (A_log, D, dt_bias, conv channels): over "model"

FSDP (cfg.fsdp): the *other* matrix dim additionally shards over "data"
(ZeRO-3 style — XLA inserts all-gathers on use, reduce-scatters on grad).
Optimizer state inherits param specs (adafactor factors drop the
corresponding reduced dim).  Batch shards over every non-"model" axis
("pod" x "data" on the multi-pod mesh).
"""
from __future__ import annotations

import re

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def data_axes(mesh: Mesh):
    """All non-model axes, as a tuple usable in a PartitionSpec entry."""
    axes = tuple(a for a in mesh.axis_names if a != "model")
    return axes if len(axes) > 1 else axes[0]


def batch_pspec(mesh: Mesh) -> P:
    return P(data_axes(mesh))


# (regex on "/"-joined path) -> (last-dims spec builder)
# `F` placeholder = fsdp axis ("data" when cfg.fsdp else None).
_RULES = [
    (r"experts/w[gu]/w$", ("model", "F", None)),   # (E, d, f): EP + fsdp(d)
    (r"experts/wd/w$", ("model", None, "F")),      # (E, f, d)
    (r"router/w$", (None, None)),                  # replicate router
    (r"(wq|wk|wv|wg|wu)/w$", ("F", "model")),      # column-parallel
    (r"(wo|wd)/w$", ("model", "F")),               # row-parallel
    (r"in_proj/w$", ("F", "model")),
    (r"out_proj/w$", ("model", "F")),
    (r"(wq|wk|wv|wg|wu|in_proj)/b$", ("model",)),
    (r"(wo|wd|out_proj)/b$", (None,)),
    (r"embed/emb$", ("model", "F")),               # vocab-parallel embedding
    (r"head/w$", ("F", "model")),
    (r"head/b$", ("model",)),
    (r"conv_w$", (None, "model")),
    (r"conv_b$", ("model",)),
    (r"(A_log|D|dt_bias)$", ("model",)),
    (r"(norm|n1|n2|n3|final_norm|enc_norm)/(g|b)$", (None,)),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def _fix_divisibility(spec_entries, shape, mesh: Mesh):
    """Drop/relocate axes whose size does not divide the dim.

    If dim d's assigned axis does not divide shape[d], try to move that
    axis to another unassigned dim (preferring trailing dims) that DOES
    divide — e.g. a 49155-vocab embedding shards its d_model dim instead.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = list(spec_entries)

    def axis_size(a):
        if a is None:
            return 1
        if isinstance(a, tuple):
            n = 1
            for x in a:
                n *= sizes[x]
            return n
        return sizes[a]

    for i, a in enumerate(entries):
        if a is None:
            continue
        if shape[i] % axis_size(a) == 0:
            continue
        entries[i] = None
        for j in range(len(entries) - 1, -1, -1):
            if j == i or entries[j] is not None:
                continue
            if shape[j] % axis_size(a) == 0:
                entries[j] = a
                break
    return tuple(entries)


def lm_param_pspecs(params, cfg: ArchConfig, mesh: Mesh | None = None):
    """PartitionSpec tree matching ``params`` (stacked layer dims -> None)."""
    fsdp = "data" if cfg.fsdp else None

    def spec_for(path, leaf):
        s = _path_str(path)
        for pat, tail in _RULES:
            if re.search(pat, s):
                tail = tuple(fsdp if t == "F" else t for t in tail)
                lead = (None,) * (leaf.ndim - len(tail))
                entries = lead + tail
                if mesh is not None:
                    entries = _fix_divisibility(entries, leaf.shape, mesh)
                return P(*entries)
        return P()  # replicate by default (norm scales, scalars)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def opt_state_pspecs(opt_name: str, param_specs):
    """Optimizer-state spec tree mirroring ``Optimizer.init`` structures."""
    if opt_name == "adamw":
        return {"m": param_specs, "v": param_specs, "step": P()}
    if opt_name == "sgdm":
        return {"mu": param_specs, "step": P()}
    if opt_name == "adafactor":
        def leaf(spec):
            parts = tuple(spec)
            if len(parts) >= 2:
                return {"r": P(*parts[:-1]), "c": P(*(parts[:-2] + parts[-1:]))}
            return {"v": spec}
        return {"f": jax.tree.map(leaf, param_specs,
                                  is_leaf=lambda x: isinstance(x, P)),
                "step": P()}
    raise ValueError(opt_name)


def cache_pspecs(caches, mesh: Mesh, batch: int):
    """Decode-cache specs.  Batch shards over data axes when divisible;
    otherwise (batch=1 long-context) the sequence dim shards (SP)."""
    daxes = data_axes(mesh)
    dsize = 1
    for a in mesh.axis_names:
        if a != "model":
            dsize *= mesh.shape[a]
    batch_sharded = batch % dsize == 0 and batch >= dsize

    def spec_for(path, leaf):
        s = _path_str(path)
        if re.search(r"pool_(k|v)$", s) and leaf.ndim >= 4:
            # Paged serving pools (L?, n_pages, page_size, KV, dh): KV
            # heads over "model", matching the ring layout above so the
            # einsum decode path contracts without resharding.  Pages are
            # NOT data-sharded: any slot's page table may name any page,
            # so the pool must be addressable from every data shard.
            tail = (None, None, "model", None)
            lead = (None,) * (leaf.ndim - 4)
            return P(*_fix_divisibility(lead + tail, leaf.shape, mesh))
        if re.search(r"(^|/)(k|v)$", s) and leaf.ndim >= 4:
            # (L?, B, T, KV, dh): KV heads over model — the same layout
            # the sharded fused attention kernel consumes (shard_fused:
            # KV over "model", batch over data), so decode steps never
            # reshard the cache.  When KV does not divide the model axis
            # _fix_divisibility relocates the axis (typically onto dh).
            if batch_sharded:
                tail = (daxes, None, "model", None)
            else:
                tail = (None, daxes, "model", None)  # SP over cache length
            lead = (None,) * (leaf.ndim - 4)
            return P(*_fix_divisibility(lead + tail, leaf.shape, mesh))
        if re.search(r"ssm$", s) and leaf.ndim >= 3:
            # (L?, B, N, nh * p): heads over model
            tail = (daxes if batch_sharded else None, None, "model")
            lead = (None,) * (leaf.ndim - 3)
            return P(*_fix_divisibility(lead + tail, leaf.shape, mesh))
        if re.search(r"conv$", s) and leaf.ndim >= 3:
            tail = (daxes if batch_sharded else None, None, "model")
            lead = (None,) * (leaf.ndim - 3)
            return P(*_fix_divisibility(lead + tail, leaf.shape, mesh))
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, caches)


def to_shardings(tree_of_pspecs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_pspecs,
                        is_leaf=lambda x: isinstance(x, P))
