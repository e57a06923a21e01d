"""Architecture + shape configuration schema.

Every assigned architecture is an ``ArchConfig`` (one module per arch in
this package); every workload cell is an (ArchConfig, ShapeConfig) pair.
Configs are frozen dataclasses — hashable, usable as static jit args.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                    # per-expert hidden width
    interleave: int = 1          # MoE every `interleave`-th layer (1 = all)
    n_shared_experts: int = 0    # llama4-style always-on shared expert(s)


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int                 # N
    head_dim: int = 64           # p
    expand: int = 2              # d_inner = expand * d_model
    conv_kernel: int = 4
    n_groups: int = 1
    chunk: int = 256             # SSD chunk length


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec
    n_layers: int
    d_model: int
    n_heads: int                 # 0 for attention-free
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0              # 0 -> d_model // n_heads
    qkv_bias: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    attn_every: int = 0          # hybrid: shared attn block every k layers
    # Mixer of each layer ("mamba" | "attention"), every layer with an MLP
    # (granite-4.0-h); the stack runs the first n_layers.  Empty: the
    # family decides.
    layer_types: tuple = ()
    n_enc_layers: int = 0        # encdec: encoder depth (n_layers = decoder)
    sliding_window: int = 0      # 0 = full attention
    rope_theta: float = 10000.0
    use_rope: bool = True        # False: no position embedding (NoPE)
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    act: str = "swiglu"          # swiglu | gelu
    # Modality frontend stubs: number of precomputed embedding positions
    # (audio frames / image patches) prepended to the token sequence.
    n_frontend_tokens: int = 0
    frontend: str = "none"       # none | audio | vision
    # Distribution hints (consumed by launch/ + distributed/):
    fsdp: bool = False           # shard leftover param dim over "data"
    remat: bool = True
    optimizer: str = "adamw"     # adamw | adafactor | sgdm
    scan_layers: bool = True
    scan_block: int = 1          # layers grouped per scan step (heterogeneous stacks)
    q_chunk: int = 1024          # attention query-chunk length (memory bound)
    unroll_attn_chunks: bool = False  # python-loop chunks (dry-run costing)
    # --- perf-iteration knobs (§Perf; off by default = paper-faithful) ---
    shard_attn_heads: bool = False   # repeat-KV full-head attention with
                                     # explicit head sharding over "model"
    constrain_logits: bool = False   # keep LM-head logits vocab-sharded
                                     # through the loss (vocab-parallel xent)
    cache_dtype: str = "float32"     # KV-cache storage dtype ("bfloat16"
                                     # halves decode HBM traffic)
    unshard_weights: bool = False    # FSDP: constrain weights to their
                                     # non-data-sharded spec at use (forces
                                     # ZeRO-3 all-gather instead of GSPMD's
                                     # batch-replicated partial contraction)
    mesh_data_axes: tuple = ("data",)  # axis names batch shards over (set
                                       # by launch/ for multi-pod meshes)

    @property
    def kinds(self) -> tuple:
        """The mixer kind of each layer that runs (``layer_types``)."""
        return tuple(self.layer_types[:self.n_layers])

    @property
    def head_dim(self) -> int:
        if self.d_head:
            return self.d_head
        return self.d_model // self.n_heads if self.n_heads else 0

    # ------------------------------------------------------------ counting
    def param_count(self) -> int:
        """Analytic parameter count (embeddings + layers + head)."""
        d, v = self.d_model, self.vocab
        n = v * d  # token embedding
        if not self.tie_embeddings:
            n += d * v  # LM head
        if self.layer_types:
            n += sum(self._attn_params() if k == "attention"
                     else self._ssm_params() for k in self.kinds)
            n += self.n_layers * (2 * d + self._ffn_params(self.d_ff))
        else:
            n += self.n_layers * self._layer_params()
        if self.n_enc_layers:
            n += self.n_enc_layers * self._enc_layer_params()
        if self.family == "hybrid" and self.attn_every:
            n += self._attn_params() + self._ffn_params(self.d_ff)
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed top-k experts)."""
        if self.family != "moe" or self.moe is None:
            return self.param_count()
        d, v, m = self.d_model, self.vocab, self.moe
        n = v * d + (0 if self.tie_embeddings else d * v)
        per_layer_dense = self._attn_params() + 2 * d
        moe_layers = self.n_layers // m.interleave
        dense_layers = self.n_layers - moe_layers
        n += dense_layers * (per_layer_dense + self._ffn_params(self.d_ff))
        active_ffn = (m.top_k + m.n_shared_experts) * self._ffn_params(m.d_ff)
        n += moe_layers * (per_layer_dense + active_ffn + d * m.n_experts)
        return n

    def _attn_params(self) -> int:
        d, dh = self.d_model, self.head_dim
        h, kv = self.n_heads, self.n_kv_heads
        n = d * h * dh + 2 * d * kv * dh + h * dh * d
        if self.qkv_bias:
            n += (h + 2 * kv) * dh
        return n

    def _ffn_params(self, d_ff: int) -> int:
        mult = 3 if self.act == "swiglu" else 2
        return mult * self.d_model * d_ff

    def _layer_params(self) -> int:
        d = self.d_model
        if self.family == "ssm":
            return self._ssm_params() + d
        if self.family == "hybrid":
            return self._ssm_params() + d
        n = self._attn_params() + 2 * d  # attn + 2 norms
        if self.family == "moe" and self.moe is not None:
            m = self.moe
            if self.n_layers % max(m.interleave, 1) == 0:
                pass
            # average params per layer across the interleave pattern
            moe_frac = 1.0 / m.interleave
            ffn = (1 - moe_frac) * self._ffn_params(self.d_ff)
            ffn += moe_frac * (
                m.n_experts * self._ffn_params(m.d_ff)
                + m.n_shared_experts * self._ffn_params(m.d_ff)
                + d * m.n_experts
            )
            return n + int(ffn)
        return n + self._ffn_params(self.d_ff)

    def _enc_layer_params(self) -> int:
        # encoder self-attn + decoder gains cross-attn; folded approximation:
        return self._attn_params() + 2 * self.d_model + self._ffn_params(self.d_ff)

    def _ssm_params(self) -> int:
        assert self.ssm is not None
        s, d = self.ssm, self.d_model
        d_in = s.expand * d
        nheads = d_in // s.head_dim
        n = d * (2 * d_in + 2 * s.n_groups * s.d_state + nheads)  # in_proj
        n += d_in * d  # out_proj
        n += (d_in + 2 * s.n_groups * s.d_state) * s.conv_kernel  # conv
        n += 2 * nheads + d_in  # A_log, D, dt_bias-ish
        return n


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# Populated by configs/__init__.py import side effects.
ARCH_REGISTRY: dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    ARCH_REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    import repro.configs  # noqa: F401  (ensure registry populated)

    if name not in ARCH_REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCH_REGISTRY)}")
    return ARCH_REGISTRY[name]


def reduced(cfg: ArchConfig, **overrides) -> ArchConfig:
    """Smoke-test variant: same family/topology, tiny dims."""
    base = dict(
        n_layers=min(cfg.n_layers, 2),
        d_model=128,
        n_heads=min(cfg.n_heads, 4) or 0,
        n_kv_heads=min(cfg.n_kv_heads, 2) or 0,
        d_ff=256 if cfg.d_ff else 0,
        vocab=512,
        d_head=32 if cfg.n_heads else 0,
        n_enc_layers=min(cfg.n_enc_layers, 2),
        n_frontend_tokens=min(cfg.n_frontend_tokens, 8),
        fsdp=False,
    )
    if cfg.moe is not None:
        base["moe"] = dataclasses.replace(
            cfg.moe, n_experts=min(cfg.moe.n_experts, 8),
            top_k=min(cfg.moe.top_k, 2), d_ff=64)
    if cfg.ssm is not None:
        base["ssm"] = dataclasses.replace(
            cfg.ssm, d_state=16, head_dim=16, chunk=8)
    if cfg.attn_every:
        base["attn_every"] = 2
        base["n_layers"] = 4
    if cfg.layer_types:
        # One of each kind between Mamba layers: both scans and the
        # attention layer between them.
        base["layer_types"] = ("mamba", "attention", "mamba")
        base["n_layers"] = 3
    base.update(overrides)
    return dataclasses.replace(cfg, name=cfg.name + "-smoke", **base)
