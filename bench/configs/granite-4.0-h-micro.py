"""granite-4.0-h-micro as the system under test: the program's stack of
Mamba-2 and attention layers (``layer_types``) served through the
continuous-batching engine, built from the sizes in
``granite-4.0-h-micro.json`` with the weights the reference module makes."""
from __future__ import annotations


def arch(sizes: dict):
    from repro.configs.base import ArchConfig, SSMConfig
    d, heads, head = (sizes["hidden_size"], sizes["mamba_n_heads"],
                      sizes["mamba_d_head"])
    if sizes["mamba_expand"] * d != heads * head:
        raise ValueError(f"mamba_expand x hidden_size ({sizes['mamba_expand']} "
                         f"x {d}) is not mamba_n_heads x mamba_d_head "
                         f"({heads} x {head})")
    return ArchConfig(
        name="granite-4.0-h-micro", family="hybrid",
        n_layers=sizes["num_hidden_layers"], d_model=d,
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["shared_intermediate_size"], vocab=sizes["vocab_size"],
        tie_embeddings=sizes["tie_word_embeddings"],
        ssm=SSMConfig(d_state=sizes["mamba_d_state"], head_dim=head,
                      expand=sizes["mamba_expand"],
                      conv_kernel=sizes["mamba_d_conv"],
                      n_groups=sizes["mamba_n_groups"],
                      chunk=sizes["mamba_chunk_size"]),
        layer_types=tuple(sizes["layer_types"]),
        use_rope=sizes["position_embedding_type"] != "nope",
        rope_theta=sizes["rope_theta"], norm_eps=sizes["rms_norm_eps"],
        act="swiglu", qkv_bias=sizes["attention_bias"])


def policy(numerics: str):
    """``mode:multiplier`` as the program's numerics policy."""
    from repro.core.policy import NumericsPolicy
    mode, mult = numerics.split(":")
    return NumericsPolicy(mode=mode, multiplier=mult)


def serve_engine(sizes: dict, traffic: dict, numerics: str, params):
    from repro.serve.scheduler import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        arch(sizes), {"served": policy(numerics)}, params,
        max_len=traffic["prompt_len"][1] + traffic["output_len"][1],
        capacity=traffic["capacity"], page_size=traffic["page_size"])


# ------------------------------------------------------------------ work
def matmul_params(sizes: dict) -> dict:
    """Weights that multiply a token's activations: the Mamba layers' in
    and out projections, the attention projections, every layer's MLP and
    the tied head (the embedding lookup and the depthwise conv multiply no
    weight matrix)."""
    d, f = sizes["hidden_size"], sizes["shared_intermediate_size"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = d // h
    d_in = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    proj = 2 * d_in + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"] \
        + sizes["mamba_n_heads"]
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    mamba = d * proj + d_in * d
    attention = d * h * dh + 2 * d * kv * dh + h * dh * d
    return {"mamba": kinds.count("mamba") * mamba,
            "attention": kinds.count("attention") * attention,
            "mlp": len(kinds) * 3 * d * f,
            "head": d * sizes["vocab_size"]}


def serve_flops_per_token(sizes: dict) -> float:
    """2 N per processed token (prompt or decoded); the state update's
    products and the attention scores are not counted."""
    return 2.0 * sum(matmul_params(sizes).values())
