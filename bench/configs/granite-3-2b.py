"""granite-3-2b as the system under test: the program's dense decoder,
training step and continuous-batching engine, built from the sizes in
``granite-3-2b.json`` with the weights the reference module makes."""
from __future__ import annotations


def arch(sizes: dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name="granite-3-2b", family="dense",
        n_layers=sizes["num_hidden_layers"], d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["intermediate_size"], vocab=sizes["vocab_size"],
        tie_embeddings=sizes["tie_word_embeddings"],
        rope_theta=sizes["rope_theta"], norm_eps=sizes["rms_norm_eps"],
        act="swiglu", qkv_bias=sizes["attention_bias"])


def policy(numerics: str):
    """``mode:multiplier`` as the program's numerics policy."""
    from repro.core.policy import NumericsPolicy
    mode, mult = numerics.split(":")
    return NumericsPolicy(mode=mode, multiplier=mult)


def train_step(sizes: dict, traffic: dict, numerics: str):
    """The jitted training step as ``launch/train.train`` builds it, and
    the optimizer's ``init``."""
    import jax
    from repro.models.transformer import lm_loss
    from repro.optim.optimizers import cosine_schedule, make_optimizer
    from repro.train.step import make_train_step
    cfg, pol = arch(sizes), policy(numerics)
    opt = make_optimizer(traffic["optimizer"], cosine_schedule(
        traffic["lr"], traffic["warmup_steps"], traffic["total_steps"]))
    step = make_train_step(lambda p, b: lm_loss(p, b, cfg, pol), opt,
                           clip_norm=traffic["clip_norm"])
    return jax.jit(step, donate_argnums=(0, 1)), opt.init


def serve_engine(sizes: dict, traffic: dict, numerics: str, params):
    from repro.serve.scheduler import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        arch(sizes), {"served": policy(numerics)}, params,
        max_len=traffic["prompt_len"][1] + traffic["output_len"][1],
        capacity=traffic["capacity"], page_size=traffic["page_size"])


# ------------------------------------------------------------------ work
def matmul_params(sizes: dict) -> dict:
    """Weights that multiply a token's activations: the layers' and the
    tied head's (the embedding lookup multiplies nothing)."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = d // h
    layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    return {"layers": sizes["num_hidden_layers"] * layer,
            "head": d * sizes["vocab_size"]}


def train_flops(sizes: dict, traffic: dict) -> float:
    """Model FLOPs of one training step: 6 N per token for the weights,
    plus the attention scores and values (causal: half the square),
    forward and backward (3x).  Recomputation is not counted."""
    n = sum(matmul_params(sizes).values())
    b, s = traffic["batch"], traffic["seq"]
    attn = (sizes["num_hidden_layers"] * 2 * 2 * s * s / 2
            * sizes["hidden_size"])
    return 6.0 * n * b * s + 3.0 * attn * b


def serve_flops_per_token(sizes: dict) -> float:
    """2 N per processed token (prompt or decoded)."""
    return 2.0 * sum(matmul_params(sizes).values())
