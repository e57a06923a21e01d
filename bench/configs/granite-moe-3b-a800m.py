"""granite-moe-3b-a800m as the system under test: the program's decoder
with its dropless sparse-expert layer and training step, built from the
sizes in ``granite-moe-3b-a800m.json`` with the weights the reference
module makes."""
from __future__ import annotations


def arch(sizes: dict):
    from repro.configs.base import ArchConfig, MoEConfig
    return ArchConfig(
        name="granite-moe-3b-a800m", family="moe",
        n_layers=sizes["num_hidden_layers"], d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["intermediate_size"], vocab=sizes["vocab_size"],
        moe=MoEConfig(n_experts=sizes["num_local_experts"],
                      top_k=sizes["num_experts_per_tok"],
                      d_ff=sizes["intermediate_size"]),
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


def routes(sizes: dict, numerics: str, params, tokens):
    """The experts the program's router picks for ``tokens`` (B, S) in each
    layer, (layers, B*S, k), from its own forward pass: the decoder's
    layers run one at a time, as ``lm_forward`` runs them in training."""
    import jax
    from repro.models.attention import attention
    from repro.models.layers import embed, rmsnorm
    from repro.models.moe import moe_ffn, route
    cfg, pol = arch(sizes), policy(numerics)

    @jax.jit
    def run(params, tokens):
        x, sels = embed(params["embed"], tokens), []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            a, _ = attention(lp["attn"], rmsnorm(lp["n1"], x, cfg.norm_eps),
                             cfg, pol)
            x = x + a
            h = rmsnorm(lp["n2"], x, cfg.norm_eps)
            sels.append(route(lp["moe"]["router"], h.reshape(-1, h.shape[-1]),
                              cfg, pol)[1])
            x = x + moe_ffn(lp["moe"], h, cfg, pol)[0]
        return jax.numpy.stack(sels)
    return run(params, tokens)


# ------------------------------------------------------------------ work
def matmul_params(sizes: dict) -> dict:
    """Weights that multiply a token's activations: the layers' attention,
    router and the k experts a token is routed to, and the tied head's
    (the embedding lookup multiplies nothing)."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    E, k = sizes["num_local_experts"], sizes["num_experts_per_tok"]
    dh = d // h
    layer = d * h * dh + 2 * d * kv * dh + h * dh * d + d * E + k * 3 * d * f
    return {"layers": sizes["num_hidden_layers"] * layer,
            "head": d * sizes["vocab_size"]}


def train_flops(sizes: dict, traffic: dict) -> float:
    """Model FLOPs of one training step: 6 N per token for the weights a
    token is multiplied by (its k active experts, not all E), plus the
    attention scores and values (causal: half the square), forward and
    backward (3x).  Recomputation and expert padding are not counted."""
    n = sum(matmul_params(sizes).values())
    b, s = traffic["batch"], traffic["seq"]
    attn = (sizes["num_hidden_layers"] * 2 * 2 * s * s / 2
            * sizes["hidden_size"])
    return 6.0 * n * b * s + 3.0 * attn * b
