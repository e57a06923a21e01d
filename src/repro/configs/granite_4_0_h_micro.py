"""granite-4.0-h-micro [hybrid]: 40L d=2048, Mamba-2 and GQA layers, an MLP
in every layer, vocab=100352 tied.

[hf:ibm-granite/granite-4.0-h-micro, config.json]  ``layer_types``: attention
at layers 5, 15, 25 and 35, Mamba-2 elsewhere (64 heads of 64, d_state
128, one group, conv 4, expand 2, chunk 256).  Attention is GQA, 32 heads
over 8 KV heads, with no position embedding ("nope").  Every layer has a
SwiGLU MLP of width 8192.
"""
from repro.configs.base import ArchConfig, SSMConfig, register

_ATTENTION = (5, 15, 25, 35)

CONFIG = register(ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab=100352,
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_kernel=4,
                  n_groups=1, chunk=256),
    layer_types=tuple("attention" if i in _ATTENTION else "mamba"
                      for i in range(40)),
    use_rope=False,
    tie_embeddings=True,
))
