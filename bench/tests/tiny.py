"""Tiny sizes of the benchmark's cells, for runs on the CPU in Pallas
interpret mode."""
from __future__ import annotations

import dataclasses

from bench import harness

# The embedding's scale keeps the logits' spread of the full width
# (0.02 * sqrt(2048) = 0.08 * sqrt(128)), so that absolute logit gaps, and
# the limits set on them at full size, mean the same here.
SIZES = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 256, "num_hidden_layers": 2, "vocab_size": 512,
         "initializer_range": 0.08}
TRAFFIC = {
    "train_steps": {"seq": 32},
    "serve_closed_loop": {"clients": 3, "capacity": 3, "prompt_len": [8, 32],
                          "prompt_strata": [[8, 16], [17, 32]],
                          "warm_prompt_lens": [16, 32], "output_len": [4, 12],
                          "check_sequences": 2},
}


def cell(workload: str) -> harness.Cell:
    c = harness.load_cell(workload)
    traffic = dict(c.traffic, **TRAFFIC[c.traffic["kind"]])
    return dataclasses.replace(c, sizes=dict(c.sizes, **SIZES), traffic=traffic)
