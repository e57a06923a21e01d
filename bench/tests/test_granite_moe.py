"""CPU tests of the granite-moe-3b-a800m training cell at a tiny size, in
Pallas interpret mode: it runs end to end through the dropless expert
layer and the grouped kernel, agrees with the plain reference, and the
control and a half batch are not correct.

    python -m pytest bench/tests/test_granite_moe.py
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

TRAIN = "granite-moe-3b-a800m.train"
SEED = 2 ** 33 + 29
# Widths cut as bench/tests/tiny.py cuts granite-3-2b's; the published 40
# experts and top 8, so that each choice weighs what it weighs at full size
# and a choice that flips where two router logits nearly tie moves the
# numbers compared no more than it does there.
SIZES = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 64, "num_hidden_layers": 2, "vocab_size": 512,
         "num_local_experts": 40, "num_experts_per_tok": 8,
         "initializer_range": 0.08}


def cell() -> harness.Cell:
    c = harness.load_cell(TRAIN)
    return dataclasses.replace(c, sizes=dict(c.sizes, **SIZES),
                               traffic=dict(c.traffic, seq=32))


def test_train_cell_runs_end_to_end():
    import jax
    from repro import obs
    c = cell()
    before = obs.routes["moe.grouped.taken"]
    out = harness.runner(c).run(c, SEED, 1.0)
    assert out.correct, out.checks
    assert obs.routes["moe.grouped.taken"] > before
    line = harness.result_line(c, out, jax.devices()[:1], traced=False)
    assert set(line["metrics"]) == {"setup_s", "train_step_s"}
    assert line["attempted"] >= 1


def test_program_routes_as_the_reference_does():
    """From the same weights the program's router and the reference's pick
    the same experts for nearly every token in every layer
    (bench/routing.py); their weights then drift apart by round-off."""
    from bench.routing import count
    out = count(cell(), SEED)
    steps = out["differing_by_step_and_layer"]
    assert len(steps) == 3 and all(len(s) == 2 for s in steps)
    assert out["choices_per_step"] == 2 * 128 * 8
    assert sum(steps[0]) < 0.01 * out["choices_per_step"]


def test_control_is_not_correct():
    from bench.calibrate import CONTROL
    c = cell()
    out = harness.runner(c).run(c, SEED, 0, numerics=CONTROL)
    assert not out.correct


def test_half_of_the_batch_left_out_is_not_correct():
    from bench.calibrate import half_batch_feed
    c = cell()
    out = harness.runner(c).run(c, SEED, 0, feed=half_batch_feed(c, SEED))
    assert not out.correct


def test_new_readers_read_nothing_where_no_grouped_kernel_ran():
    """On a recorded trace of granite-3-2b's decode ticks, which run no
    grouped launch, ``expert_share`` and ``grouped_roofline`` read None
    and raise nothing (a program without the kernel reads the same)."""
    from bench import trace as T
    from bench.peaks import peaks_for
    summary = T.load(Path(__file__).parent / "data" / "decode_ticks.xplane.pb")
    ctx = {"cell": cell(), "summary": summary, "counts": {},
           "peaks": peaks_for("TPU v5 lite")}
    for name in ("expert_share", "grouped_roofline"):
        reader = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
        assert reader.read(ctx) is None
