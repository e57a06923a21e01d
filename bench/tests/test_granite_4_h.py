"""CPU tests of the granite-4.0-h-micro serving cell at a tiny size: a run
is correct, and the control and a decode that starts from zero state in
place of the prefill's are not.

    python -m pytest bench/tests/test_granite_4_h.py
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402

SERVE = "granite-4.0-h-micro.serve-decode"
SEED = 2 ** 33 + 19
# Both kinds of layer, each Mamba width cut to the tiny hidden size; the
# oracle numerics keep the run short (the kernels' interpret mode is
# covered by tests/test_hybrid.py).
SIZES = dict(tiny.SIZES, num_hidden_layers=3, shared_intermediate_size=256,
             layer_types=["mamba", "attention", "mamba"], mamba_n_heads=8,
             mamba_d_head=32, mamba_d_state=16, numerics="amsim_jnp:afm16")


def _cell():
    c = harness.load_cell(SERVE)
    return dataclasses.replace(
        c, sizes=dict(c.sizes, **SIZES),
        traffic=dict(c.traffic, **tiny.TRAFFIC["serve_closed_loop"]))


def _gap(out):
    return dict((n, v) for n, v, _ in out.checks)["served_gap"]


def test_serve_cell_is_correct():
    cell = _cell()
    out = harness.runner(cell).run(cell, SEED, 2.0)
    assert out.correct, out.checks
    assert out.counts["emitted"] > 0


def test_control_is_not_correct():
    from bench.calibrate import CONTROL
    cell = _cell()
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, check_sequences=3,
                                                  output_len=[12, 24]))
    out = harness.runner(cell).run(cell, SEED, 3.0, numerics=CONTROL)
    assert not out.correct and _gap(out) > cell.limits["served_gap"]


def test_decode_from_zero_state_is_not_correct():
    """Every admission's Mamba state zeroed after its prefill wrote it."""
    cell = _cell()
    real = cell.system.serve_engine

    def engine(*a, **k):
        eng = real(*a, **k)
        for lane in eng._lanes.values():
            def zeroed(*args, prefill=lane.prefill):
                nxt, ok, caches = prefill(*args)
                slot, m = args[-1], caches["mamba"]
                return nxt, ok, dict(caches, mamba={
                    k: v.at[:, slot].set(0.0) for k, v in m.items()})
            lane.prefill = zeroed
        return eng

    cell = dataclasses.replace(cell, system=_Shim(cell.system, serve_engine=engine))
    out = harness.runner(cell).run(cell, SEED, 2.0)
    assert not out.correct and _gap(out) > cell.limits["served_gap"]


class _Shim:
    """A configuration module with some of its functions replaced."""

    def __init__(self, mod, **over):
        self._mod, self._over = mod, over

    def __getattr__(self, name):
        return self._over.get(name) or getattr(self._mod, name)
