"""Training traffic, the one runner of every mix of this kind: one jitted
step, driven with a new seeded batch per step.

Set-up builds the step and its state once, from the seed, and drives that
same object through the traffic's ``checked_steps`` first steps, reading
what the reference compares: the loss of each step, the first gradient as
the optimizer's state holds it after one step, and each parameter's
change after the last of them.  The window then runs whole steps of the
same object until ``seconds`` have passed.  ``train_step_s`` is the
window over the steps completed in it.

Traffic keys: ``inputs`` ("tokens": ``batch`` rows of ``seq`` tokens drawn
uniformly from the vocabulary, next-token labels), ``optimizer``, ``lr``,
``warmup_steps``, ``total_steps``, ``clip_norm``, ``checked_steps``.
"""
from __future__ import annotations

import math
import time

from bench import harness
from bench.reference import optim


def batch_maker(cell, seed: int):
    """Jitted ``i -> batch i``: every batch drawn from the seed, all rows
    different."""
    import jax
    import jax.numpy as jnp
    t = cell.traffic
    if t["inputs"] != "tokens":
        raise ValueError(f"unknown inputs {t['inputs']!r}")
    vocab = cell.sizes["vocab_size"]
    base = harness.key(seed, "inputs")

    @jax.jit
    def make(i):
        toks = jax.random.randint(jax.random.fold_in(base, i),
                                  (t["batch"], t["seq"] + 1), 0, vocab, jnp.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    return make


def setup(cell, seed: int, numerics=None, feed=None):
    """The step, its state after the checked steps, and the program's
    readings of those steps."""
    import jax
    t, sizes = cell.traffic, cell.sizes
    numerics = numerics or sizes["numerics"]
    wkey = harness.key(seed, "weights")
    make = batch_maker(cell, seed)
    feed = feed or make
    params = cell.reference.make_weights(sizes, wkey)
    step, opt_init = cell.system.train_step(sizes, t, numerics)
    opt_state = jax.jit(opt_init)(params)
    harness.note("weights and optimizer state made")
    losses, grad1 = [], None
    for i in range(t["checked_steps"]):
        params, opt_state, metrics = step(params, opt_state, feed(i))
        losses.append(float(metrics["loss"]))
        harness.note(f"checked step {i + 1}: loss {losses[-1]!r}")
        if i == 0:
            grad1 = harness.leaf_norms(optim.first_gradient(t, opt_state))
    start = cell.reference.make_weights(sizes, wkey)
    change = harness.leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))
    del start
    readings = {"losses": losses, "grad1": grad1, "change": change}
    return step, params, opt_state, make, readings


def compare(cell, seed: int, readings, make):
    """Run the reference through the same first steps and compare."""
    import jax
    t = cell.traffic
    ref = cell.reference.train_reference(
        cell.sizes, t, harness.key(seed, "weights"),
        [make(i) for i in range(t["checked_steps"])])
    rgrad = harness.leaf_norms(ref["grad1"])
    start = cell.reference.make_weights(cell.sizes, harness.key(seed, "weights"))
    rchange = harness.leaf_norms(jax.tree.map(lambda a, b: a - b,
                                              ref["params"], start))
    keep = harness.moved_leaves(rgrad)
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(readings["losses"], ref["losses"]))
    grad_gap = harness.norm_gap(readings["grad1"], rgrad)
    change_gap = harness.norm_gap(readings["change"], rchange, keep)
    lim = cell.limits
    return [("loss_gap", loss_gap, lim.get("loss_gap")),
            ("grad1_gap", grad_gap, lim.get("grad1_gap")),
            ("change_gap", change_gap, lim.get("change_gap"))]


def run(cell, seed: int, seconds: float, trace_dir=None, *, devs=None,
        numerics=None, feed=None):
    step, params, opt_state, make, readings = setup(cell, seed, numerics, feed)
    n0 = cell.traffic["checked_steps"]
    make(n0)                                   # compiled before the window
    setup_s = harness.process_age_s()
    steps, nonfinite, metrics, elapsed = 0, 0, None, 0.0
    t0 = time.perf_counter()
    with harness.window(trace_dir):
        while seconds > 0:
            with harness.span("bench.feed"):
                batch = make(n0 + steps)
            with harness.span("bench.step"):
                params, opt_state, metrics = step(params, opt_state, batch)
            with harness.span("bench.sync"):
                loss = float(metrics["loss"])
            steps += 1
            nonfinite += not math.isfinite(loss)
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    peak = harness.memory_peak_bytes(devs) if devs else 0
    harness.note(f"window: {steps} steps in {elapsed:.3f}s")
    del params, opt_state, step, metrics
    harness.free()
    checks = compare(cell, seed, readings, make)
    harness.note("reference compared")
    correct = harness.judge(checks) and nonfinite == 0 and all(
        math.isfinite(x) for x in readings["losses"])
    return harness.Outcome(
        correct=correct, attempted=steps, failed=nonfinite,
        end_to_end={"setup_s": setup_s,
                    "train_step_s": elapsed / steps if steps else math.nan},
        checks=checks, memory_peak_bytes=peak,
        counts={"steps": steps, "window_s": elapsed,
                "flops_per_step": cell.system.train_flops(cell.sizes, cell.traffic)},
        trace_dir=trace_dir)
