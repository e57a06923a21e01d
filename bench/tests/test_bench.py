"""CPU tests of the benchmark: the reference's numerics, the trace
reduction, tiny end-to-end runs in Pallas interpret mode, the refusal
without a chip, and the faults and the control that ``correct`` must
catch.

    python -m pytest bench/tests
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.tests import tiny  # noqa: E402

TRACE = Path(__file__).parent / "data" / "decode_ticks.xplane.pb"
TRAIN, SERVE = "granite-3-2b.train", "granite-3-2b.serve-decode"
SEED = 2 ** 33 + 17           # more bits than a signed 32-bit integer holds


# ------------------------------------------------------------------ numerics
def test_reference_product_is_the_multiplier():
    """The reference's afm16 product equals the program's functional model
    bit for bit over normal operands (products above the subnormal range)."""
    import jax.numpy as jnp
    from bench.reference import numerics
    from repro.core.multipliers import AFM16
    rng = np.random.default_rng(0)
    mant = rng.integers(0, 1 << 23, 20000, dtype=np.uint32)
    exp = rng.integers(100, 150, 20000, dtype=np.uint32)
    sign = rng.integers(0, 2, 20000, dtype=np.uint32)
    a = ((sign << 31) | (exp << 23) | mant).view(np.float32)
    b = np.roll(a, 11)
    a = np.concatenate([a, np.float32([0.0, -0.0, 1.9921875, 3.0])])
    b = np.concatenate([b, np.float32([2.0, 5.0, 1.9921875, 1.75])])
    want = AFM16.np_mul(a, b)
    got = np.asarray(numerics.afm16(jnp.asarray(a), jnp.asarray(b)))
    same = (want.view(np.uint32) == got.view(np.uint32)) | ((want == 0) & (got == 0))
    assert same.all()


def test_reference_gemm_and_gradients_agree_with_the_program_oracle():
    import jax
    import jax.numpy as jnp
    from bench.reference import numerics
    from repro.core.policy import NumericsPolicy
    from repro.kernels.ops import policy_matmul
    pol = NumericsPolicy(mode="amsim_jnp", multiplier="afm16")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((3, 37, 70)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 70, 45)), jnp.float32)
    mm = numerics.matmul
    f = lambda m: lambda x, w: jnp.sum(jnp.sin(m(x, w)))
    got = jax.grad(f(mm), argnums=(0, 1))(x, w)
    want = jax.grad(f(lambda x, w: policy_matmul(x, w, pol)), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(mm(x, w), policy_matmul(x, w, pol), rtol=0, atol=2e-5)
    for g, h in zip(got, want):
        np.testing.assert_allclose(g, h, rtol=0, atol=2e-5)


# ------------------------------------------------------------------ trace
def test_trace_reduction_on_a_recorded_trace():
    """Four decode ticks of granite-3-2b (4 layers, 16 slots) recorded on a
    TPU v5 lite."""
    from bench.kernels import roofline_share
    from bench.peaks import peaks_for
    s = T.load(TRACE)
    assert 3.0e9 < s.window_ns < 4.0e9
    assert 0 < s.busy_ns <= s.window_ns
    assert [m[0].split("(")[0] for m in s.modules] == ["jit_paged_serve_step"] * 4
    names = {T.kernel_name(k.text) for k in T.kernels(s)}
    assert names == {"approx_gemm_impl", "attn_impl"}
    ops = [T.shapes(k.text) for k in T.kernels(s) if "49280" in k.text]
    assert ops and ops[0][0][:2] == [("f32", (128, 2048)), ("f32", (2048, 49280))]
    share = roofline_share(s, peaks_for("TPU v5 lite"))
    assert 0 < share < 100
    top = T.top_ops(s)
    assert top[0][0] == "approx_gemm_impl" and len(top) <= 10
    assert all(g[1] >= 0 for g in T.idle_gaps(s))


def test_kernel_names_drop_jax_prefixes():
    text = ("%transpose_jvp_jit__approx_gemm_impl___.4 = f32[2048,49280]{1,0} "
            "custom-call(f32[2048,512]{1,0} %a, f32[512,49280]{1,0} %b, "
            "bf16[128,128]{1,0} %c), custom_call_target=\"tpu_custom_call\"")
    assert T.is_kernel(text) and T.kernel_name(text) == "approx_gemm_impl"
    fused = ("%_approx_gemm_impl.268 = f32[4,2048,8192]{2,1,0} fusion(f32[4,2048,8192]"
             "{2,1,0} %x, s32[] %i, f32[2048,512]{1,0} %a, f32[512,8192]{1,0} %b, "
             "bf16[128,128]{1,0} %t), kind=kCustom, calls=%fused_computation.6")
    assert T.is_kernel(fused)
    from bench.kernels import work_of
    flops, _ = work_of("approx_gemm_impl")(*T.shapes(fused))
    assert flops == 2.0 * 2048 * 512 * 8192
    assert not T.is_kernel("%fusion.40 = f32[8]{0} fusion(f32[8]{0} %a), kind=kCustom")


def test_seeds_use_every_bit():
    a = np.asarray(harness.key(SEED, "weights"))
    b = np.asarray(harness.key(SEED - 2 ** 33, "weights"))
    assert not np.array_equal(a, b)


# ------------------------------------------------------------------ runs
def _run(workload, seed=SEED, seconds=1.0, **kw):
    cell = tiny.cell(workload)
    return cell, harness.runner(cell).run(cell, seed, seconds, **kw)


def test_train_cell_runs_end_to_end():
    import jax
    cell, out = _run(TRAIN)
    assert out.correct, out.checks
    line = harness.result_line(cell, out, jax.devices()[:1], traced=False)
    assert set(line["metrics"]) == {"setup_s", "train_step_s"}
    assert list(line)[-1] == "checks" and line["attempted"] >= 1
    json.dumps(line)


def test_serve_cell_runs_end_to_end():
    import jax
    cell, out = _run(SERVE, seconds=2.0)
    assert out.correct, out.checks
    assert out.counts["emitted"] > 0 and out.counts["sample"]
    line = harness.result_line(cell, out, jax.devices()[:1], traced=False)
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
    assert out.counts["gaps"]


def test_run_without_a_chip_fails_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", TRAIN,
           "--seed", str(SEED), "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    # A directory with only the benchmark's own files fails the same way.
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cmd[1] = str(tmp_path / "bench" / "run.py")
    p = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=300,
                       cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


# ------------------------------------------------------------------ faults
def test_step_that_returns_its_state_unchanged_is_not_correct():
    cell = tiny.cell(TRAIN)
    real = cell.system.train_step

    def frozen(*a, **k):
        step, init = real(*a, **k)

        def same(params, state, batch):
            import jax
            import jax.numpy as jnp
            copy = lambda t: jax.tree.map(jnp.copy, t)
            _, _, metrics = step(copy(params), copy(state), batch)
            return params, state, metrics
        return same, init
    cell = dataclasses.replace(cell, system=_Shim(cell.system, train_step=frozen))
    out = harness.runner(cell).run(cell, SEED, 0)
    assert not out.correct
    assert dict((n, v) for n, v, _ in out.checks)["change_gap"] >= 0.99


def test_half_of_the_batch_left_out_is_not_correct():
    from bench.calibrate import half_batch_feed
    cell = tiny.cell(TRAIN)
    out = harness.runner(cell).run(cell, SEED, 0, feed=half_batch_feed(cell, SEED))
    assert not out.correct


def test_served_token_altered_where_produced_is_not_correct():
    cell = tiny.cell(SERVE)
    vocab = cell.sizes["vocab_size"]

    def alter(req, first_new):
        req.out[first_new] = (req.out[first_new] + 1) % vocab
    out = harness.runner(cell).run(cell, SEED, 2.0, alter=alter)
    assert not out.correct


# ------------------------------------------------------------------ control
def test_control_is_not_correct_in_training():
    from bench.calibrate import CONTROL
    cell = tiny.cell(TRAIN)
    out = harness.runner(cell).run(cell, SEED, 0, numerics=CONTROL)
    assert not out.correct


def test_control_is_not_correct_in_serving():
    from bench.calibrate import CONTROL
    cell = tiny.cell(SERVE)
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, check_sequences=3,
                                                  output_len=[12, 24]))
    out = harness.runner(cell).run(cell, SEED, 3.0, numerics=CONTROL)
    assert not out.correct
    assert dict((n, v) for n, v, _ in out.checks)["served_gap"] > cell.limits["served_gap"]


class _Shim:
    """A configuration module with some of its functions replaced."""

    def __init__(self, mod, **over):
        self._mod, self._over = mod, over

    def __getattr__(self, name):
        return self._over.get(name) or getattr(self._mod, name)
