"""The program's own observability (repro/obs.py): the serving engine's
counters and spans, the dispatch guards' route records, and the
benchmark's readers of them (bench/metrics/*, bench/program_trace.py)."""
import collections
import functools
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_arch, reduced
from repro.core.policy import NumericsPolicy
from repro.kernels import ops
from repro.models.transformer import init_lm
from repro.serve.scheduler import ContinuousBatchingEngine

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from bench import harness  # noqa: E402
from bench import trace as T  # noqa: E402

NATIVE = NumericsPolicy()
AFM16 = NumericsPolicy(mode="amsim", multiplier="afm16")
RECORDED = ROOT / "bench" / "tests" / "data" / "decode_ticks.xplane.pb"


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_arch("granite-3-2b"), n_layers=1)
    return cfg, init_lm(jax.random.PRNGKey(7), cfg)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=n).tolist() for n in lengths]


def _scripted(cfg, params):
    """Capacity 2; prompts of 5, 20 and 3 tokens with budgets 3, 2 and 4.
    Tick 1 admits the first two (buckets 16 and 32) and decodes both, the
    second finishing; tick 2 admits the third (bucket 16) and decodes two,
    the first finishing; ticks 3 and 4 decode the third alone."""
    cbe = ContinuousBatchingEngine(cfg, NATIVE, params, max_len=32,
                                   capacity=2, page_size=4)
    for p, n in zip(_prompts(cfg, (5, 20, 3)), (3, 2, 4)):
        cbe.submit(p, n)
    return cbe


# ------------------------------------------------------------- counters
def test_counters_count_a_scripted_stream(setup):
    cbe = _scripted(*setup)
    cbe.drain()
    c = cbe.counters
    assert c["admissions"] == 3
    assert c["prefill.tokens"] == 5 + 20 + 3
    assert c["prefill.bucket_tokens"] == 16 + 32 + 16
    assert (c["prefill.bucket.16"], c["prefill.bucket.32"]) == (2, 1)
    assert c["decode.ticks"] == 4
    assert c["decode.rows_run"] == 2 * 4
    assert c["decode.rows_live"] == 2 + 2 + 1 + 1
    assert c["preemptions"] == c["quarantines"] == 0


def test_counters_count_preemptions_and_readmissions(setup):
    """An overcommitted pool evicts mid-flight; every eviction is one
    preemption and one more admission prefill, which unembeds one row."""
    cfg, params = setup
    cbe = ContinuousBatchingEngine(cfg, NATIVE, params, max_len=32,
                                   capacity=3, page_size=4, n_pages=7)
    for p in _prompts(cfg, (6, 4, 9), seed=3):
        cbe.submit(p, 8)
    cbe.drain()
    evicted = sum(r.preemptions for r in cbe.finished.values())
    assert evicted > 0
    assert cbe.counters["preemptions"] == evicted
    assert cbe.counters["admissions"] == 3 + evicted
    assert cbe.counters["prefill.head_rows"] == 3 + evicted


def test_busy_seconds_and_trace_counts_are_views_of_the_counters(setup):
    cfg, params = setup
    cbe = ContinuousBatchingEngine(cfg, {"exact": NATIVE, "idle": NATIVE},
                                   params, max_len=32, capacity=2,
                                   page_size=4)
    assert cbe.busy_seconds == {"exact": 0.0, "idle": 0.0}
    assert cbe.decode_trace_counts == cbe.prefill_trace_counts \
        == {"exact": 0, "idle": 0}
    for p in _prompts(cfg, (5, 20, 3)):
        cbe.submit(p, 3, tier="exact")
    cbe.drain()
    busy = cbe.busy_seconds
    assert all(isinstance(v, float) for v in busy.values())
    assert busy["exact"] > 0 and busy["idle"] == 0.0
    assert busy["exact"] == cbe.counters["busy_s.exact"]
    assert cbe.decode_trace_counts == {"exact": 1, "idle": 0}
    assert cbe.prefill_trace_counts == {"exact": 2, "idle": 0}   # two buckets
    assert cbe.n_free_pages == {"exact": cbe.n_pages - 1,
                                "idle": cbe.n_pages - 1}


def test_hybrid_engine_counts_state_writes_and_rows():
    """A Mamba-2 layer's engine under amsim (granite-4.0-h-micro's kind of
    layer, tiny): one admission of a 20-token prompt (bucket 32, one SSD
    chunk of 32) and its first tick write one slot's state and update
    every slot's, through the state-update kernel."""
    import dataclasses

    cfg = reduced(get_arch("granite-4.0-h-micro"))
    cfg = dataclasses.replace(cfg, layer_types=("mamba",), n_layers=1,
                              ssm=dataclasses.replace(cfg.ssm, chunk=256))
    params = init_lm(jax.random.PRNGKey(3), cfg)
    cbe = ContinuousBatchingEngine(cfg, AFM16, params, max_len=32,
                                   capacity=2, page_size=4)
    cbe.submit(_prompts(cfg, (20,))[0], 4)
    before = collections.Counter(obs.routes)
    cbe.step()
    c = cbe.counters
    assert c["admissions"] == c["prefill.state_writes"] == 1
    assert c["decode.ticks"] == 1 and c["decode.state_rows"] == cbe.capacity
    new = obs.routes - before
    assert new["ssm.state.kernel"] >= 1 and new["ssm.chunk.32"] >= 1


# ------------------------------------------------------------- routes
def test_moe_route_records_the_grouped_kernel():
    """granite-moe-3b-a800m's layer at its published widths (traced, not
    run): a training batch, under amsim and under native numerics, and a
    128-slot decode tick all take the grouped route, and the tick's
    expert GEMMs launch the grouped kernel.  The dispatch sits in the
    layer's named scopes."""
    import jax.numpy as jnp

    from repro.models.moe import init_moe, moe_ffn
    cfg = get_arch("granite-moe-3b-a800m")
    p = jax.eval_shape(lambda k: init_moe(k, cfg), jax.random.PRNGKey(0))
    run = lambda pol, shape: jax.jit(
        lambda p, x: moe_ffn(p, x, cfg, pol)).lower(
            p, jax.ShapeDtypeStruct(shape, jnp.float32))
    before = collections.Counter(obs.routes)
    text = run(AFM16, (4, 512, cfg.d_model)).as_text(debug_info=True)
    run(NATIVE, (4, 512, cfg.d_model))
    mid = collections.Counter(obs.routes)
    run(AFM16, (128, 1, cfg.d_model))
    assert obs.routes - before >= collections.Counter({"moe.grouped.taken": 3})
    tick = obs.routes - mid
    assert {k: v for k, v in tick.items()
            if k.startswith("gemm.tile.grouped")} \
        == {"gemm.tile.grouped.64x512": 3}       # wg, wu and wd
    for scope in ("moe.router", "moe.dispatch", "moe.experts", "moe.combine"):
        assert scope in text


def test_lut_brick_route_records_the_path_each_launch_compiles():
    """An afm16 GEMM at granite-3-2b's MLP widths (traced, not run) takes
    the factored brick; an M > 7 table in interpret mode the integer
    brick over its 1-D table."""
    import jax.numpy as jnp

    from repro.core.lutgen import get_lut
    from repro.core.multipliers import get_multiplier
    from repro.kernels.approx_gemm import approx_gemm
    before = collections.Counter(obs.routes)
    jax.eval_shape(lambda x, w: ops.policy_matmul(x, w, AFM16, "wg"),
                   jax.ShapeDtypeStruct((256, 2048), jnp.float32),
                   jax.ShapeDtypeStruct((2048, 8192), jnp.float32))
    wide = get_multiplier("fp16xbf16")
    approx_gemm(jnp.ones((8, 8)), jnp.ones((8, 8)), get_lut(wide),
                wide.mantissa_bits, interpret=True)
    assert obs.routes - before == collections.Counter({
        "lut_brick.factored": 1, "lut_brick.integer.wide": 1,
        "gemm.tile.gemm2d.64x512": 1, "gemm.tile.gemm2d.16x128": 1})


def test_gemm_tile_route_records_each_launch_tile():
    """Each launch of a LUT GEMM records its output tile next to its
    brick's form (traced, not run): granite-3-2b's MLP forward, the
    forward's dx and dw, and granite-moe-3b-a800m's grouped expert GEMM
    and its weight gradient."""
    import jax.numpy as jnp

    from repro.core.lutgen import get_packed_lut
    from repro.kernels.approx_gemm import (approx_gemm_grouped,
                                           approx_gemm_grouped_dw,
                                           grouped_layout, grouped_rows_bound)
    before = collections.Counter(obs.routes)
    x = jax.ShapeDtypeStruct((256, 2048), jnp.float32)
    w = jax.ShapeDtypeStruct((2048, 8192), jnp.float32)
    jax.eval_shape(jax.grad(lambda x, w: ops.policy_matmul(
        x, w, AFM16, "wg").sum(), argnums=(0, 1)), x, w)
    E, rows = 40, 16384
    groups = grouped_layout(jnp.zeros((rows,), jnp.int32), E)
    R = grouped_rows_bound(rows, E)
    lut = get_packed_lut("afm16")
    jax.eval_shape(lambda x, w: approx_gemm_grouped(x, w, groups, lut, 7),
                   jax.ShapeDtypeStruct((R, 1536), jnp.float32),
                   jax.ShapeDtypeStruct((E, 1536, 512), jnp.float32))
    jax.eval_shape(lambda x, g: approx_gemm_grouped_dw(x, g, groups, lut, 7,
                                                       E),
                   jax.ShapeDtypeStruct((R, 1536), jnp.float32),
                   jax.ShapeDtypeStruct((R, 512), jnp.float32))
    routes = obs.routes - before
    assert {k: v for k, v in routes.items() if k.startswith("gemm.tile")} \
        == {"gemm.tile.gemm2d.64x512": 3, "gemm.tile.grouped.64x512": 1,
            "gemm.tile.grouped_dw.64x512": 1}
    assert routes["lut_brick.factored"] == 5


@pytest.mark.parametrize("m,n", [
    (256, 3072), (256, 2048), (256, 8192), (256, 49155), (2048, 49155),
    (48, 3072), (64, 2048), (32, 8192), (128, 49155), (1, 40), (96, 40),
    (256, 640), (16384, 512), (21504, 1536)])
def test_tile_rule_keeps_the_accumulator_and_the_fold(m, n):
    """The default 2-D tile at granite's site shapes (forward, dx, dw,
    prefill, decode, the router, the grouped expert GEMMs): the live
    accumulator stays within ``ACC_VREGS`` vregs, rows pad only to a
    bf16 sublane tile, lanes pad n by at most ``LANE_PAD`` of n beyond
    the 128-lane padding, and the fold (bk, chunk) resolves as it did
    before the rule, at every k."""
    from repro.kernels import autotune
    from repro.kernels.approx_gemm import _resolve
    bm, bn = autotune.tile_2d(m, n)
    assert bm * bn // (8 * 128) <= autotune.ACC_VREGS
    assert bm % 16 == 0 and bm <= min(autotune.MAX_ROWS, -(-m // 16) * 16)
    n128 = -(-n // 128) * 128
    assert bn % 128 == 0 and bn <= n128
    assert -(-n // bn) * bn - n128 <= autotune.LANE_PAD * n
    for k in (40, 256, 2048, 8192, 49155):
        got = _resolve("gemm2d", m, k, n, 7, 0, None, None, None, None,
                       True)
        assert got[:4] == (bm, bn, 128, 8)


# ------------------------------------------------------------- spans
_PARENT = {
    "engine.admit": "engine.tick", "engine.faults": "engine.tick",
    "engine.decode": "engine.tick", "engine.bookkeeping": "engine.tick",
    "engine.prefill": "engine.admit",
    "engine.prefill.upload": "engine.prefill",
    "engine.prefill.launch": "engine.prefill",
    "engine.prefill.wait": "engine.prefill",
    "engine.decode.upload": "engine.decode",
    "engine.decode.launch": "engine.decode",
    "engine.decode.wait": "engine.decode",
}


def _host_events(log_dir):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(T.trace_file(log_dir)))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in pd.planes if p.name.startswith("/host:CPU")
            for line in p.lines for e in line.events]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_engine_spans_nest_under_the_profiler(setup, tmp_path):
    """Two ticks of the scripted stream under ``jax.profiler.trace``: the
    host plane holds each span inside its parent, and the prefill spans
    carry the bucket and the true token count."""
    cbe = _scripted(*setup)
    cbe.step()                                 # compile outside the trace
    for p in _prompts(setup[0], (9,), seed=5):
        cbe.submit(p, 2)
    with jax.profiler.trace(str(tmp_path)):
        cbe.step()
        cbe.step()
    events = [e for e in _host_events(tmp_path) if e[0].startswith("engine.")]
    names = [e[0] for e in events]
    assert names.count("engine.tick") == 2
    assert set(names) == set(_PARENT) | {"engine.tick"}
    for name, start, end, _ in events:
        if name == "engine.tick":
            continue
        assert any(p == _PARENT[name] and ps <= start and end <= pe
                   for p, ps, pe, _ in events), name
    prefills = [st for n, _, _, st in events if n == "engine.prefill"]
    assert sorted((st["bucket"], st["tokens"]) for st in prefills) == \
        [(16, 3), (16, 9)]


# ------------------------------------------------------------- readers
@functools.cache
def _cell(name):
    return harness.load_cell(name)


def _read(metric, summary, cell="granite-3-2b.serve-decode"):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
    return reader.read({"summary": summary, "counts": {}, "cell": _cell(cell),
                        "peaks": {}})


def _kernel(name, tag=None, start=0.0, dur=1.0):
    meta = "" if tag is None else '\n"pass":"%s",\n"site":"%s"\n' % (tag[1], tag[0])
    text = (f"%{name} = f32[8,8]{{1,0}} custom-call(f32[8,8]{{1,0}} %a), "
            f'custom_call_target="tpu_custom_call", '
            f"frontend_attributes={{kernel_metadata={{{meta}}}}}")
    return T.Op(text, start, dur)


def _summary(ops=(), modules=(), host=(), window=(0.0, 1000.0)):
    return T.Summary(window=window, ops=list(ops), modules=list(modules),
                     host=list(host), busy_ns=0.0, gaps=[])


def test_head_and_untagged_shares_read_the_kernel_tags():
    fused = T.Op("%_approx_gemm_impl.unembed.dw.7 = f32[2,8,8]{2,1,0} "
                 "fusion(f32[8,8]{1,0} %a), kind=kCustom, calls=%f", 0, 20)
    s = _summary([_kernel("_approx_gemm_impl.head.fwd.1", ("head", "fwd"), 0, 30),
                  fused,
                  _kernel("_approx_gemm_impl.wd.dx.2", ("wd", "dx"), 0, 40),
                  _kernel("_approx_gemm_impl.3", None, 0, 10),
                  T.Op("%copy.1 = f32[8]{0} copy(f32[8]{0} %a)", 0, 500)])
    assert _read("head_share.train", s, "granite-3-2b.train") == \
        pytest.approx(50.0)
    assert _read("head_share.serve", s) == pytest.approx(50.0)
    assert _read("untagged_kernel_share.serve", s) == pytest.approx(10.0)
    assert _read("untagged_kernel_share.train", s, "granite-3-2b.train") == \
        pytest.approx(10.0)


def test_ssm_share_and_state_roofline_read_the_mamba_kernels():
    """``ssm_share.serve`` reads the kernels tagged site "ssm";
    ``ssm_state_roofline`` the state kernel's least time (its layer's
    state read and written, and every other operand, over the HBM
    bandwidth) over its device time."""
    from bench.peaks import PEAKS
    state = T.Op("%_ssm_state_impl.ssm.fwd.3 = (f32[9,64,128,4096]{3,2,1,0}, "
                 "f32[64,1,4096]{2,1,0}) custom-call(s32[1]{0} %l, "
                 "f32[9,64,128,4096]{3,2,1,0} %s, f32[64,1,4096]{2,1,0} %x, "
                 "f32[64,1,4096]{2,1,0} %d, f32[64,1,128]{2,1,0} %b, "
                 "f32[64,1,128]{2,1,0} %c, bf16[128,128]{1,0} %t), "
                 'custom_call_target="tpu_custom_call", frontend_attributes='
                 '{kernel_metadata={\n"pass":"fwd",\n"site":"ssm"\n}}',
                 0, 1e6)
    s = _summary([state,
                  _kernel("_approx_gemm_impl.ssm.fwd.1", ("ssm", "fwd"), 0, 2e6),
                  _kernel("_approx_gemm_impl.wg.fwd.2", ("wg", "fwd"), 0, 7e6)])
    assert _read("ssm_share.serve", s) == pytest.approx(30.0)
    elems = 64 * 128 * 4096
    nbytes = (8 * elems + 4 + 2 * 64 * 4096 * 4 + 2 * 64 * 128 * 4
              + 128 * 128 * 2 + 4 * 64 * 4096)
    reader = harness.load_module(harness.BENCH / "metrics" / "ssm_state_roofline.py")
    got = reader.read({"summary": s, "counts": {},
                       "peaks": PEAKS["TPU v5 lite"]})
    assert got == pytest.approx(100 * nbytes / 819e9 / 1e-3)


def test_engine_launch_ms_and_completion_lag_share_read_the_spans():
    ms = 1e6
    host = [("engine.tick", 0, 100 * ms), ("engine.tick", 100 * ms, 100 * ms),
            ("engine.decode.upload", 1 * ms, 2 * ms),
            ("engine.decode.launch", 3 * ms, 3 * ms),
            ("engine.prefill.upload", 101 * ms, 1 * ms),
            ("engine.prefill.launch", 102 * ms, 4 * ms),
            ("engine.decode.wait", 6 * ms, 90 * ms),      # run ends at 95
            ("engine.prefill.wait", 106 * ms, 50 * ms),   # run ends at 156.5
            ("$scheduler.py:1 step", 0, 200 * ms)]
    modules = [("jit_paged_serve_step", 4 * ms, 91 * ms),
               ("jit_paged_prefill", 104 * ms, 52.5 * ms)]
    s = _summary(modules=modules, host=host, window=(0.0, 200 * ms))
    assert _read("engine_launch_ms", s) == pytest.approx((2 + 3 + 1 + 4) / 2)
    assert _read("completion_lag_share", s) == pytest.approx(100 * 1 / 200)


def test_prefill_pad_share_reads_the_engine_counts_on_its_spans(
        tmp_path, monkeypatch):
    """The bucket and true token count of each admission prefill, read
    from the trace file the window's summary came from."""
    cell = "granite-3-2b.serve-decode"
    log_dir = tmp_path / ".bench_traces" / f"{cell}-7"
    with jax.profiler.trace(str(log_dir)):
        with obs.span("engine.prefill", bucket=64, tokens=40):
            pass                               # before the window
        with obs.span(T.WINDOW_SPAN):
            for bucket, tokens in ((64, 48), (128, 96)):
                with obs.span("engine.prefill", bucket=bucket, tokens=tokens):
                    pass
    summary = T.load(T.trace_file(log_dir))
    _cell(cell)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    assert _read("prefill_pad_share", summary) == \
        pytest.approx(100 * (192 - 144) / 192)


@pytest.mark.parametrize("metric", [
    "head_share.serve", "untagged_kernel_share.serve", "engine_launch_ms",
    "completion_lag_share", "prefill_pad_share", "ssm_share.serve",
    "ssm_state_roofline"])
def test_new_readers_read_nothing_from_a_program_without_them(metric):
    """Four decode ticks recorded on a TPU v5 lite before the program
    tagged its kernels or spanned its engine."""
    assert _read(metric, T.load(RECORDED)) is None
