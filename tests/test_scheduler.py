"""Continuous-batching scheduler (serve/scheduler.py): token-for-token
parity with dedicated uniform engines across ragged mixed-tier streams,
paged-vs-ring bit identity, preemption-by-recompute, windowed page
recycling, and the one-trace-per-tier contract."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.policy import NumericsPolicy
from repro.models.transformer import (init_lm, init_lm_caches,
                                      init_paged_lm_caches, lm_forward)
from repro.serve.engine import ServingEngine
from repro.serve.paged_cache import PageAllocator, pages_for
from repro.serve.scheduler import ContinuousBatchingEngine, _merge_control

NATIVE = NumericsPolicy()
AMSIM = NumericsPolicy(mode="amsim_jnp", multiplier="afm16")


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_arch("granite-3-2b"), n_layers=1)
    params = init_lm(jax.random.PRNGKey(7), cfg)
    return cfg, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=n).tolist() for n in lengths]


def _oracle(cfg, policy, params, prompts, new, max_len=32):
    """Dedicated uniform ring engine, one request at a time (B=1)."""
    eng = ServingEngine(cfg, policy, params, max_len=max_len)
    return [np.asarray(eng.generate(jnp.asarray([p], jnp.int32),
                                    max_new_tokens=new))[0].tolist()
            for p in prompts]


# ----------------------------------------------------------- paged cache
def test_page_allocator_contract():
    a = PageAllocator(5)  # pages 1..4 usable, 0 = trash
    assert a.capacity == 4
    got = a.alloc(4)
    assert sorted(got) == [1, 2, 3, 4]
    assert a.alloc(1) is None          # all-or-nothing exhaustion
    a.release([got[0]])
    with pytest.raises(ValueError):
        a.release([got[0]])            # double free
    with pytest.raises(ValueError):
        a.release([0])                 # trash page is never allocatable
    assert pages_for(0, 4) == 0 and pages_for(1, 4) == 1
    assert pages_for(4, 4) == 1 and pages_for(5, 4) == 2


def test_paged_vs_ring_bit_identity(setup):
    """A single resident request decoding through the paged cache must
    produce bit-identical logits to the ring cache: same einsum path,
    same key set, masked-out pool garbage is exactly zero after softmax."""
    cfg, params = setup
    max_len, ps = 16, 4
    prompt = jnp.asarray(_prompts(cfg, [6])[0], jnp.int32)[None]
    m = prompt.shape[1]

    ring = init_lm_caches(cfg, 1, max_len)
    lr, ring, _ = lm_forward(params, prompt, cfg, NATIVE, caches=ring)

    # Tcap == max_len and pages laid out in position order, so the
    # gathered paged view has the ring's exact (B, T, KV, dh) layout.
    pool = init_paged_lm_caches(cfg, max_len // ps + 1, ps)
    ptab = jnp.arange(1, max_len // ps + 1, dtype=jnp.int32)[None]
    merged = _merge_control(pool, ptab, jnp.ones((1,), bool),
                            jnp.zeros((1,), jnp.int32))
    lp, merged, _ = lm_forward(params, prompt, cfg, NATIVE, caches=merged)
    np.testing.assert_array_equal(np.asarray(lr[:, -1]),
                                  np.asarray(lp[:, -1]))

    tok_r = tok_p = jnp.argmax(lp[:, -1:], axis=-1).astype(jnp.int32)
    for i in range(4):
        lr, ring, _ = lm_forward(params, tok_r, cfg, NATIVE, caches=ring)
        merged = _merge_control(
            {"pool_k": merged["pool_k"], "pool_v": merged["pool_v"]},
            ptab, jnp.ones((1,), bool), jnp.full((1,), m + i, jnp.int32))
        lp, merged, _ = lm_forward(params, tok_p, cfg, NATIVE,
                                   caches=merged)
        np.testing.assert_array_equal(np.asarray(lr), np.asarray(lp),
                                      err_msg=f"decode step {i}")
        tok_r = jnp.argmax(lr[:, -1:], axis=-1).astype(jnp.int32)
        tok_p = jnp.argmax(lp[:, -1:], axis=-1).astype(jnp.int32)


# ------------------------------------------------------------- scheduler
def test_ragged_stream_matches_uniform_engine(setup):
    """Ragged prompt lengths through the scheduler (bucketed prefill,
    staggered retirement) == dedicated B=1 ring engine, token for token."""
    cfg, params = setup
    prompts = _prompts(cfg, (5, 3, 7, 4))
    want = _oracle(cfg, NATIVE, params, prompts, 6)
    cbe = ContinuousBatchingEngine(cfg, NATIVE, params, max_len=32,
                                   capacity=2, page_size=4)
    rids = [cbe.submit(p, 6) for p in prompts]
    out = cbe.drain()
    assert [out[r] for r in rids] == want
    assert cbe.decode_trace_counts == {"default": 1}
    # Prefill traces at most one per power-of-two bucket used.
    assert cbe.prefill_trace_counts["default"] <= 2
    # Everything retired: all pages back on the free list.
    assert cbe.n_free_pages["default"] == cbe.n_pages - 1


def test_capacity_one_and_single_token_requests(setup):
    """Degenerate shapes: B=1 lane (capacity=1, pure sequential) and
    max_new_tokens=1 requests that retire straight out of prefill
    without ever decoding."""
    cfg, params = setup
    prompts = _prompts(cfg, (5, 3), seed=1)
    want = _oracle(cfg, NATIVE, params, prompts, 5)
    cbe = ContinuousBatchingEngine(cfg, NATIVE, params, max_len=32,
                                   capacity=1, page_size=4)
    rids = [cbe.submit(p, 5) for p in prompts]
    out = cbe.drain()
    assert [out[r] for r in rids] == [w[:5] for w in want]

    cbe1 = ContinuousBatchingEngine(cfg, NATIVE, params, max_len=32,
                                    capacity=2, page_size=4)
    rids = [cbe1.submit(p, 1) for p in prompts]
    out = cbe1.drain()
    assert [out[r] for r in rids] == [w[:1] for w in want]
    assert cbe1.decode_trace_counts == {"default": 0}  # never decoded


def test_mixed_tier_stream_matches_per_tier_engines(setup):
    """Requests carrying different numerics tiers through ONE scheduler
    == each tier served alone by a dedicated uniform-policy engine; each
    tier's decode traced exactly once."""
    cfg, params = setup
    tiers = {"exact": NATIVE, "cheap": AMSIM}
    prompts = _prompts(cfg, (5, 4, 6, 3), seed=2)
    names = ["exact", "cheap", "exact", "cheap"]
    want = {}
    for tname, tpol in tiers.items():
        mine = [p for p, n in zip(prompts, names) if n == tname]
        for p, o in zip(mine, _oracle(cfg, tpol, params, mine, 6)):
            want[tuple(p)] = o
    cbe = ContinuousBatchingEngine(cfg, tiers, params, max_len=32,
                                   capacity=2, page_size=4)
    rids = [cbe.submit(p, 6, tier=n) for p, n in zip(prompts, names)]
    out = cbe.drain()
    for rid, p in zip(rids, prompts):
        assert out[rid] == want[tuple(p)], f"request {rid} ({p})"
    assert cbe.decode_trace_counts == {"exact": 1, "cheap": 1}


def test_preemption_by_recompute_is_token_identical(setup):
    """An overcommitted page pool forces mid-flight eviction; evicted
    requests resume by re-prefilling prompt ++ emitted and must land on
    the exact same continuation."""
    cfg, params = setup
    prompts = _prompts(cfg, (6, 4, 9), seed=3)
    want = _oracle(cfg, NATIVE, params, prompts, 8)
    cbe = ContinuousBatchingEngine(cfg, NATIVE, params, max_len=32,
                                   capacity=3, page_size=4, n_pages=7)
    rids = [cbe.submit(p, 8) for p in prompts]
    out = cbe.drain()
    assert [out[r] for r in rids] == want
    assert sum(r.preemptions for r in cbe.finished.values()) > 0, \
        "pool was sized to force preemption but none happened"
    assert cbe.decode_trace_counts == {"default": 1}


def test_windowed_stream_recycles_pages(setup):
    """Sliding-window serving releases slid-out pages mid-flight: a
    40-token stream runs inside a 4-page pool (16 token positions) and
    matches the windowed full-recompute oracle."""
    cfg, params = setup
    cfgw = dataclasses.replace(cfg, sliding_window=8)
    prompt = _prompts(cfg, [5], seed=4)[0]
    toks = list(prompt)
    for _ in range(40):
        lg, _, _ = lm_forward(params, jnp.asarray([toks], jnp.int32),
                              cfgw, NATIVE)
        toks.append(int(jnp.argmax(lg[0, -1])))
    cbe = ContinuousBatchingEngine(cfgw, NATIVE, params, max_len=64,
                                   capacity=1, page_size=4, n_pages=5)
    rid = cbe.submit(prompt, 40)
    assert cbe.drain()[rid] == toks[len(prompt):]
    assert cbe.n_free_pages["default"] == 4  # everything released


def test_cbe_decode_ticks_zero_added_retraces(setup):
    """An amsim tier's decode ticks keep the one-decode-trace-per-tier
    contract: a second wave of requests through the SAME engine (prompts
    in the first wave's prefill bucket) adds no decode trace and traces
    no LUT GEMM launch again (``obs.routes`` counts a launch's tile each
    time it is traced)."""
    from repro import obs
    cfg, params = setup
    pol = NumericsPolicy(mode="amsim", multiplier="exact7")
    prompts = _prompts(cfg, (5, 3, 6, 4), seed=4)
    tiles = lambda: sum(v for k, v in obs.routes.items()
                        if k.startswith("gemm.tile."))

    cbe = ContinuousBatchingEngine(cfg, {"cheap": pol}, params,
                                   max_len=32, capacity=2, page_size=4)
    t0 = tiles()
    rids = [cbe.submit(p, 5, tier="cheap") for p in prompts[:2]]
    out = cbe.drain()
    assert all(len(out[r]) == 5 for r in rids)
    assert tiles() > t0, "the amsim tier ran no LUT GEMM"
    assert cbe.decode_trace_counts == {"cheap": 1}

    t1 = tiles()
    rids2 = [cbe.submit(p, 4, tier="cheap") for p in prompts[2:]]
    out2 = cbe.drain()
    assert all(len(out2[r]) == 4 for r in rids2)
    assert cbe.decode_trace_counts == {"cheap": 1}, \
        "second wave retraced the decode step"
    assert tiles() == t1, "second wave traced LUT GEMM launches again"


def test_submit_validation(setup):
    cfg, params = setup
    cbe = ContinuousBatchingEngine(cfg, NATIVE, params, max_len=16,
                                   capacity=2, page_size=4)
    with pytest.raises(ValueError, match="empty"):
        cbe.submit([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        cbe.submit([1, 2], 0)
    with pytest.raises(ValueError, match="tier"):
        cbe.submit([1, 2], 4, tier="nope")
    with pytest.raises(ValueError, match="max_len"):
        cbe.submit(list(range(1, 14)), 4)      # 13 + 4 > 16
    # Boundary: prompt + budget == max_len is admissible and completes.
    rid = cbe.submit(list(range(1, 13)), 4)    # 12 + 4 == 16
    assert len(cbe.drain()[rid]) == 4
    # A request that could never fit its lane's page pool is rejected at
    # submit, not deadlocked mid-stream.
    small = ContinuousBatchingEngine(cfg, NATIVE, params, max_len=16,
                                     capacity=1, page_size=4, n_pages=3)
    with pytest.raises(ValueError, match="pages"):
        small.submit(list(range(1, 11)), 6)
