"""granite-4.0-h-micro's stack of Mamba-2 and attention layers, at a tiny
size on the CPU: the program against the benchmark's plain reference, the
padded prefill's state, the serving engine's prefill and decode ticks, the
state-update kernel against its oracle, and preemption by recompute.

The configuration is the benchmark's (``bench/configs/granite-4.0-h-micro.*``)
with its widths cut: three layers (Mamba-2, attention, Mamba-2), so that
both kinds of layer and the scan of each run are present.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.amsim import amsim_multiply
from repro.core.float_bits import jnp_round_mantissa, jnp_truncate_mantissa
from repro.core.lutgen import get_lut
from repro.core.multipliers import get_multiplier
from repro.core.policy import NumericsPolicy
from repro.kernels.ops import _amsim_lut
from repro.kernels.ref import ref_ssm_state
from repro.kernels.ssm_state import ssm_state_update
from repro.models.ssm import mamba2
from repro.models.transformer import init_paged_lm_caches, lm_forward
from repro.serve.scheduler import ContinuousBatchingEngine, _merge_control

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from bench import harness  # noqa: E402

CONFIG = "granite-4.0-h-micro"
TINY = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
        "shared_intermediate_size": 256, "num_hidden_layers": 3,
        "layer_types": ["mamba", "attention", "mamba"], "vocab_size": 512,
        "mamba_n_heads": 8, "mamba_d_head": 32, "mamba_d_state": 16,
        "mamba_chunk_size": 8, "initializer_range": 0.08}
NATIVE = NumericsPolicy()


@pytest.fixture(scope="module")
def model():
    """(sizes, program module, reference module, weights)."""
    sizes = dict(harness.load_json(ROOT / "bench" / "configs" / f"{CONFIG}.json"),
                 **TINY)
    system = harness.load_module(ROOT / "bench" / "configs" / f"{CONFIG}.py")
    ref = harness.load_module(ROOT / "bench" / "configs" / f"{CONFIG}.ref.py")
    return sizes, system, ref, ref.make_weights(sizes, jax.random.PRNGKey(11))


def _tokens(sizes, b, n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(1, sizes["vocab_size"], (b, n)), jnp.int32)


def _exact7():
    """exact7's product from its definition, and the GEMM summing it, for
    the reference: both operands cut to 7 mantissa bits, the exact product
    rounded to 7 (the entries of its table)."""
    def mul(a, b):
        return jnp_round_mantissa(jnp_truncate_mantissa(a, 7)
                                  * jnp_truncate_mantissa(b, 7), 7)

    def mm(a, b):
        return jnp.sum(mul(a[..., :, :, None], b[..., None, :, :]), axis=-2)
    return mm, mul


# ------------------------------------------------------------------ forward
@pytest.mark.parametrize("mult,tol", [("afm16", 0.3), ("exact7", 0.06)])
def test_forward_matches_the_reference(model, mult, tol):
    """The program's full forward (chunked SSD, chunk 8 over 24 positions)
    against the reference's sequential recurrence, every product the
    multiplier's.  They agree to the multiplier's rounding, not bit for
    bit: the chunked form simulates other products than the recurrence
    (C B and then x dt inside a chunk, B times x dt decayed to the
    chunk's end), and sums in other orders.  Over ten seeds of weights at
    this size the largest logit gap was 0.146 under afm16 and 0.040
    under exact7, while the same program stepping the recurrence one
    token at a time came within 0.016 of the afm16 reference; the program
    under native float32 missed the afm16 reference by 0.51 or more and
    the exact7 one by 0.073 or more."""
    sizes, system, ref, w = model
    toks = _tokens(sizes, 2, 24)
    z = ref.dims(sizes)
    if mult == "afm16":
        want = ref.served_logits(sizes, w, toks, jnp.tile(jnp.arange(24), (2, 1)))
    else:
        mm, mul = _exact7()
        want = jax.jit(lambda w, t: ref.logits_at(mm, w, ref.hidden(
            mm, mul, w, t, z)))(w, toks)
    pol = NumericsPolicy(mode="amsim_jnp", multiplier=mult)
    got, _, _ = jax.jit(lambda w, t: lm_forward(w, t, system.arch(sizes), pol))(
        w, toks)
    gap = float(jnp.max(jnp.abs(got - want)))
    assert gap < tol, gap
    native, _, _ = jax.jit(lambda w, t: lm_forward(
        w, t, system.arch(sizes), NATIVE))(w, toks)
    assert float(jnp.max(jnp.abs(native - want))) > tol


def test_padding_leaves_the_state_of_the_true_prompt(model):
    """A 20-token prompt right-padded into a 32-token bucket leaves the
    Mamba layer's recurrent and conv state of the unpadded prompt, bit for
    bit: at padding dt is 0, so the decay is 1 and the input 0, and every
    padded term a product adds is an exact zero."""
    sizes, system, ref, w = model
    cfg = system.arch(sizes)
    p = jax.tree.map(lambda a: a[0], w["layers"]["mamba"]["mixer"])
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 32, cfg.d_model)) * 0.5
    pol = NumericsPolicy(mode="amsim_jnp", multiplier="afm16")
    # A one-layer, one-slot stacked state, as an admission prefill writes it.
    s, d_in = cfg.ssm, cfg.ssm.expand * cfg.d_model
    empty = {"ssm": jnp.zeros((1, 1, s.d_state, d_in)),
             "conv": jnp.zeros((1, 1, s.conv_kernel - 1, d_in + 2 * s.d_state)),
             "layer": jnp.int32(0), "slot": jnp.zeros((1,), jnp.int32)}
    run = jax.jit(lambda u, n: mamba2(p, u, cfg, pol, cache=empty,
                                      true_len=n)[1])
    short = run(u[:, :20], None)
    padded = run(u, jnp.asarray([20], jnp.int32))
    for k in ("ssm", "conv"):
        np.testing.assert_array_equal(np.asarray(padded[k]), np.asarray(short[k]))
    assert float(jnp.abs(short["ssm"]).max()) > 0


# ------------------------------------------------------------------ engine
@pytest.mark.parametrize("numerics", ["afm16", "native"])
def test_prefill_and_ticks_match_the_full_forward(model, numerics):
    """A prefill of 11 tokens into slot 1 of two (bucket 16) and four
    greedy ticks through the paged cache and the slot state, as the
    engine drives them, against the program's full forward over the
    whole sequence, row for row.  The prefill runs the chunked form and a
    tick the recurrence, so under afm16 they agree to the multiplier's
    rounding (within the reference comparison's 0.3); under native
    float32 at the highest matmul precision, to 1e-5."""
    sizes, system, ref, w = model
    cfg = system.arch(sizes)
    pol = (NATIVE if numerics == "native"
           else NumericsPolicy(mode="amsim_jnp", multiplier="afm16"))
    tol = 1e-5 if numerics == "native" else 0.3
    C, page, n_ptab, m, ticks = 2, 4, 8, 11, 4
    caches = init_paged_lm_caches(cfg, C * n_ptab + 1, page, C)
    ptab = jnp.zeros((C, n_ptab), jnp.int32).at[1].set(1 + jnp.arange(n_ptab))
    toks = _tokens(sizes, 1, m)
    keep = lambda c: {"attention": {k: c["attention"][k] for k in ("pool_k", "pool_v")},
                      "mamba": {k: c["mamba"][k] for k in ("ssm", "conv")}}

    @jax.jit
    def prefill(w, bucket, caches):
        merged = _merge_control(caches, ptab[1:], jnp.ones((1,), bool),
                                jnp.zeros((1,), jnp.int32),
                                jnp.asarray([1], jnp.int32))
        lg, merged, _ = lm_forward(w, bucket, cfg, pol, caches=merged,
                                   logits_at=jnp.asarray([m - 1]),
                                   true_len=jnp.asarray([m], jnp.int32))
        return lg[0, 0], keep(merged)

    @jax.jit
    def tick(w, tok, start, caches):
        merged = _merge_control(caches, ptab, jnp.asarray([False, True]),
                                jnp.asarray([0, start], jnp.int32))
        step = jnp.zeros((C, 1), jnp.int32).at[1, 0].set(tok)
        lg, merged, _ = lm_forward(w, step, cfg, pol, caches=merged)
        return lg[1, 0], keep(merged)

    with jax.default_matmul_precision("highest"):
        rows = []
        lg, caches = prefill(w, jnp.zeros((1, 16), jnp.int32).at[:, :m].set(toks),
                             caches)
        seq = list(np.asarray(toks[0]))
        for i in range(ticks + 1):
            rows.append(lg)
            seq.append(int(jnp.argmax(lg)))
            if i < ticks:
                lg, caches = tick(w, seq[-1], m + i, caches)
        full, _, _ = jax.jit(lambda w, t: lm_forward(w, t, cfg, pol))(
            w, jnp.asarray([seq[:-1]]))
    gaps = np.abs(np.asarray(jnp.stack(rows)) - np.asarray(full[0, m - 1:])).max(-1)
    assert (gaps < tol).all(), gaps


def test_preempted_request_recomputes_the_same_tokens(model):
    """An overcommitted page pool evicts a request mid-flight; it resumes
    by prefilling prompt ++ emitted from zero state and must emit what an
    engine with room for every request emits.  Native float32: the
    resumed state equals the ticked one to rounding, far inside this
    case's smallest top-2 logit margin."""
    sizes, system, ref, w = model
    cfg = system.arch(sizes)
    prompts = [list(map(int, _tokens(sizes, 1, n, seed=n)[0])) for n in (6, 4, 9)]
    runs = []
    for n_pages in (None, 7):
        cbe = ContinuousBatchingEngine(cfg, NATIVE, w, max_len=32, capacity=3,
                                       page_size=4, n_pages=n_pages)
        rids = [cbe.submit(p, 8) for p in prompts]
        out = cbe.drain()
        runs.append(([out[r] for r in rids],
                     sum(r.preemptions for r in cbe.finished.values())))
    (roomy, none), (tight, preempted) = runs
    assert none == 0 and preempted > 0
    assert tight == roomy


# ------------------------------------------------------------------ kernel
@pytest.mark.parametrize("mult", ["afm16", "exact7", "mitchell8", "bf16"])
def test_state_kernel_matches_its_oracle(mult):
    """One tick of layer 1 of a two-layer, three-slot state, the kernel in
    interpret mode against ``kernels/ref.ref_ssm_state`` with the
    multiplier's Alg. 2 product: every bit of y and of the state.  Slot 2
    holds an inf, so its blocks take the integer form; layer 0 is left as
    it was."""
    m = get_multiplier(mult)
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    L, S, N, HP = 2, 3, 16, 256
    state = jax.random.normal(ks[0], (L, S, N, HP)).at[1, 2, 3, 7].set(jnp.inf)
    xdt = jax.random.normal(ks[1], (S, HP)) * 0.1
    dec = jnp.exp(-jax.random.uniform(ks[2], (S, HP)))
    b, c = jax.random.normal(ks[3], (S, N)), jax.random.normal(ks[4], (S, N))
    y, new = ssm_state_update(state, 1, xdt, dec, b, c, _amsim_lut(m),
                              m.mantissa_bits)
    lut = jnp.asarray(get_lut(m), jnp.uint32)
    want_y, want = ref_ssm_state(
        state, 1, xdt, dec, b, c,
        lambda x, z: amsim_multiply(x, z, lut, m.mantissa_bits))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want_y))
    np.testing.assert_array_equal(np.asarray(new), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))
    assert not np.isfinite(np.asarray(new[1, 2])).all()
