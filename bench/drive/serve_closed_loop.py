"""Closed-loop serving traffic: the one runner of every mix of this kind.

``clients`` clients each keep one request in the engine: when a request
finishes, its client submits the next one at once, as an evaluation
harness keeps every slot busy.  Decoding is greedy.

Every seed serves the same set of sizes in another order, so that the
seed changes the tokens and not the work:

  * prompt lengths take the strata of ``prompt_strata`` in turn, each
    length log-uniform inside its stratum, so any run of consecutive
    admissions holds the same number of each kind; the first requests use
    ``warm_prompt_lens``, so that set-up compiles every prompt shape the
    window can meet;
  * output lengths are log-uniform over ``output_len``;
  * the loop starts in its steady state: the clients' first requests have
    the quantiles of the residual output length (how many tokens a
    request in flight at a random moment still has to emit) as their
    budgets, so the window sees completions at the steady rate from its
    first tick.

Set-up admits the first requests and runs one scheduler tick, which
compiles every program.  The window runs whole ticks until ``seconds``
have passed.  ``serve_tokens_per_s`` is the tokens emitted in the window
over the window.  Every gap between consecutive tokens of a request that
ends in the window is kept for the per-layer ``itl_p95_ms``.

After the window, a sample drawn from the seed of the requests that
emitted tokens in it (every request finished in it first, the longest of
them always) is run through the reference, and the widest gap by which a
served token's logit lies below the reference's best is compared.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import harness


def _loguniform(rng, lo, hi):
    return int(round(math.exp(rng.uniform(math.log(lo), math.log(hi)))))


def residual_budgets(output_len, n: int):
    """Quantiles (i + 1/2)/n of the residual life of a request whose output
    length is log-uniform over ``output_len``: P(R = r) = P(L >= r) / E[L]."""
    lo, hi = output_len
    r = np.arange(1, hi + 1, dtype=np.float64)
    surv = np.clip((np.log(hi) - np.log(np.maximum(r, lo))) / (np.log(hi) - np.log(lo)),
                   0.0, 1.0)
    surv[r <= lo] = 1.0
    cdf = np.cumsum(surv) / surv.sum()
    return [int(r[np.searchsorted(cdf, (i + 0.5) / n)]) for i in range(n)]


class Plan:
    """The requests of one run, drawn from the seed."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.t, self.vocab = traffic, vocab
        self.rng = harness.rng(seed, "requests")
        self._drawn = 0

    def prompt_len(self) -> int:
        strata = self.t["prompt_strata"]
        lo, hi = strata[self._drawn % len(strata)]
        self._drawn += 1
        return _loguniform(self.rng, lo, hi)

    def prompt(self, n: int) -> list:
        return self.rng.integers(0, self.vocab, n).tolist()

    def first(self):
        n = self.t["clients"]
        budgets = residual_budgets(self.t["output_len"], n)
        self.rng.shuffle(budgets)
        warm = list(self.t["warm_prompt_lens"])
        lens = [warm[i] if i < len(warm) else self.prompt_len() for i in range(n)]
        return [(self.prompt(m), b) for m, b in zip(lens, budgets)]

    def next(self):
        m = self.prompt_len()
        return self.prompt(m), _loguniform(self.rng, *self.t["output_len"])


class Client:
    """One closed-loop client: its request in flight and how many of that
    request's tokens it has seen."""

    def __init__(self):
        self.req = None
        self.seen = 0


def _submit(engine, client, prompt, budget, log):
    rid = engine.submit(prompt, budget, "served")
    req = engine._queue[-1]               # the engine's record of this request
    if req.rid != rid:
        raise RuntimeError(f"request {rid} is not the last one queued")
    client.req, client.seen = req, 0
    log[rid] = {"prompt": list(prompt), "times": [], "req": req}


def run(cell, seed: int, seconds: float, trace_dir=None, *, devs=None,
        numerics=None, alter=None):
    t, sizes = cell.traffic, cell.sizes
    numerics = numerics or sizes["numerics"]
    wkey = harness.key(seed, "weights")
    params = cell.reference.make_weights(sizes, wkey)
    engine = cell.system.serve_engine(sizes, t, numerics, params)
    plan = Plan(t, sizes["vocab_size"], seed)
    clients = [Client() for _ in range(t["clients"])]
    log = {}
    for c, (p, b) in zip(clients, plan.first()):
        _submit(engine, c, p, b, log)

    def busy():
        return sum(engine.busy_seconds.values())

    def tick(t_start):
        """One scheduler tick and the closed loop's response to it."""
        b0, s = busy(), time.perf_counter()
        with harness.span("bench.engine_step"):
            engine.step()
        e = time.perf_counter()
        with harness.span("bench.clients"):
            emitted, admitted, admitted_tokens = 0, 0, 0
            for c in clients:
                req = c.req
                new = len(req.out) - c.seen
                if alter is not None and new:
                    alter(req, c.seen)
                log[req.rid]["times"] += [e] * new
                if c.seen == 0 and new:
                    log[req.rid]["admitted"] = e
                    admitted += 1
                    admitted_tokens += len(log[req.rid]["prompt"])
                c.seen, emitted = len(req.out), emitted + new
                if req.done or req.status != "ok":
                    log[req.rid]["finished"] = e
                    if t_start is None or e - t_start < seconds:
                        _submit(engine, c, *plan.next(), log)
        return {"start": s, "end": e, "busy": busy() - b0, "emitted": emitted,
                "admitted": admitted, "prompt_tokens": admitted_tokens}

    harness.note("weights made, engine built")
    tick(None)                                 # set-up: admit, compile, first decode
    harness.note("first requests admitted")
    setup_s = harness.process_age_s()
    ticks = []
    t0 = time.perf_counter()
    with harness.window(trace_dir):
        while True:
            ticks.append(tick(t0))
            if ticks[-1]["end"] - t0 >= seconds:
                break
    t_end = ticks[-1]["end"]
    peak = harness.memory_peak_bytes(devs) if devs else 0

    emitted = sum(k["emitted"] for k in ticks)
    gaps = []
    for r in log.values():
        ts = r["times"]
        gaps += [b - a for a, b in zip(ts, ts[1:]) if t0 < b <= t_end]
    attempted = len(log)
    failed = sum(r["req"].status != "ok" for r in log.values())
    sample = choose_sample(log, t0, t_end, t["check_sequences"], seed)
    harness.note(f"window: {len(ticks)} ticks, {emitted} "
                 f"tokens in {t_end - t0:.3f}s; checking {len(sample)} requests, "
                 f"{sum(len(o) for _, o in sample)} served tokens")
    by_admitted = {}
    for k in ticks:
        by_admitted.setdefault(k["admitted"], []).append(k["end"] - k["start"])
    for n, d in sorted(by_admitted.items()):
        harness.note(f"ticks with {n} admissions: {len(d)}, "
                     f"{min(d):.4f}-{max(d):.4f}s")
    i, k = max(enumerate(ticks), key=lambda ik: ik[1]["end"] - ik[1]["start"])
    harness.note(f"slowest tick: #{i}, {k['end'] - k['start']:.4f}s, of which "
                 f"{k['busy']:.4f}s in the engine's calls; {k['admitted']} "
                 f"admissions of {k['prompt_tokens']} prompt tokens")
    del engine, params
    harness.free()
    checks = compare(cell, seed, sample)
    harness.note("reference compared")
    admissions = sum(1 for r in log.values() if t0 < r.get("admitted", -1) <= t_end)
    return harness.Outcome(
        correct=harness.judge(checks) and failed == 0,
        attempted=attempted, failed=failed,
        end_to_end={"setup_s": setup_s,
                    "serve_tokens_per_s": emitted / (t_end - t0)},
        checks=checks, memory_peak_bytes=peak,
        counts={"window_s": t_end - t0, "ticks": ticks, "emitted": emitted,
                "gaps": gaps,
                "admissions": admissions,
                "prompt_tokens": sum(k["prompt_tokens"] for k in ticks),
                "decode_tokens": emitted - admissions,
                "flops_per_token": cell.system.serve_flops_per_token(sizes),
                "sample": sample},
        trace_dir=trace_dir)


def choose_sample(log, t0, t_end, n: int, seed: int):
    """Requests whose tokens the reference checks: every request finished
    in the window (the longest first), then requests admitted in it, then
    others that emitted tokens in it, drawn from the seed, ``n`` at most."""
    rng = harness.rng(seed, "sample")
    served = lambda r: len(r["req"].out)
    finished = [r for r in log.values() if t0 < r.get("finished", -1) <= t_end]
    finished.sort(key=served, reverse=True)
    admitted = [r for r in log.values()
                if t0 < r.get("admitted", -1) <= t_end and r not in finished]
    others = [r for r in log.values()
              if r not in finished and r not in admitted
              and any(t0 < x <= t_end for x in r["times"])]
    picked = finished[:1]
    for group in (finished[1:], admitted, others):
        order = rng.permutation(len(group))
        picked += [group[i] for i in order][:max(0, n - len(picked))]
    return [(r["prompt"], list(r["req"].out)) for r in picked[:n]]


def pack(sample, n: int, max_len: int, max_out: int):
    """Sequences as the reference reads them: tokens (n, max_len) holding
    prompt + served tokens but the last; positions (n, max_out) of the
    rows that predict each served token; targets and a validity mask."""
    tokens = np.zeros((n, max_len), np.int32)
    positions = np.zeros((n, max_out), np.int32)
    targets = np.zeros((n, max_out), np.int32)
    valid = np.zeros((n, max_out), bool)
    for i, (prompt, out) in enumerate(sample[:n]):
        seq = prompt + out[:-1]
        tokens[i, :len(seq)] = seq
        k = len(out)
        positions[i, :k] = np.arange(len(prompt) - 1, len(prompt) - 1 + k)
        positions[i, k:] = len(prompt) - 1
        targets[i, :k] = out
        valid[i, :k] = True
    return tokens, positions, targets, valid


def served_gaps(logits, targets, valid):
    """Per served token: the reference's best logit minus the served
    token's logit (0 where the served token is the reference's choice)."""
    import jax.numpy as jnp
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.asarray(targets)[..., None], axis=-1)[..., 0]
    return np.where(valid, np.asarray(best - got, np.float64), 0.0)


def compare(cell, seed: int, sample):
    import jax.numpy as jnp
    t = cell.traffic
    tokens, positions, targets, valid = pack(
        sample, t["check_sequences"], t["prompt_len"][1] + t["output_len"][1],
        t["output_len"][1])
    w = cell.reference.make_weights(cell.sizes, harness.key(seed, "weights"))
    logits = cell.reference.served_logits(cell.sizes, w, jnp.asarray(tokens),
                                          jnp.asarray(positions))
    gap = float(served_gaps(logits, targets, valid).max()) if valid.any() else math.inf
    return [("served_gap", gap, cell.limits.get("served_gap"))]

