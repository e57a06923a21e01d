"""Continuous batching over paged KV caches, with per-request numerics tiers.

``ContinuousBatchingEngine`` generalises ``ServingEngine`` from "one
fixed batch, ring caches, run to completion" to a request stream:
requests arrive with their own prompt length, token budget and numerics
tier, are admitted into fixed slots as capacity frees up, and retire
individually — the batch composition changes every step while the jitted
step functions never retrace (docs/serving.md).

Fixed shapes, moving batch
    Each decode step runs over a fixed-capacity ``(C, 1)`` slot tensor
    plus per-slot control arrays (page table, start position, liveness).
    Admission/eviction mutate only the host-side control mirror
    (serve/paged_cache.LaneControl); dead slots decode garbage into the
    trash page.  One trace per tier lane — asserted via trace counters.

Numerics tiers
    ``tiers`` maps tier name -> Numerics (flat policy or PolicyTable,
    docs/policies.md).  Each tier gets its own *lane*: its own slot
    capacity, page pools, allocator and jitted prefill/decode closed
    over that tier's policy, so every tier's contractions lower through
    its own resolved leaf (a trunc7 request never shares a kernel with a
    mitchell8 one).  Same-tier requests batch together; tiers run
    sequentially per tick.

Scheduling (deterministic, greedy)
    Per tick: (1) FIFO admission with head-of-line blocking (no
    reordering, so admission order is reproducible); (2) page-fault
    resolution — allocate the page each live slot's next decode write
    needs, preempting the youngest other resident of the lane when the
    pool is dry (preemption = release pages + requeue with prompt' =
    prompt ++ emitted; greedy argmax decode makes the recomputation
    token-identical);
    (3) one batched decode step per lane with live slots; (4) per-slot
    bookkeeping — append token, advance start, release window-stale
    pages, retire finished requests.

Prefill runs per admission at bucketed (power-of-two) padded length with
the true length as a *traced* argument, so ragged prompts cost at most
one trace per bucket, not one per length.

Observation (docs/serving.md, "Observing the engine")
    Each tick is an ``engine.tick`` span holding ``engine.admit`` (one
    ``engine.prefill`` per admission), ``engine.faults``,
    ``engine.decode`` and ``engine.bookkeeping``; every prefill and
    decode call splits into ``.upload``, ``.launch`` and ``.wait``.
    ``counters`` counts admissions, preemptions, quarantines, prefill
    tokens true and padded, prefills per bucket, decode ticks and rows,
    traces and busy seconds per tier; with Mamba layers, admissions that
    wrote a slot's recurrent state and slots whose state a tick updated.

Slot state (``layer_types`` stacks with Mamba-2 layers)
    Beside the KV pages each lane holds every slot's recurrent and conv
    state (``models/transformer.init_paged_lm_caches``).  The admission
    prefill writes its slot's rows, every decode tick updates every
    slot's rows in place on the donated caches, and a preemption needs
    no snapshot: the re-admission recomputes the state from the prompt.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.core.policy import Numerics
from repro.models.transformer import init_paged_lm_caches, lm_forward
from repro.serve.paged_cache import (TRASH_PAGE, LaneControl, PageAllocator,
                                     pages_for)

_MIN_BUCKET = 16


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return b


def _merge_control(caches, ptab, live, start, slot=None):
    """Broadcast the per-slot control arrays over the layer dim and merge
    them into the pool tree, so lm_forward's layer scan slices a complete
    paged cache dict per layer (models/attention._paged_cache_update).
    In a ``layer_types`` stack the pools are under ``"attention"``, and an
    admission prefill's ``slot`` (B,) goes to the Mamba layers' state
    (models/ssm.mamba2)."""
    if "pool_k" not in caches:
        out = dict(caches)
        if "attention" in caches:
            out["attention"] = _merge_control(caches["attention"], ptab,
                                              live, start)
        if slot is not None and "mamba" in caches:
            out["mamba"] = dict(caches["mamba"], slot=slot)
        return out
    L = caches["pool_k"].shape[0]
    bc = lambda a: jnp.broadcast_to(a[None], (L,) + a.shape)
    return dict(caches, ptab=bc(ptab), live=bc(live), start=bc(start))


_STATE = {"pool_k", "pool_v", "ssm", "conv"}


def _strip_control(caches):
    """Keep only the persistent device state; control is host-authoritative
    and re-uploaded every step, never read back."""
    return {k: _strip_control(v) if isinstance(v, dict) else v
            for k, v in caches.items()
            if isinstance(v, dict) or k in _STATE}


def _count_traces(fn, counters, key: str):
    """``fn`` adding one to ``counters[key]`` each time it runs: under
    ``jax.jit`` the Python body runs only when it is traced.  The wrapper
    keeps ``fn``'s name, so the compiled module keeps its name too."""
    @functools.wraps(fn)
    def traced(*args):
        counters[key] += 1
        return fn(*args)
    return traced


def make_paged_prefill(cfg: ArchConfig, policy: Numerics,
                       window: Optional[int] = None):
    def paged_prefill(params, tokens, true_len, ptab, caches, slot=None):
        """tokens (B, P) right-padded, true_len (B,) traced, ptab
        (B, n_ptab), and in a ``layer_types`` stack the slot (B,) traced
        whose Mamba state the prefill writes -> (next_token (B, 1),
        ok (B,) bool, caches).
        ``ok`` is the non-finite-logit sentinel: False marks a request
        whose next-token distribution is poisoned (argmax would be
        garbage) — the scheduler quarantines it instead of emitting.

        Padding garbage is harmless: queries past true_len are never
        read (only position true_len - 1 is unembedded, and the next
        token comes from it), their K/V writes land in
        allocated-but-not-yet-valid positions or the trash page, and
        causal masking keeps real queries from seeing anything at or
        past their own position.  A Mamba layer's state starts from zero
        and takes no step at padding (its ``dt`` is 0 there).
        """
        B = tokens.shape[0]
        merged = _merge_control(caches, ptab,
                                jnp.ones((B,), bool),
                                jnp.zeros((B,), jnp.int32), slot)
        logits, merged, _ = lm_forward(params, tokens, cfg, policy,
                                       caches=merged, window=window,
                                       logits_at=true_len - 1,
                                       true_len=true_len)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.isfinite(logits[:, 0, :]).all(axis=-1)
        return nxt, ok, _strip_control(merged)
    return paged_prefill


def make_paged_serve_step(cfg: ArchConfig, policy: Numerics,
                          window: Optional[int] = None):
    def paged_serve_step(params, tokens, live, start, ptab, caches):
        """One decode step over every slot of a lane: tokens (C, 1),
        live (C,), start (C,), ptab (C, n_ptab) -> (next (C, 1),
        ok (C,) bool, caches).  ``ok`` False = non-finite logits in that
        slot (fault quarantine, docs/robustness.md).

        Dead slots ride along at fixed shape: their writes are routed to
        the trash page and their outputs discarded by the scheduler; a
        Mamba layer updates every slot's state, a dead slot's garbage
        until an admission overwrites it.
        """
        merged = _merge_control(caches, ptab, live, start)
        logits, merged, _ = lm_forward(params, tokens, cfg, policy,
                                       caches=merged, window=window)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        ok = jnp.isfinite(logits[:, -1, :]).all(axis=-1)
        return nxt, ok, _strip_control(merged)
    return paged_serve_step


@dataclasses.dataclass
class Request:
    """One generation request in the stream.

    ``status`` is ``"ok"`` until the engine retires the request early:
    ``"fault"`` (non-finite logits with no stronger tier to retry on) or
    ``"deadline"`` (tick budget expired).  Early-retired requests keep
    whatever tokens they emitted — partial output plus an honest status
    beats argmax-of-NaN garbage.  ``expires_at`` is the absolute engine
    tick the deadline lapses at (None = no deadline); ``retiers`` counts
    fault re-admissions onto a stronger tier.
    """
    rid: int
    prompt: list
    max_new_tokens: int
    tier: str
    out: list = dataclasses.field(default_factory=list)
    preemptions: int = 0
    expires_at: Optional[int] = None
    status: str = "ok"
    retiers: int = 0

    @property
    def cur_prompt(self) -> list:
        """Prompt a (re-)admission prefills: original prompt plus every
        token already emitted (greedy decode is deterministic, so
        recomputing from here reproduces the continuation exactly)."""
        return list(self.prompt) + list(self.out)

    @property
    def done(self) -> bool:
        return len(self.out) >= self.max_new_tokens


class _Lane:
    """Per-tier execution lane: slots + page pool + jitted steps closed
    over this tier's policy."""

    def __init__(self, engine: "ContinuousBatchingEngine", name: str,
                 policy: Numerics):
        self.name, self.policy = name, policy
        self.alloc = PageAllocator(engine.n_pages)
        self.ctrl = LaneControl(engine.capacity, engine.n_ptab)
        self.slot_req: list[Optional[Request]] = [None] * engine.capacity
        self.slot_pages: list[dict] = [{} for _ in range(engine.capacity)]
        self.slot_seq = [0] * engine.capacity  # admission order, for victim pick
        self.caches = None  # allocated lazily (possibly sharded) by engine
        donate = () if jax.default_backend() == "cpu" else (5,)
        self.step = jax.jit(
            _count_traces(make_paged_serve_step(engine.cfg, policy,
                                                engine.window),
                          engine.counters, f"decode.traces.{name}"),
            donate_argnums=donate)
        donate = () if jax.default_backend() == "cpu" else (4,)
        self.prefill = jax.jit(
            _count_traces(make_paged_prefill(engine.cfg, policy,
                                             engine.window),
                          engine.counters, f"prefill.traces.{name}"),
            donate_argnums=donate)


class ContinuousBatchingEngine:
    """Greedy continuous-batching server over paged KV caches.

    Parameters
    ----------
    tiers: mapping tier name -> Numerics, or a single Numerics (becomes
        the sole tier ``"default"``).
    max_len: per-request position budget; submit rejects any request
        whose prompt + token budget exceeds it (same contract as
        ``ServingEngine.generate``).
    capacity: resident slots per tier lane.
    page_size: tokens per KV page.
    n_pages: pool size per lane, *including* the reserved trash page.
        Default fully reserves ``capacity`` requests at ``max_len``
        (no preemption unless the caller overcommits on purpose).
    window: sliding attention window (None -> cfg.sliding_window, 0 =
        off).  With a window, pages whose every key has slid out are
        released mid-flight and admission skips pages that would be
        stale on arrival, so long streams hold ~window worth of pages.
    fault_retier: optional tier name -> stronger tier name map.  When a
        request's logits go non-finite (hardware fault in that tier's
        approximate datapath, docs/robustness.md) it is re-admitted
        once, from scratch, on the mapped tier; without a mapping — or
        on a second fault — it retires with ``status="fault"``.

    ``counters`` (a ``collections.Counter``) holds the engine's running totals,
    docs/serving.md lists them; ``busy_seconds``, ``decode_trace_counts``
    and ``prefill_trace_counts`` are per-tier views of it.
    """

    def __init__(self, cfg: ArchConfig, tiers, params, *,
                 max_len: int = 512, capacity: int = 4, page_size: int = 16,
                 n_pages: Optional[int] = None, window: Optional[int] = None,
                 mesh=None, fault_retier: Optional[dict] = None):
        if not isinstance(tiers, dict):
            tiers = {"default": tiers}
        if not tiers:
            raise ValueError("need at least one tier")
        self.cfg, self.params = cfg, params
        self.max_len, self.capacity = max_len, capacity
        self.page_size = page_size
        self.n_ptab = -(-max_len // page_size)
        self.n_pages = (capacity * self.n_ptab + 1 if n_pages is None
                        else n_pages)
        self.window = cfg.sliding_window if window is None else window
        self.mesh = mesh
        if mesh is not None:
            from repro.distributed.sharding import (lm_param_pspecs,
                                                    to_shardings)
            self.params = jax.device_put(
                params, to_shardings(lm_param_pspecs(params, cfg, mesh),
                                     mesh))
        self.counters = collections.Counter()
        # Mamba layers keep recurrent state per slot beside the KV pages.
        self.slot_state = "mamba" in cfg.kinds
        self._lanes = {name: _Lane(self, name, pol)
                       for name, pol in tiers.items()}
        with self._ctx():
            for lane in self._lanes.values():
                caches = init_paged_lm_caches(cfg, self.n_pages, page_size,
                                              capacity)
                if mesh is not None:
                    from repro.distributed.sharding import (cache_pspecs,
                                                            to_shardings)
                    caches = jax.device_put(
                        caches,
                        to_shardings(cache_pspecs(caches, mesh, capacity),
                                     mesh))
                lane.caches = caches
        self.fault_retier = dict(fault_retier or {})
        for src, dst in self.fault_retier.items():
            if src not in self._lanes or dst not in self._lanes:
                raise ValueError(f"fault_retier {src!r} -> {dst!r}: both "
                                 f"must be tiers in {sorted(self._lanes)}")
            if src == dst:
                raise ValueError(f"fault_retier maps {src!r} to itself")
        self._queue: deque[Request] = deque()
        self._next_rid = 0
        self._seq = 0
        self.tick = 0
        self.finished: dict[int, Request] = {}

    # ------------------------------------------------------------- intake
    def submit(self, prompt, max_new_tokens: int, tier: str = "default", *,
               deadline: Optional[int] = None) -> int:
        """Queue one request; returns its id.  Validates up front so a
        request that could never run (or could deadlock the pool) is
        rejected at submit time, not mid-stream.  ``deadline`` is a tick
        budget: a request still unfinished ``deadline`` engine ticks
        from now retires with ``status="deadline"`` and partial output
        (per-request latency SLO)."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        if tier not in self._lanes:
            raise ValueError(f"unknown tier {tier!r}; have "
                             f"{sorted(self._lanes)}")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len ({self.max_len})")
        # The last emitted token is never written back, so a request
        # stores at most len(prompt) + max_new - 1 positions; under a
        # sliding window only ~window of them are resident at once.
        total = len(prompt) + max_new_tokens - 1
        need = pages_for(total, self.page_size)
        if self.window:
            need = min(need, pages_for(self.window, self.page_size) + 2)
        cap = self._lanes[tier].alloc.capacity
        if need > cap:
            raise ValueError(
                f"request needs up to {need} pages resident but the "
                f"{tier!r} lane pool only has {cap}; raise n_pages or "
                f"page_size")
        if deadline is not None and deadline < 1:
            raise ValueError(f"deadline must be >= 1 tick, got {deadline}")
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(
            rid, prompt, max_new_tokens, tier,
            expires_at=None if deadline is None else self.tick + deadline))
        return rid

    # ---------------------------------------------------------- scheduling
    def step(self) -> list[Request]:
        """One scheduler tick; returns the requests that finished
        (including early retirements — check ``Request.status``)."""
        finished: list[Request] = []
        self.tick += 1
        with obs.span("engine.tick", tick=self.tick), self._ctx():
            self._expire_queued(finished)
            with obs.span("engine.admit"):
                self._admit(finished)
            # Faults AFTER admission: a freshly admitted slot whose prompt
            # exactly fills its pages needs the next page before its first
            # decode write, or the KV lands in the trash page and is lost.
            with obs.span("engine.faults"):
                for lane in self._lanes.values():
                    self._resolve_faults(lane)
            for lane in self._lanes.values():
                self._decode(lane, finished)
            with obs.span("engine.bookkeeping"):
                for lane in self._lanes.values():
                    self._expire_resident(lane, finished)
            for req in finished:
                self.finished[req.rid] = req
        return finished

    def _progress(self):
        """Drain's liveness signal.  Besides queue/resident/token counts
        it tracks retirements and re-tiers: a request that is admitted,
        quarantined and re-queued on a stronger tier within one tick
        leaves the first three fields unchanged but IS forward progress
        (its retier count is bumped, and retiers are capped, so this
        can't mask a genuine head-of-line deadlock)."""
        return (len(self._queue),
                sum(int(l.ctrl.live.sum()) for l in self._lanes.values()),
                sum(len(r.out) for l in self._lanes.values()
                    for r in l.slot_req if r is not None),
                len(self.finished),
                sum(r.retiers for r in self._queue))

    def drain(self) -> dict:
        """Tick until queue and slots are empty; returns rid -> tokens."""
        while self._queue or any(l.ctrl.live.any()
                                 for l in self._lanes.values()):
            before = self._progress()
            self.step()
            after = self._progress()
            if before == after and not any(
                    l.ctrl.live.any() for l in self._lanes.values()):
                raise RuntimeError(
                    "scheduler made no progress with nothing resident — "
                    "head-of-line request cannot be admitted")
        return {rid: list(req.out) for rid, req in self.finished.items()}

    def run(self, stream) -> dict:
        """Drive a timed request stream: ``stream`` is an iterable of
        ``(arrival_tick, prompt, max_new_tokens, tier)``.  Requests are
        submitted when the scheduler tick reaches their arrival; ticks
        run until everything drains.  Returns rid -> emitted tokens, in
        submission order of the (arrival-sorted) stream."""
        pending = sorted(stream, key=lambda r: r[0])
        tick = 0
        i = 0
        while i < len(pending) or self._queue or any(
                l.ctrl.live.any() for l in self._lanes.values()):
            while i < len(pending) and pending[i][0] <= tick:
                _, prompt, max_new, tier = pending[i]
                self.submit(prompt, max_new, tier)
                i += 1
            self.step()
            tick += 1
        return {rid: list(req.out) for rid, req in self.finished.items()}

    # ------------------------------------------------------------ internals
    def _ctx(self):
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _resolve_faults(self, lane: _Lane) -> None:
        """Ensure every live slot owns the page its next decode write
        lands in, preempting the youngest other resident when the pool
        is dry."""
        ctrl, ps = lane.ctrl, self.page_size
        for slot in range(self.capacity):
            if not ctrl.live[slot]:
                continue
            idx = int(ctrl.start[slot]) // ps
            while ctrl.ptab[slot, idx] == TRASH_PAGE:
                got = lane.alloc.alloc(1)
                if got is not None:
                    ctrl.ptab[slot, idx] = got[0]
                    lane.slot_pages[slot][idx] = got[0]
                    break
                victims = [s for s in range(self.capacity)
                           if s != slot and ctrl.live[s]]
                if not victims:
                    raise RuntimeError(
                        f"lane {lane.name!r}: page pool exhausted by a "
                        f"single request — submit validation should have "
                        f"rejected it")
                self._preempt(lane, max(victims,
                                        key=lambda s: lane.slot_seq[s]))

    def _preempt(self, lane: _Lane, slot: int) -> None:
        """Evict by recompute: drop the slot's pages and requeue it at
        the front with prompt' = prompt ++ emitted."""
        req = lane.slot_req[slot]
        self._release_slot(lane, slot)
        req.preemptions += 1
        self.counters["preemptions"] += 1
        self._queue.appendleft(req)

    def _quarantine(self, req: Request, finished: list) -> None:
        """Non-finite logits in ``req``'s slot: the emitted distribution
        is poisoned, so no token is appended.  With a ``fault_retier``
        mapping and a first fault, restart the request from scratch on
        the stronger tier (its earlier tokens came off the faulty
        datapath — discard them); otherwise retire with status="fault"."""
        self.counters["quarantines"] += 1
        dst = self.fault_retier.get(req.tier)
        if dst is not None and req.retiers == 0:
            req.retiers += 1
            req.tier = dst
            req.out = []
            self._queue.appendleft(req)
        else:
            req.status = "fault"
            finished.append(req)

    def _expire_queued(self, finished: list) -> None:
        """Retire queued requests whose deadline lapsed before they ever
        got (or re-got) a slot — they can no longer finish in budget."""
        if not any(r.expires_at is not None for r in self._queue):
            return
        keep: deque[Request] = deque()
        for req in self._queue:
            if req.expires_at is not None and self.tick > req.expires_at:
                req.status = "deadline"
                finished.append(req)
            else:
                keep.append(req)
        self._queue = keep

    def _expire_resident(self, lane: _Lane, finished: list) -> None:
        """Retire live slots whose tick budget is spent (after this
        tick's decode, so a request gets exactly ``deadline`` ticks)."""
        ctrl = lane.ctrl
        for slot in range(self.capacity):
            if not ctrl.live[slot]:
                continue
            req = lane.slot_req[slot]
            if req.expires_at is not None and self.tick >= req.expires_at:
                req.status = "deadline"
                self._release_slot(lane, slot)
                finished.append(req)

    def _release_slot(self, lane: _Lane, slot: int) -> None:
        lane.alloc.release(lane.slot_pages[slot].values())
        lane.slot_pages[slot] = {}
        lane.slot_req[slot] = None
        lane.ctrl.clear_slot(slot)

    def _admit(self, finished: list) -> None:
        """FIFO admission with head-of-line blocking: the oldest queued
        request either gets a slot + pages in its tier's lane (prefill
        runs immediately) or blocks everything behind it — no
        reordering, so the schedule is reproducible."""
        while self._queue:
            req = self._queue[0]
            lane = self._lanes[req.tier]
            free = lane.ctrl.free_slots()
            if not free:
                break
            cur = req.cur_prompt
            m = len(cur)
            # Under a sliding window, skip pages that are already fully
            # stale for the *prefill's own last query* (key positions
            # < m - window are outside every mask it can apply); their
            # writes fall through to the trash page.
            lo = (max(0, m - self.window) // self.page_size
                  if self.window else 0)
            hi = pages_for(m, self.page_size) - 1
            pages = lane.alloc.alloc(hi - lo + 1)
            if pages is None:
                break
            self._queue.popleft()
            slot = free[0]
            ctrl = lane.ctrl
            for j, p in zip(range(lo, hi + 1), pages):
                ctrl.ptab[slot, j] = p
                lane.slot_pages[slot][j] = p
            P = _bucket(m)
            toks = np.zeros((1, P), np.int32)
            toks[0, :m] = cur
            with obs.span("engine.prefill", bucket=P, tokens=m):
                t0 = time.perf_counter()
                with obs.span("engine.prefill.upload"):
                    args = (jnp.asarray(toks),
                            jnp.asarray([m], dtype=jnp.int32),
                            jnp.asarray(ctrl.ptab[slot:slot + 1]))
                    state = ((jnp.asarray([slot], dtype=jnp.int32),)
                             if self.slot_state else ())
                with obs.span("engine.prefill.launch"):
                    nxt, ok, lane.caches = lane.prefill(self.params, *args,
                                                        lane.caches, *state)
                with obs.span("engine.prefill.wait"):
                    nxt, ok = np.asarray(nxt), np.asarray(ok)
                self.counters[f"busy_s.{lane.name}"] += \
                    time.perf_counter() - t0
            self.counters.update({"admissions": 1, "prefill.tokens": m,
                                  "prefill.bucket_tokens": P,
                                  "prefill.head_rows": toks.shape[0],
                                  f"prefill.bucket.{P}": 1})
            if state:
                self.counters["prefill.state_writes"] += 1
            lane.slot_req[slot] = req
            if not bool(ok[0]):
                self._release_slot(lane, slot)
                self._quarantine(req, finished)
                continue
            tok = int(nxt[0, 0])
            req.out.append(tok)
            self._seq += 1
            lane.slot_seq[slot] = self._seq
            if req.done:
                self._release_slot(lane, slot)
                finished.append(req)
            else:
                ctrl.live[slot] = True
                ctrl.start[slot] = m
                ctrl.last_tok[slot] = tok
                self._maybe_release_stale(lane, slot)

    def _decode(self, lane: _Lane, finished: list) -> None:
        ctrl = lane.ctrl
        live = int(ctrl.live.sum())
        if not live:
            return
        with obs.span("engine.decode", rows=self.capacity, live=live):
            t0 = time.perf_counter()
            with obs.span("engine.decode.upload"):
                args = self._decode_args(lane)
            with obs.span("engine.decode.launch"):
                nxt, ok, lane.caches = lane.step(self.params, *args)
            with obs.span("engine.decode.wait"):
                nxt = np.asarray(nxt)[:, 0]
                ok = np.asarray(ok)
            self.counters[f"busy_s.{lane.name}"] += time.perf_counter() - t0
        self.counters.update({"decode.ticks": 1,
                              "decode.rows_run": self.capacity,
                              "decode.rows_live": live})
        if self.slot_state:
            self.counters["decode.state_rows"] += self.capacity
        with obs.span("engine.bookkeeping"):
            for slot in range(self.capacity):
                if not ctrl.live[slot]:
                    continue
                req = lane.slot_req[slot]
                if not ok[slot]:
                    self._release_slot(lane, slot)
                    self._quarantine(req, finished)
                    continue
                tok = int(nxt[slot])
                req.out.append(tok)
                ctrl.start[slot] += 1
                ctrl.last_tok[slot] = tok
                if req.done:
                    self._release_slot(lane, slot)
                    finished.append(req)
                else:
                    self._maybe_release_stale(lane, slot)

    @staticmethod
    def _decode_args(lane: _Lane) -> tuple:
        ctrl = lane.ctrl
        return (jnp.asarray(ctrl.last_tok[:, None]), jnp.asarray(ctrl.live),
                jnp.asarray(ctrl.start), jnp.asarray(ctrl.ptab), lane.caches)

    def lower_decode(self, tier: str):
        """The tier's decode step lowered at its lane's shapes
        (``jax.stages.Lowered``): what one decode tick compiles to."""
        lane = self._lanes[tier]
        with self._ctx():
            return lane.step.lower(self.params, *self._decode_args(lane))

    def _maybe_release_stale(self, lane: _Lane, slot: int) -> None:
        """Release leading pages whose every key has slid out of the
        window for all queries from position start onward (a page j is
        dead once (j+1)*page_size - 1 <= start - window)."""
        if not self.window:
            return
        cut = (int(lane.ctrl.start[slot]) - self.window + 1) // self.page_size
        if cut <= 0:
            return
        stale = [j for j in lane.slot_pages[slot] if j < cut]
        for j in stale:
            lane.alloc.release([lane.slot_pages[slot].pop(j)])
            lane.ctrl.ptab[slot, j] = TRASH_PAGE

    # ---------------------------------------------------------- telemetry
    @property
    def decode_trace_counts(self) -> dict:
        """Tier name -> number of times its decode step was traced
        (steady-state contract: exactly 1)."""
        return {n: self.counters[f"decode.traces.{n}"] for n in self._lanes}

    @property
    def busy_seconds(self) -> dict:
        """Tier name -> host seconds spent in its prefill and decode
        calls, each waited to completion (first calls include
        compilation)."""
        return {n: float(self.counters[f"busy_s.{n}"]) for n in self._lanes}

    @property
    def prefill_trace_counts(self) -> dict:
        """Tier name -> prefill traces (at most one per prompt bucket)."""
        return {n: self.counters[f"prefill.traces.{n}"] for n in self._lanes}

    @property
    def n_free_pages(self) -> dict:
        return {n: lane.alloc.n_free for n, lane in self._lanes.items()}
