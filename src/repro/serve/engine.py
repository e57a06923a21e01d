"""Batched serving: prefill + decode steps with KV caches.

``make_serve_step`` builds the jit-able one-token decode step the
``decode_32k`` / ``long_500k`` dry-run cells lower; ``ServingEngine``
drives batched greedy generation on top of it (examples/serve_lm.py).

Every contraction in both prefill and decode routes through the policy's
batched approximate-GEMM engine (kernels/ops.py): attention score/value
einsums and MoE expert stacks lower to the single 4-D-grid Pallas kernel
in ``amsim`` mode rather than per-example maps, so serving under an
approximate multiplier pays one kernel launch per contraction per step.
KV caches are donated to the decode step off-CPU, making the ring-buffer
update in-place instead of a copy per generated token.

Sharded serving: pass ``mesh=`` and the engine places params with the
Megatron/FSDP rules (``distributed/sharding``), shards the KV caches
(batch over data axes, KV heads over "model" — the exact layout the
sharded fused attention kernel consumes) and traces prefill/decode
inside the mesh context, so ``mode="amsim"`` lowers per shard through
``distributed/shard_fused`` (kill switch REPRO_SHARD_FUSED=0; see
docs/configuration.md and docs/distributed.md).
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.policy import Numerics
from repro.models.transformer import init_lm_caches, lm_forward


def make_prefill(cfg: ArchConfig, policy: Numerics, max_len: int):
    def prefill(params, tokens, caches):
        """tokens (B, S_prompt) -> (next_token (B,1), caches).  Only
        the last position is unembedded."""
        B, S = tokens.shape
        logits, caches, _ = lm_forward(
            params, tokens, cfg, policy, caches=caches,
            logits_at=jnp.full((B,), S - 1, jnp.int32))
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, caches
    return prefill


def make_serve_step(cfg: ArchConfig, policy: Numerics,
                    window: Optional[int] = None):
    """Build the single-token decode step.  The dispatch is trace-time,
    so jit the returned step AFTER setting any REPRO_* switches."""
    def serve_step(params, tokens, caches):
        """One decode step: tokens (B, 1) -> (logits, next_token, caches)."""
        logits, caches, _ = lm_forward(params, tokens, cfg, policy,
                                       caches=caches, window=window)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return logits, nxt, caches
    return serve_step


class ServingEngine:
    """Greedy batched generation driver over prefill + decode.

    ``policy`` is a flat NumericsPolicy or a per-site PolicyTable
    (docs/policies.md): the site labels thread through lm_forward into
    prefill and every decode step, so heterogeneous tables serve with
    exactly the numerics they train with — per-site resolution is
    trace-time, adding zero per-token dispatch cost."""

    def __init__(self, cfg: ArchConfig, policy: Numerics,
                 params, max_len: int = 512, mesh=None,
                 window: Optional[int] = None):
        self.cfg, self.policy, self.params = cfg, policy, params
        self.max_len = max_len
        # None -> the architecture's own sliding window (0 = off), same
        # default lm_forward applies.  Previously this was never threaded
        # into make_serve_step, so an explicit engine-level window was
        # silently ignored by every decode step.
        self.window = cfg.sliding_window if window is None else window
        self.mesh = mesh
        if mesh is not None:
            from repro.distributed.sharding import (lm_param_pspecs,
                                                    to_shardings)
            self.params = jax.device_put(
                params, to_shardings(lm_param_pspecs(params, cfg, mesh),
                                     mesh))
        # Donate the cache argument so the per-token ring-buffer write is
        # in-place.  CPU ignores donation with a warning, so gate on
        # backend rather than donating unconditionally.
        donate = () if jax.default_backend() == "cpu" else (2,)
        self.prefill = jax.jit(make_prefill(cfg, policy, max_len),
                               donate_argnums=donate)
        self.step = jax.jit(make_serve_step(cfg, policy, window=self.window),
                            donate_argnums=donate)

    def _ctx(self):
        """Mesh context for tracing/executing: inside it, mode="amsim"
        dispatches to the sharded fused kernels (shard_fused reads the
        ambient mesh at trace time)."""
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _shard_caches(self, caches, batch: int):
        from repro.distributed.sharding import cache_pspecs, to_shardings
        return jax.device_put(
            caches, to_shardings(cache_pspecs(caches, self.mesh, batch),
                                 self.mesh))

    def generate(self, prompts, max_new_tokens: int = 32):
        """prompts: int32 (B, S) -> int32 (B, max_new_tokens).

        Greedy decode: token i is the argmax over the logits at position
        len(prompt) + i - 1, exactly the sequence a full-prefill argmax
        recomputation would produce (asserted in tests/test_serve.py for
        both native and amsim numerics).
        """
        B = prompts.shape[0]
        if max_new_tokens <= 0:
            return jnp.zeros((B, 0), jnp.int32)
        if prompts.shape[1] + max_new_tokens > self.max_len:
            # The ring buffer would silently wrap and overwrite the oldest
            # keys, corrupting every token after the wrap — fail loudly
            # instead.  (prompt_len + max_new == max_len is fine: the last
            # generated token is never written back to the cache.)
            raise ValueError(
                f"prompt length {prompts.shape[1]} + max_new_tokens "
                f"{max_new_tokens} exceeds the engine's max_len "
                f"{self.max_len}; raise max_len or shorten the request")
        with self._ctx():
            caches = init_lm_caches(self.cfg, B, self.max_len)
            if self.mesh is not None:
                caches = self._shard_caches(caches, B)
            nxt, caches = self.prefill(self.params, prompts, caches)
            # Preallocated on-device token buffer instead of a growing
            # per-token Python list + one big trailing concatenate:
            # memory is bounded up front, and because the (B, max_new)
            # int32 buffer stays on device the loop remains fully
            # async-dispatchable — no host sync per token, one transfer
            # when the caller reads the result.  The per-step
            # dynamic_update_slice copies only the tiny token buffer,
            # never the KV caches.
            buf = jnp.zeros((B, max_new_tokens), jnp.int32)
            buf = jax.lax.dynamic_update_slice(buf, nxt, (0, 0))
            for i in range(1, max_new_tokens):
                _, nxt, caches = self.step(self.params, nxt, caches)
                buf = jax.lax.dynamic_update_slice(buf, nxt, (0, i))
        return buf
