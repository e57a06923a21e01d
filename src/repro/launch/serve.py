"""Serving driver: batched greedy generation with the ServingEngine.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
      --batch 4 --prompt-len 16 --new-tokens 16 --numerics amsim_jnp \
      --multiplier afm16

Sharded (debug mesh, fused LUT kernels per shard — docs/distributed.md):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --reduced --numerics amsim --multiplier mitchell8 --mesh

Continuous batching (docs/serving.md): ``--stream N`` switches to the
paged scheduler and replays a synthetic timed request stream with ragged
prompt lengths and per-request numerics tiers:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --reduced --stream 8 --tiers exact=native,cheap=amsim_jnp:mitchell8 \
      --capacity 4 --page-size 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced
from repro.core.policy import MODES, NumericsPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.serve.engine import ServingEngine
from repro.serve.scheduler import ContinuousBatchingEngine
from repro.models.transformer import init_lm


def parse_tiers(spec: str) -> dict:
    """``name=mode[:multiplier],...`` -> {name: NumericsPolicy}."""
    tiers = {}
    for part in spec.split(","):
        name, _, pol = part.partition("=")
        if not name or not pol:
            raise SystemExit(f"bad tier spec {part!r} "
                             f"(want name=mode[:multiplier])")
        mode, _, mult = pol.partition(":")
        if mode not in MODES:
            raise SystemExit(f"tier {name!r}: unknown mode {mode!r} "
                             f"(have {sorted(MODES)})")
        tiers[name] = (NumericsPolicy() if mode == "native" and not mult
                       else NumericsPolicy(mode=mode,
                                           multiplier=mult or "fp32"))
    return tiers


def run_stream(args, cfg, params, mesh):
    """Replay a synthetic timed stream through the paged scheduler,
    report total + per-tier throughput, and return the engine."""
    tiers = parse_tiers(args.tiers)
    max_len = args.prompt_len + args.new_tokens + 1
    engine = ContinuousBatchingEngine(
        cfg, tiers, params, max_len=max_len, capacity=args.capacity,
        page_size=args.page_size, mesh=mesh)
    rng = np.random.default_rng(args.seed)
    names = sorted(tiers)
    stream = []
    for i in range(args.stream):
        plen = int(rng.integers(max(1, args.prompt_len // 2),
                                args.prompt_len + 1))
        prompt = rng.integers(1, cfg.vocab, size=plen)
        stream.append((i * args.arrival_every, prompt,
                       args.new_tokens, names[i % len(names)]))
    t0 = time.time()
    engine.run(stream)
    dt = time.time() - t0
    total = sum(len(r.out) for r in engine.finished.values())
    print(f"stream: {args.stream} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s)")
    busy = engine.busy_seconds
    for name in names:
        n = sum(len(r.out) for r in engine.finished.values()
                if r.tier == name)
        print(f"  tier {name}: {n} tokens in {busy[name]:.2f}s of calls "
              f"({n / busy[name]:.1f} tok/s, informational: includes "
              f"compilation)")
    print(f"decode traces: {engine.decode_trace_counts} "
          f"(expect 1 per tier)")
    for name, count in engine.decode_trace_counts.items():
        assert count == 1, f"tier {name} retraced decode ({count}x)"
    return engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--numerics", default="native", choices=MODES,
                    help="native | surrogate | amsim | amsim_jnp | direct "
                         "(docs/numerics.md)")
    ap.add_argument("--multiplier", default="fp32")
    ap.add_argument("--mesh", action="store_true",
                    help="serve on a 2x2 debug mesh (>= 4 devices); with "
                         "--numerics amsim the fused kernels run per shard")
    ap.add_argument("--stream", type=int, default=0, metavar="N",
                    help="continuous batching: replay a synthetic stream "
                         "of N requests through the paged scheduler "
                         "(docs/serving.md)")
    ap.add_argument("--tiers", default="default=native",
                    help="per-request numerics tiers for --stream, "
                         "name=mode[:multiplier],... ")
    ap.add_argument("--capacity", type=int, default=4,
                    help="resident slots per tier lane (--stream)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--stream)")
    ap.add_argument("--arrival-every", type=int, default=1,
                    help="scheduler ticks between request arrivals "
                         "(--stream)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.family == "encdec":
        raise SystemExit("use examples/whisper-style driver for encdec")

    key = jax.random.PRNGKey(args.seed)
    params = init_lm(key, cfg)
    mesh = make_debug_mesh(2, 2) if args.mesh else None

    if args.stream:
        run_stream(args, cfg, params, mesh)
        return

    policy = (NumericsPolicy() if args.numerics == "native" else
              NumericsPolicy(mode=args.numerics, multiplier=args.multiplier))
    engine = ServingEngine(cfg, policy, params,
                           max_len=args.prompt_len + args.new_tokens + 1,
                           mesh=mesh)
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab, jnp.int32)
    t0 = time.time()
    out = engine.generate(prompts, max_new_tokens=args.new_tokens)
    dt = time.time() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
    print(out[:, :8])


if __name__ == "__main__":
    main()
