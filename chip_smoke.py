#!/usr/bin/env python3
"""Smoke test on one TPU: the LUT-simulated main path, end to end.

    python3 chip_smoke.py                # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --four-chips   # 2x2 mesh path against one chip

Phases, in order, all in this one process (a chip belongs to one
process, so nothing here starts another):

  (a) products: a k=1 outer product over every 2^7 x 2^7 mantissa pair
      (x exponents x signs, plus zero, subnormal and overflow operands)
      through the compiled fused GEMM equals ``np_amsim_multiply``
      bitwise, treating +0 and -0 alike — afm16 and bf16;
  (b) serving: granite-3-2b at its published widths, all 40 layers,
      random weights from ``--seed``, through the continuous-batching
      engine exactly as ``launch/serve.py --stream`` builds it, with an
      exact (native) and an approximate (amsim:afm16) tier; 4 requests,
      32-64 prompt tokens, 8 new tokens each.  The amsim decode step must
      compile to Pallas TPU kernels (``tpu_custom_call``);
  (c) training: the same widths cut to 2 layers, 3 ``Trainer`` steps as
      ``launch/train.py`` wires them, native and amsim:afm16, batch 2 x
      seq 128 — finite losses; then greedy tokens of a prefill plus 4
      decode steps must match between ``amsim`` (kernels) and
      ``amsim_jnp`` (the jnp oracle).

``--four-chips`` runs only the path that exists across chips: one
training step of the 2-layer model on a 2x2 mesh with sharded fused
kernels against the same step on one device (loss to rtol 5e-5), and
sharded serving tokens against single-device tokens.

The script fails, and prints no result, when JAX finds no TPU.  Its
last line on success is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "granite-3-2b"
TIERS = "exact=native,approx=amsim:afm16"


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ (a)
def operand_grid(M: int, exponents, seed: int):
    """f32 operands covering every top-M mantissa pattern (random low
    bits, which the LUT index must ignore) at each exponent and sign,
    plus +-0, a subnormal and the largest finite value."""
    import numpy as np

    rng = np.random.default_rng(seed)
    top = np.arange(1 << M, dtype=np.uint32) << np.uint32(23 - M)
    low = rng.integers(0, 1 << (23 - M), size=top.size).astype(np.uint32)
    words = [(np.uint32(s << 31) | np.uint32(e << 23) | top | low)
             for e in exponents for s in (0, 1)]
    special = np.array([0, 1 << 31, 1, 0x7F7FFFFF], np.uint32)
    return np.concatenate(words + [special]).view(np.float32)


def check_products(name: str, exponents, seed: int) -> int:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.amsim import np_amsim_multiply
    from repro.core.lutgen import get_lut, get_packed_lut
    from repro.core.multipliers import get_multiplier
    from repro.kernels.approx_gemm import approx_gemm

    mult = get_multiplier(name)
    M = mult.mantissa_bits
    v = operand_grid(M, exponents, seed)
    a, b = v[:, None], v[None, :]
    out = np.asarray(approx_gemm(jnp.asarray(a), jnp.asarray(b),
                                 get_packed_lut(mult), M, mult=name))
    ref = np_amsim_multiply(a, b, get_lut(mult), M)
    same = (out.view(np.uint32) == ref.view(np.uint32)) | (
        (out == 0) & (ref == 0))
    bad = int((~same).sum())
    log(f"[a] {name}: {out.size} products through the fused GEMM, "
        f"{bad} differ from np_amsim_multiply")
    return bad


# ------------------------------------------------------------------ (b)
def serve_phase(cfg, seed: int, *, prompt_len: int = 64,
                new_tokens: int = 8, requests: int = 4) -> bool:
    import jax

    from repro.launch.serve import run_stream
    from repro.models.transformer import init_lm

    t0 = time.time()
    # Jitted, so each weight is generated in place: 10 GB of f32
    # weights then fit a 16 GB chip with room for the steps.
    params = jax.jit(lambda k: init_lm(k, cfg))(jax.random.PRNGKey(seed))
    n_params = sum(math.prod(x.shape) for x in jax.tree.leaves(params))
    jax.block_until_ready(params)
    log(f"[b] {cfg.name}: {cfg.n_layers} layers, d={cfg.d_model}, "
        f"d_ff={cfg.d_ff}, {n_params:.3e} params (f32) initialised in "
        f"{time.time() - t0:.1f}s")
    args = argparse.Namespace(
        tiers=TIERS, stream=requests, prompt_len=prompt_len,
        new_tokens=new_tokens, capacity=2, page_size=16, arrival_every=1,
        seed=seed)
    engine = run_stream(args, cfg, params, None)
    done = list(engine.finished.values())
    answered = (len(done) == requests
                and all(r.status == "ok" and len(r.out) == new_tokens
                        for r in done))
    log(f"[b] {len(done)}/{requests} requests answered with "
        f"{new_tokens} tokens each: {answered}")
    text = engine.lower_decode("approx").compile().as_text()
    calls = text.count("tpu_custom_call")
    log(f"[b] tpu_custom_call in the compiled amsim decode step: {calls}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[b] peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    del engine, params
    gc.collect()
    return answered and calls > 0


# ------------------------------------------------------------------ (c)
def greedy(cfg, params, policy, prompts, steps: int):
    """Tokens and decode logits of a prefill plus ``steps`` decode steps."""
    import jax
    import numpy as np

    from repro.models.transformer import init_lm_caches
    from repro.serve.engine import make_prefill, make_serve_step

    max_len = prompts.shape[1] + steps + 1
    caches = init_lm_caches(cfg, prompts.shape[0], max_len)
    nxt, caches = jax.jit(make_prefill(cfg, policy, max_len))(
        params, prompts, caches)
    step = jax.jit(make_serve_step(cfg, policy))
    toks, logits = [np.asarray(nxt)], []
    for _ in range(steps):
        lg, nxt, caches = step(params, nxt, caches)
        toks.append(np.asarray(nxt))
        logits.append(np.asarray(lg))
    return np.concatenate(toks, axis=1), np.stack(logits)


def train_phase(cfg, seed: int, *, batch: int = 2, seq: int = 128,
                steps: int = 3) -> bool:
    import jax
    import numpy as np

    from repro.configs.base import ShapeConfig
    from repro.core.policy import NumericsPolicy
    from repro.launch.train import train
    from repro.models.transformer import init_lm

    shape = ShapeConfig("smoke", seq, batch, "train")
    ok = True
    for pol in (NumericsPolicy(),
                NumericsPolicy(mode="amsim", multiplier="afm16")):
        t0 = time.time()
        state = train(cfg, pol, shape, steps=steps, seed=seed, log_every=1)
        losses = [m["loss"] for _, m in state.history]
        finite = len(losses) == steps and all(map(math.isfinite, losses))
        ok &= finite
        log(f"[c] {pol.mode}/{pol.multiplier} {cfg.n_layers}-layer "
            f"training, batch {batch} x seq {seq}: losses {losses} "
            f"(finite: {finite}; {time.time() - t0:.1f}s with compilation)")
        del state
        gc.collect()
    params = init_lm(jax.random.PRNGKey(seed), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, 32),
                                 1, cfg.vocab)
    tk, lk = greedy(cfg, params, NumericsPolicy(mode="amsim",
                                                multiplier="afm16"),
                    prompts, 4)
    tj, lj = greedy(cfg, params, NumericsPolicy(mode="amsim_jnp",
                                                multiplier="afm16"),
                    prompts, 4)
    same = bool(np.array_equal(tk, tj))
    log(f"[c] greedy tokens, prefill + 4 decode steps: amsim {tk.tolist()} "
        f"amsim_jnp {tj.tolist()} match: {same}; largest decode-logit "
        f"difference {float(np.max(np.abs(lk - lj)))}")
    return ok and same


# ------------------------------------------------------------ 4 chips
def four_chip_phase(cfg, seed: int, *, batch: int = 2, seq: int = 128) -> bool:
    import jax
    import numpy as np

    from repro.configs.base import ShapeConfig
    from repro.core.policy import NumericsPolicy
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.train import train
    from repro.models.transformer import init_lm
    from repro.serve.engine import ServingEngine

    mesh = make_debug_mesh(2, 2)
    pol = NumericsPolicy(mode="amsim", multiplier="afm16")
    shape = ShapeConfig("smoke", seq, batch, "train")
    losses = {}
    for name, m in (("one device", None), ("2x2 mesh", mesh)):
        t0 = time.time()
        state = train(cfg, pol, shape, steps=1, seed=seed, mesh=m,
                      log_every=1)
        losses[name] = state.history[0][1]["loss"]
        log(f"[4] {name}: training step loss {losses[name]!r} "
            f"({time.time() - t0:.1f}s with compilation)")
        del state
        gc.collect()
    l1, l4 = losses["one device"], losses["2x2 mesh"]
    train_ok = bool(np.isclose(l4, l1, rtol=5e-5, atol=0))
    log(f"[4] mesh vs one-device loss within rtol 5e-5: {train_ok} "
        f"(relative difference {abs(l4 - l1) / abs(l1):.3e})")

    params = init_lm(jax.random.PRNGKey(seed), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, 16),
                                 1, cfg.vocab)
    toks = {}
    for name, m in (("one device", None), ("2x2 mesh", mesh)):
        engine = ServingEngine(cfg, pol, params, max_len=32, mesh=m)
        toks[name] = np.asarray(engine.generate(prompts, max_new_tokens=8))
        del engine
    serve_ok = bool(np.array_equal(toks["one device"], toks["2x2 mesh"]))
    log(f"[4] sharded serving tokens {toks['2x2 mesh'].tolist()} == "
        f"single-device tokens: {serve_ok}")
    return train_ok and serve_ok


# --------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh path and its one-device "
                         "comparison (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        import jax

        from repro.configs import get_arch
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    if "TPU_LOG_DIR" not in os.environ:
        # The TPU runtime logs under /tmp unless told otherwise (it reads
        # this when the backend starts, below); keep its logs in the
        # checkout with the program's other caches.
        log_dir = ROOT / ".cache" / "tpu_logs"
        log_dir.mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(log_dir)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is {dev.platform}: "
              f"{dev.device_kind})", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {enable_compile_cache()}")
    cfg = get_arch(ARCH)
    cfg2 = dataclasses.replace(cfg, name=f"{cfg.name}-2L", n_layers=2)

    t0 = time.time()
    if args.four_chips:
        ok = four_chip_phase(cfg2, args.seed)
    else:
        bad = sum(check_products(name, (1, 2, 64, 127, 128, 200, 253, 254),
                                 args.seed) for name in ("afm16", "bf16"))
        ok = bad == 0
        ok &= serve_phase(cfg, args.seed)
        ok &= train_phase(cfg2, args.seed)
    log(f"phases {'passed' if ok else 'FAILED'} in {time.time() - t0:.1f}s")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
