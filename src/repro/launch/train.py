"""Distributed training driver.

Runs a *real* (reduced or full) training job on whatever devices exist:
the production mesh when 256+ devices are available, else a debug mesh.
The same cell builders as the dry-run wire shardings, so this driver is
the dry-run made executable.

Numerics-mode matrix (``--numerics``; details in docs/configuration.md
and docs/numerics.md):

  native     exact f32 — the "TFnG" baseline, GSPMD-parallel.
  surrogate  mantissa-truncated operands + native MXU dot — fastest
             approximate mode, GSPMD-parallel (truncation family only).
  amsim      fused Pallas LUT kernels.  Under a mesh the kernels run
             PER SHARD via distributed/shard_fused (column/row-parallel
             GEMMs, head/batch-sharded attention) — set
             REPRO_SHARD_FUSED=0 to fall back to GSPMD's
             replicated-kernel lowering.
  amsim_jnp  pure-jnp LUT simulation — the portable oracle; GSPMD
             shards it like any jnp program (no fused kernels).
  direct     pure-jnp bit-level multiplier model (paper's direct sim).

Heterogeneous per-site numerics (docs/policies.md): ``--numerics-table
table.json`` loads a PolicyTable, or ``--assign
"conv=mitchell8,head=native"`` assigns multipliers per site on top of
the ``--numerics``/``--multiplier`` default; the path report then
prints one line per resolved rule.  ``launch/sweep.py`` runs grids of
such assignments and reports convergence vs the fp32 baseline.

Example (CPU, reduced config, sharded fused kernels on a debug mesh):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --reduced --steps 20 --batch 8 --seq 128 --numerics amsim \
      --multiplier afm16
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import SHAPES, get_arch, reduced
from repro.configs.base import ShapeConfig
from repro.core.policy import (MODES, NumericsPolicy, PolicyTable,
                               table_from_assignments, table_from_json)
from repro.data.pipeline import lm_batch
from repro.distributed import shard_fused
from repro.distributed.sharding import (lm_param_pspecs, opt_state_pspecs,
                                        to_shardings)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import encdec as encdec_mod
from repro.models.transformer import init_lm, lm_loss
from repro.optim.optimizers import cosine_schedule, make_optimizer
from repro.train.step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig, TrainerState


def _describe_numerics(policy, mesh) -> str:
    """An honest report of which execution path this run lowers to.

    Flat policies keep the historical single line; a PolicyTable prints
    the resolved per-site table — one line per distinct rule — plus the
    execution-path note for its amsim rules."""
    if isinstance(policy, PolicyTable):
        lines = [f"numerics table ({len(policy.rules)} rules, resolved "
                 f"per site/pass — docs/policies.md):"]
        lines += [f"  {line}" for line in policy.describe()]
        has_amsim = any(r.mode == "amsim" for r in policy.rules)
        if has_amsim:
            if mesh is None:
                lines.append("  amsim rules: single-device fused LUT kernels")
            elif shard_fused.env_enabled():
                lines.append(f"  amsim rules: sharded fused LUT kernels on "
                             f"mesh {dict(mesh.shape)} "
                             f"(REPRO_SHARD_FUSED=0 to disable)")
            else:
                lines.append("  amsim rules: REPRO_SHARD_FUSED=0 — GSPMD "
                             "fallback, kernels replicated per device")
        return "\n".join(lines)
    if policy.mode != "amsim":
        return f"numerics={policy.mode}/{policy.multiplier}"
    if mesh is None:
        return (f"numerics=amsim/{policy.multiplier}: single-device fused "
                f"LUT kernels")
    if shard_fused.env_enabled():
        return (f"numerics=amsim/{policy.multiplier}: sharded fused LUT "
                f"kernels on mesh {dict(mesh.shape)} "
                f"(REPRO_SHARD_FUSED=0 to disable)")
    return (f"numerics=amsim/{policy.multiplier}: REPRO_SHARD_FUSED=0 — "
            f"GSPMD fallback, kernels replicated per device")


def main():
    ap = argparse.ArgumentParser(
        description="distributed training driver (docs/distributed.md)")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--numerics", default="native", choices=MODES,
                    help="execution mode: native (exact f32) | surrogate "
                         "(truncate + MXU) | amsim (fused Pallas LUT "
                         "kernels; sharded per shard under a mesh — see "
                         "docs/distributed.md) | amsim_jnp (portable jnp "
                         "oracle) | direct (bit-level model)")
    ap.add_argument("--multiplier", default="fp32",
                    help="approximate-multiplier name for non-native modes "
                         "(e.g. bf16, afm16, mitchell8, exact7)")
    ap.add_argument("--numerics-table", metavar="PATH", default=None,
                    help="heterogeneous per-site numerics: policy-table "
                         "JSON (schema in docs/policies.md); overrides "
                         "--numerics/--multiplier")
    ap.add_argument("--assign", metavar="SPEC", default=None,
                    help="per-site assignment shorthand, e.g. "
                         "'conv=mitchell8,head=native,dw=native' — "
                         "unassigned sites run --numerics/--multiplier "
                         "(docs/policies.md)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.numerics_table and args.assign:
        ap.error("--numerics-table and --assign are mutually exclusive "
                 "(put the assignments in the table JSON)")
    if args.numerics_table:
        policy = table_from_json(args.numerics_table)
    elif args.assign:
        default = (("native", "fp32") if args.numerics == "native"
                   else (args.numerics, args.multiplier))
        policy = table_from_assignments(args.assign, default=default)
    else:
        policy = (NumericsPolicy() if args.numerics == "native" else
                  NumericsPolicy(mode=args.numerics,
                                 multiplier=args.multiplier))
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    ndev = len(jax.devices())
    if ndev >= 256:
        mesh = make_production_mesh()
    elif ndev >= 4:
        mesh = make_debug_mesh(2, 2)
    else:
        mesh = None
    print(_describe_numerics(policy, mesh))
    state = train(cfg, policy, shape, steps=args.steps, lr=args.lr,
                  seed=args.seed, mesh=mesh, microbatches=args.microbatches,
                  ckpt_dir=args.ckpt_dir)
    print(f"done at step {state.step}; stragglers flagged: "
          f"{len(state.stragglers)}")


def train(cfg, policy, shape, *, steps: int, lr: float = 3e-4,
          seed: int = 0, mesh=None, microbatches: int = 1,
          ckpt_dir=None, log_every=None) -> TrainerState:
    """Build the params, optimizer and jitted step the way this driver
    does, and run them through the :class:`Trainer` for ``steps`` steps.
    Under ``mesh`` the state is placed with the sharding rules and the
    step traces inside the mesh context.  The returned state carries the
    logged metrics in ``history``."""
    key = jax.random.PRNGKey(seed)
    if cfg.family == "encdec":
        params = encdec_mod.init_encdec(key, cfg)
        loss_fn = lambda p, b: encdec_mod.encdec_loss(p, b, cfg, policy)
    else:
        params = init_lm(key, cfg)
        loss_fn = lambda p, b: lm_loss(p, b, cfg, policy)

    opt = make_optimizer(cfg.optimizer, cosine_schedule(lr, 10, steps))
    opt_state = opt.init(params)
    step_fn = make_train_step(loss_fn, opt, microbatches=microbatches)
    run = lambda fn, p, o, shardings=None: run_train(
        fn, cfg, shape, p, o, steps=steps, ckpt_dir=ckpt_dir,
        log_every=log_every, shardings=shardings)

    if mesh is None:
        return run(jax.jit(step_fn), params, opt_state)
    pspecs = lm_param_pspecs(params, cfg, mesh)
    psh = to_shardings(pspecs, mesh)
    osh = to_shardings(opt_state_pspecs(cfg.optimizer, pspecs), mesh)
    params = jax.device_put(params, psh)
    opt_state = jax.device_put(opt_state, osh)
    # Trace INSIDE the mesh context: shard_fused reads the ambient
    # mesh at trace time — this is what routes mode="amsim" through
    # the per-shard fused kernels instead of GSPMD's replicated
    # pallas_call lowering.
    with jax.set_mesh(mesh):
        return run(jax.jit(step_fn), params, opt_state,
                   shardings={"params": psh, "opt": osh})


def run_train(step_fn, cfg, shape, params, opt_state, *, steps: int,
              ckpt_dir=None, log_every=None, shardings=None):
    batch_fn = lambda s: lm_batch(cfg, shape, s)
    trainer = Trainer(step_fn, batch_fn, TrainerConfig(
        total_steps=steps, ckpt_dir=ckpt_dir,
        ckpt_every=max(steps // 5, 1),
        log_every=log_every or max(steps // 10, 1)), shardings=shardings)
    return trainer.run(TrainerState(params, opt_state))


if __name__ == "__main__":
    main()
