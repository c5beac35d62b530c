"""End-to-end training driver.

    python -m repro.launch.train --arch example-10m --steps 200
    python -m repro.launch.train --arch gemma3-12b --smoke --steps 20
    python -m repro.launch.train --arch example-10m --steps 100 \
        --mesh 1x2 --compress      # DP shard_map + int8 error-feedback grads
    python -m repro.launch.train --arch example-10m --auto-energy ...

Features wired in: deterministic resumable data pipeline, AdamW + schedule,
async checkpoints + preemption-safe restart (SIGTERM), straggler telemetry,
optional int8 gradient compression over the data axis (shard_map path), and
the paper's EnergyOptimalPlanner for choosing the launch configuration
(--auto-energy; see core/planner.py).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import ARCHS
from repro.configs.base import ArchDef, ShapeCell
from repro.configs.example_lm import EXAMPLES, ARCH_100M
from repro.data.pipeline import PipelineConfig, SyntheticPipeline
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.optim import adamw, compress
from repro.runtime.trainer import Trainer


def resolve_arch(name: str, smoke: bool):
    key = name.replace("example-", "")
    if key in EXAMPLES:
        return ARCH_100M, EXAMPLES[key]
    arch = ARCHS[name]
    return arch, (arch.smoke if smoke else arch.full)


def build_batch_converter(cfg):
    def convert(np_batch):
        return {k: jnp.asarray(v) for k, v in np_batch.items()}

    return convert


def make_compressed_dp_step(arch: ArchDef, cfg, opt_cfg, mesh):
    """Pure-DP training with int8 error-feedback gradient all-reduce via
    shard_map (the cross-pod compression path; params replicated)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def step(params, opt_state, residuals, batch):
        def local(params, opt_state, residuals, batch):
            def loss_of(p):
                return arch.loss_fn(cfg, p, batch)

            (loss, parts), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
            grads, residuals = compress.compressed_grad_tree(
                grads, residuals, "data"
            )
            loss = jax.lax.pmean(loss, "data")
            new_p, new_o, metrics = adamw.update(opt_cfg, params, grads, opt_state)
            return new_p, new_o, residuals, {"loss": loss, **metrics}

        repl = P()
        bspec = jax.tree_util.tree_map(lambda _: P("data"), batch)
        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(repl, repl, repl, bspec),
            out_specs=(repl, repl, repl, repl),
            check_vma=False,
        )(params, opt_state, residuals, batch)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="example-10m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="", help="e.g. 2x4 -> (data,model)")
    ap.add_argument("--compress", action="store_true", help="int8 EF grads (DP)")
    ap.add_argument("--auto-energy", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    arch, cfg = resolve_arch(args.arch, args.smoke)
    opt_cfg = adamw.AdamWConfig(
        peak_lr=args.lr, warmup_steps=args.warmup, total_steps=max(args.steps, 1)
    )

    pcfg = PipelineConfig(
        vocab=cfg.vocab, seq=args.seq, global_batch=args.batch, seed=args.seed
    )
    if arch.is_encdec():
        pcfg = PipelineConfig(
            vocab=cfg.vocab,
            seq=min(args.seq, cfg.max_target_len),
            global_batch=args.batch,
            seed=args.seed,
            n_frames=args.seq,
            d_frame=cfg.d_model,
        )
    if getattr(cfg, "vision", None) is not None:
        pcfg.n_patches = cfg.vision.n_patches
        pcfg.d_vision = cfg.vision.d_vision
    pipeline = SyntheticPipeline(pcfg)
    convert = build_batch_converter(cfg)

    if args.auto_energy:
        from repro.core.planner import EnergyOptimalPlanner

        planner = EnergyOptimalPlanner.default()
        plan = planner.plan_for_workload(
            arch_id=args.arch,
            cell=ShapeCell("train", args.seq, args.batch, "train"),
        )
        obs.log(f"[auto-energy] {plan.summary()}")

    params = arch.init(jax.random.PRNGKey(args.seed), cfg)
    opt_state = adamw.init(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    obs.log(f"arch={cfg.name} params={n_params:,}")

    if args.compress:
        if not args.mesh:
            args.mesh = f"{len(jax.devices())}"
        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(shape, ("data",) if len(shape) == 1 else ("data", "model"))
        residuals = compress.init_residuals(params)
        cstep = make_compressed_dp_step(arch, cfg, opt_cfg, mesh)
        state = {"residuals": residuals}

        def train_step(params, opt_state, batch):
            new_p, new_o, state["residuals"], metrics = cstep(
                params, opt_state, state["residuals"], convert(batch)
            )
            return new_p, new_o, metrics

    else:
        base_step = jax.jit(
            steps_mod.make_train_step(arch, cfg, opt_cfg), donate_argnums=(0, 1)
        )

        def train_step(params, opt_state, batch):
            return base_step(params, opt_state, convert(batch))

    def on_metrics(step, m):
        if step % args.log_every == 0 or step == 1:
            obs.log(
                f"step {step:5d} loss {float(m['loss']):.4f} "
                f"lr {float(m['lr']):.2e} gnorm {float(m['grad_norm']):.2f} "
                f"({m['step_time_s']*1e3:.0f} ms)",
                flush=True,
            )

    trainer = Trainer(
        train_step=train_step,
        params=params,
        opt_state=opt_state,
        pipeline=pipeline,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        on_metrics=on_metrics,
    )
    if trainer.try_restore():
        obs.log(f"resumed from step {trainer.step}")
    result = trainer.run(args.steps)
    obs.log(
        f"exit={result['exit']} step={result['step']} "
        f"final_loss={result['history'][-1]['loss']:.4f}"
        if result["history"]
        else f"exit={result['exit']}"
    )
    return result


if __name__ == "__main__":
    main()
