import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above run before ANY other import (jax locks the device count
and platform on first init): the dry-run — and only the dry-run — sees 512
placeholder CPU devices standing in for 2 pods x 256 v5e chips, and never
initializes a TPU, so it cannot take a chip another process holds.

Per cell this script:
  1. builds ShapeDtypeStruct inputs (no allocation) and the sharding specs,
  2. jits the step (train_step / prefill / serve_step) with in/out shardings,
  3. ``.lower().compile()`` — any sharding mismatch or OOM-at-compile here is
     a bug in the framework,
  4. records memory_analysis(), cost_analysis(), and the HLO-text roofline
     counts (hlo_analysis.py — scan-trip-corrected FLOPs/bytes/collectives),
  5. caches the result as experiments/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all [--skip-existing]
"""

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs import ARCHS, get_arch  # noqa: E402
from repro.configs.base import SHAPES  # noqa: E402
from repro.launch import hlo_analysis, steps  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.parallel import sharding as shd  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

# Gradient-accumulation microbatches per train cell: with scan_nest (nested
# remat) this is what brings every train_4k cell under the 16 GB/chip HBM
# budget (EXPERIMENTS.md §Perf, iteration Q4). Keys absent -> accum 1.
TRAIN_ACCUM = {
    "qwen1.5-110b": 4,
    "granite-20b": 2,
    "gemma3-12b": 4,
    "phi3.5-moe-42b-a6.6b": 2,
    "granite-moe-1b-a400m": 2,
    "phi-3-vision-4.2b": 2,
    "zamba2-7b": 2,
    "mamba2-130m": 2,
}


def _mesh_for(name: str):
    return make_production_mesh(multi_pod=(name == "multipod"))


def _lower_cell(arch_id: str, shape_name: str, mesh_name: str):
    arch = get_arch(arch_id)
    cfg = arch.full
    cell = SHAPES[shape_name]
    mesh = _mesh_for(mesh_name)
    specs = arch.input_specs(shape_name)

    import contextlib as _ctx

    with mesh:
        with steps.activation_policy(arch, cell, mesh), _ctx.ExitStack() as stack:
            if cell.kind == "train":
                params_abs, opt_abs = steps.abstract_train_state(arch, cfg)
                stack.enter_context(steps.fsdp_policy(arch, cfg, mesh, params_abs))
                pshard, oshard, bshard = steps.train_shardings(
                    arch, cfg, mesh, cell, params_abs, opt_abs, specs
                )
                fn = steps.make_train_step(
                    arch,
                    cfg,
                    adamw.AdamWConfig(),
                    zero_shardings=oshard["m"],
                    accum=TRAIN_ACCUM.get(arch_id, 1),
                )
                jitted = jax.jit(
                    fn,
                    in_shardings=(pshard, oshard, bshard),
                    out_shardings=(pshard, oshard, None),
                    donate_argnums=(0, 1),
                )
                lowered = jitted.lower(params_abs, opt_abs, specs)
            elif cell.kind == "prefill":
                params_abs = jax.eval_shape(lambda: arch.init(jax.random.PRNGKey(0), cfg))
                stack.enter_context(steps.fsdp_policy(arch, cfg, mesh, params_abs))
                pspec = shd.param_specs(params_abs, arch, mesh)
                pshard = steps.named(mesh, pspec)
                bshard = steps.named(mesh, shd.batch_specs(specs, cell, mesh))
                extra = (
                    cfg.vision.n_patches
                    if getattr(cfg, "vision", None) is not None
                    else 0
                )
                fn = steps.make_prefill(arch, cfg, max_cache_len=cell.seq + extra)
                caches_abs = jax.eval_shape(fn, params_abs, specs)[0]
                cshard = steps.named(mesh, shd.cache_specs(caches_abs, arch, cell, mesh))
                jitted = jax.jit(fn, in_shardings=(pshard, bshard), out_shardings=(cshard, None))
                lowered = jitted.lower(params_abs, specs)
            else:  # decode
                params_abs = jax.eval_shape(lambda: arch.init(jax.random.PRNGKey(0), cfg))
                stack.enter_context(steps.fsdp_policy(arch, cfg, mesh, params_abs))
                pspec = shd.param_specs(params_abs, arch, mesh)
                pshard = steps.named(mesh, pspec)
                if arch.is_encdec():
                    caches_abs = jax.eval_shape(
                        lambda: arch.init_caches(cfg, cell.batch, cell.seq, cell.seq)
                    )
                else:
                    caches_abs = jax.eval_shape(
                        lambda: arch.init_caches(cfg, cell.batch, cell.seq)
                    )
                cshard = steps.named(mesh, shd.cache_specs(caches_abs, arch, cell, mesh))
                tshard = steps.named(mesh, shd.batch_specs(specs, cell, mesh))
                fn = steps.make_serve_step(arch, cfg)
                jitted = jax.jit(
                    fn,
                    in_shardings=(pshard, cshard, tshard["token"]),
                    out_shardings=(cshard, None, None),
                    donate_argnums=(1,),
                )
                lowered = jitted.lower(params_abs, caches_abs, specs["token"])
    return lowered, mesh


def run_cell(arch_id: str, shape_name: str, mesh_name: str, out_dir: str = OUT_DIR):
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch_id}__{shape_name}__{mesh_name}.json")
    t0 = time.time()
    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": mesh_name,
        "ok": False,
    }
    try:
        lowered, mesh = _lower_cell(arch_id, shape_name, mesh_name)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        txt = compiled.as_text()
        counts = hlo_analysis.analyze(txt)
        n_dev = int(np.prod(mesh.devices.shape))
        rec.update(
            ok=True,
            n_devices=n_dev,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory_analysis={
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "alias_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(mem, k)
            },
            cost_analysis={
                k: float(v)
                for k, v in (cost or {}).items()
                if isinstance(v, (int, float)) and k in ("flops", "transcendentals")
            },
            hlo={
                "flops_per_device": counts.flops,
                "memory_bytes_per_device": counts.memory_bytes,
                "collective_bytes_per_device": counts.collective_bytes,
                "collectives": counts.collectives,
                "warnings": counts.warnings[:20],
            },
            hlo_text_bytes=len(txt),
        )
    except Exception as e:  # noqa: BLE001 - record the failure, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def all_cells():
    for arch_id, arch in ARCHS.items():
        for shape_name in SHAPES:
            if not arch.supports(shape_name):
                continue
            for mesh_name in ("pod", "multipod"):
                yield arch_id, shape_name, mesh_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    if args.all:
        todo = list(all_cells())
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        todo = [(args.arch, args.shape, args.mesh)]

    n_ok = 0
    for arch_id, shape_name, mesh_name in todo:
        path = os.path.join(args.out, f"{arch_id}__{shape_name}__{mesh_name}.json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("ok"):
                    obs.log(f"SKIP {arch_id} {shape_name} {mesh_name} (cached)")
                    n_ok += 1
                    continue
        t0 = time.time()
        rec = run_cell(arch_id, shape_name, mesh_name, args.out)
        status = "OK " if rec.get("ok") else "FAIL"
        n_ok += bool(rec.get("ok"))
        extra = (
            f"flops/dev={rec['hlo']['flops_per_device']:.3g} "
            f"coll/dev={rec['hlo']['collective_bytes_per_device']:.3g}B"
            if rec.get("ok")
            else rec.get("error", "")[:120]
        )
        obs.log(
            f"{status} {arch_id:24s} {shape_name:12s} {mesh_name:8s} "
            f"t={time.time()-t0:6.1f}s {extra}",
            flush=True,
        )
    obs.log(f"done: {n_ok}/{len(todo)} cells ok")


if __name__ == "__main__":
    main()
