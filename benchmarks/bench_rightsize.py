"""§Perf cell 3 (mamba2-130m train_4k — worst roofline fraction): the fix is
not a kernel change but the PAPER'S OWN TECHNIQUE — right-sizing the slice.

A 130M-param model on 256 chips is communication/memory-dominated: per-chip
compute shrinks 1/c while the DP gradient all-reduce stays ~2·params·dtype
per chip. This bench lowers the same cell on successively smaller
data-parallel slices and reports the roofline terms + the planner's
energy-optimal choice, tying the roofline table to the paper's thesis.

Run inside the dry-run device context:
    python -m benchmarks.bench_rightsize
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()
# a CPU placeholder-device study: never initialize the TPU (the parent
# benchmark process may hold the chip)
os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402

import jax  # noqa: E402

from benchmarks.common import emit, save_json  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.configs.base import SHAPES  # noqa: E402
from repro.core.tpu_power import HBM_BW, ICI_BW, PEAK_FLOPS_BF16  # noqa: E402
from repro.launch import hlo_analysis, steps  # noqa: E402
from repro.launch.dryrun import TRAIN_ACCUM  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.optim import adamw  # noqa: E402


def lower_on(arch_id: str, chips: int):
    arch = get_arch(arch_id)
    cfg = arch.full
    cell = SHAPES["train_4k"]
    mesh = make_mesh((chips, 1), ("data", "model"))
    specs = arch.input_specs("train_4k")
    with mesh, steps.activation_policy(arch, cell, mesh):
        params_abs, opt_abs = steps.abstract_train_state(arch, cfg)
        pshard, oshard, bshard = steps.train_shardings(
            arch, cfg, mesh, cell, params_abs, opt_abs, specs
        )
        fn = steps.make_train_step(
            arch, cfg, adamw.AdamWConfig(), zero_shardings=oshard["m"],
            accum=TRAIN_ACCUM.get(arch_id, 1),
        )
        compiled = (
            jax.jit(
                fn,
                in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1),
            )
            .lower(params_abs, opt_abs, specs)
            .compile()
        )
    counts = hlo_analysis.analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    return {
        "chips": chips,
        "compute_s": counts.flops / PEAK_FLOPS_BF16,
        "memory_s": counts.memory_bytes / HBM_BW,
        "collective_s": counts.collective_bytes / ICI_BW,
        "temp_gb": mem.temp_size_in_bytes / 2**30,
        "collectives": counts.collectives,
    }


def run(arch_id: str = "mamba2-130m"):
    rows = []
    for chips in (256, 128, 64, 32, 16):
        r = lower_on(arch_id, chips)
        t = max(r["compute_s"], r["memory_s"], r["collective_s"])
        frac = r["compute_s"] / t
        rows.append({**r, "step_time_s": t, "roofline_fraction": frac})
        emit(
            f"rightsize_{arch_id}_{chips}chips",
            0.0,
            f"comp={r['compute_s']:.3f}s_mem={r['memory_s']:.3f}s_"
            f"coll={r['collective_s']:.4f}s_frac={frac:.3f}",
        )
    # chip-seconds per step ~ energy proxy: fewer chips wins until compute-bound
    best = min(rows, key=lambda r: r["chips"] * r["step_time_s"])
    emit(
        f"rightsize_{arch_id}_best",
        0.0,
        f"{best['chips']}chips_frac={best['roofline_fraction']:.3f}"
        f"_chipseconds={best['chips']*best['step_time_s']:.1f}",
    )
    save_json(f"rightsize_{arch_id}", rows)
    return rows


if __name__ == "__main__":
    run()
