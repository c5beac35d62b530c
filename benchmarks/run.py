"""Benchmark entry point: ``python -m benchmarks.run [--quick] [--only NAME]``.

One section per paper table/figure (bench_paper_repro), plus the roofline
table from the dry-run artifacts, the TPU planner (beyond-paper), the
batched engine / SVR-fit / fleet rounds, and kernel micro-benches. Prints
``name,us_per_call,derived`` CSV lines; most sections also persist a JSON
record under ``experiments/bench/`` (schema: ``docs/benchmarks.md``).

Benchmarks self-register in ``BENCHES`` — the ``--only`` choices, the
dispatch and the unknown-name error all derive from that one registry, so
a new benchmark cannot be half-wired (listed but silently never run, or
runnable but unlisted).
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence


def _run_kernels(quick: bool) -> None:
    from benchmarks import bench_kernels

    bench_kernels.run()


def _run_paper(quick: bool) -> None:
    from benchmarks import bench_paper_repro

    bench_paper_repro.run(full=not quick)


def _run_roofline(quick: bool) -> None:
    from benchmarks import bench_roofline

    bench_roofline.run()
    # right-sizing study needs its own process (512 virtual devices)
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [_sys.executable, "-m", "benchmarks.bench_rightsize"],
        capture_output=True,
        text=True,
        timeout=1200,
    )
    print(proc.stdout, end="")


def _run_planner(quick: bool) -> None:
    from benchmarks import bench_tpu_planner

    bench_tpu_planner.run()


def _run_bench_tpu(quick: bool) -> None:
    from benchmarks import bench_tpu

    bench_tpu.run()


def _run_engine(quick: bool) -> None:
    from benchmarks import bench_engine

    bench_engine.run()


def _run_engine_scale(quick: bool) -> None:
    from benchmarks import bench_engine

    bench_engine.run_scale(quick=quick)


def _run_svr_fit(quick: bool) -> None:
    from benchmarks import bench_svr_fit

    bench_svr_fit.run()


def _run_fleet(quick: bool) -> None:
    from benchmarks import bench_fleet

    bench_fleet.run()


def _run_analysis(quick: bool) -> None:
    from benchmarks import bench_analysis

    bench_analysis.run(quick=quick)


def _run_obs(quick: bool) -> None:
    from benchmarks import bench_obs

    bench_obs.run()


def _run_service(quick: bool) -> None:
    from benchmarks import bench_service

    bench_service.run()


# name -> runner; insertion order is execution order for a full run
BENCHES = {
    "kernels": _run_kernels,
    "paper": _run_paper,
    "roofline": _run_roofline,
    "planner": _run_planner,
    "bench_tpu": _run_bench_tpu,
    "engine": _run_engine,
    "engine_scale": _run_engine_scale,
    "svr_fit": _run_svr_fit,
    "fleet": _run_fleet,
    "analysis": _run_analysis,
    "obs": _run_obs,
    "service": _run_service,
}


def run_selected(
    only: Optional[str] = None,
    *,
    quick: bool = False,
    append_trajectory: bool = False,
) -> None:
    """Run one benchmark (or all). Unknown names fail loudly with the
    valid-name list — never a silent no-op run. ``append_trajectory``
    appends the run's saved payloads as one dated entry to
    ``experiments/bench/trajectory.json`` (the run-over-run perf record;
    the per-bench JSON files are overwritten in place and keep no
    history)."""
    if only is not None and only not in BENCHES:
        raise SystemExit(
            f"unknown benchmark {only!r}; valid names: {', '.join(BENCHES)}"
        )
    from benchmarks import common

    common.RUN_RESULTS.clear()
    print("name,us_per_call,derived")
    for name, runner in BENCHES.items():
        if only in (None, name):
            runner(quick)
    if append_trajectory:
        path = common.append_trajectory(common.RUN_RESULTS, quick=quick)
        print(f"trajectory: appended {len(common.RUN_RESULTS)} result(s) to {path}")


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--quick", action="store_true", help="reduced characterization grids"
    )
    # free-form on purpose: run_selected owns the validation so the error
    # (with the valid-name list) is identical for CLI and programmatic use
    ap.add_argument(
        "--only",
        metavar="NAME",
        choices=None,
        default=None,
        help=f"run one benchmark: {', '.join(BENCHES)}",
    )
    ap.add_argument(
        "--append-trajectory",
        action="store_true",
        help="append this run's results to experiments/bench/trajectory.json "
        "(run-over-run perf record)",
    )
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run_selected(
        args.only, quick=args.quick, append_trajectory=args.append_trajectory
    )


if __name__ == "__main__":
    main()
