"""Readings of the comparison over many seeds in one process: the
program's, and the control's (the reference put in the program's place,
one precision below what the configuration states), for setting and
checking the limits in ``chipbench/correct.py`` and in the
configuration's check files. With ``--fault`` the
program runs with that fault of ``chipbench/faults.py`` planted.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...] [--fault <name>]

Prints one JSON line per seed: {"seed", "correct", "program": {...},
"control": {...}}. The benchmark's own runs never run the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
# no size cap, as chipbench/run.py
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    from chipbench import correct, faults, harness, registry
    from repro.compile_cache import enable_compile_cache
    from repro.core import svr

    bench = registry.load_benchmark()
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(bench, cell["config"])
    mix = registry.mix(cell["traffic"])
    check_files = registry.checks(cfg)
    limits = correct.limits(check_files)
    enable_compile_cache()
    device = harness.device_info(int(cell["chips"]), require_tpu=True)
    sound = {name: getattr(svr, name) for name in faults.PATCHED}
    for seed in args.seeds:
        for name, fn in sound.items():
            setattr(svr, name, fn)
        t0 = time.perf_counter()
        world = harness.World(cfg, mix, seed)
        if args.fault:
            faults.FAULTS[args.fault](world)
        rec = harness.Recorder(world)

        def on_open():
            rec.in_window = True

        replay = harness.Replay(
            world, lambda now, start: now - start >= args.seconds, on_open,
            warm=world.trace.warm_reactions,
        )
        try:
            replay.run()
        finally:
            rec.restore()
        t1 = time.perf_counter()
        program = harness.readings(world, rec, replay)
        program.update(harness.check_readings(check_files, world, rec, replay))
        t2 = time.perf_counter()
        control = {}
        if not args.fault:
            control = harness.control_readings(world, rec, program)
            control.update(harness.check_readings(check_files, world, rec, replay, control=True))
        world.close()
        print(json.dumps({
            "seed": seed, "device": device["kind"], "fault": args.fault,
            "reactions": len(replay.reactions), "window_and_setup_s": t1 - t0,
            "reference_s": t2 - t1, "control_s": time.perf_counter() - t2,
            "correct": correct.judge(program, limits)[0], "program": program, "control": control,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
