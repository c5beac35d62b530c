"""Benchmark entry point: one run of one cell, one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the repository root. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# JAX's persistent compilation cache, at a fixed path inside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
# no size cap: a capped cache keeps an access-time file per entry, and
# writing entries failed on those files on the chip's host
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
