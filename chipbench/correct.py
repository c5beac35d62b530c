"""The limits that decide ``correct``, one per number compared.

Each number is a reading of what the measured window produced against
the plain reference (``chipbench/reference.py``); ``PERF.md`` gives the
readings each limit was set from. ``LIMITS`` judges every configuration;
a configuration's check files (``chipbench/checks/<name>.py``) add limits
of their own beside them, never in their place.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

# a plan within this share of the cheapest feasible point's energy is on
# it: where two grid points lie closer, the program's float32 surfaces and
# the float64 ones may order them differently (sound runs on the chip read
# up to 2e-4 such regret)
PLAN_REGRET_TOL = 1e-3

LIMITS: Dict[str, float] = {
    # service: exact guarantees, counted
    "jobs_placed_twice": 0,
    "jobs_lost": 0,
    "jobs_unknown": 0,
    "ledger_dishonest": 0,
    "fleet_total_off": 0,
    "nodes_oversubscribed": 0,
    "rounds_missing": 0,
    "window_without_launches": 0,
    "trace_exhausted": 0,  # the trace must outlast the window
    # power: the widest relative gap of the power grid the engine plans
    # with, against Eq. 7 fitted in float64 from the same stress samples
    "power_grid_max_rel": 5e-4,
    # characterization: the widest gap of any Gram the fits built on the
    # device, against float64 (K lies in [0, 1])
    "gram_max_abs": 1.5e-5,
    # characterization: the median over planned families of each step-time
    # surface's widest relative gap, against the float64 fit
    "surface_median_rel": 3.5e-3,
    # engine: the share of the window's plans whose energy on the float64
    # surfaces and power grid exceeds the cheapest feasible grid point's by
    # more than PLAN_REGRET_TOL
    "plans_off_share": 0.12,
}


def passes(value, limit) -> bool:
    """A reading passes when it is a finite number within its limit."""
    return isinstance(value, (int, float)) and math.isfinite(value) and value <= limit


def limits(check_files: Sequence = ()) -> Dict[str, float]:
    """``LIMITS`` and the ``LIMITS`` of each check file. A check file may
    not restate a limit already set: it adds numbers, it never moves one."""
    out = dict(LIMITS)
    for c in check_files:
        for name, limit in c.LIMITS.items():
            if name in out:
                raise ValueError(f"check {c.__name__}: limit {name!r} is already set")
            out[name] = limit
    return out


def _shown(value):
    if value is None:
        return "missing"
    return value if isinstance(value, (int, float)) and math.isfinite(value) else str(value)


def judge(readings: Dict[str, float],
          limits: Dict[str, float] = LIMITS) -> Tuple[bool, Dict[str, dict]]:
    """(correct, {name: {"value", "limit"}}) over every limited number. A
    reading that is missing or not a finite number fails, and is shown as
    a string ("missing", "nan", "inf"), so that the result stays plain
    JSON."""
    checks = {
        name: {"value": _shown(readings.get(name)), "limit": limit}
        for name, limit in limits.items()
    }
    ok = all(passes(readings.get(name), limit) for name, limit in limits.items())
    return ok, checks
