"""Bring-up smoke test of the planner's main path on one TPU chip.

    python chip_smoke.py [--seed N]

Runs, in this one process, each phase through the entry points a user
calls, and compares it against a plain float64 NumPy computation on the
host made from the same seeded samples:

1. device: a TPU is present and ``kernels.ops`` resolves to the compiled
   Pallas kernels;
2. power fit (``fit_fleet_power``) against ``np.linalg.lstsq``;
3. characterization: one n = 1024 RBF Gram against NumPy's, and the
   engine's SVR families (``svr.fit_many``, Gram through
   ``rbf_gram_pallas``) against the same solver fed NumPy's Gram,
   compared by their predictions on held-out grid points;
4. engine at backlog scale: ``plan_many`` and ``pareto_many`` over 10,000
   pending workloads in ``tpu_space()`` and ``cpu_space()``, the fused
   (Pallas) arm against the exact arm and against a NumPy argmin and
   keep-set over the same step-time stack;
5. service: ``python -m repro.fleet --service --mixed --nodes 256 --jobs
   4096`` through ``SchedulerService``, checked for exactly-once
   completion, an honest energy ledger and capacity, and against the same
   trace planned through the exact path.

Every disagreement is printed with its tolerance; a phase outside its
tolerance raises, and the script exits non-zero. Compile counts and
seconds are set-up time and, like the wall seconds per phase, information
only. The last line is ``{"ok": true, "device": {...}}`` and is printed
only when every phase passed on a TPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Tolerances, each against the float64 NumPy reference on the host.
POWER_RTOL = 1e-3  # per Eq. 7 coefficient; a 4-column f32 OLS, cond(X) ~ 2e3
GRAM_ATOL = 1e-4  # K in [0, 1]
SVR_PRED_RTOL = 1e-2  # held-out step-time predictions, seconds
TIE_RTOL = 1e-5  # rows whose deciding values lie this close may differ
ENERGY_RTOL = 1e-3  # service total energy, fused vs exact path

BACKLOG = 10_000
SERVICE_NODES = 256
SERVICE_JOBS = 4096

_COMPILES = {"n": 0, "secs": 0.0}


def _on_duration(name: str, secs: float, **_kw) -> None:
    if name.endswith("backend_compile_duration"):
        _COMPILES["n"] += 1
    if name.startswith("/jax/core/compile/"):
        _COMPILES["secs"] += secs


def say(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


class Phase:
    """Wall time and compile activity of one phase, printed at its end."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = dict(_COMPILES)
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            say(
                phase=self.name,
                wall_s=time.perf_counter() - self.t0,
                compiles=_COMPILES["n"] - self.c0["n"],
                compile_s=_COMPILES["secs"] - self.c0["secs"],
            )


def check(name: str, value: float, limit: float) -> None:
    say(check=name, value=value, limit=limit)
    if not value <= limit:
        raise AssertionError(f"{name} = {value!r} exceeds {limit!r}")


def assert_compiled_kernel(name: str, fn, *args) -> None:
    text = fn.lower(*args).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{name}: no Pallas kernel in the compiled program")
    say(kernel=name, tpu_custom_call=True)


# ---------------------------------------------------------------------------
# 1. device
# ---------------------------------------------------------------------------


def phase_device() -> dict:
    from repro.kernels import ops

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform!r})")
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    say(device_kind=repr(dev.device_kind), device_count=device["count"])
    impl = ops.resolve_impl(None)
    if impl != "pallas":
        raise AssertionError(f"kernel dispatch resolved to {impl!r}, not 'pallas'")
    return device


# ---------------------------------------------------------------------------
# 2. power fit
# ---------------------------------------------------------------------------


def phase_power(seed: int):
    from repro.core.tpu_power import FleetTelemetry, fit_fleet_power

    pm = fit_fleet_power(FleetTelemetry(seed=seed))
    samples = FleetTelemetry(seed=seed).stress_grid()
    f, p, s, w = (np.asarray(a, np.float64) for a in samples)
    X = np.stack([p * f**3, p * f, np.ones_like(f), s], axis=1)
    want = np.linalg.lstsq(X, w, rcond=None)[0]
    got = np.asarray(pm.coeffs(), np.float64)
    say(power_coeffs=got.tolist(), lstsq_coeffs=want.tolist())
    rel = float(np.max(np.abs(got - want) / np.abs(want)))
    check("power_coef_max_rel", rel, POWER_RTOL)
    return pm


# ---------------------------------------------------------------------------
# 3. characterization
# ---------------------------------------------------------------------------


def gram64(x: np.ndarray, y: np.ndarray, gamma: float) -> np.ndarray:
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    d2 = np.sum((x[..., :, None, :] - y[..., None, :, :]) ** 2, axis=-1)
    return np.exp(-gamma * d2)


def predict64(model, x: np.ndarray) -> np.ndarray:
    xs = (np.asarray(x, np.float64) - model.x_mean) / model.x_std
    k = gram64(xs, model.x_train, model.gamma)
    ys = k @ np.asarray(model.beta, np.float64) + model.bias
    out = ys * model.y_std + model.y_mean
    return np.exp(out) if model.log_target else out


def phase_gram(seed: int) -> None:
    from repro.kernels import ops

    x = np.random.default_rng(seed).normal(size=(1024, 3)).astype(np.float32)
    got = np.asarray(ops.rbf_gram(jnp.asarray(x), jnp.asarray(x), 0.5))
    err = float(np.max(np.abs(got - gram64(x, x, 0.5))))
    check("gram_n1024_max_abs", err, GRAM_ATOL)


def phase_characterize(name: str, engine, workloads) -> None:
    """The engine's SVR families fitted on the chip vs the same solver
    fed NumPy's float64 Gram, compared on held-out grid points."""
    from repro.core import svr
    from repro.core.engine import ENGINE_FIT_KW

    families = {}
    for w in workloads:
        families.setdefault(w.key, w)
    sets = [engine._training_set(engine._terms_for(w)) for w in families.values()]
    models = svr.fit_many(sets, method="auto", **ENGINE_FIT_KW)
    x0 = np.asarray(sets[0][0])
    assert_compiled_kernel(
        f"{name}.rbf_gram(fit, n={len(x0)})",
        svr._gram_batched,
        jnp.zeros((len(sets),) + x0.shape, jnp.float32),
        jnp.zeros((len(sets),) + x0.shape, jnp.float32),
        ENGINE_FIT_KW["gamma"],
        None,
    )

    def numpy_gram(x, y, gamma, impl):
        return gram64(np.asarray(x), np.asarray(y), gamma)

    with mock.patch.object(svr, "_gram_batched", numpy_gram):
        ref_models = svr.fit_many(sets, method="auto", **ENGINE_FIT_KW)

    # held out: the midpoints between neighbouring grid values
    space = engine.space
    f_mid = np.convolve(space.freq_grid, [0.5, 0.5], "valid")
    c_mid = np.convolve(space.chip_grid, [0.5, 0.5], "valid")
    held = np.stack(np.meshgrid(f_mid, c_mid, indexing="ij"), -1).reshape(-1, 2)
    got = np.asarray(svr.predict_many(models, held.astype(np.float32)), np.float64)
    want = np.stack([predict64(m, held) for m in ref_models])
    rel = float(np.max(np.abs(got - want) / np.abs(want)))
    say(space=name, families=len(sets), samples=len(x0), held_out=len(held))
    check(f"{name}.svr_heldout_max_rel", rel, SVR_PRED_RTOL)


# ---------------------------------------------------------------------------
# 4. engine at backlog scale
# ---------------------------------------------------------------------------


def backlog(engine, families, n: int, seed: int):
    """``n`` pending workloads over ``families`` with seeded objectives,
    step budgets and constraints (deadlines across each family's range,
    core and clock caps); a few deadlines admit nothing on the grid."""
    from repro.core.engine import Constraints, Workload

    rng = np.random.default_rng(seed)
    space = engine.space
    f_lo, f_hi = space.freq_grid[0], space.freq_grid[-1]
    c_lo, c_hi = space.chip_grid[0], space.chip_grid[-1]
    span = {}
    for i, w in enumerate(families):
        terms = engine._terms_for(w)
        span[i] = (terms.step_time(f_hi, c_hi), terms.step_time(f_lo, c_lo))
    out = []
    for _ in range(n):
        i = int(rng.integers(len(families)))
        t_fast, t_slow = span[i]
        kw = {}
        if rng.random() < 0.6:
            lo, hi = math.log(t_fast * 0.9), math.log(t_slow)
            kw["max_time_s"] = float(math.exp(rng.uniform(lo, hi)))
        if rng.random() < 0.3:
            kw["max_cores"] = int(rng.choice(space.chip_grid))
        if rng.random() < 0.2:
            kw["max_frequency_ghz"] = float(rng.choice(space.freq_grid))
        if rng.random() < 0.2:
            kw["min_frequency_ghz"] = float(rng.choice(space.freq_grid))
        out.append(
            Workload(
                families[i].arch,
                families[i].cell,
                terms=families[i].terms,
                n_steps=int(rng.integers(1, 1000)),
                objective=str(rng.choice(["energy", "edp", "ed2p"])),
                constraints=Constraints(**kw) if kw else None,
            )
        )
    return out


def reference_masks(engine, workloads, T: np.ndarray):
    """Each workload's feasible grid points, the empty-mask rows replaced
    by the "fastest" fallback: the near-fastest points that keep every
    non-time constraint. Returns (masks, number of fallback rows)."""
    F, C, _ = engine.space.meshes()
    F, C = F.ravel(), C.ravel()
    masks = np.ones(T.shape, bool)
    fallbacks = 0
    for i, w in enumerate(workloads):
        c = w.effective_constraints()
        if c is None:
            continue
        relaxed = np.ones(T.shape[1], bool)
        if c.max_cores is not None:
            relaxed &= C <= c.max_cores
        if c.min_frequency_ghz is not None:
            relaxed &= F >= c.min_frequency_ghz
        if c.max_frequency_ghz is not None:
            relaxed &= F <= c.max_frequency_ghz
        m = relaxed if c.max_time_s is None else relaxed & (T[i] <= c.max_time_s)
        if not m.any():
            fallbacks += 1
            if not relaxed.any():
                relaxed[:] = True
            t_min = T[i][relaxed].min()
            m = relaxed & (T[i] <= t_min * (1.0 + 1e-3))
        masks[i] = m
    return masks, fallbacks


def keep_set64(T, E, mask, rows: int = 128) -> np.ndarray:
    """Pareto keep-set per row: feasible points no other feasible point
    beats on (time, energy), equal pairs kept at the lowest index."""
    b, g = T.shape
    idx = np.arange(g)
    earlier = idx[:, None] < idx[None, :]
    keep = np.zeros((b, g), bool)
    for r0 in range(0, b, rows):
        t, e, m = T[r0 : r0 + rows], E[r0 : r0 + rows], mask[r0 : r0 + rows]
        tq, tp = t[:, :, None], t[:, None, :]
        eq, ep = e[:, :, None], e[:, None, :]
        beats = m[:, :, None] & (
            ((tq < tp) & (eq <= ep))
            | ((tq == tp) & ((eq < ep) | ((eq == ep) & earlier)))
        )
        keep[r0 : r0 + rows] = m & ~beats.any(axis=1)
    return keep


def _row_near_tie(values: np.ndarray, mask: np.ndarray, a: int, b: int) -> bool:
    """True when grid points a and b hold values within ``TIE_RTOL``."""
    va, vb = values[a], values[b]
    close = abs(va - vb) <= TIE_RTOL * max(abs(va), abs(vb))
    return bool(mask[a] and mask[b] and close)


def _frontier_near_tie(T, E, mask, extra) -> bool:
    """A frontier difference is a near tie when every point kept by one
    side only has another feasible point within ``TIE_RTOL`` in time or
    in energy: the f32 and f64 orders of the two can differ."""
    for p in extra:
        others = mask.copy()
        others[p] = False
        close_t = np.abs(T[others] - T[p]) <= TIE_RTOL * abs(T[p])
        close_e = np.abs(E[others] - E[p]) <= TIE_RTOL * abs(E[p])
        if not (close_t | close_e).any():
            return False
    return True


def compare_plans(label, a, b, metric, mask) -> int:
    """Rows where chosen indices differ; each must be a near tie."""
    diff = np.flatnonzero(a != b)
    bad = [i for i in diff if not _row_near_tie(metric[i], mask[i], a[i], b[i])]
    say(compare=label, rows=len(a), differ=len(diff), near_ties=len(diff) - len(bad))
    if bad:
        i = bad[0]
        raise AssertionError(
            f"{label}: row {i} chose {a[i]} vs {b[i]} (not a near tie)"
        )
    return len(diff)


def compare_frontiers(label, a, b, T, E, mask) -> int:
    diff = np.flatnonzero((a != b).any(axis=1))
    bad = [
        i for i in diff
        if not _frontier_near_tie(T[i], E[i], mask[i], np.flatnonzero(a[i] != b[i]))
    ]
    say(compare=label, rows=len(a), differ=len(diff), near_ties=len(diff) - len(bad))
    if bad:
        raise AssertionError(f"{label}: row {bad[0]} frontiers differ (not a near tie)")
    return len(diff)


def phase_engine(name: str, engine, families, seed: int, n: int = BACKLOG) -> None:
    from repro.core import engine as engine_mod
    from repro.core.engine import OBJECTIVES, TIME_FLOOR

    ws = backlog(engine, families, n, seed)
    F, C, _ = engine.space.meshes()
    flat_of = {
        (float(f), int(c)): i for i, (f, c) in enumerate(zip(F.ravel(), C.ravel()))
    }
    b, g = len(ws), F.size

    t0 = time.perf_counter()
    fused_plans = engine.plan_many(ws)
    fused_front = engine.pareto_many(ws)
    t_fused = time.perf_counter() - t0
    t0 = time.perf_counter()
    exact_plans = engine.plan_many(ws, fused=False)
    exact_front = engine.pareto_many(ws, fused=False)
    t_exact = time.perf_counter() - t0

    fits = engine._fits_for(ws)
    T = engine._t_stack(fits).reshape(b, g)  # the float64 step-time stack
    shape = (b,) + F.shape
    axes = engine.space.axes
    assert_compiled_kernel(
        f"{name}.plan_argmin(B={b}, G={g})",
        engine_mod._plan_argmin_callable(shape, "pallas", axes),
        jnp.zeros((b, g), jnp.float32), jnp.zeros((1, g), jnp.float32),
        jnp.zeros((b,), jnp.float32), jnp.zeros((b, g), bool),
    )
    assert_compiled_kernel(
        f"{name}.pareto_mask(B={b}, G={g})",
        engine_mod._pareto_callable(shape, "pallas", axes),
        jnp.zeros((b, g), jnp.float32), jnp.zeros((1, g), jnp.float32),
        jnp.zeros((b, g), bool),
    )

    # the NumPy arm: float64 power grid, metric, argmin and keep-set
    c1, c2, c3, c4 = engine.power.coeffs()
    Fr, Cr, Pr = (a.ravel().astype(np.float64) for a in engine.space.meshes())
    W = Cr * (c1 * Fr**3 + c2 * Fr) + c3 + c4 * Pr
    Tf = np.maximum(T, TIME_FLOOR)
    k = np.asarray([OBJECTIVES[w.objective or engine.objective] for w in ws])
    metric = W[None, :] * Tf * Tf ** k[:, None]
    mask, fallbacks = reference_masks(engine, ws, T)
    ref_idx = np.argmin(np.where(mask, metric, np.inf), axis=1)
    E = W[None, :] * Tf
    ref_keep = keep_set64(Tf, E, mask)

    def plan_idx(plans):
        return np.asarray([flat_of[(p.frequency_ghz, p.chips)] for p in plans])

    def keep_of(frontiers):
        keep = np.zeros((b, g), bool)
        for i, fr in enumerate(frontiers):
            keep[i, [flat_of[(pt.frequency_ghz, pt.chips)] for pt in fr]] = True
        return keep

    fused_idx, exact_idx = plan_idx(fused_plans), plan_idx(exact_plans)
    fused_keep, exact_keep = keep_of(fused_front), keep_of(exact_front)
    say(
        space=name, backlog=b, grid=g, infeasible_rows=fallbacks,
        fused_arm_s=t_fused, exact_arm_s=t_exact,
        plans_fused_eq_exact=bool((fused_idx == exact_idx).all()),
        frontiers_fused_eq_exact=fused_front == exact_front,
    )
    compare_plans(f"{name}.plan fused~numpy", fused_idx, ref_idx, metric, mask)
    compare_plans(f"{name}.plan exact~numpy", exact_idx, ref_idx, metric, mask)
    compare_plans(f"{name}.plan fused~exact", fused_idx, exact_idx, metric, mask)
    compare_frontiers(f"{name}.frontier fused~numpy", fused_keep, ref_keep, Tf, E, mask)
    compare_frontiers(f"{name}.frontier exact~numpy", exact_keep, ref_keep, Tf, E, mask)
    compare_frontiers(
        f"{name}.frontier fused~exact", fused_keep, exact_keep, Tf, E, mask
    )


# ---------------------------------------------------------------------------
# 5. the fleet service
# ---------------------------------------------------------------------------


def check_schedule(sched, n_jobs: int) -> None:
    from repro.fleet.cluster import CapacityProfile

    ids = sorted(c.placement.job.job_id for c in sched.completed)
    if ids != list(range(n_jobs)):
        raise AssertionError(f"{len(ids)} completions for {n_jobs} jobs, or duplicates")
    for c in sched.completed:
        if c.total_energy_j != c.result.energy_j + c.prior_energy_j:
            job = c.placement.job.job_id
            raise AssertionError(f"job {job}: ledger is not result + prior")
    total = sum(c.total_energy_j for c in sched.completed)
    if not math.isclose(sched.total_energy_j(), total):
        raise AssertionError("fleet total energy is not the sum over jobs")
    for node in sched.pool:
        segs = [(r.start_s, r.end_s, r.cores) for r in node.reservations]
        if not CapacityProfile(node.spec.max_cores, segs).valid():
            raise AssertionError(f"node {node.name} is oversubscribed")
    say(
        jobs_completed_once=len(ids), honest_ledger=True, capacity_ok=True,
        total_energy_j=sched.total_energy_j(), rounds=len(sched.rounds),
        deadline_misses=sched.deadline_misses(),
    )


def phase_service(
    seed: int, nodes: int = SERVICE_NODES, n_jobs: int = SERVICE_JOBS
) -> None:
    from repro.fleet import __main__ as fleet_cli

    argv = ["--service", "--mixed", "--nodes", str(nodes), "--jobs", str(n_jobs)]
    argv += ["--seed", str(seed)]
    t0 = time.perf_counter()
    sched = fleet_cli.main(argv)
    say(service_argv=" ".join(argv), service_wall_s=time.perf_counter() - t0)
    check_schedule(sched, n_jobs)

    # the same CLI run with every engine it builds on the exact path
    exact_engines = []

    def exact_path(factory):
        def build(*args, **kwargs):
            engine = factory(*args, **kwargs)
            engine.fused = False
            exact_engines.append(engine)
            return engine

        return build

    t0 = time.perf_counter()
    with mock.patch.multiple(
        fleet_cli,
        fleet_engine=exact_path(fleet_cli.fleet_engine),
        tpu_fleet_engine=exact_path(fleet_cli.tpu_fleet_engine),
    ):
        exact = fleet_cli.main(argv)
    say(exact_service_wall_s=time.perf_counter() - t0, exact_engines=len(exact_engines))
    if not exact_engines:
        raise AssertionError("the exact-path service run built no engine")
    check_schedule(exact, n_jobs)

    def configs(s):
        placed = (c.placement for c in s.completed)
        return {p.job.job_id: (p.node, p.frequency_ghz, p.cores) for p in placed}

    a, b = configs(sched), configs(exact)
    if set(a) != set(b):
        raise AssertionError("fused and exact service runs completed different jobs")
    say(service_jobs_same_placement=sum(a[j] == b[j] for j in a), jobs=len(a))
    e_f, e_x = sched.total_energy_j(), exact.total_energy_j()
    check("service_energy_fused~exact_rel", abs(e_f - e_x) / abs(e_x), ENERGY_RTOL)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    say(compile_cache=enable_compile_cache())
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    t_start = time.perf_counter()

    with Phase("device"):
        device = phase_device()

    from benchmarks.bench_engine import FAMILIES
    from repro.configs.base import SHAPES
    from repro.core import engine as engine_mod
    from repro.core.engine import PlanningEngine, Workload, cpu_space, tpu_space
    from repro.core.node_sim import Node, PROFILES
    from repro.core.power import fit_power_model
    from repro.fleet.cluster import family_key

    with Phase("power"):
        tpu_pm = phase_power(args.seed)
        cpu_pm = fit_power_model(*Node(seed=args.seed).stress_grid())

    spaces = {
        "tpu_space": (
            PlanningEngine(tpu_pm, space=tpu_space(), noise=0.01, seed=args.seed),
            [Workload(arch, SHAPES[shape]) for arch, shape in FAMILIES],
        ),
        "cpu_space": (
            PlanningEngine(cpu_pm, space=cpu_space(), noise=0.01, seed=args.seed),
            [
                Workload(app, terms=family_key(app, n))
                for app in sorted(PROFILES)
                for n in (1.0, 2.0, 3.0)
            ],
        ),
    }
    with Phase("characterize"):
        phase_gram(args.seed)
        for name, (eng, families) in spaces.items():
            phase_characterize(name, eng, families)
    for name, (eng, families) in spaces.items():
        with Phase(f"engine.{name}"):
            phase_engine(name, eng, families, args.seed)
    with Phase("service"):
        phase_service(args.seed)

    say(trace_counts=json.dumps(engine_mod.TRACE_COUNTS, separators=(",", ":")))
    say(
        compiles=_COMPILES["n"], compile_s=_COMPILES["secs"],
        total_wall_s=time.perf_counter() - t_start,
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
