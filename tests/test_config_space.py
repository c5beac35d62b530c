"""ConfigSpace: the device-generic planning axis.

Covers the PR's parity gate and the opened TPU surface:

* the golden CPU fingerprint — plans, frontiers and a negotiated +
  migrating fleet schedule captured on the PRE-ConfigSpace engine must
  reproduce bitwise on the refactored one;
* ``ConfigSpace`` semantics: factories, validation, derived pod/socket
  coordinate, ``snap_cap``, per-space jitted-callable cache keys;
* ``core.tpu_power``: the OLS fit recovers the hidden truth coefficients
  from fleet telemetry, and the planner consumes the *fitted* surface;
* the mixed heterogeneous pool end-to-end: device-typed placement, the
  fixed-max baseline, and the journaled service replay of TPU jobs.
"""

import dataclasses
import json

import numpy as np
import pytest

from helpers.golden_cpu import GOLDEN_PATH, compute_fingerprint
from repro.core import tpu_power
from repro.core.engine import (
    CHIP_GRID,
    ConfigSpace,
    PlanningEngine,
    RooflineTerms,
    Workload,
    cpu_space,
    tpu_space,
)


# ---------------------------------------------------------------------------
# the parity gate
# ---------------------------------------------------------------------------


# Fingerprint fields that name a decision: compared exactly. Every other
# float is a measured or predicted quantity, which another JAX/XLA build
# may round differently in its last bits.
DECISION_KEYS = {
    "arch", "chips", "pods", "frequency_ghz", "cores", "node", "job_id",
    "migrations",
}
FINGERPRINT_RTOL = 1e-5


def _assert_fingerprint_matches(fresh, golden, path="fingerprint"):
    assert type(fresh) is type(golden), path
    if isinstance(golden, dict):
        assert sorted(fresh) == sorted(golden), path
        for key, want in golden.items():
            got = fresh[key]
            if key in DECISION_KEYS:
                assert got == want, f"{path}.{key}: {got!r} != {want!r}"
            else:
                _assert_fingerprint_matches(got, want, f"{path}.{key}")
    elif isinstance(golden, list):
        assert len(fresh) == len(golden), path
        for i, (got, want) in enumerate(zip(fresh, golden)):
            _assert_fingerprint_matches(got, want, f"{path}[{i}]")
    elif isinstance(golden, float):
        assert _close(fresh, golden), f"{path}: {fresh!r} vs {golden!r}"
    else:
        assert fresh == golden, path


def _close(got: float, want: float) -> bool:
    return got == want or abs(got - want) <= FINGERPRINT_RTOL * abs(want)


def test_golden_cpu_fingerprint_bitwise():
    """Every CPU decision — fused + exact plans, frontiers, a negotiated
    and migrating schedule under drift — is what the pre-refactor engine
    produced: chosen configurations, nodes, counts and the schedule's
    shape exactly, and every energy/time float to ``FINGERPRINT_RTOL``
    (the golden was recorded under another JAX build, and float identity
    across builds is not something the code can promise)."""
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    fresh = json.loads(json.dumps(compute_fingerprint()))
    _assert_fingerprint_matches(fresh, golden)


# ---------------------------------------------------------------------------
# ConfigSpace semantics
# ---------------------------------------------------------------------------


def test_factories():
    tpu = tpu_space()
    assert tpu.device == "tpu"
    assert tpu.axes == ("f_ghz", "chips", "pods")
    assert tpu.chip_grid == CHIP_GRID
    assert tpu.chips_per_pod == 256
    cpu = cpu_space()
    assert cpu.device == "cpu"
    assert cpu.axes == ("f_ghz", "cores")
    assert cpu.chip_grid == tuple(range(1, 33))
    assert cpu.chips_per_pod == 16  # socket size: the derived axis


def test_validation():
    with pytest.raises(ValueError, match="f_ghz"):
        ConfigSpace("x", "cpu", ("cores",), (1.0,), (1,), 1)
    with pytest.raises(ValueError, match="empty grid"):
        ConfigSpace("x", "cpu", ("f_ghz",), (), (1,), 1)
    with pytest.raises(ValueError, match="chips_per_pod"):
        ConfigSpace("x", "cpu", ("f_ghz",), (1.0,), (1,), 0)


def test_derived_pod_axis():
    tpu = tpu_space()
    assert [tpu.pods_for(c) for c in (16, 256, 257, 512)] == [1, 1, 2, 2]
    cpu = cpu_space()
    assert [cpu.pods_for(c) for c in (1, 16, 17, 32)] == [1, 1, 2, 2]
    F, C, P = tpu.meshes()
    assert F.shape == C.shape == P.shape == (len(tpu.freq_grid), len(CHIP_GRID))
    assert np.array_equal(P[0], np.ceil(np.asarray(CHIP_GRID) / 256))


def test_snap_cap():
    tpu = tpu_space()
    assert tpu.snap_cap(512) == 512
    assert tpu.snap_cap(300) == 256  # between grid points: snap down
    assert tpu.snap_cap(16) == 16
    assert tpu.snap_cap(15) is None  # below the grid floor
    assert cpu_space().snap_cap(7) == 7  # unit-step grid: identity


def test_legacy_kwargs_build_the_tpu_space():
    """Pre-refactor construction (no ``space``) must be the TPU space —
    the original engine's grid, bitwise."""
    pm = tpu_power.fit_fleet_power(tpu_power.FleetTelemetry(seed=0))
    legacy = PlanningEngine(pm, noise=0.01, seed=0)
    spaced = PlanningEngine(pm, space=tpu_space(), noise=0.01, seed=0)
    assert legacy.space == spaced.space
    assert legacy.freq_grid == spaced.freq_grid
    assert legacy.chip_grid == spaced.chip_grid


def test_cache_keys_carry_axes(tmp_path):
    """Two spaces with the SAME grid shape must not share a compiled
    sweep: the axis tuple is part of every jitted-callable memo key."""
    from repro.core import engine as engine_mod

    terms = RooflineTerms(100.0, 40.0, 10.0, source="synthetic")
    pm = tpu_power.fit_fleet_power(tpu_power.FleetTelemetry(seed=0))
    n_chips = len(CHIP_GRID)
    cpu = PlanningEngine(
        pm,
        space=cpu_space(chip_grid=tuple(range(1, n_chips + 1))),
        noise=0.01,
        seed=0,
        dryrun_dir=str(tmp_path),
    )
    tpu = PlanningEngine(
        pm, space=tpu_space(), noise=0.01, seed=0, dryrun_dir=str(tmp_path)
    )
    # plan the same batch shape through both engines
    for eng in (cpu, tpu):
        eng.plan_many([Workload("cs-axes-app", None, terms=terms)])
    axes_seen = {
        k[-1]
        for k in engine_mod._GRID_CALLABLE_CACHE
        if isinstance(k[-1], tuple) and k[-1] and k[-1][0] == "f_ghz"
    }
    assert ("f_ghz", "cores") in axes_seen
    assert ("f_ghz", "chips", "pods") in axes_seen


# ---------------------------------------------------------------------------
# core.tpu_power: telemetry -> OLS fit -> fitted surface (satellite 3)
# ---------------------------------------------------------------------------


def test_fit_recovers_true_coeffs():
    """``fit_power_model`` on the stress grid recovers the hidden
    ``TRUE_COEFFS`` within the telemetry noise floor."""
    pm = tpu_power.fit_fleet_power(tpu_power.FleetTelemetry(seed=0))
    fitted = (pm.c1, pm.c2, pm.c3, pm.c4)
    for got, want in zip(fitted, tpu_power.TRUE_COEFFS):
        assert got == pytest.approx(want, rel=0.05)


def test_planner_consumes_fitted_surface_not_truth():
    """The noise makes the fit distinct from the truth — and the engine's
    power projections are the FITTED surface's numbers."""
    pm = tpu_power.fit_fleet_power(tpu_power.FleetTelemetry(seed=0))
    assert (pm.c1, pm.c2, pm.c3, pm.c4) != tpu_power.TRUE_COEFFS
    eng = PlanningEngine(pm, noise=0.01, seed=0)
    f, chips = 0.9, 256
    pods = eng.space.pods_for(chips)
    assert eng.power(f, chips, pods) == pytest.approx(
        chips * (pm.c1 * f**3 + pm.c2 * f) + pm.c3 + pm.c4 * pods
    )


def test_fit_is_seed_deterministic():
    a = tpu_power.fit_fleet_power(tpu_power.FleetTelemetry(seed=3))
    b = tpu_power.fit_fleet_power(tpu_power.FleetTelemetry(seed=3))
    assert (a.c1, a.c2, a.c3, a.c4) == (b.c1, b.c2, b.c3, b.c4)


# ---------------------------------------------------------------------------
# the mixed heterogeneous pool (tentpole, end-to-end)
# ---------------------------------------------------------------------------


def _mixed_jobs():
    from repro.fleet.cluster import TermsFamily
    from repro.fleet.scheduler import Job

    jobs = [
        Job(0, "raytrace", 1.0, arrival_s=0.0, deadline_s=6000.0),
        Job(1, "swaptions", 2.0, arrival_s=50.0, deadline_s=8000.0),
        Job(4, "blackscholes", 1.0, arrival_s=240.0, deadline_s=7000.0),
    ]
    zoo = [
        (2, 10.0, "zoo:train-a", (900.0, 300.0, 120.0)),
        (3, 80.0, "zoo:train-b", (400.0, 500.0, 60.0)),
        (5, 300.0, "zoo:decode", (150.0, 700.0, 30.0)),
    ]
    for jid, arr, app, (c, m, coll) in zoo:
        fam = TermsFamily(
            base=RooflineTerms(c, m, coll, source="synthetic"), app=app
        )
        jobs.append(
            Job(
                jid,
                app,
                1.0,
                arrival_s=arr,
                deadline_s=arr + 9000.0,
                terms=fam,
                device="tpu",
            )
        )
    return sorted(jobs, key=lambda j: j.job_id)


def test_mixed_pool_scenario():
    """`run_mixed_fleet_comparison`: device-typed placement, per-device
    ConfigSpace planning, and engine energy <= the fixed-max baseline."""
    from repro.fleet.cluster import make_mixed_pool
    from repro.fleet.report import run_mixed_fleet_comparison

    jobs = _mixed_jobs()
    report, sched = run_mixed_fleet_comparison(jobs, seed=0)
    assert len(sched.completed) == len(jobs)
    pool_dev = {n.name: n.spec.device for n in make_mixed_pool(seed=0)}
    by_id = {c.placement.job.job_id: c for c in sched.completed}
    for job in jobs:
        node = by_id[job.job_id].placement.node
        assert pool_dev[node] == job.device  # never cross-device
    # TPU plans choose grid chip counts in the TPU space
    tpu_chips = {
        by_id[j.job_id].placement.cores for j in jobs if j.device == "tpu"
    }
    assert tpu_chips <= set(CHIP_GRID)
    assert report.engine_beats_all(tol=0.05)
    assert report.scenarios["fixed-max"].n_jobs == len(jobs)


def test_mixed_pool_families_and_capacity():
    from repro.fleet.cluster import TPU_SPECS, make_mixed_pool

    pool = make_mixed_pool(n_cpu=2, n_tpu=3, seed=0)
    assert pool.devices() == ("cpu", "tpu")
    assert len(pool.nodes_for("cpu")) == 2 and len(pool.nodes_for("tpu")) == 3
    assert pool.reference.spec.device == "cpu"  # CPU stays the reference
    assert pool.reference_for("tpu").spec.name.startswith(
        TPU_SPECS[0].name
    )
    assert pool.max_free_cores(0.0, "tpu") == max(
        s.max_cores for s in TPU_SPECS[:3]
    )
    cpu_only = make_mixed_pool(n_cpu=2, n_tpu=0, seed=0)
    assert cpu_only.max_free_cores(0.0, "tpu") == 0
    with pytest.raises(ValueError):
        cpu_only.reference_for("tpu")


def test_mixed_service_replay_matches_lockstep(tmp_path):
    """TPU (TermsFamily) jobs journal, crash and resume to the identical
    schedule — the wire schema round-trips the believed surface."""
    from repro.fleet.cluster import make_mixed_pool
    from repro.fleet.report import run_engine_fleet
    from repro.fleet.scheduler import fleet_engine, tpu_fleet_engine

    jobs = _mixed_jobs()

    def engines(pool):
        return {
            "cpu": fleet_engine(pool),
            "tpu": tpu_fleet_engine(pool),
        }

    lock_pool = make_mixed_pool(seed=0)
    lock_stats, _ = run_engine_fleet(
        lock_pool, jobs, engine=engines(lock_pool), negotiate=True
    )
    svc_pool = make_mixed_pool(seed=0)
    svc_stats, _ = run_engine_fleet(
        svc_pool,
        jobs,
        engine=engines(svc_pool),
        negotiate=True,
        service=True,
        service_kw=dict(journal=str(tmp_path / "mixed.json")),
    )
    assert svc_stats.total_energy_j == lock_stats.total_energy_j
    assert svc_stats.job_energy_j == lock_stats.job_energy_j


def test_job_wire_roundtrip():
    """The journal wire format reproduces a TPU job exactly, and still
    rejects believed surfaces outside the fixed schema."""
    from repro.fleet.service.store import _job_from_json, _job_to_json

    for job in _mixed_jobs():
        assert _job_from_json(json.loads(json.dumps(_job_to_json(job)))) == job
    bad = dataclasses.replace(_mixed_jobs()[0], terms=object())
    with pytest.raises(ValueError, match="journalable"):
        _job_to_json(bad)
