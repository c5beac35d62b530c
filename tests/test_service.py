"""The event-driven fleet service (tentpole): bus determinism, bitwise
parity with the lockstep driver, durable journals, fault tolerance.

The two load-bearing contracts:

* **replay determinism** — draining the ``EventBus`` reproduces the
  lockstep ``FleetScheduler.run`` schedule *bitwise* (joules, misses,
  makespan, per-job configs) on every shipped scenario shape and on
  randomized traces (the service-layer analogue of the PR-7
  fused-vs-exact parity gates);
* **fault tolerance** — any single-fault schedule (node crash mid-run,
  manager heartbeat loss, journal write torn between snapshot and
  commit) ends with ZERO lost jobs and an honest paper-units energy
  ledger (``total_energy_j`` = final segments + carried priors).

Crash-recovery (kill at every batch index) lives in
``test_service_recovery.py``; this module owns the service mechanics.
"""

import json
import math
import os
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from helpers import faults
from repro.core import svr as svr_mod
from repro.core.engine import ENGINE_FIT_KW
from repro.core.node_sim import F_MAX, FREQ_GRID, PROFILES
from repro.fleet import (
    FleetNode,
    FleetScheduler,
    Job,
    LookaheadPolicy,
    MigrationPolicy,
    Negotiator,
    NodePool,
    NodeSpec,
    fleet_engine,
    make_pool,
)
from repro.fleet.service import (
    Event,
    EventBus,
    Journal,
    JournalTorn,
    SchedulerService,
    ServiceKilled,
)
from repro.fleet.service import events as ev

QUICK_FREQS = tuple(float(f) for f in FREQ_GRID[::3])
QUICK_CORES = (1, 2, 4, 8, 16, 24, 32)
QUICK_ENGINE_KW = dict(freqs=QUICK_FREQS, cores=QUICK_CORES, noise=0.01, seed=0)
APPS = sorted(PROFILES)


def build_scheduler(
    n_nodes=3, *, negotiate=False, migration=None, lookahead=None
):
    pool = make_pool(n_nodes, seed=0)
    engine = fleet_engine(pool, **QUICK_ENGINE_KW)
    return FleetScheduler(
        pool,
        engine,
        char_freqs=QUICK_FREQS[::2],
        char_cores=(1, 8, 16, 32),
        negotiator=Negotiator(pool, engine.power) if negotiate else None,
        migration=migration,
        lookahead=lookahead,
    )


def trace(n_jobs, *, spacing=150.0, slack=3.0, inputs=(1.0,)):
    jobs, t = [], 0.0
    for i in range(n_jobs):
        app = APPS[i % len(APPS)]
        n = inputs[i % len(inputs)]
        est = PROFILES[app].time(F_MAX, 16, n)
        jobs.append(Job(i, app, n, deadline_s=t + est * slack, arrival_s=t))
        t += spacing
    return jobs


def fingerprint(sched):
    """Everything "bitwise-identical schedule" means: per-job config,
    node, exact joules/times, deadline fate, migration/restart counts,
    plus the telemetry record the rounds produced."""
    return {
        "jobs": [
            (
                c.placement.job.job_id,
                c.placement.node,
                c.placement.frequency_ghz,
                c.placement.cores,
                c.total_energy_j,
                c.total_time_s,
                c.finish_s,
                c.met_deadline,
                c.migrations,
                c.restarts,
            )
            for c in sched.completed
        ],
        "rounds": len(sched.rounds),
        "refreshes": list(sched.telemetry.refreshes),
        "preemptions": [
            (p.job_id, p.time_s, p.burned_j)
            for p in sched.telemetry.preemptions
        ],
        "makespan_s": sched.makespan_s,
        "energy_j": sched.total_energy_j(),
        "misses": sched.deadline_misses(),
    }


# ---------------------------------------------------------------------------
# the event bus: deterministic ordering, eps batching, staleness
# ---------------------------------------------------------------------------


def test_event_bus_orders_by_time_kind_then_fifo():
    bus = EventBus()
    bus.push(ev.arrival(10.0, 1))
    bus.push(ev.completion(10.0, 2, 0))
    bus.push(ev.drift(10.0, "raytrace", 1.5))
    bus.push(ev.arrival(10.0, 0))  # same (time, kind): FIFO after job 1
    bus.push(ev.tick(5.0))
    t, batch = bus.pop_batch()
    assert t == 5.0 and [e.kind for e in batch] == ["tick"]
    t, batch = bus.pop_batch()
    assert t == 10.0
    # dispatch priority: drift before completion before arrivals (FIFO)
    assert [(e.kind, e.job_id) for e in batch] == [
        ("drift", None),
        ("completion", 2),
        ("arrival", 1),
        ("arrival", 0),
    ]
    assert bus.pop_batch() == (None, [])


def test_event_bus_batches_within_time_eps():
    from repro.fleet.cluster import time_eps

    bus = EventBus()
    t0 = 1e7  # large sim time: the relative eps is what groups here
    bus.push(ev.arrival(t0, 0))
    bus.push(ev.completion(t0 + 0.5 * time_eps(t0), 1, 0))  # same instant
    bus.push(ev.arrival(t0 + 10.0, 2))  # clearly later
    t, batch = bus.pop_batch()
    assert t == t0 and len(batch) == 2
    t, batch = bus.pop_batch()
    assert t == t0 + 10.0 and len(batch) == 1


def test_event_bus_skips_stale_completions():
    bus = EventBus()
    bus.push(ev.completion(50.0, 7, gen=0))  # superseded by a relaunch
    bus.push(ev.completion(80.0, 7, gen=1))
    live = {7: 1}
    stale = lambda e: e.kind == "completion" and live.get(e.job_id) != e.gen
    t, batch = bus.pop_batch(stale)
    # the stale head must not set the batch instant
    assert t == 80.0 and [e.gen for e in batch] == [1]
    assert bus.pop_batch(stale) == (None, [])


def test_event_json_roundtrip():
    events = [
        ev.arrival(12.5, 3),
        ev.completion(99.0, 4, gen=2),
        ev.drift(7.0, "swaptions", 1.8),
        ev.node_down(5.0, "eco-1"),
        ev.heartbeat(60.0, "ref-0"),
        ev.tick(0.0),
    ]
    for e in events:
        wire = json.loads(json.dumps(e.to_json()))
        assert Event.from_json(wire) == e
    with pytest.raises(ValueError):
        Event(0.0, "not-a-kind")


# ---------------------------------------------------------------------------
# the journal: atomic commits, schema pinning, torn-write injection
# ---------------------------------------------------------------------------


def test_journal_commit_is_atomic_under_torn_write(tmp_path):
    path = str(tmp_path / "journal.json")
    journal = Journal(path)
    from repro.fleet.service import SERVICE_SCHEMA_VERSION

    first = {"schema_version": SERVICE_SCHEMA_VERSION, "now_s": 1.0, "x": 1}
    journal.commit(first)
    journal.fail_next_commit = True
    with pytest.raises(JournalTorn):
        journal.commit(
            {"schema_version": SERVICE_SCHEMA_VERSION, "now_s": 2.0, "x": 2}
        )
    # the torn commit left the previous document fully intact
    assert Journal.load(path) == first
    assert journal.commits == 1


def test_journal_refuses_schema_mismatch(tmp_path):
    path = str(tmp_path / "journal.json")
    with open(path, "w") as f:
        json.dump({"schema_version": -1, "now_s": 0.0}, f)
    with pytest.raises(ValueError, match="schema version"):
        Journal.load(path)


def test_fit_many_is_batch_composition_independent():
    """The recovery refit's soundness anchor: re-fitting a journaled
    training set in a DIFFERENT batch than the one the live service used
    must produce the bitwise-same model (``fit_many`` restarts its RNG
    per set, so batch composition cannot leak between sets)."""
    rng = np.random.default_rng(0)
    sets = []
    for i in range(3):
        x = np.asarray(rng.uniform([1.0, 1], [3.5, 32], (12, 2)), np.float32)
        y = np.asarray(10.0 / x[:, 0] + 50.0 / x[:, 1] + i, np.float32)
        sets.append((x, y))
    alone = svr_mod.fit_many([sets[1]], method="auto", **ENGINE_FIT_KW)
    batched = svr_mod.fit_many(sets, method="auto", **ENGINE_FIT_KW)
    grid = np.asarray(rng.uniform([1.0, 1], [3.5, 32], (40, 2)), np.float32)
    pred_alone = svr_mod.predict_each(alone, [grid])[0]
    pred_batched = svr_mod.predict_each([batched[1]], [grid])[0]
    assert np.array_equal(
        np.asarray(pred_alone), np.asarray(pred_batched)
    ), "fit_many models depend on batch composition — recovery refits unsound"


# ---------------------------------------------------------------------------
# replay determinism: event-driven == lockstep, bitwise
# ---------------------------------------------------------------------------


def _drift_for(jobs):
    return [(jobs[len(jobs) // 3].arrival_s + 1.0, "raytrace", 1.6)]


@pytest.mark.parametrize(
    "mode", ["fallback", "negotiated", "lookahead"]
)
def test_service_matches_lockstep_bitwise_on_shipped_shapes(mode):
    """The acceptance gate: every shipped scenario shape (cheapest-first
    fallback, negotiated + migration, horizon-aware lookahead) reproduces
    bitwise under the event-driven core."""
    kw = dict(
        fallback=dict(),
        negotiated=dict(negotiate=True, migration=MigrationPolicy()),
        lookahead=dict(
            negotiate=True,
            migration=MigrationPolicy(),
            lookahead=LookaheadPolicy(horizon_s=600.0),
        ),
    )[mode]
    jobs = trace(8)
    drift = _drift_for(jobs)
    lockstep = build_scheduler(**kw)
    lockstep.run(jobs, drift_events=drift)
    reactor = build_scheduler(**kw)
    SchedulerService(reactor).run(jobs, drift_events=drift)
    assert fingerprint(reactor) == fingerprint(lockstep)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_replay_determinism_on_randomized_traces(seed):
    """Property: randomized arrival/drift traces replay bitwise —
    joules, misses, makespan AND per-job configs (the fingerprint holds
    them all)."""
    rng = np.random.default_rng(seed)
    n_jobs = int(rng.integers(4, 8))
    spacing = float(rng.uniform(60.0, 260.0))
    slack = float(rng.uniform(2.0, 4.0))
    jobs = trace(n_jobs, spacing=spacing, slack=slack)
    drift = [
        (
            float(rng.uniform(1.0, max(spacing * n_jobs, 2.0))),
            APPS[int(rng.integers(len(APPS)))],
            float(rng.uniform(1.2, 2.0)),
        )
    ]
    negotiate = bool(rng.integers(2))
    kw = dict(negotiate=negotiate)
    if negotiate and rng.integers(2):
        kw["lookahead"] = LookaheadPolicy(horizon_s=float(rng.uniform(300, 900)))
    lockstep = build_scheduler(**kw)
    lockstep.run(jobs, drift_events=drift)
    reactor = build_scheduler(**kw)
    SchedulerService(reactor).run(jobs, drift_events=drift)
    assert fingerprint(reactor) == fingerprint(lockstep)


# ---------------------------------------------------------------------------
# fault injection: zero lost jobs, honest ledger
# ---------------------------------------------------------------------------


def _assert_zero_lost_and_honest(sched, n_jobs):
    done = sched.completed
    assert sorted(c.placement.job.job_id for c in done) == list(range(n_jobs))
    # the honest paper-units ledger: every job's _j total is its final
    # segment plus everything carried from killed/preempted segments, and
    # the fleet total is exactly their sum
    for c in done:
        assert c.total_energy_j == c.result.energy_j + c.prior_energy_j
        assert c.total_energy_j > 0
    assert math.isclose(
        sched.total_energy_j(), sum(c.total_energy_j for c in done)
    )


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_any_single_fault_ends_with_zero_lost_jobs(seed):
    """Property: one seeded fault — node crash, heartbeat loss, or a
    journal write torn between snapshot and commit — never loses a job
    and never breaks the energy ledger."""
    # a directory per example: hypothesis re-runs the body, and a
    # function-scoped fixture would be shared between examples
    with tempfile.TemporaryDirectory() as tmp:
        _single_fault_run(seed, os.path.join(tmp, f"fault-{seed}.json"))


def _single_fault_run(seed, path):
    n_jobs = 6
    jobs = trace(n_jobs)
    sched = build_scheduler(negotiate=True)
    service = SchedulerService(
        sched, journal=path, heartbeat_period_s=150.0
    )
    fault = faults.single_fault_schedule(
        seed,
        nodes=[n.name for n in sched.pool],
        t_lo_s=100.0,
        t_hi_s=900.0,
    )
    faults.inject(service, fault)
    try:
        service.run(jobs)
    except JournalTorn:
        # the simulated death between snapshot and commit: restart from
        # the journal (which atomically kept the previous commit)
        fresh = build_scheduler(negotiate=True)
        service = SchedulerService.resume(
            path, fresh, heartbeat_period_s=150.0
        )
        service.drain()
        sched = fresh
    _assert_zero_lost_and_honest(sched, n_jobs)


def test_node_down_kills_in_flight_and_requeues_honestly():
    """Deterministic in-flight kill: find the longest-running segment in
    a golden run, crash its node mid-segment, and check the job restarts
    elsewhere with the burned joules carried on its bill."""
    jobs = trace(8)
    golden = build_scheduler(negotiate=True)
    SchedulerService(golden).run(jobs)
    victim = max(golden.completed, key=lambda c: c.result.time_s)
    t_kill = victim.placement.start_s + 0.5 * victim.result.time_s
    node = victim.placement.node

    sched = build_scheduler(negotiate=True)
    service = SchedulerService(sched)
    service.inject(ev.node_down(t_kill, node))
    service.inject(ev.node_up(t_kill + 500.0, node))
    service.run(jobs)
    _assert_zero_lost_and_honest(sched, len(jobs))
    jid = victim.placement.job.job_id
    restarted = next(
        c for c in sched.completed if c.placement.job.job_id == jid
    )
    assert restarted.restarts == 1
    assert restarted.placement.node != node  # replanned off the dead node
    assert restarted.prior_energy_j > 0  # the burned segment is on the bill
    rec = next(p for p in sched.telemetry.preemptions if p.job_id == jid)
    assert rec.from_node == node and rec.burned_j > 0
    assert rec.migration_cost_j == 0.0  # a crash is not a checkpoint
    # the dead node's reservation really was truncated at the crash
    dead = next(n for n in sched.pool if n.name == node)
    cut = [r for r in dead.reservations if r.job_id == jid]
    assert cut and max(r.end_s for r in cut) == pytest.approx(t_kill)


def test_heartbeat_loss_declares_node_down_and_recovers():
    jobs = trace(6)
    sched = build_scheduler(negotiate=True)
    service = SchedulerService(sched, heartbeat_period_s=120.0)
    lost = sched.pool.nodes[1].name
    service.managers[lost].silence_after_s = 200.0
    service.run(jobs)
    _assert_zero_lost_and_honest(sched, len(jobs))
    # the service *declared* the silent node down (the node never crashed)
    assert not service.managers[lost].available
    late = [
        c
        for c in sched.completed
        if c.finish_s > 200.0 + 2.5 * 120.0 and c.placement.node == lost
    ]
    assert not late, "work was placed on a node the service cannot hear"


def test_artifact_jobs_refuse_the_journal(tmp_path):
    sched = build_scheduler()
    service = SchedulerService(sched, journal=str(tmp_path / "j.json"))
    bad = Job(0, "raytrace", 1.0, deadline_s=100.0, terms=object())
    with pytest.raises(ValueError, match="artifact"):
        service.submit(bad)


# ---------------------------------------------------------------------------
# the kill switch (the CLI's --kill-at)
# ---------------------------------------------------------------------------


def test_kill_at_raises_service_killed_with_resume_coordinates(tmp_path):
    jobs = trace(6)
    path = str(tmp_path / "killed.json")
    sched = build_scheduler()
    service = SchedulerService(sched, journal=path, kill_at_s=300.0)
    with pytest.raises(ServiceKilled) as exc:
        service.run(jobs)
    assert exc.value.journal_path == path
    assert exc.value.time_s is not None and exc.value.time_s > 300.0
    # the journal's last commit predates the kill: resumable state
    payload = Journal.load(path)
    assert payload["now_s"] <= 300.0 + 1e-6
