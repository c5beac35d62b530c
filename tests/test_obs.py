"""Flight-recorder contracts: zero-cost-off, schema-pinned-on.

The obs subsystem's whole value rests on two promises:

1. **Off is really off** — with the nulls installed (the default), the
   instrumented stack allocates nothing per hook and produces results
   bitwise-identical to pre-obs behavior (the parity test runs a full
   negotiate+migrate+lookahead fleet comparison twice, traced and
   untraced, and diffs the report JSON).
2. **On is stable** — the Chrome trace-event export keeps its pinned
   8-key schema (Perfetto loadability is a contract, not an accident),
   and identical runs produce identical metric rollups.
"""

from __future__ import annotations

import glob
import json
import math
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.obs import metrics as obs_metrics
from repro.obs import timeline as obs_timeline
from repro.obs import trace as obs_trace
from repro.obs.__main__ import main as obs_cli_main
from repro.core import engine as engine_mod
from repro.core.node_sim import F_MAX, FREQ_GRID, PROFILES
from repro.fleet import (
    Job,
    LookaheadPolicy,
    MigrationPolicy,
    fleet_engine,
    make_pool,
)
from repro.fleet import FleetScheduler
from repro.fleet.report import run_engine_fleet
from repro.fleet.service import SchedulerService
from repro.fleet.telemetry import Observation, TelemetryHub


# ---------------------------------------------------------------------------
# the shared mini-scenario: small grids, but every subsystem exercised
# (negotiation, migration via a drift event, lookahead holds)
# ---------------------------------------------------------------------------

ENGINE_KW = dict(
    freqs=tuple(float(f) for f in FREQ_GRID[::4]),
    cores=(2, 8, 16),
    noise=0.01,
    seed=0,
)


_HOST_ROW = np.zeros(352, np.float32)


def _jobs(n=8):
    apps = sorted(PROFILES)[:3]
    out = []
    for i in range(n):
        app = apps[i % len(apps)]
        est = PROFILES[app].time(F_MAX, 8, 1.0)
        out.append(Job(i, app, 1.0, deadline_s=est * 3.0, arrival_s=0.0))
    return out


def _run_scenario():
    pool = make_pool(2, seed=0)
    return run_engine_fleet(
        pool,
        _jobs(),
        engine=fleet_engine(pool, **ENGINE_KW),
        negotiate=True,
        migration=MigrationPolicy(),
        lookahead=LookaheadPolicy(horizon_s=600.0),
        drift_events=[(10.0, sorted(PROFILES)[0], 1.6)],
    )


# ---------------------------------------------------------------------------
# 1 · bitwise parity: tracing must not change one scheduling decision
# ---------------------------------------------------------------------------


def test_instrumented_run_is_bitwise_identical_to_untraced():
    stats_off, _ = _run_scenario()
    with obs.recording() as rec:
        stats_on, _ = _run_scenario()
    d_off, d_on = stats_off.to_json(), stats_on.to_json()
    # obs_rollup is the ONE field recording is allowed to populate
    rollup = d_on.pop("obs_rollup")
    d_off.pop("obs_rollup")
    assert json.dumps(d_off, sort_keys=True, default=float) == json.dumps(
        d_on, sort_keys=True, default=float
    )
    # and the recording actually recorded: spans + scenario-attributed
    # counters from every instrumented layer
    assert len(rec.trace) > 0
    assert rollup["counters"]["fleet.rounds"] > 0
    assert rollup["counters"]["fleet.jobs_placed"] == stats_on.n_jobs
    assert any(k.startswith("engine.") for k in rollup["counters"])
    assert any(k.startswith("svr.fit_route") for k in rollup["counters"])


def test_rollup_attributes_scheduler_activity():
    with obs.recording():
        stats, sched = _run_scenario()
    c = stats.obs_rollup["counters"]
    assert c["fleet.rounds"] == len(sched.rounds)
    assert c.get("fleet.refits", 0) == stats.recharacterizations
    assert c.get("fleet.migrations", 0) == stats.preemptions
    # staleness gauges (satellite 2) ride in the rollup too
    gauges = stats.obs_rollup["gauges"]
    assert any(
        k.startswith("telemetry.window_occupancy.") for k in gauges
    )
    assert any(
        k.startswith("telemetry.observation_age_s.") for k in gauges
    )


# ---------------------------------------------------------------------------
# 2 · Chrome trace-event schema pin
# ---------------------------------------------------------------------------


def test_trace_event_schema_is_pinned():
    assert obs_trace.TRACE_SCHEMA_VERSION == 1
    assert obs_trace.TRACE_EVENT_KEYS == (
        "name", "cat", "ph", "ts", "dur", "pid", "tid", "args",
    )
    with obs.recording() as rec:
        with obs.span("outer", cat="test", sim_t_s=1.5, extra=3):
            obs.event("inner", cat="test")
        _, sched = _run_scenario()
    payload = obs.export_run(rec, sched=sched)
    events = payload["traceEvents"]
    assert events, "recording produced no events"
    for ev in events:
        # EXACTLY the pinned keys, on every event (live and timeline)
        assert tuple(ev) == obs_trace.TRACE_EVENT_KEYS
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["args"], dict)
        assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0
    phases = {ev["ph"] for ev in events}
    assert "X" in phases  # complete spans
    assert "i" in phases  # instants
    assert "M" in phases  # timeline lane metadata
    # sim-clock stamps ride in args
    outer = next(ev for ev in events if ev["name"] == "outer")
    assert outer["args"]["sim_t_s"] == 1.5 and outer["args"]["extra"] == 3
    # the whole payload is one json.dump away from Perfetto
    json.dumps(payload, default=float)


def test_export_meta_and_timeline_are_consistent():
    with obs.recording() as rec:
        _, sched = _run_scenario()
    payload = obs.export_run(rec, sched=sched)
    meta = payload["meta"]
    assert meta["schema_version"] == obs_trace.TRACE_SCHEMA_VERSION
    assert meta["n_dropped_events"] == 0
    assert meta["n_timeline_segments"] == len(payload["timeline"])
    # every completed job appears as a run segment on some node lane
    runs = [s for s in payload["timeline"] if s["kind"] == "run"]
    assert len(runs) == len(sched.completed)
    lanes = {
        ev["args"]["name"]
        for ev in payload["traceEvents"]
        if ev["ph"] == "M" and ev["name"] == "thread_name"
    }
    assert {s["node"] for s in payload["timeline"]} <= lanes


# ---------------------------------------------------------------------------
# 3 · metrics-registry determinism
# ---------------------------------------------------------------------------


def test_metrics_registry_snapshots_are_deterministic():
    def fill(reg):
        # deliberately unsorted insertion order
        reg.counter("z.last").inc(3)
        reg.counter("a.first").inc()
        reg.gauge("m.level").set(0.25)
        for v in (1.0, 4.0, 2.5):
            reg.histogram("h.width_s").observe(v)
        return reg.snapshot()

    s1 = fill(obs_metrics.MetricsRegistry())
    s2 = fill(obs_metrics.MetricsRegistry())
    assert s1 == s2
    assert json.dumps(s1, sort_keys=False) == json.dumps(s2, sort_keys=False)
    # and names come out sorted regardless of insertion order
    assert list(s1["counters"]) == ["a.first", "z.last"]
    h = s1["histograms"]["h.width_s"]
    assert h["count"] == 3 and h["min"] == 1.0 and h["max"] == 4.0
    assert h["total"] == pytest.approx(7.5)


def test_two_identical_recorded_runs_roll_up_identically():
    with obs.recording():
        stats_a, _ = _run_scenario()
    with obs.recording():
        stats_b, _ = _run_scenario()
    assert json.dumps(
        stats_a.obs_rollup["counters"], sort_keys=True
    ) == json.dumps(stats_b.obs_rollup["counters"], sort_keys=True)


def test_metrics_diff_is_a_scenario_delta():
    before = {
        "counters": {"a": 2, "b": 5},
        "gauges": {"g": 1.0},
        "histograms": {"h": {"count": 2, "total": 4.0, "mean": 2.0,
                             "min": 1.0, "max": 3.0}},
    }
    after = {
        "counters": {"a": 2, "b": 9, "c": 1},
        "gauges": {"g": 7.0},
        "histograms": {"h": {"count": 5, "total": 19.0, "mean": 3.8,
                             "min": 1.0, "max": 9.0}},
    }
    d = obs_metrics.diff(before, after)
    assert d["counters"] == {"b": 4, "c": 1}  # zero-delta "a" dropped
    assert d["gauges"] == {"g": 7.0}  # gauges: last write wins
    assert d["histograms"]["h"] == {
        "count": 3, "total": 15.0, "mean": 5.0,
    }


# ---------------------------------------------------------------------------
# 4 · NullTracer no-allocation fast path
# ---------------------------------------------------------------------------


def test_null_tracer_is_installed_by_default_and_returns_singletons():
    assert obs.tracer() is obs_trace.NULL_TRACER
    assert obs.metrics_registry() is obs_metrics.NULL_METRICS
    assert not obs.enabled()
    # every null span/instrument is the SAME object — no per-call cost
    s1, s2 = obs.span("a", cat="x"), obs.span("b", cat="y", sim_t_s=2.0)
    assert s1 is s2 is obs_trace._NULL_SPAN
    assert obs.counter("a") is obs.counter("b")
    assert obs.gauge("a") is obs.gauge("b")
    assert obs.histogram("a") is obs.histogram("b")
    assert len(obs.tracer()) == 0 and obs.tracer().export() == {
        "traceEvents": []
    }


def test_null_path_allocates_nothing_in_steady_state():
    pool = make_pool(1, seed=0)
    node = pool.nodes[0]
    node.reserve(0.0, 10.0, 4, job_id=0)

    def hooks():
        with obs.span("service.batch", cat="service", sim_t_s=0.0,
                      n_events=1, step=7):
            with obs.span("round", cat="fleet", sim_t_s=0.0):
                obs.counter("fleet.rounds").inc()
                obs.histogram("fleet.round.pending_jobs").observe(3)
                obs.event("evt", cat="fleet")
                with obs.span("fleet.candidates", cat="fleet", n_nodes=1):
                    node.free_cores(1.0)
                pool.next_completion(1.0)
                with obs.span("fleet.run_on", cat="fleet", cores=4,
                              f_ghz=2.2):
                    pass
                with obs.span("engine.sweep", cat="engine", batch=1, g=352):
                    engine_mod._count_bytes("engine.h2d_bytes", _HOST_ROW)
                    engine_mod._fetch(_HOST_ROW)
                with obs.span("engine.finish_plans", cat="engine", batch=1):
                    pass
                obs._on_jax_duration(obs._COMPILE_EVENT, 0.01, fun_name="f")

    hooks()  # warm any lazy module state

    def grown_obs_bytes():
        # bytes still live after 200 hook rounds, attributed to any obs
        # source line (the test file's own loop machinery is excluded —
        # it is tracemalloc noise, not the contract)
        tracemalloc.start()
        snap_a = tracemalloc.take_snapshot()
        for _ in range(200):
            hooks()
        snap_b = tracemalloc.take_snapshot()
        tracemalloc.stop()
        obs_filter = tracemalloc.Filter(True, "*repro/obs/*")
        return sum(
            d.size_diff
            for d in snap_b.filter_traces([obs_filter]).compare_to(
                snap_a.filter_traces([obs_filter]), "lineno"
            )
            if d.size_diff > 0
        )

    # a real per-hook allocation repeats on every attempt (200 calls never
    # net to zero); transient attribution noise (a GC pass landing mid-loop
    # under full-suite memory pressure) does not survive a retry
    sizes = []
    for _ in range(3):
        sizes.append(grown_obs_bytes())
        if sizes[-1] == 0:
            break
    assert sizes[-1] == 0, sizes


def test_recording_restores_previous_state_even_on_error():
    with pytest.raises(RuntimeError):
        with obs.recording():
            assert obs.enabled()
            raise RuntimeError("boom")
    assert not obs.enabled()
    assert obs.tracer() is obs_trace.NULL_TRACER


def test_tracer_ring_buffer_drops_oldest_and_counts_drops():
    t = obs_trace.Tracer(capacity=4)
    for i in range(10):
        t.event(f"e{i}", cat="test")
    assert len(t) == 4
    assert t.n_dropped == 6
    assert [ev["name"] for ev in t.events()] == ["e6", "e7", "e8", "e9"]


# ---------------------------------------------------------------------------
# 5 · telemetry staleness gap (satellite 2 regression)
# ---------------------------------------------------------------------------


def _obs_at(family, t, err=0.0):
    pred = 10.0
    return Observation(
        family=family,
        node="n0",
        frequency_ghz=2.0,
        cores=8,
        input_size=family[1],
        predicted_time_s=pred,
        measured_time_s=pred * (1.0 + err),
        predicted_energy_j=100.0,
        measured_energy_j=100.0,
        finish_s=t,
    )


def test_silent_family_is_visible_not_quietly_unrefit():
    """The gap: a family that stops reporting can never trip the drift
    detector (min_samples unreachable), so it silently never refits.
    The staleness views must surface it."""
    hub = TelemetryHub(window=4, threshold=0.15, min_samples=2)
    chatty, silent = ("fluid", 1.0), ("ray", 2.0)
    hub.record(_obs_at(silent, t=50.0, err=0.9))  # ONE huge-error report
    for t in (100.0, 200.0, 300.0):
        hub.record(_obs_at(chatty, t, err=0.0))
    now = 1000.0
    # the broken-family signal never reaches the detector's threshold…
    assert silent not in hub.stale_families()
    # …but the staleness views see it
    assert hub.detector.occupancy(silent) == pytest.approx(0.25)
    assert hub.detector.occupancy(chatty) == pytest.approx(0.75)
    assert hub.last_observation_s(silent) == 50.0
    assert hub.observation_age_s(silent, now) == pytest.approx(950.0)
    assert hub.silent_families(now, max_age_s=800.0) == [silent]
    assert hub.silent_families(now, max_age_s=2000.0) == []
    # a family never seen at all ages from -inf
    assert hub.observation_age_s(("ghost", 1.0), now) == float("inf")

    reg = obs_metrics.MetricsRegistry()
    hub.export_staleness_gauges(reg, now)
    snap = reg.snapshot()["gauges"]
    assert snap["telemetry.window_occupancy.ray:2"] == pytest.approx(0.25)
    assert snap["telemetry.observation_age_s.ray:2"] == pytest.approx(950.0)
    assert snap["telemetry.window_occupancy.fluid:1"] == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# 6 · export + CLI round trip
# ---------------------------------------------------------------------------


def test_write_trace_and_cli_summary_round_trip(tmp_path, capsys):
    with obs.recording() as rec:
        _, sched = _run_scenario()
    path = tmp_path / "out.json"
    payload = obs.write_trace(str(path), rec, sched=sched)
    with open(path) as f:
        loaded = json.load(f)
    assert loaded["meta"]["schema_version"] == obs_trace.TRACE_SCHEMA_VERSION
    assert len(loaded["traceEvents"]) == len(payload["traceEvents"])

    assert obs_cli_main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "schema v1" in out
    assert "fleet.round" in out  # span rollup
    assert "fleet.rounds" in out  # counter table

    assert obs_cli_main([str(path), "--json"]) == 0
    rollup = json.loads(capsys.readouterr().out)
    assert set(rollup) == {"meta", "metrics", "spans"}
    names = {row["name"] for row in rollup["spans"]}
    assert "fleet.round" in names and "engine.pareto_many" in names


def test_timeline_reconstruction_kinds_and_utilization():
    with obs.recording():
        _, sched = _run_scenario()
    segments = obs_timeline.build_timeline(sched)
    kinds = {s.kind for s in segments}
    assert obs_timeline.KIND_RUN in kinds
    # the drift event forces at least one preemption in this scenario
    assert (
        len([s for s in segments if s.kind == obs_timeline.KIND_PREEMPTED])
        == sched.telemetry.n_preemptions
    )
    for s in segments:
        assert s.end_s >= s.start_s
    busy = obs_timeline.node_utilization(segments)
    assert busy and all(v > 0 for v in busy.values())
    # preempted segments carry real geometry (the new record fields)
    for s in segments:
        if s.kind == obs_timeline.KIND_PREEMPTED:
            assert s.cores > 0


# ---------------------------------------------------------------------------
# 7 · spans and counters inside the round and the engine (parsec_node world)
# ---------------------------------------------------------------------------
#
# One node of the paper's type, one engine over the full (f, cores) grid
# (G = 11 x 32 = 352), the scheduler's defaults, the event service. Jobs
# arrive far apart, so every reaction plans one job (B = 1).

N_SEQUENTIAL = 6


def _parsec_world():
    pool = make_pool(1, seed=0)
    sched = FleetScheduler(pool, fleet_engine(pool, seed=0))
    apps = sorted(PROFILES)[:2]
    jobs = [
        Job(i, apps[i % 2], 1.0, deadline_s=math.inf, arrival_s=1e6 * i)
        for i in range(N_SEQUENTIAL)
    ]
    return pool, sched, SchedulerService(sched), jobs


def _xs(spans, name):
    return [e for e in spans if e["name"] == name and e["ph"] == "X"]


def _inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


@pytest.fixture(scope="module")
def parsec_replay():
    """The recorded replay, with the reservations each capacity query
    walked summed by wrappers of the two query methods."""
    pool, sched, svc, jobs = _parsec_world()
    node = pool.nodes[0]
    walked = [0]
    free_cores, next_completion = node.free_cores, pool.next_completion

    def counted_free(*a, **kw):
        walked[0] += len(node.reservations) if node.available else 0
        return free_cores(*a, **kw)

    def counted_next(*a, **kw):
        walked[0] += sum(len(n.reservations) for n in pool.nodes)
        return next_completion(*a, **kw)

    node.free_cores, pool.next_completion = counted_free, counted_next
    with obs.recording() as rec:
        svc.run(jobs)
    return rec, sched, walked[0]


@pytest.mark.parametrize("child, parent, per", [
    ("fleet.run_on", "fleet.place", "launch"),
    ("fleet.candidates", "fleet.place", "launch"),
    ("engine.sweep", "engine.plan_many", "pass"),
    ("engine.finish_plans", "engine.plan_many", "pass"),
    ("engine.plan_many", "fleet.place", "pass"),
    ("fleet.place", "service.batch", "pass"),
])
def test_new_spans_nest_once_per_launch_and_pass(parsec_replay, child,
                                                 parent, per):
    rec, sched, _ = parsec_replay
    spans = rec.trace.events()
    kids, parents = _xs(spans, child), _xs(spans, parent)
    # one placement per plan pass at B = 1, and the candidate pass of a
    # job with no deadline finds its node at once
    assert len(sched.completed) == N_SEQUENTIAL
    assert len(kids) == N_SEQUENTIAL
    assert len(_xs(spans, "engine.plan_many")) == N_SEQUENTIAL
    for k in kids:
        assert sum(_inside(k, p) for p in parents) == 1, (child, k)
    if child in ("engine.sweep", "engine.finish_plans"):
        assert all(k["args"]["batch"] == 1 for k in kids)
    if child == "engine.sweep":
        assert all(k["args"]["g"] == 352 for k in kids)
    if child == "fleet.run_on":
        assert sorted((k["args"]["cores"], k["args"]["f_ghz"]) for k in kids) == sorted(
            (c.placement.cores, c.placement.frequency_ghz) for c in sched.completed
        )


def test_service_batch_carries_its_step(parsec_replay):
    rec, _, _ = parsec_replay
    batches = sorted(_xs(rec.trace.events(), "service.batch"), key=lambda e: e["ts"])
    assert [b["args"]["step"] for b in batches] == list(range(len(batches)))


def test_transfer_bytes_match_the_hand_reckoning(parsec_replay):
    rec, _, _ = parsec_replay
    counters = rec.metrics.snapshot()["counters"]
    g = 352  # 11 frequencies x 32 core counts
    # per B = 1 pass: T and W in f32, one f32 exponent, one bool mask
    # row up; one int32 grid index down
    per_pass_h2d = 4 * g + 4 * g + 4 + g
    assert per_pass_h2d == 3172
    assert counters["engine.h2d_bytes"] == N_SEQUENTIAL * per_pass_h2d
    assert counters["engine.d2h_bytes"] == N_SEQUENTIAL * 4


def test_capacity_rows_scanned_sum_the_reservations_walked(parsec_replay):
    rec, _, walked = parsec_replay
    counters = rec.metrics.snapshot()["counters"]
    assert walked > 0
    assert counters["fleet.capacity_rows_scanned"] == walked


def test_new_geometry_counts_a_compile_inside_the_sweep():
    pool = make_pool(1, seed=0)
    sched = FleetScheduler(pool, fleet_engine(pool, **ENGINE_KW))
    # a batch size no other test plans: its sweep compiles (or loads) here
    b = 37
    workloads = [sched._workload(j, 0.0, 16) for j in _jobs(b)]
    sched.engine.plan_many(workloads[:1])  # fits and B = 1 programs outside
    with obs.recording() as rec:
        sched.engine.plan_many(workloads)
    assert rec.metrics.snapshot()["counters"]["jax.compiles"] >= 1
    spans = rec.trace.events()
    sweeps = [s for s in _xs(spans, "engine.sweep") if s["args"]["batch"] == b]
    compiles = _xs(spans, "jax.compile")
    assert sweeps and compiles
    assert any(_inside(c, sweeps[0]) for c in compiles)


def test_a_recording_without_compiles_reads_zero():
    with obs.recording() as rec:
        pass
    assert rec.metrics.snapshot()["counters"]["jax.compiles"] == 0


def test_recorded_spans_share_the_profiler_clock(tmp_path):
    """A recording under ``jax.profiler``: the xplane's host plane holds
    every program span under its own name, nested as recorded, and
    ``service.batch`` as a step."""
    import jax
    from jax.profiler import ProfileData

    _, _, svc, jobs = _parsec_world()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.recording() as rec:
            svc.run(jobs[:3])
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1]
    spans = [e for e in rec.trace.events() if e["ph"] == "X"]
    names = {e["name"] for e in spans} - {"jax.compile"}  # reported after the fact
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                        )
    for name in names:
        assert len(host.get(name, ())) == len(_xs(spans, name)), name
    # nesting: each copy lies inside a copy of its recorded parent
    for child, parent in [("fleet.run_on", "fleet.place"),
                          ("fleet.candidates", "fleet.place"),
                          ("engine.sweep", "engine.plan_many"),
                          ("engine.finish_plans", "engine.plan_many"),
                          ("fleet.round", "service.batch")]:
        for s, e, _ in host[child]:
            assert any(ps <= s and e <= pe for ps, pe, _ in host[parent]), child
    steps = sorted((s, st["step_num"]) for s, _, st in host["service.batch"])
    assert [n for _, n in steps] == [
        e["args"]["step"] for e in sorted(_xs(spans, "service.batch"), key=lambda e: e["ts"])
    ]
