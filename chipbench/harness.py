"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, one JSON line.

The system under test is the fleet scheduler service
(``repro.fleet.service.SchedulerService`` over a ``FleetScheduler``),
replayed over a job trace in a closed loop: the next event batch is
popped only after the previous reaction has committed. The harness times
each reaction from its own hooks on the service instance (``pop_batch``
to ``_commit``) and ends the window at the first reaction boundary after
``--seconds``, with the service's own kill switch.

Set-up builds the world (node pool, engine, scheduler, service, trace)
from the configuration, the mix and the seed, and replays as many
reactions as the mix's generator asks for (``warm_reactions``): each
family is characterized when it first arrives, as in service, and every
program the window runs is compiled (or loaded from the persistent
cache) before it opens. Compiles and cache loads inside the window are
counted, never hidden.

What a configuration may say (every key but ``pool`` optional; absent,
the scheduler's and the service's defaults):

* ``pool``: ``{"nodes": n}``, ``n`` nodes cycling through
  ``cluster.DEFAULT_SPECS``; or ``{"groups": [{"count": n, "spec":
  {...}}, ...]}``, ``n`` nodes of each ``NodeSpec`` (``name``,
  ``max_cores``, ``freq_table``, ``static_power_skew``,
  ``dynamic_power_skew``, ``speed_skew``, ``cores_per_socket``), CPU
  only. Node ``i`` of the pool draws from ``seed + 101 * i``;
* ``scheduler``: ``{"negotiator": {...}, "migration": {...},
  "lookahead": {...}}``, each null or the keywords of ``Negotiator``
  (beside the pool and the engine's power model), ``MigrationPolicy``
  and ``LookaheadPolicy``;
* ``journal``: true, the service commits a journal after every reaction,
  to a file in a fresh temporary directory removed after the run;
* ``checks``: check files that join the comparison (``registry.checks``).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, NamedTuple, Optional

import numpy as np

from chipbench import correct, registry, trace_reduce

KERNEL_PATTERNS = {
    "pareto_mask": "pareto_mask",
    "plan_argmin": "plan_argmin",
    "rbf_gram": "rbf_gram",
}


class NoChip(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring)
# ---------------------------------------------------------------------------


class Compiles:
    """Backend compiles, persistent-cache loads and the seconds spent in
    either, counted from ``jax.monitoring`` events."""

    def __init__(self):
        self.n_compiles = 0
        self.n_cache_hits = 0
        self.secs = 0.0

    def on_duration(self, name: str, secs: float, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.n_compiles += 1
        if name.startswith("/jax/core/compile/") or name.startswith(
            "/jax/compilation_cache/"
        ):
            self.secs += secs

    def on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.n_cache_hits += 1

    def count(self) -> int:
        return self.n_compiles + self.n_cache_hits

    def install(self) -> "Compiles":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self


# ---------------------------------------------------------------------------
# the world: pool, engine, scheduler, service, trace
# ---------------------------------------------------------------------------


def build_pool(pool: dict, seed: int):
    """The configuration's ``pool``: ``nodes`` cycling through the default
    specs, or ``groups`` of stated specs. Mixed pools need an engine and a
    ``Reference`` per device, which the harness does not build: a group of
    another device than ``"cpu"`` is refused."""
    from repro.fleet.cluster import NodeSpec, make_mixed_pool, make_pool

    if "groups" not in pool:
        return make_pool(int(pool["nodes"]), seed=seed)
    specs = []
    for group in pool["groups"]:
        kw = dict(group["spec"])
        if kw.get("device", "cpu") != "cpu":
            raise ValueError(
                f"pool group {kw.get('name')!r} is a {kw['device']!r} group: mixed pools "
                "need an engine and a Reference per device; only \"cpu\" groups are built"
            )
        if "freq_table" in kw:
            kw["freq_table"] = tuple(float(f) for f in kw["freq_table"])
        specs += [NodeSpec(**kw)] * int(group["count"])
    # one spec per node, CPU only: distinct names (a repeated one gets
    # "-<index>") and node i seeded from seed + 101 * i, as make_pool
    return make_mixed_pool(len(specs), 0, seed=seed, cpu_specs=specs)


class World:
    """Everything one replay needs, built from the configuration, the mix
    and the seed. Two worlds built from the same arguments schedule the
    same trace identically. ``stress`` keeps the stress samples the
    engine's power fit read (the data the power reference fits from).
    ``close`` removes the journal's directory."""

    def __init__(self, cfg: dict, mix: dict, seed: int, root: str = registry.ROOT):
        from repro.fleet.negotiate import Negotiator
        from repro.fleet.scheduler import (
            FleetScheduler, LookaheadPolicy, MigrationPolicy, fleet_engine,
        )
        from repro.fleet.service import SchedulerService

        self.pool = build_pool(cfg["pool"], seed)
        node = self.pool.reference
        sweep = node.stress_grid

        def kept(*args, **kw):
            self.stress = sweep(*args, **kw)
            return self.stress

        node.stress_grid = kept
        try:
            self.engine = fleet_engine(self.pool, seed=seed)
        finally:
            del node.stress_grid
        opts = cfg.get("scheduler") or {}
        unknown = set(opts) - {"negotiator", "migration", "lookahead"}
        if unknown:
            raise ValueError(f"unknown scheduler options {sorted(unknown)}")
        kw = {}
        if opts.get("negotiator") is not None:
            kw["negotiator"] = Negotiator(self.pool, self.engine.power, **opts["negotiator"])
        if opts.get("migration") is not None:
            kw["migration"] = MigrationPolicy(**opts["migration"])
        if opts.get("lookahead") is not None:
            kw["lookahead"] = LookaheadPolicy(**opts["lookahead"])
        self.sched = FleetScheduler(self.pool, self.engine, **kw)
        # removed by close(), or when the world is collected
        self.journal_dir = (
            tempfile.TemporaryDirectory(prefix="chipbench_journal_") if cfg.get("journal") else None
        )
        self.svc = SchedulerService(self.sched, journal=None if self.journal_dir is None else
                                    os.path.join(self.journal_dir.name, "journal.json"))
        self.trace = registry.generator(mix, root)(mix, seed)

    def close(self) -> None:
        if self.journal_dir is not None:
            self.journal_dir.cleanup()


# ---------------------------------------------------------------------------
# the closed-loop replay, timed per reaction
# ---------------------------------------------------------------------------


class Reaction(NamedTuple):
    """One reaction of the window: wall seconds from popping its event
    batch to its commit returning, and the jobs it launched."""

    t0: float
    t1: float
    placed: int


class Replay:
    """Drives ``svc.run`` over the trace and records each reaction of the
    window (``Reaction``). The first ``warm`` reactions are set-up; the
    window opens at the next pop (``on_open``). ``stop(now, start)`` is
    asked after every commit in the window; when it says so, the
    service's kill switch ends the run before the next batch. After each
    commit the trace hands the service its next jobs (the generator's
    ``intake``), outside the timed reaction."""

    def __init__(self, world: World, stop: Callable[[float, float], bool],
                 on_open: Callable[[], None] = lambda: None, warm: int = 0):
        self.world = world
        self.stop = stop
        self.on_open = on_open
        self.warm = warm
        self.n_reactions = 0  # warm-up and window
        self.start: Optional[float] = None
        self.end: Optional[float] = None
        self.reactions: List[Reaction] = []  # window only
        self.exhausted = False
        self._t0: Optional[float] = None

        svc, sched, trace = world.svc, world.sched, world.trace
        pop, commit = svc.bus.pop_batch, svc._commit

        def timed_pop(*args, **kw):
            if self.start is None and self.n_reactions >= self.warm:
                self.on_open()
                self.start = time.perf_counter()
            self._t0 = time.perf_counter()
            return pop(*args, **kw)

        def timed_commit(now_s):
            commit(now_s)
            t1 = time.perf_counter()
            for job in trace.intake(sched, now_s):
                svc.submit(job)
            if self._t0 is None:  # the commit before the first batch
                return
            t0, self._t0 = self._t0, None
            self.n_reactions += 1
            if self.start is None:
                return
            placed = sched.rounds[-1].n_placed if sched.rounds else 0
            self.reactions.append(Reaction(t0, t1, placed))
            if self.stop(t1, self.start):
                self.end = t1
                svc.kill_after_batches = svc.n_batches

        svc.bus.pop_batch = timed_pop
        svc._commit = timed_commit

    def run(self) -> bool:
        """True when the trace ran out before ``stop`` said so: the
        queues emptied, or nothing was left that could wake the service."""
        from repro.fleet.service import ServiceKilled

        try:
            self.world.svc.run(())
        except ServiceKilled:
            return False
        if self.reactions:
            self.end = self.reactions[-1].t1
        self.exhausted = True
        return True


# ---------------------------------------------------------------------------
# what the run produced, recorded for the reference
# ---------------------------------------------------------------------------


class Recorder:
    """Hooks, installed from here, that keep what the timed path produced:
    every engine plan pass (its workloads and plans) and frontier pass
    (its workloads and frontiers), every SVR fit's training set and every
    Gram the fits built on the device, plus the kernel call shapes of the
    window for the rooflines. Appends only: it costs the window list
    appends."""

    def __init__(self, world: World):
        from repro.core import svr

        self.passes: List[tuple] = []  # (workloads, plans, in window)
        self.pareto_passes: List[tuple] = []  # (workloads, frontiers, in window)
        self.fits: Dict[int, tuple] = {}  # id(model) -> (x, y)
        self.grams: List[tuple] = []  # (x, y, gamma, K on device)
        self.calls: Dict[str, List[tuple]] = {k: [] for k in KERNEL_PATTERNS}
        self.in_window = False
        self._patched = []

        eng = world.engine
        plan_many = eng.plan_many
        g = int(np.prod(eng.space.meshes()[0].shape))

        def rec_plan_many(workloads, **kw):
            ws = list(workloads)
            plans = plan_many(ws, **kw)
            self.passes.append((ws, plans, self.in_window))
            if self.in_window and ws:
                self.calls["plan_argmin"].append((len(ws), g))
            return plans

        pareto_many = eng.pareto_many

        def rec_pareto_many(workloads, **kw):
            ws = list(workloads)
            fronts = pareto_many(ws, **kw)
            self.pareto_passes.append((ws, fronts, self.in_window))
            if self.in_window and ws:
                self.calls["pareto_mask"].append((len(ws), g))
            return fronts

        eng.plan_many = rec_plan_many
        eng.pareto_many = rec_pareto_many

        fit_many, gram_batched = svr.fit_many, svr._gram_batched

        def rec_fit_many(sets, **kw):
            sets = list(sets)
            models = fit_many(sets, **kw)
            for (x, y), m in zip(sets, models):
                self.fits[id(m)] = (np.asarray(x), np.asarray(y))
            return models

        def rec_gram_batched(x, y, gamma, impl):
            out = gram_batched(x, y, gamma, impl)
            self.grams.append((x, y, gamma, out))
            if self.in_window:
                self.calls["rbf_gram"].append(
                    (x.shape[0], x.shape[1], y.shape[1], x.shape[2])
                )
            return out

        self._patch(svr, "fit_many", rec_fit_many)
        self._patch(svr, "_gram_batched", rec_gram_batched)

    def _patch(self, module, name, fn):
        self._patched.append((module, name, getattr(module, name)))
        setattr(module, name, fn)

    def restore(self) -> None:
        for module, name, fn in reversed(self._patched):
            setattr(module, name, fn)
        self._patched = []


# ---------------------------------------------------------------------------
# the comparison with the reference (after the window)
# ---------------------------------------------------------------------------


def gram_high(x, y, gamma):
    """The control's Gram: the cross term in three bfloat16 passes (what
    a float32 matmul at ``high`` precision computes), f32 elsewhere."""
    import ml_dtypes

    bf = ml_dtypes.bfloat16
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.float32)
    xh = x.astype(bf).astype(np.float32)
    xl = (x - xh).astype(bf).astype(np.float32)
    yh = y.astype(bf).astype(np.float32)
    yl = (y - yh).astype(bf).astype(np.float32)
    sw = lambda a: np.swapaxes(a, -1, -2)
    xy = xh @ sw(yh) + xh @ sw(yl) + xl @ sw(yh)
    xx = np.sum(x * x, -1)[..., :, None]
    yy = np.sum(y * y, -1)[..., None, :]
    d2 = np.maximum(xx + yy - 2.0 * xy, 0.0)
    return np.exp(-gamma * d2).astype(np.float32)


def _bf16(a: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float64)


class Reference:
    """The float64 reference of one run, built from the data the run
    measured: the power grid from the stress samples, and a step-time
    surface per workload family the window planned, from that family's
    characterization samples. ``control`` builds the same one precision
    below what the configuration states: the power fit from float8
    samples (the program's fit runs its matmuls at the default precision,
    one bfloat16 pass on a TPU), the Gram at ``high``, the energy grid in
    bfloat16."""

    def __init__(self, world: World, rec: Recorder, control: bool = False):
        import ml_dtypes

        from chipbench import reference as ref

        eng = world.engine
        self.F, self.C, P = eng.space.meshes()
        coeffs = ref.fit_power64(
            *world.stress, round_to=ml_dtypes.float8_e4m3fn if control else None
        )
        self.W = ref.power_grid64(coeffs, self.F, self.C, P).ravel()
        grid = np.stack([self.F.ravel(), self.C.ravel()], 1)
        gram = gram_high if control else ref.gram64
        self.T: Dict[object, np.ndarray] = {}  # family key -> surface
        self.program_T: Dict[object, np.ndarray] = {}
        for ws, _, in_window in rec.passes + rec.pareto_passes:
            for w in ws if in_window else ():
                if w.key in self.T:
                    continue
                fit = eng._fits[w.key]
                x, y = rec.fits[id(fit.model)]
                m = ref.fit64(x, y, gram=gram)
                self.T[w.key] = np.maximum(ref.predict64(m, grid, gram=gram), ref.TIME_FLOOR)
                self.program_T[w.key] = np.asarray(fit.T, np.float64).ravel()


def power_reading(world: World, want: "Reference", got: Optional["Reference"] = None) -> float:
    """Widest relative gap of the power grid the engine plans with (or,
    for the control, the control's) against the float64 fit's."""
    have = np.asarray(world.engine._W, np.float64).ravel() if got is None else got.W
    return float(np.max(np.abs(have - want.W) / np.abs(want.W)))


def surface_readings(want: "Reference", got: Optional["Reference"] = None) -> Dict[str, float]:
    """Per planned family, the widest relative gap of its step-time surface
    against the float64 fit; the median over families is compared, the
    widest is reported."""
    from chipbench import reference as ref

    gaps = [
        ref.surface_max_rel(want.program_T[k] if got is None else got.T[k], t)
        for k, t in want.T.items()
    ]
    return {
        "surface_median_rel": float(np.median(gaps)) if gaps else 0.0,
        "surface_max_rel": max(gaps, default=0.0),
    }


def plan_readings(rec: Recorder, want: "Reference", got: Optional["Reference"] = None):
    """The window's plans on the float64 surfaces and power grid: the share
    whose energy exceeds the cheapest feasible grid point's by more than
    ``correct.PLAN_REGRET_TOL``, and the widest such excess (``got``: the
    control's plans instead, the argmin of its own energy grid in
    bfloat16)."""
    from chipbench import reference as ref

    flat_of = {
        (float(f), int(c)): i for i, (f, c) in enumerate(zip(want.F.ravel(), want.C.ravel()))
    }
    keys, chosen, cons = [], [], []
    for ws, plans, in_window in rec.passes:
        if not in_window:
            continue
        for w, p in zip(ws, plans):
            keys.append(w.key)
            chosen.append(flat_of[(p.frequency_ghz, p.chips)])
            c = w.effective_constraints()
            cons.append((None, None, None, None) if c is None else (
                c.max_time_s, c.max_cores, c.min_frequency_ghz, c.max_frequency_ghz))
    if not keys:
        return 0.0, 0.0
    T = np.stack([want.T[k] for k in keys])
    E = want.W[None, :] * T
    mask = ref.constraint_masks(T, want.F, want.C, cons)
    if got is not None:
        Tc = np.stack([got.T[k] for k in keys])
        Ec = _bf16(got.W[None, :] * Tc)
        mc = ref.constraint_masks(Tc, want.F, want.C, cons)
        chosen = np.argmin(np.where(mc, Ec, np.inf), axis=1)
    regret = ref.plan_regret(np.asarray(chosen), E, mask)
    return float(np.mean(regret > correct.PLAN_REGRET_TOL)), float(np.max(regret))


def gram_reading(rec: Recorder, control: bool = False) -> float:
    """Widest gap of any Gram the fits built on the device against float64
    on the same points (K lies in [0, 1])."""
    from chipbench import reference as ref

    gap = 0.0
    for x, y, gamma, K in rec.grams:
        x, y = np.asarray(x), np.asarray(y)
        got = gram_high(x, y, gamma) if control else np.asarray(K)
        gap = max(gap, float(np.max(np.abs(got - ref.gram64(x, y, gamma)))))
    return gap


def service_readings(world: World, replay: Replay) -> Dict[str, int]:
    from chipbench import reference as ref

    sched = world.sched
    completed = [
        (c.placement.job.job_id, c.total_energy_j, c.result.energy_j, c.prior_energy_j)
        for c in sched.completed
    ]
    nodes = [
        (n.name, n.spec.max_cores, [(r.start_s, r.end_s, r.cores) for r in n.reservations])
        for n in sched.pool
    ]
    out = ref.schedule_violations(
        world.trace.submitted,
        completed,
        [c.placement.job.job_id for c in sched._finish_queue],
        [j.job_id for j in sched._pending],
        sched.total_energy_j(),
        nodes,
    )
    out["rounds_missing"] = replay.n_reactions - len(sched.rounds)
    out["window_without_launches"] = int(sum(r.placed for r in replay.reactions) == 0)
    out["trace_exhausted"] = int(replay.exhausted)
    return out


def readings(world: World, rec: Recorder, replay: Replay) -> Dict[str, float]:
    """Every number the comparison decides ``correct`` by, and the widest
    gaps reported beside them."""
    want = Reference(world, rec)
    out = dict(service_readings(world, replay))
    out["power_grid_max_rel"] = power_reading(world, want)
    out["gram_max_abs"] = gram_reading(rec)
    out.update(surface_readings(want))
    out["plans_off_share"], out["plan_regret_max"] = plan_readings(rec, want)
    return out


def check_readings(check_files, world: World, rec: Recorder, replay: Replay,
                   control: bool = False) -> Dict[str, float]:
    """The readings of the configuration's check files: each file's
    ``read``, or with ``control`` its ``control`` where it has one."""
    out = {}
    for c in check_files:
        fn = getattr(c, "control", None) if control else c.read
        if fn is not None:
            out.update(fn(world, rec, replay))
    return out


def control_readings(world: World, rec: Recorder, program: Dict[str, float]):
    """The numbers again with the reference, one precision below what the
    configuration states, in the program's place. Service counts are the
    program's own."""
    want = Reference(world, rec)
    got = Reference(world, rec, control=True)
    out = dict(program)
    out["power_grid_max_rel"] = power_reading(world, want, got)
    out["gram_max_abs"] = gram_reading(rec, control=True)
    out.update(surface_readings(want, got))
    out["plans_off_share"], out["plan_regret_max"] = plan_readings(rec, want, got)
    return out


# ---------------------------------------------------------------------------
# the traced run's reduction
# ---------------------------------------------------------------------------


def reduce_trace(trace_dir: str, spans: List[dict], sync_obs_us: float):
    """Device numbers of the traced window. Host spans (the flight
    recorder's, on ``perf_counter``) are put on the profiler's clock by
    the ``chipbench.window`` annotation, opened at the same instant as
    the recorder's ``chipbench.window`` event."""
    devices, host = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    lo, hi = host["chipbench.window"][0]
    used = {p: ev for p, ev in devices.items() if ev}
    if not used:
        raise RuntimeError("the traced window holds no device operation")
    busy = float(np.mean([trace_reduce.busy_ns(ev, lo, hi) for ev in used.values()]))
    plane = sorted(used)[0]
    events = used[plane]
    kernel_s = {
        k: trace_reduce.kernel_ns(events, pat, lo, hi) / 1e9
        for k, pat in KERNEL_PATTERNS.items()
    }
    to_ns = lambda ts_us: lo + (ts_us - sync_obs_us) * 1e3
    host_spans = [
        (to_ns(s["ts"]), to_ns(s["ts"] + s["dur"]), s["name"])
        for s in spans if s["ph"] == "X"
    ]
    breakdown = {
        "device_ops": trace_reduce.top_ops(events, lo, hi),
        "idle_gaps": trace_reduce.attribute_gaps(
            trace_reduce.gaps(events, lo, hi), host_spans, outside="service.bus"
        )[:10],
    }
    return {
        "busy_s": busy / 1e9,
        "window_s": (hi - lo) / 1e9,
        "kernel_s": kernel_s,
        "breakdown": breakdown,
    }


class Context:
    """What a per-layer metric reader reads (see chipbench/metrics/)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def spans_named(self, name: str) -> List[dict]:
        return [s for s in self.spans if s["name"] == name and s["ph"] == "X"]

    def counter(self, name: str) -> Optional[float]:
        return self.counters.get("counters", {}).get(name)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {d0.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def parse_args(argv):
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, t_start: float, *, require_tpu: bool = True, root: str = registry.ROOT,
        faults: Optional[Callable[[World], None]] = None) -> dict:
    """One run; returns the result object. ``faults`` (tests only) breaks
    the measured world's timed path before its window."""
    import jax
    from repro import obs
    from repro.compile_cache import enable_compile_cache

    bench = registry.load_benchmark(root)
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(bench, cell["config"], root)
    mix = registry.mix(cell["traffic"], root)
    check_files = registry.checks(cfg, root)
    limits = correct.limits(check_files)
    kind = "per_layer" if args.trace else "end_to_end"
    entries = registry.metrics_for(bench, cell["name"], kind)
    readers = registry.readers(entries, root) if args.trace else {}

    enable_compile_cache()
    compiles = Compiles().install()
    device = device_info(int(cell["chips"]), require_tpu)

    t0 = time.perf_counter()
    world = World(cfg, mix, args.seed, root)
    if faults is not None:
        faults(world)
    rec = Recorder(world)
    t1 = time.perf_counter()

    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if args.trace else None
    state = {}

    def on_open():
        state["setup"] = {
            "before_build_s": t0 - t_start,
            "build_s": t1 - t0,
            "warm_replay_s": time.perf_counter() - t1,
            "compiles": compiles.n_compiles,
            "cache_hits": compiles.n_cache_hits,
            "compile_s": compiles.secs,
        }
        state["compiles0"] = compiles.count()
        rec.in_window = True
        if args.trace:
            state["recording"] = obs.recording(capacity=1 << 20)
            state["flight"] = state["recording"].__enter__()
            jax.profiler.start_trace(trace_dir)
            state["annotation"] = jax.profiler.TraceAnnotation("chipbench.window")
            state["annotation"].__enter__()
            obs.event("chipbench.window")

    def stop(now, start):
        done = now - start >= args.seconds
        if done and rec.in_window:
            rec.in_window = False
            state["compiles"] = compiles.count() - state["compiles0"]
            if args.trace:
                state["annotation"].__exit__(None, None, None)
                jax.profiler.stop_trace()
                state["recording"].__exit__(None, None, None)
        return done

    replay = Replay(world, stop, on_open, warm=world.trace.warm_reactions)
    try:
        exhausted = replay.run()
    finally:
        rec.restore()
    if replay.start is None:
        raise RuntimeError("the trace ran out during set-up")
    if exhausted:
        stop(float("inf"), 0.0)  # close the window (and the trace) at the last reaction
    window_s = replay.end - replay.start
    setup_s = replay.start - t_start
    device["memory_peak_bytes"] = memory_peak_bytes()

    lat = np.array([r.t1 - r.t0 for r in replay.reactions]) * 1e3
    placed = sum(r.placed for r in replay.reactions)
    values = {
        "decisions_per_s": placed / window_s,
        "reaction_p50_ms": float(np.percentile(lat, 50)),
        "reaction_p95_ms": float(np.percentile(lat, 95)),
        "setup_s": setup_s,
    }

    metrics = {}
    breakdown = None
    if args.trace:
        flight = state["flight"]
        spans = flight.trace.events()
        sync = [s["ts"] for s in spans if s["name"] == "chipbench.window"][0]
        dev = reduce_trace(trace_dir, spans, sync)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = dev["busy_s"]
        device["window_s"] = dev["window_s"]
        ctx = Context(
            spans=spans,
            counters=flight.metrics.snapshot(),
            rounds=world.sched.rounds[replay.warm:],
            reactions=replay.reactions,
            calls=rec.calls,
            kernel_s=dev["kernel_s"],
            busy_s=dev["busy_s"],
            window_s=dev["window_s"],
            device_kind=device["kind"],
        )
        for m in entries:
            v = readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = dev["breakdown"]
    else:
        for m in entries:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    got = readings(world, rec, replay)
    got.update(check_readings(check_files, world, rec, replay))
    world.close()
    ok, checks = correct.judge(got, limits)
    attempted = len(replay.reactions)
    failed = sum(1 for r in replay.reactions if world.trace.failed(r))
    out = {
        "correct": ok,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["info"] = {
        "window_s": window_s,
        "reactions": attempted,
        "jobs_launched": placed,
        "window_compiles": state.get("compiles"),
        "setup": state["setup"],
        "surface_max_rel": got["surface_max_rel"],
        "plan_regret_max": got["plan_regret_max"],
        "values": values,
    }
    out["checks"] = checks
    return out


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    try:
        out = run(args, t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
