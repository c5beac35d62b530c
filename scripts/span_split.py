"""Where a traced reaction's time and device ops go, on the profiler's clock.

    python3 scripts/span_split.py --workload <cell> --seed <n> --seconds <s>
        [--python-tracer 0|1] [--power-model-span] [--probe <calls>]

Run from the root of a checkout, on the machine with the chip. It makes
the benchmark's traced run (``chipbench/run.py --trace 1``, through the
same harness, unchanged) and reads the program's spans twice from its
profile: as the harness aligns them, from the one ``chipbench.window``
instant, and as the host plane's copies that a recording mirrors into
the profiler (``repro.obs.trace``). It prints the harness's JSON line
with a ``split`` object added:

- ``span_ms``: each span's recorded time per reaction;
- ``span_copies``: per span name, [recorded spans, host-plane copies];
- ``span_clock_skew_us``: the widest gap between a span's host-plane
  start and its start under the one-instant alignment;
- ``batch_offset_us``: the median signed offset (copy less aligned) of
  ``service.batch`` in the first and the last tenth of the window, the
  smallest and the largest: a drifting alignment shows here;
- ``device_ops_per_reaction`` and ``device_ops_by_span``: the device
  ops in the window per reaction, grouped by the innermost host-plane
  copy of a program span open at each op's start, with each group's op
  names;
- ``idle_gaps_by_copies``: the harness's idle-gap attribution, made with
  the host-plane copies in place of the aligned spans.

The harness's profiler session records Python function events, which
slow Python-heavy host code more than the rest; ``--python-tracer 0``
starts it without them. ``--power-model-span`` adds a span, ``diag.power_model``, around
``PowerModel.at`` (the node model's Eq. 7 evaluation on the host inside
each run). ``--probe <calls>`` then makes that many calls of the power
model alone and reports the µs per call of the host evaluation
(``PowerModel.at``), of the eager device evaluation (``PowerModel.__call__``)
outside and inside a profiler session of its own, and of the same formula
in plain Python; from that session, the events per call on every line of
every device plane and the busiest host-plane events; and the points of
the cell's node (every frequency of ``FREQ_GRID`` at 1 to 32 cores) where
the host evaluation and the eager call on this backend differ in any bit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench.trace_reduce import Interval, SpanIndex  # noqa: E402

OUTSIDE = "(no span)"


# ---------------------------------------------------------------------------
# the arithmetic, on the plain shapes chipbench.trace_reduce.load returns
# ---------------------------------------------------------------------------


def aligned_starts(
    spans: Sequence[dict], names: Iterable[str], to_ns: Callable[[float], float]
) -> Dict[str, List[float]]:
    """Per name, the recorder's complete spans' starts on the profiler's
    clock under the one-instant alignment ``to_ns``, in order."""
    names = set(names)
    out: Dict[str, List[float]] = {}
    for s in spans:
        if s["ph"] == "X" and s["name"] in names:
            out.setdefault(s["name"], []).append(to_ns(s["ts"]))
    return {n: sorted(v) for n, v in out.items()}


def start_offsets_us(
    spans: Sequence[dict],
    host: Dict[str, List[Tuple[int, int]]],
    to_ns: Callable[[float], float],
    name: str,
) -> List[float]:
    """Host-plane start less aligned start, µs, for each span of ``name``;
    the k-th recorded span is paired with the k-th copy, in start order."""
    aligned = aligned_starts(spans, [name], to_ns).get(name, [])
    copies = sorted(s for s, _ in host.get(name, ()))
    return [(c - a) / 1e3 for a, c in zip(aligned, copies)]


def clock_skew_us(
    spans: Sequence[dict],
    host: Dict[str, List[Tuple[int, int]]],
    to_ns: Callable[[float], float],
) -> Optional[float]:
    """Widest |host-plane start - aligned start|, µs, over the recorder's
    complete spans that have host-plane copies; None when none has."""
    gaps = [
        abs(d)
        for name in {s["name"] for s in spans if s["ph"] == "X" and s["name"] in host}
        for d in start_offsets_us(spans, host, to_ns, name)
    ]
    return max(gaps) if gaps else None


def program_intervals(
    host: Dict[str, List[Tuple[int, int]]], names: Iterable[str]
) -> List[Interval]:
    """The host-plane copies of the named program spans."""
    return [(s, e, n) for n in set(names) for s, e in host.get(n, ())]


def ops_by_span(ops: Iterable[Interval], spans: Sequence[Interval], reactions: int):
    """Device ops per reaction by the innermost span open at each op's
    start (``OUTSIDE`` where none is), most first, each with its op names
    and their counts per reaction."""
    index = SpanIndex(spans)
    names: Dict[str, collections.Counter] = collections.defaultdict(collections.Counter)
    for s, _, op in ops:
        names[index.innermost(s) or OUTSIDE][op.split(" | ")[-1]] += 1
    rows = [
        [span, sum(c.values()) / reactions,
         [[op, n / reactions] for op, n in sorted(c.items(), key=lambda x: (-x[1], x[0]))]]
        for span, c in names.items()
    ]
    return sorted(rows, key=lambda r: (-r[1], r[0]))


def tenth_medians(offsets: List[float]) -> Optional[List[float]]:
    """[median of the first tenth, median of the last tenth, min, max]."""
    if not offsets:
        return None
    k = max(1, len(offsets) // 10)
    return [statistics.median(offsets[:k]), statistics.median(offsets[-k:]),
            min(offsets), max(offsets)]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def split(spans, sync_us, devices, host, reactions) -> dict:
    from chipbench import trace_reduce

    lo, hi = host["chipbench.window"][0]
    to_ns = lambda ts_us: lo + (ts_us - sync_us) * 1e3  # the harness's alignment
    recorded = collections.Counter(s["name"] for s in spans if s["ph"] == "X")
    # jax.compile spans are recorded after the fact and have no copies
    mirrored = set(recorded) - {"jax.compile"}
    copies = program_intervals(host, mirrored)
    plane = sorted(p for p, ev in devices.items() if ev)[0]
    ops = trace_reduce.clip(devices[plane], lo, hi)
    dur = collections.Counter()
    for s in spans:
        if s["ph"] == "X":
            dur[s["name"]] += s["dur"]
    return {
        "span_ms": {n: dur[n] / 1e3 / reactions for n in sorted(dur)},
        "span_copies": {n: [recorded[n], len(host.get(n, ()))] for n in sorted(recorded)},
        "span_clock_skew_us": clock_skew_us(
            [s for s in spans if s["name"] in mirrored], host, to_ns),
        "batch_offset_us": tenth_medians(
            start_offsets_us(spans, host, to_ns, "service.batch")),
        "device_ops_per_reaction": len(ops) / reactions,
        "device_ops_by_span": ops_by_span(ops, copies, reactions),
        "idle_gaps_by_copies": trace_reduce.attribute_gaps(
            trace_reduce.gaps(devices[plane], lo, hi), copies, outside="service.bus"
        )[:12],
    }


def host_eager_mismatches(model, spec) -> dict:
    """The points of the (f, p) grid of the node ``spec`` describes, every
    frequency of ``FREQ_GRID`` at 1 to 32 cores, where ``model.at`` and the
    eager ``float(model(f, p, s))`` differ in any bit."""
    from repro.core.node_sim import FREQ_GRID, MAX_CORES

    points = [(float(f), p, spec.sockets(p)) for f in FREQ_GRID for p in range(1, MAX_CORES + 1)]
    differ = [[f, p, s, host, eager] for f, p, s in points
              if (host := model.at(f, p, s)).hex() != (eager := float(model(f, p, s))).hex()]
    return {"points": len(points), "differ": len(differ), "first": differ[:10]}


def probe(calls: int) -> dict:
    """Calls of the power model alone, on the host and eager on the
    device, the eager ones under a profiler session too."""
    import jax

    from chipbench import trace_reduce
    from repro.core.power import PowerModel
    from repro.fleet.cluster import DEFAULT_SPECS

    spec = DEFAULT_SPECS[0]  # the cell's node
    model = PowerModel(*spec.truth_coeffs())
    c1, c2, c3, c4 = model.coeffs()
    args = [(1.2 + 0.1 * (i % 11), 1 + i % 32, 1 + (i % 32) // 16) for i in range(calls)]
    for f, p, s in args[:20]:  # compile every eager op before timing
        float(model(f, p, s))
    t0 = time.perf_counter()
    for f, p, s in args:
        float(p * (c1 * f**3 + c2 * f) + c3 + c4 * s)
    python_us = (time.perf_counter() - t0) / calls * 1e6
    t0 = time.perf_counter()
    for f, p, s in args:
        model.at(f, p, s)
    host_us = (time.perf_counter() - t0) / calls * 1e6
    t0 = time.perf_counter()
    for f, p, s in args:
        float(model(f, p, s))
    unprofiled_us = (time.perf_counter() - t0) / calls * 1e6
    trace_dir = tempfile.mkdtemp(prefix="span_split_probe_")
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("probe.window"):
        t0 = time.perf_counter()
        for f, p, s in args:
            float(model(f, p, s))
        eager_us = (time.perf_counter() - t0) / calls * 1e6
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    lo = hi = None
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "probe.window":
                    lo, hi = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
    device_lines: Dict[str, Dict[str, float]] = {}
    host_events: collections.Counter = collections.Counter()
    for plane in pd.planes:
        for line in plane.lines:
            inside = [ev for ev in line.events
                      if lo <= int(ev.start_ns) < hi and ev.name != "probe.window"]
            if plane.name.startswith("/device:"):
                names = collections.Counter(ev.name.split(" = ")[0] for ev in inside)
                device_lines[f"{plane.name} | {line.name}"] = {
                    n: c / calls for n, c in names.most_common(12)}
            elif plane.name.startswith("/host:"):
                host_events.update(ev.name for ev in inside)
    return {
        "calls": calls,
        "host_us_per_call": host_us,
        "eager_us_per_call": unprofiled_us,
        "eager_us_per_call_profiled": eager_us,
        "python_us_per_call": python_us,
        "device_events_per_call": device_lines,
        "host_events_per_call": [[n, c / calls] for n, c in host_events.most_common(15)],
        "host_vs_eager": host_eager_mismatches(model, spec),
    }


def main(argv) -> int:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--power-model-span", action="store_true")
    ap.add_argument("--probe", type=int, default=0)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1,
                    help="0: the profiler records no Python function events")
    a = ap.parse_args(argv)

    from chipbench import harness, trace_reduce
    from repro import obs

    seen = {}
    load, reduce_trace = trace_reduce.load, harness.reduce_trace

    def keep_load(path):
        seen["devices"], seen["host"] = load(path)
        return seen["devices"], seen["host"]

    def keep_reduce(trace_dir, spans, sync_us):
        seen["spans"], seen["sync"] = spans, sync_us
        return reduce_trace(trace_dir, spans, sync_us)

    trace_reduce.load, harness.reduce_trace = keep_load, keep_reduce
    if not a.python_tracer:
        import jax.profiler

        start_trace = jax.profiler.start_trace
        quiet = jax.profiler.ProfileOptions()
        quiet.python_tracer_level = 0

        def start_quiet(log_dir, *args, **kw):
            return start_trace(log_dir, profiler_options=quiet)

        jax.profiler.start_trace = start_quiet
    if a.power_model_span:
        from repro.core.power import PowerModel

        at = PowerModel.at

        def spanned(self, *args, **kw):
            with obs.span("diag.power_model", cat="diag"):
                return at(self, *args, **kw)

        PowerModel.at = spanned

    args = harness.parse_args(["--workload", a.workload, "--seed", str(a.seed),
                               "--seconds", str(a.seconds), "--trace", "1"])
    out = harness.run(args, T_START)
    out["split"] = split(seen["spans"], seen["sync"], seen["devices"], seen["host"],
                         out["info"]["reactions"])
    if a.probe:
        out["split"]["probe"] = probe(a.probe)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
