"""scripts/span_split.py: the arithmetic that reads a traced run's spans
on the profiler's clock, on synthetic events, and its probe of the node
model's power evaluation on the CPU."""

from __future__ import annotations

import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    path = os.path.join(REPO, "scripts", "span_split.py")
    spec = importlib.util.spec_from_file_location("span_split", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


span_split = _load()


def span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


# two reactions, µs; the recorder's window event sat at 0 µs and its
# annotation opened at LO ns on the profiler's clock
SPANS = [
    span("service.batch", 0, 10_000, step=0),
    span("fleet.place", 100, 9_000),
    span("fleet.run_on", 5_600, 3_000, cores=8, f_ghz=2.2),
    span("service.batch", 20_000, 12_000, step=1),
    span("fleet.place", 20_100, 11_000),
    span("fleet.run_on", 27_700, 3_400, cores=16, f_ghz=1.8),
    span("jax.compile", 20_500, 100),
]
LO = 1_000_000


def to_ns(ts_us):
    return LO + ts_us * 1e3


def test_clock_skew_is_the_widest_start_gap():
    host = {
        "service.batch": [(to_ns(20_000) + 4_000, 0), (to_ns(0) - 1_500, 0)],
        "fleet.run_on": [(to_ns(5_600) + 250, 0), (to_ns(27_700) - 9_000, 0)],
        "not.a.program.span": [(0, 10)],
    }
    # pairs in start order: batch 1.5 µs and 4 µs, run_on 0.25 µs and 9 µs
    assert span_split.clock_skew_us(SPANS, host, to_ns) == pytest.approx(9.0)
    assert span_split.start_offsets_us(SPANS, host, to_ns, "service.batch") == (
        pytest.approx([-1.5, 4.0]))


def test_clock_skew_without_copies_is_none():
    assert span_split.clock_skew_us(SPANS, {"chipbench.window": [(0, 1)]}, to_ns) is None


def test_device_ops_go_to_the_innermost_open_program_span():
    host = {
        "service.batch": [(0, 1000)],
        "fleet.place": [(100, 900)],
        "fleet.run_on": [(500, 800)],
        "engine.sweep": [(150, 300)],
        "PjitFunction(fn)": [(160, 170)],  # the runtime's own: not a program span
    }
    names = ["service.batch", "fleet.place", "fleet.run_on", "engine.sweep"]
    spans = span_split.program_intervals(host, names)
    ops = [
        (165, 166, "jit(f) | %copy.1"),  # inside the sweep (and the runtime's event)
        (200, 250, "jit(f) | %plan_argmin"),
        (600, 610, "%reduce"),  # the node model's run
        (620, 630, "%reshape"),
        (700, 710, "%reduce"),
        (850, 860, "%copy.1"),  # fleet.place, after the run
        (1200, 1210, "%copy.1"),  # between reactions
    ]
    got = span_split.ops_by_span(ops, spans, reactions=2)
    assert got == [
        ["fleet.run_on", 1.5, [["%reduce", 1.0], ["%reshape", 0.5]]],
        ["engine.sweep", 1.0, [["%copy.1", 0.5], ["%plan_argmin", 0.5]]],
        [span_split.OUTSIDE, 0.5, [["%copy.1", 0.5]]],
        ["fleet.place", 0.5, [["%copy.1", 0.5]]],
    ]


@pytest.mark.parametrize("offsets, want", [
    ([], None),
    ([5.0], [5.0, 5.0, 5.0, 5.0]),
    ([float(i) for i in range(20)], [0.5, 18.5, 0.0, 19.0]),
])
def test_tenth_medians(offsets, want):
    assert span_split.tenth_medians(offsets) == want


def test_split_of_a_synthetic_window():
    window = (to_ns(0), to_ns(40_000))
    host = {
        "chipbench.window": [window],
        "service.batch": [(to_ns(0) + 2_000, to_ns(10_000)), (to_ns(20_000) + 2_000, to_ns(32_000))],
        "fleet.place": [(to_ns(100), to_ns(9_100)), (to_ns(20_100), to_ns(31_100))],
        "fleet.run_on": [(to_ns(5_600), to_ns(8_600)), (to_ns(27_700), to_ns(31_100))],
    }
    ops = [(to_ns(6_000), to_ns(6_001), "%reduce"), (to_ns(50_000), to_ns(50_001), "%late")]
    out = span_split.split(SPANS, 0.0, {"/device:TPU:0": ops}, host, reactions=2)
    assert out["span_ms"]["fleet.run_on"] == pytest.approx((3.0 + 3.4) / 2)
    assert out["span_copies"]["service.batch"] == [2, 2]
    assert out["span_copies"]["jax.compile"] == [1, 0]
    assert out["span_clock_skew_us"] == pytest.approx(2.0)
    assert out["device_ops_per_reaction"] == 0.5  # the late op lies outside the window
    assert out["device_ops_by_span"] == [["fleet.run_on", 0.5, [["%reduce", 0.5]]]]


def test_probe_of_the_power_model_runs_on_the_cpu():
    got = span_split.probe(20)
    assert got["calls"] == 20
    assert got["eager_us_per_call"] > 0 and got["python_us_per_call"] > 0
    assert got["host_us_per_call"] > 0
    assert got["host_vs_eager"] == {"points": 352, "differ": 0, "first": []}
    assert isinstance(got["device_events_per_call"], dict)
