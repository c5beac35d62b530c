"""The readers of the spans and counters inside the scheduler round and
the engine, on synthetic contexts."""

import pytest

from chipbench import registry
from chipbench.harness import Context


def span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


# two reactions, µs: each places one job (one candidate pass, one run on
# the node model) and plans it in one B = 1 pass
SPANS = [
    span("service.batch", 0, 10_000, step=0),
    span("fleet.place", 100, 9_000),
    span("engine.plan_many", 200, 4_000, batch=1),
    span("engine.sweep", 300, 2_500, batch=1, g=352),
    span("engine.finish_plans", 3_000, 500, batch=1),
    span("fleet.candidates", 4_500, 1_000, n_nodes=1),
    span("fleet.run_on", 5_600, 3_000, cores=8, f_ghz=2.2),
    span("service.batch", 20_000, 12_000, step=1),
    span("fleet.place", 20_100, 11_000),
    span("engine.plan_many", 20_200, 4_000, batch=1),
    span("engine.sweep", 20_300, 2_700, batch=1, g=352),
    span("engine.finish_plans", 23_100, 300, batch=1),
    span("fleet.candidates", 24_500, 2_000, n_nodes=1),
    span("fleet.candidates", 26_600, 1_000, n_nodes=1),
    span("fleet.run_on", 27_700, 3_400, cores=16, f_ghz=1.8),
]
COUNTERS = {
    "engine.h2d_bytes": 2 * 3172,
    "engine.d2h_bytes": 2 * 4,
    "fleet.capacity_rows_scanned": 7,
    "service.batches": 2,
    "jax.compiles": 0,
}


def ctx(spans=SPANS, counters=COUNTERS, **kw):
    return Context(spans=spans, counters={"counters": dict(counters)}, **kw)


@pytest.mark.parametrize("name, want", [
    ("fleet.run_on_ms", (3.0 + 3.4) / 2),
    ("fleet.candidates_ms", (1.0 + 2.0 + 1.0) / 2),
    ("engine.sweep_ms_per_job", (2.5 + 2.7) / 2),
    ("engine.finish_ms_per_job", (0.5 + 0.3) / 2),
    ("engine.transfer_bytes_per_job", 3176.0),
    ("fleet.rows_scanned_per_reaction", 3.5),
    ("engine.compiles_in_window", 0),
])
def test_reader_on_a_synthetic_context(name, want):
    assert registry.reader(name)(ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "fleet.run_on_ms",
    "fleet.candidates_ms",
    "engine.sweep_ms_per_job",
    "engine.finish_ms_per_job",
    "engine.transfer_bytes_per_job",
    "fleet.rows_scanned_per_reaction",
    "engine.compiles_in_window",
])
def test_reader_reads_nothing_from_a_program_without_the_hooks(name):
    """A program with none of these spans or counters (the parent of the
    change that added them): the reader returns None and does not raise."""
    old = [s for s in SPANS if s["name"] in ("service.batch", "fleet.place", "engine.plan_many")]
    assert registry.reader(name)(ctx(old, {"service.batches": 2})) is None


def test_reaction_p95_reads_the_window_s_reactions():
    """The window's reactions as the harness times them, pop to commit:
    numpy's 95th percentile of their wall times in ms; nothing without
    them."""
    from chipbench.harness import Reaction

    reactions = [Reaction(t0=float(i), t1=float(i) + ms / 1e3, placed=1)
                 for i, ms in enumerate(range(1, 21))]
    read = registry.reader("service.reaction_p95_ms")
    assert read(ctx(reactions=reactions)) == pytest.approx(19.05)
    assert read(ctx(reactions=[])) is None
    assert read(ctx()) is None


def test_every_registered_metric_has_a_reader():
    bench = registry.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(registry.reader(m["name"]))
