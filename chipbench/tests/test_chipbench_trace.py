"""The trace reduction on synthetic events with known overlaps and gaps."""

from chipbench import trace_reduce as tr

# device ops, ns: two overlapping kernels, a gap, a third op past the window
OPS = [
    (100, 300, "fusion.1"),
    (200, 400, "pareto_mask_kernel"),
    (600, 700, "pareto_mask_kernel"),
    (650, 680, "copy.2"),
    (950, 1200, "rbf_gram_kernel"),
]
LO, HI = 0, 1000


def test_merge_joins_overlaps():
    assert tr.merge(OPS) == [(100, 400), (600, 700), (950, 1200)]


def test_busy_is_the_union_clipped_to_the_window():
    assert tr.busy_ns(OPS, LO, HI) == 300 + 100 + 50


def test_gaps_are_the_complement():
    assert tr.gaps(OPS, LO, HI) == [(0, 100), (400, 600), (700, 950)]
    assert sum(e - s for s, e in tr.gaps(OPS, LO, HI)) + tr.busy_ns(OPS, LO, HI) == HI - LO


def test_kernel_time_sums_matching_ops_inside_the_window():
    assert tr.kernel_ns(OPS, "pareto_mask", LO, HI) == 200 + 100
    assert tr.kernel_ns(OPS, "rbf_gram", LO, HI) == 50
    assert tr.kernel_ns(OPS, "plan_argmin", LO, HI) == 0


def test_top_ops_ranks_by_total_time():
    top = tr.top_ops(OPS, LO, HI, k=2)
    assert top == [["pareto_mask_kernel", 300e-9], ["fusion.1", 200e-9]]


def test_gaps_go_to_the_innermost_open_span():
    spans = [
        (0, 1000, "service.batch"),
        (50, 500, "fleet.place"),
        (380, 480, "fleet.negotiate"),
    ]
    got = dict(map(tuple, tr.attribute_gaps(tr.gaps(OPS, LO, HI), spans)))
    # (0,100) mid 50 -> fleet.place; (400,600) mid 500 -> fleet.place (ends at 500);
    # (700,950) mid 825 -> service.batch
    assert got == {"fleet.place": (100 + 200) / 1e9, "service.batch": 250 / 1e9}


def test_gap_outside_every_span_is_named_outside():
    got = tr.attribute_gaps([(10, 20)], [(30, 40, "a")], outside="service.bus")
    assert got == [["service.bus", 10 / 1e9]]


def test_span_index_skips_closed_siblings():
    idx = tr.SpanIndex([(0, 100, "root"), (10, 20, "a"), (30, 40, "b"), (50, 60, "c")])
    assert idx.innermost(55) == "c"
    assert idx.innermost(45) == "root"
    assert idx.innermost(150) is None
