"""The fused planning-grid sweep (PR 7): kernel-level parity of the
Pallas argmin / frontier kernels against the jnp oracles, engine-level
parity of the fused ``plan_many``/``pareto_many`` paths against the exact
per-workload pipeline, and the compile-once memoization contract.

The load-bearing invariants:

* ``plan_argmin`` breaks ties to the FIRST flat index (``np.argmin``
  semantics) and returns *something* for an all-masked row (callers
  detect emptiness host-side) — both exercised explicitly, because a
  reduction reorder would silently change chosen configs.
* The fused engine paths are BITWISE identical to the exact ones on
  every ``EnergyPlan`` field / frontier point, including the
  infeasible-workload fallback.
* Two same-geometry batched calls trace each compiled grid callable at
  most once (``engine.TRACE_COUNTS``) — the 10k-job rounds depend on it.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES
from repro.core import engine as engine_mod
from repro.core.engine import (
    TIME_FLOOR,
    Constraints,
    EnergyPlan,
    PlanningEngine,
    RooflineTerms,
    Workload,
    pareto_frontier,
)
from repro.kernels import ops, ref
from repro.kernels.plan_grid import pareto_mask_pallas, plan_argmin_pallas

RNG = np.random.default_rng(7)

TERMS_A = RooflineTerms(
    compute_s=0.02, memory_s=0.008, collective_s=0.004, source="synthetic"
)
TERMS_B = RooflineTerms(
    compute_s=0.001, memory_s=0.05, collective_s=0.002, source="synthetic"
)


def _random_sweep(b, g, seed, tie_every=0, mask_p=0.8):
    rng = np.random.default_rng(seed)
    t = rng.uniform(1e-3, 2.0, (b, g)).astype(np.float32)
    w = rng.uniform(50.0, 5000.0, (1, g)).astype(np.float32)
    k = rng.choice([0.0, 1.0, 2.0], b).astype(np.float32)
    mask = (rng.random((b, g)) < mask_p).astype(np.float32)
    if tie_every:
        # force exact metric ties: duplicate whole columns
        t[:, ::tie_every] = t[:, 1::tie_every]
        w[:, ::tie_every] = w[:, 1::tie_every]
        mask[:, ::tie_every] = mask[:, 1::tie_every]
    return t, w, k, mask


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,g", [(1, 7), (8, 60), (13, 128), (40, 130)])
def test_plan_argmin_interpret_matches_ref(b, g):
    t, w, k, mask = _random_sweep(b, g, seed=b * 1000 + g)
    got = plan_argmin_pallas(
        jnp.asarray(t), jnp.asarray(w), jnp.asarray(k), jnp.asarray(mask),
        time_floor=TIME_FLOOR, interpret=True,
    )
    want = ref.plan_argmin_ref(
        jnp.asarray(t), jnp.asarray(w), jnp.asarray(k), jnp.asarray(mask),
        time_floor=TIME_FLOOR,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_plan_argmin_breaks_ties_to_first_index():
    # columns 0/1, 2/3, ... are exact duplicates: the winner must be the
    # EVEN (first) member of its pair, whichever pair wins
    t, w, k, mask = _random_sweep(6, 64, seed=3, tie_every=2, mask_p=1.0)
    for impl in ("ref", "pallas_interpret"):
        idx = np.asarray(
            ops.plan_argmin(
                jnp.asarray(t), jnp.asarray(w), jnp.asarray(k),
                jnp.asarray(mask), time_floor=TIME_FLOOR, impl=impl,
            )
        )
        assert (idx % 2 == 0).all(), (impl, idx)


def test_plan_argmin_all_masked_row_is_benign():
    t, w, k, mask = _random_sweep(4, 32, seed=9)
    mask[2] = 0.0  # empty row: any in-range index is fine, host handles it
    for impl in ("ref", "pallas_interpret"):
        idx = np.asarray(
            ops.plan_argmin(
                jnp.asarray(t), jnp.asarray(w), jnp.asarray(k),
                jnp.asarray(mask), time_floor=TIME_FLOOR, impl=impl,
            )
        )
        assert idx.shape == (4,) and (0 <= idx).all() and (idx < 32).all()


@pytest.mark.parametrize(
    "b,g,empty_row,tie_every",
    [
        (1, 12, None, 0),
        (5, 60, None, 0),
        (9, 128, None, 0),
        (16, 66, None, 0),  # tpu_space() width, two 8-row blocks
        (16, 66, None, 2),  # duplicated (t, e) columns: lowest index wins
        (5, 60, 2, 0),  # a row with every point masked keeps nothing
    ],
)
def test_pareto_mask_interpret_matches_ref(b, g, empty_row, tie_every):
    rng = np.random.default_rng(b * 100 + g)
    t = rng.uniform(1e-3, 2.0, (b, g)).astype(np.float32)
    e = rng.uniform(1.0, 500.0, (b, g)).astype(np.float32)
    mask = (rng.random((b, g)) < 0.8).astype(np.float32)
    if tie_every:
        t[:, ::tie_every] = t[:, 1::tie_every]
        e[:, ::tie_every] = e[:, 1::tie_every]
    if empty_row is not None:
        mask[empty_row] = 0.0
    got = pareto_mask_pallas(
        jnp.asarray(t), jnp.asarray(e), jnp.asarray(mask), interpret=True
    )
    want = ref.pareto_mask_ref(jnp.asarray(t), jnp.asarray(e), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if empty_row is not None:
        assert not np.asarray(got)[empty_row].any()


def test_pareto_mask_matches_host_frontier_including_ties():
    """The kernel keep-set == the host lexsort+cummin sweep, on a grid
    with duplicated (t, e) pairs (only the lowest flat index survives)."""
    rng = np.random.default_rng(11)
    t = rng.uniform(1e-3, 1.0, 48).astype(np.float64)
    e = rng.uniform(1.0, 100.0, 48).astype(np.float64)
    t[7], e[7] = t[3], e[3]  # exact duplicate pair
    t[30], e[30] = t[3], e[3]
    host = pareto_frontier(t.reshape(4, 12), e.reshape(4, 12))
    host_flat = sorted(r * 12 + c for r, c in host)
    kept = np.asarray(
        ref.pareto_mask_ref(
            jnp.asarray(t[None], jnp.float32),
            jnp.asarray(e[None], jnp.float32),
            jnp.ones((1, 48)),
        )
    )[0]
    # f32 rounding can merge near-ties the f64 host sweep keeps separate;
    # evaluate the oracle on the exact f32 values the kernel sees instead
    t32, e32 = t.astype(np.float32).astype(np.float64), e.astype(np.float32).astype(np.float64)
    host32 = pareto_frontier(t32.reshape(4, 12), e32.reshape(4, 12))
    assert sorted(np.flatnonzero(kept).tolist()) == sorted(
        r * 12 + c for r, c in host32
    )
    assert 7 not in host_flat and 30 not in host_flat  # dup keeps lowest idx


# ---------------------------------------------------------------------------
# engine: fused vs exact
# ---------------------------------------------------------------------------


def _mixed_workloads():
    cell = SHAPES["train_4k"]
    return [
        Workload("qwen1.5-110b", cell),
        Workload("qwen1.5-110b", cell, objective="edp"),
        Workload("a", terms=TERMS_A, n_steps=500, objective="ed2p"),
        Workload("b", terms=TERMS_B,
                 constraints=Constraints(max_frequency_ghz=0.9, max_cores=128)),
        Workload("a", terms=TERMS_A,
                 constraints=Constraints(max_time_s=1e-9)),  # infeasible
    ]


def test_plan_many_fused_matches_exact_bitwise(engine):
    ws = _mixed_workloads()
    exact = engine.plan_many(ws, fused=False)
    fused = engine.plan_many(ws)
    for a, b in zip(exact, fused):
        for f in dataclasses.fields(EnergyPlan):
            assert getattr(a, f.name) == getattr(b, f.name), f.name


def test_pareto_many_fused_matches_exact_bitwise(engine):
    ws = _mixed_workloads()
    exact = engine.pareto_many(ws, fused=False)
    fused = engine.pareto_many(ws)
    assert exact == fused  # ParetoPoint is a frozen dataclass: field-exact


def test_plan_matches_plan_many_slice(engine):
    ws = _mixed_workloads()[:3]
    batched = engine.plan_many(ws)
    for w, p in zip(ws, batched):
        assert engine.plan(w) == p


def test_fused_engine_flag_and_override():
    pm_engine = PlanningEngine.default(noise=0.01, seed=0, fused=False)
    ws = [Workload("a", terms=TERMS_A), Workload("b", terms=TERMS_B)]
    default_path = pm_engine.plan_many(ws)  # exact (engine default)
    override = pm_engine.plan_many(ws, fused=True)
    assert default_path == override


# ---------------------------------------------------------------------------
# compile-once memoization
# ---------------------------------------------------------------------------


def test_same_geometry_rounds_never_retrace(engine):
    ws = _mixed_workloads()[:4]  # feasible only: keep the exact arm quiet
    engine.plan_many(ws)
    engine.pareto_many(ws)
    before = dict(engine_mod.TRACE_COUNTS)
    engine.plan_many(ws)
    engine.plan_many(list(ws))  # fresh list, same geometry
    engine.pareto_many(ws)
    assert engine_mod.TRACE_COUNTS == before, (before, engine_mod.TRACE_COUNTS)


def test_trace_counts_increment_on_new_geometry():
    eng = PlanningEngine.default(noise=0.01, seed=0)
    # the callable cache is process-wide: pick a batch size no prior test
    # (or fixture) has planned at, so the geometry is genuinely new
    used = {
        key[1][0]
        for key in engine_mod._GRID_CALLABLE_CACHE
        if key[0] == "plan_argmin"
    }
    b = next(n for n in range(3, 200) if n not in used)
    ws = [Workload("a", terms=TERMS_A, n_steps=i + 1) for i in range(b)]
    before = dict(engine_mod.TRACE_COUNTS)
    eng.plan_many(ws)
    assert engine_mod.TRACE_COUNTS["plan_argmin"] == before["plan_argmin"] + 1
