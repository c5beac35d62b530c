"""The harness end to end at a tiny size on the CPU: cells found by name,
a sound run judged correct, the control and each fault the cells can have
judged not correct, and no result without a TPU. A deployment of a new
shape (a pool of stated node groups, the negotiator, the journal, its own
generator and check file) enters as files in the test's root alone."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import correct, faults, harness, registry

ROOT = registry.ROOT

# cells, configurations, mixes, a generator, a check and a metric that exist
# only in the test's root: the harness finds them by name, with no edit to
# any file of the benchmark
TINY_MIX = {"apps": ["blackscholes", "raytrace"], "input_sizes": [1.0, 3.0], "n_jobs": 4096}
TINY_METRIC = '''
def read(ctx):
    return float(len(ctx.rounds))
'''
# two node types: one characterization host first, then two of a smaller,
# slower, hungrier one; 32 cores in all, too few for 8 jobs at once, so
# jobs wait and rounds plan several
NODE_A = {"name": "xeon-a", "max_cores": 16, "freq_table": [1.2, 1.4, 1.6, 1.8, 2.0, 2.2],
          "cores_per_socket": 8}
NODE_B = {"name": "xeon-b", "max_cores": 8, "freq_table": [1.2, 1.4, 1.6, 1.8],
          "static_power_skew": 1.1, "dynamic_power_skew": 0.95, "speed_skew": 1.05,
          "cores_per_socket": 4}
TINY_FLEET = {
    "pool": {"groups": [{"count": 1, "spec": NODE_A}, {"count": 2, "spec": NODE_B}]},
    "scheduler": {"negotiator": {}, "migration": None, "lookahead": None},
    "journal": True,
    "checks": ["tiny_frontiers"],
}
TINY_QUEUE_MIX = dict(TINY_MIX, generator="tiny_queue", in_flight=8, n_jobs=100_000)
TINY_QUEUE = '''
"""Keeps ``in_flight`` jobs submitted and not finished: each commit tops
the queue up with jobs that arrive at once."""
import math

import numpy as np


class Queue:
    def __init__(self, mix, seed):
        pairs = [(a, float(s)) for s in mix["input_sizes"] for a in mix["apps"]]
        rng = np.random.default_rng(seed)
        self.specs = [pairs[k] for k in rng.integers(len(pairs), size=int(mix["n_jobs"]))]
        self.depth = int(mix["in_flight"])
        self.submitted = []
        self.warm_reactions = 8 * len(pairs)  # rounds of each batch size the queue makes

    def intake(self, sched, now_s):
        from repro.fleet.scheduler import Job

        room = self.depth - (len(self.submitted) - len(sched.completed))
        jobs = []
        for i in range(len(self.submitted), min(len(self.submitted) + room, len(self.specs))):
            app, size = self.specs[i]
            jobs.append(Job(job_id=i, app=app, input_size=size, deadline_s=math.inf, arrival_s=now_s))
        self.submitted += [j.job_id for j in jobs]
        return jobs

    def failed(self, reaction):
        # taking in a completion while every node is full launches nothing,
        # and is no failure
        return False


def make(mix, seed):
    return Queue(mix, seed)
'''
TINY_CHECK = '''
"""Every frontier the window planned keeps the engine's ordering contract."""

LIMITS = {
    # fastest point first: step time strictly rising and energy strictly
    # falling along each frontier; exact, so no frontier may break it
    "frontiers_out_of_order": 0,
}


def read(world, rec, replay):
    bad = 0
    for _, fronts, in_window in rec.pareto_passes:
        for fr in fronts if in_window else ():
            t = [p.step_time_s for p in fr]
            e = [p.energy_per_step_j for p in fr]
            bad += t != sorted(set(t)) or e != sorted(set(e), reverse=True)
    return {"frontiers_out_of_order": bad}
'''


def build_tiny_root(root) -> str:
    """A checkout's benchmark files in ``root``, plus the tiny cells."""
    root = str(root)
    bench = registry.load_benchmark()
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    base = registry.config(bench, "parsec_node")
    fleet = dict(base, **TINY_FLEET)
    fleet["guarantees"] = dict(base["guarantees"], durability=(
        "a journal commit after every reaction: a restarted service resumes from the last "
        "committed reaction's state"))
    files = {
        "configs/tiny.json": json.dumps(dict(base, pool={"nodes": 2})),
        "configs/tiny_fleet.json": json.dumps(fleet),
        "traffic/tiny_sequence.json": json.dumps(TINY_MIX),
        "traffic/tiny_queue.json": json.dumps(TINY_QUEUE_MIX),
        "generators/tiny_queue.py": TINY_QUEUE,
        "checks/tiny_frontiers.py": TINY_CHECK,
        "metrics/tiny.rounds.py": TINY_METRIC,
    }
    for name, text in files.items():
        path = os.path.join(root, "chipbench", name)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    for name in ("tiny", "tiny_fleet"):
        bench["configs"].append({"name": name, "source": "test", "file": f"chipbench/configs/{name}.json",
                                 "reduced": ["pool"], "why": "test"})
    bench["workloads"].append({"name": "tiny.sequence", "config": "tiny", "traffic": "tiny_sequence",
                               "chips": 1, "why": "test"})
    bench["workloads"].append({"name": "tiny_fleet.queue", "config": "tiny_fleet",
                               "traffic": "tiny_queue", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "tiny.rounds", "unit": "count", "better": "lower",
                               "source": "program_counter", "layer": "service",
                               "moves": "reaction_p50_ms", "workloads": ["tiny.sequence"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return build_tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def no_cache(monkeypatch):
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache", lambda: "")


def tiny_run(root, seed=5, faults=None, trace=0, workload="tiny.sequence"):
    args = harness.parse_args(
        ["--workload", workload, "--seed", str(seed), "--seconds", "1.5", "--trace", str(trace)]
    )
    return harness.run(args, time.perf_counter(), require_tpu=False, root=root, faults=faults)


def test_every_cell_resolves_its_parts(tiny_root):
    """Every cell of the benchmark, and of the test's root (a pool of
    groups, a generator mix, a check file), finds each of its parts."""
    for root in (ROOT, tiny_root):
        bench = registry.load_benchmark(root)
        for w in bench["workloads"]:
            cfg = registry.config(bench, w["config"], root)
            pool = cfg["pool"]
            if "groups" in pool:
                assert pool["groups"] and all(g["count"] > 0 for g in pool["groups"])
            else:
                assert pool["nodes"] > 0
            check_files = registry.checks(cfg, root)
            assert set(correct.limits(check_files)) >= set(correct.LIMITS)
            assert all(callable(c.read) and c.LIMITS for c in check_files)
            mix = registry.mix(w["traffic"], root)
            assert callable(registry.generator(mix, root))
            for m in registry.metrics_for(bench, w["name"], "per_layer"):
                assert callable(registry.reader(m["name"], root))


def test_an_added_cell_is_found_by_name(tiny_root):
    bench = registry.load_benchmark(tiny_root)
    cell = registry.cell(bench, "tiny.sequence")
    assert registry.config(bench, cell["config"], tiny_root)["pool"]["nodes"] == 2
    assert registry.mix(cell["traffic"], tiny_root) == TINY_MIX
    names = [m["name"] for m in registry.metrics_for(bench, "tiny.sequence", "per_layer")]
    assert "tiny.rounds" in names
    assert "tiny.rounds" not in [
        m["name"] for m in registry.metrics_for(bench, "parsec_node.sequential", "per_layer")
    ]


def test_sound_run_is_correct_and_the_control_is_not(tiny_root, no_cache, monkeypatch):
    seen = {}
    orig = harness.readings

    def keep(world, rec, replay):
        got = orig(world, rec, replay)
        seen["control"] = harness.control_readings(world, rec, got)
        return got

    monkeypatch.setattr(harness, "readings", keep)
    out = tiny_run(tiny_root)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in registry.load_benchmark(tiny_root)["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    ok, checks = correct.judge(seen["control"])
    assert not ok
    for name in ("gram_max_abs", "surface_median_rel"):
        assert not correct.passes(checks[name]["value"], checks[name]["limit"]), name


@pytest.mark.parametrize(
    "fault, check",
    [
        ("answer_altered", "plans_off_share"),
        ("half_the_batch", "surface_median_rel"),
        ("state_unchanged", "rounds_missing"),
        ("gram_in_bf16", "gram_max_abs"),
        ("predict_in_bf16", "surface_median_rel"),
    ],
    ids=["answer_altered", "half_the_batch", "state_unchanged", "gram_in_bf16", "predict_in_bf16"],
)
def test_a_broken_timed_path_is_not_correct(tiny_root, no_cache, monkeypatch, fault, check):
    from repro.core import svr

    for name in faults.PATCHED:
        monkeypatch.setattr(svr, name, getattr(svr, name))
    out = tiny_run(tiny_root, seed=6, faults=faults.FAULTS[fault])
    assert not out["correct"]
    assert not correct.passes(out["checks"][check]["value"], out["checks"][check]["limit"])


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "parsec_node.sequential",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "parsec_node.sequential",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# sha256 of json.dumps of the first 2,000 (app, input size) specs of
# parsec_node's `sequential` mix, per seed, as the generator made them
# when it lived in chipbench/traffic.py
SEQUENTIAL_DIGESTS = {
    0: "7be30bd720b239de8694a63b60e8c0c6e67ca6ec63c75f6c381fe13175a1e226",
    1: "adb5dcaecc8fb254fa8d2e62e3029c2c7c2493e623877cce97af79399bb39126",
    2: "e133c49ce15b5af7a2daf7a6b33c1d97dd238b39b9751885e1efe528d8a4171a",
    3: "57aef4b76e9d66aaddf9794f59af7d18018d09fa9f14ce11b774f517c26cbfb5",
}


@pytest.mark.parametrize("seed", sorted(SEQUENTIAL_DIGESTS))
def test_parsec_node_replays_the_same_job_sequence(seed):
    mix = registry.mix("sequential")
    trace = registry.generator(mix)(mix, seed)
    digest = hashlib.sha256(json.dumps(trace.specs[:2000]).encode()).hexdigest()
    assert digest == SEQUENTIAL_DIGESTS[seed]
    assert trace.warm_reactions == 2 * 20


def test_a_pool_is_built_from_groups(tiny_root):
    from repro.fleet.cluster import NodeSpec

    cfg = registry.config(registry.load_benchmark(tiny_root), "tiny_fleet", tiny_root)
    pool = harness.build_pool(cfg["pool"], seed=7)
    assert [n.name for n in pool] == ["xeon-a", "xeon-b", "xeon-b-2"]
    want = [NODE_A, NODE_B, NODE_B]
    for i, (node, spec) in enumerate(zip(pool, want)):
        kw = dict(spec, freq_table=tuple(spec["freq_table"]), name=node.name)
        assert node.spec == NodeSpec(**kw)
        fresh = np.random.default_rng(7 + 101 * i).bit_generator.state
        assert node.node.rng.bit_generator.state == fresh
    with pytest.raises(ValueError, match="mixed pools"):
        harness.build_pool({"groups": [{"count": 1, "spec": dict(NODE_A, device="tpu")}]}, seed=7)


@pytest.fixture(scope="module")
def fleet_run(tiny_root):
    """One sound run of the fleet cell: two node groups, the negotiator,
    the journal, 8 jobs in flight and a check file of its own."""
    import repro.compile_cache

    seen = {}

    class Kept(harness.Recorder):
        def __init__(self, world):
            super().__init__(world)
            seen["rec"] = self

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(repro.compile_cache, "enable_compile_cache", lambda: "")
        mp.setattr(harness, "Recorder", Kept)
        seen["out"] = tiny_run(tiny_root, seed=8, workload="tiny_fleet.queue",
                               faults=lambda world: seen.setdefault("world", world))
    return seen


def test_a_generator_found_by_name_plans_rounds_of_many_jobs(fleet_run):
    out = fleet_run["out"]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["frontiers_out_of_order"] == {"value": 0, "limit": 0}
    assert max(b for b, _ in fleet_run["rec"].calls["pareto_mask"]) > 1


def test_the_negotiator_s_frontier_passes_are_recorded(fleet_run):
    world, rec = fleet_run["world"], fleet_run["rec"]
    assert world.sched.negotiator is not None
    window = [(ws, fronts) for ws, fronts, in_window in rec.pareto_passes if in_window]
    assert window and all(len(ws) == len(fronts) for ws, fronts in window)
    g = world.engine._F.size
    assert rec.calls["pareto_mask"] == [(len(ws), g) for ws, _ in window]
    assert not any(in_window for _, _, in_window in rec.passes)  # negotiated rounds plan no argmin


def test_the_journal_commits_and_its_directory_goes(fleet_run):
    svc = fleet_run["world"].svc
    assert svc.journal is not None and svc.journal.commits >= fleet_run["out"]["attempted"]
    assert not os.path.exists(os.path.dirname(svc.journal.path))


def frontiers_reversed(world):
    """Every frontier handed out slowest point first, where the engine
    makes it."""
    eng = world.engine
    pareto_many = eng.pareto_many
    eng.pareto_many = lambda ws, **kw: [fr[::-1] for fr in pareto_many(ws, **kw)]


def test_a_check_file_judges_the_fault_planted_for_it(tiny_root, no_cache):
    out = tiny_run(tiny_root, seed=9, workload="tiny_fleet.queue", faults=frontiers_reversed)
    assert not out["correct"]
    check = out["checks"]["frontiers_out_of_order"]
    assert not correct.passes(check["value"], check["limit"])


def test_check_files_add_limits_and_never_move_one():
    class Check:
        __name__ = "check"
        LIMITS = {"own_gap": 0.5}

        @staticmethod
        def read(world, rec, replay):
            return {"own_gap": 0.25}

    limits = correct.limits([Check])
    assert limits == dict(correct.LIMITS, own_gap=0.5)
    ok, checks = correct.judge({}, limits)
    assert not ok and checks["own_gap"] == {"value": "missing", "limit": 0.5}
    assert harness.check_readings([Check], None, None, None) == {"own_gap": 0.25}
    assert harness.check_readings([Check], None, None, None, control=True) == {}
    Check.LIMITS = {"gram_max_abs": 1.0}
    with pytest.raises(ValueError, match="already set"):
        correct.limits([Check])
