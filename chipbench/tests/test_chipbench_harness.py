"""The harness end to end at a tiny size on the CPU: cells found by name,
a sound run judged correct, the control and each fault the cells can have
judged not correct, and no result without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import correct, faults, harness, registry

ROOT = registry.ROOT

# a cell, configuration, mix and metric that exist only in the test's root:
# the harness finds them by name, with no edit to its code
TINY_MIX = {"apps": ["blackscholes", "raytrace"], "input_sizes": [1.0, 3.0], "n_jobs": 4096}
TINY_METRIC = '''
def read(ctx):
    return float(len(ctx.rounds))
'''


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    bench = registry.load_benchmark()
    os.makedirs(root / "chipbench" / "configs")
    shutil.copytree(os.path.join(ROOT, "chipbench", "traffic"), root / "chipbench" / "traffic")
    shutil.copytree(os.path.join(ROOT, "chipbench", "metrics"), root / "chipbench" / "metrics")
    cfg = registry.config(bench, "parsec_node")
    cfg["pool"] = {"nodes": 2}
    (root / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (root / "chipbench" / "traffic" / "tiny_sequence.json").write_text(json.dumps(TINY_MIX))
    (root / "chipbench" / "metrics" / "tiny.rounds.py").write_text(TINY_METRIC)
    bench["configs"].append({"name": "tiny", "source": "test", "file": "chipbench/configs/tiny.json",
                             "reduced": ["pool"], "why": "test"})
    bench["workloads"].append({"name": "tiny.sequence", "config": "tiny", "traffic": "tiny_sequence",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "tiny.rounds", "unit": "count", "better": "lower",
                               "source": "program_counter", "layer": "service",
                               "moves": "reaction_p50_ms", "workloads": ["tiny.sequence"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.fixture
def no_cache(monkeypatch):
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache", lambda: "")


def tiny_run(root, seed=5, faults=None, trace=0):
    args = harness.parse_args(
        ["--workload", "tiny.sequence", "--seed", str(seed), "--seconds", "1.5", "--trace", str(trace)]
    )
    return harness.run(args, time.perf_counter(), require_tpu=False, root=root, faults=faults)


def test_every_cell_resolves_its_parts():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        cfg = registry.config(bench, w["config"])
        assert cfg["pool"]["nodes"] > 0
        mix = registry.mix(w["traffic"])
        assert mix["n_jobs"] > 0
        for kind in ("end_to_end", "per_layer"):
            for m in registry.metrics_for(bench, w["name"], kind):
                if kind == "per_layer":
                    assert callable(registry.reader(m["name"]))


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
