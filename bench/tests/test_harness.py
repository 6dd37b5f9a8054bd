"""The harness on the CPU at test sizes: the chip check, cells found by
name, correct runs, and runs of a broken timed path read as not correct."""
import json
import os
import subprocess
import sys

import pytest

import helpers
from harness import core


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.tiny_root(tmp_path_factory.mktemp("checkout"))


def test_cpu_platform_is_refused_with_no_result_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(helpers.BENCH / "run.py"), "--workload",
         "rollout.theta-mlp.grid", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=helpers.REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_alone_is_refused(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ (no program)."""
    import shutil
    shutil.copytree(helpers.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(helpers.REPO / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rollout.theta-mlp.grid",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env={**env, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()


def test_every_benchmark_cell_resolves_by_name():
    spec = json.loads((helpers.REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = core.find_cell(helpers.REPO, w["name"])
        assert hasattr(cell.driver, "run")
        assert cell.config["name"] == w["config"]
        for name in cell.metrics:
            assert callable(core.metric_reader(helpers.REPO, name))


@pytest.mark.parametrize("workload", [w for w, *_ in helpers.TINY_CELLS])
def test_tiny_cell_is_correct(root, workload):
    line = helpers.run_cell(root, workload, seconds=1.0)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    e2e = line["metrics"]
    assert e2e["setup_s"]["value"] > 0 and len(e2e) == 2
    assert list(line)[-1] == "checks"


def test_traced_run_reports_layer_metrics(root):
    line = helpers.run_cell(root, "rollout.tiny-mlp.grid", trace=1)
    assert line["correct"]
    assert "setup_s" not in line["metrics"]
    assert set(line["device"]) >= {"busy_s", "window_s", "memory_peak_bytes"}



@pytest.mark.parametrize("trace", [0, 1])
def test_every_policy_is_compared_however_short_the_window(root, capsys,
                                                           trace):
    """A traced run compares what an untraced one does: every candidate
    policy, also where the window's time is up after its first rollout."""
    line = helpers.run_cell(root, "rollout.tiny-mlp.grid", seconds=0.01,
                            trace=trace)
    assert line["correct"], line["checks"]
    policies = helpers.TINY_TRAFFIC["tiny-grid"]["policies"]
    assert f"policies_compared={policies} " in capsys.readouterr().err


def _negated(monkeypatch):
    from repro.core.agent import MRSchAgent
    orig = MRSchAgent.score_window
    monkeypatch.setattr(MRSchAgent, "score_window",
                        lambda self, p, obs: -orig(self, p, obs))


def test_rollout_answer_altered_is_not_correct(root, monkeypatch):
    _negated(monkeypatch)
    line = helpers.run_cell(root, "rollout.tiny-mlp.grid", seed=5)
    assert not line["correct"]
    assert line["checks"]["widest_score_gap"]["value"] > \
        line["checks"]["widest_score_gap"]["limit"]


def test_rollout_without_backfill_is_not_correct(root, monkeypatch):
    from repro.sim import SimConfig
    orig = SimConfig.for_engine
    monkeypatch.setattr(SimConfig, "for_engine", classmethod(
        lambda cls, *a, **k: orig(*a, **{**k, "backfill": False})))
    line = helpers.run_cell(root, "rollout.tiny-attn.backlog", seed=5)
    assert not line["correct"]
    assert line["checks"]["start_times_mismatched"]["value"] > 0


def test_new_config_traffic_and_metric_by_name(tmp_path):
    """A later change adds files and a BENCHMARK.json entry only."""
    root = helpers.tiny_root(tmp_path)
    bench = root / "bench"
    cfg = json.loads((bench / "tests" / "data" / "tiny-mlp.json").read_text())
    cfg.update(name="tiny-wide", nodes=96)
    cfg["state_dim"] = 10 * 4 + 2 * (96 + 16)
    (bench / "configs" / "tiny-wide.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-few.json").write_text(json.dumps(
        {"driver": "rollout", "why": "test", "envs": 2, "policies": 1,
         "trace": {"scenarios": ["S2"], "compression": 2.0},
         "reference_envs": 2, "trace_rollouts": 1}))
    (bench / "metrics" / "decisions_per_round.eval.py").write_text(
        "def read(data):\n"
        "    if not data.get('live_rounds'):\n"
        "        return None\n"
        "    return data['decisions'] / data['live_rounds']\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-wide", "source": "test",
                            "file": "bench/configs/tiny-wide.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "rollout.tiny-wide.few", "chips": 1,
                              "config": "tiny-wide", "traffic": "tiny-few",
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "eval_jobs_per_s":
            m["workloads"].append("rollout.tiny-wide.few")
    spec["per_layer"].append({"name": "decisions_per_round.eval", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "rollout scan",
                              "moves": "eval_jobs_per_s",
                              "workloads": ["rollout.tiny-wide.few"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "limits" / "rollout.tiny-wide.few.json").write_text(
        json.dumps({"limits": helpers.TINY_LIMITS}))
    line = helpers.run_cell(root, "rollout.tiny-wide.few", trace=1)
    assert line["correct"]
    assert line["metrics"]["decisions_per_round.eval"]["value"] >= 1.0
    line = helpers.run_cell(root, "rollout.tiny-wide.few", trace=0)
    assert set(line["metrics"]) == {"setup_s", "eval_jobs_per_s"}


def _fixed_work_root(tmp_path, envs):
    """A checkout whose tiny grid draws its traces and policies from a
    ``work_seed``, as the chip's grid traffic does."""
    root = helpers.tiny_root(tmp_path)
    tf = {**helpers.TINY_TRAFFIC["tiny-grid"], "envs": envs,
          "reference_envs": min(envs, 3), "work_seed": 2147483999}
    (root / "bench" / "traffic" / "tiny-grid.json").write_text(json.dumps(tf))
    return root


def test_work_seed_deals_the_same_traces_and_policies_to_every_seed(tmp_path):
    import jax
    import numpy as np
    cell = core.find_cell(_fixed_work_root(tmp_path, 8), "rollout.tiny-mlp.grid")
    runs = [cell.driver.inputs(cell.config, cell.traffic, s)
            for s in (2147483647, 2147483648 + 12345)]

    def key(tr):
        return tuple(np.concatenate([tr["submit"], tr["runtime"],
                                     tr["demands"].ravel()]).tolist())
    (ta, pa), (tb, pb) = runs
    assert sorted(map(key, ta)) == sorted(map(key, tb))
    assert list(map(key, ta)) != list(map(key, tb))
    for a, b in zip(pa, pb):
        assert all(np.array_equal(x, y) for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def test_work_seed_runs_do_the_same_work_on_every_seed(tmp_path, capsys):
    root = _fixed_work_root(tmp_path, 3)
    notes = []
    for seed in (7, 2147483648 + 7):
        line = helpers.run_cell(root, "rollout.tiny-mlp.grid", seed=seed)
        assert line["correct"], line["checks"]
        err = capsys.readouterr().err
        notes.append([w for w in err.split() if w.startswith(
            ("live_rounds=", "decisions=", "jobs_per_rollout="))])
    assert notes[0] == notes[1] and len(notes[0]) == 3
