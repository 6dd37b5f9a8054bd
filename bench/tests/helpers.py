"""A checkout of the benchmark at CPU test sizes, in a temporary root."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_TRAFFIC = {
    "tiny-grid": {"driver": "rollout", "why": "CPU test", "envs": 3, "policies": 2,
                  "trace": {"scenarios": ["S1", "S4"], "compression": 1.0},
                  "reference_envs": 3, "trace_rollouts": 1},
    "tiny-backlog": {"driver": "rollout", "why": "CPU test", "envs": 3, "policies": 2,
                     "trace": {"scenarios": [], "compression": 6.0},
                     "reference_envs": 2, "trace_rollouts": 1},
    "tiny-power-grid": {"driver": "rollout", "why": "CPU test", "envs": 3,
                        "policies": 2,
                        "trace": {"scenarios": ["S6", "S9"], "compression": 1.0},
                        "reference_envs": 3, "trace_rollouts": 1},
}

TINY_LIMITS = {"widest_score_gap": 1e-4}

TINY_CELLS = [
    ("rollout.tiny-mlp.grid", "tiny-mlp", "tiny-grid", "eval_jobs_per_s"),
    ("rollout.tiny-attn.backlog", "tiny-attn", "tiny-backlog", "eval_jobs_per_s"),
    ("rollout.tiny-power.grid", "tiny-power", "tiny-power-grid", "eval_jobs_per_s"),
]


def tiny_root(tmp: Path) -> Path:
    """Copy the benchmark into ``tmp`` and point a BENCHMARK.json at the
    CPU test configurations and traffic."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())

    def kind(cell, config_file):     # a metric's cells: same driver and module
        cfg = json.loads((REPO / config_file).read_text())
        return cell.split(".")[0], cfg["state_module"]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    real = {w["name"]: kind(w["name"], files[w["config"]])
            for w in spec["workloads"]}
    tiny = {w: kind(w, f"bench/tests/data/{c}.json") for w, c, _, _ in TINY_CELLS}
    names = {c for _, c, _, _ in TINY_CELLS}
    spec["configs"] = [{"name": n, "source": "CPU test",
                        "file": f"bench/tests/data/{n}.json", "reduced": [],
                        "why": "CPU test"} for n in sorted(names)]
    spec["workloads"] = [{"name": w, "config": c, "traffic": t, "chips": 1,
                          "why": "CPU test"} for w, c, t, _ in TINY_CELLS]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kinds = {real[o] for o in m["workloads"]}
            m["workloads"] = [w for w in tiny if tiny[w] in kinds]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    for name, t in TINY_TRAFFIC.items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(t))
    for w, *_ in TINY_CELLS:      # float32 on the CPU: gaps are rounding
        (tmp / "bench" / "limits" / f"{w}.json").write_text(
            json.dumps({"limits": TINY_LIMITS}))
    peaks = json.loads((tmp / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11,
                               "hbm_bytes": 1e10}
    (tmp / "bench" / "peaks.json").write_text(json.dumps(peaks))
    return tmp


def run_cell(root: Path, workload: str, seed: int = 3, seconds: float = 0.5,
             trace: int = 0, control: str = None) -> dict:
    """One run of a cell through the harness, without the chip check."""
    import run as bench_run
    argv = ["--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    args = bench_run.parse(argv + (["--control", control] if control else []))
    return bench_run.execute(root, args, require_tpu=False)
