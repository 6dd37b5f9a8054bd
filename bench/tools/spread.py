#!/usr/bin/env python3
"""Run a cell several times, one process after another, and report the
spread of each end-to-end metric, as the bounds are set from.

    python bench/tools/spread.py --workload <name> --seeds 1 2 3 4 5 6 \
        --sets 2 --seconds 30 [--out DIR]

Each set runs ``bench/run.py`` once per seed, the sets with the same
seeds.  For each metric and set: the median and the spread, the distance
between the first and third quartiles of ``statistics.quantiles(values,
n=4)`` as a share of the median.  Result lines go to ``DIR`` when given.
Not run by the benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--out")
    args = p.parse_args(argv)
    out = Path(args.out) if args.out else None
    if out:
        out.mkdir(parents=True, exist_ok=True)
    sets = []
    for k in range(args.sets):
        lines = []
        for seed in args.seeds:
            cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
                   args.workload, "--seed", str(seed), "--seconds",
                   str(args.seconds), "--trace", str(args.trace)]
            r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            last = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else ""
            if out:
                (out / f"set{k}_seed{seed}.out").write_text(r.stdout)
                (out / f"set{k}_seed{seed}.err").write_text(r.stderr[-20000:])
            try:
                line = json.loads(last)
            except json.JSONDecodeError:
                line = {"correct": False, "rc": r.returncode, "metrics": {}}
            line["seed"] = seed
            print(json.dumps({"set": k, "seed": seed, "rc": r.returncode,
                              "correct": line.get("correct"),
                              "metrics": {m: v["value"] for m, v in
                                          line.get("metrics", {}).items()},
                              "memory_peak_bytes": line.get("device", {}).get("memory_peak_bytes"),
                              "checks": {c: v["value"] for c, v in
                                         line.get("checks", {}).items()}}),
                  flush=True)
            lines.append(line)
        sets.append(lines)
    names = sorted({m for s in sets for l in s for m in l.get("metrics", {})})
    for name in names:
        per = []
        for s in sets:
            vals = [l["metrics"][name]["value"] for l in s if name in l.get("metrics", {})]
            if len(vals) >= 2:
                per.append({"median": statistics.median(vals),
                            "spread": spread(vals), "n": len(vals)})
        print(json.dumps({"metric": name, "sets": per}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
