#!/usr/bin/env python3
"""Readings that the correctness limits of a rollout cell are set from.

    python bench/tools/readings.py --workload <name> --seeds 1 2 3 ...

For each seed, in one process: what a run of the cell compares (the
driver's ``sample``: one greedy rollout per candidate policy and the
reference replay of its sampled environments), and then on the same
decision rows

* the program's score gaps (what a run compares),
* those of the reference computed at the configuration's stated
  precision (bfloat16 matmul operands), put in the program's place, and
* the control's: the reference with int8 (W8A8) operands, the step below
  the stated precision, put in the program's place.

Each is judged by the float32 reference at its own best action.  Prints
one JSON line per seed.  The limit must lie above every program reading
and below the control's.  Not run by the benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from harness import compare  # noqa: E402
from harness.core import device_info, find_cell  # noqa: E402


def summary(gaps):
    """The numbers a limit could be set on: widest gap, share of
    decisions off the reference's argmax, mean gap."""
    if len(gaps) == 0:
        return {"widest": None, "off_share": None, "mean": None}
    return {"widest": float(gaps.max()), "off_share": float((gaps > 0).mean()),
            "mean": float(gaps.mean())}


def readings(cfg, parts):
    """Program and controls, each judged by the float32 reference."""
    out = {}
    for name, mode in (("program", None), ("bf16", "bf16"), ("int8", "int8")):
        g = [compare.gaps(p["params"], cfg, p["rows"],
                          p["actions"] if mode is None else None, mode or "f32")
             for p in parts]
        out[name] = summary(np.concatenate(g))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--cpu", action="store_true",
                   help="allow a CPU platform (tests at small sizes)")
    p.add_argument("--root", default=str(ROOT))
    args = p.parse_args(argv)
    cell = find_cell(Path(args.root), args.workload)
    info = device_info(cell.chips, require_tpu=not args.cpu)
    print(json.dumps({"device": info}), flush=True)
    cfg, tf = cell.config, cell.traffic
    for seed in args.seeds:
        t0 = time.perf_counter()
        parts = cell.driver.sample(cfg, tf, seed)
        r = readings(cfg, parts)
        extra = {k: [p[k] for p in parts] for k in parts[0]
                 if k not in ("params", "rows", "actions")}
        print(json.dumps({"seed": seed, **r, **extra,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
