#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration, traffic mix (``bench/traffic/<name>.json``),
driver (``bench/drivers/<driver>.py``) and per-layer metric readers
(``bench/metrics/<name>.py``) are found by the names given there.

Set-up (traces, weights, compilation, warm-up) ends where the measured
window starts; ``setup_s`` counts from the start of the process.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of a short stretch of the window gives the
per-layer metrics.  After the window the cell's output is compared with
the plain reference under ``bench/harness`` (``--control int8`` puts the
reference at int8 in the program's place, for checking that the limits
catch it); each number compared is
printed with its limit as the last lines of standard error and, under
``checks``, at the end of the result line, which is the last line of
standard output.

Exits non-zero without a result line where JAX finds no TPU, fewer chips
than the cell asks for, a device kind missing from ``bench/peaks.json``,
or no ``BENCHMARK.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from harness.core import (CompileCounter, Refused, Run, device_info,  # noqa: E402
                          find_cell, metric_reader, peaks_for)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", choices=("int8",),
                   help="score with the reference in this precision in the "
                        "program's place (the control, which must read as not "
                        "correct); the benchmark's own runs never pass it")
    return p.parse_args(argv)


def configure_jax(root: Path) -> None:
    """Persistent compilation cache at a fixed path inside the checkout
    (or ``$JAX_COMPILATION_CACHE_DIR``), holding every program."""
    import jax
    from repro.runtime import configure_compile_cache
    configure_compile_cache(root / ".jax_cache")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_line(run: Run, info: dict, metrics: dict, attempted: int,
                failed: int) -> dict:
    correct = bool(run.checks) and all(c.ok for c in run.checks)
    device = {**info, "memory_peak_bytes": run.memory_peak_bytes}
    if run.trace:
        device["busy_s"] = run.layer.get("busy_s")
        device["window_s"] = run.layer.get("traced_window_s")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if run.trace and run.layer.get("breakdown"):
        out["breakdown"] = run.layer["breakdown"]
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                              "kind": c.kind} for c in run.checks}
    return out


def execute(root: Path, args, *, require_tpu: bool = True) -> dict:
    cell = find_cell(root, args.workload)
    info = device_info(cell.chips, require_tpu)
    peaks = peaks_for(root, info["kind"])
    configure_jax(root)
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), compiles=CompileCounter(),
              control=args.control)
    run.note(platform=info["platform"], kind=repr(info["kind"]),
             count=info["count"])
    if run.control:
        run.note(control=run.control)
    out = cell.driver.run(run)
    if run.trace:
        data = {**run.layer, "config": cell.config, "peaks": peaks}
        metrics = {}
        for name, m in cell.metrics.items():
            value = metric_reader(root, name)(data)
            if value is not None:
                metrics[name] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {"setup_s": {"value": run.setup_s, "unit": "s"}}
        for name, value in out["end_to_end"].items():
            metrics[name] = {"value": value,
                             "unit": cell.end_to_end[name]["unit"]}
    for c in run.checks:
        print(f"check {c.name} = {c.value!r} limit {c.kind} {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    return result_line(run, info, metrics, out["attempted"], out["failed"])


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line = execute(ROOT, args)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
