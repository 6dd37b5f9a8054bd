"""What every cell's run shares: finding the cell's files by name, the
device check, set-up and window timing, counting compilations, the
checks that decide ``correct``, and the result line."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional


class Refused(RuntimeError):
    """The run cannot be measured here (no chip, unknown device, ...)."""


# ------------------------------------------------------------- by name
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with everything found by its names."""
    root: Path
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: object
    metrics: Dict[str, dict]         # per-layer metric entries of this cell
    end_to_end: Dict[str, dict]      # end-to-end metric entries of this cell
    limits: Dict[str, float]         # bench/limits/<cell>.json: number -> limit


def find_cell(root: Path, workload: str) -> Cell:
    """Resolve a cell of ``<root>/BENCHMARK.json`` by name: its
    configuration file, ``bench/traffic/<traffic>.json``, the driver
    that file names, ``bench/drivers/<driver>.py``, and the limits of the
    numbers its correctness check compares, ``bench/limits/<cell>.json``."""
    root = Path(root)
    bench = root / "bench"
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench / "traffic" / f"{w['traffic']}.json")
    driver = load_module(bench / "drivers" / f"{traffic['driver']}.py",
                         "driver_" + traffic["driver"])

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = {m["name"]: m for m in spec["end_to_end"] if mine(m)}
    layer = {m["name"]: m for m in spec["per_layer"]
             if mine(m) and ("workloads" in m or m["moves"] in e2e)}
    limits = load_json(bench / "limits" / f"{workload}.json")["limits"]
    return Cell(root=root, name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, driver=driver, metrics=layer,
                end_to_end=e2e, limits=limits)


def metric_reader(root: Path, name: str):
    """``bench/metrics/<name>.py``: its ``read(data)`` returns the value,
    or None where it finds nothing to read."""
    return load_module(Path(root) / "bench" / "metrics" / f"{name}.py",
                       "metric_" + name).read


# ------------------------------------------------------------- timing
def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


class CompileCounter:
    """Counts backend compilations (and loads from the persistent cache)
    while ``counting`` is on."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.counting = False
        self.count = 0
        self._lock = threading.Lock()

        def listener(event, duration, **_kw):
            if event == self.EVENT and self.counting:
                with self._lock:
                    self.count += 1
        mon.register_event_duration_secs_listener(listener)


@dataclass
class Check:
    """One number compared with its limit for ``correct``."""
    name: str
    value: float
    limit: float
    kind: str = "max"                # "max": value <= limit; "min": >=

    @property
    def ok(self) -> bool:
        v = float(self.value)
        if v != v:                   # NaN never passes
            return False
        return v <= self.limit if self.kind == "max" else v >= self.limit


@dataclass
class Run:
    """State of one run, handed to the cell's driver."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    compiles: CompileCounter
    control: Optional[str] = None    # a precision of refnet's, or None
    setup_s: Optional[float] = None
    window_s: Optional[float] = None
    checks: List[Check] = field(default_factory=list)
    notes: Dict[str, object] = field(default_factory=dict)
    layer: Dict[str, object] = field(default_factory=dict)
    memory_peak_bytes: Optional[int] = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def note(self, **kw) -> None:
        """Numbers printed on an earlier line of standard error."""
        self.notes.update(kw)
        print("bench: " + " ".join(f"{k}={v}" for k, v in kw.items()),
              file=sys.stderr, flush=True)

    def check(self, name: str, value, limit: float, kind: str = "max") -> None:
        self.checks.append(Check(name, float(value), float(limit), kind))

    def check_gaps(self, gaps) -> None:
        """Score gaps of the chosen actions: their widest and mean, each
        compared where the cell's limits name it."""
        import numpy as np
        gaps = np.asarray(gaps, float)
        stats = {"widest_score_gap": gaps.max() if len(gaps) else float("inf"),
                 "mean_score_gap": gaps.mean() if len(gaps) else float("inf")}
        self.note(**stats)
        for name, value in stats.items():
            if name in self.cell.limits:
                self.check(name, value, self.cell.limits[name])

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends at its start; compilations
        inside it are counted."""
        self.setup_s = process_age_s()
        self.compiles.count = 0
        self.compiles.counting = True
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.window_s = time.perf_counter() - t0
            self.compiles.counting = False
            self.note(compiles_in_window=self.compiles.count)

    def read_memory_peak(self) -> None:
        import jax
        peaks = []
        for d in jax.local_devices()[:self.cell.chips]:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks) if peaks else None


def device_info(chips: int, require_tpu: bool) -> dict:
    """Platform, kind and count as JAX reports them; refuses a platform
    other than ``tpu`` and fewer devices than the cell asks for."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if require_tpu and info["platform"] != "tpu":
        raise Refused(f"needs a TPU; JAX found {info['platform']!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return info


def peaks_for(root: Path, kind: str) -> dict:
    """Peak rates of a device kind from ``bench/peaks.json``."""
    table = load_json(Path(root) / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]
