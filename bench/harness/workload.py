"""Theta-like job traces, made from a seed by the benchmark itself.

A copy of the statistics of the program's own Theta generator (MRSch,
arXiv:2403.16298, Sec. V: ALCF Theta, 40 % of jobs with I/O records,
17.18 % moving more than 1 GB, requests up to 285 TB) and of its Table III
burst-buffer mixes S1-S5, so that the yardstick does not move when the
program's generator does.  Every parameter comes from the configuration
file (cluster and job statistics) or the traffic file (mix, length,
compression); nothing is read from the program.

Times are whole seconds, as in a Standard Workload Format log: the
device engine keeps its clock in float32, which is exact on whole
seconds below 2**24 s (194 days), so start times compare exactly.

A trace is a dict of numpy arrays in (submit, jid) order:
``jid``, ``submit``, ``runtime``, ``walltime`` (float64 seconds) and
``demands`` (int64, one column per resource).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

DAY = 86400.0
HOUR = 3600.0

# Table III: (share of jobs with a BB request, smallest request in TB,
# node requests halved).
SCENARIOS = {
    "S1": (0.50, 5.0, False),
    "S2": (0.75, 5.0, False),
    "S3": (0.50, 20.0, False),
    "S4": (0.75, 20.0, False),
    "S5": (0.75, 20.0, True),
}


def rng_for(seed: int, *path: int) -> np.random.Generator:
    """Independent stream for one (seed, part) pair; any non-negative seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *path]))


def _arrivals(stats: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """First ``n`` arrivals of a nonhomogeneous Poisson process with
    diurnal and weekly modulation, by thinning."""
    lam_base = stats["jobs_per_day"] / DAY
    amp = stats["diurnal_amplitude"]
    lam_max = lam_base * (1 + amp)
    t, out = 0.0, []
    while len(out) < n:
        t += rng.exponential(1.0 / lam_max)
        hour = (t % DAY) / HOUR
        lam = lam_base * (1 + amp * math.sin((hour - 8.0) / 24.0 * 2 * math.pi))
        if int(t // DAY) % 7 >= 5:
            lam *= stats["weekend_factor"]
        if rng.uniform() < lam / lam_max:
            out.append(t)
    return np.asarray(out)


def base_trace(config: dict, n: int, rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """``n`` jobs with node sizes, runtimes, walltimes and the trace's own
    burst-buffer requests."""
    st = config["job_statistics"]
    nodes_cap, bb_cap = config["nodes"], config["bb_units"]
    arrivals = _arrivals(st, n, rng)
    w = np.asarray(st["size_weights"], float)
    frac = rng.choice(np.asarray(st["size_buckets"]), size=n, p=w / w.sum())
    nodes = np.maximum(1, np.round(frac * nodes_cap)).astype(np.int64)
    runtime = np.clip(rng.lognormal(math.log(st["runtime_median_s"]),
                                    st["runtime_sigma"], size=n),
                      st["runtime_min_s"], st["runtime_max_s"])
    runtime = np.maximum(np.round(runtime), 1.0)
    overest = rng.uniform(1.0, st["walltime_overestimate_max"], size=n)
    walltime = np.minimum(np.ceil(runtime * overest / 600.0) * 600.0,
                          st["walltime_cap_s"])
    walltime = np.maximum(walltime, runtime)
    # Base burst-buffer requests (Darshan-style pool).
    has_io = rng.uniform(size=n) < st["frac_jobs_with_io"]
    big = has_io & (rng.uniform(size=n)
                    < st["frac_jobs_gt_1gb"] / st["frac_jobs_with_io"])
    small = has_io & ~big
    tb = np.zeros(n)
    tb[small] = 10 ** rng.uniform(-6.0, -3.0, size=int(small.sum()))
    tb[big] = np.clip(10 ** rng.normal(-0.3, 1.3, size=int(big.sum())),
                      1e-3, st["bb_max_tb"])
    bb = np.where(tb > 0, np.ceil(tb / config["bb_unit_tb"]), 0)
    bb = np.minimum(bb, bb_cap).astype(np.int64)
    return {"submit": np.floor(arrivals), "runtime": runtime,
            "walltime": walltime, "nodes": nodes, "bb": bb}


def apply_scenario(tr: Dict[str, np.ndarray], config: dict, name: str,
                   rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """Table III: re-draw each job's BB request from a heavy-tailed pool
    restricted to [lo, 285] TB; S5 halves node requests."""
    frac, lo_tb, halve = SCENARIOS[name]
    st = config["job_statistics"]
    pool_tb = 10 ** rng.uniform(math.log10(lo_tb), math.log10(st["bb_max_tb"]),
                                size=4096)
    pool = np.minimum(np.ceil(pool_tb / config["bb_unit_tb"]),
                      config["bb_units"]).astype(np.int64)
    n = len(tr["submit"])
    out = dict(tr)
    if halve:
        out["nodes"] = np.maximum(1, tr["nodes"] // 2)
    take = rng.uniform(size=n) < frac
    out["bb"] = np.where(take, rng.choice(pool, size=n), 0).astype(np.int64)
    return out


def compress(tr: Dict[str, np.ndarray], factor: float) -> Dict[str, np.ndarray]:
    """Submit times compressed ``factor``x: a sustained deep backlog."""
    if factor == 1.0:
        return tr
    t0 = tr["submit"].min()
    return {**tr, "submit": np.floor(t0 + (tr["submit"] - t0) / factor)}


def finish(tr: Dict[str, np.ndarray], resources: Sequence[str]) -> Dict[str, np.ndarray]:
    """Attach jids, sort by (submit, jid) and stack the demand columns."""
    n = len(tr["submit"])
    jid = np.arange(n, dtype=np.int64)
    order = np.lexsort((jid, tr["submit"]))
    cols = {"node": tr["nodes"], "bb": tr["bb"]}
    return {"jid": jid[order],
            "submit": tr["submit"][order].astype(np.float64),
            "runtime": tr["runtime"][order].astype(np.float64),
            "walltime": tr["walltime"][order].astype(np.float64),
            "demands": np.stack([cols[r][order] for r in resources], axis=1)}


def make_trace(config: dict, traffic_trace: dict, seed: int, index: int,
               n_jobs: int) -> Dict[str, np.ndarray]:
    """Trace ``index`` of the mix for ``seed``: its scenario is taken in
    turn from the traffic's ``scenarios`` list."""
    rng = rng_for(seed, index)
    tr = base_trace(config, n_jobs, rng)
    scenarios = traffic_trace.get("scenarios") or []
    if scenarios:
        tr = apply_scenario(tr, config, scenarios[index % len(scenarios)], rng)
    tr = compress(tr, float(traffic_trace.get("compression", 1.0)))
    return finish(tr, config["resources"])


def make_traces(config: dict, traffic_trace: dict, seed: int, n_traces: int,
                n_jobs: int) -> List[Dict[str, np.ndarray]]:
    return [make_trace(config, traffic_trace, seed, i, n_jobs)
            for i in range(n_traces)]
