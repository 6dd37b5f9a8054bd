"""Evaluation rollouts: ``DeviceSimulator.rollout`` over a grid of traces.

A site ranking candidate policies runs each over the same grid.  Set-up
makes the traffic's ``envs`` traces of the configuration's ``trace_jobs``
jobs and the weights of ``policies`` candidate policies from the seed,
and one ``DeviceSimulator``; one whole greedy rollout and its per-env
results warm every program and host path.  The window then runs whole
rollouts, policy after policy (the parameters are an argument of the
compiled scan, so nothing recompiles), each ending with its per-env
``ScheduleMetrics``, until ``--seconds`` have passed and every policy
has run at least once, so that a traced run compares what an untraced
one does.
``eval_jobs_per_s`` is the jobs those rollouts completed over the window.
With ``--trace 1`` the first ``trace_rollouts`` rollouts are profiled.

Correctness, for every policy the window ran: on ``reference_envs``
environments drawn from the seed, the reference scheduler, replaying the
recorded actions, takes the same decisions and starts every job at the
same second, and the reference network (float32, highest precision)
rates the chosen actions within the cell's limits of its best (the
widest or the mean gap, ``bench/limits/<cell>.json``); a policy the
window ran twice took the same actions twice.  With ``run.control`` the
gaps are those of the reference at that precision, choosing its own
best action at every row, in the program's place.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from harness import compare, program, refnet, trace, workload


def _completed(results) -> int:
    return sum(sum(1 for j in r.jobs if j.end >= 0.0) for r in results)


def build(cfg: dict, tf: dict, seed: int):
    """Traces and candidate policies from the seed, and the program's
    simulator (holding the first policy)."""
    from repro.sim import DeviceSimulator, SimConfig
    traces = workload.make_traces(cfg, tf["trace"], seed, int(tf["envs"]),
                                  int(cfg["trace_jobs"]))
    params = [refnet.make_params(cfg, seed, p) for p in range(int(tf["policies"]))]
    agent = program.agent(cfg, params[0])
    sim = DeviceSimulator(program.resources(cfg),
                          [program.jobs(cfg, t) for t in traces], agent,
                          SimConfig.for_engine("device", window=cfg["window"],
                                               backfill=cfg["backfill"] == "easy"))
    return traces, params, agent, sim


def record(tf: dict, seed: int, policy: int, ro) -> dict:
    """What the reference needs of one rollout: the actions and start
    times of the environments sampled for this policy."""
    n_envs = ro.actions.shape[1]
    envs = workload.rng_for(seed, 99, policy).choice(
        n_envs, size=min(int(tf["reference_envs"]), n_envs), replace=False)
    results = ro.results
    return {"policy": policy, "envs": envs,
            "actions": [ro.actions[:, i][ro.decided[:, i]] for i in envs],
            "starts": [{j.jid: j.start for j in results[i].jobs} for i in envs]}


def replay(cfg: dict, traces, rec) -> dict:
    return compare.replay_envs(cfg, [traces[i] for i in rec["envs"]],
                               rec["actions"], rec["starts"])


def run(run):
    cfg, tf = run.config, run.traffic
    n_envs, n_jobs = int(tf["envs"]), int(cfg["trace_jobs"])
    traces, params, agent, sim = build(cfg, tf, run.seed)
    first = sim.rollout()
    per_rollout = _completed(first.results)
    run.note(envs=n_envs, jobs=n_jobs, policies=len(params),
             scan_rounds=sim.layout.rounds, live_rounds=first.stats.rounds,
             decisions=first.stats.decisions, jobs_per_rollout=per_rollout)
    del first

    traced = int(tf.get("trace_rollouts", 1)) if run.trace else 0
    capture = trace.Capture() if traced else None
    if capture:
        import jax
        import jax.numpy as jnp
        args = (sim.arrays, sim.faults_arrays, agent.init_state(),
                jnp.float32(0.0), jax.random.PRNGKey(0))
        capture.add_hlo(sim._fn(False, False).lower(*args).compile().as_text())
    rollouts, jobs_done, live, decisions = 0, 0, [], []
    recorded, seen, differing = [], {}, 0
    with run.window():
        t0 = time.perf_counter()
        while True:
            p = rollouts % len(params)
            agent.params = params[p]
            ctx = capture.active() if rollouts < traced else contextlib.nullcontext()
            with ctx:
                with trace.span("bench.rollout"):
                    ro = sim.rollout()
                with trace.span("bench.results"):
                    jobs_done += _completed(ro.results)
            if capture and rollouts + 1 == traced:
                capture.stop()
            rollouts += 1
            live.append(ro.stats.rounds)
            decisions.append(ro.stats.decisions)
            if p in seen:
                differing += int(not np.array_equal(ro.actions, seen[p]))
            else:
                seen[p] = ro.actions
                recorded.append(record(tf, run.seed, p, ro))
            if (time.perf_counter() - t0 >= run.seconds
                    and rollouts >= max(traced, len(params))):
                break
    run.read_memory_peak()
    run.note(rollouts=rollouts, window_s=run.window_s)
    out = {"end_to_end": {"eval_jobs_per_s": jobs_done / run.window_s},
           "attempted": rollouts * n_envs * n_jobs,
           "failed": rollouts * n_envs * n_jobs - jobs_done}
    if capture:
        run.layer.update(capture.reduce())
        run.layer.update(
            decisions=sum(decisions[:traced]), live_rounds=sum(live[:traced]),
            scan_rounds=traced * sim.layout.rounds, batch_rows=n_envs)

    # ----------------------------------------------------------- correct
    del sim, agent, ro, seen
    t_ref = time.perf_counter()
    gaps, bad_decisions, bad_starts = [], 0, 0
    for rec in recorded:             # one policy's rows at a time
        rep = replay(cfg, traces, rec)
        bad_decisions += rep["bad_decisions"]
        bad_starts += rep["bad_starts"]
        gaps.append(compare.gaps(params[rec["policy"]], cfg, rep["rows"],
                                 None if run.control else rep["actions"],
                                 run.control or "f32"))
    gaps = np.concatenate(gaps)
    run.note(policies_compared=len(recorded),
             reference_envs=sum(len(r["envs"]) for r in recorded),
             reference_decisions=len(gaps),
             reference_s=time.perf_counter() - t_ref,
             argmax_agreement=float((gaps == 0).mean()) if len(gaps) else 0.0)
    run.check("rollouts_differing", differing, 0)
    run.check("decisions_mismatched", bad_decisions, 0)
    run.check("start_times_mismatched", bad_starts, 0)
    run.check_gaps(gaps)
    return out


def sample(cfg: dict, tf: dict, seed: int) -> list:
    """What one run compares, for the limit readings
    (``bench/tools/readings.py``): per candidate policy, its weights and
    the reference rows and chosen actions of its sampled environments."""
    traces, params, agent, sim = build(cfg, tf, seed)
    sim.rollout()
    parts = []
    for p, w in enumerate(params):
        agent.params = w
        t0 = time.perf_counter()
        ro = sim.rollout()
        rollout_s = time.perf_counter() - t0
        rep = replay(cfg, traces, record(tf, seed, p, ro))
        parts.append({"params": w, "rows": rep["rows"], "actions": rep["actions"],
                      "mismatched": rep["bad_decisions"] + rep["bad_starts"],
                      "live_rounds": ro.stats.rounds, "rollout_s": rollout_s})
    return parts
