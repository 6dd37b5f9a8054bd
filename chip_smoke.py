#!/usr/bin/env python3
"""Smoke test of the scheduler on one TPU chip.

Drives the three paths users run -- the device rollout engine, the
vectorized trainer and the decision service -- at Theta scale (4,392
nodes, 1,293 burst-buffer units, state width 11,410) with the paper's
DFP widths (11410 -> 4000 -> 1000 -> 512) on the Pallas backend, then
the attention state module.  Weights are random, made from fixed seeds.

Phases, in order, all in this one process:

* device     -- platform, device kind and count; fails unless a TPU.
* rollout    -- ``DeviceSimulator.rollout(eps=0.1, collect=True)`` on
                eight 2-day Theta traces, then one greedy single-trace
                rollout against the sequential ``Simulator``.
* train      -- ``train_agent_vectorized`` on two 1-day Theta lanes.
* serve      -- ``DecisionService`` warm-up, then ``decide_many`` on 32
                Theta decision contexts, checked against ``agent.select``.
* attention  -- one eight-trace rollout and a short training run with
                ``state_module="attention"``, ``queue_cap=128``.

Each phase prints its seconds, its decision count and the Pallas kernels
(``tpu_custom_call``) found in the compiled programs it ran; a kernel
that ran interpreted or fell back to XLA shows up as missing.  Scores of
the Pallas path are compared with the jnp reference, computed on the
same chip at full f32 precision.  The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

printed only when every phase passed.  The script exits non-zero, with
no such line, when JAX finds no TPU or any phase fails.

Run from the repository root:  python chip_smoke.py
"""
from __future__ import annotations

import copy
import functools
import json
import re
import sys
import time
import traceback
from collections import Counter
from dataclasses import dataclass, replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (AgentConfig, FCFSPolicy, MRSchAgent,  # noqa: E402
                        TrainConfig, action_values, decision_row_dim,
                        encode_decision_row, greedy_actions_packed,
                        slots_from_jobsets, train_agent_vectorized)
from repro.core.agent import _train_steps_scan  # noqa: E402
from repro.runtime import configure_compile_cache, device_info  # noqa: E402
from repro.serve import DecisionService, ServeConfig  # noqa: E402
from repro.sim import DeviceSimulator, SimConfig, Simulator  # noqa: E402
from repro.workloads import ThetaConfig, generate_trace  # noqa: E402

# Pallas path vs the jnp reference on the same chip.  The kernels' f32
# matmuls may run as bf16 passes while the reference runs at full f32
# precision, so scores agree to a relative bound, not bit for bit.
SCORE_REL_TOL = 5e-2          # max |u_kernel - u_ref| / max |u_ref|
ARGMAX_MIN_SHARE = 0.9        # rows whose best valid action agrees
SERVE_MIN_SHARE = 0.9         # service answers equal to agent.select
# Device engine (f32 clock) vs sequential engine (f64 clock): the same
# schedule, start times equal to float32 precision at day scale.
START_RTOL, START_ATOL = 1e-6, 1e-2

MLP_FWD = ("fused_mlp_fwd",)
MLP_TRAIN = ("fused_mlp_fwd", "fused_mlp_dgrad", "fused_mlp_wgrad")
ATTN_FWD = ("mha_fwd",)
ATTN_TRAIN = ("mha_fwd", "mha_dq", "mha_dkv")

_KERNEL_NAME = re.compile(r'op_name="[^"]*/(\w+)/pallas_call"')


@dataclass(frozen=True)
class Scale:
    """Sizes of one smoke run; ``FULL`` is what the chip runs."""
    theta: ThetaConfig
    rollout_days: float = 2.0
    train_days: float = 1.0
    n_envs: int = 8
    train_lanes: int = 2
    grad_steps: int = 4
    serve_contexts: int = 32
    score_rows: int = 512
    agent: AgentConfig = AgentConfig(backend="pallas")
    queue_cap: int = 128


FULL = Scale(theta=ThetaConfig())


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


# ------------------------------------------------------------- kernels
def kernels_in(compiled) -> Counter:
    """Pallas kernels (by pallas_call name) among a compiled program's
    ``tpu_custom_call`` ops."""
    found = Counter()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_NAME.search(line)
            found[m.group(1) if m else "?"] += 1
    return found


def expect_kernels(found: Counter, expected, where: str) -> None:
    missing = [k for k in expected if not found.get(k)]
    check(not missing, f"{where}: no tpu_custom_call for {missing} "
          f"(found {dict(found)}) -- the kernel ran interpreted or on XLA")


def fmt(found: Counter) -> str:
    return ",".join(f"{k}:{v}" for k, v in sorted(found.items())) or "none"


# -------------------------------------------------------------- scores
@functools.partial(jax.jit, static_argnums=(1,))
def _scores(params, dfp, rows):
    sd, m = dfp.state_dim, dfp.n_measurements
    return action_values(params, dfp, rows[:, :sd], rows[:, sd:sd + m],
                         rows[:, sd + m:sd + 2 * m])


def compare_scores(agent: MRSchAgent, rows: np.ndarray) -> dict:
    """Kernel-path scores vs the jnp reference on packed decision rows."""
    sd, m, w = agent.enc.state_dim, agent.enc.n_resources, agent.config.window
    valid = rows[:, sd + 2 * m:sd + 2 * m + w] > 0.5
    u = np.asarray(_scores(agent.params, agent.dfp, rows))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(_scores(agent.params,
                                 replace(agent.dfp, backend="xla"), rows))
    check(np.isfinite(u).all(), "kernel scores are not finite")
    diff = float(np.abs(u - ref)[valid].max())
    rel = diff / max(float(np.abs(ref)[valid].max()), 1e-12)
    best = np.where(valid, u, -np.inf).argmax(1)
    best_ref = np.where(valid, ref, -np.inf).argmax(1)
    share = float((best == best_ref).mean())
    check(rel <= SCORE_REL_TOL,
          f"scores differ from the reference by {rel} > {SCORE_REL_TOL}")
    check(share >= ARGMAX_MIN_SHARE,
          f"argmax agrees on {share} < {ARGMAX_MIN_SHARE} of rows")
    return {"score_rows": len(rows), "score_max_abs_diff": diff,
            "score_max_rel_diff": rel, "argmax_match": share}


# ------------------------------------------------------------- phases
def traces(scale: Scale, days: float, n: int, seed0: int):
    return [generate_trace(replace(scale.theta, duration_days=days,
                                   seed=seed0 + i)) for i in range(n)]


def rollout_phase(scale: Scale, agent_cfg: AgentConfig, tag: str,
                  kernels, seq_check: bool, on_tpu: bool) -> None:
    res = scale.theta.resources()
    agent = MRSchAgent(res, agent_cfg)
    jobsets = traces(scale, scale.rollout_days, scale.n_envs, 0)
    sim = DeviceSimulator(res, jobsets, agent, SimConfig.for_engine("device"))
    # Compiling the rollout's own jitted program first fills the
    # persistent cache, so the public call below runs it from there.
    args = (sim.arrays, sim.faults_arrays, agent.init_state(),
            jnp.float32(0.1), jax.random.PRNGKey(0))
    compiled, compile_s = timed(
        lambda: sim._fn(True, True).lower(*args).compile())
    ro, first_s = timed(sim.rollout, eps=0.1, seed=0, collect=True)
    _, warm_s = timed(sim.rollout, eps=0.1, seed=0, collect=True)
    d = ro.decided
    check(ro.stats.decisions > 0, "rollout took no decisions")
    check(all(r.n_unstarted == 0 for r in ro.results),
          "rollout left jobs unstarted")
    acts = ro.actions[d]
    check(((acts >= 0) & (acts < agent.config.window)).all(),
          "actions outside the window")
    rows = ro.obs[d][:scale.score_rows]
    check(np.isfinite(rows).all(), "collected decision rows not finite")
    fields = {"state_dim": agent.enc.state_dim, "envs": scale.n_envs,
              "jobs": sim.layout.n_jobs, "rounds": sim.layout.rounds,
              "compile_s": compile_s, "first_call_s": first_s,
              "warm_call_s": warm_s, "decisions": ro.stats.decisions,
              "decisions_per_s_warm": ro.stats.decisions / warm_s}
    fields.update(compare_scores(agent, rows))
    found = kernels_in(compiled)
    fields["kernels"] = fmt(found)
    report(tag, **fields)
    if on_tpu:
        expect_kernels(found, ("window_pack",) + kernels, f"{tag} rollout")
    if not seq_check:
        return
    # One greedy single-trace rollout against the sequential engine.
    one = DeviceSimulator(res, jobsets[:1], agent,
                          SimConfig.for_engine("device"))
    dev, dev_s = timed(one.rollout)
    rec = _Recorder(agent)
    seq, seq_s = timed(Simulator(res, jobsets[0], rec, SimConfig()).run)
    dev_acts = [int(a) for a, k in zip(dev.actions[:, 0], dev.decided[:, 0])
                if k]
    same = sum(a == b for a, b in zip(dev_acts, rec.actions))
    r = dev.results[0]
    starts_ok = all(
        ja.started == jb.started and (not ja.started or np.isclose(
            ja.start, jb.start, rtol=START_RTOL, atol=START_ATOL))
        for ja, jb in zip(seq.jobs, r.jobs))
    report(f"{tag}/sequential", device_first_call_s=dev_s,
           sequential_s=seq_s,
           decisions=r.decisions, sequential_decisions=seq.decisions,
           matching_actions=f"{same}/{len(rec.actions)}",
           start_times_match=starts_ok)
    check(dev_acts == rec.actions and r.decisions == seq.decisions,
          "device rollout and sequential Simulator took different actions")
    check(starts_ok, "device and sequential start times differ")


class _Recorder:
    """Keep the sequential engine's action trace."""

    def __init__(self, policy):
        self.policy = policy
        self.actions = []

    def select(self, ctx):
        a = int(self.policy.select(ctx))
        self.actions.append(a)
        return a


def train_phase(scale: Scale, agent_cfg: AgentConfig, tag: str, kernels,
                on_tpu: bool) -> None:
    res = scale.theta.resources()
    agent = MRSchAgent(res, replace(agent_cfg, seed=1,
                                    grad_steps_per_episode=scale.grad_steps))
    lanes = traces(scale, scale.train_days, scale.train_lanes, 100)
    slots = slots_from_jobsets(res, lanes, scale.train_lanes)
    compiled, compile_s = timed(lambda: _train_steps_scan.lower(
        agent.dfp, agent.params, agent.opt_state,
        batch_shapes(agent, scale.grad_steps), agent.config.lr,
        agent.config.grad_clip).compile())
    log, run_s = timed(train_agent_vectorized, agent, slots,
                       TrainConfig(n_envs=scale.train_lanes,
                                   backend="pallas"))
    check(agent.losses, "no gradient step ran")
    check(np.isfinite(agent.losses).all(), f"loss not finite: "
          f"{agent.losses}")
    check(agent.last_grad_norm is not None
          and np.isfinite(agent.last_grad_norm), "grad norm not finite")
    steps = len(agent.losses) * scale.grad_steps
    found = kernels_in(compiled)
    report(tag, compile_s=compile_s, run_s=run_s, decisions=log.decisions,
           episodes=len(log.episodes), grad_steps=steps,
           loss=agent.losses[-1], grad_norm=agent.last_grad_norm,
           kernels=fmt(found))
    if on_tpu:
        expect_kernels(found, kernels, f"{tag} grad step")


def batch_shapes(agent: MRSchAgent, steps: int) -> dict:
    """Shapes of ``steps`` stacked replay minibatches (ReplayBuffer.sample)."""
    b, m = agent.config.batch_size, agent.enc.n_resources
    t = len(agent.config.offsets)

    def f32(*dims):
        return jax.ShapeDtypeStruct((steps, b) + dims, np.float32)
    return {"state": f32(agent.enc.state_dim), "meas": f32(m),
            "goal": f32(m), "action": jax.ShapeDtypeStruct((steps, b),
                                                           np.int32),
            "target": f32(t, m), "target_mask": f32(t)}


def serve_phase(scale: Scale, on_tpu: bool) -> None:
    res = scale.theta.resources()
    agent = MRSchAgent(res, replace(scale.agent, seed=2))
    ctxs = decision_contexts(scale, res)
    svc = DecisionService(agent, ServeConfig(max_batch=scale.serve_contexts))
    _, compile_s = timed(svc.start)         # warm-up compiles every width
    try:
        got, run_s = timed(svc.decide_many, ctxs)
        stats = svc.stats()
    finally:
        svc.stop()
    want = np.asarray([agent.select(c) for c in ctxs])
    share = float((got == want).mean())
    w = agent.config.window
    rows = np.zeros((len(ctxs), decision_row_dim(agent.enc, w)), np.float32)
    for row, c in zip(rows, ctxs):
        encode_decision_row(agent.enc, c, w, out=row)
    fields = {"compile_s": compile_s, "run_s": run_s, "decisions": len(ctxs),
              "batches": stats["batches"], "select_match": share}
    fields.update(compare_scores(agent, rows))
    # The service's program at the width these requests padded to.
    found = kernels_in(greedy_actions_packed.lower(
        agent.params, agent.dfp, rows).compile())
    fields["kernels"] = fmt(found)
    report("serve", **fields)
    if on_tpu:
        expect_kernels(found, MLP_FWD, "serve")
    check(share >= SERVE_MIN_SHARE,
          f"service agrees with agent.select on {share} < {SERVE_MIN_SHARE}")


def decision_contexts(scale: Scale, res):
    """Snapshots of live decision contexts from an FCFS replay of one
    Theta trace, spread evenly over the trace."""
    jobs = traces(scale, scale.train_days, 1, 200)[0]
    sim = Simulator(res, jobs, FCFSPolicy(), SimConfig())
    snaps = []
    while (ctx := sim.next_decision()) is not None:
        snaps.append(copy.deepcopy(ctx))
        sim.post_action(0)
    check(len(snaps) >= scale.serve_contexts,
          f"only {len(snaps)} decision contexts in the trace")
    pick = np.linspace(0, len(snaps) - 1, scale.serve_contexts).astype(int)
    return [snaps[i] for i in pick]


def run(scale: Scale, on_tpu: bool) -> list:
    """Every phase after the device check; returns the failed phases."""
    attn = replace(scale.agent, state_module="attention",
                   queue_cap=scale.queue_cap)
    phases = [
        ("rollout", lambda: rollout_phase(scale, scale.agent, "rollout",
                                          MLP_FWD, True, on_tpu)),
        ("train", lambda: train_phase(scale, scale.agent, "train",
                                      MLP_TRAIN, on_tpu)),
        ("serve", lambda: serve_phase(scale, on_tpu)),
        ("attention", lambda: (
            rollout_phase(scale, attn, "attention", ATTN_FWD + MLP_FWD,
                          False, on_tpu),
            train_phase(scale, attn, "attention/train",
                        ATTN_TRAIN + MLP_TRAIN, on_tpu))),
    ]
    failed = []
    for name, fn in phases:
        try:
            fn()
        except Exception:
            failed.append(name)
            print(f"[{name}] FAILED", flush=True)
            traceback.print_exc()
    return failed


def main() -> int:
    cache = Path(configure_compile_cache(ROOT / ".jax_cache"))
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    info = device_info()
    report("device", **info, compile_cache=cache, cache_entries_at_start=warm)
    if info["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {info['platform']!r}",
              file=sys.stderr)
        return 1
    failed = run(FULL, on_tpu=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
