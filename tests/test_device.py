"""Device-resident rollout engine: round-for-round parity with the
sequential engine (registry scenarios, both NN backends), window-pack
kernel parity, Policy-protocol gating, and ``SimConfig.for_engine``."""
import numpy as np
import pytest

from repro.core import (AgentConfig, FCFSPolicy, GAConfig, GAOptimizer,
                        MRSchAgent, ScalarRLConfig, ScalarRLPolicy,
                        supports_batch, supports_device)
from repro.kernels.window_pack.ops import pack_window
from repro.sim import (FINISHED, DeviceSimulator, Job, ResourceSpec,
                       SimConfig, Simulator, run_traces_device, sim_config)
from repro.workloads import ThetaConfig, build_scenarios
from repro.workloads.registry import build_jobs

RES = [ResourceSpec("node", 16), ResourceSpec("bb", 8)]


def synth_jobs(seed: int, n: int = 40):
    rng = np.random.default_rng(seed)
    jobs = []
    t = 0.0
    for i in range(n):
        t += float(rng.exponential(40.0))
        runtime = float(rng.uniform(20, 300))
        jobs.append(Job(jid=i, submit=t, runtime=runtime,
                        walltime=runtime * float(rng.uniform(1.0, 2.0)),
                        demands={"node": int(rng.integers(1, 12)),
                                 "bb": int(rng.integers(0, 6))}))
    return jobs


def small_agent(resources, seed: int = 0, backend: str = "xla") -> MRSchAgent:
    return MRSchAgent(resources, AgentConfig(
        state_hidden=(32, 16), state_out=8, module_hidden=4, seed=seed,
        backend=backend))


class _Recorder:
    """Wrap a policy so the sequential engine's action trace is kept."""

    def __init__(self, policy):
        self.policy = policy
        self.actions = []

    def select(self, ctx):
        a = int(self.policy.select(ctx))
        self.actions.append(a)
        return a


def seq_run(resources, jobs, policy):
    rec = _Recorder(policy)
    result = Simulator(resources, jobs, rec, SimConfig()).run()
    return result, rec.actions


def env_actions(ro, i):
    return [int(a) for a, d in zip(ro.actions[:, i], ro.decided[:, i]) if d]


def assert_results_close(a, b, rtol=1e-5, atol=1e-2):
    """Host (f64) vs device (f32 clock) results: same schedule, metrics
    equal to float32 precision (time ulp ~2e-3 s at day scale)."""
    assert a.decisions == b.decisions
    assert a.n_unstarted == b.n_unstarted
    ra, rb = a.metrics.as_row(), b.metrics.as_row()
    assert set(ra) == set(rb)
    for k in ra:
        assert np.isclose(ra[k], rb[k], rtol=rtol, atol=atol), \
            (k, ra[k], rb[k])
    for ja, jb in zip(a.jobs, b.jobs):
        assert ja.jid == jb.jid and ja.started == jb.started
        if ja.started:
            assert np.isclose(ja.start, jb.start, rtol=1e-6, atol=1e-2)


# ------------------------------------------------------- N=1 parity (pinned)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_device_equals_sequential_fcfs(seed):
    """Same actions, decision for decision, and the same schedule."""
    jobs = synth_jobs(seed)
    seq, actions = seq_run(RES, jobs, FCFSPolicy())
    dev = DeviceSimulator(RES, [jobs], FCFSPolicy())
    ro = dev.rollout()
    assert env_actions(ro, 0) == actions
    assert_results_close(seq, ro.results[0])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("scenario", ["S2", "diurnal-heavy"])
def test_device_equals_sequential_agent_registry(scenario, backend):
    """The acceptance pin: N=1 device rollout reproduces the sequential
    engine round for round on registry scenarios, on both NN backends."""
    theta = ThetaConfig.mini(seed=0, duration_days=0.4, jobs_per_day=110)
    res = theta.resources()
    jobs = build_jobs(scenario, theta, seed=1)
    agent = small_agent(res, backend=backend)
    seq, actions = seq_run(res, jobs, agent)
    ro = DeviceSimulator(res, [jobs], agent).rollout()
    assert env_actions(ro, 0) == actions
    assert_results_close(seq, ro.results[0])


@pytest.mark.parametrize("scenario,backend", [
    ("S6", "xla"), ("S7", "xla"), ("S8", "xla"), ("S9", "xla"),
    ("S10", "xla"), ("S9", "pallas")])
def test_device_equals_sequential_power_capped(scenario, backend):
    """Three resources (§V-E, S6-S10): the device rollout reproduces the
    sequential engine under the cluster's default power budget, and the
    clamped draw lets every job start."""
    theta = ThetaConfig.mini(seed=0, duration_days=0.4, jobs_per_day=110)
    res = theta.resources(power_budget_kw=theta.default_power_budget_kw())
    jobs = build_scenarios(theta, names=(scenario,), power=True)[scenario]
    agent = small_agent(res, backend=backend)
    seq, actions = seq_run(res, jobs, agent)
    ro = DeviceSimulator(res, [jobs], agent).rollout()
    assert env_actions(ro, 0) == actions
    assert_results_close(seq, ro.results[0])
    assert seq.n_unstarted == 0
    assert set(ro.stats.reserved_by) == {"node", "bb", "power"}


def test_device_equals_sequential_scalar_rl():
    jobs = synth_jobs(7)
    rl = ScalarRLPolicy(RES, ScalarRLConfig(hidden=(16, 8)))
    seq, actions = seq_run(RES, jobs, rl)
    ro = DeviceSimulator(RES, [jobs], rl).rollout()
    assert env_actions(ro, 0) == actions
    assert_results_close(seq, ro.results[0])


def test_device_multi_env_matches_per_env_sequential():
    """N>1 envs share one program but stay independent trajectories."""
    jobsets = [synth_jobs(seed, n=25) for seed in range(4)]
    ro = DeviceSimulator(RES, jobsets, FCFSPolicy()).rollout()
    for i, jobs in enumerate(jobsets):
        seq, actions = seq_run(RES, jobs, FCFSPolicy())
        assert env_actions(ro, i) == actions
        assert_results_close(seq, ro.results[i])
    st = ro.stats
    assert st.decisions == sum(r.decisions for r in ro.results)
    assert st.policy_calls == st.rounds
    assert 1 < st.max_batch <= 4
    # Each pass frees at least one job in some environment.
    finished = sum(j.state == FINISHED for r in ro.results for j in r.jobs)
    assert 0 < st.free_passes <= finished
    assert st.as_dict()["free_passes"] == st.free_passes


def test_device_no_backfill_matches_sequential():
    jobs = synth_jobs(3)
    cfg = SimConfig.for_engine("device", backfill=False)
    seq_nb = Simulator(RES, jobs, FCFSPolicy(),
                       SimConfig(backfill=False)).run()
    ro = DeviceSimulator(RES, [jobs], FCFSPolicy(), cfg).rollout()
    assert_results_close(seq_nb, ro.results[0])


# ------------------------------------------------------ reservation counter
RES3 = [ResourceSpec("node", 4), ResourceSpec("bb", 4),
        ResourceSpec("power", 4, "kW")]


@pytest.mark.parametrize("short", [("node",), ("bb",), ("power",),
                                   ("node", "power")])
def test_reserved_by_counts_the_resource_that_is_short(short):
    """Job 0 leaves one unit of each ``short`` resource free and runs to
    t=100; job 1 needs two of them, so it reserves at t=100; job 2 fits
    beside the reservation (shadow) and is backfilled, and no event
    falls before t=100.  The one reservation lands on the short
    resources alone (a tie counts each)."""
    def demands(n_short, n_other):
        return {r.name: n_short if r.name in short else n_other
                for r in RES3}
    jobs = [Job(jid=0, submit=0.0, runtime=100.0, walltime=100.0,
                demands=demands(3, 1)),
            Job(jid=1, submit=0.0, runtime=50.0, walltime=50.0,
                demands=demands(2, 1)),
            Job(jid=2, submit=0.0, runtime=150.0, walltime=150.0,
                demands=demands(1, 1))]
    seq, actions = seq_run(RES3, jobs, FCFSPolicy())
    ro = DeviceSimulator(RES3, [jobs], FCFSPolicy()).rollout()
    assert env_actions(ro, 0) == actions
    assert_results_close(seq, ro.results[0])
    assert [j.start for j in ro.results[0].jobs] == [0.0, 100.0, 0.0]
    stats = ro.stats.as_dict()
    assert {r.name: stats[f"reserved_by_{r.name}"] for r in RES3} == {
        r.name: int(r.name in short) for r in RES3}


# ------------------------------------------------------------ rollout extras
def test_rollout_collect_yields_transitions():
    jobs = synth_jobs(0, n=15)
    agent = small_agent(RES)
    dev = DeviceSimulator(RES, [jobs], agent)
    ro = dev.rollout(collect=True)
    trans = list(ro.transitions())
    assert len(trans) == ro.stats.decisions
    obs_dim = dev.layout.state_dim + 2 * 2 + dev.layout.window
    for t, i, row, a in trans:
        assert row.shape == (obs_dim,)
        assert 0 <= a < dev.layout.window
        assert bool(ro.decided[t, i])


def test_rollout_epsilon_greedy_still_schedules_everything():
    jobs = synth_jobs(1, n=20)
    ro = DeviceSimulator(RES, [jobs], small_agent(RES)).rollout(eps=1.0,
                                                                seed=3)
    assert ro.results[0].n_unstarted == 0
    assert all(0 <= a < 10 for a in env_actions(ro, 0))


def test_run_traces_device_convenience():
    jobsets = [synth_jobs(s, n=12) for s in range(2)]
    out = run_traces_device(RES, jobsets, FCFSPolicy())
    assert len(out) == 2 and all(r.n_unstarted == 0 for r in out)


# ------------------------------------------------------------ protocol gates
def test_device_rejects_host_only_policy():
    ga = GAOptimizer(GAConfig(population=4, generations=2))
    assert not supports_device(ga)
    assert supports_batch(FCFSPolicy()) and supports_device(FCFSPolicy())
    with pytest.raises(TypeError, match="device stages"):
        DeviceSimulator(RES, [synth_jobs(0, n=5)], ga)


def test_device_rejects_window_mismatch():
    agent = small_agent(RES)                       # enc.window == 10
    with pytest.raises(ValueError, match="window"):
        DeviceSimulator(RES, [synth_jobs(0, n=5)], agent,
                        SimConfig.for_engine("device", window=5))


def test_device_round_budget_error():
    cfg = SimConfig.for_engine("device", max_rounds=2)
    with pytest.raises(RuntimeError, match="round budget"):
        DeviceSimulator(RES, [synth_jobs(0, n=20)], FCFSPolicy(),
                        cfg).rollout()


# ----------------------------------------------------------- window-pack op
def test_window_pack_kernel_matches_reference():
    rng = np.random.default_rng(0)
    waiting = (rng.uniform(size=(3, 50)) < 0.4).astype(np.float32)
    feats = rng.normal(size=(3, 50, 7)).astype(np.float32)
    ref = pack_window(waiting, feats, window=10, use_pallas=False)
    ker = pack_window(waiting, feats, window=10, use_pallas=True,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(ker[0]), np.asarray(ref[0]),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ker[1]), np.asarray(ref[1]))
    np.testing.assert_array_equal(np.asarray(ker[2]), np.asarray(ref[2]))
    # Packing semantics: slot w holds the (w+1)-th waiting job's features.
    wait_idx = np.flatnonzero(waiting[1] > 0.5)
    n = min(len(wait_idx), 10)
    assert list(np.asarray(ref[1])[1, :n]) == list(wait_idx[:n])
    assert np.asarray(ref[2])[1, :n].all()


# ------------------------------------------------------ for_engine construct
def test_for_engine_is_the_single_constructor_path():
    cfg = SimConfig.for_engine("device", window=6, backfill=False,
                               max_rounds=99)
    assert (cfg.engine, cfg.window, cfg.backfill, cfg.max_rounds) \
        == ("device", 6, False, 99)
    assert sim_config(window=6).engine == "sequential"  # deprecation alias
    with pytest.raises(ValueError, match="engine"):
        SimConfig.for_engine("gpu_cluster")
    with pytest.raises(ValueError):
        SimConfig.for_engine("vector", window=0)
    with pytest.raises(ValueError):
        SimConfig.for_engine("device", max_rounds=0)


# ----------------------------------------------------------- profiler names
SCAN_PHASES = {"advance", "pack", "obs", "score", "start", "backfill_fit",
               "backfill_walk", "backfill_assign"}


@pytest.mark.parametrize("state_module", ["mlp", "attention"])
def test_scan_phases_name_the_compiled_rollout(state_module):
    """Every phase of a round carries its ``mrsch.scan.*`` scope in the
    compiled program's op_names, flat (no op under two phases), with the
    kernel scopes innermost; no gather runs outside the live-round cond,
    and the event pump's ``device_free_units`` loop lies in ``advance``."""
    import re

    import jax
    import jax.numpy as jnp
    agent = MRSchAgent(RES, AgentConfig(
        state_hidden=(32, 16), state_out=8, module_hidden=4,
        backend="pallas", state_module=state_module, queue_cap=16,
        attn_dim=8, attn_heads=2, attn_layers=1))
    sim = DeviceSimulator(RES, [synth_jobs(s, n=12) for s in range(2)],
                          agent, SimConfig.for_engine("device", backfill=True))
    text = sim._fn(False, False).lower(
        sim.arrays, sim.faults_arrays, agent.init_state(), jnp.float32(0.0),
        jax.random.PRNGKey(0)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    scan = re.compile(r"mrsch\.scan\.(\w+)")
    assert {p for n in names for p in scan.findall(n)} == SCAN_PHASES
    assert all(len(set(scan.findall(n))) <= 1 for n in names)
    # The phase opens outside the kernel scope (on the CPU the Pallas
    # interpreter repeats the outer path inside the kernel's loops, and
    # its reducer computations carry no path at all).
    kernels = [n for n in names if "mrsch.kernel." in n and scan.search(n)]
    assert all(n.index("mrsch.scan.") < n.index("mrsch.kernel.")
               for n in kernels)
    placed = {(re.search(r"mrsch\.kernel\.(\w+)", n).group(1),
               scan.search(n).group(1)) for n in kernels}
    assert placed == ({("window_pack", "pack"), ("fused_mlp", "score")}
                      | ({("mha_fwd", "score")}
                         if state_module == "attention" else set()))
    outside = [n for n in names if "/cond/" not in n]
    assert not [n for n in outside if n.endswith("/gather")]
    pump = [n for n in outside if n.endswith("/while") and scan.search(n)]
    assert pump and all("/mrsch.scan.advance/" in n for n in pump)
    # The dense layers' weights and biases are padded once, before the
    # scan, under the kernel's scope and no phase; a round pads only x.
    from repro.nn.backend import PaddedDense

    def padded(node):
        return isinstance(node, PaddedDense)
    prepared = jax.eval_shape(agent.prepare_state, agent.init_state())
    layers = list(filter(padded, jax.tree_util.tree_leaves(
        prepared, is_leaf=padded)))
    shapes = ({l.w.shape for l in layers} | {l.b.shape for l in layers}
              | {(l.w.shape[0], l.n) for l in layers})
    pads = [(tuple(int(d) for d in dims.split(",") if d), name)
            for dims, name in re.findall(
                r'\[([\d,]*)\]\S* pad\(.*op_name="([^"]*)"', text)]
    weight_pads = [name for shape, name in pads if shape in shapes]
    assert weight_pads
    assert all("mrsch.kernel.fused_mlp/" in n and not scan.search(n)
               for n in weight_pads)
    ro = sim.rollout()
    assert ro.stats.weights_prepared == len(layers) == {
        "mlp": 3 + 3 + 3 + 2 + 2, "attention": 2 + 6 + 1 + 3 + 3 + 2 + 2,
    }[state_module]
    assert ro.stats.as_dict()["weights_prepared"] == len(layers)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("scenario", ["S2", "diurnal-heavy"])
def test_prepared_weights_take_the_same_actions(scenario, backend):
    """The rollout with the agent's once-per-rollout weight preparation
    takes the actions of one that pads the weights in every round (the
    identity hook), on the registry parity fixtures; on ``xla`` nothing
    is prepared."""
    theta = ThetaConfig.mini(seed=0, duration_days=0.4, jobs_per_day=110)
    res = theta.resources()
    jobsets = [build_jobs(scenario, theta, seed=s) for s in (1, 2)]
    agent = small_agent(res, backend=backend)
    ro = DeviceSimulator(res, jobsets, agent).rollout()
    agent.prepare_state = lambda params: params
    per_round = DeviceSimulator(res, jobsets, agent).rollout()
    assert np.array_equal(ro.actions, per_round.actions)
    assert np.array_equal(ro.decided, per_round.decided)
    assert ro.stats.weights_prepared == (13 if backend == "pallas" else 0)
    assert per_round.stats.weights_prepared == 0


def test_rollout_and_results_open_device_spans(monkeypatch):
    """``rollout()`` opens ``mrsch.device.rollout`` around the dispatch
    and the fetch; building ``results`` opens ``mrsch.device.results``
    once, outside it."""
    import contextlib

    from repro.sim import device
    opened, stack = [], []

    @contextlib.contextmanager
    def record(name):
        stack.append(name)
        opened.append(tuple(stack))
        try:
            yield
        finally:
            stack.pop()

    monkeypatch.setattr(device, "annotate", record)
    ro = DeviceSimulator(RES, [synth_jobs(0, n=12)], FCFSPolicy()).rollout()
    assert opened == [("mrsch.device.rollout",),
                      ("mrsch.device.rollout", "mrsch.device.dispatch"),
                      ("mrsch.device.rollout", "mrsch.device.fetch")]
    opened.clear()
    assert ro.results[0].n_unstarted == 0 and ro.results is ro.results
    assert opened == [("mrsch.device.results",)]
