"""Unified job-lifecycle core: state-machine transitions, workflow DAGs,
requeue-on-failure, fault injection — unit coverage of
``repro.sim.lifecycle`` plus the acceptance pins: three-engine parity on
a workflow and a fault scenario (both NN backends) and a hypothesis
property that topological eligibility order is never violated."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import AgentConfig, FCFSPolicy, MRSchAgent
from repro.sim import (FAILED, FINISHED, DeviceSimulator, DrainEvent,
                       FaultSchedule, Job, ResourceSpec, SimConfig,
                       Simulator, VectorSimulator, pipeline_makespan,
                       workflow_components)
from repro.sim.lifecycle import PHANTOM_OWNER, device_free_units
from repro.workloads import ThetaConfig, build_jobs, get_scenario

RES = [ResourceSpec("node", 4)]


def run_seq(jobs, resources=RES, faults=None, policy=None):
    return Simulator(resources, jobs, policy or FCFSPolicy(), SimConfig(),
                     faults=faults).run()


# ------------------------------------------------------------- transitions
def test_dependency_holds_child_until_parent_finishes():
    jobs = [
        Job(0, 0.0, 100.0, 100.0, {"node": 1}),
        Job(1, 0.0, 50.0, 50.0, {"node": 1}, deps=(0,), think_time=30.0),
    ]
    r = run_seq(jobs)
    parent, child = r.jobs
    assert parent.state == FINISHED and child.state == FINISHED
    # Nodes were free the whole time: only the dependency gated the child.
    assert child.start == pytest.approx(parent.end + 30.0)
    assert r.metrics.pipeline_makespan == pytest.approx(child.end - 0.0)


def test_fan_in_waits_for_all_parents():
    jobs = [
        Job(0, 0.0, 60.0, 60.0, {"node": 1}),
        Job(1, 0.0, 200.0, 200.0, {"node": 1}),
        Job(2, 0.0, 10.0, 10.0, {"node": 1}, deps=(0, 1)),
    ]
    r = run_seq(jobs)
    ends = {j.jid: j.end for j in r.jobs}
    assert r.jobs[2].start == pytest.approx(max(ends[0], ends[1]))
    assert len(workflow_components(r.jobs)) == 1


def test_failure_requeues_then_finishes():
    jobs = [Job(0, 0.0, 100.0, 100.0, {"node": 4}, fail_times=(40.0,))]
    r = run_seq(jobs)
    (j,) = r.jobs
    # Attempt 1 dies at t=40, re-enters the queue, attempt 2 completes.
    assert j.state == FINISHED and j.requeues == 1
    assert j.first_start == 0.0 and j.start == pytest.approx(40.0)
    assert j.end == pytest.approx(140.0)
    assert r.metrics.requeues == 1 and r.metrics.n_failed == 0
    assert r.metrics.failed_node_hours == pytest.approx(4 * 40.0 / 3600.0)
    assert r.metrics.completed_work_frac == pytest.approx(
        400.0 / (400.0 + 160.0))


def test_requeue_bound_exhaustion_fails_job():
    faults = FaultSchedule(max_requeues=1)
    jobs = [Job(0, 0.0, 100.0, 100.0, {"node": 1},
                fail_times=(10.0, 10.0, 10.0))]
    r = run_seq(jobs, faults=faults)
    (j,) = r.jobs
    # Two kills exhaust max_requeues=1; the final kill is not a re-entry.
    assert j.state == FAILED and j.requeues == 2
    assert r.metrics.n_failed == 1 and r.metrics.requeues == 1
    assert r.metrics.completed_work_frac == 0.0


def test_parent_failure_cascades_to_held_children():
    faults = FaultSchedule(max_requeues=0)
    jobs = [
        Job(0, 0.0, 100.0, 100.0, {"node": 1}, fail_times=(10.0,)),
        Job(1, 0.0, 50.0, 50.0, {"node": 1}, deps=(0,)),
        Job(2, 0.0, 50.0, 50.0, {"node": 1}, deps=(1,)),
    ]
    r = run_seq(jobs, faults=faults)
    assert [j.state for j in r.jobs] == [FAILED, FAILED, FAILED]
    assert r.metrics.n_failed == 3
    assert r.metrics.pipeline_makespan == 0.0


def test_drain_kills_residents_and_restores():
    faults = FaultSchedule(drains=(
        DrainEvent(time=30.0, resource="node", units=4, duration=20.0),))
    jobs = [Job(0, 0.0, 100.0, 100.0, {"node": 2})]
    r = run_seq(jobs, faults=faults)
    (j,) = r.jobs
    # Killed by the drain at t=30; nodes return at t=50; reruns to 150.
    assert j.state == FINISHED and j.requeues == 1
    assert j.first_start == 0.0
    assert j.start == pytest.approx(50.0) and j.end == pytest.approx(150.0)
    assert r.metrics.failed_node_hours == pytest.approx(2 * 30.0 / 3600.0)


def test_wait_counts_from_first_submission_regression():
    """Pinned: a requeued-then-finished job's wait is measured from its
    ORIGINAL submission to its FIRST start — the kill must not reset it."""
    jobs = [
        Job(0, 0.0, 100.0, 100.0, {"node": 4}),
        Job(1, 10.0, 100.0, 100.0, {"node": 4}, fail_times=(20.0,)),
    ]
    r = run_seq(jobs)
    j1 = r.jobs[1]
    assert j1.first_start == pytest.approx(100.0)
    assert j1.wait == pytest.approx(90.0)
    assert r.metrics.avg_wait == pytest.approx(45.0)


def test_requeued_job_keeps_original_queue_position():
    """A killed job re-enters at its original submit rank, ahead of
    later arrivals that were still waiting."""
    jobs = [
        Job(0, 0.0, 100.0, 100.0, {"node": 4}, fail_times=(50.0,)),
        Job(1, 1.0, 100.0, 100.0, {"node": 4}),
        Job(2, 2.0, 100.0, 100.0, {"node": 4}),
    ]
    r = run_seq(jobs)
    starts = {j.jid: j.start for j in r.jobs}
    assert starts[0] == pytest.approx(50.0)      # retries immediately
    assert starts[1] == pytest.approx(150.0) and starts[2] == pytest.approx(250.0)


def test_fault_schedule_rejects_overlapping_drains():
    faults = FaultSchedule(drains=(
        DrainEvent(time=10.0, resource="node", units=2, duration=50.0),
        DrainEvent(time=30.0, resource="node", units=2, duration=10.0),
    ))
    with pytest.raises(ValueError, match="overlap"):
        run_seq([Job(0, 0.0, 10.0, 10.0, {"node": 1})], faults=faults)


def test_relative_fault_schedule_resolves_against_span():
    faults = FaultSchedule(relative=True, drains=(
        DrainEvent(time=0.5, resource="node", unit_frac=0.5, duration=0.25),))
    jobs = [Job(0, 0.0, 10.0, 10.0, {"node": 1}),
            Job(1, 100.0, 10.0, 10.0, {"node": 1})]
    resolved = faults.resolve(jobs, {"node": 4})
    (d,) = resolved.drains
    assert (d.time, d.units, d.duration) == (50.0, 2, 25.0)


def test_pipeline_makespan_averages_completed_components_only():
    jobs = [
        Job(0, 0.0, 10.0, 10.0, {"node": 1}),
        Job(1, 0.0, 10.0, 10.0, {"node": 1}, deps=(0,)),
        Job(2, 5.0, 10.0, 10.0, {"node": 1}),
        Job(3, 5.0, 10.0, 10.0, {"node": 1}, deps=(2,)),
    ]
    r = run_seq(jobs)
    comp_spans = []
    for comp in workflow_components(r.jobs):
        comp_spans.append(max(j.end for j in comp)
                          - min(j.submit for j in comp))
    assert r.metrics.pipeline_makespan == pytest.approx(np.mean(comp_spans))
    assert pipeline_makespan(r.jobs) == r.metrics.pipeline_makespan


# --------------------------------------------------- device unit release
def free_units_case(case: str, seed: int):
    """(mask (N, J), release (N, U), owner (N, U)): owners mix free (-1),
    phantom and job units at random, releases are nonzero where owned."""
    rng = np.random.default_rng(seed)
    n, j, u = 3, 8, 40
    owner = rng.choice(np.r_[-1, PHANTOM_OWNER, np.arange(j)],
                       size=(n, u)).astype(np.int32)
    release = np.where(owner == -1, 0.0,
                       rng.uniform(1.0, 1e4, (n, u))).astype(np.float32)
    mask = rng.uniform(size=(n, j)) < 0.4
    if case == "all_false":
        mask[:] = False
    elif case == "uneven":            # several jobs in env 0, none in env 1
        mask[:] = False
        mask[0, rng.choice(j, size=5, replace=False)] = True
        mask[2, int(rng.integers(j))] = True
    elif case == "non_contiguous":    # job 4 holds units apart in env 1
        owner[1][owner[1] == 4] = 5
        owner[1, [2, 9, 10, 31]] = 4
        release[1, [2, 9, 10, 31]] = 500.0
        mask[1] = False
        mask[1, 4] = True
    return mask, release, owner


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", ["random", "all_false", "uneven",
                                  "non_contiguous"])
def test_device_free_units_matches_owner_gather(case, seed):
    """The pass-per-job release frees exactly what gathering the mask by
    every unit's owner frees, in as many passes as the most jobs one
    environment frees."""
    import jax

    mask, release, owner = free_units_case(case, seed)
    hit = np.take_along_axis(mask, np.maximum(owner, 0), axis=1) \
        & (owner >= 0)
    got_release, got_owner, passes = jax.jit(device_free_units)(
        mask, release, owner)
    np.testing.assert_array_equal(np.asarray(got_release),
                                  np.where(hit, 0.0, release))
    np.testing.assert_array_equal(np.asarray(got_owner),
                                  np.where(hit, -1, owner))
    assert int(passes) == mask.sum(axis=1).max()
    if case == "non_contiguous":
        assert (np.asarray(got_owner)[1, [2, 9, 10, 31]] == -1).all()


# ------------------------------------------------- three-engine parity pins
def small_agent(resources, seed: int = 0, backend: str = "xla") -> MRSchAgent:
    return MRSchAgent(resources, AgentConfig(
        state_hidden=(32, 16), state_out=8, module_hidden=4, seed=seed,
        backend=backend))


def assert_lifecycle_parity(a, b):
    """Engine results agree on schedule AND lifecycle accounting (host
    f64 vs device f32 clock: ~1e-2 s slack on times)."""
    assert a.decisions == b.decisions
    assert a.n_unstarted == b.n_unstarted
    ra, rb = a.metrics.as_row(), b.metrics.as_row()
    assert ra["requeues"] == rb["requeues"]
    assert ra["n_failed"] == rb["n_failed"]
    assert np.isclose(ra["makespan"], rb["makespan"], atol=1e-2)
    assert np.isclose(ra["pipeline_makespan"], rb["pipeline_makespan"],
                      rtol=1e-5, atol=1e-2)
    assert np.isclose(ra["completed_work_frac"], rb["completed_work_frac"],
                      atol=1e-4)
    assert np.isclose(ra["avg_wait"], rb["avg_wait"], rtol=1e-5, atol=1e-2)
    for ja, jb in zip(a.jobs, b.jobs):
        assert ja.jid == jb.jid and ja.started == jb.started
        assert ja.state == jb.state and ja.requeues == jb.requeues
        if ja.started:
            assert np.isclose(ja.first_start, jb.first_start,
                              rtol=1e-6, atol=1e-2)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("scenario", ["workflow-pipelines", "faulty-drain"])
def test_three_engine_parity_lifecycle(scenario, backend):
    """Acceptance pin: N=1 device and vector reproduce the sequential
    engine round for round on a workflow-DAG and a fault-injection
    scenario, on both NN backends."""
    theta = ThetaConfig.mini(seed=0, duration_days=0.4, jobs_per_day=110)
    res = theta.resources()
    jobs = build_jobs(scenario, theta, seed=1)
    faults = get_scenario(scenario).faults
    agent = small_agent(res, backend=backend)
    seq = run_seq(jobs, resources=res, faults=faults, policy=agent)
    vec = VectorSimulator.from_jobsets(
        res, [jobs], agent, SimConfig.for_engine("vector"),
        faults=faults).run()[0]
    dev = DeviceSimulator(res, [jobs], agent, faults=faults).rollout().results[0]
    assert_lifecycle_parity(seq, vec)
    assert_lifecycle_parity(seq, dev)
    # The scenario exercised what it claims to exercise.
    if scenario.startswith("workflow"):
        assert seq.metrics.pipeline_makespan > 0.0
    else:
        assert seq.metrics.requeues > 0


def test_device_parity_fcfs_faulty_jobs_multi_env():
    """FCFS over per-env fault traces: device matches sequential per env."""
    theta = ThetaConfig.mini(seed=0, duration_days=0.3, jobs_per_day=100)
    res = theta.resources()
    jobsets = [build_jobs("faulty-jobs", theta, seed=s) for s in (1, 2)]
    ro = DeviceSimulator(res, jobsets, FCFSPolicy()).rollout()
    for i, jobs in enumerate(jobsets):
        seq = run_seq(jobs, resources=res)
        assert_lifecycle_parity(seq, ro.results[i])
    assert sum(r.metrics.requeues for r in ro.results) > 0


# ----------------------------------------------- topological-order property
def dag_jobset(seed: int):
    """Random DAG jobset: up to 2 parents per job (always earlier jids, so
    acyclic by construction), random arrival order, half the jobs carry a
    mid-run failure point."""
    rng = np.random.default_rng(seed)
    jobs = []
    for i in range(int(rng.integers(3, 11))):
        deps = ()
        if i and rng.uniform() < 0.6:
            k = int(rng.integers(1, min(i, 2) + 1))
            deps = tuple(sorted(rng.choice(i, size=k, replace=False)
                                .tolist()))
        runtime = float(rng.integers(10, 201))
        jobs.append(Job(
            jid=i, submit=float(rng.integers(0, 401)),
            runtime=runtime, walltime=runtime,
            demands={"node": int(rng.integers(1, 5))},
            deps=deps, think_time=float(rng.integers(0, 61)),
            fail_times=((runtime / 2,) if rng.uniform() < 0.5 else ())))
    return jobs


@given(seed=st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_topological_eligibility_never_violated(seed):
    """No attempt of a child may start before every parent FINISHED plus
    the child's think time — under arbitrary DAGs, arrival orders, and
    mid-run failures."""
    r = run_seq(dag_jobset(seed))
    by_id = {j.jid: j for j in r.jobs}
    for j in r.jobs:
        if not j.started:
            continue
        for d in j.deps:
            p = by_id[d]
            assert p.state == FINISHED
            assert j.first_start >= p.end + j.think_time - 1e-6
        assert j.first_start >= j.submit
