"""Simulator invariants: allocation, EASY backfill, metrics."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.sim import Cluster, Job, ResourceSpec, SimConfig, Simulator, run_trace
from repro.core import FCFSPolicy


def mk_jobs(spec):
    return [Job(jid=i, submit=s, runtime=r, walltime=w, demands=dict(d))
            for i, (s, r, w, d) in enumerate(spec)]


def test_cluster_allocate_release():
    c = Cluster([ResourceSpec("node", 8), ResourceSpec("bb", 4)])
    j = Job(0, 0.0, 100.0, 120.0, {"node": 5, "bb": 2})
    assert c.fits(j)
    c.allocate(j, 10.0)
    assert c.free == {"node": 3, "bb": 2}
    enc = c.unit_encoding(now=50.0)
    assert enc["node"][:, 0].sum() == 3          # 3 free units
    busy_ttf = enc["node"][enc["node"][:, 0] == 0, 1]
    assert np.allclose(busy_ttf, 80.0)           # est end 130 - now 50
    c.release_job(0)
    assert c.free == {"node": 8, "bb": 4}


def test_earliest_fit_time_orders_releases():
    c = Cluster([ResourceSpec("node", 4)])
    c.allocate(Job(0, 0, 100, 100, {"node": 2}), 0.0)
    c.allocate(Job(1, 0, 50, 60, {"node": 2}), 0.0)
    big = Job(2, 0, 10, 10, {"node": 4})
    assert c.earliest_fit_time(big, 0.0) == 100.0   # needs both releases


def test_fcfs_reservation_blocks_greedy_backfill():
    """A long job must not backfill past the reserved head-of-queue job."""
    jobs = mk_jobs([
        (0.0, 100.0, 100.0, {"node": 3}),       # leaves one node free
        (1.0, 10.0, 10.0, {"node": 4}),         # head: reserved at t=100
        (2.0, 500.0, 500.0, {"node": 1}),       # would delay head if started
        (3.0, 50.0, 50.0, {"node": 1}),         # fits before t=100: backfill
    ])
    res = [ResourceSpec("node", 4)]
    r = run_trace(res, jobs, FCFSPolicy())
    by = {j.jid: j for j in r.jobs}
    assert by[1].start == pytest.approx(100.0)     # reservation honored
    assert by[3].start < 100.0                     # short job backfilled
    assert by[2].start >= 100.0                    # long job did NOT jump


def test_backfill_shadow_resources():
    """Backfill allowed when it doesn't intersect the reservation."""
    jobs = mk_jobs([
        (0.0, 100.0, 100.0, {"node": 3}),
        (1.0, 10.0, 10.0, {"node": 4}),          # reserved at 100
        (2.0, 1000.0, 1000.0, {"node": 1}),      # uses the 1 free node
    ])
    r = run_trace([ResourceSpec("node", 4)], jobs, FCFSPolicy(),
                  backfill=True)
    by = {j.jid: j for j in r.jobs}
    # job 2 finishing long after 100 would steal the head's nodes -> no
    assert by[2].start >= by[1].start


def test_backfill_shadow_accounting_multi_resource():
    """A backfill candidate that fits *now* but would occupy the
    reservation's shadow units must not start; one that stays inside the
    shadow may, and it debits the shadow for later candidates."""
    jobs = mk_jobs([
        (0.0, 100.0, 100.0, {"node": 3, "bb": 0}),   # A: leaves node=1,bb=4
        (1.0, 10.0, 10.0, {"node": 2, "bb": 4}),     # B: head, reserved @100
        (2.0, 500.0, 500.0, {"node": 1, "bb": 1}),   # C: fits now, bb breaks
                                                     #    B's shadow -> wait
        (3.0, 500.0, 500.0, {"node": 1, "bb": 0}),   # D: inside shadow -> go
    ])
    res = [ResourceSpec("node", 4), ResourceSpec("bb", 4)]
    r = run_trace(res, jobs, FCFSPolicy())
    by = {j.jid: j for j in r.jobs}
    assert by[3].start == pytest.approx(3.0)       # D backfilled immediately
    assert by[1].start == pytest.approx(100.0)     # reservation honored
    assert by[2].start >= 100.0                    # C kept out of the shadow


def test_backfill_shadow_debits_accumulate():
    """Two candidates that each fit the shadow alone must not BOTH start
    when together they exceed it (the running-shadow bookkeeping)."""
    jobs = mk_jobs([
        (0.0, 100.0, 100.0, {"node": 2}),            # A: leaves 2 free
        (1.0, 10.0, 10.0, {"node": 3}),              # B: reserved @100,
                                                     #    shadow = 4-3 = 1
        (2.0, 500.0, 500.0, {"node": 1}),            # C: fills the shadow
        (3.0, 500.0, 500.0, {"node": 1}),            # D: shadow exhausted
    ])
    r = run_trace([ResourceSpec("node", 4)], jobs, FCFSPolicy())
    by = {j.jid: j for j in r.jobs}
    assert by[2].start == pytest.approx(2.0)
    assert by[1].start == pytest.approx(100.0)
    assert by[3].start >= 100.0                    # NOT also backfilled


@settings(max_examples=25, deadline=None)
@given(st.lists(
    st.tuples(st.floats(0, 1000), st.floats(1, 500), st.floats(0, 400),
              st.integers(1, 8), st.integers(0, 4)),
    min_size=1, max_size=40))
def test_simulator_invariants(spec):
    """Property: every job runs exactly once, never before submit, and
    capacity is never exceeded at any event time."""
    jobs = [Job(jid=i, submit=s, runtime=r, walltime=r + w,
                demands={"node": n, "bb": b})
            for i, (s, r, w, n, b) in enumerate(spec)]
    res = [ResourceSpec("node", 8), ResourceSpec("bb", 4)]
    r = run_trace(res, jobs, FCFSPolicy())
    assert len(r.jobs) == len(jobs)
    for j in r.jobs:
        assert j.start >= j.submit - 1e-9
        assert j.end == pytest.approx(j.start + j.runtime)
    # capacity check at every start event
    events = sorted(j.start for j in r.jobs)
    for t in events:
        for name, cap in (("node", 8), ("bb", 4)):
            used = sum(j.demands.get(name, 0) for j in r.jobs
                       if j.start <= t < j.end)
            assert used <= cap


def test_metrics_utilization_bounds():
    jobs = mk_jobs([(0.0, 100.0, 100.0, {"node": 4, "bb": 0})])
    r = run_trace([ResourceSpec("node", 4), ResourceSpec("bb", 2)], jobs,
                  FCFSPolicy())
    assert r.metrics.utilization["node"] == pytest.approx(1.0, abs=1e-6)
    assert r.metrics.utilization["bb"] == 0.0
    assert r.metrics.avg_wait == 0.0
    assert r.metrics.avg_slowdown == pytest.approx(1.0)


def test_metrics_as_row_covers_every_field():
    """Regression: ``as_row`` once silently dropped ``max_wait``, so every
    sweep/bench CSV lost the tail-latency column.  Pin that each dataclass
    field appears in the row (utilization expands to util_<name>)."""
    import dataclasses

    from repro.sim.metrics import ScheduleMetrics

    m = ScheduleMetrics(utilization={"node": 0.5, "bb": 0.25}, avg_wait=1.0,
                        avg_slowdown=2.0, avg_bounded_slowdown=1.5,
                        p95_wait=7.0, max_wait=9.0, n_jobs=3, makespan=10.0)
    row = m.as_row()
    for f in dataclasses.fields(ScheduleMetrics):
        if f.name == "utilization":
            continue
        assert row[f.name] == getattr(m, f.name), f.name
    assert row["util_node"] == 0.5 and row["util_bb"] == 0.25
    assert len(row) == len(dataclasses.fields(ScheduleMetrics)) - 1 + 2


def test_truncated_jobs_counts_queue_beyond_window():
    """Regression pin for ``truncated_jobs``: waiting jobs the W-window
    encoding cannot see, summed over decisions, identical across engines.

    Six full-machine jobs all submit at t=0 with window=2, so exactly one
    runs at a time and every decision point is deterministic.  Each
    event yields one decision with k jobs waiting (truncated k-2), then a
    follow-up decision after one start with k-1 waiting (truncated k-3):
    (4+3) + (3+2) + (2+1) + (1+0) + (0+0) + 0 = 16.
    """
    from repro.sim import run_traces, run_traces_device

    jobs = [Job(jid=i, submit=0.0, runtime=100.0, walltime=100.0,
                demands={"node": 4}) for i in range(6)]
    res = [ResourceSpec("node", 4)]
    seq = run_trace(res, jobs, FCFSPolicy(), window=2)
    assert seq.truncated_jobs == 16
    assert seq.metrics.truncated_jobs == 16
    assert seq.metrics.as_row()["truncated_jobs"] == 16
    vec = run_traces(res, [jobs], FCFSPolicy(), window=2)[0]
    dev = run_traces_device(res, [jobs], FCFSPolicy(),
                            SimConfig.for_engine("device", window=2))[0]
    assert vec.truncated_jobs == dev.truncated_jobs == 16
    # A window wide enough for the whole trace truncates nothing.
    assert run_trace(res, jobs, FCFSPolicy(), window=8).truncated_jobs == 0


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(st.lists(
    st.tuples(st.integers(0, 60), st.integers(1, 300), st.integers(0, 200),
              st.integers(1, 8), st.integers(0, 4)),
    min_size=1, max_size=25))
def test_three_engines_agree_on_f32_exact_traces(spec):
    """Property: the sequential, vector, and device engines produce the
    same schedule.  Times are drawn as integers, which float32 represents
    exactly (< 2**24), so the device engine's f32 clock can introduce no
    rounding and no event-time collisions — every derived metric must
    match across all three engines to numerical noise."""
    from repro.sim import run_traces, run_traces_device

    jobs, t = [], 0.0
    for i, (gap, r, w, n, b) in enumerate(spec):
        t += gap
        jobs.append(Job(jid=i, submit=t, runtime=float(r),
                        walltime=float(r + w),
                        demands={"node": n, "bb": b}))
    res = [ResourceSpec("node", 8), ResourceSpec("bb", 4)]
    seq = run_trace(res, jobs, FCFSPolicy())
    vec = run_traces(res, [jobs], FCFSPolicy())[0]
    dev = run_traces_device(res, [jobs], FCFSPolicy())[0]
    for other in (vec, dev):
        assert other.decisions == seq.decisions
        assert other.n_unstarted == seq.n_unstarted
        ra, rb = seq.metrics.as_row(), other.metrics.as_row()
        for k in ra:
            assert rb[k] == pytest.approx(ra[k], rel=1e-6, abs=1e-6), k
        for ja, jb in zip(seq.jobs, other.jobs):
            assert (ja.jid, ja.started) == (jb.jid, jb.started)
            if ja.started:
                assert jb.start == pytest.approx(ja.start, abs=1e-3)
