"""Shared job-lifecycle core for all three scheduling engines.

One explicit state machine::

    HELD -> ELIGIBLE -> QUEUED -> RUNNING -> FINISHED
                           ^          |
                           +-- requeue+---------> FAILED

- HELD:     submitted (or not yet submitted) with unfinished parents.
- ELIGIBLE: all parents finished; waiting out ``think_time`` before the
  job may join the queue.
- QUEUED:   visible to the scheduler (window/backfill candidates).
- RUNNING:  holds cluster units until the attempt ends.
- FINISHED: terminal success; releases children.
- FAILED:   terminal failure — a killed attempt past the requeue bound,
  or (at result time) a cascade from a FAILED ancestor.

The *transition logic* lives here and only here:

- the sequential :class:`~repro.sim.simulator.Simulator` calls the host
  methods on :class:`JobLifecycle` per event (and the lockstep
  ``VectorSimulator`` therefore inherits them per environment);
- the device engine folds the ``device_*`` pure functions below into its
  jitted ``lax.scan`` event pump over masked fixed-capacity arrays.

Queue ordering is part of the contract: the waiting queue is kept sorted
by ``(original submit, jid)`` (:func:`queue_key`).  For dependency-free
traces this equals arrival order, so historic schedules are unchanged;
for requeued or dependency-released jobs it pins one deterministic order
that the packed device engine reproduces by construction (jobs are
packed sorted by the same key).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .cluster import Cluster
from .job import Job

# State constants.  HELD must stay 0: freshly built Jobs default to it.
HELD, ELIGIBLE, QUEUED, RUNNING, FINISHED, FAILED = range(6)
STATE_NAMES = ("HELD", "ELIGIBLE", "QUEUED", "RUNNING", "FINISHED", "FAILED")

#: Attempts a job may lose before it is FAILED permanently: a job is
#: requeued after kill k while ``k <= DEFAULT_MAX_REQUEUES``.
DEFAULT_MAX_REQUEUES = 3

#: Owner id of drained (phantom-reserved) units in the device engine's
#: packed owner array; real jobs are >= 0 and free units are -1.
PHANTOM_OWNER = -2

INF = float("inf")


# --------------------------------------------------------------------------
# Fault schedule
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class DrainEvent:
    """Drain the FIRST ``units`` units of ``resource`` at ``time`` for
    ``duration`` seconds (``inf`` = permanent failure).  Resident jobs are
    killed (whole-job: rigid jobs cannot shrink) and requeued.

    ``unit_frac`` may be given instead of ``units`` so one schedule works
    across cluster sizes; it resolves against capacity at simulation
    setup.  With ``FaultSchedule.relative``, ``time``/``duration`` are
    fractions of the trace's submit span instead of seconds.
    """

    time: float
    resource: str
    units: int = 0
    duration: float = INF
    unit_frac: float = 0.0


@dataclass(frozen=True)
class FaultSchedule:
    """Deterministic per-scenario fault plan (drains + requeue bound)."""

    drains: Tuple[DrainEvent, ...] = ()
    max_requeues: int = DEFAULT_MAX_REQUEUES
    relative: bool = False

    def resolve(self, jobs: Sequence[Job],
                capacities: Dict[str, int]) -> "FaultSchedule":
        """Return an absolute schedule: fractions -> units/seconds, drains
        sorted by time, per-resource overlap rejected (a unit can belong
        to at most one outage at a time)."""
        if self.max_requeues < 0:
            raise ValueError("max_requeues must be >= 0")
        submits = [j.submit for j in jobs]
        t0 = min(submits) if submits else 0.0
        span = max((max(submits) - t0), 1.0) if submits else 1.0
        out = []
        for d in self.drains:
            if d.resource not in capacities:
                raise ValueError(f"drain on unknown resource {d.resource!r}")
            units = d.units or int(round(d.unit_frac * capacities[d.resource]))
            units = max(0, min(units, capacities[d.resource]))
            t, dur = d.time, d.duration
            if self.relative:
                t = t0 + t * span
                dur = dur * span if np.isfinite(dur) else INF
            if dur <= 0:
                raise ValueError("drain duration must be > 0")
            if units > 0:
                out.append(DrainEvent(t, d.resource, units=units, duration=dur))
        out.sort(key=lambda d: (d.time, d.resource))
        last_end: Dict[str, float] = {}
        for d in out:
            if d.time < last_end.get(d.resource, -INF):
                raise ValueError(
                    f"overlapping drains on resource {d.resource!r}")
            last_end[d.resource] = d.time + d.duration
        return FaultSchedule(tuple(out), self.max_requeues, relative=False)


def resolve_faults(faults: Optional[FaultSchedule], jobs: Sequence[Job],
                   capacities: Dict[str, int]) -> FaultSchedule:
    return (faults or FaultSchedule()).resolve(jobs, capacities)


# --------------------------------------------------------------------------
# Queue ordering
# --------------------------------------------------------------------------
def queue_key(job: Job) -> Tuple[float, int]:
    """Deterministic waiting-queue order: original submit time, then jid."""
    return (job.submit, job.jid)


def insert_queued(queue: List[Job], job: Job) -> None:
    """Insert ``job`` into ``queue`` keeping it sorted by :func:`queue_key`.

    Requeued jobs re-enter at their ORIGINAL submit position, so they do
    not lose queue priority to jobs that arrived after them.
    """
    k = queue_key(job)
    lo, hi = 0, len(queue)
    while lo < hi:
        mid = (lo + hi) // 2
        if queue_key(queue[mid]) <= k:
            lo = mid + 1
        else:
            hi = mid
    queue.insert(lo, job)


# --------------------------------------------------------------------------
# Host transition core (sequential + vector engines)
# --------------------------------------------------------------------------
class JobLifecycle:
    """Per-event host transitions over one cluster + one job set.

    The :class:`~repro.sim.simulator.Simulator` owns the event heap and
    the waiting queue; every state change flows through this object so
    the three engines cannot drift apart.
    """

    def __init__(self, jobs: Sequence[Job], cluster: Cluster,
                 faults: Optional[FaultSchedule] = None):
        self.cluster = cluster
        self.jobs = list(jobs)
        self.by_id: Dict[int, Job] = {}
        for j in self.jobs:
            if j.jid in self.by_id:
                raise ValueError(f"duplicate jid {j.jid}")
            j.state = HELD
            self.by_id[j.jid] = j
        # Dangling deps (parent not in this jobset — e.g. sampled
        # sub-traces) are treated as already satisfied.
        self.children: Dict[int, List[Job]] = {}
        for j in self.jobs:
            for d in j.deps:
                if d in self.by_id and d != j.jid:
                    self.children.setdefault(d, []).append(j)
        self.faults = resolve_faults(faults, self.jobs, cluster.capacities)
        self.max_requeues = self.faults.max_requeues
        self.submitted: set = set()
        # "node" anchors the failed-work metric; first resource otherwise.
        self.primary = "node" if "node" in cluster.names else cluster.names[0]

    # ---------------------------------------------------------- eligibility
    def ready_time(self, job: Job) -> float:
        """Time the job may join the queue: ``max(submit, max_parent(end)
        + think_time)``; ``inf`` while any present parent is unfinished."""
        t = job.submit
        for d in job.deps:
            p = self.by_id.get(d)
            if p is None or p is job:
                continue
            if p.state != FINISHED:
                return INF
            t = max(t, p.end + job.think_time)
        return t

    def on_submit(self, job: Job, now: float) -> Tuple[str, float]:
        """Submit event.  Returns ``(outcome, ready)`` where outcome is
        ``"queued"`` (insert now), ``"eligible"`` (schedule a release
        event at ``ready``) or ``"held"`` (parents pending)."""
        self.submitted.add(job.jid)
        r = self.ready_time(job)
        if r <= now:
            job.state = QUEUED
            return "queued", now
        if np.isfinite(r):
            job.state = ELIGIBLE
            return "eligible", r
        return "held", INF

    def on_release(self, job: Job) -> bool:
        """ELIGIBLE -> QUEUED (think-time expiry).  False if stale."""
        if job.state != ELIGIBLE:
            return False
        job.state = QUEUED
        return True

    # ---------------------------------------------------------- run attempts
    def attempt(self, job: Job) -> Tuple[float, bool]:
        """Duration and failure flag of the job's NEXT attempt."""
        k = job.requeues
        if k < len(job.fail_times) and job.fail_times[k] < job.runtime:
            return float(job.fail_times[k]), True
        return job.runtime, False

    def start(self, job: Job, now: float) -> float:
        """QUEUED -> RUNNING.  Allocates units and returns the attempt's
        end time (the failure point for a doomed attempt)."""
        assert job.state == QUEUED, f"start from {STATE_NAMES[job.state]}"
        self.cluster.allocate(job, now)
        dur, _ = self.attempt(job)
        job.end = now + dur
        job.state = RUNNING
        return job.end

    def is_stale_end(self, job: Job, attempt_id: int) -> bool:
        """An end event is stale when its attempt was killed by a drain
        (the job was requeued or failed since the event was scheduled)."""
        return job.state != RUNNING or job.requeues != attempt_id

    def on_end(self, job: Job, now: float) -> Tuple[str, List[Tuple[Job, float]]]:
        """RUNNING attempt reached its scheduled end.

        Returns ``(outcome, released)``: outcome is ``"finished"``,
        ``"requeued"`` or ``"failed"``; ``released`` lists newly eligible
        children as ``(child, ready_time)`` pairs (ready <= now means the
        child joins the queue in this same coalesced timestamp).
        """
        _, fails = self.attempt(job)
        if fails:
            return self.kill(job, now), []
        self.cluster.release_job(job.jid)
        job.state = FINISHED
        return "finished", self._release_children(job, now)

    def _release_children(self, job: Job, now: float) -> List[Tuple[Job, float]]:
        out = []
        for c in self.children.get(job.jid, ()):  # deterministic jobset order
            if c.state != HELD or c.jid not in self.submitted:
                continue
            r = self.ready_time(c)
            if not np.isfinite(r):
                continue
            c.state = QUEUED if r <= now else ELIGIBLE
            out.append((c, max(r, now)))
        return out

    # ---------------------------------------------------------- faults
    def kill(self, job: Job, now: float) -> str:
        """Kill the RUNNING attempt (failure point or drain).  The lost
        work is charged to ``failed_work``; the job re-enters the queue at
        its original position unless the requeue bound is exhausted."""
        assert job.state == RUNNING
        job.failed_work += job.demands.get(self.primary, 0) * (now - job.start)
        self.cluster.release_job(job.jid)
        job.requeues += 1
        job.start = -1.0
        job.end = -1.0
        if job.requeues > self.max_requeues:
            job.state = FAILED
            return "failed"
        job.state = QUEUED
        return "requeued"

    def on_drain(self, d: DrainEvent, now: float) -> List[Tuple[Job, str]]:
        """Apply a drain: kill resident jobs (ascending jid), then mark
        the unit range as phantom-reserved until the restore time."""
        out = []
        for jid in self.cluster.residents(d.resource, d.units):
            job = self.cluster.running[jid].job
            out.append((job, self.kill(job, now)))
        restore_t = d.time + d.duration
        self.cluster.apply_drain(d.resource, d.units, restore_t)
        return out

    def on_restore(self, d: DrainEvent) -> None:
        self.cluster.apply_restore(d.resource, d.units)


# --------------------------------------------------------------------------
# Result-time helpers (shared by every engine's summarize path)
# --------------------------------------------------------------------------
def cascade_failures(jobs: Sequence[Job]) -> int:
    """Mark never-started descendants of FAILED ancestors as FAILED.

    Run at result time: during simulation a HELD child of a failed parent
    simply never becomes eligible, which is indistinguishable from
    starvation; the cascade makes the verdict explicit in the metrics.
    Returns the number of jobs newly marked.
    """
    by_id = {j.jid: j for j in jobs}
    n, changed = 0, True
    while changed:
        changed = False
        for j in jobs:
            if j.state in (FINISHED, FAILED) or j.started:
                continue
            if any(by_id[d].state == FAILED
                   for d in j.deps if d in by_id and d != j.jid):
                j.state = FAILED
                n += 1
                changed = True
    return n


def workflow_components(jobs: Sequence[Job]) -> List[List[Job]]:
    """Connected components of the dependency graph (size >= 2 only)."""
    idx = {j.jid: i for i, j in enumerate(jobs)}
    parent = list(range(len(jobs)))

    def find(a: int) -> int:
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for j in jobs:
        for d in j.deps:
            if d in idx and d != j.jid:
                ra, rb = find(idx[j.jid]), find(idx[d])
                if ra != rb:
                    parent[ra] = rb
    comps: Dict[int, List[Job]] = {}
    for i, j in enumerate(jobs):
        comps.setdefault(find(i), []).append(j)
    return [c for c in comps.values() if len(c) >= 2]


def pipeline_makespan(jobs: Sequence[Job]) -> float:
    """Mean makespan (last end - first submit) over workflow components
    whose every member FINISHED; 0.0 when no component completed."""
    spans = []
    for comp in workflow_components(jobs):
        if all(j.state == FINISHED for j in comp):
            spans.append(max(j.end for j in comp) - min(j.submit for j in comp))
    return float(np.mean(spans)) if spans else 0.0


def work_summary(jobs: Sequence[Job], primary: str) -> Tuple[float, float]:
    """(completed, failed) node-seconds on the ``primary`` resource."""
    completed = sum(j.demands.get(primary, 0) * j.runtime
                    for j in jobs if j.state == FINISHED)
    failed = sum(j.failed_work for j in jobs)
    return float(completed), float(failed)


# --------------------------------------------------------------------------
# Device-side pure transitions (folded into the lax.scan event pump)
# --------------------------------------------------------------------------
# Shapes: N envs, J jobs, P max parents, A max attempts, D drains, U total
# resource units (concatenated segments).  All functions are pure and
# jit-safe; the zero-size fast paths (P == 0, A == 0, D == 0) are Python
# staging-time branches, so dependency-free traces trace exactly the same
# graph they did before the lifecycle core existed.

def device_ready(submit, deps_idx, think, end_t, finished):
    """Earliest queue-entry time per job: ``max(submit, max_parent(end) +
    think)`` while all present parents are finished, else ``+inf``."""
    import jax.numpy as jnp

    n, j, p = deps_idx.shape
    if p == 0:
        return submit
    flat = jnp.clip(deps_idx, 0, j - 1).reshape(n, j * p)
    has = deps_idx >= 0
    pfin = jnp.take_along_axis(finished, flat, axis=1).reshape(n, j, p) & has
    pend = jnp.take_along_axis(end_t, flat, axis=1).reshape(n, j, p)
    all_done = jnp.where(has, pfin, True).all(axis=2)
    pmax = jnp.where(pfin, pend, -jnp.inf).max(axis=2)
    ready = jnp.maximum(submit, pmax + think)
    return jnp.where(all_done, ready, jnp.inf)


def device_queued(ready, now, started, finished, failed):
    """QUEUED mask: eligible by ``now`` and not in any other live state."""
    return (ready <= now[:, None]) & ~started & ~finished & ~failed


def device_attempt(fail_times, requeues, runtime):
    """(duration, will_fail) of each job's NEXT attempt."""
    import jax.numpy as jnp

    if fail_times.shape[2] == 0:
        return runtime, jnp.zeros(runtime.shape, bool)
    a = fail_times.shape[2]
    k = jnp.clip(requeues, 0, a - 1)[..., None]
    ft = jnp.take_along_axis(fail_times, k, axis=2)[..., 0]
    ft = jnp.where(requeues < a, ft, jnp.inf)
    will_fail = ft < runtime
    return jnp.where(will_fail, ft, runtime), will_fail


def device_free_units(mask_j, release, owner):
    """Free every unit owned by a job in ``mask_j`` (N, J).

    Returns ``(release, owner, passes)``.  Each pass clears, in every
    environment at once, the units of its lowest-index job still set,
    by comparing ``owner`` with that job; ``passes`` (int32) is the most
    jobs any one environment frees, so a call with nothing to free costs
    one ``any`` and no pass over the (N, U) units.  Free (-1) and
    phantom (``PHANTOM_OWNER``) units never equal a job index."""
    import jax
    import jax.numpy as jnp

    jidx = jnp.arange(mask_j.shape[1], dtype=owner.dtype)

    def body(c):
        rem, release, owner, passes = c
        j = jnp.argmax(rem, axis=1).astype(owner.dtype)
        hit = (owner == j[:, None]) & rem.any(axis=1)[:, None]
        return (rem & (jidx[None, :] != j[:, None]),
                jnp.where(hit, 0.0, release), jnp.where(hit, -1, owner),
                passes + 1)

    _, release, owner, passes = jax.lax.while_loop(
        lambda c: c[0].any(), body, (mask_j, release, owner, jnp.int32(0)))
    return release, owner, passes


def device_kill(killed, now, demands, node_idx, max_requeues, st):
    """Kill RUNNING attempts in ``killed`` (N, J): free their units,
    charge the lost work, and either requeue (original queue position —
    ordering is by packed job index) or mark FAILED past the bound.
    Mutates-and-returns the relevant entries of the state dict ``st``."""
    import jax.numpy as jnp

    # where() not arithmetic masking: ``now`` is +inf for envs with no
    # event this round, and inf * 0.0 would poison the area with NaN.
    run_t = jnp.where(killed, jnp.maximum(now[:, None] - st["start"], 0.0),
                      0.0)
    work = demands * run_t[..., None]                      # (N, J, R)
    st["failed_area"] = st["failed_area"] + work.sum(axis=1)
    st["failed_work"] = st["failed_work"] + work[..., node_idx]
    st["release"], st["owner"], passes = device_free_units(
        killed, st["release"], st["owner"])
    st["free_passes"] = st["free_passes"] + passes
    st["requeues"] = st["requeues"] + killed
    st["failed"] = st["failed"] | (killed & (st["requeues"] > max_requeues))
    st["started"] = st["started"] & ~killed
    st["start"] = jnp.where(killed, -1.0, st["start"])
    st["end"] = jnp.where(killed, jnp.inf, st["end"])
    st["cur_fail"] = st["cur_fail"] & ~killed
    return st


def device_apply_ends(t, act, demands, node_idx, max_requeues, st,
                      has_kills=True):
    """Apply every attempt-end scheduled at ``t``: clean finishes release
    units and go FINISHED; failure points are killed/requeued.
    ``has_kills=False`` (a staging-time constant) skips the kill graph
    entirely for traces with no failure points and no drains."""
    running = st["started"] & ~st["finished"]
    due = running & (st["end"] == t[:, None]) & act[:, None]
    fin = due & ~st["cur_fail"] if has_kills else due
    st["finished"] = st["finished"] | fin
    st["release"], st["owner"], passes = device_free_units(
        fin, st["release"], st["owner"])
    st["free_passes"] = st["free_passes"] + passes
    if has_kills:
        st = device_kill(due & st["cur_fail"], t, demands, node_idx,
                         max_requeues, st)
    return st


def device_apply_drains(t, act, faults, demands, node_idx, st):
    """Fire drains scheduled at ``t``: kill residents of the unit range,
    then phantom-reserve it (owner = PHANTOM_OWNER) until restore."""
    import jax.numpy as jnp

    n, u = st["release"].shape
    jmax = st["started"].shape[1]
    env = jnp.arange(n)[:, None]
    for d in range(faults.drain_t.shape[1]):
        fire = act & (faults.drain_t[:, d] == t) & ~st["drain_done"][:, d]
        in_range = (faults.unit_seg[None, :] == faults.drain_res[:, d:d + 1]) \
            & (faults.unit_local[None, :] < faults.drain_units[:, d:d + 1])
        kill_u = fire[:, None] & in_range & (st["owner"] >= 0)
        killed = jnp.zeros((n, jmax), bool).at[
            env, jnp.maximum(st["owner"], 0)].max(kill_u)
        st = device_kill(killed, t, demands, node_idx,
                         faults.max_requeues, st)
        phantom = fire[:, None] & in_range
        st["release"] = jnp.where(
            phantom, faults.restore_t[:, d:d + 1], st["release"])
        st["owner"] = jnp.where(phantom, PHANTOM_OWNER, st["owner"])
        st["drain_done"] = st["drain_done"].at[:, d].max(fire)
    return st


def device_apply_restores(t, act, faults, st):
    """Return phantom units of elapsed drains to the free pool."""
    import jax.numpy as jnp

    for d in range(faults.drain_t.shape[1]):
        fire = act & (faults.restore_t[:, d] == t) \
            & st["drain_done"][:, d] & ~st["restore_done"][:, d]
        in_range = (faults.unit_seg[None, :] == faults.drain_res[:, d:d + 1]) \
            & (faults.unit_local[None, :] < faults.drain_units[:, d:d + 1])
        clear = fire[:, None] & in_range & (st["owner"] == PHANTOM_OWNER)
        st["release"] = jnp.where(clear, 0.0, st["release"])
        st["owner"] = jnp.where(clear, -1, st["owner"])
        st["restore_done"] = st["restore_done"].at[:, d].max(fire)
    return st


def device_next_event(now, ready, end_t, started, finished, failed, faults,
                      st):
    """Next event time per env: min over pending queue-entries, running
    ends, un-fired drains and un-fired restores (inf when drained)."""
    import jax.numpy as jnp

    pending = ~started & ~finished & ~failed & (ready > now[:, None])
    nxt = jnp.where(pending, ready, jnp.inf).min(axis=1)
    running = started & ~finished
    nxt = jnp.minimum(nxt, jnp.where(running, end_t, jnp.inf).min(axis=1))
    if faults is not None and faults.drain_t.shape[1]:
        nxt = jnp.minimum(nxt, jnp.where(
            ~st["drain_done"], faults.drain_t, jnp.inf).min(axis=1))
        nxt = jnp.minimum(nxt, jnp.where(
            st["drain_done"] & ~st["restore_done"], faults.restore_t,
            jnp.inf).min(axis=1))
    return nxt
