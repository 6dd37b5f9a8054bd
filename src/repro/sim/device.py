"""Device-resident rollout engine: the whole simulation loop in one jit.

``DeviceSimulator`` runs N independent trace simulations as ONE device
program: a ``lax.scan`` over scheduling rounds whose body advances
lifecycle events (one coalesced-timestamp pop per round, which the round
budget covers), packs the first-W waiting jobs per environment
(``repro.kernels.window_pack``), builds the packed decision rows
in-graph, scores them with the policy's pure ``score_window`` stage
(``repro.core.policy_api``), and applies the selected action — immediate
start with first-free unit allocation, or a reservation with
EASY-backfill shadow accounting.  The host engines pay a Python round
trip per scheduling round; here the only host work is packing the traces
up front and summarizing metrics at the end.

The job lifecycle (``repro.sim.lifecycle``) is folded into the pump via
the pure ``device_*`` transitions: per-job READY times replace the old
arrival pointer (``max(submit, max_parent(end) + think)``, ``+inf``
while a parent is unfinished), attempt ends are attempt-aware (a
failure-point attempt is killed and requeued instead of finishing),
and drain/restore events kill residents and phantom-reserve unit ranges
(owner ``PHANTOM_OWNER``) exactly like the host's ``JobLifecycle``.
Traces without dependencies, failure points, or drains stage the same
lean graph as before — the extra transitions are Python staging-time
branches on zero-size axes.

State layout (leading axis = environment):

* job arrays ``(N, J)`` — submit/runtime/walltime (f32, padded jobs
  carry ``submit = +inf`` so they never arrive) and demands ``(N, J, R)``
  (f32 unit counts; exact below 2**24); dependency indices ``(N, J, P)``
  (packed job index, -1 = none), think times ``(N, J)`` and failure
  points ``(N, J, A)`` (+inf padded);
* lifecycle state ``(N, J)`` — ``ready``/``started``/``finished``/
  ``failed`` masks, ``requeues``/``cur_fail`` attempt state,
  ``first_start_j``/``failed_work`` accounting; the waiting queue in
  (original submit, jid) order is exactly "ready and in no other live
  state, in ascending job index", which is what the window-pack kernel
  assumes (requeued jobs re-enter at their original position for free);
* per-unit cluster state ``(N, U)`` with ``U = sum(capacities)`` —
  ``release`` (estimated release time, 0 = free, mirroring
  ``Cluster.release``; drained units carry their restore time) and
  ``owner`` (job index, -1 free, -2 phantom/drained), in fixed
  per-resource segments;
* scalars per env — ``now``, ``in_pass``, ``done``, ``decisions``.

Semantics mirror ``Simulator`` event for event (coalesced timestamps
applied ends -> queue entries -> drains -> restores, scheduling-pass
continuation, first-free unit allocation, reservation at the earliest
fit time, shadow-debit backfill in queue order), so an N=1 rollout
reproduces the sequential engine round for round; times are float32 on
device, so derived metrics agree to float32 precision (pinned in
``tests/test_device.py``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.window_pack.ops import pack_window
from ..obs.profiling import annotate
from ..obs.trace import Tracer
from .cluster import TTF_HORIZON, Cluster, ResourceSpec
from .job import Job
from .lifecycle import (FAILED, FINISHED, FaultSchedule, device_apply_drains,
                        device_apply_ends, device_apply_restores,
                        device_attempt, device_next_event, device_queued,
                        device_ready, resolve_faults)
from .metrics import MetricsAccumulator
from .simulator import SimConfig, SimResult

INF = jnp.float32(jnp.inf)
HIGHEST = jax.lax.Precision.HIGHEST


class DeviceFaults(NamedTuple):
    """Packed fault schedules, one row per environment (D = max drains).

    Unused drain slots carry ``drain_t = +inf`` so they never fire;
    ``unit_seg``/``unit_local`` map every packed unit to its (resource
    segment, within-segment index) so a drain's "first k units of
    resource r" range is one vectorized compare."""
    drain_t: jnp.ndarray        # (N, D) f32, +inf = unused slot
    restore_t: jnp.ndarray      # (N, D) f32, +inf = permanent drain
    drain_res: jnp.ndarray      # (N, D) i32 resource segment index
    drain_units: jnp.ndarray    # (N, D) i32 leading units drained
    unit_seg: jnp.ndarray       # (U,)  i32 segment of each packed unit
    unit_local: jnp.ndarray     # (U,)  i32 index within the segment
    max_requeues: jnp.ndarray   # (N, 1) i32 requeue bound per env


@dataclass(frozen=True)
class DeviceLayout:
    """Static shape/semantic configuration baked into the jitted rollout."""
    names: Tuple[str, ...]
    caps: Tuple[int, ...]            # actual cluster capacities
    enc_caps: Tuple[int, ...]        # encoding section sizes (reference caps)
    window: int
    n_envs: int
    n_jobs: int                      # J, padded job axis
    rounds: int                      # T, scan length
    backfill: bool
    requires_obs: bool
    time_scale: float
    state_module: str = "mlp"        # mirrors EncodingConfig.state_module
    queue_cap: int = 0               # Q, attention layout only

    @property
    def n_resources(self) -> int:
        return len(self.names)

    @property
    def node_idx(self) -> int:
        """Resource anchoring the failed-work metric (JobLifecycle.primary)."""
        return self.names.index("node") if "node" in self.names else 0

    @property
    def segments(self) -> Tuple[Tuple[int, int], ...]:
        """(offset, capacity) per resource into the packed unit axis."""
        segs, off = [], 0
        for c in self.caps:
            segs.append((off, c))
            off += c
        return tuple(segs)

    @property
    def n_units(self) -> int:
        return int(sum(self.caps))

    @property
    def state_dim(self) -> int:
        if self.state_module == "attention":
            return (self.queue_cap * (self.n_resources + 2) + 1
                    + 2 * self.n_resources)
        return self.window * (self.n_resources + 2) + 2 * int(sum(self.enc_caps))


@dataclass
class DeviceStats:
    """Mirror of ``VectorStats`` for the device engine."""
    rounds: int = 0
    decisions: int = 0
    policy_calls: int = 0            # one in-graph score per active round
    max_batch: int = 0

    def as_dict(self) -> dict:
        return {"rounds": self.rounds, "decisions": self.decisions,
                "policy_calls": self.policy_calls,
                "max_batch": self.max_batch}


@dataclass
class DeviceRollout:
    """One device rollout: per-env results plus the decision trace.

    ``results`` materializes lazily on first access: rebuilding per-job
    Python objects for every environment is host-side work that
    collection-mode consumers (which ingest the packed decision trace,
    not ``SimResult``s) should not pay inside the rollout hot path.
    """
    actions: np.ndarray              # (T, N) int32, -1 where no decision
    decided: np.ndarray              # (T, N) bool
    stats: DeviceStats
    obs: Optional[np.ndarray] = None  # (T, N, row_dim) packed decision rows
    trace: Optional[Dict[str, np.ndarray]] = None  # rollout(trace=True):
    #   per-round state deltas + decision extras, decoded into mrsch.trace
    #   events by DeviceSimulator.emit_trace
    _build: Optional[Callable[[], List[SimResult]]] = field(
        default=None, repr=False)
    _cache: Optional[List[SimResult]] = field(default=None, repr=False)

    @property
    def results(self) -> List[SimResult]:
        """Per-env ``SimResult``s in jobset order (built on demand)."""
        if self._cache is None:
            self._cache = self._build()
        return self._cache

    def transitions(self):
        """Yield (round, env, obs_row, action) for every decision taken,
        in round order — the order the host trainer must ingest them to
        keep each environment's trajectory contiguous."""
        assert self.obs is not None, "rollout was not collected"
        for t in range(self.decided.shape[0]):
            for i in np.flatnonzero(self.decided[t]):
                yield t, int(i), self.obs[t, i], int(self.actions[t, i])


# ===================================================================== graph
def _segment_free(layout: DeviceLayout, release: jnp.ndarray) -> jnp.ndarray:
    """Free-unit counts per resource, (N, R) float32."""
    cols = [jnp.sum(release[:, off:off + cap] == 0.0, axis=1)
            for off, cap in layout.segments]
    return jnp.stack(cols, axis=1).astype(jnp.float32)


def _advance_events(layout: DeviceLayout, arrays, faults: DeviceFaults, st):
    """Batched event step: pop+apply ONE coalesced timestamp per env not
    inside a scheduling pass.  Runs inline in the round body (no
    ``while_loop`` — its computation boundaries dominate the per-round
    cost on small problems); an env that pops a decision-free timestamp
    simply pops again next round, which the round budget covers.

    Events at one timestamp apply in the host engines' kind order:
    attempt ends (clean finish or failure-point kill), then queue
    entries (implicit — the queued mask is derived from READY times),
    then drains, then restores."""
    P = arrays["deps_idx"].shape[2]
    A = arrays["fail_times"].shape[2]
    D = faults.drain_t.shape[1]
    s = dict(st)
    # A pass over an empty queue ends silently (Simulator.next_decision).
    queued_any = device_queued(s["ready"], s["now"], s["started"],
                               s["finished"], s["failed"]).any(axis=1)
    in_pass = s["in_pass"] & queued_any
    adv = ~in_pass & ~s["done"]
    t = device_next_event(s["now"], s["ready"], s["end"], s["started"],
                          s["finished"], s["failed"],
                          faults if D else None, s)
    no_ev = ~jnp.isfinite(t)
    s["done"] = s["done"] | (adv & no_ev)
    act = adv & ~no_ev
    s["now"] = jnp.where(act, t, s["now"])
    s = device_apply_ends(t, act, arrays["demands"], layout.node_idx,
                          faults.max_requeues, s, has_kills=(A > 0 or D > 0))
    if D:
        s = device_apply_drains(t, act, faults, arrays["demands"],
                                layout.node_idx, s)
        s = device_apply_restores(t, act, faults, s)
    if P:
        # Finishes may have released dependents: recompute READY times.
        s["ready"] = device_ready(arrays["submit"], arrays["deps_idx"],
                                  arrays["think"], s["end"], s["finished"])
    s["in_pass"] = in_pass | act
    return s


def _alloc_first_free(layout: DeviceLayout, release, owner, env_mask,
                      job_idx, demand, est):
    """Allocate ``demand`` (N, R) lowest-index free units for ``job_idx``
    in every env of ``env_mask`` (mirrors ``Cluster.allocate``)."""
    for r, (off, cap) in enumerate(layout.segments):
        seg = release[:, off:off + cap]
        freemask = seg == 0.0
        rank = jnp.cumsum(freemask.astype(jnp.float32), axis=1)
        take = (freemask & (rank <= demand[:, r:r + 1])
                & env_mask[:, None])
        release = release.at[:, off:off + cap].set(
            jnp.where(take, est[:, None], seg))
        owner = owner.at[:, off:off + cap].set(
            jnp.where(take, job_idx[:, None], owner[:, off:off + cap]))
    return release, owner


def _earliest_fit(layout: DeviceLayout, release, free, demand, now):
    """Per-env earliest time ``demand`` fits assuming estimated releases
    (mirrors ``Cluster.earliest_fit_time``): the need-th smallest release
    per resource (free units sort first as 0.0), max over resources.
    Permanently drained units carry ``release = +inf`` and therefore
    never count toward a future fit, exactly like the host."""
    t_res = now
    for r, (off, cap) in enumerate(layout.segments):
        seg_sorted = jnp.sort(release[:, off:off + cap], axis=1)
        need = demand[:, r]
        kth_idx = jnp.clip(need.astype(jnp.int32) - 1, 0, cap - 1)
        kth = jnp.take_along_axis(seg_sorted, kth_idx[:, None], axis=1)[:, 0]
        t_r = jnp.where(need <= free[:, r], now,
                        jnp.where(need <= float(cap), kth, INF))
        t_res = jnp.maximum(t_res, t_r)
    return t_res


def _easy_backfill(layout: DeviceLayout, arrays, st, free, need, waiting,
                   j_star, d_star, dur_all, will_fail_all):
    """EASY backfill for envs whose selection did not fit (vectorized
    mirror of ``Simulator._easy_backfill``): reservation at the earliest
    fit time, shadow accounting in queue order, then one batched
    first-fit unit assignment for every job that may jump ahead.
    ``dur_all``/``will_fail_all`` describe each job's NEXT attempt
    (``lifecycle.device_attempt``) so a backfilled doomed attempt ends at
    its failure point, exactly like an immediate start."""
    N, J, R = layout.n_envs, layout.n_jobs, layout.n_resources
    now = st["now"]
    t_res = _earliest_fit(layout, st["release"], free, d_star, now)
    do_bf = need & jnp.isfinite(t_res)
    # Shadow: free units at t_res (estimated releases) minus the
    # reservation's demand, per resource.
    shadow_cols = []
    for r, (off, cap) in enumerate(layout.segments):
        free_at = jnp.sum(st["release"][:, off:off + cap] <= t_res[:, None],
                          axis=1).astype(jnp.float32)
        shadow_cols.append(free_at - d_star[:, r])
    shadow = jnp.stack(shadow_cols, axis=1)

    ends_before_all = arrays["walltime"] + now[:, None] <= t_res[:, None]

    # The queue walk's carry only changes when a candidate actually
    # starts, and availability only ever decreases — so walking the
    # queue in order debiting as we go is equivalent to repeatedly
    # starting the FIRST still-fitting candidate.  That turns an O(J)
    # sequential scan into a while_loop with one iteration per started
    # job (almost always 0-2), each a vectorized pass over the queue.
    jidx = jnp.arange(J)
    cand = (do_bf[:, None] & (waiting > 0.5)
            & (jidx[None, :] != j_star[:, None]))          # (N, J)

    def fitting(free_c, shadow_c, go):
        # Per-resource (N, J) compares: XLA:CPU runs these an order of
        # magnitude faster than the equivalent (N, J, R) broadcast+all.
        fits_now = cand & ~go
        shadow_ok = None
        for r in range(R):
            d_r = arrays["demands"][:, :, r]
            fits_now = fits_now & (d_r <= free_c[:, r:r + 1])
            s_r = d_r <= shadow_c[:, r:r + 1]
            shadow_ok = s_r if shadow_ok is None else shadow_ok & s_r
        return fits_now & (ends_before_all | shadow_ok)

    # The loop carries the fit matrix so the condition is a 1-op any()
    # and each iteration evaluates ``fitting`` exactly once.  Each
    # iteration accepts a whole PREFIX of the fitting candidates: a
    # candidate is accepted when the cumulative demand of accepted
    # candidates up to and including it still fits (free and shadow) —
    # exactly the debits the sequential walk would have applied — and
    # the first cumulative failure blocks the rest of the queue until
    # the next iteration re-evaluates them against the debited carry.
    # One iteration per *blocking event* instead of one per start.
    def cond(c):
        return c[3].any()

    def body(c):
        free_c, shadow_c, go, ok = c
        ok_f = ok.astype(jnp.float32)
        debit_f = (ok & ~ends_before_all).astype(jnp.float32)
        free_ok = None
        shadow_fit = None
        d_acc_cols = []
        for r in range(R):
            d_r = arrays["demands"][:, :, r]
            cum_r = jnp.cumsum(ok_f * d_r, axis=1)
            f_r = cum_r <= free_c[:, r:r + 1]
            free_ok = f_r if free_ok is None else free_ok & f_r
            cums_r = jnp.cumsum(debit_f * d_r, axis=1)
            s_r = cums_r <= shadow_c[:, r:r + 1]
            shadow_fit = s_r if shadow_fit is None else shadow_fit & s_r
            d_acc_cols.append(d_r)
        passes = free_ok & (ends_before_all | shadow_fit)
        fail = ok & ~passes
        accept = ok & passes & (jnp.cumsum(fail.astype(jnp.int32), axis=1)
                                == 0)
        acc_f = accept.astype(jnp.float32)
        acc_debit_f = (accept & ~ends_before_all).astype(jnp.float32)
        d_used = jnp.stack(
            [(acc_f * d_r).sum(axis=1) for d_r in d_acc_cols], axis=1)
        s_used = jnp.stack(
            [(acc_debit_f * d_r).sum(axis=1) for d_r in d_acc_cols], axis=1)
        free_c = free_c - d_used
        shadow_c = shadow_c - s_used
        go = go | accept
        return (free_c, shadow_c, go, fitting(free_c, shadow_c, go))

    go0 = jnp.zeros((N, J), bool)
    _, _, bf_start, _ = jax.lax.while_loop(
        cond, body, (free, shadow, go0, fitting(free, shadow, go0)))

    # Unit assignment, one batched pass per resource: job j takes the
    # free units whose free-rank falls in its cumulative-demand span —
    # identical to allocating each job first-fit in queue order.  Most
    # reservation rounds backfill nothing, so the whole phase is
    # conditioned on some env actually starting a job.
    def assign_units(st):
        est_all = now[:, None] + arrays["walltime"]            # (N, J)
        release, owner = st["release"], st["owner"]
        jidx_f = jnp.arange(J, dtype=jnp.float32)
        for r, (off, cap) in enumerate(layout.segments):
            seg = release[:, off:off + cap]
            freemask = seg == 0.0
            k = jnp.cumsum(freemask.astype(jnp.float32), axis=1)  # (N, cap)
            need_j = arrays["demands"][:, :, r] * bf_start         # (N, J)
            cum = jnp.cumsum(need_j, axis=1)
            assign = (freemask[:, :, None] & bf_start[:, None, :]
                      & (k[:, :, None] > (cum - need_j)[:, None, :])
                      & (k[:, :, None] <= cum[:, None, :]))        # (N, cap, J)
            assign_f = assign.astype(jnp.float32)
            any_assign = assign.any(axis=2)
            # One-hot gathers: HIGHEST keeps job indices and release
            # times exact on the TPU's MXU (default f32 passes are bf16).
            owner_val = jnp.einsum("nuj,j->nu", assign_f, jidx_f,
                                   precision=HIGHEST)
            rel_val = jnp.einsum("nuj,nj->nu", assign_f, est_all,
                                 precision=HIGHEST)
            release = release.at[:, off:off + cap].set(
                jnp.where(any_assign, rel_val, seg))
            owner = owner.at[:, off:off + cap].set(
                jnp.where(any_assign, owner_val.astype(jnp.int32),
                          owner[:, off:off + cap]))

        started = st["started"] | bf_start
        start = jnp.where(bf_start, now[:, None], st["start"])
        end = jnp.where(bf_start, now[:, None] + dur_all, st["end"])
        est_end = jnp.where(bf_start, est_all, st["est_end"])
        fsj = jnp.where(bf_start & (st["first_start_j"] < 0),
                        now[:, None], st["first_start_j"])
        any_bf = bf_start.any(axis=1)
        first = jnp.where(any_bf, jnp.minimum(st["first_start"], now),
                          st["first_start"])
        out = {**st, "release": release, "owner": owner,
               "started": started, "start": start, "end": end,
               "est_end": est_end, "first_start": first,
               "first_start_j": fsj}
        if will_fail_all is not None:
            out["cur_fail"] = jnp.where(bf_start, will_fail_all,
                                        st["cur_fail"])
        return out

    return jax.lax.cond(bf_start.any(), assign_units, lambda st: st, st)


def _meas_goal(layout: DeviceLayout, arrays, st, free, waiting,
               has_drains: bool):
    """Measurement (utilization) + Eq. (1) goal, (N, R) each — the shared
    tail of every packed decision row, module-independent.  Drained
    (phantom-owned) units are neither busy nor free, matching
    ``Cluster.utilization``."""
    from .lifecycle import PHANTOM_OWNER
    R = layout.n_resources
    now = st["now"]
    caps_f = jnp.asarray([max(c, 1) for c in layout.caps], jnp.float32)
    if has_drains:
        ph_cols = [jnp.sum(st["owner"][:, off:off + cap] == PHANTOM_OWNER,
                           axis=1)
                   for off, cap in layout.segments]
        phantom = jnp.stack(ph_cols, axis=1).astype(jnp.float32)
        meas = 1.0 - (free + phantom) / caps_f[None, :]
    else:
        meas = 1.0 - free / caps_f[None, :]
    # Eq. (1) goal over the full waiting queue + running remainders.
    running = st["started"] & ~st["finished"]
    tw = (arrays["walltime"] * waiting
          + jnp.maximum(st["est_end"] - now[:, None], 0.0) * running)
    acc = jnp.einsum("nj,njr->nr", tw, arrays["demands"], precision=HIGHEST)
    demand_time = acc / caps_f[None, :]
    total = demand_time.sum(axis=1, keepdims=True)
    goal = jnp.where(total > 0, demand_time / jnp.maximum(total, 1e-30),
                     1.0 / R)
    return meas, goal


def _job_tokens(layout: DeviceLayout, st, win_feats, win_valid):
    """Packed job slots -> [fracs(R), walltime_norm, queued_norm] tokens.

    [fracs(R), walltime_norm] are static per job; the queued-time column
    is derived from the packed raw submit times.  Invalid slots are
    all-zero (``pack_window`` zero-fills their features)."""
    R = layout.n_resources
    ts = jnp.float32(layout.time_scale)
    valid_f = win_valid.astype(jnp.float32)
    queued = (st["now"][:, None] - win_feats[..., R + 1]) / ts * valid_f
    return jnp.concatenate([win_feats[..., :R + 1], queued[..., None]],
                           axis=-1)


def _build_obs(layout: DeviceLayout, arrays, st, win_feats, win_valid,
               meas, goal):
    """Packed decision rows [state | meas | goal | valid] in-graph,
    mirroring ``encoding.encode_decision_row`` (float32 throughout)."""
    N, R, W = layout.n_envs, layout.n_resources, layout.window
    ts = jnp.float32(layout.time_scale)
    now = st["now"]
    valid_f = win_valid.astype(jnp.float32)
    win = _job_tokens(layout, st, win_feats, win_valid)
    parts = [win.reshape(N, W * (R + 2))]
    # Unit sections use the encoding's reference section sizes; a cluster
    # with fewer units fills the leading slots (encode_state semantics).
    # avail/ttf are computed once over the whole unit axis; the per-
    # segment views below are free slices.  The TTF_HORIZON clip keeps
    # permanently drained units (release = +inf) out of the features,
    # matching encode_state.
    busy_all = st["release"] > 0.0
    avail_all = jnp.where(busy_all, 0.0, 1.0)
    ttf_all = jnp.where(
        busy_all,
        jnp.clip(st["release"] - now[:, None], 0.0, TTF_HORIZON),
        0.0) / ts
    for r, (off, cap) in enumerate(layout.segments):
        k = min(cap, int(layout.enc_caps[r]))
        avail = avail_all[:, off:off + k]
        ttf = ttf_all[:, off:off + k]
        pad = int(layout.enc_caps[r]) - k
        if pad:
            zeros = jnp.zeros((N, pad), jnp.float32)
            avail = jnp.concatenate([avail, zeros], axis=1)
            ttf = jnp.concatenate([ttf, zeros], axis=1)
        parts.extend([avail, ttf])
    return jnp.concatenate(parts + [meas, goal, valid_f], axis=1)


def _build_obs_attention(layout: DeviceLayout, arrays, st, waiting,
                         q_feats, q_valid, meas, goal):
    """Attention-layout decision rows, mirroring ``encoding.encode_state``
    with ``state_module="attention"``:
    ``[Q*(R+2) tokens | queue_len | 2R context | meas | goal | valid(W)]``.
    ``q_feats``/``q_valid`` pack the first ``queue_cap`` waiting jobs; the
    leading W slots are exactly the action window."""
    N, R, W = layout.n_envs, layout.n_resources, layout.window
    Q = layout.queue_cap
    ts = jnp.float32(layout.time_scale)
    now = st["now"]
    tok = _job_tokens(layout, st, q_feats, q_valid)
    qlen = jnp.minimum(waiting.sum(axis=1), float(Q))
    ctx_cols = []
    for r, (off, cap) in enumerate(layout.segments):
        seg = st["release"][:, off:off + cap]
        busy = seg > 0.0
        nb = busy.sum(axis=1).astype(jnp.float32)
        ctx_cols.append(1.0 - nb / float(max(cap, 1)))       # free fraction
        ttf_sum = jnp.where(
            busy, jnp.clip(seg - now[:, None], 0.0, TTF_HORIZON),
            0.0).sum(axis=1)
        ctx_cols.append(jnp.where(nb > 0, ttf_sum / jnp.maximum(nb, 1.0), 0.0)
                        / ts)                                # mean time-to-free
    return jnp.concatenate(
        [tok.reshape(N, Q * (R + 2)), qlen[:, None],
         jnp.stack(ctx_cols, axis=1), meas, goal,
         q_valid[:, :W].astype(jnp.float32)], axis=1)


def _device_rollout(layout: DeviceLayout, score_fn, explore: bool,
                    collect: bool, trace: bool, arrays,
                    faults: DeviceFaults, policy_state, eps, key):
    """The whole N-env x T-round rollout as one traced program.

    ``trace`` (static) additionally scans out per-round lifecycle deltas
    and decision extras — tiny boolean/int arrays carried through the
    scan so the hot loop stays device-resident — which
    ``DeviceSimulator.emit_trace`` decodes post-run into the same
    ``mrsch.trace/v1`` event stream the host engines emit inline."""
    N, J, R, W = (layout.n_envs, layout.n_jobs, layout.n_resources,
                  layout.window)
    P = arrays["deps_idx"].shape[2]
    A = arrays["fail_times"].shape[2]
    D = faults.drain_t.shape[1]
    has_drains = D > 0
    jidx = jnp.arange(J)
    end0 = jnp.full((N, J), jnp.inf, jnp.float32)
    finished0 = jnp.zeros((N, J), bool)
    falses0 = jnp.zeros((N, J), bool)
    now0 = jnp.zeros(N, jnp.float32)
    ready0 = device_ready(arrays["submit"], arrays["deps_idx"],
                          arrays["think"], end0, finished0)
    # Jobs ready at t=0 are queued before any event can fire (the pending-
    # ready event below is strictly future), so their scheduling pass is
    # seeded here — the host's t=0 submit pop.
    in_pass0 = device_queued(ready0, now0, falses0, finished0,
                             falses0).any(axis=1)
    st = {
        "now": now0,
        "ready": ready0,
        "started": jnp.zeros((N, J), bool),
        "finished": finished0,
        "failed": jnp.zeros((N, J), bool),
        "start": jnp.full((N, J), -1.0, jnp.float32),
        "end": end0,
        "est_end": jnp.zeros((N, J), jnp.float32),
        "first_start_j": jnp.full((N, J), -1.0, jnp.float32),
        "requeues": jnp.zeros((N, J), jnp.int32),
        "cur_fail": jnp.zeros((N, J), bool),
        "failed_work": jnp.zeros((N, J), jnp.float32),
        "failed_area": jnp.zeros((N, R), jnp.float32),
        "release": jnp.zeros((N, layout.n_units), jnp.float32),
        "owner": jnp.full((N, layout.n_units), -1, jnp.int32),
        "drain_done": jnp.zeros((N, D), bool),
        "restore_done": jnp.zeros((N, D), bool),
        "in_pass": in_pass0,
        "done": jnp.zeros(N, bool),
        "decisions": jnp.zeros(N, jnp.int32),
        "truncated": jnp.zeros(N, jnp.int32),
        "first_start": jnp.full(N, jnp.inf, jnp.float32),
        "key": key,
    }
    obs_dim = (layout.state_dim + 2 * R + W) if layout.requires_obs else W

    # Constant per rollout: keep the concat out of the per-round body.
    feats = jnp.concatenate(
        [arrays["static_feats"], arrays["submit_feat"][..., None]],
        axis=-1)

    def decide(s):
        now = s["now"]
        waiting = device_queued(s["ready"], now, s["started"], s["finished"],
                                s["failed"]).astype(jnp.float32)
        n_waiting = waiting.sum(axis=1)
        need = s["in_pass"] & (n_waiting > 0) & ~s["done"]
        free = _segment_free(layout, s["release"])
        # The attention module observes the first queue_cap waiting jobs;
        # one pack covers both the Q-token state and (its leading W
        # slots) the action window.
        attention = layout.state_module == "attention"
        K = layout.queue_cap if attention else W
        pk_feats, pk_idx, pk_valid = pack_window(waiting, feats, window=K)
        win_idx, win_valid = pk_idx[:, :W], pk_valid[:, :W]
        if not layout.requires_obs:
            obs = win_valid.astype(jnp.float32)
        else:
            meas, goal = _meas_goal(layout, arrays, s, free, waiting,
                                    has_drains)
            if attention:
                obs = _build_obs_attention(layout, arrays, s, waiting,
                                           pk_feats, pk_valid, meas, goal)
            else:
                obs = _build_obs(layout, arrays, s, pk_feats, pk_valid,
                                 meas, goal)
        # Jobs a host Simulator would drop from the observable window this
        # decision (ScheduleMetrics.truncated_jobs; the attention module
        # still reports window truncation so the A/B comparison reads the
        # same pressure signal for both modules).
        overflow = jnp.maximum(n_waiting - float(W), 0.0).astype(jnp.int32)
        s = {**s, "truncated": s["truncated"] + need * overflow}
        scores = score_fn(policy_state, obs)[:, :W]
        masked = jnp.where(win_valid, scores, -INF)
        a = jnp.argmax(masked, axis=1).astype(jnp.int32)
        if explore:
            k_next, k1, k2 = jax.random.split(s["key"], 3)
            n_valid = win_valid.sum(axis=1).astype(jnp.float32)
            a_rand = jnp.floor(jax.random.uniform(k2, (N,))
                               * jnp.maximum(n_valid, 1.0)).astype(jnp.int32)
            roll = jax.random.uniform(k1, (N,)) < eps
            a = jnp.where(roll, a_rand, a)
            s = {**s, "key": k_next}
        j_star = jnp.take_along_axis(win_idx, a[:, None], axis=1)[:, 0]
        d_star = jnp.take_along_axis(
            arrays["demands"], j_star[:, None, None], axis=1)[:, 0]   # (N, R)
        fits = jnp.all(d_star <= free, axis=1)
        start_env = need & fits
        reserve_env = need & ~fits
        # --- immediate start (scheduling pass continues).  The attempt's
        # actual duration is its failure point when the attempt is doomed
        # (lifecycle.device_attempt); the unit-release ESTIMATE still uses
        # the walltime, exactly like the host.
        if A:
            dur_all, will_fail_all = device_attempt(
                arrays["fail_times"], s["requeues"], arrays["runtime"])
        else:
            dur_all, will_fail_all = arrays["runtime"], None
        wall_star = jnp.take_along_axis(arrays["walltime"], j_star[:, None],
                                        axis=1)[:, 0]
        run_star = jnp.take_along_axis(dur_all, j_star[:, None],
                                       axis=1)[:, 0]
        est = now + wall_star
        release, owner = _alloc_first_free(
            layout, s["release"], s["owner"], start_env, j_star, d_star, est)
        sel = (jidx[None, :] == j_star[:, None]) & start_env[:, None]
        s = {**s, "release": release, "owner": owner,
             "started": s["started"] | sel,
             "start": jnp.where(sel, now[:, None], s["start"]),
             "end": jnp.where(sel, (now + run_star)[:, None], s["end"]),
             "est_end": jnp.where(sel, est[:, None], s["est_end"]),
             "first_start_j": jnp.where(sel & (s["first_start_j"] < 0),
                                        now[:, None], s["first_start_j"]),
             "decisions": s["decisions"] + need,
             "first_start": jnp.where(start_env,
                                      jnp.minimum(s["first_start"], now),
                                      s["first_start"])}
        if A:
            wf_star = jnp.take_along_axis(will_fail_all, j_star[:, None],
                                          axis=1)[:, 0]
            s = {**s, "cur_fail": jnp.where(sel, wf_star[:, None],
                                            s["cur_fail"])}
        # --- reservation + EASY backfill (scheduling pass ends).  The
        # call is cheap when no env reserved (no fitting candidates ->
        # zero queue-walk iterations, unit assignment conditioned out),
        # so it runs unconditionally rather than behind another cond.
        if layout.backfill:
            s = _easy_backfill(layout, arrays, s, free, reserve_env,
                               waiting, j_star, d_star, dur_all,
                               will_fail_all)
        s = {**s, "in_pass": s["in_pass"] & ~reserve_env}
        a_out = jnp.where(need, a, -1)
        obs_out = obs if collect else jnp.zeros((N, 0), jnp.float32)
        dec = ((j_star, fits, n_waiting.astype(jnp.int32)) if trace else ())
        return s, a_out, need, obs_out, dec

    def round_body(s, _):
        # Two-stage snapshots (pre-advance, post-advance): the deltas
        # distinguish advance-phase transitions (finish / fail / requeue
        # / drain / restore) from decide-phase starts, so a job killed
        # and restarted at the SAME timestamp decodes as both events.
        s_pre = s
        s = _advance_events(layout, arrays, faults, s)
        s_adv = s
        # Single-pop advancement can leave an env in_pass with an empty
        # queue (completion-only timestamp) — only envs with waiting
        # jobs actually need a decision this round.
        qa = device_queued(s["ready"], s["now"], s["started"], s["finished"],
                           s["failed"]).any(axis=1)
        any_need = jnp.any(s["in_pass"] & ~s["done"] & qa)

        def live(s):
            return decide(s)

        def idle(s):
            dec = ((jnp.zeros(N, jnp.int32), jnp.zeros(N, bool),
                    jnp.zeros(N, jnp.int32)) if trace else ())
            return (s, jnp.full(N, -1, jnp.int32), jnp.zeros(N, bool),
                    jnp.zeros((N, obs_dim if collect else 0), jnp.float32),
                    dec)

        s, a_out, need, obs_out, dec = jax.lax.cond(any_need, live, idle, s)
        ys = (a_out, need, obs_out)
        if trace:
            tr = {"now": s_adv["now"],
                  "finish_d": s_adv["finished"] & ~s_pre["finished"],
                  "fail_d": s_adv["failed"] & ~s_pre["failed"],
                  "requeue_d": s_adv["requeues"] > s_pre["requeues"],
                  "start_d": s["started"] & ~s_adv["started"],
                  "j_star": dec[0], "fit": dec[1], "qlen": dec[2]}
            if D:
                tr["drain_d"] = (s_adv["drain_done"]
                                 & ~s_pre["drain_done"])
                tr["restore_d"] = (s_adv["restore_done"]
                                   & ~s_pre["restore_done"])
            ys = ys + (tr,)
        return s, ys

    st, scan_out = jax.lax.scan(round_body, st, None, length=layout.rounds)
    if trace:
        actions, decided, obs_log, trace_out = scan_out
    else:
        actions, decided, obs_log = scan_out
    out = {"started": st["started"], "start": st["start"], "end": st["end"],
           "finished": st["finished"], "failed": st["failed"],
           "requeues": st["requeues"], "failed_work": st["failed_work"],
           "failed_area": st["failed_area"],
           "first_start_j": st["first_start_j"],
           "now": st["now"], "decisions": st["decisions"],
           "truncated": st["truncated"],
           "first_start": st["first_start"], "done": st["done"],
           "actions": actions, "decided": decided}
    if collect:
        out["obs"] = obs_log
    if trace:
        # Final READY times decode the first queue entry of every job
        # (host: queued exactly at max(submit, parent end + think)).
        out["trace"] = {**trace_out, "ready": st["ready"]}
    return out


# ====================================================================== host
class DeviceSimulator:
    """N jobsets, one shared cluster spec, one jitted rollout program.

    ``policy`` must implement the device stages of the ``Policy``
    protocol (``init_state`` / ``score_window``); use
    ``repro.core.policy_api.supports_device`` to check.  Construction
    packs the traces into fixed-capacity arrays and compiles the rollout
    on first use; ``run()`` matches the ``Simulator``/``VectorSimulator``
    result contract, ``rollout()`` additionally returns the decision
    trace (and, with ``collect=True``, the packed decision rows for
    training ingestion).

    ``faults`` mirrors the host engines: ``None``, one ``FaultSchedule``
    shared by every environment, or one (possibly ``None``) schedule per
    jobset.
    """

    def __init__(self, resources: Sequence[ResourceSpec],
                 jobsets: Sequence[Sequence[Job]], policy,
                 config: SimConfig | None = None, *, faults=None):
        from ..core.policy_api import supports_device
        if not supports_device(policy):
            raise TypeError(
                f"{type(policy).__name__} has no device stages "
                "(init_state/score_window) — run it through Simulator or "
                "VectorSimulator instead")
        if not jobsets or any(len(js) == 0 for js in jobsets):
            raise ValueError("DeviceSimulator needs >= 1 non-empty jobset")
        self.resources = list(resources)
        self.policy = policy
        self.config = config or SimConfig.for_engine("device")
        names = tuple(r.name for r in self.resources)
        caps = tuple(int(r.capacity) for r in self.resources)
        requires_obs = bool(getattr(policy, "requires_obs", True))
        enc = getattr(policy, "enc", None)
        if requires_obs:
            assert enc is not None, \
                f"{type(policy).__name__} requires obs but has no enc"
            if tuple(enc.resource_names) != names:
                raise ValueError(
                    f"policy encodes resources {tuple(enc.resource_names)} "
                    f"but the cluster has {names}")
            if int(enc.window) != int(self.config.window):
                raise ValueError(
                    f"policy window {enc.window} != sim window "
                    f"{self.config.window} — the device engine scores "
                    "exactly the simulation window")
            enc_caps = tuple(int(c) for c in enc.capacities)
            time_scale = float(enc.time_scale)
            state_module = str(getattr(enc, "state_module", "mlp"))
            queue_cap = int(getattr(enc, "queue_cap", 0))
        else:
            enc_caps = caps
            time_scale = 86400.0
            state_module = "mlp"
            queue_cap = 0

        self.jobsets = [sorted((j.copy() for j in js),
                               key=lambda j: (j.submit, j.jid))
                        for js in jobsets]
        N = len(self.jobsets)
        J = max(len(js) for js in self.jobsets)
        caps_map = dict(zip(names, caps))
        if faults is None or isinstance(faults, FaultSchedule):
            flist = [faults] * N
        else:
            flist = list(faults)
            if len(flist) != N:
                raise ValueError(
                    f"got {len(flist)} fault schedules for {N} jobsets")
        self._faults = [resolve_faults(f, js, caps_map)
                        for f, js in zip(flist, self.jobsets)]
        rounds = 3 * J + 2 + self._fault_rounds()
        if self.config.max_rounds is not None:
            rounds = min(rounds, int(self.config.max_rounds))
        self.layout = DeviceLayout(
            names=names, caps=caps, enc_caps=enc_caps,
            window=int(self.config.window), n_envs=N, n_jobs=J,
            rounds=rounds, backfill=bool(self.config.backfill),
            requires_obs=requires_obs, time_scale=time_scale,
            state_module=state_module, queue_cap=queue_cap)
        self.arrays = self._pack(self.jobsets)
        self.faults_arrays = self._pack_faults(self._faults)
        self.stats = DeviceStats()
        self._jitted: Dict[Tuple[bool, bool, bool], object] = {}

    def _fault_rounds(self) -> int:
        """Extra scan rounds for fault activity, max over environments:
        every kill adds one end pop and one restart decision; every drain
        adds its own pop, a restore pop, and a restart cycle per resident
        it can kill (bounded by the unit count)."""
        extra = 0
        for js, f in zip(self.jobsets, self._faults):
            kills = 0
            for job in js:
                k = 0
                for ft in job.fail_times:
                    if ft < job.runtime and k < f.max_requeues + 1:
                        k += 1
                    else:
                        break
                kills += k
            dcost = sum(2 + 2 * min(len(js), d.units) for d in f.drains)
            extra = max(extra, 2 * kills + dcost)
        return extra

    # ------------------------------------------------------------- packing
    def _pack(self, jobsets) -> Dict[str, jnp.ndarray]:
        lay = self.layout
        N, J, R = lay.n_envs, lay.n_jobs, lay.n_resources
        submit = np.full((N, J), np.inf, np.float64)
        runtime = np.zeros((N, J), np.float64)
        walltime = np.zeros((N, J), np.float64)
        demands = np.zeros((N, J, R), np.float32)
        static = np.zeros((N, J, R + 1), np.float32)
        caps_f = [float(max(c, 1)) for c in lay.caps]
        # Dependency edges resolve to packed job indices per environment;
        # dangling or self deps are dropped (JobLifecycle semantics).
        dep_lists = []
        for js in jobsets:
            id2idx = {job.jid: j for j, job in enumerate(js)}
            dep_lists.append([
                [id2idx[d] for d in job.deps
                 if d in id2idx and d != job.jid]
                for job in js])
        P = max((len(ds) for env in dep_lists for ds in env), default=0)
        A = max((len(job.fail_times) for js in jobsets for job in js),
                default=0)
        deps_idx = np.full((N, J, P), -1, np.int32)
        think = np.zeros((N, J), np.float32)
        fail_times = np.full((N, J, A), np.inf, np.float32)
        for i, js in enumerate(jobsets):
            for j, job in enumerate(js):
                submit[i, j] = job.submit
                runtime[i, j] = job.runtime
                walltime[i, j] = job.walltime
                for r, n in enumerate(lay.names):
                    d = job.demands.get(n, 0)
                    demands[i, j, r] = d
                    static[i, j, r] = d / caps_f[r]       # f64 div, f32 store
                static[i, j, R] = job.walltime / lay.time_scale
                ds = dep_lists[i][j]
                deps_idx[i, j, :len(ds)] = ds
                think[i, j] = job.think_time
                fail_times[i, j, :len(job.fail_times)] = job.fail_times
        return {
            "submit": jnp.asarray(submit, jnp.float32),
            "submit_feat": jnp.asarray(
                np.where(np.isfinite(submit), submit, 0.0), jnp.float32),
            "runtime": jnp.asarray(runtime, jnp.float32),
            "walltime": jnp.asarray(walltime, jnp.float32),
            "demands": jnp.asarray(demands),
            "static_feats": jnp.asarray(static),
            "deps_idx": jnp.asarray(deps_idx),
            "think": jnp.asarray(think),
            "fail_times": jnp.asarray(fail_times),
        }

    def _pack_faults(self, resolved: List[FaultSchedule]) -> DeviceFaults:
        lay = self.layout
        N = lay.n_envs
        D = max((len(f.drains) for f in resolved), default=0)
        drain_t = np.full((N, D), np.inf, np.float32)
        restore_t = np.full((N, D), np.inf, np.float32)
        drain_res = np.zeros((N, D), np.int32)
        drain_units = np.zeros((N, D), np.int32)
        mr = np.zeros((N, 1), np.int32)
        res_idx = {n: r for r, n in enumerate(lay.names)}
        for i, f in enumerate(resolved):
            mr[i, 0] = f.max_requeues
            for k, d in enumerate(f.drains):
                drain_t[i, k] = d.time
                restore_t[i, k] = d.time + d.duration
                drain_res[i, k] = res_idx[d.resource]
                drain_units[i, k] = d.units
        seg_cols = [np.full(cap, r, np.int32)
                    for r, (_, cap) in enumerate(lay.segments)]
        loc_cols = [np.arange(cap, dtype=np.int32)
                    for _, cap in lay.segments]
        unit_seg = (np.concatenate(seg_cols) if seg_cols
                    else np.zeros(0, np.int32))
        unit_local = (np.concatenate(loc_cols) if loc_cols
                      else np.zeros(0, np.int32))
        return DeviceFaults(
            drain_t=jnp.asarray(drain_t), restore_t=jnp.asarray(restore_t),
            drain_res=jnp.asarray(drain_res),
            drain_units=jnp.asarray(drain_units),
            unit_seg=jnp.asarray(unit_seg),
            unit_local=jnp.asarray(unit_local),
            max_requeues=jnp.asarray(mr))

    # ------------------------------------------------------------- rollout
    def _fn(self, explore: bool, collect: bool, trace: bool = False):
        key = (explore, collect, trace)
        if key not in self._jitted:
            self._jitted[key] = jax.jit(functools.partial(
                _device_rollout, self.layout, self.policy.score_window,
                explore, collect, trace))
        return self._jitted[key]

    def rollout(self, eps: Optional[float] = None, seed: int = 0,
                collect: bool = False, trace: bool = False) -> DeviceRollout:
        """Run every environment to completion in one device program.

        ``eps``: when set, actions are epsilon-greedy with in-graph
        (jax.random) draws — the device counterpart of the agent's
        training exploration (note: a *different* RNG stream than the
        host engines' numpy draws).  ``collect=True`` additionally
        returns the packed decision rows for trainer ingestion.
        ``trace=True`` (a separate jit specialization) scans out the
        per-round lifecycle deltas that ``emit_trace`` decodes into the
        ``mrsch.trace/v1`` event stream.
        """
        explore = eps is not None
        with annotate("mrsch.device.rollout"):
            raw = self._fn(explore, collect, trace)(
                self.arrays, self.faults_arrays, self.policy.init_state(),
                jnp.float32(0.0 if eps is None else eps),
                jax.random.PRNGKey(seed))
        tr = raw.pop("trace", None)
        out = {k: np.asarray(v) for k, v in raw.items()}
        if tr is not None:
            tr = {k: np.asarray(v) for k, v in tr.items()}
        if not out["done"].all():
            raise RuntimeError(
                f"device rollout exhausted its round budget "
                f"({self.layout.rounds}); raise SimConfig.max_rounds")
        decided = out["decided"]
        self.stats = DeviceStats(
            rounds=int(decided.any(axis=1).sum()),
            decisions=int(decided.sum()),
            policy_calls=int(decided.any(axis=1).sum()),
            max_batch=int(decided.sum(axis=1).max(initial=0)))
        return DeviceRollout(
            actions=out["actions"], decided=decided,
            stats=self.stats, obs=out.get("obs"), trace=tr,
            _build=lambda: self._results(out))

    def emit_trace(self, ro: DeviceRollout, tracer: Tracer,
                   env_ids: Optional[Sequence[int]] = None) -> None:
        """Decode a ``rollout(trace=True)`` into typed tracer events.

        Emits the exact event stream the sequential engine produces for
        the same jobsets/policy (canonical order restored by
        ``repro.obs.trace.canonical_events``; byte parity pinned in
        ``tests/test_obs.py`` on integer-time traces, where the f32
        device clock is exact).
        """
        tr = ro.trace
        if tr is None:
            raise ValueError("rollout was not traced; pass trace=True")
        lay = self.layout
        eids = (list(range(lay.n_envs)) if env_ids is None
                else [int(e) for e in env_ids])
        if len(eids) != lay.n_envs:
            raise ValueError(
                f"got {len(eids)} env ids for {lay.n_envs} environments")
        # First queue entry of every job: its final READY time (f32).
        for i, js in enumerate(self.jobsets):
            env, ready_i = eids[i], tr["ready"][i]
            for j, job in enumerate(js):
                if np.isfinite(ready_i[j]):
                    tracer.job_queued(env, float(ready_i[j]), job.jid)
        nreq = [[0] * len(js) for js in self.jobsets]
        T = ro.decided.shape[0]
        has_faults = "drain_d" in tr
        for t in range(T):
            for i, js in enumerate(self.jobsets):
                env = eids[i]
                now = float(tr["now"][t, i])
                fin_d, fail_d = tr["finish_d"][t, i], tr["fail_d"][t, i]
                req_d = tr["requeue_d"][t, i]
                for j in np.flatnonzero(fin_d | fail_d | req_d):
                    jid = js[j].jid
                    if fin_d[j]:
                        tracer.job_finish(env, now, jid)
                    elif fail_d[j]:
                        # The kill that crossed the requeue bound: the
                        # host emits job.fail only (no requeue event).
                        tracer.job_fail(env, now, jid)
                    else:
                        nreq[i][j] += 1
                        tracer.job_requeue(env, now, jid, nreq[i][j])
                        tracer.job_queued(env, now, jid)
                if has_faults:
                    for k in np.flatnonzero(tr["drain_d"][t, i]):
                        d = self._faults[i].drains[k]
                        tracer.drain(env, now, d.resource, d.units)
                    for k in np.flatnonzero(tr["restore_d"][t, i]):
                        d = self._faults[i].drains[k]
                        tracer.restore(env, now, d.resource, d.units)
                if not ro.decided[t, i]:
                    continue
                a = int(ro.actions[t, i])
                j_star = int(tr["j_star"][t, i])
                fit = bool(tr["fit"][t, i])
                jid = js[j_star].jid
                tracer.decision(env, now, a, jid, int(tr["qlen"][t, i]),
                                1 if fit else 0)
                if fit:
                    tracer.job_start(env, now, jid, 0)
                else:
                    tracer.reserve(env, now, jid)
                    if lay.backfill:
                        bf = np.flatnonzero(tr["start_d"][t, i])
                        for j in bf:   # ascending index == queue order
                            tracer.job_start(env, now, js[j].jid, 1)
                        tracer.backfill(env, now, len(bf))

    def run(self) -> List[SimResult]:
        """Greedy rollout; result contract matches the host engines."""
        return self.rollout().results

    # ------------------------------------------------------------- results
    def _results(self, out) -> List[SimResult]:
        results = []
        for i, js in enumerate(self.jobsets):
            jobs = []
            for j, job in enumerate(js):
                job = job.copy()
                job.requeues = int(out["requeues"][i, j])
                job.failed_work = float(out["failed_work"][i, j])
                fs = float(out["first_start_j"][i, j])
                if fs >= 0.0:
                    job.first_start = fs
                if out["finished"][i, j]:
                    job.state = FINISHED
                elif out["failed"][i, j]:
                    job.state = FAILED
                if out["started"][i, j]:
                    job.start = float(out["start"][i, j])
                    e = float(out["end"][i, j])
                    job.end = e if np.isfinite(e) else -1.0
                jobs.append(job)
            started = [jb for jb in jobs if jb.started]
            cluster = Cluster(self.resources)
            acc = MetricsAccumulator(cluster)
            acc.last_time = float(out["now"][i])
            acc.start_time = (float(out["first_start"][i]) if started
                              else None)
            # Busy area = completed attempts' occupancy + the work lost to
            # killed attempts (the host integral counted the latter while
            # the doomed attempts were running).  Drained units are
            # phantom-owned, so they contribute to neither term.
            for r, n in enumerate(self.layout.names):
                done_area = sum(
                    jb.demands.get(n, 0) * (jb.end - jb.start)
                    for jb in jobs if jb.state == FINISHED)
                acc.busy_area[n] = done_area + float(out["failed_area"][i, r])
            metrics = acc.summarize(started, all_jobs=jobs)
            metrics.truncated_jobs = int(out["truncated"][i])
            results.append(SimResult(
                metrics=metrics,
                jobs=jobs,
                makespan=float(out["now"][i]),
                decisions=int(out["decisions"][i]),
                n_unstarted=len(jobs) - len(started),
                truncated_jobs=int(out["truncated"][i]),
                requeues=metrics.requeues,
                n_failed=metrics.n_failed))
        return results


def run_traces_device(resources: Sequence[ResourceSpec],
                      jobsets: Sequence[Sequence[Job]], policy,
                      config: SimConfig | None = None,
                      faults=None) -> List[SimResult]:
    """Convenience device counterpart of ``run_trace``/``run_traces``."""
    cfg = config or SimConfig.for_engine("device")
    return DeviceSimulator(resources, jobsets, policy, cfg,
                           faults=faults).run()
