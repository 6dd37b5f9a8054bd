"""Plain sequential scheduler: the reference the rollout cells are
compared with.

A straightforward event loop over one trace with the semantics of
MRSch (arXiv:2403.16298, Sec. III-C and IV): arrivals and job ends are
the events; every timestamp opens a scheduling pass in which a policy
picks a job from the window of the first W waiting jobs (queue order:
submit time, then job id).  A picked job that fits starts at once on the
lowest-numbered free units of each resource and the pass goes on; the
first that does not fit gets a reservation at its earliest fit time
(running jobs release at start + walltime), EASY backfill then starts
every waiting job, in queue order, that fits now and either ends before
the reservation or leaves it room, and the pass ends.  Events at one
timestamp apply together, ends before arrivals.

It also builds the policy's observation (Sec. III-A): the window jobs'
demand fractions, walltime and queued time, and per resource unit an
availability bit and the time until its estimated release, both times
over one day; the measurement (utilization per resource) and the Eq. (1)
goal.  ``attention`` layouts replace the unit sections by tokens for the
first Q waiting jobs, the queue length, and per resource the free
fraction and mean time-to-free of busy units.

Nothing here imports the program.
"""
from __future__ import annotations

import heapq
from typing import Callable, Dict, List, Optional

import numpy as np

DAY = 86400.0
TTF_HORIZON = 30.0 * DAY


class Layout:
    """Observation layout of one configuration."""

    def __init__(self, config: dict):
        self.caps = [int(config["nodes"]), int(config["bb_units"])]
        self.window = int(config["window"])
        self.state_module = config["state_module"]
        self.queue_cap = int(config.get("queue_cap", 0))
        self.R = len(self.caps)

    @property
    def state_dim(self) -> int:
        jd = self.R + 2
        if self.state_module == "attention":
            return self.queue_cap * jd + 1 + 2 * self.R
        return self.window * jd + 2 * sum(self.caps)

    @property
    def row_dim(self) -> int:
        return self.state_dim + 2 * self.R + self.window


class Context:
    """What a policy sees at one decision."""

    __slots__ = ("now", "queue", "release", "running", "row")

    def __init__(self, now, queue, release, running):
        self.now = now
        self.queue = queue          # job indices in queue order (a copy)
        self.release = release      # per resource: unit release times (live)
        self.running = running      # list of (job index, est_end)
        self.row: Optional[np.ndarray] = None   # its encoded decision row


def encode_row(lay: Layout, tr: Dict[str, np.ndarray], now: float,
               queue: List[int], release: List[np.ndarray],
               running: List[tuple]) -> np.ndarray:
    """One decision row [state | meas | goal | valid] in float32."""
    R, W, jd = lay.R, lay.window, lay.R + 2
    caps = np.asarray(lay.caps, np.float64)
    row = np.zeros(lay.row_dim, np.float64)

    def job_token(j):
        tok = np.empty(jd)
        tok[:R] = tr["demands"][j] / caps
        tok[R] = tr["walltime"][j] / DAY
        tok[R + 1] = (now - tr["submit"][j]) / DAY
        return tok

    if lay.state_module == "attention":
        Q = lay.queue_cap
        for s, j in enumerate(queue[:Q]):
            row[s * jd:(s + 1) * jd] = job_token(j)
        off = Q * jd
        row[off] = min(len(queue), Q)
        off += 1
        for r in range(R):
            rel = release[r]
            busy = rel > 0.0
            nb = int(busy.sum())
            row[off] = 1.0 - nb / caps[r]
            if nb:
                row[off + 1] = (np.clip(rel[busy] - now, 0.0, TTF_HORIZON).sum()
                                / nb / DAY)
            off += 2
    else:
        for s, j in enumerate(queue[:W]):
            row[s * jd:(s + 1) * jd] = job_token(j)
        off = W * jd
        for r in range(R):
            rel = release[r]
            c = lay.caps[r]
            busy = rel > 0.0
            row[off:off + c] = ~busy
            row[off + c:off + 2 * c] = np.where(
                busy, np.clip(rel - now, 0.0, TTF_HORIZON), 0.0) / DAY
            off += 2 * c
    sd = lay.state_dim
    free = np.asarray([(rel == 0.0).sum() for rel in release], np.float64)
    row[sd:sd + R] = 1.0 - free / caps                       # measurement
    q = np.asarray(queue, np.int64)
    acc = tr["walltime"][q] @ tr["demands"][q].astype(np.float64)
    if running:
        run = np.asarray([j for j, _ in running], np.int64)
        rem = np.maximum(np.asarray([e for _, e in running]) - now, 0.0)
        acc = acc + rem @ tr["demands"][run].astype(np.float64)
    dt = acc / caps
    row[sd + R:sd + 2 * R] = dt / dt.sum() if dt.sum() > 0 else 1.0 / R
    row[sd + 2 * R:sd + 2 * R + min(len(queue), W)] = 1.0
    return row.astype(np.float32)


class Scheduler:
    """One trace, one cluster; ``run(choose)`` drives it to the end."""

    def __init__(self, lay: Layout, tr: Dict[str, np.ndarray],
                 backfill: bool = True):
        self.lay = lay
        self.tr = tr
        self.backfill = backfill
        n = len(tr["submit"])
        self.start = np.full(n, -1.0)

    def run(self, choose: Callable[[Context], int]) -> List[np.ndarray]:
        """Run to the end; ``choose`` returns a window index for each
        decision.  Returns the decision rows, in decision order."""
        lay, tr = self.lay, self.tr
        dem = tr["demands"]
        release = [np.zeros(c) for c in lay.caps]
        free = list(lay.caps)
        owner = {}                      # job -> list of unit index arrays
        running = {}                    # job -> est_end
        queue: List[int] = []
        events = []                     # (time, kind 0=end 1=arrival, job)
        for j in range(len(tr["submit"])):
            heapq.heappush(events, (tr["submit"][j], 1, j))
        rows = []

        def fits(j):
            return all(dem[j, r] <= free[r] for r in range(lay.R))

        def start(j, now):
            est = now + tr["walltime"][j]
            units = []
            for r in range(lay.R):
                idx = np.flatnonzero(release[r] == 0.0)[:dem[j, r]]
                release[r][idx] = est
                free[r] -= int(dem[j, r])
                units.append(idx)
            owner[j] = units
            running[j] = est
            self.start[j] = now
            queue.remove(j)
            heapq.heappush(events, (now + tr["runtime"][j], 0, j))

        def earliest_fit(j, now):
            t = now
            for r in range(lay.R):
                need = int(dem[j, r])
                if need <= free[r]:
                    continue
                busy = np.sort(release[r][release[r] > 0.0])
                extra = need - free[r]
                if extra > len(busy):
                    return np.inf
                t = max(t, busy[extra - 1])
            return t

        def easy_backfill(res, now):
            t_res = earliest_fit(res, now)
            if not np.isfinite(t_res):
                return
            shadow = [int((release[r] <= t_res).sum()) - int(dem[res, r])
                      for r in range(lay.R)]
            for j in list(queue):
                if j == res or not fits(j):
                    continue
                ends_before = now + tr["walltime"][j] <= t_res
                if ends_before or all(dem[j, r] <= shadow[r]
                                      for r in range(lay.R)):
                    if not ends_before:
                        for r in range(lay.R):
                            shadow[r] -= int(dem[j, r])
                    start(j, now)

        while events:
            now = events[0][0]
            batch = []
            while events and events[0][0] == now:
                batch.append(heapq.heappop(events))
            for _, kind, j in sorted(batch):
                if kind == 0:
                    for r, idx in enumerate(owner.pop(j)):
                        release[r][idx] = 0.0
                        free[r] += len(idx)
                    del running[j]
                else:
                    queue.append(j)
            queue.sort()            # trace order is (submit, jid) order
            while queue:
                ctx = Context(now, list(queue), release,
                              list(running.items()))
                ctx.row = encode_row(lay, tr, now, queue, release, ctx.running)
                rows.append(ctx.row)
                a = int(choose(ctx))
                window = queue[:lay.window]
                if not 0 <= a < len(window):
                    raise IndexError(f"action {a} outside a window of "
                                     f"{len(window)}")
                j = window[a]
                if fits(j):
                    start(j, now)
                    continue
                if self.backfill:
                    easy_backfill(j, now)
                break
        return rows


class Replay:
    """``choose`` that replays a recorded action sequence."""

    def __init__(self, actions):
        self.actions = [int(a) for a in actions]
        self.i = 0

    def __call__(self, ctx: Context) -> int:
        if self.i >= len(self.actions):
            raise IndexError("the recorded run took fewer decisions than "
                             "the reference")
        a = self.actions[self.i]
        self.i += 1
        return a
