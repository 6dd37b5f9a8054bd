"""Comparisons of what the timed path produced with the plain reference."""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from . import refnet
from .reference import Layout, Replay, Scheduler


def score_gaps(u_ref: np.ndarray, valid: np.ndarray,
               actions: np.ndarray) -> np.ndarray:
    """Per row: how far the reference's value of the chosen action lies
    below the reference's best valid action (0 where they agree)."""
    best = np.where(valid, u_ref, -np.inf).max(axis=1)
    chosen = np.take_along_axis(u_ref, actions[:, None], axis=1)[:, 0]
    return best - chosen


def replay_envs(cfg: dict, traces: Sequence[Dict[str, np.ndarray]],
                actions: Sequence[np.ndarray],
                starts: Sequence[Dict[int, float]]) -> dict:
    """Replay each environment's recorded actions through the reference
    scheduler.  Returns the decision rows the reference built, the
    actions, and counts of every disagreement: a decision the reference
    could not take (action outside its window, or more or fewer
    decisions) and every job whose start time differs."""
    lay = Layout(cfg)
    rows: List[np.ndarray] = []
    acts: List[int] = []
    bad_decisions = 0
    bad_starts = 0
    for tr, a, st in zip(traces, actions, starts):
        sched = Scheduler(lay, tr, backfill=cfg["backfill"] == "easy")
        choose = Replay(a)
        try:
            env_rows = sched.run(choose)
        except IndexError:
            bad_decisions += 1
            bad_starts += len(tr["jid"])
            continue
        if choose.i != len(choose.actions):
            bad_decisions += 1
        rows += env_rows
        acts += choose.actions[:len(env_rows)]
        ref_start = dict(zip(tr["jid"].tolist(), sched.start.tolist()))
        bad_starts += sum(1 for jid, s in ref_start.items()
                          if st.get(jid, -1.0) != s)
    return {"envs": len(traces),
            "rows": np.stack(rows) if rows else np.zeros((0, lay.row_dim), np.float32),
            "actions": np.asarray(acts, np.int64),
            "bad_decisions": bad_decisions, "bad_starts": bad_starts}


def valid_mask(cfg: dict, rows: np.ndarray) -> np.ndarray:
    """The window slots each decision row marks as valid."""
    sd, R, W = refnet.state_dim(cfg), len(cfg["resources"]), cfg["window"]
    return rows[:, sd + 2 * R:sd + 2 * R + W] > 0.5


def gaps(params, cfg: dict, rows: np.ndarray, actions: np.ndarray,
         mode: str = "f32") -> np.ndarray:
    """Per decision row, the float32 reference's score gap of the chosen
    action; ``actions=None`` takes the choices of the reference computed
    in ``mode`` (a control put in the program's place)."""
    if len(rows) == 0:
        return np.zeros(0)
    u = refnet.scores(params, cfg, rows)
    valid = valid_mask(cfg, rows)
    if actions is None:
        u_ctl = refnet.scores(params, cfg, rows, mode=mode)
        actions = np.where(valid, u_ctl, -np.inf).argmax(axis=1)
    return score_gaps(u, valid, actions)
