"""The system under test, built from a benchmark configuration.

The only module of the benchmark that imports the program (``repro``),
and the one place where its kernel path is chosen: every cell runs the
policy on the Pallas backend, as users on a TPU do.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

BACKEND = "pallas"


def resources(cfg: dict):
    from repro.sim import ResourceSpec
    caps = {"node": cfg["nodes"], "bb": cfg["bb_units"]}
    return [ResourceSpec(r, int(caps[r])) for r in cfg["resources"]]


def agent(cfg: dict, params=None):
    """An ``MRSchAgent`` with the configuration's widths; ``params``
    (made by the benchmark from the seed) replace its own."""
    from repro.core import AgentConfig, MRSchAgent
    ac = AgentConfig(
        window=cfg["window"], offsets=tuple(cfg["offsets"]),
        temporal_weights=tuple(cfg["temporal_weights"]),
        state_module=cfg["state_module"], backend=BACKEND,
        state_hidden=tuple(cfg.get("state_hidden", (4000, 1000))),
        state_out=cfg["state_out"], module_hidden=cfg["module_hidden"],
        stream_hidden=cfg["stream_hidden"],
        queue_cap=cfg.get("queue_cap", 128), attn_dim=cfg.get("attn_dim", 64),
        attn_heads=cfg.get("attn_heads", 4),
        attn_layers=cfg.get("attn_layers", 2),
        attn_mlp_mult=cfg.get("attn_mlp_mult", 2))
    a = MRSchAgent(resources(cfg), ac)
    if params is not None:
        a.params = params
    return a


def jobs(cfg: dict, tr: Dict[str, np.ndarray]) -> List:
    """A benchmark trace as the program's ``Job`` list."""
    from repro.sim import Job
    names = cfg["resources"]
    return [Job(jid=int(tr["jid"][j]), submit=float(tr["submit"][j]),
                runtime=float(tr["runtime"][j]),
                walltime=float(tr["walltime"][j]),
                demands={n: int(tr["demands"][j, r])
                         for r, n in enumerate(names)})
            for j in range(len(tr["jid"]))]
