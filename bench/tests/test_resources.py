"""The cluster and trace model follow the configuration's ``resources``:
capacities, the power draw of MRSch's three-resource case (Sec. V-E),
the widths the reference network takes, and the existing cells' traces
and weights pinned to what they were before power was added."""
import copy
import hashlib
import json

import jax
import numpy as np
import pytest

import helpers
from harness import core, program, refnet, workload

DATA = helpers.BENCH / "tests" / "data"
THETA_MLP = json.loads((helpers.BENCH / "configs" / "theta-mlp.json").read_text())
TINY_POWER = json.loads((DATA / "tiny-power.json").read_text())
S6_S10 = {"scenarios": ["S6", "S7", "S8", "S9", "S10"], "compression": 1.0}


def theta_power() -> dict:
    """Theta with the paper's 500 kW budget and 100-215 W a node."""
    cfg = copy.deepcopy(THETA_MLP)
    cfg.update(name="theta-power", resources=["node", "bb", "power"],
               power_budget_kw=500, state_dim=12420)
    cfg["job_statistics"]["power_w_per_node"] = [100.0, 215.0]
    return cfg


def program_state_dim(cfg: dict) -> int:
    """The program agent's state width, without building its weights."""
    from repro.core.encoding import EncodingConfig
    res = program.resources(cfg)
    return EncodingConfig(window=cfg["window"],
                          resource_names=tuple(r.name for r in res),
                          capacities=tuple(r.capacity for r in res),
                          state_module=cfg["state_module"],
                          queue_cap=cfg.get("queue_cap", 0)).state_dim


@pytest.mark.parametrize("cfg,width", [(TINY_POWER, 10 * 5 + 2 * (64 + 16 + 8)),
                                       (theta_power(), 12420),
                                       (THETA_MLP, 11410)])
def test_state_width_is_the_programs(cfg, width):
    assert refnet.state_dim(cfg) == program_state_dim(cfg) == width
    assert cfg["state_dim"] == width


def test_power_is_a_resource_in_kw():
    res = program.resources(theta_power())
    assert [(r.name, r.capacity, r.unit) for r in res] == [
        ("node", 4392, ""), ("bb", 1293, ""), ("power", 500, "kW")]


def test_first_layer_counts_the_power_units():
    layers = refnet.dense_layers(theta_power(), 40)
    assert (40, 12420, 4000) in layers
    # Measurement and goal modules take R = 3; the action stream W*T*R.
    assert (40, 3, 128) in layers and (40, 512, 10 * 6 * 3) in layers


@pytest.mark.parametrize("cfg,traffic,n_traces,n_jobs", [
    (TINY_POWER, {"scenarios": ["S6", "S9"], "compression": 1.0}, 6, 24),
    (theta_power(), S6_S10, 40, 512)])
def test_power_demand_never_exceeds_the_budget(cfg, traffic, n_traces, n_jobs):
    traces = workload.make_traces(cfg, traffic, 2147483711, n_traces, n_jobs)
    power = np.concatenate([t["demands"][:, 2] for t in traces])
    assert power.min() >= 1 and power.max() <= cfg["power_budget_kw"]
    # Jobs of every node of the system draw more than the budget unclamped.
    assert (power == cfg["power_budget_kw"]).any()


def test_s10_power_follows_the_halved_node_counts():
    cfg, seed = TINY_POWER, 2147483712
    s9 = workload.make_trace(cfg, {"scenarios": ["S9"]}, seed, 0, 64)
    s10 = workload.make_trace(cfg, {"scenarios": ["S10"]}, seed, 0, 64)
    assert np.array_equal(s9["jid"], s10["jid"])
    node9, node10 = s9["demands"][:, 0], s10["demands"][:, 0]
    assert np.array_equal(node10, np.maximum(1, node9 // 2))
    assert np.array_equal(s9["demands"][:, 1], s10["demands"][:, 1])
    lo, hi = cfg["job_statistics"]["power_w_per_node"]
    w = workload.rng_for(seed, 0, 1).uniform(lo, hi, size=64)[s10["jid"]]
    for nodes, tr in ((node9, s9), (node10, s10)):
        want = np.minimum(np.maximum(1, np.ceil(nodes * w / 1000.0)),
                          cfg["power_budget_kw"])
        assert np.array_equal(tr["demands"][:, 2], want)
    assert (s10["demands"][:, 2] < s9["demands"][:, 2]).any()
    # S10 is S5 with power: a configuration with power draws it for either.
    s5 = workload.make_trace(cfg, {"scenarios": ["S5"]}, seed, 0, 64)
    assert np.array_equal(s5["demands"], s10["demands"])


def test_power_leaves_the_other_columns_as_they_were():
    no_power = {**TINY_POWER, "resources": ["node", "bb"]}
    with_power = workload.make_trace(TINY_POWER, {"scenarios": ["S7"]}, 5, 2, 48)
    without = workload.make_trace(no_power, {"scenarios": ["S2"]}, 5, 2, 48)
    for k in ("jid", "submit", "runtime", "walltime"):
        assert np.array_equal(with_power[k], without[k])
    assert np.array_equal(with_power["demands"][:, :2], without["demands"])


@pytest.mark.parametrize("change,message", [
    ({"resources": ["node", "bb", "gpu"]}, "unknown resource 'gpu'"),
    ({"resources": ["node", "bb", "power"]}, "'power_budget_kw'")])
def test_unknown_resource_or_capacity_is_refused(tmp_path, change, message):
    cfg = {**json.loads((DATA / "tiny-mlp.json").read_text()), **change}
    with pytest.raises(ValueError, match=message):
        workload.capacities(cfg)
    root = helpers.tiny_root(tmp_path)
    (root / "bench" / "tests" / "data" / "tiny-mlp.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=message):
        core.find_cell(root, "rollout.tiny-mlp.grid")


@pytest.mark.parametrize("config,name,message", [
    ("tiny-mlp", "S6", "no power resource"),
    ("tiny-mlp", "S10", "no power resource"),
    ("tiny-power", "S11", "unknown scenario")])
def test_power_scenario_is_refused_without_power(config, name, message):
    cfg = json.loads((DATA / f"{config}.json").read_text())
    with pytest.raises(ValueError, match=message):
        workload.make_trace(cfg, {"scenarios": [name]}, 1, 0, 8)


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 of every trace of one seed of each cell's traffic and of its
# first policy's weights (made on the CPU), as the benchmark made them
# before the harness followed the configuration's resources.
PINNED = {
    "rollout.theta-mlp.grid": (
        "theta-mlp", "grid", 2147483659,
        "b5ccb95a788cba9bce118d6303c160899788e573489277ea75ebc368a312229d",
        "ce0a025c818d4b0030d98758fccc48f5b7e7826653aa852ba2125575d83e08f2"),
    "rollout.theta-attn.backlog": (
        "theta-attn", "backlog", 2147483681,
        "ed19470e0a884a09e138d5839aad68cc8042302fcfbe870c09d9466fb0ea1203",
        "e78ecbe14c087e4679547bb913426d92da271999aba18fc5e574aa911ee4ab95"),
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_existing_cells_traces_and_weights_are_unchanged(cell):
    config, traffic, seed, traces_sha, params_sha = PINNED[cell]
    cfg = json.loads((helpers.BENCH / "configs" / f"{config}.json").read_text())
    tf = json.loads((helpers.BENCH / "traffic" / f"{traffic}.json").read_text())
    traces = workload.make_traces(cfg, tf["trace"], seed, int(tf["envs"]),
                                  int(cfg["trace_jobs"]))
    assert _digest(a for tr in traces for a in (
        tr["jid"], tr["submit"], tr["runtime"], tr["walltime"],
        tr["demands"])) == traces_sha
    params = refnet.make_params(cfg, seed, 0)
    assert _digest(jax.tree_util.tree_leaves(params)) == params_sha
