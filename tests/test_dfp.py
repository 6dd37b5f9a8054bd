"""DFP network, goal vector (Eq. 1), replay targets, agent learning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (AgentConfig, DFPConfig, MRSchAgent, ReplayBuffer,
                        action_values, goal_vector, init_params, loss_fn,
                        predict)
from repro.core.replay import Episode
from repro.sim import Cluster, Job, ResourceSpec
from repro.sim.simulator import SchedContext


def small_cfg(state_module="mlp"):
    return DFPConfig(state_dim=64, n_measurements=2, n_actions=5,
                     offsets=(1, 2, 4), temporal_weights=(0.0, 0.5, 1.0),
                     state_hidden=(32, 16), state_out=16, module_hidden=8,
                     stream_hidden=16, state_module=state_module)


@pytest.mark.parametrize("module", ["mlp", "cnn"])
def test_predict_shapes(module):
    cfg = small_cfg(module)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B = 3
    p = predict(params, cfg, jnp.ones((B, 64)), jnp.ones((B, 2)),
                jnp.ones((B, 2)))
    assert p.shape == (B, cfg.n_actions, 3, 2)
    u = action_values(params, cfg, jnp.ones((B, 64)), jnp.ones((B, 2)),
                      jnp.ones((B, 2)))
    assert u.shape == (B, cfg.n_actions)


def test_dueling_normalization():
    """Action-stream is zero-mean over actions: mean_a p(a) equals the
    expectation stream, a property of the dueling decomposition."""
    cfg = small_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    s, m, g = (jax.random.normal(jax.random.PRNGKey(i), sh) for i, sh in
               enumerate([(2, 64), (2, 2), (2, 2)]))
    p = predict(params, cfg, s, m, g)               # (B, A, T, M)
    # Mean over actions must be action-independent (= expectation stream):
    mean_a = p.mean(axis=1)
    # Recompute with permuted action outputs should keep the same mean.
    assert np.all(np.isfinite(np.asarray(p)))
    centered = p - mean_a[:, None]
    assert np.allclose(np.asarray(centered.mean(axis=1)), 0.0, atol=1e-5)


def _ctx(cluster, window, now=0.0, queue=None):
    return SchedContext(now=now, cluster=cluster, window=window,
                        queue_len=len(window),
                        running=[rj.job for rj in cluster.running_jobs()],
                        queue=queue if queue is not None else list(window))


def test_goal_vector_eq1():
    """Eq. (1): weights proportional to sum_i P_ij * t_i, normalized."""
    c = Cluster([ResourceSpec("node", 10), ResourceSpec("bb", 10)])
    j1 = Job(0, 0, 100, 100, {"node": 5, "bb": 0})   # 0.5 * 100 node-time
    j2 = Job(1, 0, 200, 200, {"node": 0, "bb": 5})   # 0.5 * 200 bb-time
    g = goal_vector(_ctx(c, [j1, j2]), ("node", "bb"), (10, 10))
    assert g.sum() == pytest.approx(1.0, abs=1e-6)
    assert g[1] == pytest.approx(2.0 / 3.0, abs=1e-5)   # bb twice as hot


def test_goal_vector_includes_running_remaining_time():
    c = Cluster([ResourceSpec("node", 10), ResourceSpec("bb", 10)])
    r = Job(7, 0, 100, 100, {"node": 10, "bb": 0})
    c.allocate(r, 0.0)
    # at now=50 the running job has 50s of node demand left
    q = Job(8, 0, 50, 50, {"node": 0, "bb": 10})
    g = goal_vector(_ctx(c, [q], now=50.0), ("node", "bb"), (10, 10))
    assert g[0] == pytest.approx(0.5, abs=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 10), st.integers(0, 10),
                          st.floats(1, 1000)), min_size=1, max_size=10))
def test_goal_vector_simplex(jobs_spec):
    c = Cluster([ResourceSpec("node", 10), ResourceSpec("bb", 10)])
    window = [Job(i, 0, t, t, {"node": n, "bb": b})
              for i, (n, b, t) in enumerate(jobs_spec)]
    g = goal_vector(_ctx(c, window), ("node", "bb"), (10, 10))
    assert g.shape == (2,)
    assert g.min() >= 0
    assert g.sum() == pytest.approx(1.0, abs=1e-5)


def test_replay_future_targets():
    buf = ReplayBuffer(offsets=(1, 2), capacity_rows=100)
    meas = np.array([[0.0, 0.0], [1.0, 0.5], [2.0, 1.0]], np.float32)
    ep = Episode(states=np.zeros((3, 4), np.float32), meas=meas,
                 goals=np.ones((3, 2), np.float32),
                 actions=np.zeros(3, np.int32))
    buf.add(ep)
    rng = np.random.default_rng(0)
    batch = buf.sample(rng, 64)
    # for row t=0: target at offset 1 = m1-m0 = [1, .5]; offset 2 = [2, 1]
    sel = batch["state"].sum(1) == 0      # all rows, find t via meas
    t0 = np.where((batch["meas"] == [0, 0]).all(1))[0]
    assert len(t0) > 0
    np.testing.assert_allclose(batch["target"][t0[0], 0], [1.0, 0.5])
    np.testing.assert_allclose(batch["target"][t0[0], 1], [2.0, 1.0])
    np.testing.assert_allclose(batch["target_mask"][t0[0]], [1.0, 1.0])
    t2 = np.where((batch["meas"] == [2, 1]).all(1))[0]
    np.testing.assert_allclose(batch["target_mask"][t2[0]], [0.0, 0.0])


def test_loss_fits_synthetic_targets():
    """A few Adam steps must reduce the DFP loss on a fixed batch."""
    from repro.nn.optim import adam_init, adam_update
    cfg = small_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = jax.random.PRNGKey(42)
    batch = {
        "state": jax.random.normal(rng, (32, 64)),
        "meas": jax.random.uniform(rng, (32, 2)),
        "goal": jax.random.uniform(rng, (32, 2)),
        "action": jax.random.randint(rng, (32,), 0, 5),
        "target": jax.random.normal(rng, (32, 3, 2)) * 0.1,
        "target_mask": jnp.ones((32, 3)),
    }
    opt = adam_init(params)
    l0 = float(loss_fn(params, cfg, batch))
    p = params
    for _ in range(60):
        loss, grads = jax.value_and_grad(loss_fn)(p, cfg, batch)
        p, opt = adam_update(grads, opt, p, lr=3e-4)
    l1 = float(loss_fn(p, cfg, batch))
    assert l1 < l0 * 0.7, (l0, l1)


def test_agent_paper_state_dim():
    """Full Theta-scale encoding reproduces the paper's 11410-dim state."""
    res = [ResourceSpec("node", 4392), ResourceSpec("bb", 1293)]
    agent = MRSchAgent(res, AgentConfig(state_hidden=(16,), state_out=8,
                                        module_hidden=4))
    assert agent.enc.state_dim == 11410


def test_backend_parity_forward():
    """xla and pallas backends compute the same DFP outputs from the
    same params (parity bound: f32 accumulation reorder only)."""
    import dataclasses
    cfg = small_cfg()
    cfgp = dataclasses.replace(cfg, backend="pallas")
    params = init_params(jax.random.PRNGKey(0), cfg)
    B = 4
    s = jax.random.normal(jax.random.PRNGKey(1), (B, 64))
    m = jax.random.uniform(jax.random.PRNGKey(2), (B, 2))
    g = jax.random.uniform(jax.random.PRNGKey(3), (B, 2))
    np.testing.assert_allclose(
        np.asarray(predict(params, cfg, s, m, g)),
        np.asarray(predict(params, cfgp, s, m, g)), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(action_values(params, cfg, s, m, g)),
        np.asarray(action_values(params, cfgp, s, m, g)),
        rtol=1e-4, atol=1e-5)


def test_backend_parity_gradients():
    """Training-path parity: loss and its full parameter gradient pytree
    match across backends, so the custom-VJP fused backward is a drop-in
    for XLA autodiff."""
    import dataclasses
    cfg = small_cfg()
    cfgp = dataclasses.replace(cfg, backend="pallas")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = jax.random.PRNGKey(42)
    B = 8
    batch = {
        "state": jax.random.normal(rng, (B, 64)),
        "meas": jax.random.uniform(rng, (B, 2)),
        "goal": jax.random.uniform(rng, (B, 2)),
        "action": jax.random.randint(rng, (B,), 0, 5),
        "target": jax.random.normal(rng, (B, 3, 2)) * 0.1,
        "target_mask": jnp.ones((B, 3)),
    }
    lx, gx = jax.value_and_grad(loss_fn)(params, cfg, batch)
    lp, gp = jax.value_and_grad(loss_fn)(params, cfgp, batch)
    assert float(lx) == pytest.approx(float(lp), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gx),
                    jax.tree_util.tree_leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-5)


def test_backend_validation():
    with pytest.raises(ValueError, match="unknown nn backend"):
        DFPConfig(state_dim=8, n_measurements=2, n_actions=3,
                  backend="tensorflow")
    agent = MRSchAgent([ResourceSpec("node", 8), ResourceSpec("bb", 4)],
                       AgentConfig(state_hidden=(8,), state_out=4,
                                   module_hidden=2, stream_hidden=4))
    with pytest.raises(ValueError, match="unknown nn backend"):
        agent.set_backend("nope")
    assert agent.dfp.backend == "xla"
    agent.set_backend("pallas")
    assert agent.dfp.backend == "pallas"
    assert agent.config.backend == "pallas"


def test_agent_select_masks_window(rng):
    res = [ResourceSpec("node", 16), ResourceSpec("bb", 8)]
    agent = MRSchAgent(res, AgentConfig(state_hidden=(16,), state_out=8,
                                        module_hidden=4))
    c = Cluster(res)
    window = [Job(0, 0, 10, 10, {"node": 1})]
    a = agent.select(_ctx(c, window))
    assert a == 0                         # only one valid slot
