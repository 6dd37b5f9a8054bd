"""Queue-as-tokens attention state module: layout, property tests
(padding invariance, permutation equivariance), backend x module parity,
checkpoint portability, engine agreement, end-to-end training, serving.
"""
import os
import tempfile
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import AgentConfig, MRSchAgent, evaluate, train_agent
from repro.core.dfp import DFPConfig, action_values, init_params, loss_fn
from repro.core.encoding import EncodingConfig, encode_state
from repro.core.train import TrainConfig
from repro.nn.queue_encoder import (QueueEncoderConfig, encode_queue_tokens,
                                    queue_encoder_init, queue_state_features)
from repro.sim import (Job, ResourceSpec, SimConfig, Simulator, run_trace,
                       run_traces, run_traces_device)
from repro.workloads import ThetaConfig, build_jobs

RES = [ResourceSpec("node", 16), ResourceSpec("bb", 8)]


def tiny_agent(module="attention", seed=0, backend="xla", window=4,
               queue_cap=12, **kw):
    return MRSchAgent(RES, AgentConfig(
        window=window, state_module=module, queue_cap=queue_cap,
        state_hidden=(32,), state_out=16, module_hidden=8, stream_hidden=16,
        attn_dim=8, attn_heads=2, attn_layers=1, seed=seed, backend=backend,
        **kw))


def synth_jobs(seed, n=24, span=150.0):
    rng = np.random.default_rng(seed)
    return [Job(jid=i, submit=float(rng.uniform(0, span)),
                runtime=float(rng.integers(30, 400)),
                walltime=float(rng.integers(60, 600)),
                demands={"node": int(rng.integers(1, 10)),
                         "bb": int(rng.integers(0, 5))})
            for i in range(n)]


def enc_cfg(queue_cap=8, window=4):
    return QueueEncoderConfig(queue_cap=queue_cap, job_dim=4, ctx_dim=4,
                              window=window, d_model=8, n_heads=2,
                              n_layers=2, mlp_mult=2, out_dim=16)


def flat_state(tokens, qlen, ctx, queue_cap):
    """Build the attention-layout state vector from its pieces."""
    B, n, jd = tokens.shape
    out = np.zeros((B, queue_cap * jd + 1 + ctx.shape[1]), np.float32)
    out[:, :queue_cap * jd].reshape(B, queue_cap, jd)[:, :n] = tokens
    out[:, queue_cap * jd] = qlen
    out[:, queue_cap * jd + 1:] = ctx
    return out


# ------------------------------------------------------------- layout math
def test_encoding_attention_state_dim_and_validation():
    cfg = EncodingConfig(window=4, resource_names=("node", "bb"),
                         capacities=(16, 8), state_module="attention",
                         queue_cap=12)
    assert cfg.state_dim == 12 * 4 + 1 + 4
    with pytest.raises(ValueError, match="queue_cap"):
        EncodingConfig(window=4, resource_names=("node",), capacities=(8,),
                       state_module="attention", queue_cap=2)
    with pytest.raises(ValueError, match="state_module"):
        EncodingConfig(window=4, resource_names=("node",), capacities=(8,),
                       state_module="transformer")
    with pytest.raises(ValueError, match="state_dim mismatch"):
        DFPConfig(state_dim=99, n_measurements=2, n_actions=4,
                  state_module="attention", attn_queue=12)


def test_encode_state_attention_layout_values():
    """Hand-check tokens / queue_len / context against a live cluster."""
    enc = EncodingConfig(window=2, resource_names=("node", "bb"),
                         capacities=(16, 8), state_module="attention",
                         queue_cap=4)
    jobs = [Job(jid=i, submit=10.0 * i, runtime=100.0, walltime=200.0,
                demands={"node": 4, "bb": 2}) for i in range(6)]
    sim = Simulator(RES, jobs, policy=None, config=SimConfig(window=2))
    ctx = sim.next_decision()
    state = encode_state(enc, ctx)
    jd, Q = enc.job_dim, enc.queue_cap
    assert state[Q * jd] == min(ctx.queue_len, Q)
    # token 0 = first waiting job: [node_frac, bb_frac, wall_norm, queued]
    j0 = ctx.queue[0]
    np.testing.assert_allclose(
        state[:jd], [4 / 16, 2 / 8, 200.0 / enc.time_scale,
                     (ctx.now - j0.submit) / enc.time_scale], rtol=1e-6)
    # idle cluster: free fraction 1, mean time-to-free 0 (per resource)
    np.testing.assert_allclose(state[Q * jd + 1: Q * jd + 5],
                               [1.0, 0.0, 1.0, 0.0], atol=1e-7)


# ------------------------------------------------------ padding invariance
@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n_jobs=st.integers(0, 8),
       extra=st.integers(1, 24))
def test_padding_length_invariance(seed, n_jobs, extra):
    """Features must not depend on how much padding the buffer carries:
    the same valid tokens through queue_cap=8 and queue_cap=8+extra give
    the same state features under the SAME parameters (the parameter
    tree is buffer-size-agnostic by construction)."""
    cfg1 = enc_cfg(queue_cap=8)
    cfg2 = replace(cfg1, queue_cap=8 + extra)
    params = queue_encoder_init(jax.random.PRNGKey(seed), cfg1)
    rng = np.random.default_rng(seed)
    tokens = rng.normal(size=(2, n_jobs, 4)).astype(np.float32)
    qlen = np.full(2, float(n_jobs), np.float32)
    ctx = rng.normal(size=(2, 4)).astype(np.float32)
    out1 = queue_state_features(params, cfg1, jnp.asarray(
        flat_state(tokens, qlen, ctx, 8)))
    out2 = queue_state_features(params, cfg2, jnp.asarray(
        flat_state(tokens, qlen, ctx, 8 + extra)))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_permutation_equivariance(seed):
    """No positional embeddings: permuting the (fully valid) job tokens
    permutes the per-token embeddings and leaves the context token
    invariant — slot identity comes only from the pooled window readout."""
    cfg = enc_cfg(queue_cap=6)
    params = queue_encoder_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 1)
    tokens = rng.normal(size=(1, 6, 4)).astype(np.float32)
    qlen = jnp.asarray([6.0])
    ctx = jnp.asarray(rng.normal(size=(1, 4)).astype(np.float32))
    perm = rng.permutation(6)
    h = encode_queue_tokens(params, cfg, jnp.asarray(tokens), qlen, ctx)
    hp = encode_queue_tokens(params, cfg, jnp.asarray(tokens[:, perm]),
                             qlen, ctx)
    np.testing.assert_allclose(np.asarray(hp[:, 0]), np.asarray(h[:, 0]),
                               rtol=1e-4, atol=1e-5)     # context invariant
    np.testing.assert_allclose(np.asarray(hp[:, 1:]),
                               np.asarray(h[:, 1:][:, perm]),
                               rtol=1e-4, atol=1e-5)     # tokens equivariant


# ---------------------------------------------- backend x module parity
@pytest.mark.parametrize("module", ["mlp", "attention"])
@pytest.mark.parametrize("batch", [1, 5])
def test_backend_parity_outputs_and_grads(module, batch):
    """xla and pallas produce the same action values AND the same
    parameter gradients for both state modules, at N=1 and batched."""
    qcap = 12
    cfg_x = DFPConfig(
        state_dim=(qcap * 4 + 1 + 4) if module == "attention" else 40,
        n_measurements=2, n_actions=4, state_module=module,
        state_hidden=(16,), state_out=8, module_hidden=4, stream_hidden=8,
        attn_queue=qcap, attn_dim=8, attn_heads=2, attn_layers=1,
        backend="xla")
    cfg_p = replace(cfg_x, backend="pallas")
    params = init_params(jax.random.PRNGKey(3), cfg_x)
    rng = np.random.default_rng(batch)
    state = rng.normal(size=(batch, cfg_x.state_dim)).astype(np.float32)
    if module == "attention":
        # Realistic layout: valid queue length + zeroed padding tail.
        qlen = rng.integers(0, qcap + 1, batch)
        toks = state[:, :qcap * 4].reshape(batch, qcap, 4)
        for b, n in enumerate(qlen):
            toks[b, n:] = 0.0
        state[:, qcap * 4] = qlen
    meas = rng.random((batch, 2)).astype(np.float32)
    goal = rng.random((batch, 2)).astype(np.float32)
    goal /= goal.sum(axis=1, keepdims=True)
    u_x = action_values(params, cfg_x, state, meas, goal)
    u_p = action_values(params, cfg_p, state, meas, goal)
    np.testing.assert_allclose(np.asarray(u_x), np.asarray(u_p),
                               rtol=2e-4, atol=2e-4)
    batch_d = {
        "state": jnp.asarray(state), "meas": jnp.asarray(meas),
        "goal": jnp.asarray(goal),
        "action": jnp.zeros(batch, jnp.int32),
        "target": jnp.ones((batch, cfg_x.n_offsets, 2)),
        "target_mask": jnp.ones((batch, cfg_x.n_offsets)),
    }
    g_x = jax.grad(loss_fn)(params, cfg_x, batch_d)
    g_p = jax.grad(loss_fn)(params, cfg_p, batch_d)
    for gx, gp in zip(jax.tree_util.tree_leaves(g_x),
                      jax.tree_util.tree_leaves(g_p)):
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gp),
                                   rtol=5e-3, atol=1e-4)


# -------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_cross_module_failure():
    """An attention checkpoint restores exactly; loading it into an MLP
    agent (or vice versa) fails loudly via check_leaves_compat."""
    attn = tiny_agent("attention", seed=1)
    mlp = tiny_agent("mlp", seed=1)
    with tempfile.TemporaryDirectory() as d:
        pa = os.path.join(d, "attn.npz")
        attn.save(pa)
        clone = tiny_agent("attention", seed=2)
        clone.load(pa)
        for a, b in zip(jax.tree_util.tree_leaves(attn.params),
                        jax.tree_util.tree_leaves(clone.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        with pytest.raises(ValueError):
            mlp.load(pa)
        pm = os.path.join(d, "mlp.npz")
        mlp.save(pm)
        with pytest.raises(ValueError):
            attn.load(pm)


def test_train_config_rejects_module_switch():
    agent = tiny_agent("mlp")
    with pytest.raises(ValueError, match="state_module"):
        train_agent(agent, RES, [synth_jobs(0)],
                    config=TrainConfig(n_envs=1, state_module="attention"))


# ------------------------------------------------------------- the engines
@pytest.mark.parametrize("module", ["mlp", "attention"])
def test_three_engines_agree_with_module(module):
    """Sequential, lockstep-vector, and device rollouts produce identical
    schedules and metrics (incl. truncated_jobs) for both state modules."""
    agent = tiny_agent(module, seed=4)
    jobs = synth_jobs(7, n=20)
    r_seq = run_trace(RES, jobs, agent, window=4)
    r_vec = run_traces(RES, [jobs], agent, window=4)[0]
    r_dev = run_traces_device(RES, [jobs], agent,
                              SimConfig.for_engine("device", window=4))[0]
    assert (r_seq.truncated_jobs == r_vec.truncated_jobs
            == r_dev.truncated_jobs)
    rows = [r.metrics.as_row() for r in (r_seq, r_vec, r_dev)]
    for key in rows[0]:
        vals = [row[key] for row in rows]
        np.testing.assert_allclose(vals, vals[0], rtol=2e-5, atol=2e-4,
                                   err_msg=key)


# ----------------------------------------------------------- end to end
@pytest.mark.slow
def test_attention_trains_end_to_end_on_registry_scenario():
    """Loss decreases over a short vectorized run on huge-queue-flood,
    and the trained agent evaluates cleanly on the held-out trace."""
    cfg = ThetaConfig.mini(seed=0, duration_days=0.4, jobs_per_day=140.0)
    res = cfg.resources()
    agent = MRSchAgent(res, AgentConfig(
        state_module="attention", queue_cap=32,
        state_hidden=(64,), state_out=32, module_hidden=16,
        stream_hidden=32, attn_dim=16, attn_heads=2, attn_layers=1,
        batch_size=32, grad_steps_per_episode=48, eps_decay=0.6, seed=0))
    sets = [build_jobs("huge-queue-flood", cfg, seed=s) for s in (1, 2, 3)]
    log = train_agent(agent, res, sets, config=TrainConfig(n_envs=2))
    assert len(log.episode_losses) >= 2
    assert log.episode_losses[-1] < log.episode_losses[0]
    r = evaluate(agent, res, build_jobs("huge-queue-flood", cfg, seed=9),
                 window=agent.config.window)
    assert r.decisions > 0 and np.isfinite(r.metrics.avg_wait)
    assert r.truncated_jobs > 0            # the scenario actually floods


@pytest.mark.slow
def test_serving_smoke_with_attention_agent():
    """The decision service accepts the wider attention-layout rows and
    answers exactly like the agent's evaluation-mode select."""
    from repro.serve import DecisionService, ServeConfig
    agent = tiny_agent("attention", seed=6)
    jobs = synth_jobs(11, n=18)
    sim = Simulator(RES, jobs, agent, SimConfig(window=4))
    ctxs = []
    ctx = sim.next_decision()
    for _ in range(6):
        if ctx is None:
            break
        ctxs.append(ctx)
        sim.post_action(agent.select(ctx))
        ctx = sim.next_decision()
    assert ctxs
    with DecisionService(agent, ServeConfig(max_batch=4,
                                            warmup=False)) as svc:
        for c in ctxs:
            assert svc.decide(c) == agent.select(c)
