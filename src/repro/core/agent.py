"""The MRSch scheduling agent (paper §III).

Wraps the DFP network with: vector state encoding, the Eq. (1) dynamic goal
vector, epsilon-greedy exploration, the episodic replay buffer, and Adam
training on the future-measurement MSE loss.  Implements the simulator's
``SchedulingPolicy`` protocol, so the identical object drives the paper
reproduction benches and the fleet scheduler in ``repro.launch.scheduler``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.backend import resolve_backend
from ..nn.optim import AdamState, adam_init, adam_update
from ..sim.cluster import ResourceSpec
from ..sim.simulator import SchedContext
from .dfp import (DFPConfig, action_values, greedy_actions_packed,
                  init_params, loss_fn, prepare_params)
from .encoding import (EncodingConfig, decision_row_dim, encode_decision_row,
                       encode_measurement, encode_state, pad_decision_rows)
from .goal import ctx_goal
from .replay import EpisodeRecorder, ReplayBuffer, VectorEpisodeRecorder


@dataclass(frozen=True)
class AgentConfig:
    window: int = 10
    offsets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    temporal_weights: Tuple[float, ...] = (0.0, 0.0, 0.0, 0.5, 0.5, 1.0)
    lr: float = 1e-4
    batch_size: int = 64
    grad_steps_per_episode: int = 64
    buffer_rows: int = 200_000
    eps_start: float = 1.0
    eps_decay: float = 0.995          # paper §IV-C: alpha = 0.995
    eps_min: float = 0.02
    state_module: str = "mlp"         # "mlp" | "cnn" | "attention"
    backend: str = "xla"              # "xla" | "pallas" (fused-MLP kernel)
    state_hidden: Tuple[int, ...] = (4000, 1000)
    state_out: int = 512
    module_hidden: int = 128
    stream_hidden: int = 512
    # Queue-as-tokens knobs (state_module == "attention" only): the
    # encoder observes up to ``queue_cap`` waiting jobs instead of the
    # leading window of W.
    queue_cap: int = 128
    attn_dim: int = 64
    attn_heads: int = 4
    attn_layers: int = 2
    attn_mlp_mult: int = 2
    seed: int = 0
    grad_clip: float = 10.0


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2))
def _train_step(cfg: DFPConfig, params, opt_state, batch, lr, grad_clip):
    # Single-step variant, kept for per-step latency measurement
    # (benchmarks/bench_overhead.py); training uses _train_steps_scan.
    loss, grads = jax.value_and_grad(loss_fn)(params, cfg, batch)
    params, opt_state = adam_update(grads, opt_state, params, lr=lr,
                                    grad_clip=grad_clip)
    return params, opt_state, loss


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2))
def _train_steps_scan(cfg: DFPConfig, params, opt_state, batches, lr,
                      grad_clip):
    """K gradient steps in ONE dispatch: ``batches`` carries a leading
    step axis and ``lax.scan`` chains the updates, so an episode's whole
    training burst pays a single python->XLA round trip instead of K."""
    def body(carry, batch):
        params, opt_state = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, cfg, batch)
        gnorm = jnp.sqrt(sum(jnp.vdot(g, g)
                             for g in jax.tree_util.tree_leaves(grads)))
        params, opt_state = adam_update(grads, opt_state, params, lr=lr,
                                        grad_clip=grad_clip)
        return (params, opt_state), (loss, gnorm)

    (params, opt_state), (losses, gnorms) = jax.lax.scan(
        body, (params, opt_state), batches)
    return params, opt_state, losses, gnorms


@functools.partial(jax.jit, static_argnums=(1,))
def _values(params, cfg: DFPConfig, state, meas, goal, valid_mask):
    u = action_values(params, cfg, state[None], meas[None], goal[None])[0]
    return jnp.where(valid_mask, u, -jnp.inf)


class MRSchAgent:
    """DFP-based multi-resource scheduling agent."""

    def __init__(self, resources: Sequence[ResourceSpec],
                 config: AgentConfig = AgentConfig()):
        self.resources = list(resources)
        self.config = config
        names = tuple(r.name for r in self.resources)
        caps = tuple(r.capacity for r in self.resources)
        attention = config.state_module == "attention"
        self.enc = EncodingConfig(window=config.window, resource_names=names,
                                  capacities=caps,
                                  state_module=config.state_module,
                                  queue_cap=(config.queue_cap if attention
                                             else 0))
        self.dfp = DFPConfig(
            state_dim=self.enc.state_dim,
            n_measurements=len(names),
            n_actions=config.window,
            offsets=config.offsets,
            temporal_weights=config.temporal_weights,
            state_module=config.state_module,
            backend=config.backend,
            state_hidden=config.state_hidden,
            state_out=config.state_out,
            module_hidden=config.module_hidden,
            stream_hidden=config.stream_hidden,
            attn_queue=config.queue_cap,
            attn_dim=config.attn_dim,
            attn_heads=config.attn_heads,
            attn_layers=config.attn_layers,
            attn_mlp_mult=config.attn_mlp_mult,
        )
        key = jax.random.PRNGKey(config.seed)
        self.params = init_params(key, self.dfp)
        self.opt_state = adam_init(self.params)
        self.replay = ReplayBuffer(config.offsets, config.buffer_rows)
        self.recorder = EpisodeRecorder()
        self.vec_recorder = VectorEpisodeRecorder()
        self.rng = np.random.default_rng(config.seed)
        self.epsilon = config.eps_start
        self.training = False
        self.losses: List[float] = []
        self.goal_log: List[np.ndarray] = []
        # Pre-clip global gradient norm of the latest training burst,
        # surfaced into the telemetry registry by the vectorized trainer.
        self.last_grad_norm: Optional[float] = None

    def set_backend(self, backend: str) -> None:
        """Switch the NN execution backend ("xla" | "pallas") in place.

        Parameters are backend-agnostic (same pytree layout), so a
        checkpointed agent can be restored and re-run on either
        backend; the jitted forwards re-specialize on the new
        ``DFPConfig`` automatically (it is a static argument).
        """
        self.dfp = replace(self.dfp, backend=resolve_backend(backend))
        self.config = replace(self.config, backend=backend)

    # ------------------------------------------------------ Policy protocol
    # Device-side stages (repro.core.policy_api): the jitted rollout
    # engine threads ``init_state()`` through its scan and calls
    # ``score_window`` in-graph; the host stages below (``select`` /
    # ``select_batch``) are unchanged, so external callers keep working.
    requires_obs = True

    def init_state(self):
        """Policy-state pytree for the device rollout (the parameters)."""
        return self.params

    def prepare_state(self, params):
        """The rollout's once-per-rollout form of ``init_state()``: on the
        ``pallas`` backend every dense layer padded to the fused kernel's
        blocks (``dfp.prepare_params``), so the scan's rounds reuse one
        padded copy.  Pure; ``self.params`` keeps the logical layout."""
        return prepare_params(params, self.dfp)

    def score_window(self, params, obs) -> jnp.ndarray:
        """Action values from packed decision rows (pure, traceable).

        ``obs`` rows follow ``encoding.encode_decision_row``; the valid
        mask is applied by the engine, not here.  A one-row batch is
        numerically identical to the sequential ``_values`` scorer.
        """
        sd, m = self.enc.state_dim, self.enc.n_resources
        return action_values(params, self.dfp, obs[..., :sd],
                             obs[..., sd:sd + m], obs[..., sd + m:sd + 2 * m])

    # ---------------------------------------------------------------- policy
    def _ctx_goal(self, ctx: SchedContext) -> np.ndarray:
        """Eq. (1) goal for this context (shared with the serving layer)."""
        return ctx_goal(ctx, self.enc.resource_names)

    def select(self, ctx: SchedContext) -> int:
        state = encode_state(self.enc, ctx)
        meas = encode_measurement(self.enc, ctx)
        goal = self._ctx_goal(ctx)
        self.goal_log.append(goal)
        n_valid = min(len(ctx.window), self.config.window)
        if self.training and self.rng.uniform() < self.epsilon:
            action = int(self.rng.integers(0, n_valid))
        else:
            mask = np.zeros(self.config.window, bool)
            mask[:n_valid] = True
            u = _values(self.params, self.dfp, jnp.asarray(state),
                        jnp.asarray(meas), jnp.asarray(goal),
                        jnp.asarray(mask))
            action = int(np.argmax(np.asarray(u)))
        if self.training:
            self.recorder.record(state, meas, goal, action)
        return action

    def select_batch(self, ctxs: Sequence[SchedContext],
                     slots: Optional[Sequence[int]] = None) -> np.ndarray:
        """Actions for N pending decisions with ONE jitted forward.

        Used by ``repro.sim.vector.VectorSimulator`` to amortize the
        per-call dispatch overhead across environments.  In evaluation
        mode the actions are greedy and ``slots`` is ignored.  In training
        mode ``slots`` (one environment id per context) is required: each
        row gets an independent epsilon-greedy draw and its transition is
        recorded into that environment's own episode accumulator
        (``VectorEpisodeRecorder``), keeping every trajectory contiguous
        for the DFP future-measurement targets.  The host RNG is consumed
        in row order — one uniform draw per decision, plus one integer
        draw when exploring — exactly as the sequential ``select`` path,
        so an N=1 batched rollout reproduces sequential training
        bit-for-bit given the same seed.
        """
        if self.training and slots is None:
            raise RuntimeError(
                "select_batch without env slots is evaluation-only: "
                "training interleaves N environments, so each context "
                "needs a slot id routing its transition to a per-env "
                "episode accumulator — pass slots=[...] (the vectorized "
                "trainer in repro.core.train does this), or train with "
                "Simulator.run per trace")
        n = len(ctxs)
        sd, m, a = self.enc.state_dim, self.enc.n_resources, self.config.window
        # One packed row per decision (layout shared with the serving
        # layer: encoding.encode_decision_row), encoded straight into a
        # fresh buffer so a round costs one host->device transfer and
        # zero intermediate copies.
        feats = np.zeros((n, decision_row_dim(self.enc, a)), dtype=np.float32)
        for i, c in enumerate(ctxs):
            self.goal_log.append(
                encode_decision_row(self.enc, c, a, out=feats[i]))
        if not self.training:
            return self._greedy_rows(feats)
        # Epsilon-greedy: draw exploration first (host RNG in row order, the
        # same stream the sequential path consumes), then run ONE batched
        # forward over just the exploiting rows — exploring rows never pay
        # for inference, mirroring the sequential fast path.
        acts = np.zeros(n, dtype=np.int32)
        explore = np.empty(n, dtype=bool)
        for i, c in enumerate(ctxs):
            explore[i] = self.rng.uniform() < self.epsilon
            if explore[i]:
                acts[i] = int(self.rng.integers(
                    0, min(len(c.window), a)))
        exploit = np.flatnonzero(~explore)
        if exploit.size:
            acts[exploit] = self._greedy_rows(feats[exploit])
        for i, slot in enumerate(slots):
            self.vec_recorder.record(
                int(slot), feats[i, :sd].copy(), feats[i, sd:sd + m].copy(),
                feats[i, sd + m:sd + 2 * m].copy(), int(acts[i]))
        return acts

    def _greedy_rows(self, rows: np.ndarray) -> np.ndarray:
        """One jitted forward over packed decision rows -> greedy actions.

        Width is padded up to a power of two so the jit cache sees a
        small, fixed set of shapes as environments finish (or explore) at
        different times; padded rows are valid everywhere and their
        actions are discarded.  The numpy buffer goes to the jitted
        function directly — an explicit ``jnp.asarray`` would route the
        transfer through the slow python ``device_put`` path.
        """
        n = rows.shape[0]
        width = 1 << max(n - 1, 0).bit_length()
        packed = pad_decision_rows(rows, width, self.enc)
        acts = greedy_actions_packed(self.params, self.dfp, packed)
        return np.asarray(acts)[:n].astype(np.int32)

    # ---------------------------------------------------------------- train
    def begin_vector_episodes(self, n_envs: int) -> None:
        """Reset the per-environment accumulators for a batched rollout."""
        self.vec_recorder = VectorEpisodeRecorder(n_envs)

    def end_episode(self, slot: Optional[int] = None) -> Optional[float]:
        """Flush the recorded episode, run gradient steps, decay epsilon.

        ``slot=None`` closes the sequential recorder (``select`` path);
        ``slot=i`` closes environment ``i``'s accumulator from a batched
        rollout.  Either way the finished episode enters the shared replay
        buffer and, once the buffer holds a minibatch, triggers
        ``grad_steps_per_episode`` jitted train steps and one epsilon
        decay — environments finishing mid-batch therefore train the
        network while the other environments are still collecting.
        """
        ep = (self.recorder.finish() if slot is None
              else self.vec_recorder.finish(slot))
        if ep is not None:
            self.replay.add(ep)
        if not self.training or self.replay.rows < self.config.batch_size:
            return None
        mean_loss = self.train_steps(self.config.grad_steps_per_episode)
        if mean_loss is None:
            return None
        self.losses.append(mean_loss)
        self.epsilon = max(self.config.eps_min,
                           self.epsilon * self.config.eps_decay)
        return mean_loss

    def train_steps(self, steps: int) -> Optional[float]:
        """Run ``steps`` jitted gradient steps on replay samples.

        Returns the mean loss, or None when the buffer cannot yet fill a
        minibatch.  Used by ``end_episode`` and by the vectorized
        trainer's per-round interleaved updates
        (``TrainConfig.grad_steps_per_round``).
        """
        if self.replay.rows < self.config.batch_size or steps <= 0:
            return None
        samples = [self.replay.sample(self.rng, self.config.batch_size)
                   for _ in range(steps)]
        batches = {k: np.stack([s[k] for s in samples]) for k in samples[0]}
        self.params, self.opt_state, losses, gnorms = _train_steps_scan(
            self.dfp, self.params, self.opt_state, batches,
            self.config.lr, self.config.grad_clip)
        self.last_grad_norm = float(np.asarray(gnorms).mean())
        return float(np.asarray(losses).mean())

    # ---------------------------------------------------------------- io
    def save(self, path: str) -> None:
        flat, treedef = jax.tree_util.tree_flatten(self.params)
        np.savez(path, n=len(flat), epsilon=self.epsilon,
                 **{f"p{i}": np.asarray(x) for i, x in enumerate(flat)})

    def load(self, path: str) -> None:
        """Restore ``save``d parameters, validating architecture compatibility.

        The checkpoint must match the agent's current parameter tree leaf
        for leaf (count, shape, dtype) — loading a checkpoint trained with
        a different window / hidden widths / resource count raises a clear
        ``ValueError`` instead of silently unflattening incompatible
        leaves into the live tree.
        """
        from ..checkpoint import check_leaves_compat
        data = np.load(path)
        expected, treedef = jax.tree_util.tree_flatten(self.params)
        n = int(data["n"])
        missing = [f"p{i}" for i in range(n) if f"p{i}" not in data.files]
        if missing:
            raise ValueError(
                f"load({path}): checkpoint claims {n} leaves but arrays "
                f"{missing[:3]}{'...' if len(missing) > 3 else ''} are "
                "absent (truncated or hand-edited archive?)")
        got = [data[f"p{i}"] for i in range(n)]
        check_leaves_compat(expected, got, context=f"load({path})")
        self.params = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(x) for x in got])
        self.epsilon = float(data["epsilon"])
        self.opt_state = adam_init(self.params)
