"""Direct Future Prediction network (Dosovitskiy & Koltun '17) as adapted by
MRSch (paper §II-B, §III, §IV-C).

Three input modules:
  * state module   — MLP  state_dim -> 4000 -> 1000 -> 512 (leaky rectifier);
                     a CNN variant is kept for the Fig. 3 ablation.
  * measurement    — 3 fully-connected layers of 128 units.
  * goal           — 3 fully-connected layers of 128 units.

The joint representation (concat, 768) feeds two parallel streams (dueling,
Wang et al.):
  * expectation stream E(j)            -> (T*M,)
  * action stream      A(j)            -> (A, T*M), normalized to zero mean
                                          across actions.
Prediction for action a:  p_a = E + (A_a - mean_a A)   reshaped (T, M) —
the predicted *change* of each measurement at each temporal offset.

Action scoring:  u(a) = sum_tau w_tau * sum_m g_m * p_a[tau, m]
with fixed temporal weights w (DFP default (0,0,0,0.5,0.5,1)) and the
dynamic goal vector g from Eq. (1).

Training target for the taken action: f[tau, m] = m_{t+tau} - m_t (clamped
to episode end), loss = MSE over the taken action's prediction.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.backend import mlp_forward, pad_dense_layers, resolve_backend
from ..nn.modules import (conv1d_apply, conv1d_init, dense_apply, dense_init,
                          leaky_relu, mlp_init)
from ..nn.queue_encoder import (QueueEncoderConfig, queue_encoder_init,
                                queue_state_features)

STATE_MODULES = ("mlp", "cnn", "attention")


@dataclass(frozen=True)
class DFPConfig:
    state_dim: int
    n_measurements: int                       # M (one per resource)
    n_actions: int                            # A = window size W
    offsets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    temporal_weights: Tuple[float, ...] = (0.0, 0.0, 0.0, 0.5, 0.5, 1.0)
    state_hidden: Tuple[int, ...] = (4000, 1000)   # paper §IV-C
    state_out: int = 512
    module_hidden: int = 128                  # measurement/goal modules
    stream_hidden: int = 512
    state_module: str = "mlp"                 # "mlp" | "cnn" (Fig. 3 ablation)
    #                                           | "attention" (queue encoder)
    cnn_channels: Tuple[int, ...] = (8, 16)
    cnn_width: int = 9
    cnn_stride: int = 4
    # Queue-as-tokens attention state module (repro.nn.queue_encoder) —
    # only read when state_module == "attention".
    attn_queue: int = 128                     # Q: job-token buffer size
    attn_dim: int = 64                        # d_model
    attn_heads: int = 4
    attn_layers: int = 2
    attn_mlp_mult: int = 2
    backend: str = "xla"                      # "xla" | "pallas" (fused kernel)

    def __post_init__(self):
        resolve_backend(self.backend)
        if self.state_module not in STATE_MODULES:
            raise ValueError(f"unknown state_module {self.state_module!r}; "
                             f"expected one of {STATE_MODULES}")
        if self.state_module == "attention":
            expect = (self.attn_queue * (self.n_measurements + 2) + 1
                      + 2 * self.n_measurements)
            if self.state_dim != expect:
                raise ValueError(
                    f"attention state_dim mismatch: got {self.state_dim}, "
                    f"layout Q*(M+2)+1+2M = {expect} for "
                    f"attn_queue={self.attn_queue} M={self.n_measurements}")

    @property
    def n_offsets(self) -> int:
        return len(self.offsets)

    @property
    def pred_dim(self) -> int:
        return self.n_offsets * self.n_measurements

    @property
    def queue_encoder(self) -> QueueEncoderConfig:
        """Encoder architecture derived from the DFP shape contract."""
        return QueueEncoderConfig(
            queue_cap=self.attn_queue,
            job_dim=self.n_measurements + 2,
            ctx_dim=2 * self.n_measurements,
            window=self.n_actions,
            d_model=self.attn_dim,
            n_heads=self.attn_heads,
            n_layers=self.attn_layers,
            mlp_mult=self.attn_mlp_mult,
            out_dim=self.state_out,
        )


def init_params(key: jax.Array, cfg: DFPConfig):
    ks = jax.random.split(key, 8)
    params = {}
    if cfg.state_module == "mlp":
        sizes = [cfg.state_dim, *cfg.state_hidden, cfg.state_out]
        params["state"] = mlp_init(ks[0], sizes)
    elif cfg.state_module == "attention":
        params["state"] = queue_encoder_init(ks[0], cfg.queue_encoder)
    else:  # CNN ablation: 1-D convs over the state vector.
        convs = []
        in_ch = 1
        length = cfg.state_dim
        ck = jax.random.split(ks[0], len(cfg.cnn_channels))
        for i, ch in enumerate(cfg.cnn_channels):
            convs.append(conv1d_init(ck[i], in_ch, ch, cfg.cnn_width))
            in_ch = ch
            length = -(-length // cfg.cnn_stride)
        params["state"] = {
            "convs": convs,
            "proj": dense_init(ks[1], length * in_ch, cfg.state_out),
        }
    params["measurement"] = mlp_init(
        ks[2], [cfg.n_measurements, cfg.module_hidden, cfg.module_hidden,
                cfg.module_hidden])
    params["goal"] = mlp_init(
        ks[3], [cfg.n_measurements, cfg.module_hidden, cfg.module_hidden,
                cfg.module_hidden])
    joint = cfg.state_out + 2 * cfg.module_hidden
    params["expectation"] = mlp_init(ks[4], [joint, cfg.stream_hidden,
                                             cfg.pred_dim])
    params["action"] = mlp_init(ks[5], [joint, cfg.stream_hidden,
                                        cfg.n_actions * cfg.pred_dim])
    return params


def prepare_params(params, cfg: DFPConfig):
    """``params`` in the form ``predict`` runs fastest when the same
    weights score many batches: on the ``pallas`` backend every dense
    layer padded once to the fused kernel's blocks (the CNN state
    module, plain XLA ops, excepted); otherwise ``params`` itself."""
    if cfg.backend != "pallas":
        return params
    keep = {"state"} if cfg.state_module == "cnn" else set()
    return {k: v if k in keep else pad_dense_layers(v)
            for k, v in params.items()}


def _state_features(params, cfg: DFPConfig, state: jnp.ndarray) -> jnp.ndarray:
    if cfg.state_module == "mlp":
        return mlp_forward(params["state"], state,
                           final_activation="leaky_relu", backend=cfg.backend)
    if cfg.state_module == "attention":
        return queue_state_features(params["state"], cfg.queue_encoder,
                                    state, backend=cfg.backend)
    # CNN ablation stays on plain XLA ops (conv has no fused kernel).
    x = state[..., :, None]                       # (B, L, 1)
    for conv in params["state"]["convs"]:
        x = leaky_relu(conv1d_apply(conv, x, stride=cfg.cnn_stride))
    x = x.reshape(*x.shape[:-2], -1)
    return leaky_relu(dense_apply(params["state"]["proj"], x))


def predict(params, cfg: DFPConfig, state: jnp.ndarray, meas: jnp.ndarray,
            goal: jnp.ndarray) -> jnp.ndarray:
    """Batched forward pass.

    state (B, state_dim), meas (B, M), goal (B, M)
    -> predictions (B, A, T, M): per-action future measurement deltas.

    Every dense module dispatches on ``cfg.backend``: plain XLA ops or
    the fused-MLP Pallas kernel (forward and backward).
    """
    s = _state_features(params, cfg, state)
    m = mlp_forward(params["measurement"], meas,
                    final_activation="leaky_relu", backend=cfg.backend)
    g = mlp_forward(params["goal"], goal,
                    final_activation="leaky_relu", backend=cfg.backend)
    j = jnp.concatenate([s, m, g], axis=-1)
    e = mlp_forward(params["expectation"], j, backend=cfg.backend)  # (B, T*M)
    a = mlp_forward(params["action"], j, backend=cfg.backend)       # (B, A*T*M)
    a = a.reshape(*a.shape[:-1], cfg.n_actions, cfg.pred_dim)
    a = a - a.mean(axis=-2, keepdims=True)                        # dueling norm
    p = e[..., None, :] + a                                       # (B, A, T*M)
    return p.reshape(*p.shape[:-1], cfg.n_offsets, cfg.n_measurements)


def action_values(params, cfg: DFPConfig, state, meas, goal) -> jnp.ndarray:
    """u(a) = sum_tau w_tau sum_m g_m * p[a, tau, m]   -> (B, A)."""
    p = predict(params, cfg, state, meas, goal)
    w = jnp.asarray(cfg.temporal_weights, p.dtype)                # (T,)
    return jnp.einsum("batm,t,bm->ba", p, w, goal)


def loss_fn(params, cfg: DFPConfig, batch) -> jnp.ndarray:
    """MSE between the taken action's predicted and realized future deltas.

    batch: dict with state (B,S), meas (B,M), goal (B,M), action (B,),
    target (B,T,M), target_mask (B,T) — mask handles episode-end clamping.
    """
    p = predict(params, cfg, batch["state"], batch["meas"], batch["goal"])
    taken = jnp.take_along_axis(
        p, batch["action"][:, None, None, None].astype(jnp.int32), axis=1
    )[:, 0]                                                       # (B, T, M)
    err = (taken - batch["target"]) ** 2
    mask = batch["target_mask"][..., None]
    return (err * mask).sum() / jnp.maximum(mask.sum() * cfg.n_measurements, 1.0)


@functools.partial(jax.jit, static_argnums=(1,))
def greedy_action(params, cfg: DFPConfig, state, meas, goal,
                  valid_mask) -> jnp.ndarray:
    """Argmax over valid window slots (invalid slots masked to -inf)."""
    u = action_values(params, cfg, state[None], meas[None], goal[None])[0]
    u = jnp.where(valid_mask, u, -jnp.inf)
    return jnp.argmax(u)


@functools.partial(jax.jit, static_argnums=(1,))
def greedy_actions_packed(params, cfg: DFPConfig, packed) -> jnp.ndarray:
    """Batched greedy selection: ONE forward pass for N pending decisions.

    ``packed`` is one (N, state_dim + 2M + A) buffer with a row per
    decision, [state | meas | goal | valid] — a lockstep round pays
    per-call host->device transfer overhead on every input array, so the
    rollout engine ships a single buffer and we slice it on device.

    On the ``xla`` backend this is a ``vmap`` over the single-decision
    scorer, so each row's own goal vector weights its own prediction —
    environments with heterogeneous goals (different contention
    regimes, Eq. 1) batch together correctly.  The ``pallas`` backend
    scores the batch directly (``action_values`` is fully batched and
    its goal einsum is already per-row), so the fused kernel sees the
    real padded (width, dim) matmul instead of width vmapped
    single-row calls.
    """
    sd, m, a = cfg.state_dim, cfg.n_measurements, cfg.n_actions
    states = packed[:, :sd]
    meas = packed[:, sd:sd + m]
    goals = packed[:, sd + m:sd + 2 * m]
    masks = packed[:, sd + 2 * m:sd + 2 * m + a] > 0.5

    if cfg.backend == "pallas":
        u = action_values(params, cfg, states, meas, goals)
        return jnp.argmax(jnp.where(masks, u, -jnp.inf), axis=-1)

    def one(state, mrow, goal, mask):
        u = action_values(params, cfg, state[None], mrow[None], goal[None])[0]
        return jnp.argmax(jnp.where(mask, u, -jnp.inf))

    return jax.vmap(one)(states, meas, goals, masks)
