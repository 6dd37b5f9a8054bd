"""Pallas TPU kernels: fused  y = act(x @ W + b)  and its backward pass.

The MRSch agent's hot spot is the DFP state-module MLP
(11410 -> 4000 -> 1000 -> 512, leaky rectifier).  The forward kernel
fuses the matmul, bias and activation so each layer is a single HBM
round-trip: x/W stream through VMEM in (bm x bk)/(bk x bn) tiles, a f32
accumulator lives in VMEM scratch across the K-loop (innermost grid
dim), and the bias+activation epilogue runs on the last K step —
MXU-aligned tiles (multiples of 128 in M/N, K tiles of 512).

The backward kernels reuse the same tiling.  Both fuse the activation
gradient into their contraction prologue, so neither ever writes the
(M, N) tensor ``g * act'(y)`` to HBM:

  * dgrad:  dx[m, k] = sum_n (g * act'(y))[m, n] * W[k, n]
  * wgrad:  dw[k, n] = sum_m x[m, k] * (g * act'(y))[m, n]

(The small bias gradient ``db = sum_m g * act'(y)`` is left to XLA as a
fused elementwise+reduce over the same product — see ``ops.py``.)

``act'`` is recovered from the *output* y (every supported activation
has a derivative expressible in its own output), so the forward only
needs to save (x, W, y) as residuals.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACTIVATIONS = ("leaky_relu", "relu", "tanh", "linear")


def _check_activation(activation: str) -> None:
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}; "
                         f"expected one of {ACTIVATIONS}")


def _apply_activation(y, activation: str, slope: float):
    if activation == "leaky_relu":
        return jnp.where(y >= 0, y, slope * y)
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if activation == "tanh":
        return jnp.tanh(y)
    return y                                            # linear


def _activation_grad(y, activation: str, slope: float):
    """d act / d pre-activation, written in terms of the output ``y``.

    leaky_relu / relu are sign-recoverable (slope > 0), tanh' = 1 - y²;
    matches the convention JAX uses for the reference ops (derivative 1
    at exactly 0 for leaky_relu, 0 for relu).
    """
    one = jnp.ones_like(y)
    if activation == "leaky_relu":
        return jnp.where(y >= 0, one, slope * one)
    if activation == "relu":
        return jnp.where(y > 0, one, jnp.zeros_like(y))
    if activation == "tanh":
        return 1.0 - y * y
    return one                                          # linear


def _fused_mlp_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, n_k: int,
                      activation: str, slope: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        y = acc_ref[...] + b_ref[...].astype(jnp.float32)
        o_ref[...] = _apply_activation(y, activation, slope).astype(o_ref.dtype)


def fused_mlp_layer(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                    activation: str = "leaky_relu", slope: float = 0.2,
                    block_m: int = 128, block_n: int = 256,
                    block_k: int = 512, interpret: bool = False
                    ) -> jnp.ndarray:
    """x (M,K) @ w (K,N) + b (N,), fused activation.  Shapes are padded to
    block multiples by the ``ops`` wrapper.  The bias rides as a (1, N)
    row: Mosaic refuses a 1-D block whose tiling differs from XLA's."""
    _check_activation(activation)
    M, K = x.shape
    _, N = w.shape
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, \
        (M, N, K, block_m, block_n, block_k)
    n_k = K // block_k
    grid = (M // block_m, N // block_n, n_k)
    kernel = functools.partial(_fused_mlp_kernel, n_k=n_k,
                               activation=activation, slope=slope)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="fused_mlp_fwd",
    )(x, w, b.reshape(1, N))


# ------------------------------------------------------------------ backward
def _fused_mlp_dgrad_kernel(g_ref, y_ref, w_ref, dx_ref, acc_ref, *,
                            n_n: int, activation: str, slope: float):
    """dx tile (bm, bk): contract g*act'(y) (bm, bn) with W (bk, bn) over N."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gm = (g_ref[...].astype(jnp.float32)
          * _activation_grad(y_ref[...].astype(jnp.float32), activation, slope))
    acc_ref[...] += jax.lax.dot_general(
        gm, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_n - 1)
    def _epilogue():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def fused_mlp_dgrad_layer(g: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray, *,
                          activation: str = "leaky_relu", slope: float = 0.2,
                          block_m: int = 128, block_n: int = 256,
                          block_k: int = 512, interpret: bool = False
                          ) -> jnp.ndarray:
    """dx (M,K) from upstream g (M,N), saved output y (M,N), w (K,N)."""
    _check_activation(activation)
    M, N = g.shape
    K = w.shape[0]
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, \
        (M, N, K, block_m, block_n, block_k)
    n_n = N // block_n
    grid = (M // block_m, K // block_k, n_n)
    kernel = functools.partial(_fused_mlp_dgrad_kernel, n_n=n_n,
                               activation=activation, slope=slope)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, k, j: (i, j)),
            pl.BlockSpec((block_m, block_n), lambda i, k, j: (i, j)),
            pl.BlockSpec((block_k, block_n), lambda i, k, j: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_k), lambda i, k, j: (i, k)),
        out_shape=jax.ShapeDtypeStruct((M, K), g.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_k), jnp.float32)],
        interpret=interpret,
        name="fused_mlp_dgrad",
    )(g, y, w)


def _fused_mlp_wgrad_kernel(x_ref, g_ref, y_ref, dw_ref, acc_ref, *,
                            n_m: int, activation: str, slope: float):
    """dw tile (bk, bn): contract x (bm, bk) with g*act'(y) (bm, bn) over M."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gm = (g_ref[...].astype(jnp.float32)
          * _activation_grad(y_ref[...].astype(jnp.float32), activation, slope))
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), gm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_m - 1)
    def _epilogue():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def fused_mlp_wgrad_layer(x: jnp.ndarray, g: jnp.ndarray, y: jnp.ndarray, *,
                          activation: str = "leaky_relu", slope: float = 0.2,
                          block_m: int = 128, block_n: int = 256,
                          block_k: int = 512, interpret: bool = False
                          ) -> jnp.ndarray:
    """dw (K,N) from input x (M,K), upstream g (M,N), saved output y (M,N)."""
    _check_activation(activation)
    M, K = x.shape
    N = g.shape[1]
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, \
        (M, N, K, block_m, block_n, block_k)
    n_m = M // block_m
    grid = (K // block_k, N // block_n, n_m)
    kernel = functools.partial(_fused_mlp_wgrad_kernel, n_m=n_m,
                               activation=activation, slope=slope)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda k, j, m: (m, k)),
            pl.BlockSpec((block_m, block_n), lambda k, j, m: (m, j)),
            pl.BlockSpec((block_m, block_n), lambda k, j, m: (m, j)),
        ],
        out_specs=pl.BlockSpec((block_k, block_n), lambda k, j, m: (k, j)),
        out_shape=jax.ShapeDtypeStruct((K, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)],
        interpret=interpret,
        name="fused_mlp_wgrad",
    )(x, g, y)
