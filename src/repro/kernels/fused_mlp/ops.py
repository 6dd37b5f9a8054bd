"""Differentiable public wrapper for the fused-MLP Pallas kernels.

``fused_mlp`` pads (M, N, K) to block multiples, runs the Pallas kernel
(compiled on TPU, interpreted on CPU), slices the result back, and
carries a ``jax.custom_vjp`` whose backward pass runs the fused
dgrad/wgrad kernels — so both DFP inference *and* the ``lax.scan``
training bursts stay inside the kernel layer.

``pad_weights`` + ``fused_mlp_padded`` split the weight's pad from the
forward, for loops whose weights do not change (the device rollout
pads the policy's layers once per rollout, not once per round).

Block sizes are autotuned per (M, K, N) problem shape (see
``autotune_blocks``), keyed on the *padded* batch the caller actually
produces — the batched rollout engine pads its decision batch to a
power of two (``MRSchAgent._greedy_rows``), so the jit/block cache sees
a small fixed set of shapes.  Explicit ``block_*`` arguments override
the autotuner.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...obs.profiling import named_scope
from ...runtime import interpret_kernels
from .kernel import (_activation_grad, fused_mlp_dgrad_layer, fused_mlp_layer,
                     fused_mlp_wgrad_layer)


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pow2(v: int) -> int:
    return 1 << max(int(v) - 1, 0).bit_length()


def autotune_blocks(m: int, k: int, n: int) -> tuple:
    """Pick (block_m, block_n, block_k) for an (M, K) @ (K, N) layer.

    A shape-keyed heuristic (no measurement): M tiles shrink to the
    padded batch (a rollout round is often a handful of lanes x window,
    far below the 128-row MXU default); N/K tiles stay lane-aligned
    (>=128) and cap at the VMEM-friendly 256/512 the forward kernel was
    tuned with.  Upstream power-of-two batch padding keeps the set of
    distinct shapes — and thus jit specializations — small.
    """
    block_m = min(128, max(8, _pow2(m)))
    block_n = min(256, max(128, _pow2(n)))
    block_k = min(512, max(128, _pow2(k)))
    return block_m, block_n, block_k


# --------------------------------------------------------------- custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fused_mlp_2d(x, w, b, activation, slope, block_m, block_n, block_k,
                  interpret):
    """y = act(x @ w + b) on 2-D x, differentiable w.r.t. (x, w, b)."""
    return _forward_2d(x, w, b, activation, slope, block_m, block_n,
                       block_k, interpret)


def _pad_weights(w, b, block_n, block_k):
    return (_pad_to(_pad_to(w, block_k, 0), block_n, 1),
            _pad_to(b, block_n, 0))


def _forward_padded(x, wp, bp, n, activation, slope, block_m, block_n,
                    block_k, interpret):
    """act(x @ w + b)[:, :n] from a weight and bias already padded to
    their (block_k, block_n) multiples; only ``x`` is padded here."""
    M = x.shape[0]
    xp = _pad_to(_pad_to(x, block_m, 0), block_k, 1)
    y = fused_mlp_layer(xp, wp, bp, activation=activation, slope=slope,
                        block_m=block_m, block_n=block_n, block_k=block_k,
                        interpret=interpret)
    return y[:M, :n]


def _forward_2d(x, w, b, activation, slope, block_m, block_n, block_k,
                interpret):
    with named_scope("mrsch.kernel.fused_mlp"):
        wp, bp = _pad_weights(w, b, block_n, block_k)
        return _forward_padded(x, wp, bp, w.shape[1], activation, slope,
                               block_m, block_n, block_k, interpret)


def _fused_mlp_fwd(x, w, b, activation, slope, block_m, block_n, block_k,
                   interpret):
    y = _forward_2d(x, w, b, activation, slope, block_m, block_n, block_k,
                    interpret)
    return y, (x, w, b, y)


def _fused_mlp_bwd(activation, slope, block_m, block_n, block_k, interpret,
                   res, g):
    with named_scope("mrsch.kernel.fused_mlp_bwd"):
        return _fused_mlp_bwd_impl(activation, slope, block_m, block_n,
                                   block_k, interpret, res, g)


def _fused_mlp_bwd_impl(activation, slope, block_m, block_n, block_k,
                        interpret, res, g):
    x, w, b, y = res
    M, K = x.shape
    N = w.shape[1]
    gp = _pad_to(_pad_to(g, block_m, 0), block_n, 1)
    yp = _pad_to(_pad_to(y, block_m, 0), block_n, 1)
    xp = _pad_to(_pad_to(x, block_m, 0), block_k, 1)
    wp = _pad_to(_pad_to(w, block_k, 0), block_n, 1)
    dx = fused_mlp_dgrad_layer(gp, yp, wp, activation=activation, slope=slope,
                               block_m=block_m, block_n=block_n,
                               block_k=block_k, interpret=interpret)[:M, :K]
    dw = fused_mlp_wgrad_layer(xp, gp, yp, activation=activation, slope=slope,
                               block_m=block_m, block_n=block_n,
                               block_k=block_k, interpret=interpret)[:K, :N]
    # Bias grad: XLA fuses the elementwise product into the reduction,
    # so this re-reads g/y but does not materialize an (M, N) buffer.
    gm = (g.astype(jnp.float32)
          * _activation_grad(y.astype(jnp.float32), activation, slope))
    db = gm.sum(axis=0).astype(b.dtype)
    return dx.astype(x.dtype), dw.astype(w.dtype), db


_fused_mlp_2d.defvjp(_fused_mlp_fwd, _fused_mlp_bwd)

_fused_mlp_jit = jax.jit(_fused_mlp_2d, static_argnums=(3, 4, 5, 6, 7, 8))


# ------------------------------------------------------------------- public
def fused_mlp(x, w, b, *, activation: str = "leaky_relu", slope: float = 0.2,
              block_m: int | None = None, block_n: int | None = None,
              block_k: int | None = None, interpret: bool | None = None):
    """y = act(x @ w + b) with arbitrary (M, K, N); differentiable.

    ``block_* = None`` autotunes on the problem shape; ``interpret =
    None`` compiles on TPU and interprets on CPU
    (``repro.runtime.interpret_kernels``).
    """
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None]
    M, K = x.shape
    N = w.shape[1]
    bm, bn, bk = autotune_blocks(M, K, N)
    if block_m is not None:
        bm = min(block_m, max(8, _pow2(M)))
    if block_n is not None:
        bn = block_n
    if block_k is not None:
        bk = block_k
    if interpret is None:
        interpret = interpret_kernels()
    y = _fused_mlp_jit(x, w, b, activation, float(slope), bm, bn, bk,
                       bool(interpret))
    return y[0] if squeeze else y


def pad_weights(w, b):
    """Pad a dense layer's (K, N) weight and (N,) bias to the blocks that
    ``fused_mlp`` autotunes for it, for ``fused_mlp_padded``.

    The N and K blocks depend on (K, N) alone, so one padded copy serves
    every batch size: a caller whose weights are invariant over a loop
    pads them once before it instead of once per call inside it.
    """
    K, N = w.shape
    _, bn, bk = autotune_blocks(1, K, N)
    with named_scope("mrsch.kernel.fused_mlp"):
        return _pad_weights(w, b, bn, bk)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _fused_mlp_padded_jit(x, wp, bp, n, activation, slope, interpret):
    M, K = x.shape
    bm, bn, bk = autotune_blocks(M, K, n)
    if wp.shape != (-(-K // bk) * bk, -(-n // bn) * bn):
        raise ValueError(f"weight {wp.shape} is not ({K}, {n}) padded to "
                         f"blocks ({bk}, {bn}); pad it with pad_weights")
    with named_scope("mrsch.kernel.fused_mlp"):
        return _forward_padded(x, wp, bp, n, activation, slope, bm, bn, bk,
                               interpret)


def fused_mlp_padded(x, wp, bp, n: int, *, activation: str = "leaky_relu",
                     slope: float = 0.2, interpret: bool | None = None):
    """``fused_mlp(x, w, b)`` for 2-D ``x`` from ``(wp, bp) =
    pad_weights(w, b)`` and the layer's logical output width ``n``, bit
    for bit: the same blocks, kernel and K order, and the zero padding
    adds exactly 0.  Forward only (no VJP)."""
    if interpret is None:
        interpret = interpret_kernels()
    return _fused_mlp_padded_jit(x, wp, bp, int(n), activation, float(slope),
                                 bool(interpret))


def dfp_state_module(x, layers, *, interpret: bool | None = None):
    """Run the DFP state-module MLP (list of {'w','b'}) fused layer-by-layer
    (hidden layers use leaky_relu; final layer too, per MRSch §III-A)."""
    h = x
    for layer in layers:
        h = fused_mlp(h, layer["w"], layer["b"], activation="leaky_relu",
                      interpret=interpret)
    return h
