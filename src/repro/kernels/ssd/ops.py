"""jit'd public wrapper for the SSD kernel.

Takes per-head tensors in model layout (B, S, H, ...), flattens to
(B*H, S, ...), computes the within-chunk cumulative decay, pads S to a
chunk multiple (decay of padded steps = 0 input), and calls the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...runtime import interpret_kernels
from .kernel import ssd_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, dA, B, C, *, chunk: int = 256,
        interpret: bool | None = None):
    """x (B,S,H,P); dt/dA (B,S,H); B/C (B,S,H,N) -> y (B,S,H,P).

    ``dA`` = dt * A (negative); the kernel consumes the in-chunk cumsum.
    ``interpret=None`` compiles on TPU and interprets on CPU.
    """
    if interpret is None:
        interpret = interpret_kernels()
    b, S, H, P = x.shape
    N = B.shape[-1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad

    def flat(t, d):
        return t.transpose(0, 2, 1, 3).reshape(b * H, Sp, d)

    xf = flat(x, P)
    Bf = flat(B, N)
    Cf = flat(C, N)
    dtf = dt.transpose(0, 2, 1).reshape(b * H, Sp, 1)
    dAf = dA.transpose(0, 2, 1).reshape(b * H, Sp, 1)
    # within-chunk cumulative decay
    l = dAf.reshape(b * H, Sp // chunk, chunk, 1)
    l = jnp.cumsum(l, axis=2).reshape(b * H, Sp, 1)
    y = ssd_kernel(xf, dtf, l, Bf, Cf, chunk=chunk, interpret=interpret)
    y = y.reshape(b, H, Sp, P).transpose(0, 2, 1, 3)
    return y[:, :S]
