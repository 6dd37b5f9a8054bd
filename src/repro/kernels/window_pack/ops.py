"""Public wrapper for the window pack/select op.

``pack_window`` pads (N, J, F, W) to tile multiples, runs the Pallas
kernel, and slices the results back.  The platform picks the path: the
compiled kernel on TPU, the vectorized jnp reference on CPU (the op sits
inside the device rollout engine's scan, and interpret-mode Pallas would
execute the kernel body in Python on every round), and any other
platform is refused.  The tests still drive the kernel on CPU via
``use_pallas=True, interpret=True``.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...obs.profiling import named_scope
from ...runtime import interpret_kernels
from .kernel import ROWS, window_pack_kernel
from .ref import pack_window_reference

CHUNK = 256        # jobs per grid step along J


def _pad_axis(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pack_window(waiting: jnp.ndarray, feats: jnp.ndarray, *, window: int,
                use_pallas: bool | None = None,
                interpret: bool | None = None):
    """First ``window`` waiting jobs per environment, densely packed.

    waiting (N, J) 0/1 float, feats (N, J, F) float32 ->
    (win_feats (N, W, F) f32, win_idx (N, W) i32, win_valid (N, W) bool).
    Traceable (safe inside jit); padding/slicing is shape-static.
    """
    if use_pallas is None:
        use_pallas = not interpret_kernels()
    with named_scope("mrsch.kernel.window_pack"):
        if not use_pallas:
            return pack_window_reference(waiting, feats, window=window)
        if interpret is None:
            interpret = interpret_kernels()
        N, J = waiting.shape
        F = feats.shape[2]
        chunk = min(CHUNK, J + (-J) % 128)
        wp = _pad_axis(_pad_axis(waiting.astype(jnp.float32), ROWS, 0),
                       chunk, 1)
        fp = _pad_axis(_pad_axis(_pad_axis(feats.astype(jnp.float32),
                                           ROWS, 0), chunk, 1), 128, 2)
        Wp = window + ((-window) % 8)
        wf, wi, wv = window_pack_kernel(wp, fp, window=Wp, chunk=chunk,
                                        interpret=bool(interpret))
        return (wf[:N, :window, :F], wi[:N, :window, 0].astype(jnp.int32),
                wv[:N, :window, 0] > 0.5)
