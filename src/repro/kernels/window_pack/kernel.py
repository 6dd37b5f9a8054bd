"""Pallas TPU kernel: pack the first W waiting jobs into a dense window.

The selection is a one-hot matrix ``sel[w, j] = waiting_j AND
(count_j == w+1)``, where ``count_j`` is the number of waiting jobs up to
and including j, so the gather becomes a (W, chunk) @ (chunk, F) MXU
matmul instead of a serial scan over the job axis; the window indices
(``sel`` against a job-index iota) and the validity mask (row-sum of
``sel``) are lane reductions of the same matrix.

Grid = (env blocks of 8 rows, job chunks), the chunk axis innermost.
Within a chunk the prefix count is one matmul against an upper-triangular
ones matrix (0/1 operands, so exact at any MXU precision); the count of
earlier chunks rides in VMEM scratch, so VMEM stays bounded whatever the
job count.  The outputs' block index does not move along the chunk axis:
they stay resident and accumulate each chunk's (disjoint) selections.

Shapes are padded by the ``ops`` wrapper: N to 8 rows, J to a chunk
multiple, F to 128 lanes, W to 8 sublanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8           # env rows per block (f32 sublanes)


def _window_pack_kernel(waiting_ref, feats_ref, wf_ref, wi_ref, wv_ref,
                        count_ref):
    c = pl.program_id(1)
    rows, chunk = waiting_ref.shape
    W = wf_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        wf_ref[...] = jnp.zeros_like(wf_ref)
        wi_ref[...] = jnp.zeros_like(wi_ref)
        wv_ref[...] = jnp.zeros_like(wv_ref)
        count_ref[...] = jnp.zeros_like(count_ref)

    waiting = waiting_ref[...]                                  # (8, chunk)
    upper = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    count = count_ref[...] + jnp.dot(waiting, upper.astype(jnp.float32),
                                     preferred_element_type=jnp.float32)
    count_ref[...] += waiting.sum(axis=1, keepdims=True)
    slot = (jax.lax.broadcasted_iota(jnp.int32, (W, chunk), 0) + 1
            ).astype(jnp.float32)
    jidx = (c * chunk + jax.lax.broadcasted_iota(jnp.int32, (W, chunk), 1)
            ).astype(jnp.float32)
    for r in range(rows):
        sel = jnp.where((waiting[r:r + 1] > 0.5) & (count[r:r + 1] == slot),
                        1.0, 0.0)                               # (W, chunk)
        # HIGHEST keeps the gathered f32 features (raw submit times among
        # them) exact instead of rounding them to bf16 on the MXU.
        wf_ref[r] += jnp.dot(sel, feats_ref[r],
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
        wi_ref[r] += (sel * jidx).sum(axis=1, keepdims=True)
        wv_ref[r] += sel.sum(axis=1, keepdims=True)


def window_pack_kernel(waiting: jnp.ndarray, feats: jnp.ndarray, *,
                       window: int, chunk: int, interpret: bool = False):
    """waiting (N, J) f32 0/1, feats (N, J, F) f32 ->
    (win_feats (N, W, F) f32, win_idx (N, W, 1) f32, win_valid (N, W, 1)
    f32).  Window indices come back as exact f32 (J < 2**24).

    N, J, F and ``window`` must already be tile-aligned (``ops`` pads)."""
    N, J = waiting.shape
    F = feats.shape[2]
    W = window
    assert (N % ROWS == 0 and J % chunk == 0 and chunk % 128 == 0
            and F % 128 == 0 and W % 8 == 0), (N, J, chunk, F, W)
    col = jax.ShapeDtypeStruct((N, W, 1), jnp.float32)
    return pl.pallas_call(
        _window_pack_kernel,
        grid=(N // ROWS, J // chunk),
        in_specs=[
            pl.BlockSpec((ROWS, chunk), lambda i, c: (i, c)),
            pl.BlockSpec((ROWS, chunk, F), lambda i, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, W, F), lambda i, c: (i, 0, 0)),
            pl.BlockSpec((ROWS, W, 1), lambda i, c: (i, 0, 0)),
            pl.BlockSpec((ROWS, W, 1), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((N, W, F), jnp.float32), col, col],
        scratch_shapes=[pltpu.VMEM((ROWS, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="window_pack",
    )(waiting, feats)
