"""Pallas TPU kernel: causal FlashAttention (online softmax).

Grid = (batch*heads, n_q_blocks, n_k_blocks) with the K loop innermost; the
running max m, normalizer l and f32 output accumulator persist in VMEM
scratch across K steps of one (bh, qi) tile.  Causal masking is positional;
blocks entirely above the diagonal contribute nothing (masked to -inf;
the `ops` wrapper also clips the K grid per Q block via masking — on real
TPUs a further win is to skip those blocks with a scalar prefetch grid,
noted in EXPERIMENTS §Perf).

Tiles: q (bq x dh), k/v (bk x dh), MXU-aligned (bq, bk multiples of 128
for bf16; dh 64-256 as the model dictates).

Alongside the causal kernel live the masked non-causal variants backing
the queue-as-tokens encoder (``repro.nn.queue_encoder``): a forward that
masks to a *per-row* KV length and emits log-sum-exp rows
(``mha_fwd_kernel``), and the dq / dkv backward kernels
(``mha_bwd_kernels``) that recompute p from (q, k, lse) flash-style —
wired into a ``jax.custom_vjp`` by ``ops.mha``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_k: int, block_q: int, block_k: int, scale: float,
                  causal: bool, seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                     # (bq, dh)
    k = k_ref[0]                                     # (bk, dh)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_len
    if causal:
        mask = mask & (qpos >= kpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                              # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


# The per-row (BH,) KV lengths sit whole in SMEM; grid step (b, ., .)
# reads its scalar as ``len_ref[b]``.  Per-query statistics (lse, delta)
# are (BH, 1, Sq) rows: the unit sublane axis keeps their (1, 1, block_q)
# blocks within the TPU's tiling.
_LENGTHS_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _kv_mask(len_ref, kpos):
    """Keys at positions below this batch-head row's length are valid."""
    return kpos.astype(jnp.float32) < len_ref[pl.program_id(0)]


def _mha_fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                    m_ref, l_ref, acc_ref, *,
                    n_k: int, block_q: int, block_k: int, scale: float):
    """Non-causal forward masked to a per-row KV length, emitting the
    log-sum-exp rows the backward kernels recompute p from.

    Differences from ``_flash_kernel``: the mask bound is a per-(batch,
    head) runtime value rather than a static scalar, and ``p`` is
    multiplied by the mask — when a row is fully masked every score is
    NEG_INF, so ``m_new == NEG_INF`` and ``exp(s - m_new)`` would be 1
    for the masked entries; the multiply keeps ``l == 0`` and the output
    exactly zero (matching the masked reference) instead of garbage.
    """
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                     # (bq, dh)
    k = k_ref[0]                                     # (bk, dh)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = _kv_mask(len_ref, kpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                              # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # Clamp so fully-masked rows store a huge-negative (finite) lse:
        # the backward's exp(s - lse) then stays finite and the mask
        # multiply zeroes it, instead of inf - inf = NaN.
        lse_ref[0] = (m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))).T


def mha_fwd_kernel(q, k, v, lengths, *, block_q: int = 128,
                   block_k: int = 128, interpret: bool = False):
    """q (BH, Sq, dh), k/v (BH, Sk, dh) padded to block multiples;
    ``lengths`` (BH,) float32 true KV lengths.  Returns (o, lse) with
    lse (BH, 1, Sq)."""
    BH, Sq, dh = q.shape
    Sk = k.shape[1]
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_q, n_k = Sq // block_q, Sk // block_k
    kernel = functools.partial(_mha_fwd_kernel, n_k=n_k, block_q=block_q,
                               block_k=block_k, scale=dh ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            _LENGTHS_SPEC,
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # normalizer
            pltpu.VMEM((block_q, dh), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="mha_fwd",
    )(lengths.astype(jnp.float32), q, k, v)


def _mha_bwd_dq_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dq_ref, acc_ref, *,
                       n_k: int, block_k: int, scale: float):
    """dq for one (bh, qi) tile, accumulated over K blocks:
    p = exp(s - lse) * mask; ds = p * (do @ v^T - delta); dq = ds @ k."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = _kv_mask(len_ref, kpos).astype(jnp.float32)
    p = jnp.exp(s - lse_ref[0].T) * mask
    dp = jnp.dot(do_ref[0], v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0].T) * scale
    acc_ref[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _mha_bwd_dkv_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                        n_q: int, block_k: int, scale: float):
    """dk/dv for one (bh, ki) tile, accumulated over Q blocks:
    dv = p^T @ do; dk = ds^T @ q (same recomputed p/ds as the dq pass)."""
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    kpos = (pl.program_id(1) * k.shape[0]
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    mask = _kv_mask(len_ref, kpos).astype(jnp.float32)
    p = jnp.exp(s - lse_ref[0].T) * mask
    do = do_ref[0]
    dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0].T) * scale
    dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def mha_bwd_kernels(q, k, v, do, lse, delta, lengths, *,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """Backward of ``mha_fwd_kernel``: returns (dq, dk, dv).

    All sequence axes must already be padded to block multiples; ``do``
    must be zero in padded query rows (the ops wrapper pads with zeros),
    so padded rows contribute nothing to dk/dv.  ``lse`` and ``delta``
    are (BH, 1, Sq).
    """
    BH, Sq, dh = q.shape
    Sk = k.shape[1]
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = dh ** -0.5
    lens = lengths.astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(_mha_bwd_dq_kernel, n_k=n_k, block_k=block_k,
                          scale=scale),
        grid=(BH, n_q, n_k),
        in_specs=[
            _LENGTHS_SPEC,
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),  # k
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),  # v
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),  # do
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),   # lse
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),   # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
        name="mha_dq",
    )(lens, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_mha_bwd_dkv_kernel, n_q=n_q, block_k=block_k,
                          scale=scale),
        grid=(BH, n_k, n_q),
        in_specs=[
            _LENGTHS_SPEC,
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, j, 0)),  # q
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, i, 0)),  # k
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, i, 0)),  # v
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, j, 0)),  # do
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, j)),   # lse
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, j)),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, dh), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, dh), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dh), jnp.float32),
            pltpu.VMEM((block_k, dh), jnp.float32),
        ],
        interpret=interpret,
        name="mha_dkv",
    )(lens, q, k, v, do, lse, delta)
    return dq, dk, dv


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           kv_len: int | None = None,
                           interpret: bool = False) -> jnp.ndarray:
    """q (BH, Sq, dh), k/v (BH, Sk, dh) — padded to block multiples by ops.
    ``kv_len`` = true (unpadded) KV length for masking."""
    BH, Sq, dh = q.shape
    Sk = k.shape[1]
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = dh ** -0.5
    kernel = functools.partial(
        _flash_kernel, n_k=n_k, block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, seq_len=kv_len or Sk)
    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # normalizer
            pltpu.VMEM((block_q, dh), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
