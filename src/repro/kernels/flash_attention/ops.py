"""jit'd public wrapper: GQA-aware flash attention.

Flattens (B, H) -> BH, repeats KV heads to query heads (simple v1 GQA;
a grouped-DOT kernel that avoids the repeat is a recorded §Perf follow-up),
pads sequence lengths to block multiples, and calls the Pallas kernel
(compiled on TPU, interpreted on CPU)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...obs.profiling import named_scope
from ...runtime import interpret_kernels
from .kernel import flash_attention_kernel, mha_bwd_kernels, mha_fwd_kernel


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """q (B, Sq, H, dh); k/v (B, Sk, KV, dh); returns (B, Sq, H, dh).
    ``interpret=None`` compiles on TPU and interprets on CPU."""
    if interpret is None:
        interpret = interpret_kernels()
    with named_scope("mrsch.kernel.flash_attention"):
        return _flash_attention_impl(q, k, v, causal=causal, block_q=block_q,
                                     block_k=block_k, interpret=interpret)


def _flash_attention_impl(q, k, v, *, causal, block_q, block_k, interpret):
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, -1, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, -1, dh)

    pad_q = (-Sq) % block_q
    Sk = kf.shape[1]
    pad_k = (-Sk) % block_k
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    out = flash_attention_kernel(qf, kf, vf, causal=causal, block_q=block_q,
                                 block_k=block_k, kv_len=Sk,
                                 interpret=interpret)
    out = out[:, :Sq]
    return out.reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)


# ------------------------------------------------------------ masked mha
# Differentiable non-causal attention over per-row variable-length token
# sets — the hot path of the queue-as-tokens state encoder
# (repro.nn.queue_encoder).  Mirrors fused_mlp/ops.py: a custom_vjp whose
# forward and backward both run Pallas kernels, padding handled inside
# the vjp boundary, compiled on TPU and interpreted on CPU.

def _pad_seq(x, mult: int, axis: int = 1):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _mha_fwd_impl(q, k, v, lengths, block_q, block_k, interpret):
    with named_scope("mrsch.kernel.mha_fwd"):
        Sq = q.shape[1]
        o, lse = mha_fwd_kernel(
            _pad_seq(q, block_q), _pad_seq(k, block_k), _pad_seq(v, block_k),
            lengths, block_q=block_q, block_k=block_k, interpret=interpret)
        return o[:, :Sq], lse[:, :, :Sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _mha(q, k, v, lengths, block_q, block_k, interpret):
    return _mha_fwd_impl(q, k, v, lengths, block_q, block_k, interpret)[0]


def _mha_fwd(q, k, v, lengths, block_q, block_k, interpret):
    o, lse = _mha_fwd_impl(q, k, v, lengths, block_q, block_k, interpret)
    return o, (q, k, v, lengths, o, lse)


def _mha_bwd(block_q, block_k, interpret, res, do):
    with named_scope("mrsch.kernel.mha_bwd"):
        return _mha_bwd_impl(block_q, block_k, interpret, res, do)


def _mha_bwd_impl(block_q, block_k, interpret, res, do):
    q, k, v, lengths, o, lse = res
    Sq, Sk = q.shape[1], k.shape[1]
    # delta = rowsum(do * o): the softmax-jacobian correction, computed
    # once host-graph-side instead of inside both backward kernels.
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(axis=-1)
    dq, dk, dv = mha_bwd_kernels(
        _pad_seq(q, block_q), _pad_seq(k, block_k), _pad_seq(v, block_k),
        _pad_seq(do, block_q), _pad_seq(lse, block_q, axis=2),
        _pad_seq(delta[:, None], block_q, axis=2), lengths,
        block_q=block_q, block_k=block_k, interpret=interpret)
    # lengths ride through as a float array (custom_vjp nondiff_argnums
    # cannot carry traced arrays) — their cotangent is defined as zero.
    return (dq[:, :Sq], dk[:, :Sk], dv[:, :Sk], jnp.zeros_like(lengths))


_mha.defvjp(_mha_fwd, _mha_bwd)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def mha(q, k, v, lengths=None, *, block_q: int = 128, block_k: int = 128,
        interpret: bool | None = None):
    """Masked non-causal flash attention, differentiable in q/k/v.

    q (BH, Sq, dh), k/v (BH, Sk, dh); ``lengths`` (BH,) — valid KV tokens
    per batch-head row (keys at positions >= length are masked out; a
    fully-masked row outputs exactly 0, matching ``ref.attention_ref``
    with lengths).  ``None`` means every key is valid.  The backward pass
    runs the fused dq/dkv Pallas kernels via ``jax.custom_vjp``;
    ``interpret=None`` compiles on TPU and interprets on CPU.
    """
    if interpret is None:
        interpret = interpret_kernels()
    BH, _, _ = q.shape
    Sk = k.shape[1]
    if lengths is None:
        lens = jnp.full((BH,), float(Sk), jnp.float32)
    else:
        lens = jnp.minimum(lengths.astype(jnp.float32), float(Sk))
    return _mha(q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), lens, block_q, block_k,
                bool(interpret))
