"""Operations and bytes of the work a cell asks for, from the
configuration's logical shapes: the policy network's at the
configuration's stated ``dtype``, the engine's state (job features,
masks, indices) at float32, which its exact times need.

These count what the algorithm needs, not what an implementation does:
padding, re-laid-out copies, wider storage than the stated precision and
recomputation are not work, so a kernel that stops doing them reads as a
higher share of its roofline.
"""
from __future__ import annotations

from . import refnet

F32 = 4
ITEMSIZE = {"float32": 4, "bfloat16": 2}


def itemsize(cfg: dict) -> int:
    """Bytes of one element of the policy network at the configuration's
    stated precision."""
    return ITEMSIZE[cfg["dtype"]]


def dense_cost(m: int, k: int, n: int, size: int) -> tuple:
    """y = act(x @ W + b), x (m, k), W (k, n), elements of ``size``
    bytes: FLOPs and HBM bytes (read x, W and b once, write y once)."""
    return 2 * m * k * n, size * (m * k + k * n + n + m * n)


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The shortest time the chip could take: compute or bandwidth bound."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


def attention_cost(bh: int, s: int, hd: int, size: int) -> tuple:
    """Masked softmax attention over s tokens for bh batch-heads:
    QK^T and PV (2 * 2 * s * s * hd each row-head); reads q, k, v and
    writes o once, elements of ``size`` bytes."""
    return 4 * bh * s * s * hd, size * 4 * bh * s * hd


def attention_shape(cfg: dict, rows: int) -> tuple:
    """(batch-heads, tokens, head width) of one attention layer."""
    return (rows * cfg["attn_heads"], cfg["queue_cap"] + 1,
            cfg["attn_dim"] // cfg["attn_heads"])


def window_pack_bytes(n: int, j: int, k: int, f: int) -> int:
    """Pack the first k of j waiting jobs of n environments (the
    engine's float32 state): read the waiting mask (n, j) and features
    (n, j, f); write features (n, k, f), indices (n, k) and a one-byte
    valid mask (n, k)."""
    return F32 * (n * j + n * j * f + n * k * f + n * k) + n * k


def flops_per_row(cfg: dict) -> float:
    """FLOPs of one forward of the policy for one decision row."""
    size = itemsize(cfg)
    total = sum(dense_cost(m, k, n, size)[0]
                for m, k, n in refnet.dense_layers(cfg, 1))
    if cfg["state_module"] == "attention":
        bh, s, hd = attention_shape(cfg, 1)
        total += cfg["attn_layers"] * attention_cost(bh, s, hd, size)[0]
    return total


def dense_least_time(cfg: dict, rows: int, peaks: dict) -> float:
    """Least time of every dense layer of one forward over ``rows``."""
    size = itemsize(cfg)
    return sum(least_time(*dense_cost(m, k, n, size), peaks)
               for m, k, n in refnet.dense_layers(cfg, rows))
