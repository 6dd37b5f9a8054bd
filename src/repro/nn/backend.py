"""Pluggable execution backend for dense/MLP forwards.

Two backends run the same ``{'layers': [{'w','b'}, ...]}`` param pytree
(checkpoints are backend-agnostic — switching backends never touches
the parameter layout):

* ``"xla"``    — plain jnp ops (``dense_apply`` + activation), the
                 reference path; bit-identical to the historical
                 ``mlp_apply`` pipeline.
* ``"pallas"`` — every layer runs through the fused matmul+bias+act
                 Pallas kernel (``repro.kernels.fused_mlp``), forward
                 AND backward (custom VJP with fused dgrad/wgrad), so
                 jitted gradient bursts stay inside the kernel layer.
                 Compiled on TPU, interpret-mode fallback elsewhere.

Activations are named (strings), not callables, so the Pallas epilogue
can fuse them; ``None`` means linear.

``pad_dense_layers`` turns a param pytree's dense layers into
``PaddedDense`` (the kernel's padded weight and bias), which the
``pallas`` forward consumes as is: a caller that runs the same weights
many times pads them once.  Checkpoints keep the logical layout.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax

from ..kernels.fused_mlp.kernel import _apply_activation, _check_activation
from ..kernels.fused_mlp.ops import fused_mlp, fused_mlp_padded, pad_weights
from .modules import Params, dense_apply

BACKENDS = ("xla", "pallas")


def resolve_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown nn backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    return backend


def _named_activation(y, activation: Optional[str], slope: float):
    # Single dispatch table with the Pallas epilogue (kernel.py), so an
    # activation added there is automatically available on both backends.
    if activation is None:
        return y
    _check_activation(activation)
    return _apply_activation(y, activation, slope)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class PaddedDense:
    """A dense layer for the ``pallas`` backend, its weight and bias
    padded by ``fused_mlp.pad_weights``; ``n`` is the logical output
    width (static)."""
    w: jax.Array
    b: jax.Array
    n: int

    def tree_flatten(self):
        return (self.w, self.b), self.n

    @classmethod
    def tree_unflatten(cls, n, children):
        return cls(*children, n)


def _is_dense(node) -> bool:
    return (isinstance(node, dict) and set(node) == {"w", "b"}
            and node["w"].ndim == 2)


def pad_dense_layers(params):
    """``params`` with every ``{'w', 'b'}`` layer of a 2-D weight as a
    ``PaddedDense``; every other leaf as it is.  For a pytree that the
    ``pallas`` ``dense_forward`` runs; the input is not modified."""
    def pad(node):
        if not _is_dense(node):
            return node
        return PaddedDense(*pad_weights(node["w"], node["b"]),
                           node["w"].shape[1])
    return jax.tree_util.tree_map(pad, params, is_leaf=_is_dense)


def count_padded(params) -> int:
    """The number of ``PaddedDense`` layers in a pytree."""
    def is_padded(node):
        return isinstance(node, PaddedDense)
    return sum(map(is_padded,
                   jax.tree_util.tree_leaves(params, is_leaf=is_padded)))


def dense_forward(layer: Params, x, activation: Optional[str] = None, *,
                  slope: float = 0.2, backend: str = "xla",
                  interpret: Optional[bool] = None):
    """One dense layer + optional named activation on the given backend
    (a ``PaddedDense`` layer on ``pallas`` only)."""
    if resolve_backend(backend) == "pallas":
        if isinstance(layer, PaddedDense):
            return fused_mlp_padded(x, layer.w, layer.b, layer.n,
                                    activation=activation or "linear",
                                    slope=slope, interpret=interpret)
        return fused_mlp(x, layer["w"], layer["b"],
                         activation=activation or "linear", slope=slope,
                         interpret=interpret)
    return _named_activation(dense_apply(layer, x), activation, slope)


def mlp_forward(params: Params, x, hidden_activation: str = "leaky_relu",
                final_activation: Optional[str] = None, *,
                slope: float = 0.2, backend: str = "xla",
                interpret: Optional[bool] = None):
    """MLP forward with named activations, dispatched per backend.

    ``backend="xla"`` reproduces ``mlp_apply`` (+ optional trailing
    activation) exactly; ``backend="pallas"`` runs each layer through
    the fused kernel.
    """
    n = len(params["layers"])
    for i, layer in enumerate(params["layers"]):
        act = hidden_activation if i < n - 1 else final_activation
        x = dense_forward(layer, x, act, slope=slope, backend=backend,
                          interpret=interpret)
    return x
