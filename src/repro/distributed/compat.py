"""The jax spellings the distributed code goes through.

The repository supports the installed jax (see ``requirements.txt``);
these names keep call sites short and give mesh construction one place
to pick its axis types.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty when unavailable)."""
    return compiled.cost_analysis() or {}


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))
