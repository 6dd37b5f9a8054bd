"""Shared arithmetic of the per-layer metric readers (``bench/metrics``).

Each reader gets the traced run's reduction (``trace.reduce_profile``),
the cell's own counts, the configuration and the device's peaks, and
returns a number or None where there is nothing to read.
"""
from __future__ import annotations

from . import work


def idle_share(data):
    if not data.get("traced_window_s"):
        return None
    return 100.0 * (1.0 - data["busy_s"] / data["traced_window_s"])


def mfu(data, flops):
    if not data.get("traced_window_s") or not flops:
        return None
    return 100.0 * flops / (data["traced_window_s"] * data["peaks"]["flops_bf16"])


def roofline(data, scope, least_s):
    """Least time over the device time under ``mrsch.kernel.<scope>``."""
    spent = (data.get("scope_s") or {}).get(scope)
    if not spent or not least_s:
        return None
    return 100.0 * least_s / spent


def rollout_mfu(data):
    if "decisions" not in data:
        return None
    return mfu(data, data["decisions"] * work.flops_per_row(data["config"]))
