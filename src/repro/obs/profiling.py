"""Profiling hooks: named trace annotations + wall-clock spans.

Two complementary mechanisms:

- :func:`named_scope` — ``jax.named_scope``: a *trace-time* name-stack
  entry, so HLO ops compiled from a region carry the name and a
  ``jax.profiler`` device trace attributes kernel time to it.  Zero
  runtime cost after compilation; this is what wraps every Pallas kernel
  dispatch (``fused_mlp``, ``flash_attention``, ``window_pack``).
- :func:`annotate` — ``jax.profiler.TraceAnnotation``: a *host-side*
  profiler annotation for engine phases (device rollout, vector policy
  dispatch, serve micro-batch, train step), visible on the Python
  timeline of a captured profile.

Both are thin wrappers over jax's own.

:func:`span` is the tracer-facing counterpart: it measures a wall-clock
phase and emits a ``prof.span`` event, which `tools/trace_report.py`
aggregates into the per-phase time table.
"""
from __future__ import annotations

import contextlib
import time
from typing import ContextManager

import jax

from .trace import NULL, Tracer

__all__ = ["annotate", "named_scope", "span"]


def annotate(name: str) -> ContextManager:
    """Host-side ``jax.profiler.TraceAnnotation(name)``."""
    return jax.profiler.TraceAnnotation(name)


def named_scope(name: str) -> ContextManager:
    """Trace-time ``jax.named_scope(name)``."""
    return jax.named_scope(name)


@contextlib.contextmanager
def span(tracer: Tracer, name: str):
    """Time a wall-clock phase; emit ``prof.span`` + a profiler
    annotation.  Safe (and free) with the NULL tracer."""
    with annotate(f"mrsch.{name}"):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            (tracer or NULL).span(name, time.perf_counter() - t0)
