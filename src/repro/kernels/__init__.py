"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships three files: kernel.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (jit'd public wrapper, compiled on TPU and interpreted on
CPU via ``repro.runtime.interpret_kernels``), and ref.py (pure-jnp oracle
used by the per-kernel allclose test sweeps).
"""
