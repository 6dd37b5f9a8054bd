"""Compile the main path's Pallas kernels for a TPU v5e, without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot:
blocks that break the (8, 128) tiling, ops Mosaic cannot lower, and VMEM
overruns.  Nothing runs; each test asserts that its kernel reached the
compiled program as a ``tpu_custom_call`` (not interpreted, not replaced
by XLA ops).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports every test file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import mha
from repro.kernels.fused_mlp.ops import fused_mlp
from repro.kernels.window_pack.ops import pack_window

THETA_STATE = 11410          # paper state width (W=10, Theta's units)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def kernel_calls(fn, *args) -> str:
    """HLO lines of the TPU-compiled program that call a Pallas kernel."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return "\n".join(line for line in text.splitlines()
                     if 'custom_call_target="tpu_custom_call"' in line)


@pytest.mark.parametrize("m", [8, 512])
def test_fused_mlp_fwd_bwd_compiles_at_paper_width(shape, m):
    def loss(x, w, b):
        return fused_mlp(x, w, b, interpret=False).sum()

    args = (shape(m, THETA_STATE), shape(THETA_STATE, 4000), shape(4000))
    calls = kernel_calls(jax.value_and_grad(loss, (0, 1, 2)), *args)
    for name in ("fused_mlp_fwd", "fused_mlp_dgrad", "fused_mlp_wgrad"):
        assert f"/{name}/pallas_call" in calls, name


@pytest.mark.parametrize("seq", [129, 513])
def test_mha_fwd_bwd_compiles_at_queue_cap(shape, seq):
    """S = 1 + queue_cap for queue caps 128 and 512; BH = 8 envs x 4
    heads, head dim 16 (the encoder's defaults)."""
    def loss(q, k, v, lens):
        return mha(q, k, v, lens, interpret=False).sum()

    qkv = shape(32, seq, 16)
    calls = kernel_calls(jax.value_and_grad(loss, (0, 1, 2)),
                         qkv, qkv, qkv, shape(32))
    for name in ("mha_fwd", "mha_dq", "mha_dkv"):
        assert f"/{name}/pallas_call" in calls, name


@pytest.mark.parametrize("window", [10, 128])
def test_window_pack_compiles_at_theta_job_count(shape, window):
    """Five months of Theta is about 12k jobs; window 10 is the MLP
    module's, 128 the attention module's queue cap."""
    def pack(waiting, feats):
        return pack_window(waiting, feats, window=window, use_pallas=True,
                           interpret=False)

    calls = kernel_calls(pack, shape(8, 12000), shape(8, 12000, 4))
    assert "/window_pack/pallas_call" in calls


def test_theta_rollout_compiles_and_fits_one_chip(shape, monkeypatch):
    """The whole N=8 collection rollout at Theta scale (2-day traces,
    paper-width DFP, Pallas backend) compiles for one v5e chip and its
    buffers fit in its 16 GB."""
    from repro.core import AgentConfig, MRSchAgent
    from repro.sim import DeviceSimulator, SimConfig
    from repro.workloads import ThetaConfig, generate_trace
    # Kernel choice follows the default backend, which is the CPU here.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    theta = ThetaConfig(duration_days=2.0)
    res = theta.resources()
    agent = MRSchAgent(res, AgentConfig(backend="pallas"))
    jobsets = [generate_trace(ThetaConfig(duration_days=2.0, seed=s))
               for s in range(8)]
    sim = DeviceSimulator(res, jobsets, agent, SimConfig.for_engine("device"))
    args = jax.tree.map(lambda x: shape(*np.shape(x), dtype=x.dtype),
                        (sim.arrays, sim.faults_arrays, agent.init_state(),
                         jnp.float32(0.1), jax.random.PRNGKey(0)))
    compiled = sim._fn(True, True).lower(*args).compile()
    text = compiled.as_text()
    assert "/window_pack/pallas_call" in text
    assert "/fused_mlp_fwd/pallas_call" in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert agent.enc.state_dim == THETA_STATE
    assert total < 16e9, total
