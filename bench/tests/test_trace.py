"""The trace reduction: on hand-made nested events, and on a small trace
recorded on a v5e chip (``bench/tools/record_trace.py``)."""
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import helpers
from harness import trace

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def profile(host, device, modules=()):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=list(modules)),
                                         NS(name="XLA Ops", events=device),
                                         NS(name="Async XLA Ops",
                                            events=[ev("%copy-start.1 = x", 0, 10**9)])]),
        NS(name="#Chip0 Misc", lines=[]),
    ])


def test_nested_events_reduce_to_self_time_busy_and_named_gaps():
    hlo = ('HloModule jit_f, is_scheduled=true\n'
           '  %while.3 = (f32[]) while(%a), metadata={op_name="jit(f)/while"}\n'
           '  %fusion.1 = f32[8] fusion(%b), metadata={op_name="jit(f)/while/body/'
           'mrsch.kernel.fused_mlp/fused_mlp_fwd/pallas_call"}\n'
           '  ROOT %add.2 = f32[8] add(%c, %d), metadata={op_name="jit(f)/while/body/add"}\n')
    host = [ev("bench.rollout", 1_000, 1_000_000), ev("bench.results", 1_001_000, 400_000),
            ev("mrsch.device.rollout", 1_000, 50_000)]
    # while 100us..700us holds fusion (300us) and add (100us); a second
    # add after a 200 us gap; all inside the window [1us, 1401us].
    device = [ev("%while.3 = (f32[]) while(...)", 100_000, 600_000),
              ev("%fusion.1 = f32[8] fusion(...)", 150_000, 300_000),
              ev("%add.2 = f32[8] add(...)", 500_000, 100_000),
              ev("%add.2 = f32[8] add(...)", 900_000, 100_000),
              ev("%add.2 = f32[8] add(...)", 5_000_000, 100_000)]   # outside
    modules = [ev("jit_f(42)", 100_000, 600_000), ev("jit_f(42)", 900_000, 100_000),
               ev("jit_f(42)", 5_000_000, 100_000)]
    out = trace.reduce_profile(profile(host, device, modules),
                               {trace.module_name(hlo): trace.op_names(hlo)})
    assert out["traced_window_s"] == pytest.approx(1_400_000e-9)
    assert out["busy_s"] == pytest.approx(700_000e-9)
    assert out["scope_s"]["fused_mlp"] == pytest.approx(300_000e-9)
    # other: while self time 200us + two adds 200us
    assert out["scope_s"]["other"] == pytest.approx(400_000e-9)
    assert "unmapped" not in out["scope_s"]
    gaps = dict(out["breakdown"]["idle_gaps"])
    # 1..100us: the innermost span at its middle is the program's
    # dispatch; 700..900us lies in bench.rollout alone; 1000..1401us has
    # its middle in bench.results.
    assert gaps["mrsch.device.rollout"] == pytest.approx(99_000e-9)
    assert gaps["bench.rollout"] == pytest.approx(200_000e-9)
    assert gaps["bench.results"] == pytest.approx(401_000e-9)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["mrsch.kernel.fused_mlp/fused_mlp_fwd/pallas_call:fusion.1"] == pytest.approx(300e-6)


def test_ops_are_looked_up_in_their_own_programs_hlo():
    """``fusion.1`` of another program is not the kernel fusion of
    ``jit_f``: it counts as unmapped, and ``jit_f``'s own ops as before."""
    hlo = ('HloModule jit_f\n'
           '  %fusion.1 = f32[8] fusion(%b), metadata={op_name="jit(f)/'
           'mrsch.kernel.fused_mlp/fused_mlp_fwd/pallas_call"}\n'
           '  ROOT %add.2 = f32[8] add(%c, %d), metadata={op_name="jit(f)/add"}\n')
    host = [ev("bench.rollout", 0, 1_000_000)]
    device = [ev("%fusion.1 = f32[8] fusion(...)", 100_000, 100_000),
              ev("%add.2 = f32[8] add(...)", 200_000, 50_000),
              ev("%fusion.1 = f32[40] fusion(...)", 400_000, 30_000),
              ev("%convert.1 = f32[40] convert(...)", 600_000, 20_000)]
    modules = [ev("jit_f(1)", 100_000, 150_000), ev("jit_g(2)", 400_000, 30_000),
               ev("jit_h(3)", 600_000, 20_000)]
    out = trace.reduce_profile(profile(host, device, modules),
                               {"jit_f": trace.op_names(hlo), "jit_g": {}})
    assert out["scope_s"]["fused_mlp"] == pytest.approx(100_000e-9)
    # jit_g's HLO was added but names no fusion.1: outside every scope
    assert out["scope_s"]["other"] == pytest.approx(80_000e-9)
    assert out["scope_s"]["unmapped"] == pytest.approx(20_000e-9)
    assert out["busy_s"] == pytest.approx(200_000e-9)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["jit_h:convert.1"] == pytest.approx(20e-6)
    assert trace.program("jit_f(123)") == "jit_f"
    with pytest.raises(ValueError):
        trace.module_name("%add.2 = f32[8] add()")


def test_no_bench_span_or_device_reads_nothing():
    assert trace.reduce_profile(profile([], [ev("%a = b", 0, 5)]), {}) == {}
    assert trace.reduce_profile(NS(planes=[]), {}) == {}


def test_self_times_and_union():
    timed, top = trace.self_times([(0, 10, "a"), (2, 4, "b"), (5, 7, "c"),
                                   (12, 13, "d")])
    assert [t[3] for t in timed] == [6, 2, 2, 1]
    assert top == [(0, 10), (12, 13)]
    assert trace.union([(0, 3), (2, 5), (7, 8)]) == [(0, 5), (7, 8)]


def test_recorded_v5e_trace():
    """``bench/tools/record_trace.py`` on one v5e: two calls of a step
    (pad 857 ns, ``fused_mlp_fwd`` 14,042 ns, a fusion outside the kernel
    scope 456 ns) inside ``bench.step`` spans, 20 ms of host sleep in
    ``bench.host`` spans after each.  The device plane runs about 1 ms
    ahead of the host plane's clock, so the first step's device events
    (at 45.184 ms) fall before the first span (46.199 ms) and outside
    the window; the second step's (66.381 ms) fall inside.  Values
    checked by hand against ``trace.events.jsonl``."""
    from jax.profiler import ProfileData
    d = DATA / "chip_trace"
    hlo = (d / "trace.hlo.txt").read_text()
    out = trace.reduce_profile(ProfileData.from_file(str(d / "trace.xplane.pb")),
                               {trace.module_name(hlo): trace.op_names(hlo)})
    # window: 46,198,921 ns to 68,345,406 + 20,052,336 ns
    assert out["traced_window_s"] == pytest.approx(42_198_821e-9)
    assert out["busy_s"] == pytest.approx((857 + 14_042 + 456) * 1e-9)
    assert out["scope_s"]["fused_mlp"] == pytest.approx((857 + 14_042) * 1e-9)
    assert out["scope_s"]["other"] == pytest.approx(456e-9)
    assert "unmapped" not in out["scope_s"]
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["bench.host"] == pytest.approx(
        42_198_821e-9 - 15_355e-9 - 3e-9)
    events = [json.loads(x) for x in (d / "trace.events.jsonl").read_text().splitlines()]
    kernel = [e for e in events if e["name"].startswith("%fused_mlp_fwd.1")]
    assert [e["dur"] for e in kernel] == [14048.0, 14042.0]
