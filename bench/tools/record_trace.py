#!/usr/bin/env python3
"""Record the small chip trace that ``bench/tests/test_trace.py`` reduces.

    python bench/tools/record_trace.py <out_dir>

On one chip: a jitted step with one dense layer through the program's
``fused_mlp`` kernel (scope ``mrsch.kernel.fused_mlp``) and a reduction
outside any kernel scope, run twice inside ``bench.step`` spans with
20 ms of host sleep between them in a ``bench.host`` span.  Writes the
profile (``trace.xplane.pb``), the step's compiled HLO text
(``trace.hlo.txt``) and every XLA Ops event as JSON lines
(``trace.events.jsonl``) for checking the reduction by hand.
"""
from __future__ import annotations

import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.fused_mlp.ops import fused_mlp  # noqa: E402


@jax.jit
def step(x, w, b):
    y = fused_mlp(x, w, b, activation="leaky_relu")
    return jnp.tanh(y).sum(axis=1)


def main(out: str) -> int:
    out = Path(out)
    out.mkdir(parents=True, exist_ok=True)
    x = jnp.ones((40, 2048), jnp.float32)
    w = jnp.full((2048, 1024), 0.01, jnp.float32)
    b = jnp.zeros((1024,), jnp.float32)
    step(x, w, b).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.step"):
            step(x, w, b).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    pb = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(pb, out / "trace.xplane.pb")
    (out / "trace.hlo.txt").write_text(
        step.lower(x, w, b).compile().as_text())
    from jax.profiler import ProfileData
    with open(out / "trace.events.jsonl", "w") as f:
        for plane in ProfileData.from_file(pb).planes:
            for line in plane.lines:
                for e in line.events:
                    f.write(json.dumps({"plane": plane.name, "line": line.name,
                                        "name": e.name[:120], "start": e.start_ns,
                                        "dur": e.duration_ns}) + "\n")
    shutil.rmtree(d)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
