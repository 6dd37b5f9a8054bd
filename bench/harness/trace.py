"""Profiler capture and its reduction to device busy time, per-scope
device time and idle gaps.

What a TPU trace holds (read by hand on a v5e): each device is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per executed
HLO instruction, named by the instruction's text (``%fusion.12 = ...``),
with start and duration in ns on the host's clock, and whose line
``XLA Modules`` has one event per execution of a compiled program, named
``<module>(<fingerprint>)``, spanning that program's ops.  Control flow
nests: a ``while`` or ``conditional`` event spans the events of its
body, so time is attributed by self time (an event's duration less that
of the events it contains).  The events carry no source location; the
``named_scope`` of an instruction (``mrsch.kernel.fused_mlp`` and so on)
is read from the ``op_name`` metadata of the compiled program's HLO
text, by program and instruction name: instruction names such as
``fusion.12`` repeat from one program to the next, so an op is looked up
only in the HLO of the program whose ``XLA Modules`` event holds it.  An
op of a program whose HLO the run did not add counts as ``unmapped``,
apart from ``other`` (the added programs' time outside every kernel
scope).  Host planes (``/host:CPU``) hold the benchmark's own
``TraceAnnotation`` spans (``bench.*``), the program's (``mrsch.*``) and
the runtime's, on the same clock.

The traced window runs from the start of the first ``bench.*`` span to
the end of the last.  Busy time is the union of the top-level
``XLA Ops`` intervals inside it, averaged over the devices; an idle gap
is a stretch between them, named by the innermost host span that covers
its middle (gaps under 50 us are summed under one name).
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

SHORT_GAP_NS = 50_000     # shorter idle gaps are summed, not named
KERNEL_SCOPE = re.compile(r"mrsch\.kernel\.([A-Za-z0-9_]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_MODULE = re.compile(r"^\s*HloModule\s+([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def span(name: str):
    """A host span on the profiler's timeline (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Capture:
    """Profiles a stretch of the window into a temporary directory."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.started = False
        self.stopped = False
        self.hlo: Dict[str, Dict[str, str]] = {}

    @contextlib.contextmanager
    def active(self):
        import jax
        if not self.started:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.started = True
        yield

    def stop(self) -> None:
        import jax
        if self.started and not self.stopped:
            jax.profiler.stop_trace()
            self.stopped = True

    def add_hlo(self, text: str) -> None:
        """Instruction -> op_name of a compiled program that ran, under
        the program's module name."""
        self.hlo[module_name(text)] = op_names(text)

    def reduce(self) -> dict:
        self.stop()
        try:
            files = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if not files:
                return {}
            from jax.profiler import ProfileData
            return reduce_profile(ProfileData.from_file(files[0]), self.hlo)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def op_names(hlo_text: str) -> Dict[str, str]:
    """Map every instruction of an HLO module's text to its op_name."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        n = _OP_NAME.search(line)
        if n:
            out[m.group(1)] = n.group(1)
    return out


def module_name(hlo_text: str) -> str:
    """The module name an HLO module's text opens with."""
    m = _MODULE.match(hlo_text)
    if not m:
        raise ValueError("not the text of an HLO module")
    return m.group(1)


def program(module_event_name: str) -> str:
    """``jit_f(123)`` -> ``jit_f``: the module name of an ``XLA Modules``
    event."""
    return module_event_name.split("(", 1)[0]


def program_at(modules, starts, t: float) -> Optional[str]:
    """The program of the sorted ``(start, end, name)`` module events
    (``starts``: their starts) that holds time ``t``, or None."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][2] if i >= 0 and t < modules[i][1] else None


def instruction(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``mrsch.kernel.*`` scope of an op_name, or None."""
    found = KERNEL_SCOPE.findall(op_name or "")
    return found[-1] if found else None


def short_op(op_name: str, instr: str) -> str:
    parts = [p for p in (op_name or "").split("/") if p]
    tail = "/".join(parts[-3:]) if parts else ""
    return f"{tail}:{instr}" if tail else instr


def self_times(events: List[Tuple[float, float, str]]):
    """(start, end, name) intervals that nest -> [(start, end, name,
    self)] with each interval's time not covered by those inside it, and
    the top-level intervals."""
    events = sorted(events, key=lambda e: (e[0], -(e[1] - e[0])))
    out, top, stack = [], [], []
    child = defaultdict(float)
    for i, (s, e, n) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        else:
            top.append((s, e))
        stack.append(i)
    for i, (s, e, n) in enumerate(events):
        out.append((s, e, n, max(e - s - child[i], 0.0)))
    return out, top


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def reduce_profile(pd, hlo: Dict[str, Dict[str, str]]) -> dict:
    """Busy, per-scope and idle times of a profile (seconds); ``hlo``
    maps a program's module name to its instructions' op_names."""
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events]
        elif plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops = evs
                elif line.name == "XLA Modules":
                    modules = sorted((s, e, program(n)) for s, e, n in evs)
            if ops:
                devices.append((ops, modules))
    marks = [(s, e) for s, e, n in host if n.startswith("bench.")]
    if not marks or not devices:
        return {}
    w0, w1 = min(s for s, _ in marks), max(e for _, e in marks)
    scope_s = defaultdict(float)
    op_s = defaultdict(float)
    busy_total = 0.0
    gaps = []
    for evs, modules in devices:
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in evs
                  if e > w0 and s < w1]
        timed, top = self_times(inside)
        starts = [m[0] for m in modules]
        for s, e, n, own in timed:
            instr = instruction(n)
            prog = program_at(modules, starts, s)
            if prog in hlo:
                op_name = hlo[prog].get(instr, "")
                scope_s[scope_of(op_name) or "other"] += own
                op_s[short_op(op_name, instr)] += own
            else:
                scope_s["unmapped"] += own
                op_s[f"{prog}:{instr}"] += own
        busy = union(top)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    n_dev = len(devices)
    labelled = defaultdict(float)
    for a, b in gaps:
        if b - a < SHORT_GAP_NS:
            labelled["gaps under 50 us"] += (b - a) / n_dev
            continue
        mid = (a + b) / 2
        covering = [(e - s, n) for s, e, n in host if s <= mid <= e]
        label = min(covering)[1] if covering else "no host span"
        labelled[label] += (b - a) / n_dev
    ns = 1e-9
    return {
        "traced_window_s": (w1 - w0) * ns,
        "busy_s": busy_total / n_dev * ns,
        "scope_s": {k: v / n_dev * ns for k, v in scope_s.items()},
        "breakdown": {
            "device_ops": [[k, v / n_dev * ns] for k, v in
                           sorted(op_s.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[k, v * ns] for k, v in
                          sorted(labelled.items(), key=lambda kv: -kv[1])[:10]],
        },
    }
