"""The names PR 25 put in a profiler trace, read from `trace_reduce`'s
planes: a Pallas kernel's fixed `name=` (the HLO instruction's name, hence
the first word of `trace_reduce.short_name`), the program an operation
belongs to (the `XLA Modules` event, `jit_pstep(<id>)`, that holds it in
time), and the program's own annotations on the host plane (events named
`iteration`, `step.readback`, `train.shard`, ...). Everything else, the
window, an operation, self time, busy and idle, is `trace_reduce`'s.
Where each name lands in the `.xplane.pb`: `benchmark/README_tracing.md`.
"""
from __future__ import annotations

import bisect
import re

from benchmark import trace_reduce


def planes_of(facts):
    """The traced slice's planes, loaded once a run and kept in `facts`.
    None where the run reduced no device trace (`--trace 0`, or the CPU
    rehearsal, whose reason `run.py` has printed); a trace that was
    reduced and cannot be read again raises."""
    if "named_planes" not in facts:
        facts["named_planes"] = None if facts.get("device") is None else \
            trace_reduce.load_xplane(facts["trace_dir"])
    return facts["named_planes"]


def kernel_of(short):
    """`paged_flash_decode` of `paged_flash_decode.12 tpu_custom_call
    f32[...]`: the instruction's name without the index XLA appends."""
    return short.split(" ", 1)[0].rsplit(".", 1)[0]


def program_runs(dev):
    """Sorted [(start_ns, end_ns, module name)] of the programs that ran
    on a device plane: its `XLA Modules` events, `jit_pstep(<id>)`."""
    return sorted(
        (s, s + d, re.sub(r"\(\d+\)$", "", name))
        for ln in dev["lines"] if ln["name"] == "XLA Modules"
        for name, s, d in ln["events"])


def ops_by_program(planes):
    """({(module name, kernel): [self seconds, calls]} of the first
    device's operations, {module name: runs}); None with no device plane.
    The module is None for an operation no `XLA Modules` event holds."""
    devs = trace_reduce.device_planes(planes)
    if not devs:
        return None
    runs = program_runs(devs[0])
    starts = [r[0] for r in runs]

    def program_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][2] if i >= 0 and t < runs[i][1] else None

    table = trace_reduce.self_times(
        ((program_at(s), kernel_of(short)), s, e)
        for short, s, e in trace_reduce.op_events(devs[0]))
    count = {}
    for _, _, name in runs:
        count[name] = count.get(name, 0) + 1
    return table, count


def idle_gaps(planes):
    """Sorted [(start, end)] of the intervals of the window in which no
    operation runs on the first device; None with no device plane."""
    devs = trace_reduce.device_planes(planes)
    if not devs:
        return None
    w0, w1 = trace_reduce.window_of(planes)
    busy = trace_reduce.merged(
        (max(s, w0), min(e, w1))
        for _, s, e in trace_reduce.op_events(devs[0]))
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def clipped(gaps, name, s, e):
    """The pieces (name, start, end) of [s, e] that lie inside the sorted
    disjoint `gaps`."""
    i = max(bisect.bisect_right(gaps, (s, float("inf"))) - 1, 0)
    while i < len(gaps) and gaps[i][0] < e:
        a, b = max(s, gaps[i][0]), min(e, gaps[i][1])
        if b > a:
            yield name, a, b
        i += 1
