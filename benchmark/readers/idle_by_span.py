"""Share, in %, of the first device's idle time in the traced slice that
lies inside one of the program's own innermost annotations: the host
phases `serving/tracing.py` and `SpmdTrainer.step` enter as
`TraceAnnotation`s, which the profiler records on the same clock as the
device's operations. `spans` is a regular expression over annotation
names; the `roots` (`iteration`, `train.step`) bracket everything and say
nothing, so time that lies in a root and in none of its children counts
as not attributed, like time outside every annotation. `work`, if given,
is a regular expression over the names a root holds when it did work
(`decode.step`, `join`): a root that holds none is an idle spin of the
engine, which the tracer drops from its record but the profiler has
already seen, and it is left out with everything inside it. Idle time and
the window are `trace_reduce`'s, and so is the nesting: each annotation is
cut to the idle gaps and `self_times` gives every piece to the innermost.
An earlier line gives the idle seconds by span name. None where the trace
holds no such annotation (the parent of PR 25) or there is no device trace
(`--trace 0`, the CPU rehearsal)."""
import bisect
import re

from benchmark import named_trace, trace_reduce
from benchmark.util import say


def _working(events, roots, work):
    """`events` without the roots that hold no `work` event and without
    what lies inside those."""
    if work is None:
        return events
    rx = re.compile(work)
    marks = sorted(s for n, s, _ in events if rx.search(n))
    spins = sorted((s, e) for n, s, e in events if n in roots
                   and bisect.bisect_left(marks, s) ==
                   bisect.bisect_right(marks, e))
    starts = [s for s, _ in spins]

    def spun(s, e):
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and e <= spins[i][1]

    return [ev for ev in events if not spun(ev[1], ev[2])]


def read(facts, roots, spans, work=None):
    planes = named_trace.planes_of(facts)
    gaps = planes and named_trace.idle_gaps(planes)
    if not gaps:
        return None
    idle = sum(e - s for s, e in gaps) * 1e-9
    rx = re.compile(spans)
    by_name, seen = {}, False
    for p in trace_reduce.host_planes(planes):
        for ln in p["lines"]:      # one thread a line: its events nest
            own = _working([(n, s, s + d) for n, s, d in ln["events"]
                            if n in roots or rx.search(n)], roots, work)
            seen = seen or any(n not in roots for n, _, _ in own)
            # a gap inside a child cuts parent and child to the same
            # piece: the one that began first holds the other
            pieces = sorted(((a, -b, s, -e), (name, a, b))
                            for name, s, e in own
                            for _, a, b in named_trace.clipped(
                                gaps, name, s, e))
            for name, (sec, _) in trace_reduce.self_times(
                    piece for _, piece in pieces).items():
                by_name[name] = by_name.get(name, 0.0) + sec
    if not seen:
        return None
    inside = {k: v for k, v in by_name.items() if k not in roots}
    say(idle_by_span=dict(sorted(inside.items(), key=lambda kv: -kv[1])),
        idle_s=idle, not_in_a_span_s=idle - sum(inside.values()))
    return 100.0 * sum(inside.values()) / idle
