"""A percentile, in ms, of the durations of the host-plane events of one
name in the traced slice: the annotations the program itself enters
(`SpmdTrainer.step`: `train.next_key`, `train.shard`, `train.enqueue`;
profiler/trace.py `annotation`). They are on the profiler's clock, beside
the device's operations. None where the trace holds no such event (the
parent of PR 25 enters none) or there is no device trace."""
from benchmark import named_trace, trace_reduce
from benchmark.util import percentile


def read(facts, name, q=50):
    planes = named_trace.planes_of(facts)
    if planes is None:
        return None
    durs = [d for p in trace_reduce.host_planes(planes)
            for ln in p["lines"] for n, _, d in ln["events"] if n == name]
    if not durs:
        return None
    return percentile(durs, q) * 1e-6
