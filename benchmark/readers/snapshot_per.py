"""A monotonic counter of `ServingMetrics.snapshot()` per unit of another,
differenced between the snapshots the driver took at window open and close:

    delta(counter) / delta(per) x scale

Keys are dotted paths (`host.cpu_ns`). `host_cpu_ms_per_iter` is the engine
thread's CPU time in the host part of a working iteration, in ms:
delta(host.cpu_ns) / delta(host.iterations) x 1e-6. None where a snapshot
lacks a key (a program without the `host` section) or delta(per) is 0.

An earlier line gives the window's deltas of the whole section the counter
lies in (for `host`: the stalls with their cpu / gc split, collections by
generation, `spans_dropped`), list entries differenced one by one."""
from benchmark.readers.snapshot_ratio import _at
from benchmark.util import say


def _delta(a, b):
    """b - a for numbers and, entry by entry, for lists; b otherwise."""
    if isinstance(b, list):
        return [y - x for x, y in zip(a, b)]
    if isinstance(b, (int, float)) and not isinstance(b, bool):
        return b - a
    return b


def read(facts, counter, per, scale=1.0):
    a, b = facts.get("snapshot_open"), facts.get("snapshot_close")
    if a is None or b is None:
        return None
    vals = [_at(a, counter), _at(b, counter), _at(a, per), _at(b, per)]
    if any(v is None for v in vals):
        return None
    c0, c1, p0, p1 = vals
    if p1 == p0:
        return None
    section = counter.split(".")[0]
    opened, closed = _at(a, section), _at(b, section)
    say(snapshot_deltas={section: {k: _delta(opened.get(k), v)
                                   for k, v in closed.items()}},
        of=counter)
    return (c1 - c0) / (p1 - p0) * float(scale)
