"""A percentile of a list of host-clock seconds a driver left in `facts`
(steps between read-backs, time inside tr.step, gaps at the client)."""
from benchmark.util import percentile


def read(facts, key, q, scale=1.0):
    values = facts.get(key)
    if values is None or len(values) == 0:
        return None
    return percentile(values, q) * scale
