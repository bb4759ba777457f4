"""A percentile, in ms, over the window's requests of the time each spent
in spans of one name (`queue`: submit to queue exit, re-opened when page
backpressure defers the request; `join`: join begin to join end), from the
program's own spans (serving/tracing.py, host clock)."""
from benchmark.util import percentile


def read(facts, span, q):
    spans, ids = facts.get("spans"), facts.get("window_request_ids")
    if not spans or not ids:
        return None
    per_request = {}
    for name, trace_id, t0, t1 in spans:
        if name == span and trace_id in ids:
            per_request[trace_id] = per_request.get(trace_id, 0.0) + t1 - t0
    if not per_request:
        return None
    return percentile(list(per_request.values()), q) * 1e3
