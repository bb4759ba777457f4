"""A ratio of monotonic counters of `ServingMetrics.snapshot()`, differenced
between the snapshots the driver took at window open and close:

    100 x delta(counter) / (delta(per) x of)

with `of` a constant of the closing snapshot. Keys are dotted paths
(`paging.page_iterations`). `pages_in_use_share` is the mean share of the
pool's pages in use over the window's iterations: delta(page_iterations) /
(delta(iterations) x pages_total). None where a snapshot lacks a key (the
parent of PR 25 has no `page_iterations`)."""


def _at(doc, path):
    for key in path.split("."):
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc


def read(facts, counter, per, of):
    a, b = facts.get("snapshot_open"), facts.get("snapshot_close")
    if a is None or b is None:
        return None
    vals = [_at(a, counter), _at(b, counter), _at(a, per), _at(b, per),
            _at(b, of)]
    if any(v is None for v in vals):
        return None
    c0, c1, p0, p1, total = vals
    if p1 <= p0 or total <= 0:
        return None
    return 100.0 * (c1 - c0) / ((p1 - p0) * total)
