"""Sums and differences of the engine-track spans (serving/tracing.py:
`iteration` and what nests under it, trace id 0) over the window's
iterations, from the program's own spans on the tracer's clock.

The driver hands over (name, trace_id, t0, t1) only, so a child is given
to its `iteration` by containment in time. The window is
[iteration_t[0], iteration_t[-1]] — the engine's `on_iteration` callbacks
the driver kept, which are on the same clock (`time.perf_counter`); an
iteration counts if it lies inside it.

    total = sum over those iterations of
            (time in spans named in `add`) - (time in spans named in `subtract`)

`over` None: total / iterations, in ms. `over` "iteration": 100 x total /
the iterations' own time, in %. None where the program records no
`iteration` span (the parent of PR 25) or tracing was off."""
import bisect


def read(facts, add, subtract=(), over=None):
    spans, t = facts.get("spans"), facts.get("iteration_t")
    if not spans or t is None or len(t) < 2:
        return None
    w0, w1 = float(t[0]), float(t[-1])
    its = sorted((t0, t1) for name, tid, t0, t1 in spans
                 if name == "iteration" and tid == 0
                 and t0 >= w0 and t1 <= w1)
    if not its:
        return None
    starts = [a for a, _ in its]
    sign = {n: 1.0 for n in add}
    sign.update({n: -1.0 for n in subtract})
    total = 0.0
    for name, tid, t0, t1 in spans:
        if tid != 0 or name not in sign:
            continue
        i = bisect.bisect_right(starts, t0) - 1
        if i >= 0 and t1 <= its[i][1]:
            total += sign[name] * (t1 - t0)
    if over is None:
        return 1e3 * total / len(its)
    return 100.0 * total / sum(b - a for a, b in its)
