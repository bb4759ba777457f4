"""Mean period, in ms, of the engine iterations that had work (a slot
occupied or a request queued when the iteration ended), from the times the
engine called its `on_iteration` callback inside the window. Idle spins of
the server loop (it wakes every few ms with nothing to do) are left out,
so the number is the iteration's cost and not the traffic's gaps."""
import numpy as np


def read(facts):
    t, busy = facts.get("iteration_t"), facts.get("iteration_busy")
    if t is None or len(t) < 2:
        return None
    dt = np.diff(t)[busy[1:]]
    return None if len(dt) == 0 else float(dt.mean()) * 1e3
