"""Share, in %, of the chip's memory bandwidth that the bytes a decode step
MUST move make of an iteration's period: the cost file's `step_bytes` at
the window's mean occupancy and context / (mean period of the iterations
that had work x the peak). Joins share the iterations, so the share is of
the serving loop as it ran, not of the step alone. None on the CPU
rehearsal and where there is nothing to read."""
import importlib

from benchmark.readers import iteration_period, serve_facts


def read(facts, costs):
    means = serve_facts.window_means(facts)
    period_ms = iteration_period.read(facts)
    if "peaks" not in facts or means is None or not period_ms:
        return None
    cost = importlib.import_module("benchmark." + costs)
    slots, context = means
    need = cost.step_bytes(facts["config"], slots, context)
    return 100.0 * need / (period_ms * 1e-3
                           * facts["peaks"]["hbm_bytes_per_s"])
