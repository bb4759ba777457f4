"""What an expert stack's layers counted over the window, from
`snapshot()["experts"]` differenced between the snapshots the driver took
at window open and close (the counters leave each program with its tokens):

- `routed_share`: % of the routed token-slots that fell on experts held
  here (100 x held / all: held experts over all experts, 6.25 for 16 of
  256, if routing is uniform).
- `load_max_over_mean`: the fullest held expert's token-slots over the
  held experts' mean, a layer a program, summed (load_max x experts held
  / held slots: 1 if the held experts draw alike).
- `dropped_slots`: held slots the experts' loops did not reach: 0.

None where a snapshot has no `experts` section (a program without expert
counters, as the parent of PR 35) or nothing was routed."""
from benchmark.readers import serve_facts


def read(facts, what):
    d = {k: serve_facts.delta(facts, "experts." + k)
         for k in ("token_slots", "held_slots", "load_max",
                   "dropped_slots")}
    if any(v is None for v in d.values()) or not d["token_slots"]:
        return None
    if what == "routed_share":
        return 100.0 * d["held_slots"] / d["token_slots"]
    if what == "load_max_over_mean":
        if not d["held_slots"]:
            return None
        return d["load_max"] * facts["config"]["experts_held"][1] \
            / d["held_slots"]
    if what == "dropped_slots":
        return d["dropped_slots"]
    raise ValueError(f"expert_counts: what={what!r}")
