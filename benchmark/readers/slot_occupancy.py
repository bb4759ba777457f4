"""Mean share of slots occupied over the window's iterations: the
occupancy the engine reports to its `on_iteration` callback (the quantity
behind snapshot()["slot_occupancy"], here restricted to the window)."""


def read(facts):
    occ = facts.get("occupancy")
    if occ is None or len(occ) == 0:
        return None
    return 100.0 * float(occ.mean()) / facts["num_slots"]
