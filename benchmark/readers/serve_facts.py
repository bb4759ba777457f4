"""What the serving readers of PR 33 share: the window's counts from the
two snapshots of `ServingMetrics` the closed-loop driver took (open and
close), or None where a snapshot lacks a key (a program without the
`cache` block, as the parent of PR 33)."""
from benchmark.readers.snapshot_ratio import _at


def delta(facts, path):
    a, b = facts.get("snapshot_open"), facts.get("snapshot_close")
    if a is None or b is None:
        return None
    x, y = _at(a, path), _at(b, path)
    return None if x is None or y is None else y - x


def window_means(facts):
    """(mean occupied slots, mean written positions a slot) over the
    window's iterations: occupancy from the `on_iteration` callbacks, the
    positions from `paging.live_page_iterations` (ceil(written / page) a
    slot, summed: a position count rounded up to pages, 1 % high at
    these lengths). None where there is nothing to read."""
    occ = facts.get("occupancy")
    its = delta(facts, "iterations")
    pages = delta(facts, "paging.live_page_iterations")
    if occ is None or len(occ) == 0 or not its or pages is None:
        return None
    slots = float(sum(occ)) / len(occ)
    if slots <= 0:
        return None
    page = facts["config"]["pool"]["page_size"]
    return slots, pages * page / its / slots
