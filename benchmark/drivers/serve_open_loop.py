"""An open loop: requests are due at times fixed by the mix's arrival
process and its own seed, whatever the server does. This thread sleeps to each
due time and submits; the server's own thread runs the engine. Warm traffic
at the same rate runs for `warm_s` before the window opens, so the window
starts in steady state; after the window the generator stops (a traced run
first offers `trace_slice_s` more under the profiler) and the server
drains, so every request due in the window resolves.

End to end, over requests DUE in the window: `gap_p95_ms` = gaps between
consecutive tokens at the client, pooled. Time to first token (first token
at the client minus the time the request was DUE) is left in `facts` for the
per-layer readers: with iterations of 0.4 s its percentiles swing by a tenth
between two runs of one seed, more than any bound could hold (PERF.md
section 6, PR 24). `attempted` = requests due in the
window, `failed` = those of them that did not resolve ok."""
from __future__ import annotations

import time

from benchmark.drivers._serving import (Pool, client_latencies,
                                        reference_check, summary_ms)
from benchmark.traffic_gen import arrival_offsets
from benchmark.util import percentile, say


def offer(pool, t0, offsets, until=None):
    """Submit one request at each t0 + offset; returns the records. Sleeps
    coarsely, then spins the last millisecond: the host clock's own error
    is about half a millisecond."""
    recs = []
    for off in offsets:
        due = t0 + float(off)
        while True:
            left = due - time.perf_counter()
            if left <= 0:
                break
            time.sleep(left - 0.001 if left > 0.002 else 0)
        recs.append(pool.submit(due))
    if until is not None:
        left = until - time.perf_counter()
        if left > 0:
            time.sleep(left)
    return recs


def lateness_ms(recs):
    return [(r.sent - r.due) * 1e3 for r in recs]


def run(run):
    mix = run.traffic
    arrivals = mix["arrivals"]
    warm_s = float(mix["warm_s"])
    pool = Pool(run)

    t0 = time.perf_counter()
    warm = offer(pool, t0, arrival_offsets(arrivals, warm_s, 6),
                 until=t0 + warm_s)
    snap_open = pool.snapshot()
    t_open = run.open_window()
    recs = offer(pool, t_open,
                 arrival_offsets(arrivals, run.seconds, 4),
                 until=t_open + run.seconds)
    t_close = run.close_window()
    snap_close = pool.snapshot()
    outstanding = sum(1 for r in warm + recs if r.pending())
    if run.trace:
        slice_s = float(mix["trace_slice_s"])
        with run.device_trace():
            t1 = time.perf_counter()
            offer(pool, t1,
                          arrival_offsets(arrivals, slice_s, 7),
                          until=t1 + slice_s)
    ok_health = pool.stop(drain=True)

    window = t_close - t_open
    failed = [r for r in recs if not r.ok()]
    ttft, gaps = client_latencies(recs)
    late = lateness_ms(recs)
    its = pool.watcher.between(t_open, t_close)
    occ, depth = its["occupancy"], its["queue_depth"]
    ok_ref = reference_check(run, pool, recs)
    say(window_s=window, requests_due_in_window=len(recs),
        rate_per_s=arrivals["rate_per_s"], warm_requests=len(warm),
        outstanding_at_close=outstanding, refused=pool.refused,
        ttft_ms=summary_ms(ttft), gap_ms=summary_ms(gaps),
        generator_lateness_ms={"p50": percentile(late, 50),
                               "p95": percentile(late, 95),
                               "max": max(late)},
        mean_occupancy=float(occ.mean()) if len(occ) else None,
        mean_queue_depth=float(depth.mean()) if len(depth) else None)
    run.facts.update(window_s=window, snapshot_open=snap_open,
                     snapshot_close=snap_close, client_gaps_s=gaps,
                     client_ttft_s=ttft, **its,
                     num_slots=pool.engine.num_slots,
                     window_request_ids={r.req.id for r in recs
                                         if r.req is not None})
    return {"attempted": len(recs), "failed": len(failed),
            "correct": ok_ref and ok_health and not failed,
            "end_to_end": {"gap_p95_ms": percentile(gaps, 95) * 1e3}}
