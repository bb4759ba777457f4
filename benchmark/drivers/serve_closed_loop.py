"""A closed loop of `clients` callers: each sends its next request when its
last one resolves (an offline batch job). One client thread (this one)
waits on a queue of completions; the server's own thread runs the engine.

The window opens once the pool has been full and a request has finished,
or after `warm.max_s` seconds of warm traffic. At its close the server is
stopped without a drain: `attempted` = requests that resolved inside the
window, `failed` = those of them not ok; requests in flight at the close
are aborted and counted in neither.

End to end: `serve_tokens_per_s` = tokens streamed to clients (stream_cb)
inside the window / its seconds, host clock."""
from __future__ import annotations

import queue
import time

from benchmark.drivers._serving import Pool, reference_check, summary_ms
from benchmark.util import say


def run(run):
    mix = run.traffic
    pool = Pool(run)
    done_q = queue.Queue()
    pool.on_done = lambda rec: done_q.put((rec, time.perf_counter()))
    recs, finished = [], []
    t_warm = time.perf_counter()
    for _ in range(int(mix["clients"])):
        recs.append(pool.submit(time.perf_counter()))

    phase, t_open, t_close, t_stop = "warm", None, None, None
    snap_open = snap_close = None
    trace_ctx = None
    while phase != "stop":
        try:
            rec, t_done = done_q.get(timeout=0.02)
            rec.done_at = t_done
            finished.append(rec)
            recs.append(pool.submit(time.perf_counter()))   # same client
        except queue.Empty:
            pass
        now = time.perf_counter()
        if phase == "warm":
            warmed = pool.watcher.was_full_at is not None and finished
            if warmed or now - t_warm >= float(mix["warm"]["max_s"]):
                say(warm_s=now - t_warm, pool_was_full=bool(
                    pool.watcher.was_full_at), finished_in_warm=len(finished))
                snap_open = pool.snapshot()
                t_open = run.open_window()
                phase = "window"
        elif phase == "window" and now >= t_open + run.seconds:
            t_close = run.close_window()
            snap_close = pool.snapshot()
            if run.trace:
                trace_ctx = run.device_trace()
                trace_ctx.__enter__()
                t_stop = time.perf_counter() + float(mix["trace_slice_s"])
                phase = "trace"
            else:
                phase = "stop"
        elif phase == "trace" and now >= t_stop:
            trace_ctx.__exit__(None, None, None)
            phase = "stop"
    ok_health = pool.stop(drain=False)

    window = t_close - t_open
    in_window = [r for r in finished if t_open <= r.done_at < t_close]
    failed = [r for r in in_window if not r.ok()]
    tokens = sum(1 for r in recs for t in r.token_t if t_open <= t < t_close)
    gaps = [b - a for r in recs for a, b in zip(r.token_t, r.token_t[1:])
            if t_open <= b < t_close]
    its = pool.watcher.between(t_open, t_close)
    occ, depth = its["occupancy"], its["queue_depth"]
    ok_ref = reference_check(run, pool, in_window)
    say(window_s=window, tokens_in_window=tokens,
        requests_resolved_in_window=len(in_window),
        gap_ms=summary_ms(gaps),
        mean_occupancy=float(occ.mean()) if len(occ) else None,
        mean_queue_depth=float(depth.mean()) if len(depth) else None)
    run.facts.update(window_s=window, snapshot_open=snap_open,
                     snapshot_close=snap_close, client_gaps_s=gaps,
                     **its,
                     num_slots=pool.engine.num_slots)
    return {"attempted": len(in_window), "failed": len(failed),
            "correct": ok_ref and ok_health and not failed,
            "end_to_end": {"serve_tokens_per_s": tokens / window}}
