"""Find, once, the highest arrival rate an open-loop cell sustains.

    python benchmark/sweep.py --workload <open-loop cell> --rates 4,4.5,5,5.5,6 \
        --span 40 --seed 1

One process, one set-up (the pool is built and compiled once); each rate is
offered for `--span` seconds with the cell's own lengths, and the pool
drains between rates so that each starts empty. A rate is sustained if the
requests outstanding at the END of its span exceed those at its MIDDLE by
no more than a tenth of the requests sent in the second half (and none was
refused): a span that starts empty first fills to rate x latency requests
in flight, which is not a backlog, so the span has to be several latencies
long and only its second half is judged. The knee is the last rate before
the first that is not sustained. A
cell below the knee fixes its rate at about four fifths of it, as a number
in its traffic file; nothing searches for a rate at run time.

Prints one JSON line per rate and a last line with the knee. Not run by the
driver: its table goes into PERF.md."""
from __future__ import annotations

import argparse
import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as harness, util                 # noqa: E402
from benchmark.util import percentile, say                  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second, ascending")
    ap.add_argument("--span", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.seconds, args.trace = args.span, 0

    cell, config, traffic = util.load_cell(args.workload, args.rehearse)
    if traffic["driver"] != "serve_open_loop":
        raise SystemExit("a rate sweep needs an open-loop cell")

    from benchmark.drivers._serving import Pool, client_latencies
    from benchmark.drivers.serve_open_loop import lateness_ms, offer
    from benchmark.traffic_gen import arrival_offsets

    got = harness.acquire(1, args.rehearse, "the sweep")
    if got is None:
        return 2
    devs, counts = got
    run = harness.Run(args, cell, config, traffic, devs, counts)
    pool = Pool(run)
    # warm the path once at a low rate, so no rate pays first-use costs
    t0 = time.perf_counter()
    warm = offer(pool, t0, arrival_offsets(
        dict(traffic["arrivals"], rate_per_s=1.0), 4.0, 6))
    for r in warm:
        r.req.result(timeout=600)

    rows = []
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        arrivals = dict(copy.deepcopy(traffic["arrivals"]), rate_per_s=rate)
        offsets = arrival_offsets(arrivals, args.span, 10 + i)
        first_half = offsets < args.span / 2
        t0 = time.perf_counter()
        recs = offer(pool, t0, offsets[first_half],
                     until=t0 + args.span / 2)
        middle = sum(1 for r in recs if r.pending())
        second = offer(pool, t0, offsets[~first_half],
                       until=t0 + args.span)
        recs += second
        after = sum(1 for r in recs if r.pending())
        for r in recs:                         # drain before the next rate
            if r.req is not None:
                r.req.result(timeout=600)
        drained_s = time.perf_counter() - t0 - args.span
        ttft, gaps = client_latencies(recs)
        late = lateness_ms(recs)
        ttft2, _ = client_latencies(second)
        row = {"rate_per_s": rate, "sent": len(recs),
               "outstanding_at_middle": middle, "outstanding_at_end": after,
               "growth_share_of_second_half": (after - middle) / len(second),
               "refused": sum(1 for r in recs if r.req is None),
               "sustained": (after - middle) <= 0.1 * len(second)
               and all(r.req is not None for r in recs),
               "ttft_p95_ms_second_half": percentile(ttft2, 95) * 1e3,
               "ttft_p50_ms": percentile(ttft, 50) * 1e3,
               "ttft_p95_ms": percentile(ttft, 95) * 1e3,
               "gap_p50_ms": percentile(gaps, 50) * 1e3,
               "gap_p95_ms": percentile(gaps, 95) * 1e3,
               "drain_after_span_s": drained_s,
               "generator_lateness_p95_ms": percentile(late, 95),
               "failed": sum(1 for r in recs if not r.ok())}
        rows.append(row)
        say(**row)
    ok = pool.stop(drain=True)
    knee = None                 # the last rate before the first that fails
    for row in sorted(rows, key=lambda r: r["rate_per_s"]):
        if not row["sustained"]:
            break
        knee = row["rate_per_s"]
    say(sweep="done", cell=args.workload, span_s=args.span, knee_per_s=knee,
        four_fifths=None if knee is None else 0.8 * knee, pool_health=ok,
        device_kind=devs[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
