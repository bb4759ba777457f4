#!/usr/bin/env python
"""Per-request latency waterfalls from a serving chrome-trace file.

Reads a trace exported by `Tracer.export_chrome_trace` (the artifact
`bench.py serving_* --trace`, `tools/chaos_check.py --trace`, or any
`paddle_tpu.serving.session_scope()` run writes) and renders the
per-request breakdown: queue / join(prefill) / pending-splice / decode
phase totals with p50/p95 across requests, plus the slowest requests
as ASCII waterfalls. The same trace loads graphically in Perfetto
(ui.perfetto.dev) — this is the terminal view.

    python tools/trace_report.py /tmp/trace.json [--top 10]
    python tools/trace_report.py trace.json --percentiles 50,95,99
"""
import argparse
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="chrome-trace JSON file")
    ap.add_argument("--percentiles", default="50,95",
                    help="comma-separated percentiles for the phase "
                         "table")
    ap.add_argument("--top", type=int, default=8,
                    help="render the N slowest requests as waterfalls "
                         "(0 = table only)")
    ap.add_argument("--incomplete", action="store_true",
                    help="also list requests whose waterfall is "
                         "incomplete (missing queue/join/terminal)")
    args = ap.parse_args(argv)

    # pure-stdlib + numpy path: no jax import needed to read a trace
    from paddle_tpu.serving.tracing import (load_chrome_trace,
                                            waterfall_report, waterfalls)

    events = load_chrome_trace(args.trace)
    pcts = tuple(float(q) for q in args.percentiles.split(","))
    print(waterfall_report(events, percentiles=pcts, top=args.top))
    if args.incomplete:
        wf = waterfalls(events)
        bad = {tid: w for tid, w in wf.items() if not w["complete"]}
        if bad:
            print(f"\nincomplete waterfalls ({len(bad)}):")
            for tid, w in sorted(bad.items()):
                have = sorted({e["name"] for e in w["spans"]})
                print(f"  req {tid}: spans={have} reason={w['reason']}")
        else:
            print("\nall waterfalls complete")
    # engine-track quick stats
    compiles = [e for e in events if e.get("name") == "compile"]
    steps = [e for e in events if e.get("name") == "decode.step"]
    retraces = [e for e in events if e.get("name") == "retrace"]
    if compiles:
        total_ms = sum(e.get("dur", 0) for e in compiles) / 1e3
        print(f"\ncompiles: {len(compiles)} "
              f"({total_ms:.1f}ms total compile wall)")
        for e in compiles:
            print(f"  {e['args'].get('key')}  "
                  f"{e.get('dur', 0) / 1e3:9.1f}ms  "
                  f"count={e['args'].get('count')}")
    pre = [e for e in events if e.get("name") == "precompile"]
    if pre:
        hits = sum(1 for e in pre if e["args"].get("source") == "cache")
        total_ms = sum(e.get("dur", 0) for e in pre) / 1e3
        print(f"startup precompile: {len(pre)} programs "
              f"({hits} from cache, {len(pre) - hits} compiled; "
              f"{total_ms:.1f}ms wall)")
    if steps:
        print(f"decode steps: {len(steps)}")
    # the engine's own phases (children of `iteration`): where an
    # iteration's host time goes
    its = [e for e in events if e.get("name") == "iteration"]
    if its:
        total = sum(e.get("dur", 0) for e in its) or 1
        print(f"iterations with work: {len(its)} "
              f"({total / 1e3 / len(its):.2f}ms mean)")
        for phase in ("iter.harvest", "iter.admit", "iter.tok0",
                      "iter.chunks", "step.map_pages", "step.enqueue",
                      "step.readback", "iter.deliver", "iter.account"):
            dur = sum(e.get("dur", 0) for e in events
                      if e.get("name") == phase)
            if dur:
                print(f"  {phase:<16}{dur / 1e3 / len(its):9.3f}ms "
                      f"an iteration ({100 * dur / total:5.1f}%)")
    drafts = [e for e in events if e.get("name") == "decode.draft"]
    verifies = [e for e in events if e.get("name") == "decode.verify"]
    if verifies:
        acc = sum(e["args"].get("accepted", 0) for e in verifies)
        prop = sum(e["args"].get("proposed", 0) for e in verifies)
        d_ms = sum(e.get("dur", 0) for e in drafts) / 1e3
        v_ms = sum(e.get("dur", 0) for e in verifies) / 1e3
        print(f"speculation: {len(verifies)} draft/verify pairs, "
              f"acceptance {acc}/{prop} ({acc / max(1, prop):.0%}), "
              f"draft {d_ms:.1f}ms + verify {v_ms:.1f}ms wall")
    if retraces:
        print(f"RETRACE VIOLATIONS: {len(retraces)}")
        for e in retraces:
            print(f"  {e['args']}")
        return 1
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:            # e.g. piped into head
        sys.exit(0)
