#!/usr/bin/env python
"""Prometheus text-format dump of ServingMetrics (+ tracer counters).

The serving runtime's `ServingMetrics.snapshot()` is a nested dict;
operators scrape flat Prometheus metrics. This CLI renders the one into
the other via `paddle_tpu.serving.metrics.to_prometheus` (the schema of
record is `SNAPSHOT_DOCS` — every snapshot key is documented there and
the doc test pins the two in sync). Usage:

    # render a saved snapshot (json.dump(engine.metrics.snapshot()))
    python tools/metrics_dump.py --snapshot snap.json [-o out.prom]

    # drive a tiny in-process pool and dump ITS metrics (self-test /
    # schema preview; runs on the CPU pin, no hardware needed)
    JAX_PLATFORMS=cpu python tools/metrics_dump.py --demo

In-process, prefer the library route:

    from paddle_tpu.serving import to_prometheus
    text = to_prometheus(engine.metrics.snapshot(), tracer=tracer)
"""
import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _demo_snapshot():
    """Serve a few requests through a tiny PAGED pool (speculation
    enabled) under a tracer session AND an armed cost-accounting
    session, so the dump previews every snapshot section — memory
    ledger, host time by cause, goodput, speculation counters, radix
    prefix-cache stats, cold-start report, traffic-shaping slo
    counters included — and return (snapshot, tracer). The workload
    shares an 8-token preamble so the prefix section shows a whole
    hit, a partial (pattach) hit, and misses; it runs class-tagged
    through a ShapingScheduler over a `prefill_chunk=4` pool so the
    slo section shows chunked prefills and per-class attainment."""
    import tempfile

    import numpy as np

    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.profiler import costs
    from paddle_tpu.serving import (AdapterPool, Request,
                                    ServingEngine, ShapingScheduler,
                                    session_scope)

    np.random.seed(0)
    layer = TransformerDecoderLayer(32, 2, 64, dropout=0.0)
    dec = TransformerDecoder(layer, 2)
    dec.eval()
    # a 2-tenant AdapterPool so the tenancy section renders too
    pool = AdapterPool(dec, capacity=3, rank=4)
    pool.register_random("t1", seed=1)
    pool.register_random("t2", seed=2)
    eng = ServingEngine(dec, nn.Embedding(17, 32), nn.Linear(32, 17),
                        num_slots=4, max_len=32, spec_k=4, paged=True,
                        page_size=4, num_pages=64, prefill_chunk=4,
                        adapters=pool, hbm_budget_bytes=1 << 20)
    sched = ShapingScheduler(max_queue=16, metrics=eng.metrics)
    rs = np.random.RandomState(1)
    memory = rs.randn(4, 32).astype("f4")
    pre = [0, 5, 9, 2, 11, 7, 3, 14]       # shared 8-token preamble
    prompts = [
        (pre + [6, 8], None, "batch"),     # cold CHUNKED prefill (miss)
        (pre + [6, 8], None, "interactive"),   # identical: whole hit
        (pre + [12, 4, 10], None, "batch"),    # shared 2 pages: partial
        (pre + [6, 8], "t1", "batch"),     # adapter subtree: miss
        ([0, 4, 13], "t2", "interactive"),     # unrelated: miss
        (pre + [6, 8], "t1", "batch"),     # adapter repeat: whole hit
    ]
    with costs.accounting_scope(), session_scope() as tr:
        # startup precompile into a throwaway AOT cache dir: the
        # cold_start section renders (and the serve below runs on the
        # precompiled programs — zero jit stalls, like production)
        eng.precompile((4, 32), dtype="float32",
                       prompt_buckets=(4, 16),
                       cache=tempfile.mkdtemp(prefix="pt_aot_demo_"))
        reqs = []
        for toks, name, slo in prompts:
            r = Request(np.asarray(toks, np.int32), memory,
                        max_new_tokens=int(rs.randint(2, 8)),
                        eos_id=1, adapter=name, slo=slo)
            sched.submit(r)
            reqs.append(r)
        eng.serve_until_idle(sched, max_iterations=500)
        for r in reqs:
            assert r.result(timeout=5).ok
        # snapshot INSIDE the scope so the compile-temp high-water of
        # the armed cost book lands in the memory section
        snap = eng.metrics.snapshot()
    return snap, tr


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--snapshot",
                    help="path to a json.dump'd metrics snapshot")
    ap.add_argument("--demo", action="store_true",
                    help="drive a tiny in-process pool and dump it")
    ap.add_argument("--prefix", default="paddle_tpu_serving")
    ap.add_argument("-o", "--out", help="write here instead of stdout")
    args = ap.parse_args(argv)

    from paddle_tpu.serving.metrics import to_prometheus

    tracer = None
    if args.demo:
        snap, tracer = _demo_snapshot()
    elif args.snapshot:
        with open(args.snapshot) as f:
            snap = json.load(f)
    else:
        ap.error("one of --snapshot or --demo is required")
    text = to_prometheus(snap, tracer=tracer, prefix=args.prefix)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"wrote {args.out} ({len(text.splitlines())} lines)")
    else:
        sys.stdout.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
