#!/usr/bin/env python
"""Noise-aware perf-regression gate over the committed baselines.

Committed timings guarded only by hand-read JSON let a silent perf
regression ship. This gate
turns the committed `OP_BENCH.json` / `BENCH_DETAILS.json` baselines
into a standing assertion: re-measure a row set fresh, compare each
row against its baseline under a per-row relative tolerance
(median-of-k on the fresh side; the op harness itself medians pair
slopes), exit nonzero on regression, and write the full comparison as
`PERF_GATE.json` next to the baselines.

Row semantics:
  op rows     OP_BENCH.json `ops[name].step_us` — LOWER is better; a
              row regresses when fresh > tol x baseline.
  bench rows  BENCH_DETAILS.json `[metric].value` (the headline
              speedup/throughput) — HIGHER is better; a row regresses
              when fresh < baseline / tol. A baseline row inflated 2x
              (or a real 2x slowdown) fails under the default 1.5x
              tolerance.

Usage:
  python tools/perf_gate.py --quick            # 2-row op smoke (CI /
                                               # tier-1; seconds)
  python tools/perf_gate.py                    # default row set (op
                                               # quick-8; minutes)
  python tools/perf_gate.py --ops matmul,abs --bench fused_optimizer
  python tools/perf_gate.py --allow matmul     # tolerate named rows
  python tools/perf_gate.py --op-baseline alt.json --out gate.json

Noise discipline (1-core CPU box): fresh measurements are the MEDIAN
of k runs (--k, default 3); tolerances default loose (op 2x — the
scripts/ci.sh precedent — and bench 1.5x) and are per-row overridable
via --tol-op/--tol-bench. Allowlisted rows are still measured and
recorded, just not fatal — the paper trail survives in PERF_GATE.json.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

OP_BASELINE = os.path.join(REPO, "OP_BENCH.json")
BENCH_BASELINE = os.path.join(REPO, "BENCH_DETAILS.json")
GATE_OUT = os.path.join(REPO, "PERF_GATE.json")

#: the tier-1 smoke subset: two cheap, committed op rows (sub-ms
#: steps, sub-second compiles) so the gate ITSELF is exercised on
#: every CI run without denting the budget
QUICK_OPS = ("sequence_mask", "tile")

#: default full-run row set: the op harness's quick-8 plus the bench
#: rows cheap enough to re-measure in minutes (the serving rows are
#: wall-clock-shaped and re-anchored per PR instead)
DEFAULT_BENCH = ("fused_optimizer",)

#: speculative-decoding rows folded into the full-run default (PR 10):
#: one verify row and its plain-step pair, so a regression in the
#: k-token verify path (the spec hot kernel) fails the gate
SPEC_OPS = ("spec_decode_plain_b1_L2048",
            "spec_decode_verify_k4_b1_L2048",
            # the paged spec pair (PR 13): the paged decode step and
            # the paged k-token verify it widens into — a regression
            # in the block-table verify path fails the gate
            "paged_decode_b8_L2048_p16_f32",
            "paged_verify_k4_f32")

#: multi-tenant rows folded into the full-run default (PR 15): the
#: decode-shaped base linear and its adapter-carrying pair (the
#: step_us gap is the per-dispatch cost of carrying LoRA banks — a
#: regression here taxes EVERY multi-tenant decode step), plus the
#: int8-vs-f32 weight matmul row (paired in-row via measure_pair)
LORA_OPS = ("lora_base_b8", "lora_decode_r8_b8", "int8_matmul_vs_f32")

#: radix prefix-attach pair folded into the full-run default (PR 16):
#: the shallow and deep matched-depth attach rows (tail-only verify
#: attention through the clipped page table, measured paired in-row
#: against the same-depth whole-prompt prefill — the int8_matmul
#: precedent). step_us is the tail side, so a regression in the
#: pattach hot path — the thing every partial radix hit rides — fails
#: the gate even while the whole-prompt path stays fast
RADIX_OPS = ("prefix_attach_m4_t1", "prefix_attach_m16_t1")

#: zero-copy join rows folded into the full-run default (PR 17): the
#: dense slot splice and the paged page scatter, each measured paired
#: in-row DONATED vs undonated (measure_pair). step_us is the donated
#: side — the write every join in the family now dispatches — so a
#: regression in the in-place path fails the gate even if the old
#: copying path would have hidden it
JOIN_OPS = ("join_inplace_vs_copy_dense", "join_inplace_vs_copy_paged")

#: tuned-vs-fallback rows folded into the full-run default (PR 11):
#: the autotuned flash_decode config must NEVER be slower than the
#: hand-picked constants it replaced. Both sides are measured fresh,
#: PAIRED (op_bench.measure_pair — the only stable way to compare
#: sub-2x deltas on this 1-core box); no committed baseline involved.
#: On an untuned device the table resolves to the fallback itself, so
#: the row times the same config twice and trivially holds — the gate
#: only bites where a sweep actually installed a different config.
TUNING_ROWS = (("flash_decode", (64, 2048, "float32")),)


# ----------------------------------------------------------------------
# pure comparison core (unit-tested directly; no measurement involved)
# ----------------------------------------------------------------------

def evaluate_row(direction, baseline, fresh, tol):
    """One row's verdict: "pass" or "regress". `tol` is a ratio > 1;
    "lower" rows regress when fresh > tol * baseline, "higher" rows
    when fresh < baseline / tol."""
    if direction not in ("lower", "higher"):
        raise ValueError(f"direction must be lower|higher: {direction}")
    if tol <= 1.0:
        raise ValueError(f"tol must be > 1, got {tol}")
    if baseline is None or fresh is None or baseline <= 0:
        return "missing"
    if direction == "lower":
        return "regress" if fresh > tol * baseline else "pass"
    return "regress" if fresh < baseline / tol else "pass"


def gate(rows, allowlist=()):
    """Apply verdicts + the allowlist to measured rows. Each row dict
    needs {name, direction, baseline, fresh, tol}; rows missing either
    side get status "missing-row" (fatal: a silently vanished baseline
    row must not pass as green). Returns the PERF_GATE.json payload."""
    allow = set(allowlist)
    out_rows = []
    regressions = []
    missing = []
    for r in rows:
        row = dict(r)
        verdict = evaluate_row(r["direction"], r.get("baseline"),
                               r.get("fresh"), r["tol"])
        if verdict == "missing":
            row["status"] = "missing-row"
            missing.append(r["name"])
        elif verdict == "regress" and r["name"] in allow:
            row["status"] = "allowlisted"
        elif verdict == "regress":
            row["status"] = "regress"
            regressions.append(r["name"])
        else:
            row["status"] = "pass"
        b, f = r.get("baseline"), r.get("fresh")
        if b and f:
            row["ratio"] = round(f / b, 4)
        out_rows.append(row)
    return {"rows": out_rows,
            "regressions": regressions,
            "missing": missing,
            "ok": not regressions and not missing}


# ----------------------------------------------------------------------
# fresh measurement
# ----------------------------------------------------------------------

def measure_op(name, k=3, quiet=True):
    """Median-of-k fresh step_us for one op_bench config."""
    import op_bench

    cfgs = {c[0]: c[1:] for c in op_bench._configs()}
    if name not in cfgs:
        return None
    builder, *rest = cfgs[name]
    opts = rest[0] if rest else {}
    vals = []
    for _ in range(int(k)):
        if getattr(builder, "_direct", False):
            r = builder()
        else:
            r = op_bench.bench_one(name, builder, **opts)
        if "step_us" not in r:
            return None
        vals.append(float(r["step_us"]))
        if not quiet:
            print(f"  {name}: {r['step_us']}us", file=sys.stderr)
    return statistics.median(vals)


def measure_bench(metric, k=1, quiet=True):
    """Median-of-k fresh headline `value` for one bench.py config."""
    import bench

    fn = dict([
        ("mnist", bench._mnist_static), ("resnet50", bench._resnet50),
        ("ernie", bench._ernie), ("ctr_ps", bench._ctr_dnn_ps),
        ("long_context", bench._long_context_attention),
        ("ernie_long", bench._ernie_long),
        ("packed_varlen", bench._packed_varlen),
        ("fused_optimizer", bench._fused_optimizer),
        ("decode_throughput", bench._decode_throughput),
        ("cold_start", bench._cold_start),
        ("serving_throughput", bench._serving_throughput),
        ("serving_paged", bench._serving_paged),
        ("serving_radix", bench._serving_radix),
        ("serving_slo", bench._serving_slo),
        ("serving_sharded", bench._serving_sharded),
    ]).get(metric)
    if fn is None:
        return None
    vals = []
    for _ in range(int(k)):
        r = fn()
        if "value" not in r:
            return None
        vals.append(float(r["value"]))
        if not quiet:
            print(f"  {metric}: {r['value']}", file=sys.stderr)
    return statistics.median(vals)


def measure_tuning_row(kernel, key, *, steps=12, k=5, batch=4,
                       heads=4, quiet=True):
    """(fallback_s, tuned_s) for one tuning-table row, measured PAIRED
    via op_bench.measure_pair over the real dispatch path. The tuned
    side is whatever the active table resolves for (kernel, key) on
    this device (the fallback itself when untuned)."""
    import op_bench

    from paddle_tpu.tuning import autotune as AT
    from paddle_tpu.tuning import table as TBL

    fb = AT.fallback_config(kernel, key)
    tuned = TBL.lookup(kernel, key) or fb
    tuned = {kk: tuned[kk] for kk in TBL.KERNEL_KNOBS[kernel]
             if kk in tuned} or fb
    run_fb = AT.build_runner(kernel, key, fb, batch, heads)
    run_tuned = AT.build_runner(kernel, key, tuned, batch, heads)
    dt_fb, dt_tuned = op_bench.measure_pair(run_fb, run_tuned,
                                            steps=steps, k=k)
    if not quiet:
        print(f"  tuning:{kernel}:{TBL.key_str(key)} fallback "
              f"{dt_fb * 1e6:.1f}us ({fb}) tuned "
              f"{dt_tuned * 1e6:.1f}us ({tuned})", file=sys.stderr)
    return dt_fb, dt_tuned


def build_tuning_rows(tuning_rows, tol, k=5, quiet=True,
                      measure=measure_tuning_row):
    """Tuned-config-never-slower rows: baseline = the hand-picked
    fallback's PAIRED measurement, fresh = the tuned config's —
    direction 'lower', so a tuned entry slower than the constants it
    replaced regresses. `measure` is injectable for unit tests."""
    rows = []
    for kernel, key in tuning_rows:
        name = "tuning:" + kernel + ":" + "/".join(str(x) for x in key)
        try:
            dt_fb, dt_tuned = measure(kernel, key, k=k, quiet=quiet)
        except Exception as e:
            rows.append({"name": name, "direction": "lower",
                         "unit": "paired_us", "tol": tol,
                         "baseline": None, "fresh": None,
                         "error": f"{type(e).__name__}: {e}"})
            continue
        rows.append({"name": name, "direction": "lower",
                     "unit": "paired_us", "tol": tol,
                     "baseline": round(dt_fb * 1e6, 2),
                     "fresh": round(dt_tuned * 1e6, 2)})
    return rows


def build_rows(op_names, bench_names, op_base, bench_base, tol_op,
               tol_bench, k, quiet=True):
    """Measure every selected row fresh and pair it with its
    baseline."""
    rows = []
    for name in op_names:
        b = (op_base.get("ops", {}).get(name, {}) or {}).get("step_us")
        rows.append({"name": f"op:{name}", "direction": "lower",
                     "unit": "step_us", "tol": tol_op,
                     "baseline": float(b) if b else None,
                     "fresh": measure_op(name, k=k, quiet=quiet)})
    for name in bench_names:
        b = (bench_base.get(name, {}) or {}).get("value")
        rows.append({"name": f"bench:{name}", "direction": "higher",
                     "unit": "value", "tol": tol_bench,
                     "baseline": float(b) if b else None,
                     "fresh": measure_bench(name, k=max(1, k // 3 or 1),
                                            quiet=quiet)})
    return rows


def run_gate(op_names=(), bench_names=(), *, op_baseline=OP_BASELINE,
             bench_baseline=BENCH_BASELINE, tol_op=2.0, tol_bench=1.5,
             k=3, allowlist=(), out=GATE_OUT, quiet=True,
             tuning_rows=(), tol_tuning=1.5):
    """Measure, compare, persist. Returns the gate payload (and writes
    it to `out`); callers decide the exit code from payload["ok"]."""

    def _load(path):
        try:
            with open(path) as f:
                return json.load(f)
        except Exception:
            return {}

    op_base = _load(op_baseline)
    bench_base = _load(bench_baseline)
    rows = build_rows(op_names, bench_names, op_base, bench_base,
                      tol_op, tol_bench, k, quiet=quiet)
    rows += build_tuning_rows(tuning_rows, tol_tuning, k=max(3, k),
                              quiet=quiet)
    payload = gate(rows, allowlist)
    payload["config"] = {
        "op_baseline": os.path.abspath(op_baseline),
        "bench_baseline": os.path.abspath(bench_baseline),
        "backend": op_base.get("backend"),
        "tol_op": tol_op, "tol_bench": tol_bench,
        "tol_tuning": tol_tuning, "k": k,
        "allowlist": sorted(allowlist)}
    if out:
        with open(out, "w") as f:
            json.dump(payload, f, indent=1)
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help=f"2-row op smoke {QUICK_OPS} with a loose "
                         f"(4x) tolerance — the CI/tier-1 invocation")
    ap.add_argument("--ops", default=None,
                    help="comma-separated op_bench rows")
    ap.add_argument("--bench", default=None,
                    help="comma-separated bench.py rows")
    ap.add_argument("--k", type=int, default=3,
                    help="fresh-side median-of-k (bench rows use "
                         "max(1, k//3))")
    ap.add_argument("--tol-op", type=float, default=2.0)
    ap.add_argument("--tol-bench", type=float, default=1.5)
    ap.add_argument("--tol-tuning", type=float, default=1.5)
    ap.add_argument("--tuning", default=None,
                    help="comma-separated tuning rows KERNEL:d/L/dtype"
                         " (default: the TUNING_ROWS set on full "
                         "runs; 'none' to skip)")
    ap.add_argument("--allow", default="",
                    help="comma-separated row names (op:NAME / "
                         "bench:NAME) that may regress without "
                         "failing the gate")
    ap.add_argument("--op-baseline", default=OP_BASELINE)
    ap.add_argument("--bench-baseline", default=BENCH_BASELINE)
    ap.add_argument("--out", default=GATE_OUT)
    ap.add_argument("--cpu", action="store_true",
                    help="pin to the CPU jax backend")
    args = ap.parse_args(argv)
    if args.cpu:
        import _cpu_debug  # noqa: F401

    if args.quick:
        op_names = list(QUICK_OPS)
        bench_names = []
        tuning_rows = []
        if args.tol_op == 2.0:
            # micro-second rows on a timeshared core need headroom;
            # the quick gate is a smoke of the MACHINERY, the full run
            # keeps the tight default
            args.tol_op = 4.0
    else:
        op_names = ([c[0] for c in _quick8()] + list(SPEC_OPS)
                    + list(LORA_OPS)
                    + list(RADIX_OPS)
                    + list(JOIN_OPS)) if args.ops is None else []
        bench_names = list(DEFAULT_BENCH) if args.bench is None else []
        tuning_rows = list(TUNING_ROWS)
    if args.ops is not None:
        op_names = [s for s in args.ops.split(",") if s]
    if args.bench is not None:
        bench_names = [s for s in args.bench.split(",") if s]
    if args.tuning is not None:
        tuning_rows = [] if args.tuning == "none" else [
            (s.split(":")[0], tuple(
                int(p) if p.isdigit() else p
                for p in s.split(":")[1].split("/")))
            for s in args.tuning.split(",") if s]

    payload = run_gate(
        op_names, bench_names, op_baseline=args.op_baseline,
        bench_baseline=args.bench_baseline, tol_op=args.tol_op,
        tol_bench=args.tol_bench, k=args.k,
        allowlist=[s for s in args.allow.split(",") if s],
        out=args.out, quiet=False, tuning_rows=tuning_rows,
        tol_tuning=args.tol_tuning)
    for r in payload["rows"]:
        print(f"{r['status']:>12}  {r['name']:<28} "
              f"baseline={r.get('baseline')} fresh={r.get('fresh')} "
              f"ratio={r.get('ratio')} tol={r['tol']}",
              file=sys.stderr)
    for name in payload["regressions"]:
        print(f"REGRESSION {name}", file=sys.stderr)
    for name in payload["missing"]:
        print(f"MISSING ROW {name}", file=sys.stderr)
    print(json.dumps({"ok": payload["ok"],
                      "regressions": payload["regressions"],
                      "missing": payload["missing"],
                      "out": args.out}))
    return 0 if payload["ok"] else 1


def _quick8():
    import op_bench

    return op_bench._configs()[:8]


if __name__ == "__main__":
    sys.exit(main())
