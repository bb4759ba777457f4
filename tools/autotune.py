#!/usr/bin/env python
"""Kernel autotune CLI: sweep pallas block configs, persist winners.

Front end over `paddle_tpu.tuning.autotune`: enumerate candidate
configs per (kernel, head_dim, seq bucket, dtype) key, time each with
the shared `tools/op_bench.measure` harness, prune candidates whose
analytic roofline floor (profiler.costs.DeviceSpec) already exceeds
the incumbent, and record winners keyed by this host's device_kind.

Usage:
  python tools/autotune.py --sweep flash_decode          # one kernel
  python tools/autotune.py --sweep all --out /tmp/t.json # everything
  python tools/autotune.py --smoke --dry-run             # CI smoke:
                                   # tiny key set, winners printed,
                                   # nothing written (scripts/ci.sh)
  python tools/autotune.py --sweep flash_decode --merge  # fold the
                                   # winners into the COMMITTED table
                                   # (paddle_tpu/tuning/tables/
                                   # default.json) under this device
  python tools/autotune.py --init  # regenerate the committed
                                   # fallback tier from the hand-
                                   # picked heuristics ('any' entries)
  python tools/autotune.py --show  # render the active table

Run sweeps alone on the chip: it belongs to one process at a time. On
CPU the decode/verify dispatchers run their reference
composition (config-invariant), so a CPU sweep only proves mechanics —
real block wins need the device; the committed 'any' tier keeps
untuned devices bit-identical to the hand-picked constants either way.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _log(msg):
    print(msg, file=sys.stderr)


#: --smoke: the CI key set — one cheap key per sweep-worthy kernel
#: family, small enough for seconds on the CPU pin
SMOKE_KEYS = {
    "flash_decode": [(64, 512, "float32")],
    "int8_matmul": [(256, 256, "float32")],
    "lora_matmul": [(256, 8, "float32")],
}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--sweep", default=None,
                    help="comma-separated kernels (or 'all'): "
                         "flash_fwd,flash_bwd,flash_decode,"
                         "flash_verify,paged_flash_decode")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI key set (flash_decode d64/L512) "
                         "with a short measurement budget")
    ap.add_argument("--dry-run", action="store_true",
                    help="print winners, write nothing")
    ap.add_argument("--merge", action="store_true",
                    help="fold winners into the committed default "
                         "table (device-keyed) instead of --out")
    ap.add_argument("--out", default=None,
                    help="write the swept table here (default: print)")
    ap.add_argument("--init", action="store_true",
                    help="regenerate the committed fallback tier from "
                         "the hand-picked heuristics")
    ap.add_argument("--show", action="store_true",
                    help="render the active table and exit")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20,
                    help="measurement scan length per candidate")
    ap.add_argument("--k", type=int, default=5,
                    help="median-of-k pair slopes per candidate")
    ap.add_argument("--cpu", action="store_true",
                    help="pin to the virtual-CPU jax backend")
    args = ap.parse_args(argv)
    if args.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import _cpu_debug  # noqa: F401

    from paddle_tpu.tuning import autotune as AT
    from paddle_tpu.tuning import table as TBL

    if args.show:
        t = TBL.get_table()
        rows = t.entries() if t is not None else []
        for dev, kern, key, cfg in rows:
            print(f"{dev:12s} {kern:20s} {key:32s} {json.dumps(cfg)}")
        print(f"# {len(rows)} entries "
              f"(device tier: {TBL.current_device_kind()!r})")
        return 0

    if args.init:
        tbl = TBL.TuningTable()
        try:
            tbl.merge(TBL.TuningTable.load(TBL.committed_table_path()))
        except TBL.TableError:
            pass
        for kernel, key, cfg in AT.fallback_entries():
            tbl.put(kernel, key, cfg, device_kind="any")
        tbl.save(TBL.committed_table_path())
        _log(f"wrote {len(tbl)} entries -> "
             f"{TBL.committed_table_path()}")
        return 0

    if not args.sweep and not args.smoke:
        ap.error("one of --sweep/--smoke/--init/--show is required")

    if args.smoke:
        keysets = dict(SMOKE_KEYS)
        args.steps = min(args.steps, 10)
        args.k = min(args.k, 3)
    else:
        kernels = (list(AT.DEFAULT_KEYS) if args.sweep == "all"
                   else args.sweep.split(","))
        keysets = {}
        for kern in kernels:
            if kern not in AT.DEFAULT_KEYS:
                ap.error(f"unknown kernel {kern!r}")
            keysets[kern] = AT.DEFAULT_KEYS[kern]

    measurer = AT.default_measurer(batch=args.batch, heads=args.heads,
                                   steps=args.steps, k=args.k)
    device = TBL.current_device_kind()
    swept = TBL.TuningTable()
    reports = []
    for kernel, keys in keysets.items():
        for key in keys:
            _log(f"sweep {kernel} {TBL.key_str(key)} "
                 f"({len(AT.candidates(kernel, key))} candidates)")
            rep = AT.sweep_key(kernel, key, measurer=measurer,
                               batch=args.batch, heads=args.heads,
                               log=_log)
            reports.append(rep)
            AT.apply_report(swept, rep, device_kind=device)
            _log(f"  winner {rep['winner']} {rep['step_us']}us "
                 f"(fallback {rep['fallback']} {rep['fallback_us']}us,"
                 f" timed {rep['timed']}, pruned {rep['pruned']})")

    print(json.dumps({"device_kind": device, "swept": len(reports),
                      "winners": reports}, indent=1))
    if args.dry_run:
        _log("dry run: nothing written")
        return 0
    if args.merge:
        target = TBL.committed_table_path()
        tbl = TBL.TuningTable()
        try:
            tbl.merge(TBL.TuningTable.load(target))
        except TBL.TableError:
            pass
        tbl.merge(swept)
        tbl.save(target)
        _log(f"merged {len(reports)} winners into {target}")
    elif args.out:
        swept.save(args.out)
        _log(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
