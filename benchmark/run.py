"""One cell of the benchmark, one process, one line out.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name: `benchmark/workloads/<cell>.json` names a
configuration (`configs/`), a traffic mix (`traffic/`); the configuration
names its builder (`builders/`) and its plain reference (`reference/`), the
mix its driver (`drivers/`). With `--trace 1` every per-layer metric whose
file under `layer_metrics/` lists this cell is read by the reader it names
(`readers/`). Adding a cell, a mix, a configuration or a metric is adding
files; nothing here lists what exists.

This is the only process that touches JAX. It never sets JAX_PLATFORMS and
never falls back: where the first device is not a TPU, or there are fewer
chips than the cell asks for, it exits non-zero with no result line.
`--rehearse` (for the tests under benchmark/tests only) runs the tiny
presets on the CPU and stamps the line `"platform": "cpu"`.

Set-up (`setup_s`) is process start to window open: imports, weights from
`--seed`, compile or cache read, the reference check where it can run before
the window, warm traffic. Nothing may compile inside the window: the run is
not `correct` if JAX's own compile counter moved between open and close.

The last line of standard output is the contract's object; every earlier
line is one JSON object of detail.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse      # noqa: E402
import contextlib    # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import util                      # noqa: E402
from benchmark.util import say                  # noqa: E402

#: run-time outputs (the profiler's trace) live here, inside the checkout;
#: the root .gitignore lists it
OUT_DIR = os.path.join(ROOT, ".benchmark_out")


class Run:
    """What a driver is handed: the cell's documents, the loaded builder and
    reference, the devices, and the window's bookkeeping. `facts` is what
    the per-layer readers read."""

    def __init__(self, args, cell, config, traffic, devices, counts):
        self.cell_name = args.workload
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.devices, self.counts = devices, counts
        self.builder = util.load_module("builders", config["builder"])
        self.reference = util.load_module("reference", config["reference"])
        self.facts = {"config": config, "traffic": traffic,
                      "chips": int(cell["chips"])}
        self.setup_s = None
        self._open_counts = self._close_counts = None

    def open_window(self):
        now = time.perf_counter()
        self.setup_s = now - T_PROCESS_START
        self._open_counts = self.counts.as_dict()
        say(window="open", setup_s=self.setup_s,
            compile_counts=self._open_counts)
        return now

    def close_window(self):
        now = time.perf_counter()
        self._close_counts = self.counts.as_dict()
        return now

    def compiles_in_window(self):
        return (self._close_counts["requests"]
                - self._open_counts["requests"])

    @contextlib.contextmanager
    def device_trace(self):
        """The JAX profiler around a steady slice AFTER the window, so that
        it costs the window's host-clock numbers nothing."""
        import jax

        log_dir = os.path.join(OUT_DIR, "trace", self.cell_name)
        shutil.rmtree(log_dir, ignore_errors=True)
        os.makedirs(log_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # it slows the host it observes
        opts.host_tracer_level = 2
        t0 = time.perf_counter()
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        t1 = time.perf_counter()
        try:
            yield
        finally:
            t2 = time.perf_counter()
            jax.profiler.stop_trace()
            t3 = time.perf_counter()
            say(device_trace=log_dir, start_s=t1 - t0, traced_s=t2 - t1,
                stop_s=t3 - t2)
            self.facts["trace_dir"] = log_dir


def reduce_trace(run):
    """facts["device"]: the reduction of the traced slice, or None with the
    reason on a line (the CPU rehearsal has no device plane)."""
    from benchmark import trace_reduce

    try:
        planes = trace_reduce.load_xplane(run.facts["trace_dir"])
        say(trace_planes=[{"plane": p["name"], "lines": [
            [ln["name"], len(ln["events"])] for ln in p["lines"]]}
            for p in planes])
        red = trace_reduce.reduce(planes)
    except (KeyError, ValueError, FileNotFoundError) as e:
        if not run.rehearse:
            raise
        say(device_trace_not_reduced=f"{type(e).__name__}: {e}")
        return None
    say(device_trace_reduced={k: red[k] for k in (
        "window_s", "busy_s", "idle_share", "devices")},
        top_ops=[[n, s, red["ops"][n][1]] for n, s in red["device_ops"]],
        idle_gaps=red["idle_gaps"], seconds_by_kind=red["by_kind"][:12],
        distinct_ops=len(red["ops"]))
    say(top_ops_whole_names=[red["full_names"][n][:600]
                             for n, _ in red["device_ops"]])
    return red


def layer_metrics(run):
    """{name: {"value", "unit"}} of every per-layer metric whose file lists
    this cell and whose reader found something to read."""
    out = {}
    for name in util.names_in("layer_metrics"):
        doc = util.load_json("layer_metrics", name + ".json")
        cells = doc.get("workloads")
        if cells is not None and run.cell_name not in cells:
            continue
        reader = util.load_module("readers", doc["reader"])
        value = reader.read(run.facts, **doc.get("args", {}))
        if value is None:
            say(layer_metric=doc["name"], left_out="nothing to read")
            continue
        out[doc["name"]] = {"value": float(value), "unit": doc["unit"]}
    return out


def acquire(need, rehearse, who):
    """Turn the compile cache on and take the devices: (devices, compile
    counts), or None with the reason on stderr where the first device is
    not what the run needs or there are too few. Never falls back."""
    import jax

    from paddle_tpu.core import compile_cache

    counts = compile_cache.enable()
    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want or len(devs) < need:
        print(f"benchmark: {who} needs {need} {want} device(s), jax found "
              f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})",
              file=sys.stderr)
        return None
    return devs[:need], counts


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny presets on the CPU, for benchmark/tests only")
    args = ap.parse_args(argv)

    cell, config, traffic = util.load_cell(args.workload, args.rehearse)
    driver = util.load_module("drivers", traffic["driver"])

    import jax

    got = acquire(int(cell["chips"]), args.rehearse,
                  f"cell {args.workload}")
    if got is None:
        return 2
    devs, counts = got
    say(benchmark="start", cell=args.workload, seed=args.seed,
        seconds=args.seconds, trace=args.trace, rehearsal=args.rehearse,
        jax=jax.__version__, device_kind=devs[0].device_kind,
        compile_cache_dir=jax.config.jax_compilation_cache_dir)

    run = Run(args, cell, config, traffic, devs, counts)
    if not args.rehearse:
        run.facts["peaks"] = util.peak_for(devs[0].device_kind)
    result = driver.run(run)

    in_window = run.compiles_in_window()
    say(check="no_compile_in_window", ok=in_window == 0,
        compile_requests_in_window=in_window,
        compile_counts_at_end=counts.as_dict())
    correct = bool(result["correct"]) and in_window == 0

    end_to_end = dict(result["end_to_end"], setup_s=run.setup_s)
    missing = set(cell["end_to_end"]) ^ set(end_to_end)
    if missing:
        raise RuntimeError(f"cell {args.workload} and its driver disagree "
                           f"on the end-to-end metrics: {sorted(missing)}")
    units = {m["name"]: m["unit"] for m in util.manifest()["end_to_end"]}
    run.facts.update(end_to_end=end_to_end, setup_s=run.setup_s,
                     compile_counts_at_open=run._open_counts)
    say(end_to_end=end_to_end, attempted=result["attempted"],
        failed=result["failed"])

    # The runtime's peak_bytes_in_use counts the buffers the process holds
    # (weights, optimizer state, pool, batches) and NOT the scratch a
    # compiled program allocates while it runs (PERF.md section 6, PR 24:
    # an ERNIE step at batch 256 read 1.89 GB, less than its activations
    # alone). The peak on the chip is the held buffers plus the largest
    # program's scratch, which XLA's own memory analysis states.
    held = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    scratch = int(run.facts.get("program_temp_bytes", 0))
    peak = held + scratch
    say(memory={"peak_bytes_in_use": int(held),
                "largest_program_temp_bytes": scratch,
                "memory_peak_bytes": int(peak)})
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        red = run.facts["device"] = reduce_trace(run)
        line["metrics"] = layer_metrics(run)
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            line["breakdown"] = {"device_ops": red["device_ops"],
                                 "idle_gaps": red["idle_gaps"]}
    else:
        line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                           for k, v in end_to_end.items()}
    line["device"] = device
    if args.rehearse:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
