"""Block-shape sweep driver for the pallas kernels.

TVM's conclusion (PAPERS.md) — searched tile selection beats
hand-picked tiles by integer factors — applied to this repo's four
kernel families. For each (kernel, head_dim, seq bucket, dtype) key
the sweep:

  1. enumerates the legal candidate configs (`candidates()`: block
     pairs that tile the sequence, split factors that keep lane-
     friendly 128-multiples — the same legality gates the kernels
     enforce);
  2. prunes candidates whose ANALYTIC roofline lower bound
     (`analytic_cost()` flops/bytes against the `DeviceSpec` peaks —
     causal block-granularity overshoot included) already exceeds the
     incumbent's measured time: a candidate that cannot win is never
     timed;
  3. times the survivors with the shared `tools/op_bench.measure`
     harness (median-of-k pair slopes, the 1-core-box discipline);
  4. stops early once the incumbent sits within `stop_factor` of the
     key's roofline — the DeviceSpec peak is the sweep's floor;
  5. records the winner (config + step_us + source="sweep") for
     `TuningTable.put`, keyed by device_kind.

`fallback_config()` reproduces the hand-picked constants the kernels
used before tuning existed; the committed default table is GENERATED
from it (`fallback_entries()`), which is what makes the tuned-off and
untuned-device paths bit-identical to the old kernels — pinned by
tests/test_tuning.py.
"""
from __future__ import annotations

import math

from . import table as _table

__all__ = ["candidates", "fallback_config", "fallback_entries",
           "analytic_cost", "roofline_seconds", "prune", "sweep_key",
           "build_runner", "default_measurer", "apply_report",
           "DEFAULT_KEYS"]

#: block-size ladder the fwd/bwd sweep draws from (the v5e sweep of
#: tools/tune_flash.py measured over exactly this set)
BLOCK_LADDER = (128, 256, 384, 512)
#: split-K ladder for the decode/verify kernels
SPLIT_LADDER = (1, 2, 4, 8, 16)


def _op_bench():
    """tools/op_bench.py as a module (tools/ is not a package; the
    repo's tests/tools import it by path the same way)."""
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import op_bench

    return op_bench


# ----------------------------------------------------------------------
# keys, candidates, fallbacks
# ----------------------------------------------------------------------

def _dims_of(kernel, key):
    """Parse a key tuple back into named dims. Key layouts (every seq
    component pre-bucketed by the caller):

        flash_fwd/bwd       (d, sq, sk, dtype)
        flash_decode        (d, L, dtype)
        flash_verify        (d, L, dtype, T)
        paged_flash_decode  (d, psz, dtype)
        paged_flash_verify  (d, psz, dtype, T)
        int8_matmul         (d, n, dtype)   d = contraction bucket,
                                            n = output-channel bucket
        lora_matmul         (d, r, dtype)   d = model-dim bucket,
                                            r = adapter rank
    """
    if kernel in ("flash_fwd", "flash_bwd"):
        d, sq, sk, dt = key
        return {"d": int(d), "sq": int(sq), "sk": int(sk),
                "dtype": str(dt)}
    if kernel == "flash_decode":
        d, L, dt = key
        return {"d": int(d), "L": int(L), "dtype": str(dt)}
    if kernel == "flash_verify":
        d, L, dt, T = key
        return {"d": int(d), "L": int(L), "dtype": str(dt),
                "T": int(T)}
    if kernel == "paged_flash_decode":
        d, psz, dt = key
        return {"d": int(d), "psz": int(psz), "dtype": str(dt)}
    if kernel == "paged_flash_verify":
        d, psz, dt, T = key
        return {"d": int(d), "psz": int(psz), "dtype": str(dt),
                "T": int(T)}
    if kernel == "int8_matmul":
        d, n, dt = key
        return {"d": int(d), "n": int(n), "dtype": str(dt)}
    if kernel == "lora_matmul":
        d, r, dt = key
        return {"d": int(d), "r": int(r), "dtype": str(dt)}
    raise ValueError(f"unknown kernel {kernel!r}")


def candidates(kernel, key):
    """Legal configs for (kernel, key) — the kernels' own tiling gates
    applied up front so the sweep never times an unbuildable config."""
    dims = _dims_of(kernel, key)
    if kernel in ("flash_fwd", "flash_bwd"):
        sq, sk = dims["sq"], dims["sk"]
        out = []
        for bq in BLOCK_LADDER:
            for bk in BLOCK_LADDER:
                if sq % min(bq, sq) == 0 and sk % min(bk, sk) == 0:
                    out.append({"block_q": bq, "block_k": bk})
        return out
    if kernel in ("flash_decode", "flash_verify"):
        L = dims["L"]
        return [{"split_k": n} for n in SPLIT_LADDER
                if L % n == 0 and (L // n) % 128 == 0]
    if kernel == "paged_flash_decode":
        # dispatch-level knob only: the grid is (slot, block of pages),
        # the block from the shapes (`_paged_block_pages`)
        return [{"kernel": True}, {"kernel": False}]
    if kernel == "paged_flash_verify":
        # the kernel grid is fixed by the pages, so kernel-on has no
        # block freedom; kernel-off falls back to gather + the dense
        # verify dispatch, whose split_k ladder IS tunable (legality
        # at the nominal 8-mapped-pages logical length)
        L = dims["psz"] * 8
        return [{"kernel": True, "split_k": 0}] + \
            [{"kernel": False, "split_k": n} for n in SPLIT_LADDER
             if L % n == 0 and (L // n) % 128 == 0]
    if kernel == "int8_matmul":
        # tile ladder at the nominal decode-batch m (ops.quant's
        # INT8_BLOCK_* sets); legality = the tile divides the bucket
        from ..ops.quant import INT8_BLOCK_M, INT8_BLOCK_N

        n = dims["n"]
        return [{"block_m": bm, "block_n": bn}
                for bm in INT8_BLOCK_M for bn in INT8_BLOCK_N
                if n % bn == 0]
    if kernel == "lora_matmul":
        # dispatch-level knob only: the gathered grid is (slot,)
        return [{"kernel": True}, {"kernel": False}]
    raise ValueError(f"unknown kernel {kernel!r}")


def fallback_config(kernel, key):
    """The hand-picked constants the kernels shipped with — what an
    untuned device (or PT_TUNING=0) uses, verbatim. Mirrors
    `ops/attention.py`'s heuristics via their own functions, so the
    two can never drift."""
    from ..ops import attention as A

    dims = _dims_of(kernel, key)
    if kernel in ("flash_fwd", "flash_bwd"):
        bq, bk = A._pick_blocks_heuristic(dims["sq"], dims["sk"])
        return {"block_q": bq, "block_k": bk}
    if kernel in ("flash_decode", "flash_verify"):
        return {"split_k": A._pick_decode_splits_heuristic(dims["L"])}
    if kernel == "paged_flash_decode":
        return {"kernel": True}
    if kernel == "paged_flash_verify":
        return dict(A._paged_verify_heuristic())
    if kernel == "int8_matmul":
        from ..ops import quant as Q

        bm, bn = Q._pick_int8_blocks_heuristic(8, dims["n"])
        return {"block_m": bm, "block_n": bn}
    if kernel == "lora_matmul":
        from ..ops import quant as Q

        return dict(Q._lora_dispatch_heuristic())
    raise ValueError(f"unknown kernel {kernel!r}")


#: the key grid the committed fallback table covers: every decode-pool
#: shape the engines bucket to, plus the training seq lengths the
#: benches exercise
DEFAULT_KEYS = {
    "flash_fwd": [(d, s, s, dt)
                  for d in (64, 128) for s in (512, 1024, 2048, 4096)
                  for dt in ("float32", "bfloat16")],
    "flash_bwd": [(d, s, s, dt)
                  for d in (64, 128) for s in (512, 1024, 2048, 4096)
                  for dt in ("float32", "bfloat16")],
    "flash_decode": [(d, L, dt)
                     for d in (64, 128) for L in (512, 2048, 8192)
                     for dt in ("float32", "bfloat16")],
    "flash_verify": [(d, L, dt, T)
                     for d in (64, 128) for L in (512, 2048)
                     for dt in ("float32", "bfloat16")
                     for T in (2, 4, 8)],
    "paged_flash_decode": [(d, psz, dt)
                           for d in (64, 128) for psz in (16, 64)
                           for dt in ("float32", "int8")],
    "paged_flash_verify": [(d, psz, dt, T)
                           for d in (64, 128) for psz in (16, 64)
                           for dt in ("float32", "int8")
                           for T in (2, 4)],
    "int8_matmul": [(d, n, dt)
                    for d in (256, 1024) for n in (256, 1024)
                    for dt in ("float32", "bfloat16")],
    "lora_matmul": [(d, r, "float32")
                    for d in (256, 1024) for r in (8, 32)],
}


def fallback_entries():
    """[(kernel, key, config)] rows for the committed default table:
    every DEFAULT_KEYS key mapped to its hand-picked constants with
    source='fallback'. tools/autotune.py --init writes these."""
    out = []
    for kernel, keys in DEFAULT_KEYS.items():
        for key in keys:
            cfg = dict(fallback_config(kernel, key))
            cfg["source"] = "fallback"
            out.append((kernel, key, cfg))
    return out


# ----------------------------------------------------------------------
# analytic roofline (the prune + the stop condition)
# ----------------------------------------------------------------------

def _dtype_bytes(dt):
    import numpy as np

    try:
        return np.dtype(dt).itemsize
    except TypeError:
        return 4


def analytic_cost(kernel, key, config, batch=1, heads=1, causal=True):
    """{flops, bytes} LOWER BOUND for one kernel invocation under
    `config`: the matmul work over the blocks the grid actually
    visits. Block granularity is the point — a causal sweep with big
    key blocks visits (and masks) more dead positions, so its floor
    rises; that is exactly what the prune compares."""
    dims = _dims_of(kernel, key)
    d = dims["d"]
    ib = _dtype_bytes(dims["dtype"])
    bh = batch * heads
    if kernel in ("flash_fwd", "flash_bwd"):
        sq, sk = dims["sq"], dims["sk"]
        bq = min(int(config["block_q"]), sq)
        bk = min(int(config["block_k"]), sk)
        nq = sq // bq
        pairs = 0
        for qi in range(nq):
            if causal and sq == sk:
                pairs += min(math.ceil((qi + 1) * bq / bk), sk // bk)
            else:
                pairs += sk // bk
        # QK^T + PV per visited pair (x2.5 for the bwd's dq/dk/dv
        # recompute stack)
        mm = 4.0 * bq * bk * d * pairs
        if kernel == "flash_bwd":
            mm *= 2.5
        byt = (sq * d + pairs * 2.0 * bk * d) * ib
        return {"flops": bh * mm, "bytes": bh * byt}
    if kernel in ("flash_decode", "flash_verify"):
        L = dims["L"]
        T = dims.get("T", 1)
        n = int(config["split_k"])
        # every split reads its K/V slice; the XLA combine touches
        # n * (T, d) partials
        flops = bh * (4.0 * T * L * d + n * T * (2.0 * d + 8.0))
        byt = bh * (2.0 * L * d * ib + n * T * (d + 2) * 4.0)
        return {"flops": flops, "bytes": byt}
    if kernel == "paged_flash_decode":
        psz = dims["psz"]
        L = psz * 8  # nominal 8 mapped pages; relative cost only
        gather = 0.0 if config.get("kernel", True) else 2.0 * L * d * ib
        return {"flops": bh * 4.0 * L * d,
                "bytes": bh * (2.0 * L * d * ib + gather)}
    if kernel == "paged_flash_verify":
        psz, T = dims["psz"], dims["T"]
        L = psz * 8  # nominal 8 mapped pages; relative cost only
        gather = 0.0 if config.get("kernel", True) else 2.0 * L * d * ib
        return {"flops": bh * 4.0 * T * L * d,
                "bytes": bh * (2.0 * L * d * ib + gather)}
    if kernel == "int8_matmul":
        # nominal decode-batch m = 8 rows; the int8 weight tile is the
        # byte-traffic floor (the whole point of the storage format)
        n = dims["n"]
        m = 8
        return {"flops": bh * 2.0 * m * d * n,
                "bytes": bh * (d * n * 1.0 + n * 4.0 +
                               m * (d + n) * ib)}
    if kernel == "lora_matmul":
        # nominal 8-slot pool, one token per row: two rank-r matmuls
        # per row + the gathered bank rows' traffic
        r = dims["r"]
        m = 8
        gather = 0.0 if config.get("kernel", True) \
            else m * (d * r + r * d) * 4.0
        return {"flops": bh * 2.0 * m * (d * r + r * d),
                "bytes": bh * (m * (d * r + r * d) * 4.0 + gather)}
    raise ValueError(f"unknown kernel {kernel!r}")


def roofline_seconds(cost, spec):
    """The device's floor for a {flops, bytes} cost: compute-bound or
    bandwidth-bound, whichever binds."""
    return max(cost["flops"] / spec.peak_flops,
               cost["bytes"] / spec.peak_bytes_per_s)


def prune(kernel, key, cands, incumbent_s, spec, batch=1, heads=1):
    """Split candidates into (survivors, pruned): a candidate whose
    roofline floor already exceeds the incumbent's MEASURED time can
    never win and is never timed."""
    if incumbent_s is None:
        return list(cands), []
    keep, cut = [], []
    for c in cands:
        floor = roofline_seconds(
            analytic_cost(kernel, key, c, batch, heads), spec)
        (cut if floor > incumbent_s else keep).append(c)
    return keep, cut


# ----------------------------------------------------------------------
# measurement + the sweep driver
# ----------------------------------------------------------------------

def build_runner(kernel, key, config, batch=4, heads=4):
    """Zero-arg timed closure for one (kernel, key, config): jits the
    REAL dispatch path under the candidate config over fixed random
    operands. The sweep measures it with op_bench.measure; the perf
    gate's tuned-vs-fallback rows measure two of these PAIRED with
    op_bench.measure_pair. On non-TPU backends the decode/verify
    dispatchers run their XLA reference (config-invariant there) —
    mechanics still exercise end to end; real block wins need the
    chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import attention as A

    dims = _dims_of(kernel, key)
    d = dims["d"]
    dt = jnp.dtype(dims["dtype"]) if dims["dtype"] != "int8" \
        else jnp.float32
    rs = np.random.RandomState(0)
    if kernel in ("flash_fwd", "flash_bwd"):
        sq, sk = dims["sq"], dims["sk"]
        q = jnp.asarray(rs.randn(batch, heads, sq, d), dt)
        kv = jnp.asarray(rs.randn(batch, heads, sk, d), dt)
        interp = not A._on_tpu()
        bq = min(int(config["block_q"]), sq)
        bk = min(int(config["block_k"]), sk)

        if kernel == "flash_fwd":
            fn = jax.jit(lambda a, b, c: A.flash_attention_fwd(
                a, b, c, None, True, None, bq, bk, interp)[0])
            return lambda: fn(q, kv, kv)
        g = jax.jit(jax.grad(
            lambda a, b, c: A.flash_attention(
                a, b, c, None, True, None, interp, bq, bk)
            .astype(jnp.float32).sum(), (0, 1, 2)))
        return lambda: g(q, kv, kv)
    if kernel in ("flash_decode", "flash_verify"):
        L, T = dims["L"], dims.get("T", 1)
        q = jnp.asarray(rs.randn(batch, heads, T, d), dt)
        kv = jnp.asarray(rs.randn(batch, heads, L, d), dt)
        length = jnp.full((batch,), L, jnp.int32)
        disp = A.verify_attention if kernel == "flash_verify" \
            else A.decode_attention
        fn = jax.jit(lambda a, b, c, n: disp(
            a, b, c, n, split_k=int(config["split_k"])))
        return lambda: fn(q, kv, kv, length)
    if kernel == "paged_flash_verify":
        psz, T = dims["psz"], dims["T"]
        n_pages, mp = 32, 8
        q = jnp.asarray(rs.randn(batch, heads, T, d), jnp.float32)
        pages = jnp.asarray(
            rs.randn(n_pages + 1, psz, heads * d), jnp.float32)
        tbl = jnp.asarray(
            rs.randint(0, n_pages, (batch, mp)), jnp.int32)
        length = jnp.full((batch,), mp * psz, jnp.int32)
        use_kernel = bool(config.get("kernel", True)) and \
            A._on_tpu()   # off-chip, both rows time the gather
        #                   fallback (interpret mode would time the
        #                   emulator, not the kernel)
        if use_kernel:
            fn = jax.jit(lambda a, kp, vp, t, n: A.paged_flash_verify(
                a, kp, vp, None, None, t, n))
        else:
            split = int(config.get("split_k", 0)) or None
            fn = jax.jit(lambda a, kp, vp, t, n: A.verify_attention(
                a, A.paged_gather_kv(kp, None, t, heads, a.dtype),
                A.paged_gather_kv(vp, None, t, heads, a.dtype), n,
                split_k=split))
        return lambda: fn(q, pages, pages, tbl, length)
    if kernel == "int8_matmul":
        from ..ops import quant as Q

        n = dims["n"]
        m = max(8, batch)
        x = jnp.asarray(rs.randn(m, d), dt)
        w = jnp.asarray(rs.randn(d, n) * 0.05, jnp.float32)
        wq, ws = Q.quantize_int8_weight(w)
        bm = int(config.get("block_m", 0)) or None
        bn = int(config.get("block_n", 0)) or None
        # on the CPU harness the dispatcher times the XLA reference
        # (config-invariant); on-chip the explicit blocks pin the
        # candidate tile, same contract as the flash runners
        fn = jax.jit(lambda a, q_, s_: Q.int8_matmul(
            a, q_, s_, block_m=bm, block_n=bn))
        return lambda: fn(x, wq, ws)
    if kernel == "lora_matmul":
        from ..ops import quant as Q

        r = dims["r"]
        n_ad = 4
        x = jnp.asarray(rs.randn(batch, 1, d), dt)
        Ab = jnp.asarray(rs.randn(n_ad, d, r) * 0.05, jnp.float32)
        Bb = jnp.asarray(rs.randn(n_ad, r, d) * 0.05, jnp.float32)
        ids = jnp.asarray(rs.randint(0, n_ad, (batch,)), jnp.int32)
        if bool(config.get("kernel", True)) and A._on_tpu():
            fn = jax.jit(lambda a, wa, wb, i: Q.lora_delta(
                a, wa, wb, i))
        else:
            fn = jax.jit(lambda a, wa, wb, i: Q.lora_delta_reference(
                a, wa, wb, i))
        return lambda: fn(x, Ab, Bb, ids)
    if kernel == "paged_flash_decode":
        psz = dims["psz"]
        n_pages, mp = 32, 8
        q = jnp.asarray(rs.randn(batch, heads, 1, d), jnp.float32)
        pages = jnp.asarray(
            rs.randn(n_pages + 1, psz, heads * d), jnp.float32)
        tbl = jnp.asarray(
            rs.randint(0, n_pages, (batch, mp)), jnp.int32)
        length = jnp.full((batch,), mp * psz, jnp.int32)
        use_kernel = bool(config.get("kernel", True)) and \
            A._on_tpu()   # off-chip, both rows time the gather
        #                   reference (interpret mode would time the
        #                   emulator, not the kernel)
        if use_kernel:
            fn = jax.jit(lambda a, kp, vp, t, n: A.paged_flash_decode(
                a, kp, vp, None, None, t, n))
        else:
            fn = jax.jit(lambda a, kp, vp, t, n:
                         A.decode_attention_reference(
                             a, A.paged_gather_kv(kp, None, t, heads,
                                                  a.dtype),
                             A.paged_gather_kv(vp, None, t, heads,
                                               a.dtype), n))
        return lambda: fn(q, pages, pages, tbl, length)
    raise ValueError(f"unknown kernel {kernel!r}")


def default_measurer(batch=4, heads=4, steps=20, k=5):
    """measurer(kernel, key, config) -> seconds over `build_runner`'s
    real dispatch path, timed with the shared op_bench harness."""
    def measurer(kernel, key, config):
        return _op_bench().measure(
            build_runner(kernel, key, config, batch, heads),
            steps=steps, k=k)

    return measurer


def sweep_key(kernel, key, *, measurer, spec=None, batch=1, heads=1,
              stop_factor=1.1, log=None):
    """Sweep ONE (kernel, key): returns a report dict

        {kernel, key, winner, step_us, fallback, fallback_us,
         timed, pruned, stopped_at_roofline}

    The fallback config is ALWAYS timed first (it is the incumbent the
    prune and the stop condition compare against), so the winner can
    never be slower than the shipped constants *as measured here*."""
    from ..profiler import costs as _costs

    spec = spec if spec is not None else _costs.detect_spec()
    fb = fallback_config(kernel, key)
    t_fb = measurer(kernel, key, fb)
    best, t_best = dict(fb), t_fb
    cands = [c for c in candidates(kernel, key) if c != fb]
    keep, cut = prune(kernel, key, cands, t_fb, spec, batch, heads)
    timed = 1
    stopped = False
    for c in keep:
        floor = roofline_seconds(
            analytic_cost(kernel, key, best, batch, heads), spec)
        if t_best <= stop_factor * floor:
            stopped = True   # incumbent already at the device roofline
            break
        if roofline_seconds(analytic_cost(kernel, key, c, batch,
                                          heads), spec) > t_best:
            cut.append(c)    # incumbent improved past this floor
            continue
        t = measurer(kernel, key, c)
        timed += 1
        if log is not None:
            log(f"  {kernel} {_table.key_str(key)} {c} -> "
                f"{t * 1e6:.1f}us")
        if t < t_best:
            best, t_best = dict(c), t
    report = {"kernel": kernel, "key": _table.key_str(key),
              "winner": best, "step_us": round(t_best * 1e6, 2),
              "fallback": fb, "fallback_us": round(t_fb * 1e6, 2),
              "timed": timed, "pruned": len(cut),
              "stopped_at_roofline": stopped}
    return report


def apply_report(tbl, report, device_kind=None):
    """Install a sweep_key report's winner into `tbl` (device-keyed,
    source='sweep'; the measured step_us rides along for the paper
    trail)."""
    cfg = dict(report["winner"])
    cfg["source"] = "sweep"
    cfg["step_us"] = report["step_us"]
    tbl.put(report["kernel"], report["key"], cfg,
            device_kind=device_kind or _table.current_device_kind())
    return tbl
