#!/usr/bin/env python
"""Flash-attention block-size sweep on the attached TPU chip.

Measures fwd+bwd (causal bf16) per-step time for (block_q, block_k)
combinations with bench.py's two-point marginal methodology, against the
XLA fused reference. Writes the winners to stdout; _pick_blocks in
ops/attention.py encodes the result as a static table.

Usage: python tools/tune_flash.py [--seqs 1024,2048,4096] [--iters N]
Run alone on the chip: it belongs to one process at a time.
"""
import argparse
import functools
import sys
import time

sys.path.insert(0, ".")

import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="1024,2048,4096")
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--b", type=int, default=2)
    ap.add_argument("--h", type=int, default=16)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--dropout", type=float, default=0.0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import bench
    from paddle_tpu.ops import attention as att

    assert att._on_tpu(), "the flash kernels compile for a TPU backend only"

    iters_by_seq = {1024: 256, 2048: 96, 4096: 32}
    seed = jnp.array([1234], jnp.int32)

    for S in [int(s) for s in args.seqs.split(",")]:
        n_it = args.iters or iters_by_seq.get(S, 48)
        q = jnp.asarray(np.random.RandomState(0).randn(
            args.b, args.h, S, args.d), jnp.bfloat16)

        def timeit(fn):
            def loss(q, k, v):
                return fn(q, k, v).astype(jnp.float32).sum()

            g = jax.grad(loss, (0, 1, 2))

            @functools.partial(jax.jit, static_argnums=3)
            def run_n(q, k, v, n):
                def body(c, _):
                    qp = (q * (1 + c * 1e-9)).astype(q.dtype)
                    gq, gk, gv = g(qp, k, v)
                    return gq.astype(jnp.float32).mean(), None
                c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=n)
                return c

            def timed(n):
                t0 = time.perf_counter()
                r = float(run_n(q, q, q, n))
                assert r == r
                return time.perf_counter() - t0

            dt, _, _ = bench._marginal_step_time(timed, n_it, lo_frac=4)
            return dt * 1e3

        t_ref = timeit(lambda q, k, v: att.sdpa_reference(
            q, k, v, None, True, None))
        print(f"seq{S}: xla_ref {t_ref:.3f} ms")
        results = {}
        for bq in (128, 256, 512, 1024):
            for bk in (128, 256, 512, 1024):
                if bq > S or bk > S:
                    continue
                try:
                    t = timeit(lambda q, k, v, bq=bq, bk=bk:
                               att.flash_attention(
                                   q, k, v, None, True, None,
                                   block_q=bq, block_k=bk,
                                   dropout_p=args.dropout,
                                   dropout_seed=(seed if args.dropout
                                                 else None)))
                    results[(bq, bk)] = t
                    print(f"  bq{bq} bk{bk}: {t:.3f} ms "
                          f"({t_ref / t:.3f}x vs ref)")
                except Exception as e:
                    print(f"  bq{bq} bk{bk}: FAIL {type(e).__name__}")
        best = min(results, key=results.get)
        print(f"seq{S} BEST: bq{best[0]} bk{best[1]} = "
              f"{results[best]:.3f} ms ({t_ref / results[best]:.3f}x)")


if __name__ == "__main__":
    main()
