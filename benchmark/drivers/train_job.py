"""A fine-tuning job as a script runs it: a new host batch every step,
`tr.step` (which shards the batch and enqueues the compiled step), the loss
read back every `log_interval` steps and when the window closes.

End to end: `train_tokens_per_s` = batch x seq_len x steps / seconds, host
clock, over every step enqueued in the window, the window closed by
`block_until_ready` on the last loss and the parameters (padding counts:
the job's sequences are full)."""
from __future__ import annotations

import time

import numpy as np

from benchmark.traffic_gen import BatchMaker
from benchmark.util import say


def reference_check(run, tr):
    """`tr.eval_step` against the plain float32 reference on the same
    weights and a seeded batch, before any update."""
    import jax
    import jax.numpy as jnp

    chk = run.config["check"]
    b, s = chk["eval_batch"]
    ids = BatchMaker({"batch": b, "seq_len": s}, run.seed + 1,
                     run.config["vocab_size"], 2).next()[0]
    got = np.asarray(tr.eval_step((ids,)), np.float32)
    ref_fn = jax.jit(lambda p, i: run.reference.logits(p, i, run.config))
    want = np.asarray(ref_fn(tr.params, jnp.asarray(ids)), np.float32)
    err = float(np.max(np.abs(got - want)))
    ok = bool(np.isfinite(got).all()) and err <= chk["logits_atol"]
    say(check="reference_logits", ok=ok, max_abs_err=err,
        atol=chk["logits_atol"], ref_max_abs=float(np.max(np.abs(want))),
        batch=[b, s])
    return ok


def step_temp_bytes(tr, ids, labels):
    """Scratch the compiled train step allocates while it runs, from XLA's
    memory analysis of the executable for THIS batch shape (the compile is
    answered by the cache). None where the backend gives no analysis."""
    import jax

    data = tr.shard_batch(ids, labels)
    compiled = tr._step_fn.lower(tr.params, tr.opt_state, tr.buffers,
                                 jax.random.PRNGKey(0), data[:-1],
                                 data[-1]).compile()
    analysis = compiled.memory_analysis()
    say(step_memory_analysis={k: getattr(analysis, k, None) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")})
    return int(getattr(analysis, "temp_size_in_bytes", 0) or 0)


def run(run):
    import jax

    job = run.traffic
    tr = run.builder.build(run.config, run.seed, run.devices)
    ok_ref = reference_check(run, tr)
    batches = BatchMaker(job, run.seed, run.config["vocab_size"],
                         run.config["trainer"]["num_classes"])
    b, s = batches.shape
    every = int(job["log_interval"])

    # warm the one shape the job uses: compile (or read) the step, then a
    # few more so the allocator and the dispatch path are steady
    for _ in range(int(job["warm_steps"])):
        ids, labels = batches.next()
        warm_loss = float(tr.step((ids,), labels))
    say(warm_loss=warm_loss)

    losses, dispatch_s, read_marks = [], [], []
    t_open = run.open_window()
    t_mark, n_mark = t_open, 0
    deadline = t_open + run.seconds
    while True:
        ids, labels = batches.next()
        t0 = time.perf_counter()
        loss = tr.step((ids,), labels)
        t1 = time.perf_counter()
        dispatch_s.append(t1 - t0)
        losses.append(loss)
        n = len(losses)
        if n % every == 0:
            float(loss)                       # the job's log line
            t1 = time.perf_counter()
            read_marks.append((t1 - t_mark) / (n - n_mark))
            t_mark, n_mark = t1, n
        if t1 >= deadline:
            break
    jax.block_until_ready((loss, tr.params))
    t_close = run.close_window()
    steps = len(losses)
    window = t_close - t_open
    tokens_per_s = steps * b * s / window

    if run.trace:
        # a steady slice after the window: the same loop under the profiler
        with run.device_trace():
            t_end = time.perf_counter() + float(job["trace_slice_s"])
            while time.perf_counter() < t_end:
                ids, labels = batches.next()
                last = tr.step((ids,), labels)
            jax.block_until_ready(last)

    run.facts["program_temp_bytes"] = step_temp_bytes(tr, ids, labels)
    vals = np.asarray(jax.device_get(losses), np.float64)
    bad = int((~np.isfinite(vals)).sum())
    lo, hi = run.config["check"]["loss_band"]
    tail = float(vals[-every:].mean())
    ok_band = bool(lo <= tail <= hi)
    say(check="loss_band", ok=ok_band, mean_of_last_steps=tail,
        band=[lo, hi], steps=steps, first_loss=float(vals[0]),
        last_loss=float(vals[-1]), not_finite=bad)
    say(window_s=window, steps=steps, batch=[b, s],
        median_step_ms=(float(np.median(read_marks)) * 1e3
                        if read_marks else None),
        read_backs=len(read_marks))
    run.facts.update(
        window_s=window, steps=steps, tokens_per_step=b * s,
        tokens_per_s=tokens_per_s, step_s_between_reads=read_marks,
        dispatch_s=dispatch_s)
    return {"attempted": steps, "failed": bad,
            "correct": ok_ref and ok_band and bad == 0,
            "end_to_end": {"train_tokens_per_s": tokens_per_s}}
