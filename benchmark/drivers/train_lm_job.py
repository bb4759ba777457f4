"""A language-model pre-training job as a script runs it: the loop of
`train_job` (a new host batch every step through `tr.step`, the loss read
back every `log_interval` steps and when the window closes) over next-token
batches, with the expert layers' counters read at the same log line.

Reads from the mix: `batch`, `seq_len`, `log_interval`, `warm_steps`,
`trace_slice_s`; from the configuration: `vocab_size` (the slice ids are
drawn from), `num_experts_per_tok`, `hybrid_override_pattern`,
`experts_held`, `trainer` (lr, warmup_steps, weight_decay) and `check`
(`eval_batch` and the limits of the checks below). A batch is `seq_len +
1` uniform ids a row from `--seed`, through the generator that is there
(`traffic_gen.BatchMaker`): the inputs are the first `seq_len`, the labels
the same ids shifted by one, so a row is one stretch of a concatenated
stream with no reset at document boundaries.

End to end: `train_tokens_per_s` = batch x seq_len x steps / seconds, host
clock, over every step enqueued in the window, the window closed by
`block_until_ready` on the last loss and the parameters.

Leaves in `run.facts`: `window_s`, `steps`, `tokens_per_step`,
`tokens_per_s`, `step_s_between_reads`, `dispatch_s`,
`train_flops_per_token` (benchmark/costs_nemotron_h.py), and one entry a
log line of `routed_share_pct` (token-slots that fell on held experts, %
of all token-slots), `expert_load_max_over_mean` (the fullest held expert
of any layer over the mean) and `dropped_slots`; `program_temp_bytes`.

`correct`, all against the plain reference on the trainer's own weights:

- `reference_logits`, before any update: `tr.eval_step` logits on a seeded
  batch at the timed shape, one sequence at a time in the reference (its
  attention goes query block by query block, its recurrence position by
  position);
- `expert_layer`, before any update: the first expert layer alone, as the
  step casts its weights, on one seeded input of the timed shape, three
  times: routed as the job starts (the router's choices, as a histogram
  over ALL experts, against the reference's float32 choices), with every
  token's slots forced onto held experts, and with every held slot forced
  onto ONE held expert; the routed experts' part of the result (the
  shared expert's projection zeroed) against the dense masked loop each
  time, the counters against the counts the forcing implies, no slot
  dropped;
- `first_update`: update number one of the TIMED step (the first warm
  step). The gradient it computed is read from Adam's first moment (zero
  before, so m = (1 - beta1) g). `update_rel_err` is the parameters after
  the step against plain AdamW applied to that gradient, as the norm of
  the difference over the norm of the plain update: a state left unchanged
  reads 1. `grad_projection` is the reference loss's derivative along that
  gradient (forward mode, nothing kept per position) over the gradient's
  squared norm: 1 if the step's gradient is the reference's, 1/2 if it is
  twice it, 0 if there is none;
- every loss finite; the mean of the last `log_interval` losses inside
  `loss_band`; no dropped slot at any log line."""
from __future__ import annotations

import time

import numpy as np

from benchmark import costs_nemotron_h
from benchmark.drivers.train_job import step_temp_bytes
from benchmark.traffic_gen import BatchMaker
from benchmark.util import say


class TokenBatches:
    """(ids, labels) [batch, seq_len] of a stream of uniform ids."""

    def __init__(self, job, seed, vocab):
        self.shape = (int(job["batch"]), int(job["seq_len"]))
        self.rows = BatchMaker(
            {"batch": self.shape[0], "seq_len": self.shape[1] + 1},
            seed, vocab, 2)

    def next(self):
        tokens = self.rows.next()[0]
        return tokens[:, :-1], tokens[:, 1:]


def logits_errors(got, want, atol):
    """How far `got` lies from `want` ([b, s, vocab] float32): the root
    mean square of the difference over that of `want`, and the share of
    positions whose every logit is within `atol`. A top-k choice that
    rounding flips moves single positions by far more than rounding moves
    the rest, so the largest difference alone says little."""
    diff = np.abs(got - want)
    return {
        "rel_rms_err": float(np.sqrt((diff ** 2).mean() / (want ** 2).mean())),
        "positions_within_atol": float((diff.max(-1) <= atol).mean()),
        "max_abs_err": float(diff.max()),
        "ref_rms": float(np.sqrt((want ** 2).mean())),
    }


def reference_check(run, tr):
    import jax
    import jax.numpy as jnp

    chk = run.config["check"]
    b, s = chk["eval_batch"]
    ids = TokenBatches({"batch": b, "seq_len": s}, run.seed + 1,
                       run.config["vocab_size"]).next()[0]
    got = np.asarray(tr.eval_step((ids,)), np.float32)
    state = {**tr.params, **tr.buffers}
    ref_fn = jax.jit(lambda p, i: run.reference.logits(p, i, run.config))
    want = np.concatenate([
        np.asarray(ref_fn(state, jnp.asarray(ids[i:i + 1])), np.float32)
        for i in range(b)])
    err = logits_errors(got, want, chk["logits_atol"])
    ok = bool(np.isfinite(got).all()
              and err["rel_rms_err"] <= chk["rel_rms_max"]
              and err["positions_within_atol"] >= chk["within_atol_min"])
    say(check="reference_logits", ok=ok, **err, atol=chk["logits_atol"],
        rel_rms_max=chk["rel_rms_max"],
        within_atol_min=chk["within_atol_min"], batch=[b, s])
    return ok


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((got - want) ** 2).mean() / (want ** 2).mean()))


def expert_layer_check(run, tr):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import functionalize

    cfg, chk = run.config, run.config["check"]
    b, s = chk["eval_batch"]
    k = int(cfg["num_experts_per_tok"])
    first, count = cfg["experts_held"]
    at = cfg["hybrid_override_pattern"].index("E")
    prefix = f"layers.{at}.mixer."
    fm = functionalize(tr.fm.layer.layers[at].mixer)

    def own(tree):
        return {n[len(prefix):]: v for n, v in tree.items()
                if n.startswith(prefix)}

    # the routed part alone: beside the shared expert, which every token
    # passes through (and `reference_logits` holds), a lost tile of rows
    # would not show in the result's root mean square
    shared = "shared_experts.down_proj.weight"

    def layer(masters, bf, x):
        p = own(tr.cast_params(masters))
        p[shared] = jnp.zeros_like(p[shared])
        return fm.apply(p, bf, None, x.astype(p[shared].dtype),
                        training=False)

    def plain(masters, bf, x):
        with jax.default_matmul_precision("highest"):
            p = {"m." + n: v for n, v in {**own(masters), **bf}.items()}
            u = x.astype(tr.compute_dtype).astype(jnp.float32)
            loads = (run.reference.router(p, "m.", u, cfg)[1][..., None]
                     == jnp.arange(cfg["router_experts"])).sum((0, 1, 2))
            return run.reference.experts(p, "m.", u, cfg,
                                         shared=False), loads

    layer, plain = jax.jit(layer), jax.jit(plain)
    masters = {n: v for n, v in tr.params.items() if n.startswith(prefix)}
    bufs = own(tr.buffers)
    x = jnp.asarray(np.random.default_rng(run.seed + 3).standard_normal(
        (b, s, int(cfg["hidden_size"])), np.float32))
    bias_name = "gate.e_score_correction_bias"
    start = np.asarray(bufs[bias_name], np.float32)
    held = np.arange(first, first + count)
    all_held, one_expert = start.copy(), start.copy()
    all_held[held[:k]] = 100.0            # every slot of every token
    one_expert[held] = -100.0
    one_expert[first] = 100.0             # one slot a token, one expert
    cases = {"as_started": (start, None, None),
             "all_slots_held": (all_held, b * s * k, count / k),
             "one_held_expert": (one_expert, b * s, float(count))}
    ok, said = True, {}
    for name, (bias, slots, skew) in cases.items():
        bf = {**bufs, bias_name: jnp.asarray(bias)}
        got, after = layer(masters, bf, x)
        want, loads = plain(masters, bf, x)
        after = jax.device_get(after)
        seen = {
            "rel_rms_err": _rel_rms(got, want),
            "router_disagreement": float(
                0.5 * np.abs(np.asarray(after["expert_load_val"])
                             - np.asarray(loads)).sum() / (b * s * k)),
            "routed_slots": float(after["routed_slots_val"]),
            "load_max_over_mean": float(
                after["load_max_val"]
                / max(float(after["load_mean_val"]), 1e-9)),
            "dropped_slots": float(after["dropped_slots_val"])}
        good = (seen["rel_rms_err"] <= chk["expert_rel_rms_max"]
                and seen["router_disagreement"]
                <= chk["router_disagreement_max"]
                and seen["dropped_slots"] == 0
                and (slots is None or (
                    seen["routed_slots"] == slots
                    and abs(seen["load_max_over_mean"] - skew) < 1e-3)))
        ok, said[name] = ok and bool(good), seen
    say(check="expert_layer", ok=ok, layer=at, batch=[b, s], **said,
        expert_rel_rms_max=chk["expert_rel_rms_max"],
        router_disagreement_max=chk["router_disagreement_max"])
    return ok


def held_to_plain(reference, p0, p1, m, lr, weight_decay, decayed,
                  beta1=0.9):
    """One parameter of the step against plain AdamW: the step's own
    gradient is Adam's first moment after one update from zero over
    (1 - beta1); returns three squared norms: of the parameter after the
    step less plain AdamW's of that gradient, of the plain update, of the
    gradient."""
    import jax.numpy as jnp

    grad = m / (1.0 - beta1)
    plain = reference.adamw_first_update(p0, grad, lr, weight_decay,
                                         decayed, beta1=beta1)

    def sq(v):
        return (v.astype(jnp.float32) ** 2).sum()

    return sq(p1 - plain), sq(plain - p0), sq(grad)


def first_update_check(run, tr, ids, labels, beta1=0.9):
    """Runs update number one through `tr.step` and holds it to the plain
    reference; returns (ok, the step's loss). Nothing of the parameters'
    size is held on the device beside the trainer's own state: the
    parameters before the step wait on the host and come back one at a
    time, and the gradient is taken from the first moment inside the
    program that needs it."""
    import functools

    import jax
    import jax.numpy as jnp

    cfg, chk, t = run.config, run.config["check"], run.config["trainer"]
    lr = t["lr"] / t["warmup_steps"]      # the warm-up's first rate
    before = jax.device_get(tr.params)    # the step donates the device's
    loss = float(tr.step((ids,), labels))
    moments = tr.opt_state.m
    one = jax.jit(functools.partial(
        held_to_plain, run.reference, lr=lr,
        weight_decay=t["weight_decay"], beta1=beta1),
        static_argnames="decayed")
    # matrices decay; vectors and the depthwise convolution's taps do not
    off, moved, grad_sq = np.sum([
        [float(v) for v in one(p0, tr.params[n], moments[n],
                               decayed=p0.ndim >= 2 and "conv1d" not in n)]
        for n, p0 in before.items()], axis=0)
    del before
    # a plain update of nothing (no gradient reached the moments) holds
    # nothing: read as a state left unchanged
    update_rel_err = float(np.sqrt(off / moved)) if moved > 0 else 1.0

    state = {**tr.params, **{n: v for n, v in tr.buffers.items()
                             if n.endswith("e_score_correction_bias")}}
    along = jax.jit(lambda p, m, i, l: run.reference.loss_along(
        p, {n: v / (1.0 - beta1) for n, v in m.items()}, i, l, cfg))
    pairs = [along(state, moments, jnp.asarray(ids[i:i + 1]),
                   jnp.asarray(labels[i:i + 1])) for i in range(len(ids))]
    ref_loss = float(np.mean([float(p[0]) for p in pairs]))
    projection = (float(np.mean([float(p[1]) for p in pairs])) / grad_sq
                  if grad_sq > 0 else 0.0)
    lo, hi = chk["grad_projection_band"]
    ok = bool(update_rel_err <= chk["update_rel_err_max"]
              and lo <= projection <= hi and np.isfinite(loss))
    say(check="first_update", ok=ok, update_rel_err=update_rel_err,
        update_rel_err_max=chk["update_rel_err_max"],
        grad_projection=projection, grad_projection_band=[lo, hi],
        grad_norm=float(grad_sq) ** 0.5, lr=lr, step_loss=loss,
        reference_loss=ref_loss)
    return ok, loss


def routing_counters(tr, slots_a_layer):
    """The expert layers' counters of the last step, from the step's
    returned buffers: (% of token-slots on held experts, fullest held
    expert over the mean, dropped slots)."""
    import jax

    vals = jax.device_get({n: v for n, v in tr.buffers.items()
                           if n.endswith("_val")})

    def of(kind):
        return np.asarray([float(v) for n, v in sorted(vals.items())
                           if n.endswith(kind)])

    routed, mean = of("routed_slots_val"), of("load_mean_val")
    return (100.0 * routed.sum() / (len(routed) * slots_a_layer),
            float((of("load_max_val") / np.maximum(mean, 1e-9)).max()),
            float(of("dropped_slots_val").sum()))


def run(run):
    import jax

    job = run.traffic
    tr = run.builder.build(run.config, run.seed, run.devices)
    ok_ref = reference_check(run, tr) and expert_layer_check(run, tr)
    batches = TokenBatches(job, run.seed, run.config["vocab_size"])
    b, s = batches.shape
    every = int(job["log_interval"])
    slots = b * s * int(run.config["num_experts_per_tok"])

    ok_update, warm_loss = first_update_check(run, tr, *batches.next())
    for _ in range(int(job["warm_steps"]) - 1):
        ids, labels = batches.next()
        warm_loss = float(tr.step((ids,), labels))
    say(warm_loss=warm_loss)

    losses, dispatch_s, read_marks, counters = [], [], [], []
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
            counters.append(routing_counters(tr, slots))
            t1 = time.perf_counter()
            read_marks.append((t1 - t_mark) / (n - n_mark))
            t_mark, n_mark = t1, n
        if t1 >= deadline:
            break
    jax.block_until_ready((loss, tr.params))
    t_close = run.close_window()
    counters.append(routing_counters(tr, slots))
    steps = len(losses)
    window = t_close - t_open
    tokens_per_s = steps * b * s / window

    if run.trace:
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
    share, skew, dropped = (list(c) for c in zip(*counters))
    ok_drop = max(dropped) == 0
    say(check="no_dropped_slot", ok=ok_drop, dropped_slots=max(dropped),
        routed_share_pct=[min(share), max(share)],
        expert_load_max_over_mean=[min(skew), max(skew)],
        log_lines=len(counters),
        by_log_line=[[round(v, 3) for v in c] for c in counters])
    say(window_s=window, steps=steps, batch=[b, s],
        median_step_ms=(float(np.median(read_marks)) * 1e3
                        if read_marks else None),
        read_backs=len(read_marks))
    run.facts.update(
        window_s=window, steps=steps, tokens_per_step=b * s,
        tokens_per_s=tokens_per_s, step_s_between_reads=read_marks,
        dispatch_s=dispatch_s, routed_share_pct=share,
        expert_load_max_over_mean=skew, dropped_slots=dropped,
        train_flops_per_token=costs_nemotron_h.train_flops_per_token(
            run.config, s))
    return {"attempted": steps, "failed": bad,
            "correct": (ok_ref and ok_update and ok_band and ok_drop
                        and bad == 0),
            "end_to_end": {"train_tokens_per_s": tokens_per_s}}
