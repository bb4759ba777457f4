"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py               one TPU chip: trainer, server, kernels
    python chip_smoke.py --four-chips  four chips: the dp x tp train step and
                                       the sharded paged pool, each against
                                       the same work on one device
    python chip_smoke.py --rehearse    the same control flow on the CPU
                                       backend at a tiny size, kernels in
                                       interpret mode (add --four-chips with
                                       XLA_FLAGS=--xla_force_host_platform_
                                       device_count=4 for the mesh phases)

It drives the two main paths through the entry points a user calls
(`SpmdTrainer`, `ServingServer` over `ServingEngine(paged=True)`) at the
full width of models the repository supports: ERNIE-base (12 x 768) and
the `nn.Transformer` default decoder (6 x 512). Weights are random, made
from `--seed`. It times nothing for the record: seconds are printed so
that a slow phase is seen, not as a measurement.

One process, which is the only one that touches JAX; it never sets
JAX_PLATFORMS and never falls back: without `--rehearse` a first device
that is not a TPU ends the run with a non-zero code and no result line.
A phase that fails is reported and fails the run; the others still run,
so one call to the chip shows every fault there is.

The last line of standard output is the result:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Every earlier line is one JSON object per phase or check.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

#: What the token comparisons hold to. Bit-equality is not asked for: on
#: the chip a Pallas kernel and the XLA composition it replaces round
#: differently, a near-tie argmax then flips one token, and everything a
#: request generates after a flip legitimately differs. So the measure is
#: the share of positions that agree, and it is held to floors that a
#: correct pool clears with room (flips are rare per token) and a pool
#: that masks, maps pages or positions wrongly cannot reach: it diverges
#: at the first or second token of every request (share under 0.1).
TOKEN_AGREEMENT_FLOOR = 0.25    # all positions of all requests
FIRST_TOKEN_FLOOR = 0.75        # requests whose first token agrees

REAL = {
    "ernie": dict(vocab_size=30522, hidden_size=768, num_layers=12,
                  num_heads=12, intermediate_size=3072),
    "short": (32, 128), "long": (8, 1024), "scan_steps": 24,
    "decoder": dict(d_model=512, nhead=8, ffn=2048, layers=6, vocab=30522),
    "pool": dict(num_slots=16, max_len=1024), "mem_len": 64,
    "prompt_lens": (16, 23, 40, 64, 97, 150, 210, 300), "new_tokens": 32,
    # generate_eager retraces every token: each new token is ~20 one-op
    # programs, and the chip's compiler takes about half a second for
    # each, so the eager reference gets a small input — the shortest
    # request's first tokens — and the whole traffic is compared with
    # the same pool on its XLA compositions instead
    "eager_requests": 1, "eager_tokens": 4,
    "four_prompt_lens": (16, 23, 60, 64, 200, 250), "four_new_tokens": 16,
    "flash": dict(b=8, h=12, s=1024, d=64),
    "decode": dict(b=16, h=8, L=1024, d=64, T=4, psz=16),
    "int8": ((16, 512, 2048), (2048, 512, 2048)),
    "lora": dict(b=16, s=1, d=512, r=8, n_out=512, n=4),
}

TINY = {
    "ernie": dict(vocab_size=512, hidden_size=64, num_layers=2,
                  num_heads=4, intermediate_size=128),
    "short": (4, 16), "long": (2, 128), "scan_steps": 24,
    "decoder": dict(d_model=64, nhead=4, ffn=128, layers=2, vocab=97),
    "pool": dict(num_slots=4, max_len=64), "mem_len": 4,
    "prompt_lens": (3, 5, 8, 9, 14, 20), "new_tokens": 8,
    "eager_requests": 6, "eager_tokens": 8,
    "four_prompt_lens": (3, 8, 9, 20), "four_new_tokens": 6,
    "flash": dict(b=1, h=2, s=256, d=64),
    "decode": dict(b=2, h=2, L=256, d=64, T=4, psz=16),
    "int8": ((16, 128, 256),),
    "lora": dict(b=2, s=1, d=128, r=8, n_out=128, n=3),
}


def say(**record):
    print(json.dumps(record), flush=True)


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def softmax_ce(logits, labels):
    import jax
    import jax.numpy as jnp

    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.take_along_axis(lp, labels[:, None], -1).mean()


# ----------------------------------------------------------------------
# trainer
# ----------------------------------------------------------------------

def make_trainer(size, mesh, seed, dropout, rules=None):
    import paddle_tpu as paddle
    from paddle_tpu.optimizer import functional as fopt
    from paddle_tpu.parallel import SpmdTrainer
    from paddle_tpu.text import ErnieConfig, ErnieForSequenceClassification

    paddle.seed(seed)
    cfg = ErnieConfig(max_position=size["long"][1] + 2,
                      hidden_dropout=dropout, attn_dropout=dropout,
                      num_classes=2, **size["ernie"])
    net = ErnieForSequenceClassification(cfg)
    return SpmdTrainer(net, softmax_ce, fopt.adamw(5e-5), mesh=mesh,
                       rules=rules, compute_dtype="bfloat16")


def make_batch(size, which, seed):
    rs = np.random.RandomState(seed)
    b, s = size[which]
    ids = rs.randint(1, size["ernie"]["vocab_size"], (b, s)).astype(np.int64)
    labels = rs.randint(0, 2, (b,)).astype(np.int64)
    return ids, labels


def compiled_step_text(tr, ids, labels, key):
    """Text of the train step as the compiler built it for THIS batch
    shape (the persistent cache answers the second compile)."""
    data = tr.shard_batch(ids, labels)
    return tr._step_fn.lower(tr.params, tr.opt_state, tr.buffers, key,
                             data[:-1], data[-1]).compile().as_text()


def phase_trainer(size, seed, rehearse):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import init_mesh

    dev = jax.devices()[0]
    tr = make_trainer(size, init_mesh(dp=1, devices=[dev]), seed, 0.1)
    key = jax.random.PRNGKey(seed)

    # loss on the fixed batch in eval mode (no dropout noise) before and
    # after the updates; at this width and step count the fall is the
    # classifier finding the batch's label prior, which is enough to
    # show that gradients reach the weights and the update is applied
    ids, labels = make_batch(size, "short", seed)

    def eval_loss():
        return float(softmax_ce(tr.eval_step((ids,)), jnp.asarray(labels)))

    first = eval_loss()
    train_loss = float(tr.run_steps((ids,), labels, size["scan_steps"],
                                    rng=key))
    last = eval_loss()
    assert np.isfinite([first, train_loss, last]).all(), \
        (first, train_loss, last)
    assert last < first, f"loss did not fall on a fixed batch: " \
                         f"{first} -> {last}"

    ids_l, labels_l = make_batch(size, "long", seed + 1)
    long_losses = [float(tr.step((ids_l,), labels_l,
                                 rng=jax.random.fold_in(key, i)))
                   for i in range(2)]
    assert np.isfinite(long_losses).all(), long_losses
    n_kernels = compiled_step_text(tr, ids_l, labels_l, key).count(
        "tpu_custom_call")
    if not rehearse:
        # fwd + dQ + dK/dV per layer; zero means sdpa_bshd took the
        # XLA reference in-model, which is what this check is for
        assert n_kernels > 0, "the seq-%d train step holds no Pallas " \
                              "kernel" % size["long"][1]
    return {"short_batch": list(size["short"]), "eval_loss_first": first,
            "train_loss_at_step_%d" % size["scan_steps"]: train_loss,
            "eval_loss_after_%d_steps" % size["scan_steps"]: last,
            "long_batch": list(size["long"]), "long_losses": long_losses,
            "tpu_custom_calls_in_long_step": n_kernels}


# ----------------------------------------------------------------------
# server
# ----------------------------------------------------------------------

def make_stack(size, seed):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)

    d = size["decoder"]
    paddle.seed(seed)
    layer = TransformerDecoderLayer(d["d_model"], d["nhead"], d["ffn"],
                                    dropout=0.0)
    dec = TransformerDecoder(layer, d["layers"])
    dec.eval()
    return dec, nn.Embedding(d["vocab"], d["d_model"]), \
        nn.Linear(d["d_model"], d["vocab"])


def make_requests(size, lens, seed):
    rs = np.random.RandomState(seed)
    d = size["decoder"]
    out = []
    for n in lens:
        p = rs.randint(2, d["vocab"], (n,)).astype(np.int32)
        p[0] = 0
        out.append((p, rs.randn(size["mem_len"],
                                d["d_model"]).astype(np.float32)))
    return out


def serve(engine, work, new_tokens, repeat_first=False):
    """Drive `work` through a threaded ServingServer; returns the token
    lists in submission order. `repeat_first` re-submits request 0
    after the drain so the prefix cache's attach path runs too."""
    from paddle_tpu.serving import ServingServer

    srv = ServingServer(engine, max_queue=64)
    try:
        reqs = [srv.submit(p, m, max_new_tokens=new_tokens, eos_id=1)
                for p, m in work]
        res = [r.result(timeout=900) for r in reqs]
        if repeat_first:
            p, m = work[0]
            res.append(srv.submit(p, m, max_new_tokens=new_tokens,
                                  eos_id=1).result(timeout=900))
    finally:
        srv.shutdown(drain=True, timeout=60)
    bad = [i for i, r in enumerate(res) if not r.ok]
    assert not bad, f"requests {bad} did not resolve ok: " \
                    f"{[res[i] for i in bad]}"
    return [list(r.tokens) for r in res]


def pool_health(engine):
    """The no-hidden-failure checks on a drained paged pool."""
    snap = engine.metrics.snapshot()
    err = snap["errors"]
    assert (err["count"], err["retries"], err["fallbacks"]) == (0, 0, 0), err
    traces = {str(k): v for k, v in engine.trace_counts.items()}
    assert traces and all(v == 1 for v in traces.values()), traces
    engine.flush_prefix_cache()
    engine._alloc.check()
    assert engine._alloc.pages_free == engine._alloc.n_pages, \
        (engine._alloc.pages_free, engine._alloc.n_pages)
    return {"errors": err["count"], "retries": err["retries"],
            "fallbacks": err["fallbacks"], "programs_traced_once": traces,
            "pages_free": int(engine._alloc.pages_free),
            "n_pages": int(engine._alloc.n_pages),
            "prefix": snap.get("prefix")}


def program_text(engine, kind):
    """Compiled text of the engine's program of `kind` ("pstep", ...),
    rebuilt from the engine's own start-up description of it."""
    for key, build, args in engine._startup_programs(()):
        if key[0] == kind:
            return build().lower(*args).compile().as_text()
    raise KeyError(kind)


def eager_tokens(stack, work, new_tokens):
    """Reference streams from `generate_eager` on the concat-grown cache,
    requests of one prompt bucket batched."""
    import jax.numpy as jnp

    from paddle_tpu.core.bucketing import bucket_size
    from paddle_tpu.text.generation import generate_eager

    dec, embed, proj = stack
    out = [None] * len(work)
    by_bucket = {}
    for i, (p, _) in enumerate(work):
        by_bucket.setdefault(bucket_size(len(p)), []).append(i)
    for pb, idx in sorted(by_bucket.items()):
        width = max(len(work[i][0]) for i in idx)
        prompts = np.ones((len(idx), width), np.int32)
        for row, i in enumerate(idx):
            prompts[row, :len(work[i][0])] = work[i][0]
        toks, lens = generate_eager(
            dec, embed, proj,
            jnp.asarray(np.stack([work[i][1] for i in idx])),
            jnp.asarray(prompts),
            jnp.asarray([len(work[i][0]) for i in idx], jnp.int32),
            bos_id=0, eos_id=1, max_new_tokens=new_tokens,
            pad_prompt_to=pb)
        toks, lens = np.asarray(toks), np.asarray(lens)
        for row, i in enumerate(idx):
            out[i] = [int(t) for t in
                      toks[row, :min(int(lens[row]), new_tokens)]]
    return out


def agreement(got, want):
    """(share of positions that agree, per-request common prefix)."""
    same = total = 0
    prefixes = []
    for g, w in zip(got, want):
        n = max(len(g), len(w))
        total += n
        same += sum(1 for a, b in zip(g, w) if a == b)
        k = 0
        while k < min(len(g), len(w)) and g[k] == w[k]:
            k += 1
        prefixes.append(k)
    return same / max(total, 1), prefixes


@contextlib.contextmanager
def xla_compositions_only():
    """The program's documented switch: with PT_FLASH_ATTENTION=0 every
    dispatcher takes its XLA composition. Read at trace time, so it must
    cover the building AND the serving of an engine."""
    old = os.environ.get("PT_FLASH_ATTENTION")
    os.environ["PT_FLASH_ATTENTION"] = "0"
    try:
        yield
    finally:
        if old is None:
            del os.environ["PT_FLASH_ATTENTION"]
        else:
            os.environ["PT_FLASH_ATTENTION"] = old


def first_token_share(got, want):
    return sum(1 for g, w in zip(got, want) if g[:1] == w[:1]) / len(want)


def phase_server(size, seed, rehearse):
    from paddle_tpu.serving import ServingEngine

    work = make_requests(size, size["prompt_lens"], seed)
    n_new = size["new_tokens"]

    # the pool as a user gets it: Pallas kernels where the gates say so
    stack = make_stack(size, seed)
    eng = ServingEngine(*stack, paged=True, **size["pool"])
    toks = serve(eng, work, n_new, repeat_first=True)
    health = pool_health(eng)
    n_kernels = program_text(eng, "pstep").count("tpu_custom_call")
    if not rehearse:
        assert n_kernels > 0, "the pstep program holds no Pallas kernel"
    del eng

    # reference 1, the whole traffic: the same pool, same weights, same
    # device, on its XLA compositions — the path the CPU suite holds
    # bit-equal to generate_eager
    with xla_compositions_only():
        ref_eng = ServingEngine(*make_stack(size, seed), paged=True,
                                **size["pool"])
        ref = serve(ref_eng, work, n_new, repeat_first=True)
        ref_health = pool_health(ref_eng)
        ref_kernels = program_text(ref_eng, "pstep").count(
            "tpu_custom_call")
    assert ref_kernels == 0, "the switch left a kernel in the reference"
    del ref_eng
    share, prefixes = agreement(toks, ref)
    first = first_token_share(toks, ref)
    assert share >= TOKEN_AGREEMENT_FLOOR, (share, prefixes)
    assert first >= FIRST_TOKEN_FLOOR, (first, prefixes)

    # reference 2, a small input: generate_eager itself
    n_eager, t_eager = size["eager_requests"], size["eager_tokens"]
    eager = eager_tokens(stack, work[:n_eager], t_eager)
    _, eager_vs_pool = agreement([t[:t_eager] for t in toks[:n_eager]],
                                 eager)
    _, eager_vs_ref = agreement([t[:t_eager] for t in ref[:n_eager]],
                                eager)
    assert min(eager_vs_pool) >= 1 and min(eager_vs_ref) >= 2, \
        (eager, eager_vs_pool, eager_vs_ref)
    repeat_share, _ = agreement(toks[-1:], toks[:1])
    return dict(health, requests=len(toks), new_tokens=n_new,
                tokens_out=sum(len(t) for t in toks),
                tpu_custom_calls_in_pstep=n_kernels,
                reference_pool_errors=ref_health["errors"],
                token_agreement_with_xla_pool=round(share, 4),
                agreement_floor=TOKEN_AGREEMENT_FLOOR,
                first_token_agreement=round(first, 4),
                first_token_floor=FIRST_TOKEN_FLOOR,
                common_prefix_per_request=prefixes,
                generate_eager_requests=n_eager,
                generate_eager_tokens=t_eager,
                eager_common_prefix_with_pool=eager_vs_pool,
                eager_common_prefix_with_xla_pool=eager_vs_ref,
                repeat_request_agrees_with_first=round(repeat_share, 4))


# ----------------------------------------------------------------------
# kernels against their references
# ----------------------------------------------------------------------

def close(name, got, want, atol, rtol=0.0):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    ok = bool(np.isfinite(got).all()) and err <= atol + rtol * scale
    return {"check": name, "ok": ok, "max_abs_err": err,
            "ref_max_abs": scale, "atol": atol, "rtol": rtol}


def phase_kernels(size, seed, rehearse):
    """Each kernel called directly (no dispatcher that may choose the
    reference) against its reference, which runs at full f32 matmul
    precision so the difference printed is the kernel's own."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import attention as A
    from paddle_tpu.ops import quant as Q
    from paddle_tpu.serving import paging as PG

    interp = bool(rehearse)
    rs = np.random.RandomState(seed)

    def exact():
        return jax.default_matmul_precision("float32")

    checks = []

    def rnd(*shape, dtype=jnp.float32, scale=0.5):
        return jnp.asarray(rs.randn(*shape) * scale, dtype)

    # ---- flash attention fwd + bwd: bf16 and f32, key bias, causal ----
    f = size["flash"]
    b, h, s, d = f["b"], f["h"], f["s"], f["d"]
    bias = jnp.where(jnp.arange(s)[None, :] < (s - 37),
                     jnp.float32(0.0), jnp.float32(-1e30))
    bias = jnp.broadcast_to(bias, (b, s))
    for dt, atol in ((jnp.bfloat16, 4e-2), (jnp.float32, 1e-2)):
        q, k, v = (rnd(b, h, s, d, dtype=dt) for _ in range(3))
        g = rnd(b, h, s, d, dtype=dt)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                                    * g.astype(jnp.float32)).sum()

        def flash(q, k, v):
            return A.flash_attention(q, k, v, bias, True, None,
                                     interpret=interp)

        def ref(q, k, v):
            return A.sdpa_reference(q, k, v, bias[:, None, None, :], True)

        out = jax.jit(flash)(q, k, v)
        grads = jax.jit(jax.grad(loss(flash), (0, 1, 2)))(q, k, v)
        with exact():
            out_r = jax.jit(ref)(q, k, v)
            grads_r = jax.jit(jax.grad(loss(ref), (0, 1, 2)))(q, k, v)
        tag = f"flash_attention[{jnp.dtype(dt).name}]"
        checks.append(close(tag + ".fwd", out, out_r, atol))
        for n, a, r in zip("qkv", grads, grads_r):
            checks.append(close(f"{tag}.d{n}", a, r, atol, rtol=2e-2))

    # ---- in-kernel dropout: the statistics tests/test_flash_dropout.py
    # could only ask for on a chip (the Mosaic PRNG has no CPU lowering;
    # interpret mode draws the hash bits instead) ----
    p_drop = 0.2
    q, k = (rnd(b, h, s, d, dtype=jnp.bfloat16) for _ in range(2))
    ones = jnp.ones((b, h, s, d), jnp.bfloat16)
    drop = jax.jit(lambda sd: A.flash_attention(
        q, k, ones, None, False, None, interpret=interp,
        dropout_p=p_drop, dropout_seed=sd))
    o1 = np.asarray(drop(jnp.array([11], jnp.int32)), np.float32)
    o1b = np.asarray(drop(jnp.array([11], jnp.int32)), np.float32)
    o2 = np.asarray(drop(jnp.array([12], jnp.int32)), np.float32)
    # with v = 1 every output is sum_j p_ij keep_ij / (1 - p): mean 1
    checks.append({"check": "flash_attention.dropout", "p": p_drop,
                   "ok": bool(np.array_equal(o1, o1b)
                              and not np.array_equal(o1, o2)
                              and abs(float(o1.mean()) - 1.0) < 0.02),
                   "same_seed_equal": bool(np.array_equal(o1, o1b)),
                   "other_seed_differs": not np.array_equal(o1, o2),
                   "mean_with_unit_values": float(o1.mean())})

    # ---- decode / verify against a dense cache ----
    c = size["decode"]
    b, h, L, d, T, psz = c["b"], c["h"], c["L"], c["d"], c["T"], c["psz"]
    lengths = jnp.asarray(rs.randint(T + 1, L, (b,)), jnp.int32)
    kbias = jnp.where(jnp.asarray(rs.rand(b, L) < 0.05),
                      jnp.float32(-1e30), jnp.float32(0.0))
    kbias = kbias.at[:, 0].set(0.0)
    k, v = rnd(b, h, L, d), rnd(b, h, L, d)
    q1, qT = rnd(b, h, 1, d), rnd(b, h, T, d)
    out = jax.jit(lambda *a: A.flash_decode(*a, interpret=interp))(
        q1, k, v, lengths, kbias)
    with exact():
        want = jax.jit(A.decode_attention_reference)(q1, k, v, lengths,
                                                     kbias)
    checks.append(close("flash_decode", out, want, 1e-2))
    out = jax.jit(lambda *a: A.flash_verify(*a, interpret=interp))(
        qT, k, v, lengths, kbias)
    with exact():
        want = jax.jit(A.verify_attention_reference)(qT, k, v, lengths,
                                                     kbias)
    checks.append(close("flash_verify", out, want, 1e-2))

    # ---- paged decode / verify: f32, bf16 and int8 pages ----
    mp = L // psz
    n_pages = b * mp
    table = jnp.asarray(rs.permutation(n_pages).reshape(b, mp), jnp.int32)
    for kv_dt, atol in (("float32", 1e-2), ("bfloat16", 2e-2),
                        ("int8", 2e-2)):
        kp, ks = PG.quantize_chunks(rnd(n_pages + 1, psz, h * d), kv_dt,
                                    kv_dt == "int8", h)
        vp, vs = PG.quantize_chunks(rnd(n_pages + 1, psz, h * d), kv_dt,
                                    kv_dt == "int8", h)
        kd = A.paged_gather_kv(kp, ks, table, h, jnp.float32)
        vd = A.paged_gather_kv(vp, vs, table, h, jnp.float32)
        out = jax.jit(lambda *a: A.paged_flash_decode(
            *a, interpret=interp))(q1, kp, vp, ks, vs, table, lengths,
                                   kbias)
        with exact():
            want = jax.jit(A.decode_attention_reference)(
                q1, kd, vd, lengths, kbias)
        checks.append(close(f"paged_flash_decode[{kv_dt}]", out, want,
                            atol))
        out = jax.jit(lambda *a: A.paged_flash_verify(
            *a, interpret=interp))(qT, kp, vp, ks, vs, table, lengths,
                                   kbias)
        with exact():
            want = jax.jit(A.verify_attention_reference)(
                qT, kd, vd, lengths, kbias)
        checks.append(close(f"paged_flash_verify[{kv_dt}]", out, want,
                            atol))

    # ---- int8 weight matmul ----
    for m, d_in, n in size["int8"]:
        x = rnd(m, d_in)
        wq, scale = Q.quantize_int8_weight(rnd(d_in, n, scale=0.05))
        bm, bn = Q._pick_int8_blocks_heuristic(m, n)
        out = jax.jit(Q._int8_matmul_call(m, d_in, n, bm, bn, interp))(
            x, wq, scale.reshape(1, n))
        with exact():
            want = jax.jit(Q.int8_matmul_reference)(x, wq, scale)
        checks.append(close(f"int8_matmul[{m}x{d_in}x{n}]", out, want,
                            1e-2, rtol=1e-2))

    # ---- gathered LoRA ----
    lo = size["lora"]
    x = rnd(lo["b"], lo["s"], lo["d"])
    bank_a = rnd(lo["n"], lo["d"], lo["r"], scale=0.1).at[0].set(0.0)
    bank_b = rnd(lo["n"], lo["r"], lo["n_out"], scale=0.1).at[0].set(0.0)
    ids = jnp.asarray(rs.randint(0, lo["n"], (lo["b"],)), jnp.int32)
    out = jax.jit(Q._lora_gather_call(
        lo["b"], lo["s"], lo["d"], lo["r"], lo["n_out"], interp))(
        ids, x, bank_a, bank_b)
    with exact():
        want = jax.jit(Q.lora_delta_reference)(x, bank_a, bank_b, ids)
    checks.append(close("lora_delta", out, want, 1e-2, rtol=1e-2))

    for rec in checks:
        say(**rec)
    bad = [rec["check"] for rec in checks if not rec["ok"]]
    assert not bad, f"kernels off their references: {bad}"
    return {"kernels_checked": len(checks), "interpret": interp}


# ----------------------------------------------------------------------
# four chips: what exists only across chips, and what it is compared with
# ----------------------------------------------------------------------

def spread_over(tree, n):
    """Every leaf sits on n devices; returns how many are really split
    (not whole on each device)."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    short = [x.shape for x in leaves if len(x.sharding.device_set) != n]
    assert not short, f"leaves not on {n} devices: {short[:4]}"
    return sum(1 for x in leaves if not x.sharding.is_fully_replicated)


def collectives_in(text):
    return {op: text.count(op + "(") + text.count(op + "-start(")
            for op in ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")}


def phase_four_trainer(size, seed, rehearse):
    """The ERNIE-base step under dp=2 x tp=2 against one device, same
    weights, same batch. Dropout is off on both sides: the two programs
    draw their masks differently, and this compares the sharded
    arithmetic, not the random stream."""
    import jax

    from paddle_tpu.parallel import COMMON_TP_RULES, init_mesh

    devs = jax.devices()[:4]
    key = jax.random.PRNGKey(seed)
    ids, labels = make_batch(size, "short", seed)
    n = 4
    losses = {}
    for name, mesh_kw, rules in (
            ("one_device", dict(dp=1, devices=devs[:1]), None),
            ("dp2_tp2", dict(dp=2, tp=2, devices=devs), COMMON_TP_RULES)):
        tr = make_trainer(size, init_mesh(**mesh_kw), seed, 0.0, rules)
        losses[name] = [float(tr.step((ids,), labels, rng=key))
                        for _ in range(n)]
        if rules is not None:
            split = spread_over(tr.params, 4)
            assert split > 0, "no parameter is split over the mesh"
            found = collectives_in(compiled_step_text(tr, ids, labels, key))
            assert found["all-reduce"] > 0, found
        del tr
    a, b = np.asarray(losses["one_device"]), np.asarray(losses["dp2_tp2"])
    assert np.isfinite(a).all() and np.isfinite(b).all(), losses
    # bf16 compute, reductions re-associated across four devices
    assert np.allclose(a, b, rtol=2e-2, atol=2e-2), losses
    return {"losses": losses, "rtol": 2e-2,
            "max_abs_loss_diff": float(np.max(np.abs(a - b))),
            "params_split_over_mesh": split, "collectives": found}


def phase_four_server(size, seed, rehearse):
    """The sharded paged pool (dp=2 x tp=2, layout "gathered") against
    the one-chip paged pool: same weights, same requests."""
    import jax

    from paddle_tpu.parallel import init_mesh
    from paddle_tpu.serving import ServingEngine, ShardedServingEngine

    devs = jax.devices()[:4]
    work = make_requests(size, size["four_prompt_lens"], seed)
    n_new = size["four_new_tokens"]

    with jax.default_device(devs[0]):
        one = ServingEngine(*make_stack(size, seed), paged=True,
                            **size["pool"])
        toks_one = serve(one, work, n_new)
        health_one = pool_health(one)
    del one

    mesh = init_mesh(dp=2, tp=2, devices=devs)
    eng = ShardedServingEngine(*make_stack(size, seed), mesh=mesh,
                               paged=True, **size["pool"])
    toks = serve(eng, work, n_new)
    split_pool = spread_over(eng._state, 4)
    split_params = spread_over(eng._params(), 4)
    assert split_pool > 0 and split_params > 0, (split_pool, split_params)
    health = pool_health(eng)
    text = program_text(eng, "pstep")
    found = collectives_in(text)
    assert found["all-gather"] > 0, found
    # none, by choice: the chip's compiler cannot partition a Mosaic
    # kernel, so a partitioned program takes the XLA compositions
    # (ops.attention.partitioned_trace); the one-chip pool it is
    # compared with runs the kernels
    n_kernels = text.count("tpu_custom_call")
    share, prefixes = agreement(toks, toks_one)
    first = first_token_share(toks, toks_one)
    assert share >= TOKEN_AGREEMENT_FLOOR, (share, prefixes)
    assert first >= FIRST_TOKEN_FLOOR, (first, prefixes)
    return {"one_chip_pool": health_one, "sharded_pool": health,
            "token_agreement_with_one_chip_pool": round(share, 4),
            "agreement_floor": TOKEN_AGREEMENT_FLOOR,
            "first_token_agreement": round(first, 4),
            "common_prefix_per_request": prefixes,
            "pool_leaves_split": split_pool,
            "param_leaves_split": split_params, "collectives": found,
            "tpu_custom_calls_in_sharded_pstep": n_kernels}


# ----------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip phases and nothing else")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU backend, tiny sizes, interpreted kernels")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from paddle_tpu.core import compile_cache

    counts = compile_cache.enable()
    devs = jax.devices()
    want = "cpu" if args.rehearse else "tpu"
    need = 4 if args.four_chips else 1
    if devs[0].platform != want or len(devs) < need:
        # no result line: a run on the wrong device proves nothing
        print(f"chip_smoke: needs {need} {want} device(s), jax found "
              f"{len(devs)} x {devs[0].platform} "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2
    size = TINY if args.rehearse else REAL
    phases = ([("four_trainer", phase_four_trainer),
               ("four_server", phase_four_server)] if args.four_chips else
              [("trainer", phase_trainer), ("server", phase_server),
               ("kernels", phase_kernels)])
    say(smoke="start", rehearsal=args.rehearse, seed=args.seed,
        jax=jax.__version__, phases=[n for n, _ in phases])
    failed = []
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        before = counts.as_dict()
        try:
            rec = fn(size, args.seed, args.rehearse)
        except Exception as e:   # report it, run the other phases, fail
            traceback.print_exc()
            failed.append(name)
            rec = {"error": f"{type(e).__name__}: {e}"[:2000]}
        after = counts.as_dict()
        say(phase=name, ok=name not in failed,
            seconds=round(time.perf_counter() - t0, 2),
            compiled=after["compiled"] - before["compiled"],
            read_from_cache=(after["read_from_cache"]
                             - before["read_from_cache"]),
            peak_bytes_in_use=peak_bytes(devs[0]), **rec)
    # the size limit is the environment's (JAX_COMPILATION_CACHE_MAX_SIZE):
    # where one run writes more than it, the least recently used entries
    # go first and a second run finds nothing of the first
    cache_dir = jax.config.jax_compilation_cache_dir
    say(compile_cache=counts.as_dict(), directory=cache_dir,
        max_bytes=jax.config.jax_compilation_cache_max_size,
        bytes_on_disk=sum(e.stat().st_size for e in os.scandir(cache_dir)
                          if e.is_file()),
        seconds_total=round(time.perf_counter() - t_all, 2))
    if failed:
        print(f"chip_smoke: FAILED phases {failed}", file=sys.stderr)
        return 1
    result = {"ok": True,
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs)}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
