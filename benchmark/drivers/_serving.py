"""What the two serving drivers share: building the pool behind a threaded
ServingServer, the client's record of one request (every time on the
client's clock), the per-iteration watcher, and the reference check."""
from __future__ import annotations

import time

import numpy as np

from paddle_tpu.serving.scheduler import QueueFull

from benchmark.traffic_gen import RequestMaker, rng
from benchmark.util import percentile, say


class Rec:
    """One request as its client sees it. `due` is when it should have
    been sent (open loop) or was sent (closed loop)."""

    __slots__ = ("due", "sent", "token_t", "done_at", "req", "prompt",
                 "memory", "n_new")

    def __init__(self, due, prompt, memory, n_new):
        self.due, self.prompt, self.memory, self.n_new = \
            due, prompt, memory, n_new
        self.sent = self.done_at = self.req = None
        self.token_t = []

    def on_token(self, request, token):       # the request's stream_cb
        self.token_t.append(time.perf_counter())

    def pending(self):
        return self.req is not None and not self.req.future.done()

    def ok(self):
        if self.req is None:            # refused at the door: a failure
            return False
        f = self.req.future
        return f.done() and f.exception() is None and f.result().ok \
            and len(f.result().tokens) == self.n_new


class IterationWatcher:
    """Engine callback: what each iteration left behind."""

    def __init__(self):
        self.t, self.occupancy, self.queue_depth = [], [], []
        self.was_full_at = None
        self.num_slots = None

    def on_iteration(self, info):
        now = time.perf_counter()
        self.t.append(now)
        self.occupancy.append(info["occupancy"])
        self.queue_depth.append(info["queue_depth"])
        if self.was_full_at is None and self.num_slots is not None \
                and info["occupancy"] >= self.num_slots:
            self.was_full_at = now

    def between(self, t0, t1):
        t = np.asarray(self.t)
        keep = (t >= t0) & (t < t1)
        occ = np.asarray(self.occupancy)[keep]
        depth = np.asarray(self.queue_depth)[keep]
        return {"iteration_t": t[keep], "occupancy": occ,
                "queue_depth": depth,
                "iteration_busy": (occ > 0) | (depth > 0)}


class Pool:
    """The system under test, ready to serve: engine built and every
    program of the mix's buckets compiled (or read from the cache)."""

    def __init__(self, run):
        from paddle_tpu.serving import ServingServer
        from paddle_tpu.serving import tracing as serving_tracing

        cfg, mix = run.config, run.traffic
        self.run = run
        self.watcher = IterationWatcher()
        self.engine = run.builder.build(cfg, run.seed, run.devices,
                                        callbacks=[self.watcher])
        self.watcher.num_slots = self.engine.num_slots
        self.mem_shape = tuple(cfg["assumed"]["memory_shape"])
        report = self.engine.precompile(
            self.mem_shape, prompt_buckets=tuple(mix["prompt_buckets"]))
        say(precompile=report,
            memory_ledger=self.engine.metrics.snapshot().get("memory"))
        self.maker = RequestMaker(mix, run.seed, cfg["vocab_size"],
                                  self.mem_shape)
        # host spans only in the traced run: end-to-end numbers are taken
        # with tracing off
        self.tracer = serving_tracing.start_session() if run.trace else None
        self.server = ServingServer(self.engine,
                                    max_queue=cfg["pool"]["max_queue"])
        self.on_done = None
        self.refused = 0

    def submit(self, due):
        prompt, mem, n_new = self.maker.next()
        rec = Rec(due, prompt, mem, n_new)
        rec.sent = time.perf_counter()
        try:
            rec.req = self.server.submit(
                prompt, mem, max_new_tokens=n_new, eos_id=None,
                stream_cb=rec.on_token)
        except QueueFull:
            self.refused += 1
            return rec
        if self.on_done is not None:
            rec.req.future.add_done_callback(
                lambda f, rec=rec: self.on_done(rec))
        return rec

    def snapshot(self):
        return self.engine.metrics.snapshot()

    def stop(self, drain):
        from paddle_tpu.serving import tracing as serving_tracing

        self.server.shutdown(drain=drain, timeout=600)
        if self.tracer is not None:
            self.run.facts["spans"] = [
                (s.name, s.trace_id, s.t0, s.t1)
                for s in self.tracer.spans() if s.t1 is not None]
            serving_tracing.end_session()
        temps = {}
        for key, fn in dict.items(self.engine._compiled):
            fn = getattr(fn, "raw", fn)
            fn = getattr(fn, "compiled", fn)
            analysis = getattr(fn, "memory_analysis", lambda: None)()
            if analysis is not None:
                temps[str(key)] = int(analysis.temp_size_in_bytes)
        say(program_temp_bytes=temps)
        self.run.facts["program_temp_bytes"] = max(temps.values(), default=0)
        ok, health = self.run.builder.pool_health(self.engine)
        say(check="pool_health", ok=ok, **health)
        return ok


def reference_check(run, pool, recs):
    """A seeded sample of finished requests, teacher-forced through the
    plain float32 reference: every generated token must be the reference's
    own choice or within the stated margin of it."""
    import jax
    import jax.numpy as jnp

    chk, mix = run.config["check"], run.traffic
    done = [r for r in recs if r.ok()]
    k = int(chk["sample_requests"])
    if len(done) < k:
        say(check="reference_tokens", ok=False,
            error=f"only {len(done)} finished requests to sample {k} from")
        return False
    pick = rng(run.seed, 8).choice(len(done), k, replace=False)
    length = int(mix["prompt_len"]["max"]) + int(mix["new_tokens"]["max"])
    toks = np.zeros((k, length), np.int32)
    n_valid = np.zeros((k,), np.int32)
    mems = np.zeros((k,) + pool.mem_shape, np.float32)
    spans = []
    for row, i in enumerate(pick):
        r = done[i]
        gen = np.asarray(r.req.future.result().tokens, np.int32)
        p = len(r.prompt)
        toks[row, :p], toks[row, p:p + len(gen)] = r.prompt, gen
        n_valid[row], mems[row] = p + len(gen), r.memory
        spans.append((p - 1, p - 1 + len(gen)))
    fn = jax.jit(lambda pr, t, n, m: run.reference.token_margins(
        pr, t, n, m, run.config))
    short, is_top = fn(pool.engine._params(), jnp.asarray(toks),
                       jnp.asarray(n_valid), jnp.asarray(mems))
    short, is_top = np.asarray(short), np.asarray(is_top)
    worst, hits, total = 0.0, 0, 0
    for row, (a, b) in enumerate(spans):
        worst = max(worst, float(short[row, a:b].max()))
        hits += int(is_top[row, a:b].sum())
        total += b - a
    ok = bool(np.isfinite(short).all()) and worst <= chk["margin_sigma"]
    say(check="reference_tokens", ok=ok, requests=k, tokens=total,
        argmax_match_share=hits / max(total, 1),
        worst_shortfall_sigma=worst, margin_sigma=chk["margin_sigma"])
    return ok


def summary_ms(seconds):
    """Sample count, median and 95th percentile of host-clock seconds, in
    ms, for an earlier line (None where there is no sample)."""
    if len(seconds) == 0:
        return {"n": 0, "p50": None, "p95": None}
    return {"n": len(seconds), "p50": percentile(seconds, 50) * 1e3,
            "p95": percentile(seconds, 95) * 1e3}


def client_latencies(recs):
    """(ttft seconds from DUE, pooled gaps between consecutive tokens)."""
    ttft = [r.token_t[0] - r.due for r in recs if r.token_t]
    gaps = [g for r in recs for g in np.diff(r.token_t)]
    return ttft, gaps
