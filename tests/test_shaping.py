"""Traffic-shaping scheduler subsystem: chunked prefill, SLO classes,
fairness-aware preemption.

The two bug classes this feature invites get bit-match soaks against
uninterrupted runs: (1) a k-wide masked page write clobbering a chunk
boundary — chunked prefill must BIT-MATCH whole-prompt prefill across
chunk-size x page-size parity (spec on and off), under the armed
retrace sentinel; (2) preemption landing mid-spec-replay — a
preempted-and-resumed request (including a re-preempt DURING replay)
must bit-match an unpreempted twin, with resume riding the prefix
cache (`prefill_count` proves no re-prefill). Plus the scheduler-side
units: WFQ ordering/lag, class priority, watermark admission gating,
`ServingMetrics.reset()`, the "slo" snapshot section, and the chaos
cells for faults mid-chunk-sequence and mid-preemption.
"""
import time

import numpy as np
import pytest

from paddle_tpu import nn
from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                             TransformerDecoderLayer)
from paddle_tpu.serving import (BATCH, INTERACTIVE, QueueFull, Request,
                                Scheduler, ServingEngine,
                                ServingMetrics, ShapingScheduler,
                                SLOClass, retrace_sentinel)
from paddle_tpu.serving.metrics import SNAPSHOT_DOCS, flatten_snapshot
from paddle_tpu.testing import faults
from paddle_tpu.text.generation import bucket_size, generate_eager


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


def _small_stack(seed=7, D=32, H=2, V=17, layers=2):
    np.random.seed(seed)
    layer = TransformerDecoderLayer(D, H, 64, dropout=0.0)
    dec = TransformerDecoder(layer, layers)
    dec.eval()
    embed = nn.Embedding(V, D)
    proj = nn.Linear(D, V)
    return dec, embed, proj, D, V


def _mk_request(rs, D, V, pmin=1, pmax=6, nmax=10, **kw):
    P = int(rs.randint(pmin, pmax + 1))
    prompt = rs.randint(2, V, (P,)).astype(np.int32)
    prompt[0] = 0
    mem_seed = int(prompt.sum()) * 131 + P
    mem = np.random.RandomState(mem_seed).randn(4, D).astype("f4")
    n = int(rs.randint(2, nmax + 1))
    return Request(prompt, mem, max_new_tokens=n, eos_id=1, **kw)


def _drive(eng, reqs, max_iterations=5000, sched=None):
    if sched is None:
        sched = Scheduler(max_queue=len(reqs) + 8)
    for r in reqs:
        sched.submit(r)
    eng.serve_until_idle(sched, max_iterations=max_iterations)
    return [r.result(timeout=5) for r in reqs]


def _eager_reference(stack, r):
    import jax.numpy as jnp

    dec, embed, proj, D, V = stack
    toks, lens = generate_eager(
        dec, embed, proj, jnp.asarray(r.memory[None]),
        jnp.asarray(r.prompt[None]),
        jnp.asarray([r.prompt.shape[0]], jnp.int32), bos_id=0,
        eos_id=1, max_new_tokens=r.max_new_tokens,
        pad_prompt_to=bucket_size(r.prompt.shape[0]))
    return np.asarray(toks)[0][:int(np.asarray(lens)[0])]


def _specs(seed, n, D, V, pmin=1, pmax=14, nmax=8):
    rs = np.random.RandomState(seed)
    return [(r.prompt, r.memory, r.max_new_tokens)
            for r in (_mk_request(rs, D, V, pmin=pmin, pmax=pmax,
                                  nmax=nmax) for _ in range(n))]


def _reqs(specs, **kw):
    return [Request(p.copy(), m, max_new_tokens=n, eos_id=1, **kw)
            for p, m, n in specs]


# ----------------------------------------------------------------------
# bug class 1: chunk boundaries — chunked == whole-prompt, bit for bit
# ----------------------------------------------------------------------

def test_chunked_prefill_bitmatch_dense():
    """Dense pool: chunked prefill bit-matches whole-prompt prefill
    AND the eager oracle for every request, under the armed retrace
    sentinel, with ONE cjoin compile per chunk bucket (never per
    prompt)."""
    stack = _small_stack(seed=21)
    dec, embed, proj, D, V = stack
    specs = _specs(22, 8, D, V)
    plain = ServingEngine(dec, embed, proj, num_slots=3, max_len=32)
    res_p = _drive(plain, _reqs(specs))
    eng = ServingEngine(dec, embed, proj, num_slots=3, max_len=32,
                        prefill_chunk=4)
    retrace_sentinel(eng).__enter__()   # disarmed by conftest teardown
    reqs = _reqs(specs)
    res_c = _drive(eng, reqs)
    for a, b, r in zip(res_p, res_c, reqs):
        assert a.ok and b.ok
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(b.tokens, _eager_reference(
            stack, r)[:len(b.tokens)])
    assert eng.metrics.chunked_prefills > 0
    assert eng.metrics.chunks > eng.metrics.chunked_prefills
    cjoins = {k: v for k, v in eng.trace_counts.items()
              if k[0] == "cjoin"}
    assert cjoins and set(cjoins.values()) == {1}, cjoins


@pytest.mark.parametrize("chunk,page,spec_k", [
    (4, 4, 0), (4, 4, 4), (8, 4, 0), (8, 8, 4)])
def test_chunked_prefill_bitmatch_paged(chunk, page, spec_k):
    """Paged pool, chunk-size x page-size parity grid, spec off and
    on: chunked output bit-matches the whole-prompt twin; no page
    leaks; k-wide masked verify writes never clobber a chunk boundary
    (the bit-match would catch exactly that)."""
    stack = _small_stack(seed=31)
    dec, embed, proj, D, V = stack
    specs = _specs(32, 8, D, V)
    kw = dict(paged=True, page_size=page, num_pages=64)
    if spec_k:
        kw["spec_k"] = spec_k
    plain = ServingEngine(dec, embed, proj, num_slots=3, max_len=32,
                          **kw)
    res_p = _drive(plain, _reqs(specs))
    eng = ServingEngine(dec, embed, proj, num_slots=3, max_len=32,
                        prefill_chunk=chunk, **kw)
    retrace_sentinel(eng).__enter__()
    res_c = _drive(eng, _reqs(specs))
    for a, b in zip(res_p, res_c):
        assert a.ok and b.ok
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert eng.metrics.chunked_prefills > 0
    pcjoins = {k: v for k, v in eng.trace_counts.items()
               if k[0] == "pcjoin"}
    assert pcjoins and set(pcjoins.values()) == {1}, pcjoins
    eng.flush_prefix_cache()
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages


def test_prefill_chunk_knob_validation():
    dec, embed, proj, D, V = _small_stack(seed=5)
    with pytest.raises(ValueError, match="power of two"):
        ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                      prefill_chunk=6)
    with pytest.raises(ValueError, match="multiple of"):
        ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                      paged=True, page_size=8, prefill_chunk=4)


# ----------------------------------------------------------------------
# bug class 2: preemption / resume — bit-identical to unpreempted
# ----------------------------------------------------------------------

@pytest.mark.parametrize("spec_k", [0, 4])
def test_preempt_resume_bitmatch_and_attach(spec_k):
    """Batch slots preempted for interactive arrivals resume bit-
    identical to an unpreempted twin (spec on and off). Resume rides
    the prefix cache: prefill_count stays at the cold prefills —
    no preempted prompt is ever re-prefilled."""
    stack = _small_stack(seed=41)
    dec, embed, proj, D, V = stack
    kw = dict(paged=True, page_size=4, num_pages=48)
    if spec_k:
        kw["spec_k"] = spec_k
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        **kw)
    # batch decode budgets pinned LONG so the slots are still busy
    # when the interactive wave lands — preemption must trigger
    bspecs = [(p, m, 12) for p, m, _ in _specs(42, 3, D, V,
                                               pmin=4, pmax=8)]
    ispecs = _specs(43, 3, D, V, pmin=1, pmax=4, nmax=6)
    batch = _reqs(bspecs, slo="batch")
    inter = _reqs(ispecs, slo="interactive")
    sched = ShapingScheduler(max_queue=32, metrics=eng.metrics)
    for r in batch:
        sched.submit(r)
    for _ in range(2):          # fill both slots with batch work
        eng.run_iteration(sched)
    cold_prefills = eng.prefill_count
    for r in inter:
        sched.submit(r)
    eng.serve_until_idle(sched, max_iterations=5000)
    res = [r.result(timeout=5) for r in batch + inter]
    assert all(r.ok for r in res)
    assert eng.metrics.preemptions >= 1
    assert eng.metrics.resumes == eng.metrics.preemptions
    assert eng.metrics.replay_tokens > 0
    # every request's prompt is cold at most once (prefill or chunk) —
    # the two batch requests already resident, the third still queued
    # behind them when the interactive wave lands, and the interactive
    # ones — and NO resume re-prefilled
    assert cold_prefills == 2
    assert eng.prefill_count <= len(batch) + len(inter)
    # unpreempted twin, one class, same requests
    twin = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                         **kw)
    res_t = _drive(twin, _reqs(bspecs + ispecs))
    for a, b in zip(res, res_t):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    eng.flush_prefix_cache()
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages


def test_preempt_during_spec_replay_bitmatch():
    """The nastier half of bug class 2: a SECOND preemption lands
    while the resumed request is still replaying already-delivered
    tokens through the spec stepper. The replay counter must re-arm to
    the full delivered count and the final tokens still bit-match an
    unpreempted twin."""
    stack = _small_stack(seed=51)
    dec, embed, proj, D, V = stack
    # spec_k=2 bounds absorption to 3 replay tokens per decode step, so
    # preempting at >= 5 delivered tokens GUARANTEES the resume is
    # still mid-replay after its first post-join iteration
    kw = dict(paged=True, page_size=4, num_pages=48, spec_k=2)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        **kw)
    spec = None
    for seed in range(52, 64):   # a prompt that never hits eos early
        p, m, n = _specs(seed, 1, D, V, pmin=5, pmax=8, nmax=8)[0]
        cand = Request(p.copy(), m, max_new_tokens=8, eos_id=1)
        if len(_eager_reference(stack, cand)) >= 8:
            spec = [(p, m, 8)]
            break
    assert spec is not None, "no eos-free candidate prompt found"
    r = _reqs(spec, slo="batch")[0]
    sched = ShapingScheduler(max_queue=8, metrics=eng.metrics)
    sched.submit(r)
    while len(r.tokens) < 5:
        eng.run_iteration(sched)
    # first preemption: mid-decode
    s = r.slot
    assert eng.can_preempt(s)
    assert eng.preempt_slot(s, eng.clock()) is r
    assert r._replay == len(r.tokens) > 0
    sched.requeue_preempted(r)
    # resume, then preempt AGAIN while the replay is still draining
    eng.run_iteration(sched)                 # re-join (attach)
    assert r.slot is not None
    while r._replay == 0 or r.state != "RUNNING":
        eng.run_iteration(sched)             # reach mid-replay
        if r.state == "DONE":
            pytest.fail("finished before a mid-replay preempt landed")
    n_before = len(r.tokens)
    assert eng.preempt_slot(r.slot, eng.clock()) is r
    assert r._replay == n_before             # re-armed to FULL count
    sched.requeue_preempted(r)
    eng.serve_until_idle(sched, max_iterations=2000)
    out = r.result(timeout=5)
    assert out.ok and r._preemptions == 2
    twin = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                         **kw)
    res_t = _drive(twin, _reqs(spec))[0]
    np.testing.assert_array_equal(out.tokens, res_t.tokens)
    assert eng.metrics.resumes == eng.metrics.preemptions == 2


# ----------------------------------------------------------------------
# the shaper itself: class priority, WFQ, gating (no engine needed)
# ----------------------------------------------------------------------

def _tiny_req(tenant=None, slo=None, P=4, n=4, clock=None):
    prompt = np.zeros(P, np.int32)
    return Request(prompt, None, max_new_tokens=n, eos_id=1,
                   adapter=tenant, slo=slo)


def test_class_priority_and_deadline_order():
    """Interactive always pops before queued batch work regardless of
    arrival order; within a class the earliest TTFT deadline wins."""
    clk = FakeClock()
    sched = ShapingScheduler(max_queue=16, clock=clk)
    b1 = sched.submit(_tiny_req(slo="batch"))
    clk.advance(0.1)
    b2 = sched.submit(_tiny_req(slo="batch"))
    clk.advance(0.1)
    i1 = sched.submit(_tiny_req(slo="interactive"))
    assert sched.depth() == 3
    assert sched.pop_ready(clk()) is i1
    assert sched.pop_ready(clk()) is b1     # earlier deadline first
    assert sched.pop_ready(clk()) is b2
    assert sched.pop_ready(clk()) is None
    # string class names resolved + stamped at submit
    assert b1.slo is BATCH and i1.slo is INTERACTIVE
    with pytest.raises(ValueError, match="unknown SLO class"):
        sched.submit(_tiny_req(slo="gold"))


def test_wfq_weights_and_lag():
    """Two tenants, weights 2:1, equal-cost batch backlogs: pops
    interleave ~2:1 toward the heavy tenant and the light tenant's
    virtual-time lag exceeds the heavy one's while backlogged."""
    clk = FakeClock()
    sched = ShapingScheduler(max_queue=64, clock=clk,
                             tenant_weights={"a": 2.0, "b": 1.0})
    for _ in range(6):
        sched.submit(_tiny_req(tenant="a", slo="batch"))
        sched.submit(_tiny_req(tenant="b", slo="batch"))
    order = []
    for _ in range(9):
        order.append(sched.pop_ready(clk()).adapter)
    # first 9 pops: tenant a (weight 2) gets ~2x tenant b's service
    assert order.count("a") == 6 and order.count("b") == 3, order
    lag = sched.wfq_lag_by_tenant()
    assert lag["b"] >= lag["a"] >= 0.0
    # push_front returns ahead of everything, uncharged
    r = sched.pop_ready(clk())
    sched.push_front(r)
    assert sched.pop_ready(clk()) is r
    order.append(r.adapter)
    # drain: the light tenant's extra per-pop charge leaves its finish
    # tag leading the pool virtual time once its backlog is served
    while True:
        nxt = sched.pop_ready(clk())
        if nxt is None:
            break
        order.append(nxt.adapter)
    assert order.count("a") == 6 and order.count("b") == 6
    lag = sched.wfq_lag_by_tenant()
    assert lag.get("b", 0.0) > lag.get("a", 0.0) == 0.0


def test_admission_gate_watermark_and_drain():
    """Batch admission closes while the HBM ledger sits above the
    watermark; interactive keeps flowing. Drain closes everything;
    abort_queued empties in shaping order."""
    m = ServingMetrics()
    m.set_memory_provider(lambda: None, budget_bytes=100,
                          watermark_frac=0.9)
    clk = FakeClock()
    sched = ShapingScheduler(max_queue=16, clock=clk, metrics=m)
    m.check_memory_watermark(95)            # above: gate arms
    assert m.watermark_exceeded()
    with pytest.raises(QueueFull, match="admission gated"):
        sched.submit(_tiny_req(slo="batch"))
    i1 = sched.submit(_tiny_req(slo="interactive"))   # unaffected
    m.check_memory_watermark(10)            # back under: gate opens
    b1 = sched.submit(_tiny_req(slo="batch"))
    assert sched.depth() == 2
    sched.drain()
    with pytest.raises(RuntimeError, match="draining"):
        sched.submit(_tiny_req(slo="interactive"))
    dead = sched.abort_queued("shutdown", clk())
    assert dead == [i1, b1]
    assert all(r.finish_reason == "shutdown" for r in dead)


def test_queue_full_and_pop_all():
    clk = FakeClock()
    sched = ShapingScheduler(max_queue=2, clock=clk)
    a = sched.submit(_tiny_req(tenant="x", slo="batch"))
    b = sched.submit(_tiny_req(tenant="y", slo="interactive"))
    with pytest.raises(QueueFull, match="high-water"):
        sched.submit(_tiny_req(slo="batch"))
    assert set(sched.pop_all()) == {a, b} and sched.depth() == 0


# ----------------------------------------------------------------------
# metrics: reset() + the "slo" snapshot section
# ----------------------------------------------------------------------

def test_metrics_reset_keeps_identity():
    m = ServingMetrics()
    provider_called = []
    m.set_memory_provider(
        lambda: provider_called.append(1) or {"weights_bytes": 8,
                                              "pool_bytes": 8,
                                              "in_use_bytes": 16},
        budget_bytes=1000)
    m.record_submit()
    m.record_preemption()
    m.record_chunk()
    m.record_prefix("whole", matched_tokens=8, prompt_tokens=8)
    m.record_slo_finish("interactive", 0.1, 0.05, 0.5, 0.1)
    snap = m.snapshot()
    assert snap["requests"]["submitted"] == 1
    assert snap["slo"]["preemptions"] == 1
    m.reset()
    snap = m.snapshot()
    assert snap["requests"]["submitted"] == 0
    assert "slo" not in snap and "prefix" not in snap
    # identity wiring survives: ledger provider + budget still armed
    assert snap["memory"]["budget_bytes"] == 1000
    assert provider_called


def test_slo_snapshot_schema_covered_by_docs():
    """Every key the "slo" section can emit is documented in
    SNAPSHOT_DOCS (the schema-of-record contract test_tracing pins for
    the full snapshot)."""
    m = ServingMetrics()
    m.record_chunked_join()
    m.record_chunk()
    m.record_preemption()
    m.record_resume()
    m.record_replay_token()
    m.record_slo_finish("interactive", 0.1, 0.05, 0.5, 0.1)
    m.record_slo_finish("batch", 5.0, 0.5, 30.0, 1.0)
    m.set_wfq_lag({"base": 12.5})
    flat = flatten_snapshot(m.snapshot())
    slo_keys = {k for k in flat if k.startswith("slo.")}
    assert slo_keys == {k for k in SNAPSHOT_DOCS
                        if k.startswith("slo.")}, slo_keys
    assert flat["slo.ttft_attainment"] == {"interactive": 1.0,
                                           "batch": 1.0}
    assert flat["slo.wfq_lag_by_tenant"] == {"base": 12.5}


def test_engine_records_slo_attainment():
    """A classed request finishing on the engine lands in the per-
    class attainment split (the engine computes TTFT/TPOT against the
    class targets at finish)."""
    dec, embed, proj, D, V = _small_stack(seed=61)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32)
    rs = np.random.RandomState(62)
    reqs = [_mk_request(rs, D, V, slo="interactive") for _ in range(2)]
    sched = ShapingScheduler(max_queue=8, metrics=eng.metrics)
    _drive(eng, reqs, sched=sched)
    snap = eng.metrics.snapshot()
    att = snap["slo"]["ttft_attainment"]
    assert "interactive" in att and 0.0 <= att["interactive"] <= 1.0
    assert snap["slo"]["preemptions"] == 0


# ----------------------------------------------------------------------
# chaos: faults mid-chunk-sequence and mid-preemption (tier-1 cells)
# ----------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_fault_mid_chunk_sequence():
    """A raise on serving.prefill_chunk (every 3rd chunk) mid-sequence:
    the victim's future resolves with the error, its pages are
    released, survivors complete and BIT-MATCH the eager oracle, the
    free list returns to initial, and the pool revives."""
    stack = _small_stack(seed=71)
    dec, embed, proj, D, V = stack
    eng = ServingEngine(dec, embed, proj, num_slots=3, max_len=32,
                        paged=True, page_size=4, num_pages=64,
                        prefill_chunk=4, max_attempts=1,
                        backoff_base_s=0.0)
    specs = _specs(72, 6, D, V, pmin=9, pmax=14)
    reqs = _reqs(specs)
    with faults.inject("serving.prefill_chunk", on="every", k=3) as inj:
        sched = Scheduler(max_queue=32)
        for r in reqs:
            sched.submit(r)
        eng.serve_until_idle(sched, max_iterations=5000)
        assert inj.fired
    ok, failed = [], []
    for r in reqs:
        assert r.future.done()
        (ok if r.finish_reason in ("eos", "length") else failed).append(r)
    assert failed, "the armed plan never killed a chunk sequence"
    assert ok, "no survivors"
    for r in ok:
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32),
            _eager_reference(stack, r)[:len(r.tokens)])
    eng.flush_prefix_cache()
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages
    # pool revives: clean chunked request completes
    clean = _reqs(_specs(73, 1, D, V, pmin=9, pmax=12))
    assert _drive(eng, clean)[0].ok


@pytest.mark.chaos
def test_chaos_fault_mid_preemption():
    """A raise on serving.preempt: the fault fires BEFORE any
    mutation, so the aborted preemption leaves the victim running —
    every request still completes OK, pages leak-free, survivors
    bit-match the eager oracle."""
    stack = _small_stack(seed=81)
    dec, embed, proj, D, V = stack
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        paged=True, page_size=4, num_pages=48)
    batch = _reqs([(p, m, 12) for p, m, _ in
                   _specs(82, 2, D, V, pmin=5, pmax=8)], slo="batch")
    inter = _reqs(_specs(83, 3, D, V, pmin=1, pmax=4, nmax=6),
                  slo="interactive")
    sched = ShapingScheduler(max_queue=32, metrics=eng.metrics)
    with faults.inject("serving.preempt", on="nth", n=1,
                       max_fires=1) as inj:
        for r in batch:
            sched.submit(r)
        for _ in range(2):
            eng.run_iteration(sched)
        for r in inter:
            sched.submit(r)
        eng.serve_until_idle(sched, max_iterations=5000)
        assert inj.fired
    for r in batch + inter:
        assert r.result(timeout=5).ok
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32),
            _eager_reference(stack, r)[:len(r.tokens)])
    assert eng.metrics.errors >= 1        # the aborted attempt
    # the NEXT attempt (plan exhausted) succeeded: preemption recovered
    assert eng.metrics.preemptions >= 1
    eng.flush_prefix_cache()
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages


# ----------------------------------------------------------------------
# threaded frontend: ServingServer carries a caller-built scheduler
# ----------------------------------------------------------------------

def test_server_scheduler_and_slo_passthrough():
    """`ServingServer(eng, scheduler=ShapingScheduler(...))` runs the
    shaping policy on the server's own loop thread, and `submit(slo=)`
    forwards the class name — resolved at admission, visible on the
    Request. The FIFO default stays when scheduler is omitted."""
    from paddle_tpu.serving import ServingServer
    stack = _small_stack(seed=91)
    dec, embed, proj, D, V = stack
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        prefill_chunk=4)
    sched = ShapingScheduler(max_queue=16, metrics=eng.metrics)
    server = ServingServer(eng, scheduler=sched)
    assert server.scheduler is sched
    try:
        specs = _specs(92, 4, D, V, pmin=2, pmax=10)
        reqs = [server.submit(p.copy(), m, max_new_tokens=n, eos_id=1,
                              slo=("interactive" if i % 2 else "batch"))
                for i, (p, m, n) in enumerate(specs)]
        res = [r.result(timeout=60) for r in reqs]
        assert all(r.ok for r in res)
        # admission resolved the class names onto the requests
        assert [r.slo.name for r in reqs] == \
            ["batch", "interactive"] * 2
        for r in reqs:
            np.testing.assert_array_equal(
                np.asarray(r.result().tokens, np.int32),
                _eager_reference(stack, r))
        with pytest.raises(ValueError, match="unknown SLO class"):
            server.submit(specs[0][0].copy(), specs[0][1],
                          max_new_tokens=2, eos_id=1, slo="platinum")
        assert eng.metrics.chunked_prefills >= 1   # P>4 went chunked
    finally:
        server.shutdown(drain=True, timeout=60)
    # default stays FIFO when no scheduler is passed
    fifo_server = ServingServer(eng, start=False)
    assert isinstance(fifo_server.scheduler, Scheduler)
    assert not isinstance(fifo_server.scheduler, ShapingScheduler)
