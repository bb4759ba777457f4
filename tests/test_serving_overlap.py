"""One decode step in flight (PR 32): a loop that owns consecutive
iterations (`ServingServer._loop`, `serve_until_idle`: `run_ahead`)
enqueues step N+1 before it reads step N's tokens.

Covers: (a) the token streams are the series loop's, request by request,
each token once and in order, through `ServingServer` (dense and paged,
mixed lengths, ends by `eos_id` and by count, a slot re-admitted in the
iteration its request ends); (b) a request cancelled or past its
deadline with a token in flight gets nothing after its finish and leaks
no page; (c) speculation, chunked prefill, a pending disaggregated
prefill, preemption, the artifact engine and a retried step run in
series and say so in `snapshot()["pipeline"]`; (d) a failure that
surfaces where the tokens are read evicts as a failed step does and the
pool serves on; (e) `run_iteration()` alone delivers what it computed;
(f) each program traces once and no span name is lost.
"""
import time

import numpy as np
import pytest

from paddle_tpu import nn
from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                             TransformerDecoderLayer)
from paddle_tpu.profiler import trace as T
from paddle_tpu.serving import (ArtifactServingEngine, Request, Scheduler,
                                ServingCallback, ServingEngine,
                                ServingServer, retrace_sentinel)
from paddle_tpu.serving.shaping import ShapingScheduler
from paddle_tpu.testing import faults


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


_POOLS = {"dense": {}, "paged": dict(paged=True, page_size=4)}


def _engine(stack, pool="dense", num_slots=3, max_len=32, **kw):
    return ServingEngine(*stack[:3], num_slots=num_slots, max_len=max_len,
                         **_POOLS[pool], **kw)


def _specs(seed, n, D, V, pmin=1, pmax=6, nmin=2, nmax=10):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        P = int(rs.randint(pmin, pmax + 1))
        prompt = rs.randint(2, V, (P,)).astype(np.int32)
        prompt[0] = 0
        mem = np.random.RandomState(int(prompt.sum()) * 131 + P) \
            .randn(4, D).astype("f4")
        out.append((prompt, mem, int(rs.randint(nmin, nmax + 1))))
    return out


def _reqs(specs, eos_id=1, **kw):
    return [Request(p.copy(), m, max_new_tokens=n, eos_id=eos_id, **kw)
            for p, m, n in specs]


def _series(eng, reqs, sched=None):
    """The series loop: `run_iteration` by hand, every step read before
    the next is enqueued."""
    sched = sched or Scheduler(max_queue=len(reqs) + 8)
    for r in reqs:
        sched.submit(r)
    n = 0
    while sched.depth() > 0 or eng.occupancy() > 0:
        eng.run_iteration(sched)
        assert eng._flight is None
        n += 1
        assert n < 5000
    return [r.result(timeout=5) for r in reqs]


def _pipeline(eng):
    return eng.metrics.snapshot()["pipeline"]


def _leak_free(eng):
    if hasattr(eng, "_alloc"):
        eng.flush_prefix_cache()
        eng._alloc.check()
        assert eng._alloc.pages_free == eng.num_pages


# ----------------------------------------------------------------------
# (a) the same tokens, each once and in order
# ----------------------------------------------------------------------

@pytest.mark.parametrize("pool", ["dense", "paged"])
def test_server_streams_match_the_series_loop(pool):
    """Mixed lengths through a threaded ServingServer: every request's
    streamed tokens are the series loop's, in order and once; some end
    by `eos_id` (found a step late: their extra slot-step is counted
    and its token dropped), some by count; most steps went ahead."""
    stack = _small_stack(seed=301)
    D, V = stack[3], stack[4]
    specs = _specs(302, 24, D, V)
    want = _series(_engine(stack, pool), _reqs(specs))
    eng = _engine(stack, pool)
    retrace_sentinel(eng).__enter__()   # disarmed by conftest teardown
    streams = [[] for _ in specs]
    srv = ServingServer(eng, max_queue=64)
    got = [srv.submit(p, m, max_new_tokens=n, eos_id=1,
                      stream_cb=lambda r, t, i=i: streams[i].append(t))
           for i, (p, m, n) in enumerate(specs)]
    srv.shutdown(drain=True, timeout=300)
    reasons = set()
    for w, g, st in zip(want, got, streams):
        res = g.result(timeout=5)
        assert res.ok and res.finish_reason == w.finish_reason
        np.testing.assert_array_equal(res.tokens, w.tokens)
        assert st == list(w.tokens)
        reasons.add(res.finish_reason)
    assert reasons == {"eos", "length"}
    pipe = _pipeline(eng)
    assert pipe["steps_ahead"] > pipe["decode_steps"] // 2
    assert pipe["steps_ahead"] + sum(pipe["series_steps"].values()) \
        == pipe["decode_steps"]
    assert set(pipe["series_steps"]) <= {"idle"}
    n_eos = sum(w.finish_reason == "eos" and len(w.tokens) > 1
                for w in want)
    assert 1 <= pipe["late_slot_steps"] <= n_eos + len(want)
    assert eng._flight is None and not eng.running()
    assert len([k for k in eng.trace_counts
                if k[0] in ("step", "pstep")]) == 1
    assert set(eng.trace_counts.values()) == {1}
    _leak_free(eng)


@pytest.mark.parametrize("pool", ["dense", "paged"])
def test_slot_is_readmitted_in_the_iteration_its_request_ends(pool):
    """An end by count is known a step ahead: the request gives its
    slot up with its last token in flight, the next one JOINS in the
    iteration that reads that token (`on_join` before `on_finish`), and
    no slot-step is wasted."""
    stack = _small_stack(seed=311)
    D, V = stack[3], stack[4]
    specs = [(p, m, 4) for p, m, _ in _specs(312, 3, D, V)]
    want = _series(_engine(stack, pool, num_slots=1),
                   _reqs(specs, eos_id=None))
    events = []

    class Log(ServingCallback):
        def on_join(self, r, s):
            events.append(("join", r.id))

        def on_finish(self, r):
            events.append(("finish", r.id))

    eng = _engine(stack, pool, num_slots=1, callbacks=[Log()])
    reqs = _reqs(specs, eos_id=None)
    sched = Scheduler(max_queue=8)
    for r in reqs:
        sched.submit(r)
    eng.serve_until_idle(sched, max_iterations=200)
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(r.result(timeout=5).tokens, w.tokens)
    a, b, c = (r.id for r in reqs)
    assert events == [("join", a), ("join", b), ("finish", a),
                      ("join", c), ("finish", b), ("finish", c)]
    pipe = _pipeline(eng)
    assert pipe["late_slot_steps"] == 0
    # token 0 comes from the join: three steps a request, nine in all,
    # and only the first found nothing unread
    assert pipe["decode_steps"] == 9 and pipe["steps_ahead"] == 8
    assert pipe["series_steps"] == {"idle": 1}
    _leak_free(eng)


def test_one_token_requests_never_enter_a_step():
    """`max_new_tokens=1`: token 0 is the last, which is known at the
    join, so the joiner is left out of the step enqueued before token 0
    is read."""
    stack = _small_stack(seed=321)
    D, V = stack[3], stack[4]
    specs = [(p, m, 1) for p, m, _ in _specs(322, 4, D, V)]
    want = _series(_engine(stack), _reqs(specs, eos_id=None))
    eng = _engine(stack)
    reqs = _reqs(specs, eos_id=None)
    sched = Scheduler(max_queue=8)
    for r in reqs:
        sched.submit(r)
    eng.serve_until_idle(sched, max_iterations=50)
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(r.result(timeout=5).tokens, w.tokens)
    pipe = _pipeline(eng)
    assert pipe["decode_steps"] == 0 and pipe["late_slot_steps"] == 0


def test_eos_found_late_costs_one_slot_step_and_no_token():
    """A request that ends by `eos_id` is found when its token is read,
    a step late: exactly the series tokens reach the caller, the extra
    slot-step is counted, the pages come back."""
    stack = _small_stack(seed=331)
    D, V = stack[3], stack[4]
    spec = None
    for p, m, _ in _specs(332, 40, D, V):
        probe = _series(_engine(stack, "paged", num_slots=1),
                        [Request(p.copy(), m, max_new_tokens=12,
                                 eos_id=None)])[0]
        toks = list(probe.tokens)
        if len(set(toks[2:8])) > 1 and toks[4] not in toks[:4]:
            spec = (p, m, toks[4])       # ends at its fifth token
            break
    assert spec is not None, "no prompt whose fifth token is new"
    p, m, eos = spec
    want = _series(_engine(stack, "paged", num_slots=1),
                   [Request(p.copy(), m, max_new_tokens=12, eos_id=eos)])[0]
    assert want.finish_reason == "eos" and len(want.tokens) == 5
    eng = _engine(stack, "paged", num_slots=1)
    r = Request(p.copy(), m, max_new_tokens=12, eos_id=eos)
    sched = Scheduler(max_queue=2)
    sched.submit(r)
    eng.serve_until_idle(sched, max_iterations=50)
    res = r.result(timeout=5)
    assert res.finish_reason == "eos"
    np.testing.assert_array_equal(res.tokens, want.tokens)
    pipe = _pipeline(eng)
    assert pipe["late_slot_steps"] == 1
    assert pipe["decode_steps"] == 5     # four delivered, one late
    _leak_free(eng)


# ----------------------------------------------------------------------
# (b) ended with a token in flight
# ----------------------------------------------------------------------

@pytest.mark.parametrize("pool", ["dense", "paged"])
@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_ended_with_a_token_in_flight_gets_nothing_more(pool, how):
    stack = _small_stack(seed=341)
    D, V = stack[3], stack[4]
    clock = FakeClock()
    finished = {}

    class Log(ServingCallback):
        def on_finish(self, r):
            finished[r.id] = len(r.tokens)

    eng = _engine(stack, pool, clock=clock, callbacks=[Log()])
    (p, m, _), (p2, m2, _) = _specs(342, 2, D, V)
    seen = []
    r = Request(p.copy(), m, max_new_tokens=20, eos_id=None,
                deadline=5.0 if how == "deadline" else None,
                stream_cb=lambda req, t: seen.append(t))
    other = Request(p2.copy(), m2, max_new_tokens=6, eos_id=None)
    sched = Scheduler(max_queue=4, clock=clock)
    sched.submit(r)
    sched.submit(other)
    for _ in range(3):
        eng.run_ahead(sched)
    assert eng._flight is not None and len(r.tokens) == 3
    assert r in [q for _, q in eng._flight.pairs]
    if how == "cancel":
        r.cancel()
    else:
        clock.advance(10.0)
    eng.run_ahead(sched)                 # harvest, then the read
    res = r.result(timeout=5)
    assert res.finish_reason == ("cancelled" if how == "cancel"
                                 else "timeout")
    assert len(res.tokens) == finished[r.id] == len(seen) == 3
    assert _pipeline(eng)["late_slot_steps"] == 1
    eng.serve_until_idle(sched, max_iterations=50)
    assert other.result(timeout=5).ok and len(other.tokens) == 6
    assert len(seen) == 3                # and nothing since
    assert eng._flight is None
    _leak_free(eng)


# ----------------------------------------------------------------------
# (c) where a step cannot go ahead, the loop stays in series and says so
# ----------------------------------------------------------------------

def _series_case(name, stack):
    """(engine factory, request factory, scheduler factory) of a
    configuration whose steps cannot all go ahead."""
    D, V = stack[3], stack[4]
    sched = lambda eng: Scheduler(max_queue=32)          # noqa: E731
    if name == "spec":
        mk = lambda: _engine(stack, "paged", spec_k=3)   # noqa: E731
        specs = _specs(352, 6, D, V)
    elif name == "chunk":
        mk = lambda: _engine(stack, "paged", prefill_chunk=4)  # noqa: E731
        specs = _specs(353, 6, D, V, pmin=2, pmax=14, nmin=6)
    elif name == "preempt":
        mk = lambda: _engine(stack, "paged", num_slots=2,      # noqa: E731
                             num_pages=48)
        specs = _specs(354, 3, D, V, pmin=4, pmax=8, nmin=12, nmax=12)
        sched = lambda eng: ShapingScheduler(                  # noqa: E731
            max_queue=32, metrics=eng.metrics)
    else:
        raise KeyError(name)
    return mk, specs, sched


@pytest.mark.parametrize("name", ["spec", "chunk", "preempt"])
def test_series_reasons_are_counted_and_tokens_unchanged(name):
    stack = _small_stack(seed=351)
    D, V = stack[3], stack[4]
    mk, specs, mk_sched = _series_case(name, stack)
    slo = "batch" if name == "preempt" else None

    def drive(loop):
        eng = mk()
        sched = mk_sched(eng)
        reqs = _reqs(specs, slo=slo)
        for r in reqs:
            sched.submit(r)
        if name == "preempt":
            for _ in range(3):            # both slots busy with batch work
                eng.run_ahead(sched) if loop == "ahead" \
                    else eng.run_iteration(sched)
            late = _reqs(_specs(355, 2, D, V, pmax=4, nmax=5),
                         slo="interactive")
            for r in late:
                sched.submit(r)
            reqs += late
        if loop == "ahead":
            eng.serve_until_idle(sched, max_iterations=5000)
        else:
            _series(eng, [], sched)
        return eng, [r.result(timeout=5) for r in reqs]

    _, want = drive("series")
    eng, got = drive("ahead")
    for w, g in zip(want, got):
        assert g.ok and g.finish_reason == w.finish_reason
        np.testing.assert_array_equal(g.tokens, w.tokens)
    pipe = _pipeline(eng)
    assert pipe["series_steps"].get(name, 0) >= 1, pipe
    if name == "spec":
        assert pipe["steps_ahead"] == 0
        assert set(pipe["series_steps"]) == {"spec"}
    else:
        assert pipe["steps_ahead"] >= 1  # and ahead again afterwards
    if name == "preempt":
        assert eng.metrics.preemptions >= 1
    assert pipe["steps_ahead"] + sum(pipe["series_steps"].values()) \
        == pipe["decode_steps"]
    assert eng._flight is None
    _leak_free(eng)


def test_pending_disaggregated_prefill_runs_in_series():
    from paddle_tpu.parallel import init_mesh
    from paddle_tpu.serving import ShardedServingEngine

    stack = _small_stack(seed=361)
    D, V = stack[3], stack[4]
    specs = _specs(362, 6, D, V)
    want = _series(_engine(stack), _reqs(specs))
    eng = ShardedServingEngine(*stack[:3],
                               mesh=init_mesh(dp=2, fsdp=2, tp=2),
                               num_slots=3, max_len=32,
                               prefill="disaggregated")
    reqs = _reqs(specs)
    sched = Scheduler(max_queue=16)
    for r in reqs:
        sched.submit(r)
    eng.serve_until_idle(sched, max_iterations=20000)
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(r.result(timeout=5).tokens, w.tokens)
    pipe = _pipeline(eng)
    assert pipe["series_steps"].get("pending", 0) >= 1, pipe
    assert pipe["steps_ahead"] + sum(pipe["series_steps"].values()) \
        == pipe["decode_steps"]
    assert not eng._pending and eng._flight is None


def test_artifact_engine_runs_in_series():
    table = np.eye(5, dtype=np.float32)
    eng = ArtifactServingEngine(lambda ids: [table[ids]], num_slots=2,
                                max_len=8, dtype=np.int64)
    rs = np.random.RandomState(2)
    reqs = [Request(rs.randint(0, 5, (2,)).astype(np.int64),
                    max_new_tokens=3, eos_id=None) for _ in range(4)]
    sched = Scheduler(max_queue=8)
    for r in reqs:
        sched.submit(r)
    eng.serve_until_idle(sched, max_iterations=50)
    for r in reqs:                       # an identity table repeats
        assert r.result(timeout=5).tokens.tolist() == [int(r.prompt[-1])] * 3
    pipe = _pipeline(eng)
    assert pipe["steps_ahead"] == 0 and pipe["decode_steps"] >= 6
    assert set(pipe["series_steps"]) == {"host"}


def test_a_failed_attempt_lands_the_flight_and_retries_in_series():
    stack = _small_stack(seed=371)
    D, V = stack[3], stack[4]
    specs = [(p, m, 8) for p, m, _ in _specs(372, 3, D, V)]
    want = _series(_engine(stack), _reqs(specs, eos_id=None))
    eng = _engine(stack, max_attempts=2, backoff_base_s=0.0)
    reqs = _reqs(specs, eos_id=None)
    sched = Scheduler(max_queue=8)
    for r in reqs:
        sched.submit(r)
    with faults.inject("serving.decode_step", on="nth", n=4):
        eng.serve_until_idle(sched, max_iterations=100)
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(r.result(timeout=5).tokens, w.tokens)
    pipe = _pipeline(eng)
    assert pipe["series_steps"].get("retry") == 1, pipe
    assert eng.metrics.snapshot()["errors"]["retries"] == 1
    assert pipe["steps_ahead"] >= 3 and pipe["late_slot_steps"] == 0


# ----------------------------------------------------------------------
# (d) a failure where the tokens are read
# ----------------------------------------------------------------------

@pytest.mark.parametrize("pool", ["dense", "paged"])
def test_failure_at_the_deferred_readback_evicts_and_pool_survives(pool):
    """The read of step N fails after step N+1 was enqueued on its
    state: both are poisoned. Every request in flight is evicted with
    its partial tokens and the cause — the one that had given its slot
    up with its last token in flight too — and the pool serves on."""
    stack = _small_stack(seed=381)
    D, V = stack[3], stack[4]
    specs = _specs(382, 3, D, V)
    lens = (3, 12, 12)                   # the first ends by count early
    reqs = [Request(p.copy(), m, max_new_tokens=n, eos_id=None)
            for (p, m, _), n in zip(specs, lens)]
    eng = _engine(stack, pool)
    sched = Scheduler(max_queue=8)
    for r in reqs:
        sched.submit(r)
    # joins take two iterations (two joins an iteration); the third
    # read is the one that would hand the first request its last token
    with faults.inject("serving.step_readback", on="nth", n=2):
        for _ in range(4):
            eng.run_ahead(sched)
    for r in reqs:
        res = r.result(timeout=5)
        assert res.finish_reason == "error" and not res.ok
        assert isinstance(res.error, faults.InjectedFault)
        assert 1 <= len(res.tokens) < r.max_new_tokens
    snap = eng.metrics.snapshot()
    assert snap["errors"]["evictions_on_error"] == 3
    assert snap["requests"]["failed"] == 3
    assert eng._flight is None and not eng.running()
    fresh_specs = _specs(383, 3, D, V)
    want = _series(_engine(stack, pool), _reqs(fresh_specs))
    fresh = _reqs(fresh_specs)
    for r in fresh:
        sched.submit(r)
    eng.serve_until_idle(sched, max_iterations=200)
    for w, r in zip(want, fresh):
        res = r.result(timeout=5)
        assert res.ok
        np.testing.assert_array_equal(res.tokens, w.tokens)
    assert set(eng.trace_counts.values()) == {1}
    _leak_free(eng)


def test_abortive_shutdown_lands_the_flight():
    """`shutdown(drain=False)`: what is in flight is delivered before
    the requests are finalized; each stream is a prefix of the series
    stream and no future hangs."""
    stack = _small_stack(seed=391)
    D, V = stack[3], stack[4]
    specs = [(p, m, 200) for p, m, _ in _specs(392, 3, D, V)]
    want = _series(_engine(stack, max_len=256), _reqs(specs, eos_id=None))
    eng = _engine(stack, max_len=256)
    srv = ServingServer(eng, max_queue=8)
    got = [srv.submit(p, m, max_new_tokens=n, eos_id=None)
           for p, m, n in specs]
    while min(len(r.tokens) for r in got) < 5:
        time.sleep(0.005)
    srv.shutdown(drain=False, timeout=60)
    assert eng._flight is None and not eng.running()
    for w, r in zip(want, got):
        res = r.result(timeout=5)
        assert res.finish_reason == "shutdown"
        assert 5 <= len(res.tokens) < 200
        np.testing.assert_array_equal(res.tokens,
                                      w.tokens[:len(res.tokens)])


# ----------------------------------------------------------------------
# (e) run_iteration() by hand keeps its contract
# ----------------------------------------------------------------------

@pytest.mark.parametrize("pool", ["dense", "paged"])
def test_run_iteration_alone_delivers_what_it_computed(pool):
    stack = _small_stack(seed=401)
    D, V = stack[3], stack[4]
    (p, m, _), = _specs(402, 1, D, V)
    eng = _engine(stack, pool)
    r = Request(p.copy(), m, max_new_tokens=6, eos_id=None)
    sched = Scheduler(max_queue=2)
    sched.submit(r)
    for k in range(1, 6):
        assert eng.run_iteration(sched) is True
        assert eng._flight is None
        # the join's token 0 and the first step's token, then one a call
        assert len(r.tokens) == k + 1
    assert r.result(timeout=5).finish_reason == "length"
    assert eng.run_iteration(sched) is False
    pipe = _pipeline(eng)
    assert pipe["steps_ahead"] == 0
    assert pipe["series_steps"] == {"idle": 5} and pipe["depth"] == 1
    # a hand-stepped iteration may follow a loop's: the flight lands
    r2 = Request(p.copy(), m, max_new_tokens=6, eos_id=None)
    sched.submit(r2)
    eng.run_ahead(sched)
    eng.run_ahead(sched)
    assert eng._flight is not None and len(r2.tokens) == 2
    eng.run_iteration(sched)
    assert eng._flight is None and len(r2.tokens) == 4
    eng.serve_until_idle(sched, max_iterations=20)
    np.testing.assert_array_equal(r2.result(timeout=5).tokens,
                                  r.result(timeout=5).tokens)


# ----------------------------------------------------------------------
# (f) programs traced once, span names kept
# ----------------------------------------------------------------------

def test_spans_keep_their_names_and_programs_trace_once():
    stack = _small_stack(seed=411)
    D, V = stack[3], stack[4]
    specs = _specs(412, 8, D, V, nmin=4)
    eng = _engine(stack, "paged")
    retrace_sentinel(eng).__enter__()   # disarmed by conftest teardown
    with T.session_scope() as tr:
        srv = ServingServer(eng, max_queue=16)
        got = [srv.submit(p, m, max_new_tokens=n, eos_id=None)
               for p, m, n in specs]
        srv.shutdown(drain=True, timeout=300)
    assert all(r.result(timeout=5).ok for r in got)
    assert not tr.open_spans()
    spans = tr.spans()
    names = {s.name for s in spans}
    assert {"iteration", "iter.harvest", "iter.admit", "join",
            "iter.tok0", "iter.chunks", "step.map_pages", "step.enqueue",
            "step.readback", "decode.step", "iter.deliver",
            "iter.account"} <= names
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.name.startswith("step."):
            assert by_id[s.parent_id].name == "decode.step"
    its = [s for s in spans if s.name == "iteration"]
    steps = [s.attrs["step"] for s in its if "step" in s.attrs]
    pipe = _pipeline(eng)
    assert steps.count("ahead") == pipe["steps_ahead"] >= 1
    assert len(steps) == pipe["decode_steps"]
    assert sum(s.attrs.get("late_slot_steps", 0) for s in its) \
        == pipe["late_slot_steps"] == 0
    # where steps go ahead, token 0 is read after the step is enqueued
    for it in its:
        kids = sorted((s for s in spans if s.parent_id == it.span_id),
                      key=lambda s: s.t0)
        order = [s.name for s in kids]
        if it.attrs.get("step") == "ahead" and "iter.tok0" in order:
            assert order.index("decode.step") < order.index("iter.tok0")
    assert set(eng.trace_counts.values()) == {1}
    assert [k[0] for k in eng.trace_counts].count("pstep") == 1
    _leak_free(eng)
