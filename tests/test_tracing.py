"""End-to-end request tracing, compile observer, retrace sentinel.

Covers: raw tracer span nesting/ordering and the chrome-trace JSON
schema round-trip; the per-request waterfall completeness contract
under a ragged-arrival soak on the dense, paged and sharded engines
(every admitted request exports a complete queue -> join -> decode ->
finish/error waterfall, loadable in Perfetto); compile-observer spans
(one per jit trace, with duration and cache key); the retrace sentinel
(raise and log modes, budget overrides); disabled-mode cost (nothing
recorded, zero allocations attributable to the tracing modules on the
decode hot path); the chaos cell (an evicted request's trace ends with
an error span); the profiler.RecordEvent fix (event_type recorded,
bounded buffer, surfaces into an active tracer session); and the
ServingMetrics snapshot schema (flattened keys == SNAPSHOT_DOCS ==
the README tables, Prometheus rendering).
"""
import json

import numpy as np
import pytest

from paddle_tpu import nn
from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                             TransformerDecoderLayer)
from paddle_tpu.profiler import trace as T
from paddle_tpu.serving import (Request, Scheduler, ServingEngine,
                                retrace_sentinel)
from paddle_tpu.serving import tracing as rt
from paddle_tpu.serving.metrics import (SNAPSHOT_DOCS, ServingMetrics,
                                        flatten_snapshot, to_prometheus)
from paddle_tpu.testing import faults


def _small_stack(seed=7, D=32, H=2, V=17, layers=2):
    np.random.seed(seed)
    layer = TransformerDecoderLayer(D, H, 64, dropout=0.0)
    dec = TransformerDecoder(layer, layers)
    dec.eval()
    embed = nn.Embedding(V, D)
    proj = nn.Linear(D, V)
    return dec, embed, proj, D, V


def _mk_request(rs, D, V, pmax=6, nmax=10, **kw):
    P = int(rs.randint(1, pmax + 1))
    prompt = rs.randint(2, V, (P,)).astype(np.int32)
    prompt[0] = 0
    mem_seed = int(prompt.sum()) * 131 + P
    mem = np.random.RandomState(mem_seed).randn(4, D).astype("f4")
    n = int(rs.randint(2, nmax + 1))
    return Request(prompt, mem, max_new_tokens=n, eos_id=1, **kw)


def _ragged_soak(eng, stack, n_requests, seed, sched=None):
    """Submit `n_requests` in ragged waves between iterations; drive to
    idle; every future must resolve ok. Returns the requests."""
    D, V = stack[3], stack[4]
    sched = sched or Scheduler(max_queue=4 * n_requests)
    rs = np.random.RandomState(seed)
    reqs = []

    def wave(k):
        for _ in range(k):
            r = _mk_request(rs, D, V)
            sched.submit(r)
            reqs.append(r)

    wave(4)
    it = 0
    while len(reqs) < n_requests or sched.depth() > 0 or \
            eng.occupancy() > 0:
        eng.run_iteration(sched)
        it += 1
        if len(reqs) < n_requests and it % 3 == 0:
            wave(int(rs.randint(1, 5)))
        assert it < 3000
    for r in reqs:
        assert r.result(timeout=5).ok
    return reqs


def _check_export(tr, reqs, tmp_path, tag):
    """Export -> reload -> schema + waterfall-completeness assertions
    shared by the dense/paged/sharded soaks."""
    path = str(tmp_path / f"{tag}.json")
    tr.export_chrome_trace(path)
    payload = json.load(open(path))
    assert set(payload) == {"traceEvents", "displayTimeUnit"}
    events = payload["traceEvents"]
    # chrome-trace schema: every event has the required fields and
    # non-negative relative timestamps/durations
    for ev in events:
        assert ev["ph"] in ("X", "M", "C"), ev
        assert "name" in ev and "pid" in ev
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
    # waterfall completeness: every admitted request has queue + join
    # spans and a terminal finish event, grouped by its trace id
    wf = rt.waterfalls(events)
    ids = {r.id for r in reqs}
    assert ids <= set(wf), (sorted(ids), sorted(wf))
    for r in reqs:
        w = wf[r.id]
        assert w["complete"], (r.id, sorted(
            e["name"] for e in w["spans"]))
        assert w["terminal"] == "finish"
        assert w["tokens"] == len(r.result().tokens)
        assert w["total_ms"] >= w["phases"]["queue"] >= 0
    # the report renders
    rep = rt.waterfall_report(events, top=3)
    assert "phase" in rep and "p50(ms)" in rep and "req " in rep
    return events


# ----------------------------------------------------------------------
# raw tracer: nesting, ordering, schema round-trip
# ----------------------------------------------------------------------

def test_span_nesting_ordering_and_roundtrip(tmp_path):
    tr = T.Tracer(capacity=16)
    root = tr.begin("request", cat="request", trace_id=9)
    child = tr.begin("queue", cat="request", trace_id=9, parent=root)
    with tr.span("inner", cat="span", trace_id=9, parent=child):
        pass
    tr.end(child)
    tr.instant("finish", cat="request", trace_id=9, parent=root)
    tr.end(root, reason="eos")
    spans = tr.spans()
    by_name = {s.name: s for s in spans}
    assert by_name["queue"].parent_id == root.span_id
    assert by_name["inner"].parent_id == child.span_id
    # nesting: child intervals inside the parent's
    assert root.t0 <= child.t0 <= child.t1 <= root.t1
    assert child.t0 <= by_name["inner"].t0 <= by_name["inner"].t1 \
        <= child.t1
    # completion order in the ring: inner ended before queue, queue
    # before request
    names = [s.name for s in spans]
    assert names.index("inner") < names.index("queue") < \
        names.index("request")
    # round-trip
    path = tr.export_chrome_trace(str(tmp_path / "t.json"))
    evs = rt.load_chrome_trace(path)
    req_evs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in req_evs} == {"request", "queue",
                                           "inner", "finish"}
    for e in req_evs:
        assert e["args"]["trace_id"] == 9
    # parent ids survive export
    q = next(e for e in req_evs if e["name"] == "queue")
    assert q["args"]["parent_id"] == root.span_id


def test_ring_buffer_caps_and_counts_drops():
    tr = T.Tracer(capacity=8)
    for i in range(20):
        tr.instant(f"e{i}")
    assert len(tr.spans()) == 8
    assert tr.dropped == 12
    assert [s.name for s in tr.spans()] == [f"e{i}"
                                            for i in range(12, 20)]


def test_session_management():
    assert T.session() is None
    tr = T.start_session()
    try:
        with pytest.raises(RuntimeError, match="already active"):
            T.start_session()
        assert T.session() is tr
    finally:
        assert T.end_session() is tr
    assert T.session() is None and T.end_session() is None


# ----------------------------------------------------------------------
# the acceptance soaks: dense / paged / sharded waterfalls
# ----------------------------------------------------------------------

def test_waterfall_soak_dense_engine(tmp_path):
    """Ragged-arrival soak on the dense pool under a tracer session +
    retrace sentinel: complete per-request waterfalls, compile spans
    with durations, decode.step spans carrying the co-residents."""
    dec, embed, proj, D, V = _small_stack(seed=121)
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32)
    with T.session_scope() as tr, retrace_sentinel(eng):
        reqs = _ragged_soak(eng, (dec, embed, proj, D, V), 16,
                            seed=122)
    events = _check_export(tr, reqs, tmp_path, "dense")
    # compile observer: one span per jit trace, duration > 0, count 1
    compiles = [e for e in events if e["name"] == "compile"]
    assert compiles, "no compile spans recorded"
    keys = {e["args"]["key"] for e in compiles}
    assert any("'step'" in k for k in keys), keys
    assert any("'join'" in k for k in keys), keys
    for e in compiles:
        assert e["dur"] > 0 and e["args"]["count"] == 1
    # every request co-resided in at least one recorded decode step
    steps = [e for e in events if e["name"] == "decode.step"]
    assert steps
    seen = set()
    for e in steps:
        assert e["args"]["n_active"] == len(e["args"]["slots"])
        seen.update(e["args"]["slots"])
    decoded = {r.id for r in reqs if len(r.result().tokens) > 1}
    assert decoded <= seen


def test_waterfall_soak_paged_engine(tmp_path):
    """Same soak through the paged pool: pjoin/pstep compile keys,
    prefix_hit attribute on join spans, page gauges on decode.step."""
    dec, embed, proj, D, V = _small_stack(seed=131)
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                        paged=True, page_size=8)
    sched = Scheduler(max_queue=64)
    rs = np.random.RandomState(132)
    protos = [_mk_request(rs, D, V) for _ in range(4)]
    with T.session_scope() as tr, retrace_sentinel(eng):
        reqs = []
        for i in range(10):            # repeats ride the prefix cache
            p = protos[i % len(protos)]
            r = Request(p.prompt.copy(), p.memory,
                        max_new_tokens=p.max_new_tokens, eos_id=1)
            sched.submit(r)
            reqs.append(r)
            eng.run_iteration(sched)
        it = 0
        while sched.depth() > 0 or eng.occupancy() > 0:
            eng.run_iteration(sched)
            it += 1
            assert it < 2000
        for r in reqs:
            assert r.result(timeout=5).ok
    events = _check_export(tr, reqs, tmp_path, "paged")
    joins = [e for e in events if e["name"] == "join"]
    hits = [e for e in joins if e["args"].get("prefix_hit")]
    assert hits, "no prefix-hit join spans despite repeated prompts"
    misses = [e for e in joins if e["args"].get("prefix_hit") is False]
    assert misses
    steps = [e for e in events if e["name"] == "decode.step"]
    assert all("pages_in_use" in e["args"] and "pages_free" in
               e["args"] for e in steps), steps[0]["args"]
    keys = {e["args"]["key"] for e in events if e["name"] == "compile"}
    assert any("'pstep'" in k for k in keys), keys


def test_waterfall_soak_sharded_engine(tmp_path):
    """Same soak through the mesh-sharded pool (dp2 x fsdp2 x tp2):
    complete waterfalls plus shard-occupancy gauges on decode.step."""
    from paddle_tpu.parallel import init_mesh
    from paddle_tpu.serving import ShardedServingEngine

    mesh = init_mesh(dp=2, fsdp=2, tp=2)
    dec, embed, proj, D, V = _small_stack(seed=141)
    eng = ShardedServingEngine(dec, embed, proj, mesh=mesh,
                               num_slots=4, max_len=32)
    with T.session_scope() as tr, retrace_sentinel(eng):
        reqs = _ragged_soak(eng, (dec, embed, proj, D, V), 8, seed=142)
    events = _check_export(tr, reqs, tmp_path, "sharded")
    steps = [e for e in events if e["name"] == "decode.step"]
    assert steps and all(len(e["args"]["shard_occupancy"]) == 2
                         for e in steps)


# ----------------------------------------------------------------------
# chaos: an evicted request's trace ends with an error span
# ----------------------------------------------------------------------

@pytest.mark.chaos
def test_evicted_request_trace_ends_with_error_span(tmp_path):
    dec, embed, proj, D, V = _small_stack(seed=151)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        max_attempts=2, backoff_base_s=0.0)
    sched = Scheduler(max_queue=8)
    rs = np.random.RandomState(152)
    with T.session_scope() as tr:
        a = Request(np.asarray([0, 3, 4], np.int32),
                    rs.randn(4, D).astype("f4"), max_new_tokens=20,
                    eos_id=None)
        sched.submit(a)
        for _ in range(3):
            eng.run_iteration(sched)
        assert len(a.tokens) >= 2
        with faults.inject("serving.decode_step", on="always",
                           max_fires=2):
            eng.run_iteration(sched)
        assert a.result(timeout=5).finish_reason == "error"
        # a failed JOIN also traces as an error terminal
        b = _mk_request(rs, D, V)
        sched.submit(b)
        with faults.inject("serving.prefill", on="always"):
            eng.run_iteration(sched)
        with pytest.raises(faults.InjectedFault):
            b.result(timeout=5)
    events = tr.chrome_trace_events()
    wf = rt.waterfalls(events)
    for r in (a, b):
        w = wf[r.id]
        assert w["terminal"] == "error", w
        err = [e for e in w["spans"] if e["name"] == "error"]
        assert err and err[0]["args"]["error"] == "InjectedFault"
        # the error event is the LAST event of the request's trace
        assert w["spans"][-1]["name"] in ("error", "request")
    # failed join span is closed with ok=False
    joins = [e for e in wf[b.id]["spans"] if e["name"] == "join"]
    assert joins and joins[-1]["args"]["ok"] is False


# ----------------------------------------------------------------------
# retrace sentinel
# ----------------------------------------------------------------------

def test_retrace_sentinel_raise_log_and_budgets():
    dec, embed, proj, D, V = _small_stack(seed=161)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32)
    sched = Scheduler(max_queue=8)
    rs = np.random.RandomState(162)
    with retrace_sentinel(eng) as s:
        r = _mk_request(rs, D, V)
        sched.submit(r)
        eng.serve_until_idle(sched, max_iterations=200)
        assert r.result(timeout=5).ok
        assert not s.violations          # first compiles are in budget
    step_key = ("step",) + eng._pool_key
    # a retrace (count -> 2) fires the sentinel at the offending trace
    with retrace_sentinel(eng):
        with pytest.raises(T.RetraceError, match="traced 2 times"):
            eng.trace_counts[step_key] += 1
    eng.trace_counts[step_key] -= 1      # undo the simulated retrace
    # log mode records instead of raising; assert_ok surfaces it
    with retrace_sentinel(eng, mode="log") as s:
        eng.trace_counts[step_key] += 1
        assert len(s.violations) == 1
        assert s.violations[0]["key"] == step_key
        with pytest.raises(T.RetraceError):
            s.assert_ok()
    eng.trace_counts[step_key] -= 1
    # budget overrides by key kind
    with retrace_sentinel(eng, budgets={"step": 3}) as s:
        eng.trace_counts[step_key] += 1  # count 2 <= budget 3
        eng.trace_counts[("join", 2)] = 1
        assert not s.violations
    eng.trace_counts[step_key] -= 1
    # outside any sentinel scope increments are free again
    eng.trace_counts[step_key] += 5
    eng.trace_counts[step_key] -= 5


def test_sentinel_violation_fails_request_loudly():
    """A retrace mid-serve surfaces as a failed request (the sentinel
    raises inside the traced body), never a silent slowdown."""
    dec, embed, proj, D, V = _small_stack(seed=171)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        max_attempts=1)
    sched = Scheduler(max_queue=8)
    rs = np.random.RandomState(172)
    r0 = _mk_request(rs, D, V)
    sched.submit(r0)
    eng.serve_until_idle(sched, max_iterations=200)
    assert r0.result(timeout=5).ok
    # simulate a retrace regression: drop a compiled join program so
    # the next join of that bucket traces AGAIN under the sentinel
    jkey = next(k for k in eng.trace_counts if k[0] == "join")
    raw = dict.__getitem__(eng._compiled, jkey)   # keep cache type
    del eng._compiled[jkey]
    try:
        with retrace_sentinel(eng):
            r1 = Request(r0.prompt.copy(), r0.memory,
                         max_new_tokens=4, eos_id=1)
            sched.submit(r1)
            for _ in range(3):
                eng.run_iteration(sched)
        with pytest.raises(T.RetraceError):
            r1.result(timeout=5)
    finally:
        dict.__setitem__(eng._compiled, jkey, raw)


# ----------------------------------------------------------------------
# disabled mode: nothing recorded, nothing allocated
# ----------------------------------------------------------------------

def test_disabled_mode_records_and_allocates_nothing():
    import tracemalloc

    dec, embed, proj, D, V = _small_stack(seed=181)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=128)
    sched = Scheduler(max_queue=8)
    rs = np.random.RandomState(182)
    r = Request(np.asarray([0, 3], np.int32),
                rs.randn(4, D).astype("f4"), max_new_tokens=100,
                eos_id=None)
    sched.submit(r)
    for _ in range(5):                   # join + warm the decode step
        eng.run_iteration(sched)
    assert r._trace is None              # no session at submit
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot()
    for _ in range(20):
        eng.run_iteration(sched)
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grew = [d for d in snap2.compare_to(snap1, "filename")
            if d.size_diff > 0 and any(
                m in (d.traceback[0].filename or "")
                for m in ("profiler/trace", "serving/tracing"))]
    assert not grew, [str(g) for g in grew]
    assert T.session() is None
    r.cancel()
    eng.serve_until_idle(sched, max_iterations=50)


# ----------------------------------------------------------------------
# profiler.RecordEvent satellite
# ----------------------------------------------------------------------

def test_record_event_type_capacity_and_tracer_surface():
    import paddle_tpu.profiler as prof

    prof.reset()
    with prof.RecordEvent("unit_x", event_type="kernel"):
        pass
    evs = prof.events()
    assert evs and evs[-1][0] == "unit_x" and evs[-1][1] == "kernel"
    assert "kernel" in prof.summary()
    # bounded buffer: capacity cap keeps the NEWEST events
    old_cap = prof._EVENTS_CAP
    try:
        prof.set_events_capacity(4)
        for i in range(7):
            with prof.RecordEvent(f"e{i}"):
                pass
        names = [e[0] for e in prof.events()]
        assert names == ["e3", "e4", "e5", "e6"]
    finally:
        prof.set_events_capacity(old_cap)
    prof.reset()
    assert prof.events() == []
    # surfaces into an active tracer session
    with T.session_scope() as tr:
        with prof.RecordEvent("in_session", event_type="step"):
            pass
    spans = [s for s in tr.spans() if s.name == "in_session"]
    assert len(spans) == 1 and spans[0].cat == "record_event"
    assert spans[0].attrs["event_type"] == "step"


# ----------------------------------------------------------------------
# snapshot schema + Prometheus + README sync
# ----------------------------------------------------------------------

def _full_metrics():
    """A ServingMetrics with every section populated (paging +
    sharding + memory ledger + MFU/goodput gauges recorded) — no
    engine needed."""
    from paddle_tpu.profiler.costs import CPU_SPEC

    m = ServingMetrics()
    m.record_submit()
    m.record_join()
    m.record_first_token(0.01)
    m.record_token()
    m.record_decode(1, 0.002)
    m.record_finish("eos", 1)
    m.record_error("stream_cb", RuntimeError("x"))
    m.record_retry("slot_join")
    m.record_prefix("whole", matched_tokens=8, prompt_tokens=8)
    m.record_prefix("partial", matched_tokens=5, prompt_tokens=9)
    m.record_prefix("miss", prompt_tokens=7)
    m.record_cow_copy()
    m.record_page_wait()
    m.record_oom_eviction()
    m.record_step_gap(0.001)
    m.record_prefill_step(0.003)
    m.record_collective(0.001)
    m.record_spec_step(2, 6, 4, 0.0005, 0.002, k_eff=3,
                       variant="paged", k_shrinks=1, k_grows=0)
    m.record_token("t1")              # tenancy: per-tenant tokens
    m.record_adapter_acquire(True)
    m.record_adapter_acquire(False)
    m.record_adapter_load()
    m.record_adapter_eviction()
    m.record_adapter_wait()
    m.record_iteration(1, 0.5, pages_in_use=3, pages_free=5,
                       bytes_per_active_token=128.0,
                       shard_occupancy=[0.5, 0.25],
                       tenant_slots={"base": 1, "t1": 1},
                       trie_nodes=4, trie_pages=6,
                       cache={"state_resets": 1, "prefill_tokens": 9,
                              "ring_wraps": 2})
    m.set_cache_bytes({"paged": 4096, "latent": 2048, "ring": 1024,
                       "recurrent": 512, "static": 0})
    m.set_expert_counters(("token_slots", "held_slots", "load_max",
                           "dropped_slots"))
    m.record_iteration(queue_depth=0, occupancy=0.5,
                       experts={"token_slots": 64, "held_slots": 5,
                                "load_max": 2, "dropped_slots": 0})
    m.set_memory_provider(
        lambda: {"weights_bytes": 1000, "pool_bytes": 500,
                 "adapter_bytes": 128, "in_use_bytes": 1200,
                 "compile_temp_peak_bytes": 64},
        budget_bytes=2000)
    m.record_step_utilization(1e6, 2e6, 0.001, CPU_SPEC, "xla")
    m.record_cold_start({"time_to_ready_s": 1.5, "programs": 4,
                         "loaded_from_cache": 3, "compiled": 1,
                         "cache_errors": 0, "warm": 0})
    m.record_chunked_join()               # traffic shaping: slo section
    m.record_chunk()
    m.record_preemption()
    m.record_resume()
    m.record_replay_token()
    m.record_slo_finish("interactive", 0.1, 0.05, 0.5, 0.1)
    m.set_wfq_lag({"base": 1.0})
    return m


def test_snapshot_schema_matches_docs_exactly():
    flat = flatten_snapshot(_full_metrics().snapshot())
    assert set(flat) == set(SNAPSHOT_DOCS), (
        sorted(set(flat) ^ set(SNAPSHOT_DOCS)))
    # base sections only: still a strict subset of the documented keys
    flat_base = flatten_snapshot(ServingMetrics().snapshot())
    assert set(flat_base) < set(SNAPSHOT_DOCS)
    # one source of truth with the static analyzer: rule PTA202
    # (snapshot-doc-drift, paddle_tpu.analysis.repo_rules) checks the
    # SAME invariant against the snapshot() SOURCE, so a key added to
    # either side fails both this runtime test and the CI gate
    # (tools/static_check.py)
    from paddle_tpu.analysis import repo_rules

    assert repo_rules.RULE_SNAPSHOT_DOC == "PTA202"
    assert repo_rules.snapshot_doc_findings() == []


def test_prometheus_rendering():
    m = _full_metrics()
    tr = T.Tracer()
    tr.count("compiles", 3)
    text = to_prometheus(m.snapshot(), tracer=tr)
    assert "# TYPE paddle_tpu_serving_requests_submitted counter" \
        in text
    assert "paddle_tpu_serving_requests_submitted 1.0" in text
    assert 'paddle_tpu_serving_ttft_ms{stat="p50"}' in text
    assert 'paddle_tpu_serving_sharding_per_shard_occupancy' \
           '{index="1"} 0.25' in text
    assert 'where="stream_cb"' in text          # errors.last info
    assert 'counter="compiles"} 3.0' in text
    # a snapshot without optional sections renders too
    assert "paging" not in to_prometheus(ServingMetrics().snapshot())


def test_readme_documents_snapshot_keys_and_span_catalog():
    import os

    readme = open(os.path.join(os.path.dirname(__file__), "..",
                               "README.md")).read()
    for key in SNAPSHOT_DOCS:
        assert f"`{key}`" in readme, \
            f"README metrics table is missing `{key}`"
    for name, _ in rt.SPAN_CATALOG:
        assert f"`{name}`" in readme, \
            f"README span-catalog table is missing `{name}`"


# ----------------------------------------------------------------------
# sampling mode (PR 9): bounded always-on sessions
# ----------------------------------------------------------------------

def test_sampling_deterministic_and_bounded():
    tr = T.Tracer(sample=0.5)
    picks = [tr.should_sample(i) for i in range(200)]
    # deterministic: same ids -> same decisions
    assert picks == [tr.should_sample(i) for i in range(200)]
    # roughly the requested fraction (hash-uniform over ids)
    assert 60 <= sum(picks) <= 140
    # sample=1 keeps everything; invalid fractions refuse loudly
    assert all(T.Tracer(sample=1.0).should_sample(i)
               for i in range(50))
    with pytest.raises(ValueError):
        T.Tracer(sample=0.0)
    with pytest.raises(ValueError):
        T.Tracer(sample=1.5)


def test_sampled_session_traces_only_sampled_requests():
    dec, embed, proj, D, V = _small_stack()
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32)
    sched = Scheduler(max_queue=64)
    rs = np.random.RandomState(3)
    with T.session_scope(sample=0.5) as tr:
        reqs = []
        for _ in range(12):
            r = _mk_request(rs, D, V)
            sched.submit(r)
            reqs.append(r)
        eng.serve_until_idle(sched, max_iterations=2000)
        for r in reqs:
            assert r.result(timeout=5).ok
    sampled = {r.id for r in reqs if tr.should_sample(r.id)}
    unsampled = {r.id for r in reqs} - sampled
    assert sampled and unsampled, "seed produced a degenerate split"
    wf = rt.waterfalls(tr.chrome_trace_events())
    assert sampled <= set(wf)
    assert not (unsampled & set(wf))
    for rid in sampled:
        assert wf[rid]["complete"]
    # the split is visible as session counters
    assert tr.counters["requests_sampled"] == len(sampled)
    assert tr.counters["requests_unsampled"] == len(unsampled)
    # an unsampled request never got a _ReqTrace attached
    assert all(r._trace is None for r in reqs)


# ----------------------------------------------------------------------
# XPlane span links (PR 9): host spans carry ids into the device trace
# ----------------------------------------------------------------------

def test_record_event_span_links_in_lockstep_profile(tmp_path):
    from paddle_tpu import profiler as prof

    trace_dir = str(tmp_path / "xplane")
    prof.start_profiler(trace_dir=trace_dir)
    try:
        assert T._SESSION is not None   # lockstep tracer session
        with prof.RecordEvent("linked_op", event_type="step",
                              trace_id=42):
            np.ones(4).sum()
    finally:
        prof.stop_profiler()
    # the lockstep session exported host_trace.json with the span's
    # identity (trace_id + span_id) — the same ids RecordEvent stamped
    # into the TraceAnnotation metadata on the device timeline
    assert prof.last_host_trace is not None
    events = rt.load_chrome_trace(prof.last_host_trace)
    linked = [e for e in events
              if e.get("name") == "linked_op" and e["ph"] == "X"]
    assert linked, [e.get("name") for e in events]
    args = linked[0]["args"]
    assert args["trace_id"] == 42
    assert args["span_id"] > 0
    assert args["event_type"] == "step"


def test_record_event_without_profiler_still_spans():
    from paddle_tpu import profiler as prof

    with T.session_scope() as tr:
        with prof.RecordEvent("plain", event_type="op"):
            pass
    spans = [s for s in tr.spans() if s.name == "plain"]
    assert len(spans) == 1
    assert spans[0].cat == "record_event"
    assert spans[0].t1 is not None


# ----------------------------------------------------------------------
# the program's spans on the profiler's clock (PR 25)
# ----------------------------------------------------------------------

_ENGINE_CHAIN = ("iteration", "iter.admit", "join", "decode.step",
                 "step.enqueue", "step.readback", "iter.deliver")


def _host_events(trace_dir):
    """[(name, start_ns, end_ns, stats)] of every host-plane event of
    the newest .xplane.pb under a jax.profiler log directory."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    assert paths, f"no .xplane.pb under {trace_dir}"
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns,
                            dict(ev.stats)))
    return out


def _profiled(tmp_path, fn):
    import jax

    d = str(tmp_path / "xplane")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_events(d)


def test_engine_spans_reach_the_profiler_nested_and_linked(tmp_path):
    """One served request under a session AND a jax.profiler trace: the
    engine-track spans and the request's join are host-plane events of
    the same names, nested in program order (a step's tokens are read
    in the iteration AFTER the one that enqueued it), each with its
    span_id;
    `iteration` carries the tracer clock at its start."""
    dec, embed, proj, D, V = _small_stack(seed=191)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32)
    sched = Scheduler(max_queue=4)
    r = Request(np.asarray([0, 3, 5], np.int32),
                np.random.RandomState(5).randn(4, D).astype("f4"),
                max_new_tokens=4, eos_id=None)
    eng.precompile((4, D), prompt_buckets=(4,))      # no compile inside

    def serve():
        with T.session_scope() as tr:
            sched.submit(r)
            eng.serve_until_idle(sched, max_iterations=50)
            serve.tracer = tr

    events = _profiled(tmp_path, serve)
    assert r.result(timeout=5).ok
    tr = serve.tracer
    by_id = {s.span_id: s for s in tr.spans()}
    mine = [e for e in events if e[0] in _ENGINE_CHAIN]
    its = sorted((e for e in mine if e[0] == "iteration"),
                 key=lambda e: e[1])

    def within(root):
        return sorted((e for e in mine if root[1] <= e[1]
                       and e[2] <= root[2]), key=lambda e: (e[1], -e[2]))

    # `serve_until_idle` owns consecutive iterations, so the first one
    # admits the request and ENQUEUES a step; the second enqueues its
    # own step first and then reads and delivers the first one's token
    first, inside = within(its[0]), within(its[1])
    assert [e[0] for e in first] == [
        "iteration", "iter.admit", "join", "decode.step", "step.enqueue"]
    assert [e[0] for e in inside] == [
        "iteration", "iter.admit", "decode.step", "step.enqueue",
        "step.readback", "iter.deliver"]
    ev = {e[0]: e for e in inside}
    ev["join"] = first[2]
    for parent, child in (("iteration", "iter.admit"),
                          ("iteration", "decode.step"),
                          ("decode.step", "step.enqueue"),
                          ("decode.step", "step.readback"),
                          ("iteration", "iter.deliver")):
        assert ev[parent][1] <= ev[child][1] and \
            ev[child][2] <= ev[parent][2], (parent, child)
    assert first[1][1] <= first[2][1] and first[2][2] <= first[1][2]
    assert ev["step.enqueue"][2] <= ev["step.readback"][1]
    assert ev["decode.step"][2] <= ev["iter.deliver"][1]
    inside = first + inside
    for name, _, _, stats in inside:
        sp = by_id[stats["span_id"]]          # the SAME span, by id
        assert sp.name == name
    assert ev["join"][3]["trace_id"] == r.id
    assert first[0][3]["joins"] == 1
    # (strings stay on the tracer's span: the profiler takes numbers)
    assert by_id[first[0][3]["span_id"]].attrs["step"] == "idle"
    assert by_id[ev["iteration"][3]["span_id"]].attrs["step"] == "ahead"
    assert ev["iter.deliver"][3]["tokens"] == 1
    # the clock pair: the annotation starts where the tracer's span does
    root = by_id[ev["iteration"][3]["span_id"]]
    assert ev["iteration"][3]["t0_perf_ns"] == int(root.t0 * 1e9)
    # lifecycle spans cross threads and are not forwarded
    assert not [e for e in events if e[0] in ("request", "queue",
                                              "decode")]


def test_iteration_spans_nest_and_idle_spins_are_dropped():
    """The tracer's own record of an iteration: children inside their
    parent, their durations summing to no more than it; gauges computed
    once and shared with decode.step; an idle spin leaves no span."""
    dec, embed, proj, D, V = _small_stack(seed=201)
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                        paged=True, page_size=8)
    stack = (dec, embed, proj, D, V)
    calls = []
    gauges = eng._iteration_gauges
    eng._iteration_gauges = lambda: calls.append(1) or gauges()
    with T.session_scope() as tr:
        _ragged_soak(eng, stack, 6, seed=202)
        n_work = len([s for s in tr.spans() if s.name == "iteration"])
        assert len(calls) == n_work           # once an iteration
        assert eng.run_iteration(Scheduler(max_queue=2)) is False
        assert eng._iter_trace is None
    spans = tr.spans()
    assert len([s for s in spans if s.name == "iteration"]) == n_work
    assert not tr.open_spans()
    by_id = {s.span_id: s for s in spans}
    kids = {}
    for s in spans:
        if s.cat == "engine" and s.parent_id is not None:
            kids.setdefault(s.parent_id, []).append(s)
    assert kids
    for pid, children in kids.items():
        p = by_id[pid]
        assert p.name in ("iteration", "decode.step", "iter.admit")
        for c in children:
            assert p.t0 <= c.t0 and c.t1 <= p.t1, (p.name, c.name)
        assert sum(c.duration_s for c in children) <= \
            p.duration_s + 1e-9, p.name
    joins = [s for s in spans if s.name == "join"]
    assert joins and all(by_id[j.parent_id].name == "iter.admit"
                         for j in joins)
    for it in (s for s in spans if s.name == "iteration"):
        assert {"joins", "occupancy", "queue_depth", "pages_in_use",
                "pages_free", "t0_perf_ns"} <= set(it.attrs), it.attrs
        step = [c for c in kids.get(it.span_id, [])
                if c.name == "decode.step"]
        if step:
            assert step[0].attrs["pages_in_use"] == \
                it.attrs["pages_in_use"]
            assert it.attrs["n_active"] == step[0].attrs["n_active"]


def test_a_retried_step_nests_under_decode_step():
    """An attempt that dies inside `step.enqueue` leaves that span open;
    the retry closes it first, so its own spans are children of
    `decode.step` and not of the failed attempt's."""
    dec, embed, proj, D, V = _small_stack(seed=205)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32)
    eng._sleep = lambda s: None
    sched = Scheduler(max_queue=4)
    r = Request(np.asarray([0, 3, 5], np.int32),
                np.random.RandomState(5).randn(4, D).astype("f4"),
                max_new_tokens=3, eos_id=None)
    program, failed = eng._program, []

    def flaky(key, build):
        if key[0] == "step" and not failed:
            failed.append(key)
            raise RuntimeError("injected: the enqueue dies once")
        return program(key, build)

    eng._program = flaky
    with T.session_scope() as tr:
        sched.submit(r)
        eng.serve_until_idle(sched, max_iterations=50)
    assert failed and r.result(timeout=5).ok
    assert not tr.open_spans()
    by_id = {s.span_id: s for s in tr.spans()}
    enq = [s for s in tr.spans() if s.name == "step.enqueue"]
    # token 0 comes from the join, two decode steps follow; and the
    # failed attempt's span is in the record too
    assert len(enq) == 1 + 2
    for s in tr.spans():
        if s.name.startswith("step."):
            assert by_id[s.parent_id].name == "decode.step", s.name


def test_trainer_step_is_annotated_without_a_session(tmp_path):
    """SpmdTrainer.step: train.step (the step's number on it) over
    train.next_key, train.shard, train.enqueue — profiler annotations
    only, no tracer session involved."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.optimizer import functional as fopt
    from paddle_tpu.parallel import SpmdTrainer, init_mesh

    init_mesh(dp=1, devices=jax.devices("cpu")[:1])
    net = nn.Linear(8, 3)
    tr = SpmdTrainer(
        net, lambda out, y: jnp.mean((out - y) ** 2), fopt.sgd(0.1))
    x = np.random.RandomState(0).randn(4, 8).astype("f4")
    y = np.zeros((4, 3), "f4")
    float(tr.step((x,), y))                              # compile
    assert T.session() is None
    events = _profiled(tmp_path, lambda: float(tr.step((x,), y)))
    ev = {e[0]: e for e in events if e[0].startswith("train.")}
    assert set(ev) == {"train.step", "train.next_key", "train.shard",
                       "train.enqueue"}
    root = ev["train.step"]
    assert root[3]["step_num"] == 2
    order = [ev[n] for n in ("train.next_key", "train.shard",
                             "train.enqueue")]
    for a, b in zip(order, order[1:]):
        assert a[2] <= b[1]
    assert all(root[1] <= e[1] and e[2] <= root[2] for e in order)
    # the compiled step is named and scoped for the device's side
    data = tr.shard_batch(x, y)
    text = tr._step_fn.lower(tr.params, tr.opt_state, tr.buffers,
                             jax.random.PRNGKey(0), data[:-1],
                             data[-1]).as_text(debug_info=True)
    assert "module @jit_train_step" in text
    assert "train_step/fwd_bwd/" in text
    assert "train_step/optimizer/" in text


def _pallas_call_names():
    """{file: [name= of every pallas_call]} under paddle_tpu/ops, from
    the AST; a call without a literal name= yields None (a choice
    between two literals, one kernel body under two names, yields
    both)."""
    import ast
    import glob
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "paddle_tpu",
                        "ops")
    out = {}
    for path in sorted(glob.glob(os.path.join(root, "*.py"))):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "attr", None) == "pallas_call":
                name = [c.value for k in node.keywords
                        if k.arg == "name"
                        for c in ((k.value.body, k.value.orelse)
                                  if isinstance(k.value, ast.IfExp)
                                  else (k.value,))
                        if isinstance(c, ast.Constant)]
                out.setdefault(os.path.basename(path), []).extend(
                    name or [None])
    return out


def test_every_pallas_kernel_has_a_fixed_name():
    names = _pallas_call_names()
    flat = [n for ns in names.values() for n in ns]
    assert len(flat) >= 14 and None not in flat, names
    # the two spellings of one kernel (with and without scalar
    # prefetch) share its name; no two kernels do
    assert sorted(set(flat)) == sorted([
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_decode",
        "flash_verify", "paged_flash_decode", "paged_flash_verify",
        "int8_matmul", "lora_gather", "fused_conv_fwd",
        "fused_conv_bwd", "selective_scan"])
    assert all(not any(c.isdigit() for c in n.replace("int8", ""))
               for n in flat)                     # no shape in a name


@pytest.mark.parametrize("placement", ["single", "sharded"])
def test_pool_programs_lower_under_their_kind(placement):
    """Every program a placement builds is jitted from a body named
    key[0] under jax.named_scope(key[0])."""
    import jax.numpy as jnp

    dec, embed, proj, D, V = _small_stack(seed=211)
    if placement == "single":
        eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                            paged=True, page_size=8)
    else:
        from paddle_tpu.parallel import init_mesh
        from paddle_tpu.serving import ShardedServingEngine

        eng = ShardedServingEngine(
            dec, embed, proj, mesh=init_mesh(dp=2, fsdp=2, tp=2),
            num_slots=4, max_len=32)

    def body(x):
        return {"tok": x + 1}

    for key in (("pstep", 2), ("pjoin", 8), ("attach",), ("join", 4)):
        fn = eng.placement.build(key, body, has_aux=False)
        assert fn.__name__ == key[0]
        text = fn.lower(jnp.zeros((4,), jnp.int32)).as_text(
            debug_info=True)
        assert f"module @jit_{key[0]}" in text, text[:200]
        assert f"jit({key[0]})/{key[0]}/add" in text


def test_page_iterations_counts_pages_per_iteration():
    dec, embed, proj, D, V = _small_stack(seed=221)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        paged=True, page_size=8)
    sched = Scheduler(max_queue=4)
    sched.submit(Request(np.asarray([0, 3, 5], np.int32),
                         np.random.RandomState(5).randn(4, D).astype(
                             "f4"), max_new_tokens=12, eos_id=None))
    want = 0
    for _ in range(6):
        before = eng.metrics.snapshot().get("paging", {}).get(
            "page_iterations", 0)
        eng.run_iteration(sched)
        pg = eng.metrics.snapshot()["paging"]
        assert pg["page_iterations"] - before == pg["pages_in_use"]
        assert pg["pages_total"] == pg["pages_in_use"] + \
            pg["pages_free"] == eng.num_pages
        want += pg["pages_in_use"]
    assert eng.metrics.snapshot()["paging"]["page_iterations"] == want > 0
    eng.serve_until_idle(sched, max_iterations=50)


def test_live_page_iterations_counts_the_written_pages():
    """`paging.live_page_iterations` grows each iteration by the sum
    over occupied slots of ceil(written / page_size) — the (slot, page)
    grid steps of a paged decode call that do work — and its growth
    over iterations x `paging.table_entries_total` is their share; an
    idle spin adds nothing."""
    from paddle_tpu.serving.paging import pages_for

    dec, embed, proj, D, V = _small_stack(seed=221)
    eng = ServingEngine(dec, embed, proj, num_slots=3, max_len=32,
                        paged=True, page_size=8)
    sched = Scheduler(max_queue=4)
    rs = np.random.RandomState(5)
    for prompt, n_new in (([0, 3, 5], 14), ([0, 2, 4, 6, 7, 9, 3, 5, 8],
                                            6)):
        sched.submit(Request(np.asarray(prompt, np.int32),
                             rs.randn(4, D).astype("f4"),
                             max_new_tokens=n_new, eos_id=None))
    want, its = 0, 0
    while sched.depth() > 0 or eng.occupancy() > 0:
        before = eng.metrics.snapshot().get("paging", {}).get(
            "live_page_iterations", 0)
        eng.run_iteration(sched)
        its += 1
        live = sum(pages_for(int(eng._index[s]), 8)
                   for s, r in enumerate(eng.slots) if r is not None)
        pg = eng.metrics.snapshot()["paging"]
        assert pg["live_page_iterations"] - before == live
        # a slot's written pages are mapped pages
        assert live <= pg["pages_in_use"]
        want += live
    pg = eng.metrics.snapshot()["paging"]
    assert pg["table_entries_total"] == 3 * eng.max_pages == 12
    assert pg["live_page_iterations"] == want > its
    share = want / (its * pg["table_entries_total"])
    assert 0 < share < 1
    assert eng.metrics.snapshot()["iterations"] == its
    for _ in range(3):
        eng.run_iteration(sched)            # idle: no slot, no queue
    assert eng.metrics.snapshot()["paging"]["live_page_iterations"] \
        == want


def test_live_block_iterations_counts_the_grid_steps_that_work():
    """`paging.live_block_iterations` grows each iteration by the sum
    over occupied slots of ceil(written pages / pages_per_block): the
    grid steps of a paged decode call that fetch and compute.
    `paging.pages_per_block` is the decode call's block from the pool's
    shapes (2 heads of 64 on 128 lanes, 8-token pages, 4 pages a slot:
    4), `live_blocks` rides the `iteration` span, and the benchmark's
    `snapshot_ratio` over the three keys reads what the
    `block_fill_share.*` files declare: written pages over the page
    rows the working steps take."""
    import os

    from benchmark.readers import snapshot_ratio
    from paddle_tpu.ops import attention as A
    from paddle_tpu.serving.paging import pages_for

    dec, embed, proj, D, V = _small_stack(seed=221, D=128)
    eng = ServingEngine(dec, embed, proj, num_slots=3, max_len=32,
                        paged=True, page_size=8)
    sched = Scheduler(max_queue=4)
    rs = np.random.RandomState(5)
    for prompt, n_new in (([0, 3, 5], 14), ([0, 2, 4, 6, 7, 9, 3, 5, 8],
                                            6)):
        sched.submit(Request(np.asarray(prompt, np.int32),
                             rs.randn(4, D).astype("f4"),
                             max_new_tokens=n_new, eos_id=None))
    blocks = pages = 0
    opened = None
    with T.session_scope() as tr:
        while sched.depth() > 0 or eng.occupancy() > 0:
            eng.run_iteration(sched)
            if opened is None:
                opened = eng.metrics.snapshot()
            written = [pages_for(int(eng._index[s]), 8)
                       for s, r in enumerate(eng.slots) if r is not None]
            pages += sum(written)
            blocks += sum(-(-n // 4) for n in written)
    pg = eng.metrics.snapshot()["paging"]
    assert pg["pages_per_block"] == 4 == A._paged_block_pages(
        8, 128, 1, eng.max_pages, "float32")
    assert pg["live_block_iterations"] == blocks > 0
    assert pg["live_page_iterations"] == pages > blocks
    roots = [sp for sp in tr.spans() if sp.name == "iteration"]
    assert sum(sp.attrs["live_blocks"] for sp in roots) == blocks
    assert all(sp.attrs["pages_per_block"] == 4 for sp in roots)
    for suffix in ("sat", "chat", "steady"):
        with open(os.path.join(
                os.path.dirname(__file__), "..", "benchmark",
                "layer_metrics", f"block_fill_share.{suffix}.json")) as f:
            doc = json.load(f)
        assert doc["reader"] == "snapshot_ratio"
        got = snapshot_ratio.read(
            {"snapshot_open": opened,
             "snapshot_close": eng.metrics.snapshot()}, **doc["args"])
        o = opened["paging"]
        want = 100.0 * (pages - o["live_page_iterations"]) / (
            (blocks - o["live_block_iterations"]) * 4)
        assert got == pytest.approx(want) and 25 <= got <= 100
    # a pool whose rows do not tile takes the gather: a block is a page
    dec, embed, proj, D, V = _small_stack(seed=221)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        paged=True, page_size=8)
    sched = Scheduler(max_queue=2)
    sched.submit(Request(np.asarray([0, 3, 5], np.int32),
                         rs.randn(4, D).astype("f4"), max_new_tokens=4,
                         eos_id=None))
    eng.serve_until_idle(sched, max_iterations=50)
    pg = eng.metrics.snapshot()["paging"]
    assert pg["pages_per_block"] == 1
    assert pg["live_block_iterations"] == pg["live_page_iterations"] > 0


def test_one_function_builds_profiler_annotations():
    """`trace.annotation` is the only place in paddle_tpu that
    constructs a TraceAnnotation / StepTraceAnnotation."""
    import ast
    import glob
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "paddle_tpu")
    sites = []
    for path in glob.glob(os.path.join(root, "**", "*.py"),
                          recursive=True):
        tree = ast.parse(open(path).read())
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.Module)):
                continue
            for node in ast.iter_child_nodes(fn) if isinstance(
                    fn, ast.Module) else ast.walk(fn):
                if isinstance(node, ast.Call) and getattr(
                        node.func, "attr", getattr(node.func, "id", "")
                        ) in ("TraceAnnotation", "StepTraceAnnotation"):
                    sites.append((os.path.relpath(path, root),
                                  getattr(fn, "name", "<module>")))
    assert sorted(set(sites)) == [("profiler/trace.py", "annotation")], \
        sites
