"""Resource & cost observability: the profiler.costs accounting layer.

Covers: the live HBM ledger's exactness against hand-computed byte
footprints for the dense and paged pools (fp32 / bf16 / int8 pages);
the budget watermark (warns BEFORE OutOfPages/OOM, once per
excursion); XLA cost/memory capture over the shared JitCache and the
cost/compile/trace key-join round-trip (one identity across the cost
book, the compile spans, and trace_counts); MFU monotonicity in the
pool batch size on the fixed CPU spec; goodput dropping under an
injected-fault soak and recovering afterwards; hapi fit step-timing
telemetry; and the perf-gate comparison cells (pass / regress /
allowlisted / missing-row) plus a live 1-row smoke of the gate
machinery against the committed OP_BENCH baseline.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                             TransformerDecoderLayer)
from paddle_tpu.profiler import costs as C
from paddle_tpu.profiler import trace as T
from paddle_tpu.serving import Request, Scheduler, ServingEngine
from paddle_tpu.testing import faults

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")


def _stack(seed=7, D=32, H=2, V=17, layers=2, ffn=64):
    np.random.seed(seed)
    layer = TransformerDecoderLayer(D, H, ffn, dropout=0.0)
    dec = TransformerDecoder(layer, layers)
    dec.eval()
    return dec, nn.Embedding(V, D), nn.Linear(D, V), D, V


def _param_bytes(*nets):
    return sum(int(np.prod(p.shape)) * 4
               for net in nets for p in net.parameters())


def _mk_request(rs, D, V, pmax=6, nmax=8, **kw):
    P = int(rs.randint(1, pmax + 1))
    prompt = rs.randint(2, V, (P,)).astype(np.int32)
    prompt[0] = 0
    mem = np.random.RandomState(P * 31).randn(4, D).astype("f4")
    return Request(prompt, mem,
                   max_new_tokens=int(rs.randint(2, nmax + 1)),
                   eos_id=1, **kw)


def _serve(eng, n, seed=3, **kw):
    sched = Scheduler(max_queue=4 * n)
    rs = np.random.RandomState(seed)
    reqs = [sched.submit(_mk_request(rs, eng._mem_shape[1]
                                     if eng._mem_shape else 32, 17,
                                     **kw))
            for _ in range(n)]
    eng.serve_until_idle(sched, max_iterations=4000)
    return reqs


# ----------------------------------------------------------------------
# HBM ledger exactness
# ----------------------------------------------------------------------

def _expected_dense_pool(dec, S, L, M, Dm, itemsize=4):
    total = 4 * S + 4 * S * L + itemsize * S * M * Dm
    for layer in dec.layers:
        h, dh = layer.self_attn.num_heads, layer.self_attn.head_dim
        total += 2 * S * h * L * dh * itemsize + 4 * S  # K+V+index
        hc, dc = layer.cross_attn.num_heads, layer.cross_attn.head_dim
        total += 2 * S * hc * M * dc * itemsize
    return total


def _expected_paged_pool(dec, S, L, M, Dm, page_size, num_pages,
                         kv_dtype, itemsize=4):
    import jax.numpy as jnp

    from paddle_tpu.serving.paging import resolve_kv_dtype

    storage, quantized = resolve_kv_dtype(kv_dtype, jnp.float32)
    st = jnp.dtype(storage).itemsize
    total = 4 * S + 4 * S * L + itemsize * S * M * Dm
    total += S * (L // page_size) * 4               # device page table
    for layer in dec.layers:
        h, dh = layer.self_attn.num_heads, layer.self_attn.head_dim
        total += 2 * (num_pages + 1) * h * page_size * dh * st
        if quantized:
            total += 2 * (num_pages + 1) * h * 4    # [P+1, 1, H] f32
        hc, dc = layer.cross_attn.num_heads, layer.cross_attn.head_dim
        total += 2 * S * hc * M * dc * itemsize
    return total


def test_dense_ledger_matches_hand_computed_bytes():
    dec, embed, proj, D, V = _stack()
    S, L, M = 4, 32, 4
    eng = ServingEngine(dec, embed, proj, num_slots=S, max_len=L)
    mem = np.zeros((M, D), "f4")
    eng._ensure_state(mem)            # builds the pool, no compiles
    led = eng.memory_ledger()
    assert led["pool_bytes"] == _expected_dense_pool(dec, S, L, M, D)
    assert led["weights_bytes"] == _param_bytes(dec, embed, proj)
    snap = eng.metrics.snapshot()["memory"]
    assert snap["total_bytes"] == \
        led["weights_bytes"] + led["pool_bytes"]
    # dense pool: committed == live
    assert snap["in_use_bytes"] == snap["total_bytes"]


@pytest.mark.parametrize("kv_dtype", [None, "bfloat16", "int8"])
def test_paged_ledger_matches_hand_computed_bytes(kv_dtype):
    dec, embed, proj, D, V = _stack()
    S, L, M, page, pages = 4, 32, 4, 8, 12
    eng = ServingEngine(dec, embed, proj, num_slots=S, max_len=L,
                        paged=True, page_size=page, num_pages=pages,
                        kv_dtype=kv_dtype)
    eng._ensure_state(np.zeros((M, D), "f4"))
    led = eng.memory_ledger()
    assert led["pool_bytes"] == _expected_paged_pool(
        dec, S, L, M, D, page, pages, kv_dtype)
    snap = eng.metrics.snapshot()["memory"]
    assert snap["total_bytes"] == \
        _param_bytes(dec, embed, proj) + led["pool_bytes"]
    # nothing mapped yet: live = committed - every free page
    assert snap["in_use_bytes"] == \
        snap["total_bytes"] - pages * eng._page_bytes


def test_watermark_warns_before_oom():
    # unit: crossing fires once per excursion (hysteresis)
    from paddle_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.budget_bytes = 1000
    m.watermark_frac = 0.9
    assert not m.check_memory_watermark(800)
    assert m.check_memory_watermark(950)
    assert m.check_memory_watermark(960)   # still above: no new warn
    assert not m.check_memory_watermark(500)
    assert m.check_memory_watermark(901)
    assert m.watermark_warnings == 2
    # engine: a dense pool whose committed footprint exceeds the
    # watermark warns the moment the pool is BUILT — before any join
    # could OOM
    dec, embed, proj, D, V = _stack()
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                        hbm_budget_bytes=100_000)   # weights ~107KB
    eng._ensure_state(np.zeros((4, D), "f4"))
    snap = eng.metrics.snapshot()["memory"]
    assert snap["watermark_warnings"] == 1
    assert snap["budget_used_frac"] > 1.0


# ----------------------------------------------------------------------
# XLA capture + the cost/compile/trace key-join
# ----------------------------------------------------------------------

def test_costbook_capture_and_key_join_roundtrip():
    dec, embed, proj, D, V = _stack()
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32)
    with C.accounting_scope() as bk, T.session_scope() as tr:
        reqs = _serve(eng, 5)
        assert all(r.result(timeout=5).ok for r in reqs)
        snap = eng.metrics.snapshot()
    # every compiled program got an XLA cost record with real numbers
    assert bk.keys(), "nothing captured"
    for c in bk.costs():
        assert c.source == "xla"
        assert c.flops > 0 and c.bytes_accessed > 0
        assert c.argument_bytes > 0
    # key-join round-trip: cost book == trace_counts == compile spans
    traced = {k for k, v in eng.trace_counts.items() if v > 0}
    booked = {k for owner, k in bk.keys()
              if owner == "ServingEngine"}
    assert booked == traced
    span_keys = {s.attrs["key"] for s in tr.spans()
                 if s.cat == "compile"}
    assert span_keys == {T._key_str(k) for k in traced}
    # the armed soak populated the MFU gauges from the step's record
    assert snap["mfu"]["cost_source"] == "xla"
    assert snap["mfu"]["flops_per_step"] > 0
    assert snap["mfu"]["model_flops_util"]["n"] > 0
    assert snap["mfu"]["bandwidth_util"]["n"] > 0
    # compile temp high-water reached the memory section while armed
    assert snap["memory"]["compile_temp_peak_bytes"] == \
        bk.temp_high_water()
    # the retrace sentinel did NOT see the capture's deliberate
    # re-lowers: every key still counts exactly one trace
    assert all(v == 1 for v in eng.trace_counts.values()), \
        dict(eng.trace_counts)


def test_capture_disabled_falls_back_to_analytic():
    dec, embed, proj, D, V = _stack()
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32)
    with C.accounting_scope(capture_xla=False) as bk:
        reqs = _serve(eng, 3)
        assert all(r.result(timeout=5).ok for r in reqs)
        snap = eng.metrics.snapshot()
    assert bk.keys()
    assert all(c.source == "analytic" for c in bk.costs())
    assert snap["mfu"]["cost_source"] == "analytic"
    assert snap["mfu"]["flops_per_step"] > 0


# ----------------------------------------------------------------------
# MFU math
# ----------------------------------------------------------------------

def test_mfu_monotone_in_batch_size_on_cpu_spec():
    dec, embed, proj, D, V = _stack()
    flops = []
    for S in (2, 4, 8):
        eng = ServingEngine(dec, embed, proj, num_slots=S, max_len=32)
        eng._ensure_state(np.zeros((4, D), "f4"))
        hint = eng.cost_hint(eng._step_cost_key())
        flops.append(hint["flops"])
    assert flops[0] < flops[1] < flops[2]
    # at a fixed reference step time, MFU is monotone in the batch's
    # flops — and stays a sane fraction of peak on the CPU spec
    ref_dt = 1e-3
    ms = [C.mfu(f, ref_dt, C.CPU_SPEC) for f in flops]
    assert ms[0] < ms[1] < ms[2]
    assert all(0 < m < 1 for m in ms)
    assert C.mfu(1e9, 0.0, C.CPU_SPEC) == 0.0
    assert C.bw_util(1e9, 0.0, C.CPU_SPEC) == 0.0


def test_device_spec_detection_and_table():
    spec = C.detect_spec()
    assert spec.name == "cpu"          # tests pin the CPU backend
    for s in C.DEVICE_SPECS.values():
        assert s.peak_flops > 0 and s.peak_bytes_per_s > 0
        d = s.as_dict()
        assert set(d) == {"name", "peak_tflops", "peak_gbps", "hbm_gb"}


@pytest.mark.parametrize("kind,tflops", [("TPU v5 lite", 197.0),
                                         ("TPU v9 imagined", None)])
def test_device_spec_by_reported_kind(monkeypatch, kind, tflops):
    """The table is keyed by the `device_kind` the chip really reports
    (a v5e says "TPU v5 lite") with the published peaks; a non-CPU
    device it does not know is an error, never the CPU's roofline."""
    import jax

    class _Dev:
        device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    if tflops is None:
        with pytest.raises(LookupError, match="TPU v9 imagined"):
            C.detect_spec()
    else:
        spec = C.detect_spec()
        assert spec.as_dict() == {"name": kind, "peak_tflops": tflops,
                                  "peak_gbps": 819.0, "hbm_gb": 16.0}


# ----------------------------------------------------------------------
# goodput under faults
# ----------------------------------------------------------------------

def test_goodput_drops_under_faults_and_recovers():
    dec, embed, proj, D, V = _stack()
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                        max_attempts=1)
    reqs = _serve(eng, 4, nmax=6)
    assert all(r.result(timeout=5).ok for r in reqs)
    g0 = eng.metrics.snapshot()["goodput"]
    assert g0["ratio"] == 1.0 and g0["useful_tokens"] > 0
    # inject decode-step failures mid-soak: in-flight requests get
    # evicted with partial tokens -> wasted grows, ratio drops
    with faults.inject("serving.decode_step", on="nth", n=3,
                       max_fires=1):
        sched = Scheduler(max_queue=16)
        rs = np.random.RandomState(11)
        bad = [_mk_request(rs, D, V, nmax=8) for _ in range(4)]
        for r in bad:
            # the wave must still be decoding when the third step
            # fails: no eos (a 17-token vocab emits it within a token
            # or two under some weight draws) and a full budget
            r.eos_id, r.max_new_tokens = None, 8
            sched.submit(r)
        eng.serve_until_idle(sched, max_iterations=2000)
        for r in bad:
            r.result(timeout=5)
    g1 = eng.metrics.snapshot()["goodput"]
    assert g1["wasted_tokens"] > 0
    assert g1["ratio"] < 1.0
    # clean serving afterwards: useful grows, ratio recovers upwards
    more = _serve(eng, 8, seed=5)
    assert all(r.result(timeout=5).ok for r in more)
    g2 = eng.metrics.snapshot()["goodput"]
    assert g2["useful_tokens"] > g1["useful_tokens"]
    assert g2["ratio"] > g1["ratio"]
    # warmup windows divert tokens out of the useful numerator
    eng.metrics.begin_warmup()
    warm = _serve(eng, 2, seed=9)
    assert all(r.result(timeout=5).ok for r in warm)
    eng.metrics.end_warmup()
    g3 = eng.metrics.snapshot()["goodput"]
    assert g3["warmup_tokens"] > 0


def test_retry_tokens_counted():
    dec, embed, proj, D, V = _stack()
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        max_attempts=3, backoff_base_s=0.0)
    reqs = _serve(eng, 2, nmax=4)
    assert all(r.result(timeout=5).ok for r in reqs)
    with faults.inject("serving.decode_step", on="nth", n=2,
                       max_fires=1):
        reqs = _serve(eng, 2, seed=8, nmax=6)
    # the retried attempt burned active-slot token work, then the step
    # succeeded: requests still finish ok and the burn is on the books
    assert all(r.result(timeout=5).ok for r in reqs)
    g = eng.metrics.snapshot()["goodput"]
    assert g["retry_tokens"] > 0
    assert g["ratio"] < 1.0


# ----------------------------------------------------------------------
# hapi fit telemetry
# ----------------------------------------------------------------------

def test_fit_step_timing_and_goodput():
    from paddle_tpu.io import TensorDataset

    np.random.seed(0)
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    m = paddle.Model(net)
    m.prepare(optimizer=paddle.optimizer.Adam(
        0.01, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss())
    rs = np.random.RandomState(1)
    ds = TensorDataset([rs.randn(16, 4).astype("f4"),
                        rs.randint(0, 2, (16, 1)).astype("i8")])
    with pytest.raises(RuntimeError):
        m.fit_report()
    m.fit(ds, batch_size=4, epochs=2, verbose=0)
    st = m.fit_stats
    assert st["steps"] == 8
    assert 0 < st["train_s"] <= st["wall_s"]
    assert 0 < st["goodput"] <= 1.0
    assert st["step_ms_p50"] > 0
    rep = m.fit_report(flops_per_step=1e6)
    assert rep["mfu"] > 0 and rep["device"]["name"] == "cpu"


# ----------------------------------------------------------------------
# perf gate
# ----------------------------------------------------------------------

def _gate_mod():
    sys.path.insert(0, TOOLS)
    import perf_gate

    return perf_gate


def test_perf_gate_unit_cells():
    pg = _gate_mod()
    # lower-better (op step time): 2x slower fails, within-tol passes
    assert pg.evaluate_row("lower", 100.0, 150.0, 2.0) == "pass"
    assert pg.evaluate_row("lower", 100.0, 201.0, 2.0) == "regress"
    # higher-better (bench value): a 2x-inflated baseline fails
    assert pg.evaluate_row("higher", 3.8, 3.0, 1.5) == "pass"
    assert pg.evaluate_row("higher", 7.6, 3.0, 1.5) == "regress"
    assert pg.evaluate_row("higher", None, 3.0, 1.5) == "missing"
    with pytest.raises(ValueError):
        pg.evaluate_row("sideways", 1, 1, 2.0)
    rows = [
        {"name": "op:a", "direction": "lower", "tol": 2.0,
         "baseline": 10.0, "fresh": 11.0},
        {"name": "op:b", "direction": "lower", "tol": 2.0,
         "baseline": 10.0, "fresh": 25.0},
        {"name": "op:c", "direction": "lower", "tol": 2.0,
         "baseline": 10.0, "fresh": 30.0},
        {"name": "bench:d", "direction": "higher", "tol": 1.5,
         "baseline": 4.0, "fresh": None},
    ]
    out = pg.gate(rows, allowlist=["op:c"])
    st = {r["name"]: r["status"] for r in out["rows"]}
    assert st == {"op:a": "pass", "op:b": "regress",
                  "op:c": "allowlisted", "bench:d": "missing-row"}
    assert out["regressions"] == ["op:b"]
    assert out["missing"] == ["bench:d"]
    assert not out["ok"]
    # all-pass -> ok
    assert pg.gate(rows[:1])["ok"]


def test_perf_gate_live_smoke(tmp_path):
    """Tier-1 smoke of the MACHINERY: one real cheap op row measured
    fresh against the committed OP_BENCH baseline (loose tolerance —
    this box timeshares one core), then the same fresh measurement
    re-gated against a synthetically tampered baseline must fail with
    the row named."""
    pg = _gate_mod()
    out = tmp_path / "gate.json"
    payload = pg.run_gate(["sequence_mask"], k=1, tol_op=25.0,
                          out=str(out))
    assert payload["ok"], payload
    assert json.load(open(out))["rows"][0]["name"] == \
        "op:sequence_mask"
    # re-gate the SAME fresh number against a tampered baseline (no
    # second measurement): baseline shrunk so fresh reads as a >25x
    # regression
    row = dict(payload["rows"][0])
    row["baseline"] = row["fresh"] / 30.0
    bad = pg.gate([row])
    assert not bad["ok"]
    assert bad["regressions"] == ["op:sequence_mask"]
