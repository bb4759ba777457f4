"""Static analyzer (paddle_tpu.analysis): every rule id proven live.

For each rule there is a MINIMAL deliberately-broken fixture the
analyzer must flag (the rule is dead the day this stops failing), plus
the real-tree acceptance run: the committed baseline makes the whole
gate green, and the step-donation fix is proven live at runtime (the
decode step actually consumes its carry).

Rules under test (see README "Static analysis"):
  PTA101 jaxpr-baked-const        PTA201 lock-unguarded-mutation
  PTA102 jaxpr-undonated-carry    PTA202 snapshot-doc-drift
  PTA103 jaxpr-dtype-promotion    PTA203 unregistered-fault-point
  PTA104 jaxpr-host-callback      PTA204 host-call-in-jit-body
  PTA105 jaxpr-unsharded-carry
"""
import textwrap

import numpy as np
import pytest

from paddle_tpu.analysis import (Baseline, Finding, analyze_program,
                                 check_source, repo_rules)

LB = 4096  # "large" threshold for the tiny fixture programs


def _rules(findings, rule):
    return [f for f in findings if f.rule == rule]


def _jit(fn, **kw):
    import jax

    return jax.jit(fn, **kw)


# ----------------------------------------------------------------------
# jaxpr rules: one broken fixture each (+ the fixed twin stays clean)
# ----------------------------------------------------------------------

def test_pta101_baked_constant():
    import jax.numpy as jnp

    big = np.arange(2048, dtype=np.float32)          # 8 KiB baked in

    def bad(x):
        return x + jnp.asarray(big)

    fs = analyze_program(("step", 1), _jit(bad),
                         (jnp.zeros(2048, jnp.float32),),
                         large_bytes=LB)
    assert len(_rules(fs, "PTA101")) == 1

    def good(x, table):                              # passed as arg
        return x + table

    fs = analyze_program(("step", 1), _jit(good),
                         (jnp.zeros(2048, jnp.float32),
                          jnp.asarray(big)), large_bytes=LB)
    assert not _rules(fs, "PTA101")


def test_pta102_undonated_carry():
    import jax.numpy as jnp

    def step(state, x):
        return {"kv": state["kv"] + x}, x * 2

    st = {"kv": jnp.zeros((64, 64), jnp.float32)}    # 16 KiB carry
    fs = analyze_program(("step", 1), _jit(step),
                         (st, jnp.float32(1.0)),
                         owner="Fix", large_bytes=LB)
    (f,) = _rules(fs, "PTA102")
    assert f.baseline_key == "Fix:step:arg0"

    fs = analyze_program(("step", 1),
                         _jit(step, donate_argnums=(0,)),
                         (st, jnp.float32(1.0)), large_bytes=LB)
    assert not _rules(fs, "PTA102")

    # declared donation (backend-gated wrappers) also satisfies it
    fs = analyze_program(("step", 1), _jit(step),
                         (st, jnp.float32(1.0)), large_bytes=LB,
                         declared_donated=(0,))
    assert not _rules(fs, "PTA102")


def test_pta103_dtype_promotion():
    import jax.numpy as jnp

    def widen(x):                       # bf16 op upcast to f32
        return x + jnp.float32(1.0)

    fs = analyze_program(("step", 1), _jit(widen),
                         (jnp.zeros((4,), jnp.bfloat16),),
                         large_bytes=LB)
    assert any("bfloat16 -> float32" in f.message
               for f in _rules(fs, "PTA103"))

    def f64(x):                         # weak python-float -> f64
        return jnp.where(x > 0, 0.0, -1e30)

    fs = analyze_program(("step", 1), _jit(f64),
                         (jnp.zeros((4,), jnp.float32),),
                         large_bytes=LB)
    assert any("float64" in f.message for f in _rules(fs, "PTA103"))

    def clean(x):                       # typed literals: no finding
        return jnp.where(x > 0, jnp.float32(0.0), jnp.float32(-1e30))

    fs = analyze_program(("step", 1), _jit(clean),
                         (jnp.zeros((4,), jnp.float32),),
                         large_bytes=LB)
    assert not _rules(fs, "PTA103")


def test_pta104_host_callback():
    import jax

    # jax 0.9 stages jax.debug.print as its own `debug_print`
    # primitive; jax.debug.callback still stages `debug_callback`
    def printing(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    def calling(x):
        jax.debug.callback(lambda v: None, x)
        return x + 1

    for bad, prim in ((printing, "debug_print"),
                      (calling, "debug_callback")):
        fs = analyze_program(("step", 1), _jit(bad),
                             (jax.numpy.zeros((4,)),), large_bytes=LB)
        assert any(f"`{prim}`" in f.message
                   for f in _rules(fs, "PTA104")), (prim, fs)


def test_pta105_unsharded_carry():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    mesh = Mesh(np.asarray(devs[:2]).reshape(2), ("dp",))
    ns = NamedSharding(mesh, P("dp"))

    def step(state):
        good = jax.lax.with_sharding_constraint(state["a"] + 1, ns)
        bad = state["b"] * 2                 # carry, no constraint
        return {"a": good, "b": bad}

    st = {"a": jnp.zeros((2, 64, 16), jnp.float32),
          "b": jnp.zeros((2, 64, 16), jnp.float32)}
    fs = analyze_program(("step", 1), _jit(step), (st,),
                         sharded=True, large_bytes=LB)
    assert len(_rules(fs, "PTA105")) == 1
    # derived-from-constrained and passthrough carries are both fine
    def ok(state):
        a = jax.lax.with_sharding_constraint(state["a"] + 1, ns)
        return {"a": a * 2, "b": state["b"]}

    fs = analyze_program(("step", 1), _jit(ok), (st,),
                         sharded=True, large_bytes=LB)
    assert not _rules(fs, "PTA105")


# ----------------------------------------------------------------------
# AST rules
# ----------------------------------------------------------------------

_LOCKED_SRC = textwrap.dedent('''
    import threading

    class Sink:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
            self.stats = {"hits": 0}
            self.rows = []

        def locked(self):
            with self._lock:
                self.n += 1
                self.rows.append(1)

        def unlocked(self):
            self.n += 1
            self.stats["hits"] += 1
            self.rows.append(2)

        def exempt(self):   # analysis: single-threaded
            self.n = 0

        def exempt_stmt(self):
            self.n = 0      # analysis: single-threaded

    class Unlocked:
        def free(self):     # no lock attr => class not checked
            self.x = 1
''')


def test_pta201_lock_discipline():
    fs = check_source(_LOCKED_SRC, "fixture.py")
    hits = _rules(fs, "PTA201")
    assert sorted(f.baseline_key for f in hits) == [
        "fixture.py:Sink.unlocked:n",
        "fixture.py:Sink.unlocked:rows",
        "fixture.py:Sink.unlocked:stats",
    ]


def test_pta204_host_calls_in_jit_bodies():
    src = textwrap.dedent('''
        import jax
        import numpy as np
        import time

        class Eng:
            def _step_body(self, key):
                def step_fn(state):
                    x = np.asarray(state)     # host transfer
                    t = time.time()           # host clock
                    return x
                return step_fn

            def host_side(self):
                return np.zeros(3)            # not a jitted body: fine

        def _build():
            def fused(p):
                return np.square(p)           # jax.jit(fused) below
            return jax.jit(fused, donate_argnums=(0,))
    ''')
    fs = check_source(src, "fixture.py")
    keys = sorted(f.baseline_key for f in _rules(fs, "PTA204"))
    assert keys == [
        "fixture.py:fused:np.square",
        "fixture.py:step_fn:np.asarray",
        "fixture.py:step_fn:time.time",
    ]


# ----------------------------------------------------------------------
# repo rules
# ----------------------------------------------------------------------

def test_pta202_snapshot_doc_drift(tmp_path):
    src = textwrap.dedent('''
        class ServingMetrics:
            def snapshot(self):
                mem = {"pool_bytes": 1}
                return {
                    "joins": self.joins,
                    "requests": {"submitted": 1, "ghost": 2},
                    **({} if self.m is None else {"memory": mem}),
                }
    ''')
    keys = repo_rules.snapshot_keys_from_source(src)
    assert keys == {"joins", "requests.submitted", "requests.ghost",
                    "memory.pool_bytes"}
    p = tmp_path / "metrics.py"
    p.write_text(src)
    docs = {"joins": 0, "requests.submitted": 0,
            "memory.pool_bytes": 0, "requests.dropped_doc": 0}
    fs = repo_rules.snapshot_doc_findings(str(p), docs=docs)
    assert {f.baseline_key for f in fs} == {
        "snapshot:undocumented:requests.ghost",
        "snapshot:unemitted:requests.dropped_doc"}
    assert all(f.rule == repo_rules.RULE_SNAPSHOT_DOC for f in fs)


def test_pta202_real_tree_in_sync():
    """The static extraction agrees with SNAPSHOT_DOCS on the real
    metrics module — the same invariant the dynamic doc-test in
    test_tracing.py pins, enforced at the source level."""
    assert repo_rules.snapshot_doc_findings() == []


def test_pta203_unregistered_fault_point(tmp_path):
    prod = tmp_path / "prod.py"
    prod.write_text('from x import faults\n'
                    '_PT = faults.point("serving.real")\n')
    t = tmp_path / "test_it.py"
    t.write_text('faults.inject("serving.real")\n'
                 'faults.inject("serving.typo")\n')
    fs = repo_rules.fault_point_findings([str(prod)], [str(t)])
    assert [f.baseline_key for f in fs] == ["faults:serving.typo"]
    assert fs[0].rule == repo_rules.RULE_FAULT_POINT


# ----------------------------------------------------------------------
# baseline mechanics (the ratchet)
# ----------------------------------------------------------------------

def test_baseline_match_wildcard_and_stale(tmp_path):
    b = Baseline([
        {"rule": "PTA102", "match": "*:join:arg2", "justification": "j"},
        {"rule": "PTA102", "match": "Dead:*", "justification": "j"},
    ])
    f1 = Finding("PTA102", "w", "m", baseline_key="Eng:join:arg2")
    f2 = Finding("PTA102", "w", "m", baseline_key="Eng:step:arg2")
    f3 = Finding("PTA101", "w", "m", baseline_key="Eng:join:arg2")
    new, baselined, stale = b.split([f1, f2, f3])
    assert baselined == [f1]           # wildcard hit
    assert new == [f2, f3]             # wrong key / wrong rule
    assert stale == [{"rule": "PTA102", "match": "Dead:*",
                      "justification": "j"}]
    p = tmp_path / "b.json"
    b.save(p)
    assert len(Baseline.load(p).entries) == 2
    with pytest.raises(ValueError):
        Baseline([{"rule": "PTA102", "match": "x"}])  # no justification


# ----------------------------------------------------------------------
# the real tree: gate green, donation live, sentinel-safe
# ----------------------------------------------------------------------

def test_real_tree_static_findings_empty():
    """AST + repo lints over serving/, tuning/, profiler/ and the
    fused optimizer: ZERO findings on the committed tree (everything
    real was fixed at introduction time; nothing is baselined here)."""
    from paddle_tpu.analysis import static_findings

    assert static_findings() == []


def test_real_tree_program_gate_green():
    """The full program matrix (dense / spec / paged / sharded +
    fused optimizer step): every finding carries a justified baseline
    entry, none are new, no baseline entry is stale."""
    from paddle_tpu.analysis import run

    rep = run(fast=False)
    assert rep["ok"], [f.as_dict() for f in rep["new"]]
    assert rep["stale_baseline"] == []
    # the donation audit is alive AND the whole program matrix
    # donates its pool carry now: neither the step family nor the
    # join family (join/pjoin/attach/cow/pattach/splice/bsplice)
    # contributes a PTA102 finding — the only remaining waiver is the
    # fused optimizer's caller-owned grad buffers
    keys = {f.baseline_key for f in rep["baselined"]}
    for kind in ("join", "pjoin", "attach", "cow", "pattach",
                 "splice", "bsplice", "step", "pstep", "sstep"):
        assert not any(f":{kind}:" in k for k in keys), (kind, keys)
    assert all("FusedOptimizerStep" in k for k in keys), keys


def test_step_donation_is_live():
    """The PTA102 fix is real: the compiled decode step consumes its
    pool carry (donated buffer), it does not copy it."""
    import time

    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.serving import Request, Scheduler, ServingEngine

    np.random.seed(0)
    layer = TransformerDecoderLayer(32, 2, 64, dropout=0.0)
    dec = TransformerDecoder(layer, 2)
    dec.eval()
    eng = ServingEngine(dec, nn.Embedding(17, 32), nn.Linear(32, 17),
                        num_slots=2, max_len=32, clock=time.monotonic)
    sched = Scheduler(max_queue=4)
    rs = np.random.RandomState(1)
    prompt = rs.randint(2, 17, (3,)).astype(np.int32)
    prompt[0] = 0
    r = Request(prompt, rs.randn(4, 32).astype("f4"),
                max_new_tokens=4, eos_id=None)
    sched.submit(r)
    eng.run_iteration(sched)               # join + first decode step
    old_kv = eng._state["inc"][0].k
    eng.run_iteration(sched)               # donated step consumes it
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(old_kv)
    eng.serve_until_idle(sched, max_iterations=50)
    assert r.result(timeout=5).ok


def test_analyze_engine_does_not_trip_sentinel():
    """Analyzing a LIVE engine re-traces its programs deliberately;
    suppression + counter restore keep the retrace sentinel silent and
    trace_counts unchanged (same discipline as profiler.costs)."""
    from paddle_tpu.analysis import analyze_engine
    from paddle_tpu.analysis.runner import _small_stack
    from paddle_tpu.serving import ServingEngine, retrace_sentinel

    dec, emb, proj = _small_stack(seed=21)
    eng = ServingEngine(dec, emb, proj, num_slots=2, max_len=32)
    with retrace_sentinel(eng):
        analyze_engine(eng, (4, 32), prompt_buckets=(8,))
        before = dict(eng.trace_counts)
        analyze_engine(eng, (4, 32), prompt_buckets=(8,))
        assert dict(eng.trace_counts) == before
