"""The serving loop's host time by cause: each cause planted in a stream
callback and read back from `snapshot()["host"]`, the `iteration` span's
attributes, the collector's `gc` spans and the profiler's host events.

Every test runs under its own time limit (`_limited`): the collector hook
must never deadlock, and a hang fails the test instead of the suite.
"""
import functools
import gc
import threading
import time

import numpy as np
import pytest

from paddle_tpu import nn
from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                             TransformerDecoderLayer)
from paddle_tpu.profiler import trace as T
from paddle_tpu.serving import Request, Scheduler, ServingEngine
from paddle_tpu.serving.metrics import HOST_STALL_NS

N_NEW = 12          # tokens a request: token 0 in iter.tok0, the rest in
#                     iter.deliver, inside the host part


def _limited(seconds):
    """Run the test in a daemon thread and fail it past `seconds`."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kw):
            out = []

            def body():
                try:
                    fn(*args, **kw)
                except BaseException as e:     # handed to the test
                    out.append(e)

            t = threading.Thread(target=body, daemon=True)
            t.start()
            t.join(seconds)
            assert not t.is_alive(), f"{fn.__name__} ran past {seconds} s"
            if out:
                raise out[0]
        return run
    return wrap


def _build():
    """A small dense pool with its programs compiled by one request."""
    np.random.seed(17)
    dec = TransformerDecoder(TransformerDecoderLayer(32, 2, 64, dropout=0.0),
                             2)
    dec.eval()
    eng = ServingEngine(dec, nn.Embedding(17, 32), nn.Linear(32, 17),
                        num_slots=2, max_len=32)
    _serve(eng, None)
    return eng


@pytest.fixture(scope="module")
def engine():
    return _build()


def _serve(eng, stream_cb):
    """One request of N_NEW tokens, served to the end."""
    sched = Scheduler(max_queue=4)
    r = Request(np.asarray([0, 3, 5], np.int32),
                np.random.RandomState(5).randn(4, 32).astype("f4"),
                max_new_tokens=N_NEW, eos_id=None, stream_cb=stream_cb)
    sched.submit(r)
    eng.serve_until_idle(sched, max_iterations=100)
    assert r.result(timeout=5).ok


def _host(eng):
    h = eng.metrics.snapshot()["host"]
    assert h["cpu_ns"] + h["offcpu_ns"] == h["host_ns"]
    assert h["cpu_ns"] >= 0 and h["offcpu_ns"] >= 0
    assert h["gc_ns"] == sum(h["gc_ns_by_gen"])
    return h


def _delta(a, b):
    return {k: ([y - x for x, y in zip(a[k], b[k])]
                if isinstance(a[k], list) else b[k] - a[k]) for k in a}


def _accounted(eng, stream_cb):
    """(delta of snapshot()["host"], the session's tracer) over one
    request served under a fresh session."""
    with T.session_scope() as tr:
        _serve(eng, lambda r, tok: None)
        before = _host(eng)
        _serve(eng, stream_cb)
        after = _host(eng)
    return _delta(before, after), tr


def _pairs(eng, stream_cb):
    """(planted, plain, tracer): the deltas of a request whose callback
    does the planted work and of one whose callback does nothing."""
    plain, _ = _accounted(eng, lambda r, tok: None)
    planted, tr = _accounted(eng, stream_cb)
    return planted, plain, tr


@_limited(300)
def test_sleep_in_a_stream_callback_is_off_cpu(engine):
    planted, plain, tr = _pairs(engine, lambda r, tok: time.sleep(0.005))
    calls = N_NEW - 1
    assert planted["iterations"] > 0 and plain["iterations"] > 0
    assert planted["offcpu_ns"] >= 5e6 * calls
    assert planted["offcpu_ns"] - plain["offcpu_ns"] >= 4e6 * calls
    assert planted["cpu_ns"] - plain["cpu_ns"] < 2e6 * calls
    its = [s for s in tr.spans() if s.name == "iteration"]
    assert its and all({"cpu_ms", "offcpu_ms", "gc_ms"} <= set(s.attrs)
                       for s in its)


@_limited(300)
def test_a_sleep_inside_a_device_wait_is_no_host_time(engine):
    def first(r, tok):
        if len(r.tokens) == 1:      # token 0: delivered inside iter.tok0
            time.sleep(0.05)

    planted, plain, _ = _pairs(engine, first)
    assert planted["wait_ns"] - plain["wait_ns"] >= 45e6
    assert planted["host_ns"] - plain["host_ns"] < 25e6
    assert planted["cpu_ns"] - plain["cpu_ns"] > -5e6


def _spin(r, tok):
    t0 = time.thread_time_ns()
    while time.thread_time_ns() - t0 < 5_000_000:
        pass


@_limited(300)
def test_spinning_in_a_stream_callback_is_on_cpu(engine):
    planted, plain, _ = _pairs(engine, _spin)
    calls = N_NEW - 1
    assert planted["cpu_ns"] >= 5e6 * calls
    assert planted["cpu_ns"] - plain["cpu_ns"] >= 4e6 * calls


@_limited(300)
def test_a_collection_in_a_stream_callback_is_gc_and_a_stall(engine):
    # enough live containers that a full collection passes the stall
    # line on any machine
    ballast = [[] for _ in range(2_000_000)]
    calls = []

    def collect(r, tok):
        if len(r.tokens) == 3:
            t0 = time.perf_counter_ns()
            gc.collect()
            calls.append(time.perf_counter_ns() - t0)

    d, tr = _accounted(engine, collect)
    del ballast
    assert len(calls) == 1
    assert d["gc_collections"][2] >= 1
    assert d["gc_ns_by_gen"][2] > 0 and d["gc_ns"] >= d["gc_ns_by_gen"][2]
    full = [s for s in tr.spans()
            if s.name == "gc" and s.attrs["generation"] == 2]
    assert full and all(s.trace_id == 0 and s.t1 >= s.t0 for s in full)
    assert "collected" in full[-1].attrs
    ours = 1e9 * max(s.duration_s for s in full)
    its = [s for s in tr.spans() if s.name == "iteration"]
    assert max(s.attrs["gc_ms"] for s in its) * 1e6 >= 0.9 * ours
    if calls[0] > HOST_STALL_NS:
        assert d["stalls"] >= 1
        assert d["stall_gc_ns"] >= 0.9 * ours
        assert d["stall_host_ns"] >= calls[0]
        assert d["stall_cpu_ns"] <= d["stall_host_ns"]


def _host_events(trace_dir):
    import glob
    import os

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@_limited(300)
def test_a_collection_is_a_gc_event_inside_its_iteration(engine, tmp_path):
    import jax

    def collect(r, tok):
        if len(r.tokens) == 3:
            gc.collect()

    d = str(tmp_path / "xplane")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with T.session_scope():
            _serve(engine, collect)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(d)
    gcs = [e for e in events if e[0] == "gc" and e[3]["generation"] == 2]
    its = [e for e in events if e[0] == "iteration"]
    assert gcs and its
    inside = [g for g in gcs for i in its if i[1] <= g[1] and g[2] <= i[2]]
    assert inside
    assert "collected" in inside[0][3]
    it = [i for i in its if i[1] <= inside[0][1] <= i[2]][0]
    assert {"cpu_ms", "offcpu_ms", "gc_ms"} <= set(it[3])


@_limited(60)
def test_a_collection_under_the_tracers_own_lock_finishes():
    with T.session_scope() as tr:
        done = []

        def body():
            with tr._lock:
                gc.collect()
                done.append(True)

        t = threading.Thread(target=body, daemon=True)
        t.start()
        t.join(30)
        assert not t.is_alive(), "the collector hook deadlocked"
        assert done
        assert [s for s in tr.spans() if s.name == "gc"]


@_limited(120)
def test_no_session_no_hook_and_no_host_section():
    def ours():
        return [h for h in gc.callbacks if isinstance(h, T._GcHook)]

    assert T.session() is None and not ours()
    eng = _build()                      # served with no session
    assert "host" not in eng.metrics.snapshot()
    tr = T.start_session()
    try:
        assert ours() and ours()[0].tr is tr
        gc.collect()
        n = tr.gc_totals()
        assert n[2] > 0 and n[5] >= 1
    finally:
        T.end_session()
    assert not ours()
    gc.collect()                        # no session: nothing counts
    assert tr.gc_totals() == n
    T.start_session()
    T.reset()
    assert not ours()
