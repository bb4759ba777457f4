"""Profiler.

Reference parity: platform/profiler.h:126 RecordEvent RAII +
fluid/profiler.py (start_profiler/stop_profiler/profiler context). TPU-native
design: host annotations forward to jax.profiler.TraceAnnotation; device
timelines come from the XLA/XPlane trace (`start_profiler` starts a
jax.profiler trace whose output loads in TensorBoard / Perfetto — the
chrome://tracing equivalent of platform/device_tracer.cc).

`profiler.trace` adds the span-based host tracer the serving stack
reports into (per-request timelines, compile observer, retrace
sentinel); `start_profiler`/`stop_profiler` start and stop a tracer
session in lockstep with the XPlane trace, so the host span dump
(`<trace_dir>/host_trace.json`) loads in Perfetto next to the device
timeline.
"""
from __future__ import annotations

import collections
import contextlib
import os
import time

from . import trace  # noqa: F401  (paddle_tpu.profiler.trace)

#: host RecordEvent ring: bounded so an always-on process can leave
#: profiling annotations in place without unbounded growth
_EVENTS_CAP = 65536
_events = collections.deque(maxlen=_EVENTS_CAP)
_trace_dir = None
_active = False
_own_tracer = False
last_host_trace = None


def set_events_capacity(cap):
    """Resize the RecordEvent ring buffer (keeps the newest events)."""
    global _events, _EVENTS_CAP
    _EVENTS_CAP = int(cap)
    _events = collections.deque(_events, maxlen=_EVENTS_CAP)


class RecordEvent:
    """platform/profiler.h:126 parity; also usable as a decorator.
    Records (name, event_type, duration) host-side, enters a profiler
    annotation of the same name (`trace.annotation`, the one place
    that builds them), and — when a `profiler.trace` session is
    active — surfaces the event as a span in the tracer.

    Span links: under a session the span is opened first, so its
    (trace_id, span_id) identity exists before the device work runs
    and rides in the annotation's metadata — Perfetto shows both on
    the XPlane event's args, so a host span and its device timeline
    region correlate by id. Pass `trace_id=` to link the event to a
    request's trace (serving code passes the request id); `step_num=`
    makes it a step annotation (the profiler's `Steps` line). This is
    the spelling for code that runs without a session (the trainer)."""

    def __init__(self, name, event_type="op", trace_id=0,
                 step_num=None):
        self.name = name
        self.event_type = event_type
        self.trace_id = int(trace_id)
        self.step_num = step_num
        self._ann = None
        self._t0 = None
        self._span = self._tracer = None

    def __enter__(self):
        tr = trace._SESSION
        if tr is not None:
            self._tracer = tr
            self._span = tr.begin(self.name, cat="record_event",
                                  trace_id=self.trace_id,
                                  attrs={"event_type": self.event_type},
                                  forward=True, step_num=self.step_num)
        else:
            self._ann = trace.annotation(self.name, self.step_num)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        dt = t1 - self._t0
        _events.append((self.name, self.event_type, dt))
        if self._span is not None:
            # on the tracer that opened it, whatever the session is now
            self._tracer.end(self._span)
            self._span = self._tracer = None
        else:
            self._ann.__exit__(*exc)
            self._ann = None
        # _host_lib is only non-None after enable_host_trace(): the native
        # build/load never happens (nor does any lock) on the hot path
        # unless host tracing was explicitly turned on.
        if _host_lib is not None and _host_lib.pt_prof_enabled():
            now = _host_lib.pt_prof_now_ns()
            _host_lib.pt_prof_record(self.name.encode(),
                                     now - int(dt * 1e9), now)
        return False


_host_lib = None


def _native():
    """Native host-event recorder (csrc/ptcore/profiler.cc) when built."""
    global _host_lib
    if _host_lib is None:
        try:
            from ..core.native import load_library

            _host_lib = load_library()
        except Exception:
            return None
    return _host_lib


def export_chrome_tracing(path):
    """Dump host RecordEvents as a chrome://tracing JSON file
    (platform/device_tracer.cc GenProfile capability)."""
    lib = _native()
    if lib is None:
        raise RuntimeError("native profiler unavailable")
    if lib.pt_prof_dump(path.encode()) != 0:
        raise IOError(f"trace dump failed: {path}")
    return path


def enable_host_trace():
    lib = _native()
    if lib is not None:
        lib.pt_prof_enable()


def disable_host_trace():
    lib = _native()
    if lib is not None:
        lib.pt_prof_disable()


def start_profiler(state="All", tracer_option="Default",
                   trace_dir="/tmp/paddle_tpu_trace"):
    """Start the XPlane device trace AND a `profiler.trace` span
    session in lockstep (unless one is already active, which is then
    left under its owner's control)."""
    global _trace_dir, _active, _own_tracer
    import jax

    _trace_dir = trace_dir
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    if trace._SESSION is None:
        trace.start_session()
        _own_tracer = True
    _active = True


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    """Stop the XPlane trace; the lockstep tracer session (if this
    module started it) is ended and exported to
    `<trace_dir>/host_trace.json` (`profiler.last_host_trace`)."""
    global _active, _own_tracer, last_host_trace
    import jax

    if _active:
        jax.profiler.stop_trace()
        _active = False
        if _own_tracer:
            _own_tracer = False
            tr = trace.end_session()
            if tr is not None and _trace_dir is not None:
                last_host_trace = tr.export_chrome_trace(
                    os.path.join(_trace_dir, "host_trace.json"))
    return summary()


def reset_profiler():
    _events.clear()


def reset():
    """Clear the host RecordEvent buffer (alias of reset_profiler)."""
    reset_profiler()


def events():
    """The recorded (name, event_type, duration_s) host events, newest
    `set_events_capacity()` of them."""
    return list(_events)


def summary():
    agg = {}
    for name, etype, dt in _events:
        tot, cnt = agg.get((name, etype), (0.0, 0))
        agg[(name, etype)] = (tot + dt, cnt + 1)
    lines = ["Event                          Type     Calls    "
             "Total(ms)   Avg(ms)"]
    for (name, etype), (tot, cnt) in sorted(agg.items(),
                                            key=lambda kv: -kv[1][0]):
        lines.append(f"{name:<30} {etype:<8} {cnt:>6} "
                     f"{tot * 1e3:>11.3f} {tot / cnt * 1e3:>9.3f}")
    return "\n".join(lines)


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile"):
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)
