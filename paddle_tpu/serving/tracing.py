"""Request-lifecycle tracing for the serving stack.

`profiler.trace` provides the tracer; this module is the serving-side
vocabulary: one trace per request (trace id = request id), spans for
every lifecycle phase, engine-track spans for the batched decode step
and every compile, and the waterfall reconstruction the report tool
and tests share. Instrumented call sites in scheduler/engine/server
all guard with ``if _trace._SESSION is not None:`` — one module-global
read when tracing is off.

The engine-track spans (`iteration` and everything under it) and the
request's `join` begin and end on the engine's thread, so they are
also forwarded to the JAX profiler as `TraceAnnotation`s
(`Tracer.begin(forward=True)`): a `jax.profiler` trace taken under a
session shows the program's own phases beside the device's
operations, and the root `iteration` annotation carries the tracer
clock's reading at its start (`t0_perf_ns`), one (profiler ns,
`perf_counter` ns) pair per iteration, which lays the spans that are
NOT forwarded (`request`, `queue`, `decode`, `pending_splice`, every
`add_complete` span) on the same clock.

Device-side names (a profiler trace, not spans): every pool program is
`jit_<kind>` (`serving/layers.py` `named_program`); under `pstep` /
`pjoin` a served causal LM scopes each block's mixer by its kind, `mamba`,
`swa`, `full`, `xattn`, `gmu` (`text/models.py`), as the decoder stack's
attention is `attn`; every Pallas kernel has a fixed name
(`selective_scan` is the join's scan).

Span catalog (exported Chrome-trace names):

  request         per-request root: submit() -> finish/fail
  queue           admission queue wait: submit -> slot pop (re-opened
                  when page backpressure defers the request back to
                  the queue head)
  join            slot join: prefill / prefix attach / disaggregated
                  dispatch -> return (attrs: slot, prompt bucket,
                  prefix_hit; a prefill also prefill_tokens)
  join.prefix_match  instant under the join: the radix prefix-cache
                  consult (attrs: kind whole/partial/miss,
                  matched_pages, matched_tokens)
  pending_splice  disaggregated only: prefill dispatched -> K/V
                  spliced into the live pool (the window the slot is
                  occupied-but-masked)
  decode          slot residency in batched decode: activation -> last
                  token (attrs: steps, tokens)
  first_token     instant: the request's first delivered token (TTFT)
  finish          terminal instant: finish_reason for completed
                  requests
  error           terminal instant: failed/evicted requests, with the
                  cause
  prefill_chunk   one chunked-prefill dispatch interleaved between
                  decode steps (attrs: pos — the post-chunk prompt
                  frontier — and done on the final chunk)
  preempt         instant: the slot was evicted to the prefix cache to
                  free capacity (attrs: slot, tokens so far)
  iteration       engine track root: one run_iteration / run_ahead
                  that did work (an idle spin is dropped from the tracer's record;
                  a jax.profiler trace running at the time has
                  already seen its annotations, so a reader of the
                  profiler's events leaves out an `iteration` that
                  holds no decode.step and no join; attrs: joins, n_active,
                  occupancy, queue_depth, the page-pool and shard
                  gauges — live_pages and live_blocks among them: the
                  written pages a paged decode call reads and its grid
                  steps of pages_per_block pages that read them —,
                  t0_perf_ns; cache — this iteration's
                  state_resets, prefill_tokens and ring_wraps, where
                  any —; experts — token_slots, held_slots, load_max
                  and dropped_slots of the steps and joins whose
                  tokens this iteration read, where the stack has
                  expert layers —; step — "ahead" where the
                  iteration's step was enqueued before the last one's
                  tokens were read, else why not: idle, spec, chunk,
                  pending, preempt, retry, host —; late_slot_steps;
                  cpu_ms, offcpu_ms — the host part, the iteration
                  less its step.readback and iter.tok0 waits, split
                  into the engine thread's CPU time and the rest —
                  and gc_ms, the collector's time on any thread)
  iter.harvest    cancellation / deadline sweep and the poll of
                  disaggregated prefills
  iter.admit      the admission loop; the admitted requests' join
                  spans are its children (attrs: joins)
  iter.tok0       resolving the round's first tokens: the host's wait
                  for the join programs (attrs: n); before decode.step
                  in series, after it where the step went ahead
  iter.chunks     one chunk for every slot mid chunked-prefill
  decode.step     the iteration's decode phase: its batched step
                  enqueued, all attempts, and a step's tokens read —
                  this one's in series, the LAST iteration's where
                  steps go ahead (attrs: n_active, slots — of the step
                  enqueued here —, occupancy, queue depth, page-pool
                  and shard gauges)
  step.map_pages  under decode.step: mapping the pages the step writes
  step.enqueue    under decode.step: building the arguments and
                  calling the compiled program
  step.readback   the host's wait for a step's tokens: under
                  decode.step after step.enqueue, where it waits for
                  the step enqueued an iteration EARLIER when steps go
                  ahead; under iter.admit or iteration where a flight
                  lands out of turn (preemption, a series reason)
  decode.draft    under decode.step: a speculative draft proposal
                  dispatch, to its result (attrs: n_active, proposed)
  decode.verify   under decode.step: the k-token verify dispatch and
                  read-back (attrs: n_active, proposed, accepted)
  iter.deliver    delivering the step's tokens to the requests and
                  their stream callbacks (attrs: tokens)
  iter.account    the iteration's gauges, metrics and on_iteration
                  callbacks
  compile         engine track: one jit trace+compile (attrs: cache
                  key, duration, count)
  retrace         engine track instant: a retrace-sentinel violation
  gc              a collection of generation 1 or 2, on any thread
                  (attrs: generation, collected; trace id 0): a
                  profiler annotation on the thread it ran on, so a
                  device gap it caused is labelled `gc`, and a span in
                  the ring from the next commit on (`profiler/trace.py`
                  `_GcHook`; generation 0 only counts)
"""
from __future__ import annotations

import time

import numpy as np

from ..profiler import trace as _trace

__all__ = [
    "SPAN_CATALOG", "retrace_sentinel", "RetraceSentinel",
    "RetraceError", "session_scope", "start_session", "end_session",
    "load_chrome_trace", "waterfalls", "waterfall_report",
    "IterationTrace",
]

# re-exported so serving code/tests have one import surface
RetraceError = _trace.RetraceError
RetraceSentinel = _trace.RetraceSentinel
retrace_sentinel = _trace.retrace_sentinel
session_scope = _trace.session_scope
start_session = _trace.start_session
end_session = _trace.end_session

#: (span name, meaning) — the README "Observability" table and the
#: report tool's legend both render from this
SPAN_CATALOG = (
    ("request", "per-request root: submit -> finish/fail"),
    ("queue", "admission queue wait: submit -> slot pop"),
    ("join", "slot join: prefill / prefix attach / disagg dispatch"),
    ("join.prefix_match", "instant: radix prefix-cache consult "
                          "(kind, matched pages/tokens)"),
    ("pending_splice", "disaggregated prefill in flight -> spliced"),
    ("decode", "slot residency in batched decode steps"),
    ("prefill_chunk", "one chunked-prefill dispatch interleaved "
                      "between decode steps (pos, done)"),
    ("preempt", "instant: slot evicted to the prefix cache for "
                "higher-priority work"),
    ("first_token", "instant: first delivered token (TTFT)"),
    ("finish", "terminal instant: finish_reason"),
    ("error", "terminal instant: failure cause"),
    ("iteration", "engine track root: one run_iteration that did "
                  "work (joins, occupancy, gauges, t0_perf_ns; cpu_ms, "
                  "offcpu_ms, gc_ms of its host part)"),
    ("iter.harvest", "engine track: cancellation / deadline sweep, "
                     "poll of disaggregated prefills"),
    ("iter.admit", "engine track: the admission loop; parent of the "
                   "round's join spans"),
    ("iter.tok0", "engine track: resolving the round's first tokens "
                  "(the wait for the join programs)"),
    ("iter.chunks", "engine track: one chunk per slot mid "
                    "chunked-prefill"),
    ("decode.step", "engine track: the batched decode step enqueued "
                    "and a step's tokens read"),
    ("step.map_pages", "under decode.step: mapping the pages the "
                       "step writes"),
    ("step.enqueue", "under decode.step: arguments built, program "
                     "called"),
    ("step.readback", "the wait for a step's tokens: this "
                      "iteration's in series, the last one's where "
                      "steps go ahead"),
    ("decode.draft", "under decode.step: speculative draft proposal"),
    ("decode.verify", "under decode.step: k-token speculative "
                      "verify"),
    ("iter.deliver", "engine track: tokens to requests and stream "
                     "callbacks"),
    ("iter.account", "engine track: gauges, metrics, on_iteration "
                     "callbacks"),
    ("compile", "engine track: one jit trace+compile"),
    ("precompile", "engine track: one startup program readied "
                   "(source: cache deserialize | AOT compile)"),
    ("retrace", "engine track: retrace-sentinel violation"),
    ("gc", "a collection of generation 1 or 2 on any thread "
           "(generation, collected)"),
)


class _ReqTrace:
    """Per-request span bookkeeping, attached as `Request._trace`."""

    __slots__ = ("tr", "tid", "root", "queue", "join", "splice",
                 "decode", "steps")

    def __init__(self, tr, tid, root, queue):
        self.tr = tr
        self.tid = tid
        self.root = root
        self.queue = queue
        self.join = None
        self.splice = None
        self.decode = None
        self.steps = 0


# ----------------------------------------------------------------------
# lifecycle hooks (call sites pre-check _trace._SESSION)
# ----------------------------------------------------------------------

def on_submit(r):
    tr = _trace._SESSION
    if tr is None:
        return
    # sampling mode (start_session(sample=...)): an unsampled request
    # costs exactly this one branch — r._trace stays None, so every
    # downstream hook short-circuits on the attribute it already reads
    if tr.sample is not None and not tr.should_sample(r.id):
        tr.count("requests_unsampled")
        return
    if tr.sample is not None:
        tr.count("requests_sampled")
    root = tr.begin("request", cat="request", trace_id=r.id,
                    attrs={"prompt_len": int(r.prompt.shape[0]),
                           "max_new_tokens": r.max_new_tokens})
    queue = tr.begin("queue", cat="request", trace_id=r.id,
                     parent=root)
    r._trace = _ReqTrace(tr, r.id, root, queue)


def on_queue_exit(r):
    rt = r._trace
    if rt is not None:
        rt.tr.end(rt.queue)


def on_requeue(r):
    """Page backpressure deferred the request back to the queue head:
    re-open a queue span so the waterfall shows the extra wait."""
    rt = r._trace
    if rt is not None:
        rt.queue = rt.tr.begin("queue", cat="request", trace_id=rt.tid,
                               parent=rt.root,
                               attrs={"deferred": True})


def on_join_begin(r, slot, parent=None):
    """The join begins and ends on the engine's thread, so it is
    forwarded to the profiler; `parent` is the round's `iter.admit`
    span (the request's root where there is none)."""
    rt = r._trace
    if rt is not None:
        rt.tr.end(rt.queue)          # idempotent if already ended
        rt.join = rt.tr.begin("join", cat="request", trace_id=rt.tid,
                              parent=parent or rt.root,
                              attrs={"slot": slot}, forward=True)


def on_join_attr(r, **attrs):
    rt = r._trace
    if rt is not None and rt.join is not None:
        rt.join.attrs.update(attrs)


def on_prefix_match(r, kind, matched_pages=0, matched_tokens=0):
    """Instant span under the join: what the radix prefix cache
    returned for this request ("whole" / "partial" / "miss") and how
    much of the prompt it served — the per-request view of the
    hit_token_ratio gauge."""
    rt = r._trace
    if rt is not None:
        rt.tr.instant("join.prefix_match", cat="request",
                      trace_id=rt.tid, parent=rt.join or rt.root,
                      attrs={"kind": kind,
                             "matched_pages": int(matched_pages),
                             "matched_tokens": int(matched_tokens)})


def on_join_end(r, ok=True, pending=False, error=None):
    rt = r._trace
    if rt is None:
        return
    attrs = {}
    if error is not None:
        attrs = {"error": type(error).__name__}
    rt.tr.end(rt.join, ok=ok, **attrs)
    if ok and pending:
        rt.splice = rt.tr.begin("pending_splice", cat="request",
                                trace_id=rt.tid, parent=rt.root)
    elif ok:
        _begin_decode(rt)


def _begin_decode(rt):
    if rt.decode is None:
        rt.decode = rt.tr.begin("decode", cat="request",
                                trace_id=rt.tid, parent=rt.root)


def on_splice_end(r, ok=True, error=None):
    rt = r._trace
    if rt is None:
        return
    attrs = {} if error is None else {"error": type(error).__name__}
    rt.tr.end(rt.splice, ok=ok, **attrs)
    if ok:
        _begin_decode(rt)


def on_chunk(r, t0, t1, pos, done):
    """One chunked-prefill dispatch for this request's slot ([t0, t1],
    engine clock): `pos` is the POST-chunk prompt frontier, `done`
    marks the final chunk (the join is complete and the slot decodes
    from here on)."""
    rt = r._trace
    if rt is not None:
        rt.tr.add_complete("prefill_chunk", t0, t1, cat="request",
                           trace_id=rt.tid, parent=rt.root,
                           attrs={"pos": int(pos), "done": bool(done)})
        if done:
            _begin_decode(rt)


def on_preempt(r, slot, n_tokens):
    """The shaping scheduler evicted this request's slot to the prefix
    cache; the decode span closes here and a fresh queue span opens
    (the request re-enters admission and resumes via attach)."""
    rt = r._trace
    if rt is None:
        return
    rt.tr.instant("preempt", cat="request", trace_id=rt.tid,
                  parent=rt.root,
                  attrs={"slot": int(slot), "tokens": int(n_tokens)})
    rt.tr.end(rt.decode, steps=rt.steps, tokens=int(n_tokens))
    rt.decode = None
    rt.queue = rt.tr.begin("queue", cat="request", trace_id=rt.tid,
                           parent=rt.root, attrs={"preempted": True})


def on_first_token(r):
    rt = r._trace
    if rt is not None:
        rt.tr.instant("first_token", cat="request", trace_id=rt.tid,
                      parent=rt.root)


def on_finish(r, reason, error=None):
    """Terminal hook — fired from Request.finish()/fail(), so every
    path (eos/length, deadline, cancel, eviction, server crash) closes
    the trace. Evicted/failed requests end with an ``error`` span."""
    rt = r._trace
    if rt is None:
        return
    tr = rt.tr
    tr.end(rt.queue)
    tr.end(rt.join)
    tr.end(rt.splice)
    tr.end(rt.decode, steps=rt.steps, tokens=len(r.tokens))
    if reason == "error" or error is not None:
        attrs = {"reason": reason}
        if error is not None:
            attrs["error"] = type(error).__name__
            attrs["message"] = str(error)[:200]
        tr.instant("error", cat="request", trace_id=rt.tid,
                   parent=rt.root, attrs=attrs)
    else:
        tr.instant("finish", cat="request", trace_id=rt.tid,
                   parent=rt.root, attrs={"reason": reason})
    tr.end(rt.root, reason=reason, tokens=len(r.tokens))
    r._trace = None


class IterationTrace:
    """The engine-track spans of ONE `run_iteration`, on the engine's
    thread: the root `iteration` and whatever is opened under it,
    properly nested and forwarded to the profiler. The engine keeps it
    as `engine._iter_trace` while the iteration runs (None with no
    session), which is what the steppers' call sites test.

    It also splits the host's time by cause. The thread's CPU clock and
    the collector's totals are read at the root's begin and at close;
    the device waits (`begin_wait` / `end_wait`: `step.readback`,
    `iter.tok0`) read the wall and CPU clocks around themselves. An
    iteration that did work then has a host part (wall less the waits),
    its `cpu` (the thread's CPU time in it) and `offcpu` (the rest:
    waiting for the GIL, descheduled, page faults), and `gc` (collector
    time on any thread, a cause inside one of the two). They go on the
    root as `cpu_ms`, `offcpu_ms`, `gc_ms` and into `metrics`'
    `snapshot()["host"]`. Where the thread clock advances in scheduler
    ticks (10 ms steps on some sandboxed hosts), one iteration's split
    is a sample that may fall outside [0, host part]; sums over many
    iterations are what it estimates."""

    __slots__ = ("tr", "stack", "step", "held", "metrics", "t0_ns",
                 "cpu0_ns", "gc0", "wait_ns", "wait_cpu_ns", "w_ns",
                 "w_cpu_ns")

    def __init__(self, tr, metrics=None):
        self.tr = tr
        self.metrics = metrics
        # the CPU clock's intervals lie inside the wall clock's, and a
        # wait's enclose it, so a thread that never leaves the CPU
        # reads no negative off-CPU time
        self.gc0 = tr.gc_totals()
        self.t0_ns = time.perf_counter_ns()
        self.cpu0_ns = time.thread_time_ns()
        root = tr.begin("iteration", cat="engine", forward=True)
        # the offset between the profiler's clock and the tracer's:
        # this annotation's start is `t0_perf_ns` on the tracer's
        root.attrs["t0_perf_ns"] = t0 = int(root.t0 * 1e9)
        root.ann.set_metadata(t0_perf_ns=t0)
        self.stack = [root]
        self.step = None          # this iteration's decode.step span
        self.held = []            # closed spans, recorded at close()
        self.wait_ns = self.wait_cpu_ns = 0
        self.w_ns = self.w_cpu_ns = 0

    def begin(self, name, **attrs):
        sp = self.tr.begin(name, cat="engine", parent=self.stack[-1],
                           attrs=attrs, forward=True)
        self.stack.append(sp)
        return sp

    def begin_wait(self, name, **attrs):
        """`begin` for a span in which the host waits for the device:
        its wall and CPU time are kept out of the host part."""
        sp = self.begin(name, **attrs)
        self.w_cpu_ns = time.thread_time_ns()
        self.w_ns = time.perf_counter_ns()
        return sp

    def end_wait(self, sp, **attrs):
        self.wait_ns += time.perf_counter_ns() - self.w_ns
        self.wait_cpu_ns += time.thread_time_ns() - self.w_cpu_ns
        self.end(sp, **attrs)

    def unwind(self, sp):
        """Close whatever an exception left open under `sp` (the engine
        does it before every attempt of a retried op, so a retry's spans
        are `sp`'s children and not the failed attempt's)."""
        while self.stack[-1] is not sp:
            self.held.append(self.tr.end(self.stack.pop(), False))

    def end(self, sp, **attrs):
        self.unwind(sp)
        self.stack.pop()
        self.held.append(self.tr.end(sp, False, **attrs))

    def begin_step(self):
        self.step = self.begin("decode.step")
        return self.step

    def end_step(self, pairs, occupancy, queue_depth, **attrs):
        """Close `decode.step` with the trace ids of the requests the
        step enqueued under it was issued for (`pairs`: (slot, request),
        empty where the span only read an earlier step's tokens) in
        ``slots`` — every decode step a request co-resides in is
        recoverable from the trace."""
        tids = []
        for _, r in pairs:
            tids.append(r.id)
            rt = r._trace
            if rt is not None:
                _begin_decode(rt)
                rt.steps += 1
        self.end(self.step, n_active=len(tids), slots=tids,
                 occupancy=occupancy, queue_depth=queue_depth, **attrs)

    def close(self, progress, gauges=None, **attrs):
        """End the iteration: `attrs` (joins, occupancy, queue depth)
        and the iteration's `gauges`, computed ONCE by the engine, go
        on the root; the gauges also go on the record of `decode.step`,
        which has always carried them. An idle spin leaves nothing in
        the tracer's record, so sums over `iteration` cover work only
        (its annotations were entered before that could be known: a
        profiler trace running at the time shows them)."""
        while len(self.stack) > 1:
            self.held.append(self.tr.end(self.stack.pop(), False))
        root = self.stack.pop()
        if not progress:
            self.tr.end(root, False)
            return
        # not clamped to [0, host]: where the thread clock moves in
        # scheduler ticks, a clamp would bias the sums
        cpu = time.thread_time_ns() - self.cpu0_ns - self.wait_cpu_ns
        host = time.perf_counter_ns() - self.t0_ns - self.wait_ns
        gc = [b - a for a, b in zip(self.gc0, self.tr.gc_totals())]
        gc_ns = gc[0] + gc[1] + gc[2]
        attrs.update(cpu_ms=round(cpu * 1e-6, 3),
                     offcpu_ms=round((host - cpu) * 1e-6, 3),
                     gc_ms=round(gc_ns * 1e-6, 3))
        if self.metrics is not None:
            self.metrics.record_host(host, cpu, self.wait_ns, gc[:3],
                                     gc[3:], self.tr.dropped)
        for k, v in (gauges or {}).items():
            attrs[k] = (round(float(v), 3) if isinstance(v, float)
                        else list(v) if isinstance(v, (list, tuple))
                        else v)
            if self.step is not None:
                self.step.attrs[k] = attrs[k]
        if self.step is not None:
            attrs["n_active"] = self.step.attrs.get("n_active", 0)
        self.held.append(self.tr.end(root, False, **attrs))
        self.tr.commit(self.held)


# ----------------------------------------------------------------------
# waterfall reconstruction (shared by tools/trace_report.py and tests)
# ----------------------------------------------------------------------

_PHASES = ("queue", "join", "pending_splice", "decode")


def load_chrome_trace(path):
    """Read a chrome-trace JSON file back into its event list."""
    import json

    with open(path) as f:
        payload = json.load(f)
    return payload["traceEvents"] if isinstance(payload, dict) \
        else payload


def waterfalls(events):
    """Group request-track events into per-request waterfalls:
    {trace_id: {"spans": [...], "phases": {phase: total_ms},
    "total_ms", "reason", "tokens", "complete"}}. `complete` requires
    the root request span plus queue, join and a terminal
    finish/error event — the acceptance contract for every admitted
    request."""
    out = {}
    for ev in events:
        if ev.get("ph") not in ("X",) or ev.get("cat") != "request":
            continue
        tid = ev.get("args", {}).get("trace_id")
        if tid is None:
            continue
        out.setdefault(tid, []).append(ev)
    result = {}
    for tid, evs in out.items():
        evs.sort(key=lambda e: e["ts"])
        phases = {p: 0.0 for p in _PHASES}
        root = None
        reason = None
        terminal = None
        tokens = None
        for e in evs:
            n = e["name"]
            if n == "request":
                root = e
                reason = e["args"].get("reason", reason)
                tokens = e["args"].get("tokens", tokens)
            elif n in phases:
                phases[n] += e.get("dur", 0.0) / 1e3
            elif n in ("finish", "error"):
                terminal = n
                reason = e["args"].get("reason", reason)
        total = (root.get("dur", 0.0) / 1e3) if root else None
        result[tid] = {
            "spans": evs,
            "phases": {k: round(v, 3) for k, v in phases.items()},
            "total_ms": None if total is None else round(total, 3),
            "reason": reason,
            "terminal": terminal,
            "tokens": tokens,
            "complete": (root is not None and terminal is not None
                         and any(e["name"] == "queue" for e in evs)
                         and any(e["name"] == "join" for e in evs)),
        }
    return result


def waterfall_report(events, percentiles=(50, 95), top=0, width=48):
    """Render the per-request latency breakdown: per-phase
    p<percentiles> across all requests, then (optionally) the `top`
    slowest requests as ASCII waterfalls."""
    wf = waterfalls(events)
    lines = []
    done = [w for w in wf.values() if w["total_ms"] is not None]
    lines.append(f"requests: {len(wf)} traced, {len(done)} finished, "
                 f"{sum(1 for w in wf.values() if w['complete'])} "
                 f"complete waterfalls")
    if not done:
        return "\n".join(lines)
    hdr = "phase".ljust(16) + "".join(
        f"p{int(q)}(ms)".rjust(12) for q in percentiles) \
        + "mean(ms)".rjust(12)
    lines.append(hdr)
    for phase in _PHASES + ("total",):
        vals = np.asarray([w["total_ms"] if phase == "total"
                           else w["phases"][phase] for w in done])
        row = phase.ljust(16) + "".join(
            f"{float(np.percentile(vals, q)):12.2f}"
            for q in percentiles) + f"{float(vals.mean()):12.2f}"
        lines.append(row)
    if top:
        lines.append("")
        slowest = sorted(wf.items(),
                         key=lambda kv: -(kv[1]["total_ms"] or 0))[:top]
        scale = max(w["total_ms"] or 0 for _, w in slowest) or 1.0
        glyph = {"queue": ".", "join": "#", "pending_splice": "~",
                 "decode": "="}
        for tid, w in slowest:
            bar = ""
            for p in _PHASES:
                n = int(round(w["phases"][p] / scale * width))
                bar += glyph[p] * n
            lines.append(f"req {tid:>6} {w['total_ms'] or 0:9.2f}ms "
                         f"|{bar:<{width}}| {w['reason']}")
        lines.append("legend: .=queue  #=join  ~=pending_splice  "
                     "==decode")
    return "\n".join(lines)
