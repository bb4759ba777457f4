"""Admission control for the continuous-batching serving runtime.

Requests arrive at arbitrary times; slots free up one request at a
time. The scheduler sits between them as a bounded FIFO with the
serving-side policies the engine itself should not know about:

  * **backpressure** — past the high-water mark `submit` raises
    `QueueFull` instead of queueing unboundedly (the caller sheds load
    or retries; an unbounded queue just converts overload into
    timeouts);
  * **deadline / timeout eviction** — a request carries an absolute
    `deadline` (engine clock); expired requests are finalized with
    their partial output instead of occupying a slot;
  * **cancellation** — `Request.cancel()` marks the request; queued
    requests are finalized on the next pop, in-flight ones are evicted
    by the engine's fault harvest at the next iteration boundary;
  * **graceful drain** — `drain()` closes admission while everything
    already accepted runs to completion.

The scheduler never touches device state: it hands `Request` objects to
the engine's `run_iteration` and finalizes the ones that die in the
queue. All methods are thread-safe; `Request.future` is a
`concurrent.futures.Future` resolving to a `RequestResult` (partial
tokens included for timeout/cancel — delivery semantics are "best
effort by the deadline", not all-or-nothing)."""
from __future__ import annotations

import collections
import itertools
import threading
import time

import numpy as np
from concurrent.futures import Future, InvalidStateError

from ..profiler import trace as _trace
from ..testing import faults
from . import tracing as _rt

__all__ = ["QueueFull", "Request", "RequestResult", "Scheduler"]

_PT_ADMIT = faults.point("scheduler.admit")

#: terminal finish reasons
FINISH_REASONS = ("eos", "length", "cancelled", "timeout", "drain",
                  "shutdown", "error")


class QueueFull(RuntimeError):
    """Backpressure: the bounded request queue is at its high-water
    mark. Shed load or retry later."""


class RequestResult(collections.namedtuple(
        "RequestResult", ["tokens", "finish_reason", "ttft_s",
                          "latency_s", "error"])):
    """What a request's future resolves to. `tokens` is the generated
    int32 array (possibly partial for timeout/cancel), `finish_reason`
    one of FINISH_REASONS, `ttft_s`/`latency_s` the request's own
    time-to-first-token and end-to-end latency (None when it never
    produced a token). `error` carries the cause when the engine
    evicted the request on an internal failure (finish_reason
    "error" with partial tokens) — None otherwise."""
    __slots__ = ()

    def __new__(cls, tokens, finish_reason, ttft_s=None, latency_s=None,
                error=None):
        return super().__new__(cls, tokens, finish_reason, ttft_s,
                               latency_s, error)

    @property
    def ok(self):
        return self.finish_reason in ("eos", "length", "drain")


class Request:
    """One generation request. Built by the frontend (or directly in
    tests), consumed by the scheduler + engine. Host-side only."""

    _ids = itertools.count()

    def __init__(self, prompt, memory=None, *, max_new_tokens=32,
                 eos_id=1, deadline=None, stream_cb=None, spec=True,
                 adapter=None, slo=None):
        prompt = np.asarray(prompt)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be 1-D [P], got "
                             f"{prompt.shape}")
        self.id = next(Request._ids)
        self.prompt = prompt
        self.memory = None if memory is None else np.asarray(memory)
        self._mem_scope = None        # the prefix trie's memory digest
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.eos_id = eos_id
        self.deadline = deadline      # absolute engine-clock seconds
        self.stream_cb = stream_cb    # called (request, token) per token
        # speculative decoding opt-out: on a spec-enabled engine a
        # spec=False request decodes one oracle token per step (its
        # draft lanes ride along unmatched) — output is identical
        # either way, this only trades verify width for latency
        self.spec = bool(spec)
        # multi-tenant serving: the registered adapter name this
        # request decodes under (None = the base model; per-request
        # opt-out rides the same compiled program with bank row 0's
        # zero delta)
        self.adapter = adapter
        # traffic shaping (serving.shaping): the request's SLO class —
        # an SLOClass, or a class name the ShapingScheduler resolves at
        # submit. None under the plain FIFO = no class semantics.
        self.slo = slo
        # preemption bookkeeping (paged engines only): tokens already
        # delivered that a post-resume replay must re-absorb silently,
        # and how many times this request has been preempted
        self._replay = 0
        self._preemptions = 0
        self.tokens = []              # generated so far (ints)
        self.state = "QUEUED"         # QUEUED -> RUNNING -> DONE
        self.finish_reason = None
        self.future = Future()
        self.slot = None
        self.submitted_at = None
        self.first_token_at = None
        self.finished_at = None
        self._cancelled = threading.Event()
        self._trace = None            # _ReqTrace under a tracer session

    # ---- caller-facing ----
    def cancel(self):
        """Request cancellation. Queued requests die on the next
        scheduler pop; in-flight ones are evicted at the next engine
        iteration boundary (their partial tokens are delivered)."""
        self._cancelled.set()

    @property
    def cancelled(self):
        return self._cancelled.is_set()

    def result(self, timeout=None):
        """Block for the RequestResult (partial for timeout/cancel)."""
        return self.future.result(timeout)

    # ---- engine/scheduler-facing ----
    def expired(self, now):
        return self.deadline is not None and now >= self.deadline

    def finish(self, reason, now, error=None):
        if self.state == "DONE":      # idempotent: harvest races drain
            return
        self.state = "DONE"
        self.finish_reason = reason
        self.finished_at = now
        if self._trace is not None:
            _rt.on_finish(self, reason, error)
        ttft = (None if self.first_token_at is None or
                self.submitted_at is None
                else self.first_token_at - self.submitted_at)
        lat = (None if self.submitted_at is None
               else now - self.submitted_at)
        try:
            self.future.set_result(RequestResult(
                np.asarray(self.tokens, np.int32), reason, ttft, lat,
                error))
        except InvalidStateError:
            pass   # already failed by a server-crash declaration

    def fail(self, exc, now):
        """Fail THIS request's future with the cause (per-request
        isolation: a broken join/admission kills one future, never the
        pool). Idempotent against a concurrent finish()."""
        if self.state == "DONE":
            return
        self.state = "DONE"
        self.finish_reason = "error"
        self.finished_at = now
        if self._trace is not None:
            _rt.on_finish(self, "error", exc)
        try:
            self.future.set_exception(exc)
        except InvalidStateError:
            pass


class Scheduler:
    """Bounded FIFO with deadline/cancel screening and drain."""

    def __init__(self, max_queue=64, clock=time.monotonic):
        self.max_queue = int(max_queue)
        self.clock = clock
        self._q = collections.deque()
        self._lock = threading.Lock()
        self._draining = False

    def submit(self, request):
        """Enqueue, or raise QueueFull past the high-water mark /
        RuntimeError after drain started. Sets `submitted_at`."""
        now = self.clock()
        _PT_ADMIT()   # fault point: an injected raise = admission lost
        with self._lock:
            if self._draining:
                raise RuntimeError("scheduler is draining: admission "
                                   "closed")
            if len(self._q) >= self.max_queue:
                raise QueueFull(
                    f"request queue at high-water mark "
                    f"({self.max_queue}); shed load or retry")
            request.submitted_at = now
            self._q.append(request)
        if _trace._SESSION is not None:
            _rt.on_submit(request)
        return request

    def pop_ready(self, now=None, on_dead=None):
        """Next admissible request (FIFO), finalizing any queued
        request that was cancelled or missed its deadline on the way
        (`on_dead(request)` fires for each — the engine's metrics
        hook). Returns None when the queue is empty."""
        if now is None:
            now = self.clock()
        while True:
            with self._lock:
                if not self._q:
                    return None
                r = self._q.popleft()
            if r.cancelled or r.expired(now):
                r.finish("cancelled" if r.cancelled else "timeout", now)
                if on_dead is not None:
                    on_dead(r)
                continue
            if r._trace is not None:
                _rt.on_queue_exit(r)
            return r

    def push_front(self, request):
        """Return an already-admitted request to the HEAD of the queue
        (FIFO order preserved): the paged engine's page-headroom gate
        defers the queue head when free pages can't cover its prompt +
        reservation — OutOfPages backpressure keeps it queued instead
        of failing it. Bypasses the high-water mark and drain checks on
        purpose: the request was admitted once already."""
        if request._trace is not None:
            _rt.on_requeue(request)
        with self._lock:
            self._q.appendleft(request)

    def depth(self):
        with self._lock:
            return len(self._q)

    # ---- drain / teardown ----
    def drain(self):
        """Close admission; queued + running work keeps flowing."""
        with self._lock:
            self._draining = True

    @property
    def draining(self):
        return self._draining

    def pop_all(self):
        """Drain the queue raw (server-crash path): the requests are
        returned unfinalized for the caller to fail/finish."""
        with self._lock:
            out = list(self._q)
            self._q.clear()
        return out

    def abort_queued(self, reason, now=None):
        """Finalize everything still queued (non-drain shutdown)."""
        if now is None:
            now = self.clock()
        out = []
        while True:
            with self._lock:
                if not self._q:
                    return out
                r = self._q.popleft()
            r.finish(reason if not r.cancelled else "cancelled", now)
            out.append(r)
