"""Thread-based serving frontend: submit(prompt) -> future, streaming.

The engine is single-threaded by design (all device work happens on one
thread); the server wraps it in an always-on loop thread and exposes a
thread-safe `submit` to any number of caller threads. Tokens stream per
iteration through the request's `stream_cb`; the final result is a
`concurrent.futures`-style future on the returned `Request`.

Lifecycle: `shutdown(drain=True)` closes admission and lets everything
already accepted run to completion (graceful drain); `drain=False`
aborts in-flight work at the next iteration boundary, delivering
partial tokens with finish_reason "shutdown". The server is host code
only: it runs wherever the engine's backend does — the tests drive it
on the CPU backend, `chip_smoke.py` on one TPU chip, where this process
is the only one that may hold the chip."""
from __future__ import annotations

import threading
import time

from ..profiler import trace as _trace
from .scheduler import Request, Scheduler

__all__ = ["ServingServer", "ServerCrashed"]


class ServerCrashed(RuntimeError):
    """The serving loop died (or refused to stop in time). Every
    outstanding future has been failed with this as the cause; further
    `submit()` calls raise it immediately."""


class ServingServer:
    """Always-on generation frontend over a serving engine.

        server = ServingServer(engine, max_queue=64)
        req = server.submit(prompt, memory=mem, max_new_tokens=32,
                            timeout=2.0, stream_cb=on_token)
        result = req.result()          # RequestResult(tokens, ...)
        server.shutdown(drain=True)

    `submit` raises `QueueFull` past the queue's high-water mark
    (backpressure) and ValueError for requests the pool can never
    serve (admission pre-check)."""

    def __init__(self, engine, *, max_queue=64, clock=None,
                 idle_wait_s=0.005, start=True, scheduler=None):
        self.engine = engine
        if clock is None:
            clock = engine.clock
        self.clock = clock
        # a caller-built scheduler (e.g. ShapingScheduler with SLO
        # classes / tenant weights) rides the same loop; default FIFO
        self.scheduler = scheduler if scheduler is not None else \
            Scheduler(max_queue=max_queue, clock=clock)
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._drained = threading.Event()
        self._dead = False
        self._crash_cause = None
        self._idle_wait_s = float(idle_wait_s)
        self._thread = threading.Thread(
            target=self._loop, name="paddle-tpu-serving", daemon=True)
        self._started = False
        if start:
            self.start()

    # ------------------------------------------------------------------
    def start(self):
        if not self._started:
            self._started = True
            self._thread.start()
        return self

    def submit(self, prompt, memory=None, *, max_new_tokens=32,
               eos_id=1, deadline=None, timeout=None, stream_cb=None,
               spec=True, adapter=None, slo=None):
        """Enqueue one generation request; returns the `Request` whose
        `.result()` blocks for a RequestResult and whose `.cancel()`
        withdraws it. `timeout` (seconds from now) is sugar for an
        absolute `deadline` on the engine clock. `adapter` names the
        registered tenant adapter to decode under (None = base model;
        needs an engine with an AdapterPool). `slo` is the request's
        SLO class (an `SLOClass` or a class name a `ShapingScheduler`
        resolves at submit; ignored by the FIFO scheduler). Raises
        QueueFull under backpressure, RuntimeError after shutdown/drain
        began, and ValueError for unservable requests."""
        if self._dead:
            raise ServerCrashed(
                f"server is dead ({self._crash_cause!r}); restart it")
        if timeout is not None:
            deadline = self.clock() + float(timeout)
        r = Request(prompt, memory, max_new_tokens=max_new_tokens,
                    eos_id=eos_id, deadline=deadline,
                    stream_cb=stream_cb, spec=spec, adapter=adapter,
                    slo=slo)
        self.engine.admit_check(r)   # fail fast, before queueing
        try:
            self.scheduler.submit(r)
        except Exception as e:
            self.engine.metrics.record_reject()
            self.engine._cbs.emit("on_reject", r, type(e).__name__)
            raise
        self.engine.metrics.record_submit()
        self.engine._cbs.emit("on_submit", r)
        self._wake.set()
        return r

    def metrics_snapshot(self):
        return self.engine.metrics.snapshot()

    # ------------------------------------------------------------------
    def _idle(self):
        return self.scheduler.depth() == 0 and self.engine.idle()

    def _loop(self):
        try:
            while True:
                if self._stop.is_set():
                    break
                # this loop owns the next iteration too: the engine
                # may leave a decode step's tokens unread until then
                progress = self.engine.run_ahead(self.scheduler)
                if self.scheduler.draining and self._idle():
                    break   # graceful drain complete
                if not progress:
                    self._wake.wait(self._idle_wait_s)
                    self._wake.clear()
        except BaseException as e:
            # the engine isolates per-request failures; anything that
            # still escapes is a loop-level crash — fail every future
            # rather than hanging their callers
            self._declare_dead(e)
        finally:
            self._drained.set()

    def _declare_dead(self, cause):
        """Mark the server dead: close admission, fail every queued and
        in-flight future with a ServerCrashed cause, make subsequent
        submit() raise immediately. Engine state is left untouched — a
        hung loop thread may still own it."""
        self._dead = True
        self._crash_cause = cause
        self._stop.set()
        self.scheduler.drain()
        self.engine.metrics.record_error("server_crash", cause)
        if _trace._SESSION is not None:
            _trace._SESSION.instant(
                "server_crash", cat="engine",
                attrs={"cause": type(cause).__name__})
        exc = ServerCrashed(f"serving loop crashed: {cause!r}")
        exc.__cause__ = cause if isinstance(cause, BaseException) \
            else None
        now = self.clock()
        doomed = self.scheduler.pop_all() + self.engine.running()
        for r in doomed:
            r.fail(exc, now)   # idempotent vs a racing finish()
            self.engine.metrics.record_finish("error", len(r.tokens))
            self.engine._cbs.emit("on_finish", r)

    # ------------------------------------------------------------------
    def shutdown(self, drain=True, timeout=None):
        """Stop serving. drain=True: close admission, run accepted work
        to completion, then stop (graceful). drain=False: stop at the
        next iteration boundary, finalizing queued AND in-flight
        requests with finish_reason "shutdown" (partial tokens
        delivered)."""
        if not self._started or self._dead:
            return
        if drain:
            self.scheduler.drain()
        else:
            self._stop.set()
        self._wake.set()
        self._thread.join(timeout)
        if self._thread.is_alive():
            # the loop is wedged: declare the server dead so no future
            # ever hangs — queued + in-flight futures fail with a
            # ServerCrashed cause and submit() rejects from now on
            self._declare_dead(
                TimeoutError(f"serving loop did not stop within "
                             f"{timeout}s"))
            raise TimeoutError(
                "serving loop did not stop in time; server marked "
                "dead, outstanding futures failed with ServerCrashed")
        if not drain:
            now = self.clock()
            self.scheduler.drain()
            for r in self.scheduler.abort_queued("shutdown", now):
                self.engine.metrics.record_finish(r.finish_reason,
                                                  len(r.tokens))
                self.engine._cbs.emit("on_finish", r)
            self.engine.abort_active("shutdown", now)
        self._started = False

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown(drain=exc == (None, None, None))
        return False
