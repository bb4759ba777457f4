"""Continuous-batching generation engines: a fixed slot pool over the
static KV cache.

`DecodeEngine` (text/generation.py) made whole-batch generation one
compiled program, but a batch is an all-or-nothing unit: a straggler
request pins every finished row and new arrivals wait for a full
drain. The serving engines here do Orca/vLLM-style *iteration-level*
batching instead — the scheduling unit is ONE decode step:

  * the pool owns S cache slots: per-layer `StaticKVCache` buffers of
    shape [S, H, max_len, D] with PER-ROW write indices, plus pooled
    cross-attention K/V, pad-bias rows, and memory rows;
  * the decode step is ONE jitted call of static shape [S, ...] with a
    per-slot active mask — compiled once per pool config, regardless of
    which requests occupy which slots (`trace_counts` proves it);
  * a finished/evicted slot is refilled by prefilling the new prompt
    (batch-1, prompt bucketed to a power of two) through the regular
    flash-capable path and SPLICING its K/V rows + write index into the
    live pool with `dynamic_update_slice` — the slot id and prompt
    length are traced scalars, so slot join never retraces either
    (one compile per prompt bucket).

Numerics contract: every slot reproduces `generate_eager` for its own
prompt bit-for-bit at the token level — all per-slot ops are row-wise,
so co-resident requests can never perturb each other's output; the
soak test in tests/test_serving.py holds this across joins, evictions,
and timeouts.

`ArtifactServingEngine` applies the same slot lifecycle to inference
Program artifacts (ids -> logits, no threadable cache): each iteration
re-runs every active slot's bucketed prefix, batched across slots —
the `Predictor.generate` serving mode behind
`Config.enable_serving_engine()`.
"""
from __future__ import annotations

import time

import numpy as np

from ..core.bucketing import bucket_size, pad_prompt_row, pad_token_rows
from ..profiler import costs as _costs
from ..profiler import trace as _trace
from ..testing import faults
from . import tracing as _rt
from .paging import (OutOfPages, PageAllocator, RadixPrefixCache,
                     pages_for)
from .metrics import CallbackList, ServingMetrics

__all__ = ["ServingEngine", "PagedServingEngine",
           "ArtifactServingEngine", "WatchdogTimeout"]

#: fault points instrumenting the slot lifecycle (armed only in tests /
#: chaos runs; a disarmed hit is one boolean read)
_PT_SLOT_JOIN = faults.point("serving.slot_join")
_PT_PREFILL = faults.point("serving.prefill")
_PT_PATTACH = faults.point("serving.pattach")
_PT_DECODE = faults.point("serving.decode_step")
_PT_READBACK = faults.point("serving.step_readback")
_PT_CHUNK = faults.point("serving.prefill_chunk")
_PT_PREEMPT = faults.point("serving.preempt")


def _reject_sharded_params(params, engine_name):
    """Fail FAST (and loudly) when a single-chip engine is handed
    mesh-sharded weights. A weight committed across several devices
    would make the jitted join/step programs run SPMD over the whole
    mesh with a single-device pool layout — at best slow, at worst a
    silently different reduction order than the engine's bit-match
    contract. The sharded pool engine exists for exactly this."""
    for name, v in params.items():
        sh = getattr(v, "sharding", None)
        if sh is None:
            continue
        try:
            multi = len(sh.device_set) > 1
            replicated = bool(getattr(sh, "is_fully_replicated", False))
        except Exception:
            continue
        if multi and not replicated:
            raise ValueError(
                f"{engine_name} was handed mesh-sharded weights "
                f"(param {name!r} is laid out across "
                f"{len(sh.device_set)} devices: {sh}); the single-chip "
                f"slot pool cannot serve them. Use "
                f"paddle_tpu.serving.sharded.ShardedServingEngine, "
                f"which lays weights out tp/fsdp and shards the slot "
                f"pool data-parallel over the same mesh.")


def _tree_bytes(x):
    """Logical byte footprint of a pytree of arrays: every leaf counts
    at size x itemsize (aliased leaves count each — the ledger reports
    committed CAPACITY, which is what the two pool k/v views occupy
    once they diverge). Pure metadata walk: never syncs the device."""
    if x is None:
        return 0
    if isinstance(x, dict):
        return sum(_tree_bytes(v) for v in x.values())
    if isinstance(x, (list, tuple)):   # incl. NamedTuple caches
        return sum(_tree_bytes(v) for v in x)
    x = getattr(x, "_data", x)         # Tensor wrapper -> array
    size = getattr(x, "size", None)
    dt = getattr(x, "dtype", None)
    if size is None or dt is None:
        return 0
    itemsize = getattr(dt, "itemsize", None)
    if itemsize is None:
        itemsize = np.dtype(str(dt)).itemsize
    return int(size) * int(itemsize)


class WatchdogTimeout(TimeoutError):
    """An engine operation completed but blew its `watchdog_s` wall
    budget — treated as a failure (retried with backoff, then failed
    cleanly) so one slow/hung compile can't wedge the pool silently."""


class PoolCarryLost(RuntimeError):
    """The donated pool-state carry was consumed by a dispatch that
    died without assigning a replacement: no valid buffer survives to
    retry on. Raised instead of dispatching dead buffers; the caller
    escalates to the all-or-nothing recovery (_fail_active ->
    _reset_pool) so the pool rebuilds and keeps serving."""


class _Flight:
    """A decode step between its enqueue and the delivery of its
    tokens. `toks` is what the step returned: the unread device array
    [S] of a step whose successor can be enqueued before it is read,
    else host arrays ([S], or the speculative (emit [S, k], n_emit
    [S])). `pairs` is taken at enqueue: a token in flight belongs to
    the request that held the slot THEN, whoever holds the slot when
    the token is read."""

    __slots__ = ("toks", "pairs", "t0")

    def __init__(self, toks, pairs, t0):
        self.toks, self.pairs, self.t0 = toks, pairs, t0

    def unread(self):
        return not isinstance(self.toks, (np.ndarray, tuple))


class _CachedProgram:
    """A program deserialized from the persistent AOT cache, with a
    rebuild escape hatch: a stale-but-CRC-valid entry whose argument
    layout no longer matches the live pool raises TypeError at the
    AOT arg check — rebuild the jitted program in place (one compile,
    recorded as an `aot_cache` error) instead of crashing the serve.
    The happy path is one try frame around the raw executable call."""

    __slots__ = ("_engine", "_key", "_build", "compiled", "_fell_back")

    def __init__(self, engine, key, build, compiled):
        self._engine = engine
        self._key = key
        self._build = build
        self.compiled = compiled
        self._fell_back = False

    def __call__(self, *args):
        if not self._fell_back:
            try:
                return self.compiled(*args)
            except TypeError as e:
                self._fell_back = True
                self._engine.metrics.record_error("aot_cache", e)
                self.compiled = self._build()
        return self.compiled(*args)


class _EngineBase:
    """Slot lifecycle + per-iteration orchestration shared by the
    model-backed and artifact-backed engines. Subclasses implement
    `_join(slot, request) -> first_token | None`, `_decode_step(active)
    -> (tokens [S], active)`, and optionally `_evict(slot)` /
    `admit_check` / `_series_reason`.

    One `run_iteration(scheduler)` is the continuous-batching unit:
    (1) fault harvest — cancelled / past-deadline requests leave their
    slots with partial output; (2) admission — up to
    `max_joins_per_iter` queued requests prefill into free slots (the
    prefill/decode interleave policy: bounding joins per iteration
    bounds the decode stall co-resident requests see); (3) one batched
    decode step over the active mask. NOT thread-safe — drive it from
    one thread (the `ServingServer` loop or a synchronous drain)."""

    #: the multi-tenant AdapterPool (serving/adapters.py); model-backed
    #: engines set it from the `adapters=` knob, the Artifact engine
    #: never does — base-class code guards on None
    _apool = None

    def __init__(self, num_slots, *, max_joins_per_iter=2, metrics=None,
                 callbacks=(), clock=time.monotonic, max_attempts=3,
                 backoff_base_s=0.01, backoff_cap_s=0.5,
                 watchdog_s=None, sleep=time.sleep,
                 hbm_budget_bytes=None, hbm_watermark=0.9):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        self.num_slots = int(num_slots)
        self.max_joins_per_iter = int(max_joins_per_iter)
        self.clock = clock
        self.metrics = metrics if metrics is not None else \
            ServingMetrics(clock=clock)
        self._cbs = CallbackList(
            callbacks,
            on_error=lambda hook, e: self.metrics.record_error(
                f"callback.{hook}", e))
        self.slots = [None] * self.num_slots   # Request | None
        # slots whose request holds the slot but whose pool state is
        # not spliced yet (a disaggregated prefill still in flight on
        # the prefill mesh slice): occupied for admission, EXCLUDED
        # from the decode-step active mask until _poll_pending splices
        self._pending = set()
        self._last_step_done = None   # decode-step inter-arrival clock
        #: the running iteration's engine-track spans (tracing.
        #: IterationTrace) under a tracer session, else None
        self._iter_trace = None
        # trace_counts is observable: the retrace sentinel / tracer see
        # every increment (= one jax trace = one compile) as it happens
        self.trace_counts = _trace.ObservedCounter(
            owner=type(self).__name__)
        # failure-isolation knobs: every join/decode runs under a
        # capped-exponential retry loop and an optional wall watchdog
        self.max_attempts = max(1, int(max_attempts))
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.watchdog_s = watchdog_s
        self._sleep = sleep
        # HBM budget for the live memory ledger's watermark (warn
        # BEFORE OutOfPages/OOM); model-backed subclasses register
        # their ledger provider with the metrics sink
        self.hbm_budget_bytes = hbm_budget_bytes
        if hbm_budget_bytes is not None:
            self.metrics.budget_bytes = int(hbm_budget_bytes)
            self.metrics.watermark_frac = float(hbm_watermark)
        self._weights_bytes = None   # cached by memory_ledger()
        #: the decode step whose tokens are still unread (`_Flight`): a
        #: loop that owns consecutive iterations (`run_ahead`) leaves
        #: one behind and the next iteration reads it AFTER it has
        #: enqueued its own step; `run_iteration` never leaves one
        self._flight = None

    # ---- subclass surface ----
    def admit_check(self, request):
        """Raise ValueError for requests this pool can never serve."""

    def _join(self, slot, request):
        raise NotImplementedError

    def _decode_step(self, active):
        """One batched step over `active`: (tokens, the mask it really
        ran over). Tokens are host arrays, or the unread device array
        where `_series_reason()` can be None (`_Flight`)."""
        raise NotImplementedError

    def _evict(self, slot):
        """Host-side bookkeeping on slot release (device state needs
        none: the active mask hides the slot and the next join splices
        over it)."""

    def _join_fallback(self, request, exc):
        """Last-resort degradation after a join failed all attempts.
        Return True when the request was served another way (its future
        resolved); False to fail the future with `exc`."""
        return False

    def _admission_gate(self, request):
        """Resource headroom check beyond a free slot. Returning False
        pushes the request back to the queue HEAD (it stays admitted,
        just deferred) and ends this iteration's joins — the paged
        engine's OutOfPages backpressure path."""
        return True

    def _iteration_gauges(self):
        """Extra per-iteration gauges for metrics.record_iteration
        (the paged engine reports page occupancy here)."""
        return None

    def _reset_pool(self):
        """Rebuild device pool state after a decode-step failure (all
        in-flight requests have been evicted)."""

    def _poll_pending(self, now):
        """Advance asynchronous joins (the sharded engine's
        disaggregated prefill): splice any prefill whose arrays are
        ready into the pool and activate the slot. Returns True when
        any slot was activated. Default engines join synchronously —
        no-op."""
        return False

    def _choose_slot(self, free):
        """Pick the slot a new request joins into. The sharded engine
        overrides this to balance occupancy across the dp shards of
        the slot axis."""
        return free[0]

    def _advance_chunks(self, now):
        """Run ONE prefill chunk for every slot mid chunked-prefill.
        Called between admission and the decode step — a chunk-joined
        slot's first chunk must dispatch BEFORE any decode step, so the
        decode step's masked k/v writes (which land at the slot's pool
        index) can never clobber prompt positions the chunk family owns.
        Returns True when any chunk ran. Default: no chunking."""
        return False

    def preempt_slot(self, s, now):
        """Evict the RUNNING request in slot `s` to the prefix cache so
        a later re-admission resumes via a cheap attach instead of a
        re-prefill. Returns the preempted Request (re-queueable), or
        None when this engine has no preemption mechanism (dense pools:
        nothing to park the KV in). Default: no mechanism."""
        return None

    def can_preempt(self, s):
        """True when slot `s` currently holds a preemptible-in-principle
        request (engine-side mechanics only — class policy lives in the
        shaper)."""
        return False

    def _preempt_for(self, scheduler, now):
        """Admission found no free slot: ask the scheduler (duck-typed
        — only the ShapingScheduler implements the hook) for a victim
        slot, evict it to the prefix cache, and requeue the preempted
        request. Returns the freed slot index or None."""
        pick = getattr(scheduler, "pick_preempt_victim", None)
        if pick is None:
            return None
        s = pick(self, now)
        if s is None:
            return None
        if self._flight is not None:
            # preempt_slot snapshots the victim's tokens, so what is in
            # flight lands first; that may free a slot by itself
            self._land()
            free = [i for i, r in enumerate(self.slots) if r is None]
            if free:
                return free[0]
            s = pick(self, now)
            if s is None:
                return None
        try:
            r = self.preempt_slot(s, now)
        except Exception as e:
            # the preempt fault point fires BEFORE any mutation, so a
            # failed preemption leaves slot, pages, and queue intact —
            # record it and let this iteration's admission just stop
            self.metrics.record_error("preempt", e)
            return None
        if r is None:
            return None
        requeue = getattr(scheduler, "requeue_preempted",
                          scheduler.push_front)
        requeue(r)
        return s

    #: jit-cache key KINDS whose pool-state carry argument is donated
    #: into the compiled program (position of the state arg in the
    #: body signature). Donation lets XLA alias the KV pool in place
    #: instead of copying it every dispatch — on the decode hot path
    #: that copy is the whole cache, and on the JOIN family it is the
    #: whole-pool memcpy that masked the prefix cache's TTFT win (a
    #: mid-page radix hit paid it twice: cow + pattach). The whole
    #: program matrix donates now; per-request isolation survives via
    #: a generation-checked alias instead of a copy:
    #:
    #:  - every engine-injected fault point (_PT_SLOT_JOIN/_PT_PREFILL/
    #:    _PT_PATTACH/_PT_SPLICE) fires host-side BEFORE dispatch, so a
    #:    failed attempt's carry is the untouched pre-join buffer and
    #:    the guarded retry re-runs on it bit-identically;
    #:  - an attempt that EXECUTED before failing (watchdog overrun)
    #:    already reassigned self._state inside the op closure — join
    #:    programs write only their target slot, so the retry re-runs
    #:    slot-idempotently on the surviving carry and co-resident
    #:    slots stay bit-identical;
    #:  - the one remaining hazard — a carry consumed by donation with
    #:    no replacement assigned (a dispatch that died mid-execution)
    #:    — is detected by _carry_alive() before every attempt and in
    #:    the join/splice failure handlers, and escalates to the
    #:    existing all-or-nothing recovery (_fail_active -> _reset_pool)
    #:    instead of re-dispatching dead buffers.
    #:
    #: The static analyzer's donation audit (PTA102) reads this same
    #: declaration (one source of truth for the jit builders AND the
    #: audit); ANALYSIS_BASELINE.json carries no join-family waivers.
    _DONATED_KINDS = {"step": 2, "sstep": 2, "pstep": 2, "pverify": 2,
                      "join": 2, "pjoin": 2, "attach": 2, "cow": 0,
                      "pattach": 4, "splice": 0, "bsplice": 0,
                      "cjoin": 4, "pcjoin": 4}

    def _program(self, key, build):
        """Get-or-build a compiled program from the observed jit
        cache: a miss stores `build()`'s result and returns the
        observing wrapper, so every trace surfaces as a compile span."""
        fn = self._compiled.get(key)
        if fn is None:
            self._compiled[key] = build()
            fn = self._compiled[key]   # the observed wrapper
        return fn

    def _donate_argnums(self, key):
        """donate_argnums for the program at `key` (() = donate
        nothing). One declaration shared by the jit builders AND the
        static analyzer, so the audit can never drift from the code."""
        kind = key[0] if isinstance(key, tuple) and key else key
        pos = self._DONATED_KINDS.get(kind)
        return () if pos is None else (pos,)

    # ---- cost/memory accounting (profiler.costs) ----
    def _step_cost_key(self):
        """The jit-cache key of this engine's batched decode step (the
        identity the cost book, the compile observer, and the retrace
        sentinel all share). None = no compiled step (Artifact pool)."""
        return None

    def cost_hint(self, key):
        """Analytic {flops, bytes_accessed, ...} for `key` — the
        CPU-safe fallback when XLA's cost analysis returns nothing (or
        the program compiled before accounting armed). None = no
        estimate for this key."""
        return None

    # ---- zero-warmup startup: AOT precompile + persistent cache ----
    def _startup_programs(self, prompt_buckets):
        """[(key, build, example_args)] for every compiled program
        this pool config serves with: the jit-cache key, a zero-arg
        builder returning the jitted program, and arguments shaped
        EXACTLY like the runtime calls (so an AOT lower().compile()
        yields the executable the hot path will invoke). Default: none
        (the Artifact engine's programs live in its Predictor)."""
        return []

    def _program_fingerprint(self):
        """Identity folded into every persistent-cache key so two
        engines with different models/pool configs can never collide
        in one cache directory."""
        return type(self).__name__

    def _program_cache_key(self, key):
        return f"{self._program_fingerprint()}|{key!r}"

    def _precompile_run(self, progs, cache, persist):
        """Ready every (key, build, args) program: deserialize from
        the persistent cache when possible, AOT lower+compile
        otherwise (and persist the result), and install the finished
        executable in the jit cache — the serving hot path then never
        traces. Returns the cold_start report."""
        from ..tuning.aot_cache import AotCompileCache

        t_start = time.perf_counter()
        if cache is not None and not isinstance(cache, AotCompileCache):
            cache = AotCompileCache(cache)
        err0 = (cache.stats["corrupt"] + cache.stats["stale"]) \
            if cache is not None else 0
        n_loaded = n_compiled = n_ready = n_failed = 0
        for key, build, args in progs:
            if key in self._compiled:
                n_ready += 1
                continue
            t0 = time.perf_counter()
            fn = None
            source = "cache"
            if cache is not None:
                loaded = cache.load(self._program_cache_key(key))
                if loaded is not None:
                    fn = _CachedProgram(self, key, build, loaded)
                    n_loaded += 1
            if fn is None:
                source = "compile"
                try:
                    fn = build().lower(*args).compile()
                except Exception as e:
                    # a program that cannot AOT-compile here still
                    # compiles lazily at first use — precompile must
                    # never take the pool down
                    self.metrics.record_error("precompile", e)
                    n_failed += 1
                    continue
                n_compiled += 1
                if cache is not None and persist:
                    cache.store(self._program_cache_key(key), fn)
            t1 = time.perf_counter()
            self._compiled[key] = fn
            n_ready += 1
            if _trace._SESSION is not None:
                _trace.record_precompile(self, key, t0, t1, source)
            if _costs._BOOK is not None:
                compiled = fn.compiled if isinstance(
                    fn, _CachedProgram) else fn
                _costs.capture_compiled(self, key, compiled,
                                        compile_s=t1 - t0)
        errs = ((cache.stats["corrupt"] + cache.stats["stale"])
                if cache is not None else 0) - err0
        report = {
            "time_to_ready_s": round(time.perf_counter() - t_start, 4),
            "programs": n_ready,
            "loaded_from_cache": n_loaded,
            "compiled": n_compiled,
            "cache_errors": errs,
            "build_failures": n_failed,
            "warm": int(n_compiled == 0 and n_failed == 0),
        }
        self.metrics.record_cold_start(report)
        return report

    # ---- watchdog + retry/backoff ----
    def _guarded(self, opname, fn, retry_tokens=0, failed=None):
        """Run one engine op with up to `max_attempts` tries, capped
        exponential backoff between them, and a wall-clock watchdog: an
        op that returns but took > `watchdog_s` is treated as failed
        (a hung compile/dispatch that eventually unwedges must not be
        trusted to have left the iteration on schedule). The final
        failure propagates to the caller, which isolates it. `failed`
        is the exception of a first attempt the caller made itself."""
        last = failed
        for attempt in range(failed is not None, self.max_attempts):
            if attempt:
                self.metrics.record_retry(opname, retry_tokens)
                self._sleep(min(self.backoff_cap_s,
                                self.backoff_base_s * (2 ** (attempt - 1))))
            t0 = time.monotonic()
            try:
                out = fn()
            except Exception as e:
                last = e
                continue
            if self.watchdog_s is not None:
                dt = time.monotonic() - t0
                if dt > self.watchdog_s:
                    last = WatchdogTimeout(
                        f"{opname} took {dt:.3f}s > watchdog budget "
                        f"{self.watchdog_s}s")
                    continue
            return out
        raise last

    def _carry_alive(self):
        """True when every leaf of the device pool carry is still
        live. Donated join/step programs consume their input carry;
        normally the op closure reassigns self._state before anything
        can observe the dead buffer, but a dispatch that dies
        mid-execution leaves the consumed carry with no replacement —
        this sweep (a few hundred host-side is_deleted checks, no
        device work) is how the retry path refuses to re-dispatch
        dead buffers."""
        state = getattr(self, "_state", None)
        if state is None:
            return True
        import jax

        return not any(getattr(x, "is_deleted", lambda: False)()
                       for x in jax.tree_util.tree_leaves(state))

    def _join_attempt(self, s, r):
        _PT_SLOT_JOIN()
        if not self._carry_alive():
            raise PoolCarryLost(
                "pool carry consumed by a failed dispatch with no "
                "replacement state — refusing to retry the join on "
                "dead buffers")
        return self._join(s, r)

    def _step_attempt(self, active):
        """One try at a decode step: the fault point, then the
        subclass's step. Returns its `_Flight` (the tokens unread where
        the stepper leaves them on the device)."""
        it = self._iter_trace
        if it is not None:
            it.unwind(it.step)    # a failed attempt's open spans
        _PT_DECODE()
        t0 = self.clock()
        toks, active = self._decode_step(active)
        return _Flight(toks, [(int(s), self.slots[s])
                              for s in np.flatnonzero(active)], t0)

    def _series_attempt(self, active):
        """A step in series: enqueued and read inside one guarded try,
        so the watchdog times the whole step."""
        fl = self._step_attempt(active)
        self._read(fl)
        return fl

    def _read(self, fl):
        """Block until the flight's tokens are on the host."""
        if not fl.unread():
            return
        it = self._iter_trace
        if it is not None:
            sp = it.begin_wait("step.readback")
        try:
            _PT_READBACK()
            toks = np.asarray(fl.toks)
        finally:
            if it is not None:
                it.end_wait(sp)
        # what the program counted leaves it behind its tokens, in the
        # one array this read brings to the host
        fl.toks = toks[:self.num_slots]
        self._take_counts(toks[self.num_slots:])

    def _take_counts(self, counts):
        """Counters a program returned behind its tokens (host array;
        empty where the served stack counts nothing)."""

    def _deliver_flight(self, fl):
        """Hand a read flight's tokens to the requests it was enqueued
        for: (tokens delivered, slot-steps dropped). A request that
        ended while its token was in flight (cancelled, past its
        deadline, `eos_id` found a step late) gets nothing more."""
        now = self.clock()
        n = late = 0
        if isinstance(fl.toks, tuple):
            # speculative step: (emit [S, k], n_emit [S]) — up to k
            # tokens per slot per iteration; delivery stops the moment
            # the request finishes (eos / max_new_tokens), dropping the
            # over-speculated tail exactly like the eager oracle would
            emit, n_emit = fl.toks
            for s, r in fl.pairs:
                for j in range(int(n_emit[s])):
                    if r.state == "DONE":
                        break
                    self._deliver(r, int(emit[s, j]), now)
                    n += 1
        else:
            for s, r in fl.pairs:
                if r.state == "DONE":
                    late += 1
                    continue
                self._deliver(r, int(fl.toks[s]), now)
                n += 1
        self.metrics.record_decode(n, now - fl.t0, late)
        # decode-step inter-arrival: the latency co-resident requests
        # actually SEE between their tokens — inline prefill inflates
        # it, disaggregated prefill doesn't
        if self._last_step_done is not None:
            self.metrics.record_step_gap(now - self._last_step_done)
        self._last_step_done = now
        return n, late

    def _land(self):
        """Read what is in flight and deliver it, out of turn: before a
        preemption, before a step in series, at a drain. False when the
        read failed (every request evicted, the pool rebuilt)."""
        fl = self._flight
        try:
            self._read(fl)
        except Exception as e:
            self.metrics.record_error("decode_step", e)
            self._fail_active(e)
            return False
        self._flight = None
        self._deliver_flight(fl)
        return True

    def _fail_active(self, exc):
        """Decode-step failure that survived retries, or that surfaced
        where a step's tokens were read: every in-flight request is
        poisoned (the batched step is all-or-nothing, and a step
        enqueued behind a failed one ran on its state), so evict them
        all with their partial tokens + the cause, rebuild the pool
        state, and keep serving — the pool itself survives."""
        now = self.clock()
        doomed = self.running()
        self._flight = None
        for s, r in enumerate(self.slots):
            if r is not None:
                self._vacate(s)
        for r in doomed:
            self.metrics.record_finish("error", len(r.tokens))
            self.metrics.record_eviction_on_error()
            r.finish("error", now, error=exc)
            self._cbs.emit("on_finish", r)
        self._reset_pool()

    # ---- slot lifecycle ----
    def occupancy(self):
        return sum(r is not None for r in self.slots)

    def idle(self):
        """No request in a slot and no step in flight: with an empty
        queue there is nothing left to do."""
        return self._flight is None and self.occupancy() == 0

    def running(self):
        """Every request the engine holds: in a slot, or out of it with
        its last token still in flight."""
        out = [r for r in self.slots if r is not None]
        if self._flight is not None:
            out += [r for _, r in self._flight.pairs
                    if r.slot is None and r.state == "RUNNING"]
        return out

    def _vacate(self, s):
        self.slots[s] = None
        self._evict(s)

    def _finish_slot(self, s, reason, now):
        r = self.slots[s]
        self._vacate(s)
        self._finish_request(r, reason, now)

    def _finish_request(self, r, reason, now):
        self.metrics.record_finish(reason, len(r.tokens))
        if reason in ("eos", "length"):
            # slo is an SLOClass once a ShapingScheduler admitted the
            # request; a string class name through the plain FIFO is
            # never resolved — no class semantics, nothing to record
            slo = getattr(r, "slo", None)
            if hasattr(slo, "ttft_target_s") \
                    and r.first_token_at is not None \
                    and r.submitted_at is not None:
                ttft = r.first_token_at - r.submitted_at
                n = len(r.tokens)
                tpot = ((now - r.first_token_at) / (n - 1)
                        if n > 1 else 0.0)
                self.metrics.record_slo_finish(
                    slo.name, ttft, tpot, slo.ttft_target_s,
                    slo.tpot_target_s)
        r.finish(reason, now)
        self._cbs.emit("on_finish", r)

    def _tenant_of(self, r):
        """Tenant label for per-tenant accounting (None = tenancy off:
        the engine carries no AdapterPool)."""
        if self._apool is None:
            return None
        return getattr(r, "adapter", None) or "base"

    @staticmethod
    def _owed(r):
        """Tokens `r` is still to be handed before it ends by count
        (a resumed request first re-absorbs what its caller holds)."""
        return r.max_new_tokens - len(r.tokens) + getattr(r, "_replay", 0)

    def _deliver(self, r, tok, now):
        if r.state == "DONE":
            return
        rep = getattr(r, "_replay", 0)
        if rep > 0:
            # post-preemption replay: the resumed slot re-decodes
            # tokens the caller already holds (determinism makes the
            # replay bit-identical); absorb them silently — no append,
            # no stream callback, no TTFT/throughput double-count
            r._replay = rep - 1
            self.metrics.record_replay_token()
            return
        r.tokens.append(tok)
        self.metrics.record_token(self._tenant_of(r))
        if r.first_token_at is None:
            r.first_token_at = now
            if r._trace is not None:
                _rt.on_first_token(r)
            if r.submitted_at is not None:
                self.metrics.record_first_token(now - r.submitted_at)
        self._cbs.emit("on_token", r, tok)
        if r.stream_cb is not None:
            try:
                r.stream_cb(r, tok)
            except Exception as e:
                # a broken streaming callback must not stall the pool,
                # but the failure is recorded, never swallowed
                self.metrics.record_error("stream_cb", e)
        if r.eos_id is not None and tok == r.eos_id:
            reason = "eos"
        elif len(r.tokens) >= r.max_new_tokens:
            reason = "length"
        else:
            return
        if r.slot is not None:    # None: it gave the slot up a step ago
            self._vacate(r.slot)
        self._finish_request(r, reason, now)

    # ---- the continuous-batching iteration ----
    def run_iteration(self, scheduler):
        """One iteration: harvest faults, admit new work, decode one
        token for every active slot. Returns True when any work was
        done (False = idle: empty queue, empty pool). What it computed
        is delivered when it returns. Under a tracer session the
        iteration's phases are engine-track spans (`serving/tracing.py`
        `IterationTrace`)."""
        return self._run(scheduler, False)

    def run_ahead(self, scheduler):
        """`run_iteration` for a loop that owns the NEXT iteration too
        (`ServingServer._loop`, `serve_until_idle`): where the stepper
        keeps the next step's inputs on the device, the step is
        enqueued and its tokens are left unread; the next call enqueues
        ITS step first and only then reads and delivers these, so the
        host delivers, harvests and admits while the device computes. A
        call that finds nothing to enqueue reads what is left: nothing
        stays in flight past an idle call, and `abort_active` lands it
        too."""
        return self._run(scheduler, True)

    def _run(self, scheduler, keep):
        tr = _trace._SESSION
        if tr is None:
            return self._iterate(scheduler, None, {}, keep)
        it = self._iter_trace = _rt.IterationTrace(tr, self.metrics)
        progress, attrs = False, {}
        try:
            progress = self._iterate(scheduler, it, attrs, keep)
            return progress
        finally:
            self._iter_trace = None
            it.close(progress, **attrs)

    def _series_reason(self):
        """Why the next decode step cannot be enqueued before the last
        one's tokens are read; None where it can. Read after admission
        from what the engine holds. Default: the step computes from
        host values (`ArtifactServingEngine`)."""
        return "host"

    def _iterate(self, scheduler, it, it_attrs, keep):
        now = self.clock()
        progress = False
        had_flight = self._flight is not None
        if it is not None:
            sp = it.begin("iter.harvest")
        if had_flight:
            # a request whose LAST token is in flight (it ends by
            # count) gives its slot up now, so this round can admit
            # into it; the token still reaches it, by `pairs`
            for s, r in self._flight.pairs:
                if self.slots[s] is r and self._owed(r) <= 1:
                    self._vacate(s)
                    r.slot = None
            progress = True
        # 1. fault harvest: cancellation + deadline eviction happen at
        # iteration boundaries — partial tokens are delivered; a token
        # in flight for such a request is dropped where it is read
        for s, r in enumerate(self.slots):
            if r is None:
                continue
            if r.cancelled:
                self._finish_slot(s, "cancelled", now)
                progress = True
            elif r.expired(now):
                self._finish_slot(s, "timeout", now)
                progress = True
        # 1b. asynchronous joins: splice finished disaggregated
        # prefills into the pool (no-op for synchronous engines)
        if self._poll_pending(now):
            progress = True
        if it is not None:
            it.end(sp)
            sp = it.begin("iter.admit")
        # 2. admission: refill free slots, bounded per iteration

        def _queue_death(req):   # cancelled/expired while QUEUED
            self.metrics.record_finish(req.finish_reason,
                                       len(req.tokens))
            self._cbs.emit("on_finish", req)

        joins = 0
        tok0s = []   # (request, traced token-0) resolved after the loop
        while joins < self.max_joins_per_iter:
            free = [i for i, r in enumerate(self.slots) if r is None]
            if not free:
                # fairness-aware preemption: a full pool defers to the
                # scheduler (duck-typed — only the ShapingScheduler
                # implements the hook) to evict a lower-class slot to
                # the prefix cache; resume later rides a cheap attach
                s = self._preempt_for(scheduler, now)
                if s is None:
                    break
                free = [s]
                progress = True
            r = scheduler.pop_ready(now, on_dead=_queue_death)
            if r is None:
                break
            try:
                self.admit_check(r)
            except Exception as e:
                # unservable request that bypassed the frontend check
                self.metrics.record_error("admit", e)
                r.fail(e, now)
                self.metrics.record_finish("error", len(r.tokens))
                self._cbs.emit("on_finish", r)
                continue
            if not self._admission_gate(r):
                # resource backpressure (paged: not enough free pages):
                # the request stays queued at the head, joins stop for
                # this iteration, decode keeps draining the pool
                scheduler.push_front(r)
                break
            s = self._choose_slot(free)
            r.state, r.slot = "RUNNING", s
            self.slots[s] = r
            if it is not None:
                _rt.on_join_begin(r, s, sp)
            try:
                tok = self._guarded("slot_join",
                                    lambda: self._join_attempt(s, r))
            except Exception as e:
                # per-request isolation: the failed join kills THIS
                # request's future (or degrades it to the eager path),
                # frees the slot, and the pool keeps serving
                self._vacate(s)
                r.slot = None
                if r._trace is not None:
                    _rt.on_join_end(r, ok=False, error=e)
                self.metrics.record_error("slot_join", e)
                if not self._join_fallback(r, e):
                    r.fail(e, self.clock())
                    self.metrics.record_finish("error", len(r.tokens))
                    self._cbs.emit("on_finish", r)
                progress = True
                if not self._carry_alive():
                    # the failed attempt consumed the donated carry
                    # without replacing it: no valid pool state
                    # survives for the co-resident slots — rebuild
                    # (all-or-nothing recovery, same as a dead step)
                    self._fail_active(e)
                    break
                continue
            joins += 1
            progress = True
            if r._trace is not None:
                _rt.on_join_end(r, pending=s in self._pending)
            if getattr(r, "_replay", 0) > 0:
                # a preempted request re-joining: its replay counter
                # was armed at preemption and survives to here
                self.metrics.record_resume()
            self.metrics.record_join()
            self._cbs.emit("on_join", r, s)
            if tok is not None:   # prefill already produced token 0
                tok0s.append((r, tok))
        if it is not None:
            it.end(sp, joins=joins)
        # 3. the decode step: ahead of the last one's tokens where the
        # stepper and the slots allow it and the caller owns the next
        # iteration, else in series. `why` is what a step that finds
        # nothing unread will say of itself: a preemption landed the
        # flight, the series reason, or the pool stood empty / the
        # caller steps by hand ("idle").
        reason = self._series_reason()
        series = reason is not None or not keep
        why = ("preempt" if had_flight and self._flight is None
               else reason or "idle")
        if series:
            if self._flight is not None:
                self._land()
            self._resolve_tok0(tok0s, it)
        if it is not None:
            sp = it.begin("iter.chunks")
        # 3a. chunked prefill: one chunk per mid-prefill slot, BEFORE
        # the decode step — a freshly chunk-joined slot's first chunk
        # must set the pool index past its pad hole before any masked
        # decode-step write can land inside the prompt region
        if self._advance_chunks(self.clock()):
            progress = True
        if it is not None:
            it.end(sp)
        # slots with a disaggregated prefill still in flight stay
        # masked out; so does a joiner whose only token is the unread
        # token 0 (in series it is delivered by now and the slot free)
        last = {id(r) for r, _ in tok0s if self._owed(r) <= 1}
        active = np.asarray(
            [r is not None and s not in self._pending
             and id(r) not in last
             for s, r in enumerate(self.slots)], bool)
        stepping = active.any()
        if stepping or self._flight is not None:
            progress = True
            issued = read = error = None
            if it is not None:
                it.begin_step()
            try:
                if stepping:
                    issued, why = self._issue(active, series, why)
                if self._flight is not None:
                    self._read(self._flight)    # the LAST step's tokens
                    read, self._flight = self._flight, None
            except Exception as e:
                error, issued = e, None
                self.metrics.record_error("decode_step", e)
                self._fail_active(e)
            if issued is not None:
                self.metrics.record_step(why)
                it_attrs["step"] = why or "ahead"
                if issued.unread():
                    self._flight = issued
                else:             # a step in series: read already
                    read = issued
            if it is not None:
                it.end_step(issued.pairs if issued is not None else (),
                            self.occupancy(), scheduler.depth(),
                            **({} if error is None else
                               {"error": type(error).__name__}))
            if read is not None:
                if it is not None:
                    sp = it.begin("iter.deliver")
                n, it_attrs["late_slot_steps"] = self._deliver_flight(read)
                if it is not None:
                    it.end(sp, tokens=n)
        else:
            self._last_step_done = None
        if not series:
            self._resolve_tok0(tok0s, it)
        if it is not None:
            sp = it.begin("iter.account")
        # computed ONCE an iteration: it has a side effect (the memory
        # watermark check), and the spans carry what the metrics record
        gauges = self._iteration_gauges() or {}
        depth, occ = scheduler.depth(), self.occupancy()
        self.metrics.record_iteration(depth, occ / self.num_slots,
                                      **gauges)
        lag_fn = getattr(scheduler, "wfq_lag_by_tenant", None)
        if lag_fn is not None:
            self.metrics.set_wfq_lag(lag_fn())
        self._cbs.emit("on_iteration", {
            "queue_depth": depth, "occupancy": occ, "joins": joins})
        if it is not None:
            it.end(sp)
            it_attrs.update(joins=joins, occupancy=occ,
                            queue_depth=depth, gauges=gauges)
        return progress

    def _issue(self, active, series, why):
        """Enqueue the decode step over `active`: (its `_Flight`, why
        it found nothing unread — None where it went ahead of the last
        step's tokens). In series the flight comes back read. An
        attempt ahead that fails is the last one ahead: what is in
        flight lands, and the retries run in series. (The watchdog
        times whole steps, so steps in series only: ahead, the time
        from enqueue to tokens holds the next iteration's host work.)"""
        n = int(active.sum())
        if series:
            return self._guarded(
                "decode_step", lambda: self._series_attempt(active),
                retry_tokens=n), why
        try:
            return (self._step_attempt(active),
                    None if self._flight is not None else why)
        except Exception as e:
            failed = e
        if self._flight is not None:
            self._land()
        # the landing may have ended requests (eos; the pool rebuilt)
        active = active & np.asarray([r is not None for r in self.slots])
        if not active.any():
            raise failed
        return self._guarded(
            "decode_step", lambda: self._series_attempt(active),
            retry_tokens=n, failed=failed), "retry"

    def _resolve_tok0(self, tok0s, it):
        """Deliver the admission round's first tokens AFTER the last
        join dispatched: the traced scalars sync here (one natural host
        sync instead of a blocking int() per join) — in series before
        the step, otherwise after it is enqueued. A request finishing
        at token 0 by `eos_id` is then found a step late."""
        if it is not None:
            sp = it.begin_wait("iter.tok0", n=len(tok0s))
        for r, tok in tok0s:
            tok = np.asarray(tok).reshape(-1)   # the sync: [tok0, counts]
            self._take_counts(tok[1:])
            if r.state != "DONE":     # evicted with a failed step
                self._deliver(r, int(tok[0]), self.clock())
        del tok0s[:]
        if it is not None:
            it.end_wait(sp)

    def serve_until_idle(self, scheduler, max_iterations=None):
        """Synchronous drive: iterate until queue and pool are empty
        and nothing is in flight. The offline path (Predictor.generate,
        benches, tests) — online serving wraps run_ahead in a
        ServingServer thread."""
        it = 0
        while scheduler.depth() > 0 or not self.idle():
            self.run_ahead(scheduler)
            it += 1
            if max_iterations is not None and it >= max_iterations:
                raise RuntimeError(
                    f"serve_until_idle: no convergence after {it} "
                    f"iterations")

    def abort_active(self, reason, now=None):
        """Finalize every in-flight request (non-drain shutdown);
        partial tokens are delivered, a step in flight included."""
        if self._flight is not None:
            self._land()
        if now is None:
            now = self.clock()
        for s, r in enumerate(self.slots):
            if r is not None:
                self._finish_slot(
                    s, "cancelled" if r.cancelled else reason, now)


class ServingEngine(_EngineBase):
    """The always-on model-backed engine: (decoder, embed, project)
    triple — the same step net `DecodeEngine` compiles — over a pooled
    StaticKVCache of `num_slots` rows x `max_len` positions.

    Admission contract: a request needs `bucket(prompt_len) +
    max_new_tokens <= max_len` cache positions and a cross-attention
    `memory` of the pool's [M, D] shape (fixed by the first join).
    Token positions follow the DecodeEngine convention — prompt at
    [0, Pb), its pad hole key-masked forever, generated tokens at
    absolute slots Pb, Pb+1, ... — which is what makes every slot's
    output bit-comparable to a solo `generate_eager` run."""

    def __new__(cls, *args, **kw):
        # `paged=True` routes construction to the paged-pool engine so
        # callers opt into paging without a second entry point
        if cls is ServingEngine and kw.get("paged"):
            return object.__new__(PagedServingEngine)
        return object.__new__(cls)

    def __init__(self, decoder, embed=None, project=None, *, num_slots=8,
                 max_len=128, max_joins_per_iter=2, metrics=None,
                 callbacks=(), clock=time.monotonic,
                 eager_fallback=False, paged=False, spec_k=None,
                 spec_ngram=2, spec_adapt=True, spec_adapt_low=0.15,
                 spec_adapt_high=0.6, spec_adapt_patience=4,
                 spec_adapt_alpha=0.3, adapters=None, quantize=None,
                 prefill_chunk=None, **kw):
        super().__init__(num_slots, max_joins_per_iter=max_joins_per_iter,
                         metrics=metrics, callbacks=callbacks, clock=clock,
                         **kw)
        from ..parallel.functional import functionalize
        from ..text.generation import _StepNet
        from .layers import (CausalLMDriver, DecoderStackDriver,
                             DenseLayout, PagedLayout, PlainStepper,
                             SpecStepper)

        # a decoder-only causal LM (it says what each block keeps of a
        # sequence: `cache_kinds()`) is served whole, with no embed /
        # project beside it and no `memory` on its requests; what its
        # recurrent states and window rings rule out is refused here,
        # by name, and never silently ignored
        self.causal = hasattr(decoder, "cache_kinds")
        if self.causal:
            self._refuse_for_state(
                decoder, embed, project,
                paged=isinstance(self, PagedServingEngine), spec_k=spec_k,
                adapters=adapters, quantize=quantize,
                prefill_chunk=prefill_chunk,
                eager_fallback=eager_fallback,
                sharded=getattr(self, "_accepts_sharded_params", False))
        elif embed is None or project is None:
            raise ValueError(
                "ServingEngine(decoder, embed, project): a decoder stack "
                "needs its embedding and its projection; only a causal "
                "LM with cache_kinds() is served alone")
        # int8 base weights: quantize="int8" rewrites every large
        # dense weight of the stack (decoder projections + FFN, the
        # embedding vocab table, the logits projection) to symmetric
        # per-output-channel int8 + f32 scales BEFORE functionalize
        # snapshots the state — the compiled programs then carry int8
        # weight buffers and the scaled-int8 matmul path
        # (ops/quant.py). In place and one-way: the engine owns the
        # model it serves. With quantize=None nothing is touched and
        # the fp32 path is bit-identical to every prior PR.
        if quantize is not None:
            if str(quantize) != "int8":
                raise ValueError(f"quantize={quantize!r}: only 'int8' "
                                 f"is supported")
            from .adapters import quantize_net

            quantize_net(decoder, embed, project)
        self.quantize = quantize
        # batched LoRA adapters: an AdapterPool turns every step/join
        # program into an adapter-carrying one — per-slot adapter ids
        # + stacked A/B banks ride in as traced inputs, so tenant
        # switches and hot-load/evict never retrace
        if adapters is not None and adapters.decoder is not decoder:
            raise ValueError("the AdapterPool was built for a "
                             "different decoder than this engine "
                             "serves")
        self._apool = adapters
        self._adapter_rows = np.zeros(int(num_slots), np.int64)
        if adapters is not None:
            adapters.bind_metrics(self.metrics)
        self.eager_fallback = bool(eager_fallback)
        self.max_len = int(max_len)
        # speculative decoding (text/speculative.py): spec_k >= 2 turns
        # the batched decode step into a draft + k-token-verify pair
        # delivering up to spec_k tokens per slot per iteration —
        # bit-identical tokens, fewer dispatches. The pool carries
        # spec_k extra cache positions so a round's fixed-k verify
        # write never clips (admission keeps the max_len contract).
        # Works on EVERY pool layout: the paged pool's verify rides
        # multi-token page writes + the block-table verify kernel.
        if spec_k is not None:
            spec_k = int(spec_k)
            if spec_k < 2:
                raise ValueError("spec_k must be >= 2 (the pending "
                                 "token plus at least one draft)")
        self.spec_k = spec_k
        self.spec_ngram = int(spec_ngram)
        # adaptive effective k: shrink/regrow the live draft depth
        # batch-wide on the acceptance-rate EMA with hysteresis (the
        # force-rejected tail rides the same fixed-k program, so a k
        # change NEVER retraces); see layers.SpecStepper
        self.spec_adapt = bool(spec_adapt)
        self.spec_adapt_low = float(spec_adapt_low)
        self.spec_adapt_high = float(spec_adapt_high)
        self.spec_adapt_patience = int(spec_adapt_patience)
        self.spec_adapt_alpha = float(spec_adapt_alpha)
        # chunked prefill (the mechanism; serving/shaping.py is the
        # policy): prompts longer than `prefill_chunk` positions
        # prefill in fixed-size chunks dispatched BETWEEN decode
        # steps — run_iteration runs ONE chunk per mid-prefill slot
        # per iteration — so the decode-step inter-arrival co-resident
        # requests see is bounded by one chunk at ANY prompt length.
        # Power of two so chunk buckets ride the compile-bucket grid
        # (one cjoin/pcjoin compile per chunk bucket, never per
        # prompt); the paged engine additionally requires a page
        # multiple so every chunk boundary is page-aligned.
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 2 or prefill_chunk & (prefill_chunk - 1):
                raise ValueError(
                    f"prefill_chunk={prefill_chunk}: must be a power "
                    f"of two >= 2 (compile-bucket granularity)")
        self.prefill_chunk = prefill_chunk
        self._chunking = {}   # slot -> mid-chunked-prefill progress
        self._fm_cross = None   # lazy cross-K/V net (attach + chunks)
        self._pool_len = self.max_len + (spec_k or 0)
        # the composable pool layers (serving/layers.py): cache layout
        # x placement x stepper — every program body lives there, the
        # engine classes are configuration shims
        self.layout = (PagedLayout(self)
                       if isinstance(self, PagedServingEngine)
                       else DenseLayout(self))
        self.placement = self._make_placement()
        self.stepper = (SpecStepper(self) if self.spec_k
                        else PlainStepper(self))
        self._net = decoder if self.causal else \
            _StepNet(decoder, embed, project)
        self._fm = functionalize(self._net)
        #: what the layout and the engine ask of the served stack
        #: (layers.py): its cache kinds, and what its requests carry
        self.driver = (CausalLMDriver(self) if self.causal
                       else DecoderStackDriver(self))
        # this iteration's cache counts (`_iteration_gauges` hands them
        # to the metrics and the `iteration` span, then clears them),
        # and what its programs' expert layers counted
        self._cache_iter = {}
        self._experts_iter = {}
        if not getattr(self, "_accepts_sharded_params", False):
            _reject_sharded_params(
                self._fm.params(),
                f"{type(self).__name__}"
                f"{'(paged=True)' if paged else ''}")
        # jit cache whose entries the compile observer wraps: each
        # trace+compile surfaces as a "compile" span with its duration
        self._compiled = _trace.JitCache(self)
        self._state = None          # lazily built on first join
        self._mem_shape = None
        self._np_dtype = None
        self._pool_key = None
        self._n_params = None       # cached dense param count (hints)
        # the live HBM ledger: snapshot()["memory"] reports this
        # engine's weights + pool footprint (and the budget watermark
        # warns before the pool runs dry)
        self.metrics.set_memory_provider(self.memory_ledger)

    @staticmethod
    def _refuse_for_state(model, embed, project, *, paged, spec_k,
                          adapters, quantize, prefill_chunk,
                          eager_fallback, sharded):
        """A stack with recurrent or ring state, or with latent pages:
        every option that would have to snapshot, roll back or replay
        such a state, or read latent rows where a program reads K/V
        pages, raises."""
        name = type(model).__name__
        stateful = {k for k in model.cache_kinds()
                    if k in ("recurrent", "ring")}
        latent = "latent" in model.cache_kinds()
        if embed is not None or project is not None:
            raise ValueError(
                f"{name} is a whole causal LM: pass it alone, without "
                f"embed / project")
        if not paged:
            raise ValueError(
                f"{name} keeps its attention cache in pages: construct "
                f"with paged=True (the dense pool has no latent, ring or "
                f"recurrent state)")
        if sharded:
            raise ValueError(
                f"the sharded engine (ShardedServingEngine): it places a "
                f"(decoder, embed, project) triple's parameters and K/V "
                f"pools by rule, and has none for {name}'s state")
        if not (stateful or latent):
            return
        what = (f"{name} keeps {'/'.join(sorted(stateful))} state a slot"
                if stateful else
                f"{name} keeps one latent row a token a block")
        if spec_k is not None:
            raise ValueError(
                f"speculative decoding (spec_k={spec_k}): {what}, and "
                + ("a rejected draft is undone by moving a write index "
                   "back, which no scan state or ring can follow"
                   if stateful else
                   "the k-token verify step reads K/V pages: no verify "
                   "program reads latent rows"))
        if adapters is not None:
            raise ValueError(
                f"LoRA tenants (adapters=): {what}; the adapter scope "
                f"rewrites nn.Linear projections, which these mixers do "
                f"not go through, so a tenant's adapter would be ignored")
        if quantize is not None:
            raise ValueError(
                f"int8 weights (quantize={quantize!r}): {what}; "
                f"quantize_net rewrites the (decoder, embed, project) "
                f"triple's nn.Linear weights and would leave this model "
                f"as it is")
        if prefill_chunk is not None:
            raise ValueError(
                f"chunked prefill (prefill_chunk={prefill_chunk}): "
                f"{what}; "
                + ("a chunk resumes from K/V pages alone, and the scan "
                   "state and rings of a half-read prompt are not "
                   "carried from chunk to chunk" if stateful else
                   "a chunk's join would read the earlier chunks' latent "
                   "rows through the page table, which no program does "
                   "yet"))
        if eager_fallback:
            raise ValueError(
                f"eager fallback (eager_fallback=True): {what}; the "
                f"fallback runs generate_eager over a (decoder, embed, "
                f"project) triple")

    # ------------------------------------------------------------------
    def _make_placement(self):
        """The program-build strategy (layers.py): plain single-chip
        jit here; the sharded engine overrides with the mesh-annotated
        wrap."""
        from .layers import SinglePlacement

        return SinglePlacement(self)

    def _pool_variant(self):
        """Label for per-pool-variant metric splits (the speculation
        section's step-ms breakdown)."""
        base = "paged" if isinstance(self, PagedServingEngine) \
            else "dense"
        if getattr(self, "_accepts_sharded_params", False):
            return "sharded-" + base
        return base

    # ---- multi-tenant adapter plumbing (serving/adapters.py) ----
    def _adapter_pool_key(self):
        """Adapter-config component of the pool key: adapter-carrying
        programs have different signatures (ids + banks ride in), so
        the jit-cache/AOT identities must not collide with a
        base-only pool of the same shape."""
        if self._apool is None:
            return ()
        p = self._apool
        return (("lora", p.capacity, p.rank, len(p.targets)),)

    def _placed_banks(self):
        """The stacked A/B banks as the programs' traced inputs (the
        sharded engine overrides with a mesh-replicated copy, cached
        per pool version)."""
        return self._apool.banks()

    def _adapter_args(self):
        """(per-slot adapter ids [S] int32, banks) appended to every
        step-family dispatch — traced data, never part of a cache
        key, so adapter switches and hot-loads never retrace."""
        if self._apool is None:
            return ()
        import jax.numpy as jnp

        return (jnp.asarray(self._adapter_rows.astype(np.int32)),
                self._placed_banks())

    def _lora_ctx(self, ad):
        """The trace scope a program body opens around fm.apply: `ad`
        is the body's (ids-or-scalar, banks) tail (empty when the
        engine carries no pool — a zero-cost nullcontext)."""
        import contextlib

        if not ad:
            return contextlib.nullcontext()
        import jax.numpy as jnp

        from ..ops.quant import lora_scope

        ids, banks = ad
        return lora_scope(jnp.asarray(ids, jnp.int32).reshape(-1),
                          banks)

    def _acquire_adapter(self, r):
        """Pin the request's adapter bank row for its slot (0 = base).
        Runs inside the join attempt, so a transient load fault rides
        the join's retry loop; the caller releases on a later join
        failure."""
        if self._apool is None:
            return 0
        name = getattr(r, "adapter", None)
        if name is None:
            return 0
        return self._apool.acquire(name)

    def _release_adapter_row(self, row):
        if self._apool is not None and row:
            self._apool.release(row)

    def _adapter_gate(self, r):
        """Admission headroom for the request's adapter: False defers
        the queue head (push_front) until a bank row frees — the
        OutOfAdapters backpressure path, mirroring OutOfPages."""
        if self._apool is None:
            return True
        name = getattr(r, "adapter", None)
        if name is None or self._apool.can_acquire(name):
            return True
        self.metrics.record_adapter_wait()
        return False

    def _admission_gate(self, r):
        return self._adapter_gate(r)

    def _tenant_slot_counts(self):
        out = {}
        for s, req in enumerate(self.slots):
            if req is None:
                continue
            t = self._tenant_of(req)
            out[t] = out.get(t, 0) + 1
        return out

    def _iteration_gauges(self):
        if self._apool is None:
            return None
        return {"tenant_slots": self._tenant_slot_counts()}

    def _evict(self, s):
        self._chunking.pop(s, None)
        self._pending.discard(s)
        row = int(self._adapter_rows[s])
        if row:
            self._adapter_rows[s] = 0
            self._release_adapter_row(row)

    # ---- the cross-attention K/V net (attach + chunk families) ----
    def _ensure_cross(self):
        """Lazily build the functionalized 'memory -> per-layer cross
        K/V' net the prefix-attach and chunked-prefill program
        families run (they never run a self-attention prefill, but
        the joiner's own cross K/V is per-request compute)."""
        if self._fm_cross is None:
            self._fm_cross = _make_cross_kv_fm(self._net.decoder)

    def _cross_params(self):
        """Cross-attention K/V net params for the attach/chunk paths
        (the sharded engine overrides with its mesh-placed copy)."""
        return self._fm_cross.params()

    def _params(self):
        """Param pytree the compiled programs run over. The sharded
        engine overrides this with its mesh-placed copy."""
        return self._fm.params()

    def _buffers(self):
        return self._fm.buffers()

    def _max_len_detail(self):
        """Suffix for the max_len overflow message (the paged engine
        reports the page-granular limit here)."""
        return ""

    # ---- the live HBM ledger ----
    def weights_bytes(self):
        """Byte footprint of the params + buffers the compiled
        programs run over (the placed copy for sharded engines)."""
        if self._weights_bytes is None:
            self._weights_bytes = _tree_bytes(self._params()) + \
                _tree_bytes(self._buffers())
        return self._weights_bytes

    def pool_bytes(self):
        """Byte footprint of the slot-pool device state (0 before the
        first join builds it)."""
        return _tree_bytes(self._state)

    def pool_in_use_bytes(self):
        """The LIVE portion of the pool. The dense pool preallocates
        every row, so committed == live; the paged engine subtracts
        unmapped pages."""
        return self.pool_bytes()

    def adapter_bytes(self):
        """Byte footprint of the stacked LoRA banks (0 without an
        AdapterPool) — the ledger's adapter component, exactly the
        pool's analytic capacity * (d_in + d_out) * r * 4 sum."""
        return 0 if self._apool is None else self._apool.bytes()

    def memory_ledger(self):
        """The `memory` section's raw components — weights, pool,
        adapter banks, live bytes, and the compile temp high-water
        from the armed cost book (0 when accounting is off)."""
        w = self.weights_bytes()
        p = self.pool_bytes()
        a = self.adapter_bytes()
        return {"weights_bytes": w, "pool_bytes": p,
                "adapter_bytes": a,
                "in_use_bytes": w + a + self.pool_in_use_bytes(),
                "compile_temp_peak_bytes": _costs.temp_high_water()}

    # ---- analytic cost hints (profiler.costs fallback) ----
    def _model_dims(self):
        """(n_params, n_layers, heads, head_dim, mem_len) for the
        analytic flop formulas; None before the pool shape is known."""
        if self._n_params is None:
            self._n_params = sum(
                int(getattr(v, "size", 0))
                for v in self._params().values()) + sum(
                int(getattr(v, "size", 0))
                for v in self._buffers().values())
        decoder = self._net.decoder
        h0 = decoder.layers[0].self_attn
        M = self._mem_shape[0] if self._mem_shape else 0
        return (self._n_params, len(decoder.layers), h0.num_heads,
                h0.head_dim, M)

    def _step_cost_key(self):
        if self._pool_key is None:
            return None
        return self.layout.step_key() if not self.spec_k \
            else self.layout.spec_step_key()

    def cost_hint(self, key):
        if self.causal:
            return None   # the analytic formulas are the decoder stack's
        kind = key[0] if isinstance(key, tuple) and key else key
        n_params, n_layers, heads, hd, M = self._model_dims()
        pool = self.pool_bytes()
        w = self.weights_bytes()
        if kind in ("step", "pstep", "sstep", "pverify"):
            # the compiled step computes ALL S rows over the full
            # (masked) max_len window, active or not; the k-token
            # verify step feeds spec_k query rows through the same net
            flops = _costs.transformer_decode_flops(
                n_params, self.num_slots, self.max_len, n_layers,
                heads, hd, mem_len=M)
            if kind in ("sstep", "pverify"):
                flops *= (self.spec_k or 1)
            return {"flops": flops, "bytes_accessed": w + pool,
                    "argument_bytes": w + pool}
        if kind == "draft":
            # pure gathers over the [S, L] token mirror — byte traffic
            return {"flops": 0.0, "bytes_accessed": pool,
                    "argument_bytes": pool}
        if kind in ("join", "pjoin", "prefill") and len(key) > 1:
            Pb = int(key[1])
            flops = _costs.transformer_prefill_flops(
                n_params, 1, Pb, n_layers, heads, hd, mem_len=M)
            return {"flops": flops, "bytes_accessed": w + pool,
                    "argument_bytes": w + pool}
        if kind == "pattach" and len(key) > 2:
            # tail-only prefill: Tb query rows through the net, each
            # attending over at most the (Mb + tail) page window
            Tb = int(key[2])
            flops = _costs.transformer_prefill_flops(
                n_params, 1, Tb, n_layers, heads, hd, mem_len=M)
            return {"flops": flops, "bytes_accessed": w + pool,
                    "argument_bytes": w + pool}
        if kind == "cjoin" and len(key) > 1:
            # one chunk: Cb query rows through the net
            Cb = int(key[1])
            flops = _costs.transformer_prefill_flops(
                n_params, 1, Cb, n_layers, heads, hd, mem_len=M)
            return {"flops": flops, "bytes_accessed": w + pool,
                    "argument_bytes": w + pool}
        if kind == "pcjoin" and len(key) > 2:
            Cb = int(key[2])
            flops = _costs.transformer_prefill_flops(
                n_params, 1, Cb, n_layers, heads, hd, mem_len=M)
            return {"flops": flops, "bytes_accessed": w + pool,
                    "argument_bytes": w + pool}
        if kind in ("attach", "cow", "splice"):
            # row splices / page copies: byte traffic, ~no matmul flops
            return {"flops": 0.0, "bytes_accessed": pool,
                    "argument_bytes": pool}
        return None

    def admit_check(self, r):
        name = getattr(r, "adapter", None)
        if name is not None:
            if self._apool is None:
                raise ValueError(
                    f"request names adapter {name!r} but this engine "
                    f"carries no AdapterPool (adapters=)")
            if not self._apool.registered(name):
                raise ValueError(
                    f"adapter {name!r} is not registered with the "
                    f"pool (tenants: {self._apool.tenants()})")
        P = max(1, int(r.prompt.shape[0]))
        Pb = bucket_size(P)
        if Pb + r.max_new_tokens > self.max_len:
            raise ValueError(
                f"request needs bucket({P})={Pb} prompt slots + "
                f"{r.max_new_tokens} decode slots > pool max_len "
                f"{self.max_len}{self._max_len_detail()}")
        self.driver.check_memory(r)

    def _ensure_state(self, memory):
        if self._state is not None:
            return
        from ..text.generation import NEG

        self._neg = float(NEG)
        memory, self._mem_shape, dtype = self.driver.pin_memory(memory)
        self._np_dtype = np.dtype(str(dtype))
        self._state = self.layout.build_state(memory)
        self._pool_key = self.layout.pool_key(memory)
        self._post_state_build()

    def _post_state_build(self):
        if self.metrics.budget_bytes > 0:
            # the dense pool commits its whole footprint up front:
            # check the watermark the moment it exists
            self.metrics.check_memory_watermark(
                self.weights_bytes() + self.pool_bytes())

    # ------------------------------------------------------------------
    def _join_adapter_args(self, row):
        """The (adapter id, banks) tail a join/prefill program takes
        when the engine carries a pool (batch-1: one traced scalar
        id)."""
        if self._apool is None:
            return ()
        import jax.numpy as jnp

        return (jnp.int32(row), self._placed_banks())

    def _join(self, s, r):
        import jax.numpy as jnp

        _PT_PREFILL()
        self._ensure_state(r.memory)
        # idempotent under the retry loop: an attempt that executed
        # but blew the watchdog already pinned its adapter row —
        # release it before this attempt acquires, or the row's
        # refcount leaks one per retry
        prev = int(self._adapter_rows[s])
        if prev:
            self._adapter_rows[s] = 0
            self._release_adapter_row(prev)
        row = self._acquire_adapter(r)
        pad_id = int(r.eos_id) if r.eos_id is not None else 0
        prompt_b, P0, Pb = pad_prompt_row(r.prompt, pad_id)
        if r._trace is not None:
            _rt.on_join_attr(r, prompt_bucket=Pb)
        if self.prefill_chunk is not None and P0 > self.prefill_chunk:
            return self._chunk_begin(s, r, prompt_b, P0, Pb, row)
        fn = self._program(("join", Pb), lambda: self._build_join(Pb))
        try:
            self._state, tok0 = fn(
                self._params(), self._buffers(), self._state,
                jnp.int32(s), jnp.asarray(prompt_b),
                jnp.asarray([P0], jnp.int32),
                jnp.asarray(np.asarray(r.memory, self._np_dtype)[None]),
                *self._join_adapter_args(row))
        except Exception:
            self._release_adapter_row(row)
            raise
        self._adapter_rows[s] = row
        return tok0   # traced scalar: run_iteration resolves post-loop

    def _build_join(self, Pb):
        """Every program build is `placement.build(layout body)`: one
        source of truth for the math in layers.py, one trace_counts
        key whichever placement wraps it."""
        key = self.layout.join_key(Pb)
        return self.placement.build(key, self.layout.join_body(Pb),
                                    has_aux=True)

    # ---- chunked prefill (the cjoin/pcjoin program family) ----
    def _chunk_begin(self, s, r, prompt_b, P0, Pb, row):
        """Register the slot as mid-chunked-prefill: NO program runs
        at join time — run_iteration's _advance_chunks dispatches one
        chunk per iteration, interleaved with decode steps. The slot
        sits in `_pending` (occupied for admission, excluded from the
        decode-step active mask) until the final chunk delivers its
        token 0. `info["pos"]` is the next prompt position to write:
        it advances only AFTER a chunk dispatch succeeds, so the
        guarded retry loop re-runs the SAME chunk (the splice is
        position-idempotent)."""
        self._ensure_cross()
        self._adapter_rows[s] = row
        self._chunking[s] = {"r": r, "prompt_b": prompt_b, "P0": P0,
                             "Pb": Pb, "pos": 0}
        self._pending.add(s)
        self.metrics.record_chunked_join()
        return None   # token 0 arrives with the final chunk

    def _chunk_bucket(self, pos, P0):
        """(Cb, final?) for the chunk starting at `pos`: full
        `prefill_chunk` mid-prompt, the tail's power-of-two bucket
        (>= 2) for the final chunk. Never crosses Pb: the final
        bucket is <= prefill_chunk, which divides every prompt bucket
        this path serves (chunking requires P0 > prefill_chunk)."""
        chunk = self.prefill_chunk
        if pos + chunk < P0:
            return chunk, False
        return max(2, bucket_size(P0 - pos)), True

    def _advance_chunks(self, now):
        if not self._chunking:
            return False
        progress = False
        for s in sorted(self._chunking):
            info = self._chunking.get(s)
            r = info["r"] if info is not None else None
            if r is None or self.slots[s] is not r or \
                    r.state == "DONE":
                continue   # harvested between registration and now
            _ts0 = (time.perf_counter()
                    if _trace._SESSION is not None else 0.0)
            try:
                tok0 = self._guarded(
                    "prefill_chunk",
                    lambda s=s, info=info: self._chunk_attempt(s, info))
            except Exception as e:
                # per-request isolation, mirroring the join failure
                # path: the failed chunk kills THIS request's future
                # and frees the slot; the pool keeps serving
                self._vacate(s)
                r.slot = None
                self.metrics.record_error("prefill_chunk", e)
                r.fail(e, self.clock())
                self.metrics.record_finish("error", len(r.tokens))
                self._cbs.emit("on_finish", r)
                progress = True
                if not self._carry_alive():
                    self._fail_active(e)
                    break
                continue
            progress = True
            done = info["pos"] >= info["P0"]
            self.metrics.record_chunk()
            if r._trace is not None:
                _rt.on_chunk(r, _ts0, time.perf_counter(),
                             info["pos"], done)
            if done:
                self._chunking.pop(s, None)
                self._pending.discard(s)
                self._chunk_finalize(s, info)
                self._deliver(r, int(tok0), self.clock())
        return progress

    def _chunk_attempt(self, s, info):
        _PT_CHUNK()
        if not self._carry_alive():
            raise PoolCarryLost(
                "pool carry consumed by a failed dispatch with no "
                "replacement state — refusing to run a prefill chunk "
                "on dead buffers")
        return self._chunk_step(s, info)

    def _chunk_step(self, s, info):
        import jax.numpy as jnp

        r = info["r"]
        P0, Pb, pos = info["P0"], info["Pb"], info["pos"]
        Cb, _ = self._chunk_bucket(pos, P0)
        rows = info["prompt_b"][:, pos:pos + Cb]
        fn = self._program(("cjoin", Cb),
                           lambda: self._build_cjoin(Cb))
        self._state, tok0 = fn(
            self._params(), self._buffers(), self._cross_params(),
            self._fm_cross.buffers(), self._state, jnp.int32(s),
            jnp.asarray(rows), jnp.int32(pos),
            jnp.asarray([P0], jnp.int32), jnp.int32(Pb),
            jnp.asarray(np.asarray(r.memory, self._np_dtype)[None]),
            *self._attach_spec_rows(info["prompt_b"], Pb),
            *self._join_adapter_args(int(self._adapter_rows[s])))
        info["pos"] = pos + Cb
        return tok0

    def _chunk_finalize(self, s, info):
        """Host bookkeeping once the final chunk ran (the paged
        engine maps the tail pages into the radix trie and COWs a
        shared tail page here; the dense pool's splice already set
        the slot's write index to Pb)."""

    def _build_cjoin(self, Cb):
        return self.placement.build(
            ("cjoin", Cb), self.layout.cjoin_body(Cb), has_aux=True)

    def _attach_spec_rows(self, prompt_b, Pb):
        """Spec-pool splice rows for the attach/chunk families: the
        slot's draft history is the PROMPT (the n-gram draft proposes
        from it), padded to the pool row. () when spec is off."""
        if not self.spec_k:
            return ()
        import jax.numpy as jnp

        row = np.zeros((1, self._pool_len), np.int32)
        row[0, :Pb] = np.asarray(prompt_b[0], np.int32)
        return (jnp.asarray(row),)

    def _reset_pool(self):
        # dropped wholesale: the next join's _ensure_state rebuilds a
        # zeroed pool (all slots are empty by now); the compiled
        # join/step programs are pure and stay cached — no retrace
        self._state = None

    # ---- graceful degradation: solo eager serve ----
    def _join_fallback(self, r, exc):
        """`eager_fallback=True`: after a join fails every attempt
        (persistent compile/dispatch failure), serve the request solo
        on the eager concat-cache path — slower, but the caller gets
        its exact tokens instead of an exception."""
        if not self.eager_fallback:
            return False
        try:
            toks, n = self._run_eager(r)
        except Exception as e:
            self.metrics.record_error("eager_fallback", e)
            return False
        self.metrics.record_fallback()
        now = self.clock()
        for t in toks[:n]:
            r.tokens.append(int(t))
            self.metrics.record_token(self._tenant_of(r))
            if r.first_token_at is None:
                r.first_token_at = now
                if r.submitted_at is not None:
                    self.metrics.record_first_token(
                        now - r.submitted_at)
            self._cbs.emit("on_token", r, int(t))
            if r.stream_cb is not None:
                try:
                    r.stream_cb(r, int(t))
                except Exception as e:
                    self.metrics.record_error("stream_cb", e)
        reason = ("eos" if r.eos_id is not None and r.tokens and
                  r.tokens[-1] == r.eos_id else "length")
        self.metrics.record_finish(reason, len(r.tokens))
        r.finish(reason, now)
        self._cbs.emit("on_finish", r)
        return True

    def _run_eager(self, r):
        import jax.numpy as jnp

        from ..text.generation import generate_eager

        net = self._net
        eos = int(r.eos_id) if r.eos_id is not None else -1
        toks, lens = generate_eager(
            net.decoder, net.embed, net.project,
            jnp.asarray(np.asarray(r.memory)[None]),
            jnp.asarray(r.prompt[None]),
            jnp.asarray([r.prompt.shape[0]], jnp.int32),
            bos_id=0, eos_id=eos, max_new_tokens=r.max_new_tokens,
            pad_prompt_to=bucket_size(max(1, int(r.prompt.shape[0]))))
        n = min(int(np.asarray(lens)[0]), r.max_new_tokens)
        return np.asarray(toks)[0], n

    # ------------------------------------------------------------------
    def _decode_step(self, active):
        # plain vs speculative is the Stepper axis (layers.py): one
        # batched step, or the draft + k-token-verify pair with the
        # adaptive effective-k controller
        return self.stepper.decode(active)

    def _series_reason(self):
        if not self.stepper.ahead:
            return "spec"
        if self._chunking:
            return "chunk"
        if self._pending:
            return "pending"
        return None

    def _build_step(self, key):
        return self.placement.build(key, self.layout.step_body(key),
                                    has_aux=True)

    def _build_spec_step(self, vkey):
        return self.placement.build(
            vkey, self.layout.spec_step_body(vkey), has_aux=True)

    def _build_draft(self, dkey):
        # pure gathers over per-slot rows; under a mesh the SPMD
        # partitioner follows the operand layouts, no pinning needed —
        # every placement builds it plain
        import jax

        from .layers import named_program

        return jax.jit(named_program(dkey, self.layout.draft_body(dkey)))

    # ------------------------------------------------------------------
    # zero-warmup startup: AOT precompile + persistent cache
    # ------------------------------------------------------------------
    def precompile(self, memory=None, *, dtype="float32",
                   prompt_buckets=(8, 16, 32, 64), cache=None,
                   persist=True):
        """Ready EVERY serving program of this pool config before the
        first request: one join program per prompt bucket plus the
        batched decode step (or the spec draft/verify pair; the paged
        pool adds attach/cow). Programs come out of the persistent
        `cache` (an `AotCompileCache` or a directory path) when a
        valid entry exists — zero compiles, the warm start — and are
        AOT lower().compile()d otherwise, with the result persisted
        for the NEXT start. `memory` is the cross-attention memory: an
        example [M, D] array or its shape tuple (+ `dtype`); it pins
        the pool config exactly like the first join would, so
        admission semantics are unchanged. Returns the cold_start
        report (also recorded in `ServingMetrics.snapshot()`)."""
        self._ensure_state(self.driver.pin_memory(memory, dtype)[0])
        progs = self._startup_programs(prompt_buckets)
        return self._precompile_run(progs, cache, persist)

    def _program_fingerprint(self):
        from ..tuning.aot_cache import model_fingerprint

        return (f"{type(self).__name__}|"
                f"{model_fingerprint(self._fm.params(), self._fm.buffers())}|"
                f"{self._pool_key}")

    def _startup_adapter_args(self):
        """Step-shaped (ids [S], banks) example args for precompile —
        placement-mirrored like every other example arg."""
        if self._apool is None:
            return ()
        import jax.numpy as jnp

        return (jnp.zeros((self.num_slots,), jnp.int32),
                self._placed_banks())

    def _startup_programs(self, prompt_buckets):
        import jax.numpy as jnp

        S = self.num_slots
        params, buffers, state = self._params(), self._buffers(), \
            self._state
        M, Dm = self._mem_shape
        mem1 = jnp.zeros((1, M, Dm), jnp.dtype(self._np_dtype))
        one = jnp.asarray([1], jnp.int32)
        active = jnp.zeros((S,), bool)
        jad = self._join_adapter_args(0)
        sad = self._startup_adapter_args()
        progs = []
        for Pb in sorted({bucket_size(int(p)) for p in prompt_buckets}):
            progs.append((
                ("join", Pb), lambda Pb=Pb: self._build_join(Pb),
                (params, buffers, state, jnp.int32(0),
                 jnp.zeros((1, Pb), jnp.int32), one, mem1) + jad))
        if self.spec_k:
            dkey = ("draft",) + self._pool_key
            progs.append((
                dkey, lambda dkey=dkey: self._build_draft(dkey),
                (state["hist"], state["tok"], state["plen"],
                 state["pbk"], state["inc"][0].index)))
            vkey = ("sstep",) + self._pool_key
            progs.append((
                vkey, lambda vkey=vkey: self._build_spec_step(vkey),
                (params, buffers, state) + sad +
                (jnp.zeros((S, self.spec_k - 1), jnp.int32), active,
                 active, jnp.int32(self.spec_k))))
        else:
            skey = ("step",) + self._pool_key
            progs.append((
                skey, lambda skey=skey: self._build_step(skey),
                (params, buffers, state) + sad + (active,)))
        if self.prefill_chunk:
            # bucket-length prompts chunk in full-size chunks only
            # (prefill_chunk divides every bucket it splits), so ONE
            # cjoin program covers the precompile surface; ragged
            # final chunks compile their smaller bucket on demand
            self._ensure_cross()
            spec_rows = ((jnp.zeros((1, self._pool_len), jnp.int32),)
                         if self.spec_k else ())
            Cb = self.prefill_chunk
            progs.append((
                ("cjoin", Cb), lambda Cb=Cb: self._build_cjoin(Cb),
                (params, buffers, self._cross_params(),
                 self._fm_cross.buffers(), state, jnp.int32(0),
                 jnp.zeros((1, Cb), jnp.int32), jnp.int32(0), one,
                 jnp.int32(2 * Cb), mem1) + spec_rows + jad))
        return progs


def _make_cross_kv_fm(decoder):
    """Functionalized 'memory -> per-layer cross-attention StaticCache'
    net: the prefix-hit attach path needs the joiner's OWN cross-attn
    K/V (memory is per-request) but must not run any self-attention
    prefill — this is the only model compute a shared-prefix join
    performs."""
    from ..nn.layer.layers import Layer
    from ..nn.layer.transformer import MultiHeadAttention as MHA
    from ..parallel.functional import functionalize

    class _CrossKV(Layer):
        def __init__(self, dec):
            super().__init__()
            self.dec = dec

        def forward(self, memory):
            return [layer.cross_attn.gen_cache(
                memory, type=MHA.StaticCache)
                for layer in self.dec.layers]

    return functionalize(_CrossKV(decoder))


class PagedServingEngine(ServingEngine):
    """The serving pool over PAGED KV storage: `ServingEngine(...,
    paged=True)`. Device K/V lives in a global pool of fixed-size pages
    ([num_pages + 1, page_size, H * D] per layer — static shape, one
    compile per pool config); each slot maps its logical positions
    through a host-owned int32 page table shipped to the device as a
    traced input every step, so page mapping, joins, and evictions
    never retrace:

      * slot join allocates only the pages the PROMPT bucket needs;
        decode pages are mapped on demand as the write position crosses
        page boundaries, so pool occupancy is bounded by actual tokens,
        not worst-case max_len — `num_pages` can be far below
        `num_slots * max_pages` (oversubscription);
      * a prompt already in the prefix cache joins with ZERO prefill
        FLOPs: the shared pages are mapped read-only (refcounted) and
        only the page the joiner will decode-write into is copied
        (copy-on-write), so co-resident requests sharing a prefix stay
        bit-isolated;
      * admission runs on free-page headroom (prompt pages + a decode
        reservation) — insufficient pages DEFER the queue head
        (OutOfPages backpressure, `metrics.page_waits`) instead of
        failing it; if oversubscription still runs dry mid-decode, the
        starved slot is evicted with partials + an `OutOfPages` cause
        (`metrics.oom_evictions`) and the pool keeps serving;
      * pages store fp32 (default: bit-identical to the dense pool's
        decode), bf16, or int8 + per-(page, head) scales behind
        `kv_dtype=`, dequantized at read time (in-kernel on TPU).

    Numerics contract: with `kv_dtype=None` (compute dtype) every
    request's tokens bit-match both the dense `ServingEngine` and a
    solo `generate_eager` run, provided `max_len` is a page multiple
    (it is rounded up to one — a non-multiple would change the masked
    softmax width)."""

    def __init__(self, decoder, embed=None, project=None, *, num_slots=8,
                 max_len=128, page_size=16, num_pages=None,
                 kv_dtype=None, prefix_cache=None, prefix_capacity=64,
                 radix_mid_page="round_down",
                 reserve_decode_frac=1.0, paged=True, **kw):
        page_size = int(page_size)
        max_len = pages_for(max_len, page_size) * page_size
        super().__init__(decoder, embed, project, num_slots=num_slots,
                         max_len=max_len, **kw)
        self.page_size = page_size
        if self.prefill_chunk is not None and \
                self.prefill_chunk % page_size:
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must be a "
                f"multiple of page_size={page_size}: chunk frontiers "
                f"must be page-aligned so every finished chunk is a "
                f"radix-trie-insertable run of full pages")
        # a speculative pool writes up to spec_k tokens past a row's
        # admitted budget before rolling back — round the logical pool
        # length (and the table width) up to page-cover that overhang;
        # admission still enforces the max_len contract
        self._pool_len = pages_for(self.max_len + (self.spec_k or 0),
                                   page_size) * page_size
        self.max_pages = self._pool_len // page_size
        self.num_pages = (int(num_pages) if num_pages is not None
                          else self.num_slots * self.max_pages)
        self.kv_dtype = kv_dtype
        # None, the default, means "on where the stack allows it": a
        # stack that keeps a scan state or a ring is served with the
        # prefix cache off, and raises for what would park such a state
        self.prefix_cache = prefix_cache = \
            self.driver.settle_page_options(prefix_cache, kv_dtype)
        self.reserve_decode_frac = float(reserve_decode_frac)
        self._alloc = PageAllocator(self.num_pages, page_size)
        self._prefix = (RadixPrefixCache(self._alloc, prefix_capacity,
                                         page_size=page_size,
                                         mid_page=radix_mid_page)
                        if prefix_cache else None)
        self._partial_ok = None   # resolved lazily (needs jnp)
        self._trie_counted = dict.fromkeys(
            ("digests", "evictions", "evict_visits"), 0)
        if self._prefix is not None and self._apool is not None:
            # eager tenant invalidation: an adapter re-register drops
            # the stale subtree immediately (the generation key would
            # also catch it lazily on next touch)
            import weakref

            wr = weakref.ref(self)

            def _drop(name, gen):
                e = wr()
                if e is not None and e._prefix is not None:
                    e._prefix.drop_tenant(name)

            self._apool.on_invalidate(_drop)
        self._table = np.full((self.num_slots, self.max_pages), -1,
                              np.int32)
        self._index = np.zeros(self.num_slots, np.int32)
        # total pages each occupied slot will have mapped by the time
        # its request hits max_new_tokens — admission subtracts the
        # not-yet-mapped remainder from the free-page headroom so
        # reserve_decode_frac=1.0 is a no-OOM guarantee
        self._slot_pages_total = np.zeros(self.num_slots, np.int64)
        self._page_bytes = None
        self._pages_per_block = 1    # set with the pool (grid step's pages)
        self._pool_total_bytes = None  # ledger cache (watermark path)
        self._prefix_params = None   # param identity the cache is
        #                              valid for (see _check_params)
        self.prefill_count = 0   # real prefills run (prefix hits skip)

    # ------------------------------------------------------------------
    def _max_len_detail(self):
        return (f" (= {self.max_pages} pages x {self.page_size} "
                f"tokens, paged)")

    # ---- the live HBM ledger (paged) ----
    def table_bytes(self):
        """The int32 page table shipped to the device every step."""
        return self.num_slots * self.max_pages * 4

    def pool_bytes(self):
        b = _tree_bytes(self._state)
        return b + self.table_bytes() if b else 0

    def pool_in_use_bytes(self):
        """Committed pool minus the UNMAPPED pages: the paged pool's
        whole point is that live bytes track actual tokens, not
        worst-case max_len — this is the number the budget watermark
        and oversubscription monitoring care about."""
        total = self.pool_bytes()
        if not total or not self._page_bytes:
            return total
        return total - self._alloc.pages_free * self._page_bytes


    def _spec_overhang(self):
        """Cache positions a speculative verify may write past a row's
        accepted budget before the rollback (the force-rejected tail):
        admission and the per-slot page reservations must cover them."""
        return (self.spec_k - 1) if self.spec_k else 0

    def admit_check(self, r):
        super().admit_check(r)
        # liveness: a request the whole (empty) pool could never hold
        # must fail fast, not defer at the backpressure gate forever
        P = max(1, int(r.prompt.shape[0]))
        need = pages_for(bucket_size(P) + r.max_new_tokens +
                         self._spec_overhang(), self.page_size)
        if need > self.num_pages:
            raise ValueError(
                f"request needs {need} pages > pool num_pages "
                f"{self.num_pages} ({self.page_size}-token pages)")

    def _post_state_build(self):
        import jax.numpy as jnp

        from .paging import resolve_kv_dtype

        storage, quantized = resolve_kv_dtype(
            self.kv_dtype, jnp.dtype(self._np_dtype))
        self._page_bytes = self.driver.page_row_bytes(storage, quantized)
        self._pages_per_block = self._decode_block_pages(storage)
        self._pool_total_bytes = self.pool_bytes()
        self.metrics.set_cache_bytes({
            kind: _tree_bytes(self._state.get(kind))
            for kind in ("paged", "latent", "ring", "recurrent",
                         "static")})
        if self.driver.counts:
            self.metrics.set_expert_counters(self.driver.counts)
        if self.metrics.budget_bytes > 0:
            self.metrics.check_memory_watermark(
                self.weights_bytes() + self.pool_in_use_bytes())

    def _decode_block_pages(self, storage):
        """Pages a grid step of the decode call takes over this pool
        (1 where it takes the gather)."""
        return self.driver.pages_per_block(storage)

    def _take_counts(self, counts):
        """An expert stack's counters (`driver.counts`' order), read
        with the tokens of the step or join that counted them: added to
        this iteration's."""
        for name, v in zip(self.driver.counts, counts):
            self._experts_iter[name] = self._experts_iter.get(name, 0) \
                + int(v)

    def _count_cache(self, **counts):
        """Add to this iteration's cache counts (state_resets,
        prefill_tokens, ring_wraps)."""
        for k, v in counts.items():
            if v:
                self._cache_iter[k] = self._cache_iter.get(k, 0) + int(v)

    # ---- host page bookkeeping ----
    def _alloc_pages(self, n):
        """Allocate n pages, reclaiming LRU prefix-cache entries under
        pressure first."""
        if self._alloc.pages_free < n and self._prefix is not None:
            self._prefix.reclaim(n)
        return self._alloc.alloc(n)

    def _release_slot(self, s):
        mapped = [int(p) for p in self._table[s] if p >= 0]
        if mapped:
            self._alloc.decref(mapped)
        self._table[s] = -1
        self._index[s] = 0
        self._slot_pages_total[s] = 0

    def _evict(self, s):
        super()._evict(s)          # adapter row release
        self._release_slot(s)

    def _device_table(self):
        import jax.numpy as jnp

        # unmapped entries point at the trash row (num_pages): inactive
        # slots' masked decode writes can never land on live pages
        return jnp.asarray(np.where(self._table < 0, self.num_pages,
                                    self._table).astype(np.int32))

    def flush_prefix_cache(self):
        """Drop every prefix-cache entry (releases the cache's page
        references; pages still mapped by live slots survive via their
        own refs). After a full drain this returns the allocator to
        all-free — the chaos leak check pivots on it."""
        if self._prefix is not None:
            self._prefix.flush()

    def _reset_pool(self):
        # a decode-step failure evicted every slot (pages returned);
        # the device pages are rebuilt zeroed on the next join, so the
        # prefix cache's pages would hold garbage — flush it
        self.flush_prefix_cache()
        self._table[:] = -1
        self._index[:] = 0
        self._state = None
        self._pool_total_bytes = None

    # ---- admission: free-page headroom ----
    def _pages_needed(self, r):
        P0 = max(1, int(r.prompt.shape[0]))
        Pb = bucket_size(P0)
        n_pp = pages_for(Pb, self.page_size)
        need_prompt = n_pp
        if self._prefix is not None:
            pad_id = int(r.eos_id) if r.eos_id is not None else 0
            row, P0, Pb = pad_prompt_row(r.prompt, pad_id)
            res = self._prefix.peek(
                row[0, :P0], P0, Pb, self._mem_scope(r),
                self._tenant_key(r),
                allow_partial=self._radix_partial_ok())
            if res is not None and res[0] == "whole":
                # shared pages are free; only a COW of the partial
                # tail page (when the bucket ends mid-page) is new
                need_prompt = 1 if Pb % self.page_size else 0
            elif res is not None:
                # matched prefix pages are free; the joiner allocates
                # the rest (COW page included) + a possible tail COW
                m = len(res[1]["pages"])
                need_prompt = (n_pp - m) + \
                    (1 if Pb % self.page_size else 0)
        total = pages_for(Pb + r.max_new_tokens +
                          self._spec_overhang(), self.page_size)
        reserve = int(np.ceil(
            self.reserve_decode_frac * (total - n_pp)))
        return need_prompt + reserve

    def _outstanding_reservations(self):
        """Pages already-admitted slots will still map before they
        finish (scaled by the reservation fraction): subtracted from
        the free headroom so admission never promises the same page
        twice."""
        out = 0
        for s, r in enumerate(self.slots):
            if r is None:
                continue
            mapped = int((self._table[s] >= 0).sum())
            remain = max(0, int(self._slot_pages_total[s]) - mapped)
            out += int(np.ceil(self.reserve_decode_frac * remain))
        return out

    def _admission_gate(self, r):
        if not self._adapter_gate(r):
            return False
        need = self._pages_needed(r) + self._outstanding_reservations()
        if self._alloc.pages_free < need and self._prefix is not None:
            self._prefix.reclaim(need)
        if self._alloc.pages_free >= need:
            return True
        self.metrics.record_page_wait()
        return False

    def _iteration_gauges(self):
        gauges = dict(super()._iteration_gauges() or {})
        gauges.update({"pages_in_use": self._alloc.pages_in_use,
                       "pages_free": self._alloc.pages_free})
        if self._cache_iter:
            gauges["cache"], self._cache_iter = self._cache_iter, {}
        if self._experts_iter:
            gauges["experts"], self._experts_iter = self._experts_iter, {}
        if self._prefix is not None:
            st = self._prefix.stats()
            # the trie's counters are its lifetime's: hand the metrics
            # what this iteration added, as for the cache counts
            counts = {k: st[k] - self._trie_counted[k]
                      for k in self._trie_counted}
            self._trie_counted = {k: st[k] for k in counts}
            gauges.update({"trie_nodes": st["nodes"],
                           "trie_pages": st["pages"],
                           "trie_counts": counts})
        written = [int(self._index[s])
                   for s, r in enumerate(self.slots) if r is not None]
        active_toks = sum(written)
        # the written pages a paged decode call reads, and the grid
        # steps (blocks of `pages_per_block` pages) that read them
        live = [pages_for(n, self.page_size) for n in written]
        gauges.update({
            "live_pages": sum(live),
            "live_blocks": sum(-(-n // self._pages_per_block)
                               for n in live),
            "pages_per_block": self._pages_per_block,
            "table_entries": self.num_slots * self.max_pages})
        if active_toks and self._page_bytes:
            gauges["bytes_per_active_token"] = \
                self._alloc.pages_in_use * self._page_bytes \
                / active_toks
        # budget watermark: live bytes grow page by page, so the
        # crossing fires while free headroom still exists — BEFORE the
        # OutOfPages backpressure/eviction paths. Costs two int ops per
        # iteration, and only when a budget was configured.
        if self.metrics.budget_bytes > 0 and \
                self._pool_total_bytes is not None:
            in_use = self.weights_bytes() + self._pool_total_bytes \
                - self._alloc.pages_free * (self._page_bytes or 0)
            self.metrics.check_memory_watermark(in_use)
        return gauges

    # ---- join: prefill into pages, or attach shared prefix pages ----
    def _mem_scope(self, r):
        """The radix trie's memory scope for `r`: its memory's digest,
        taken ONCE a request at the first need (the admission gate's
        peek) and carried on the Request for lookup, insert and a
        preemption's re-insert. Never cached by array identity across
        requests: a client sends a fresh memory with each one."""
        if r._mem_scope is None:
            r._mem_scope = self._prefix.mem_digest(r.memory)
        return r._mem_scope

    def _tenant_key(self, r):
        """The radix trie's tenant scope. The prompt K/V depend on the
        adapter that prefilled them (LoRA on the K/V projections from
        token 0), so adapter traffic gets its own subtree keyed by
        (adapter name, registration GENERATION — never the recyclable
        bank row), while adapter-LESS requests share ONE base subtree
        across every logical tenant: base-model preambles are the only
        pages that are safely identical across tenants."""
        name = getattr(r, "adapter", None)
        if name is None or self._apool is None:
            return None
        return (name, self._apool.generation(name))

    def _radix_partial_ok(self):
        """Partial (tail-prefill) reuse is admitted only when pages
        store the COMPUTE dtype: the pattach tail attends to the seed
        K/V as STORED, while a cold prefill attends to full-precision
        K/V before quantization — under int8/bf16 storage the two
        diverge, so quantized pools keep whole-prompt reuse only
        (whole hits replay the same decode-read path either way)."""
        if self._partial_ok is None:
            import jax.numpy as jnp

            from .paging import resolve_kv_dtype

            storage, quantized = resolve_kv_dtype(
                self.kv_dtype, jnp.dtype(self._np_dtype))
            self._partial_ok = (not quantized and
                                storage == jnp.dtype(self._np_dtype))
        return self._partial_ok

    def _check_params(self):
        """Prefix-cache entries hold MODEL-DERIVED state (prompt K/V
        pages + the first greedy token), so a weight update makes them
        stale — unlike the compiled programs, which take params as
        arguments every call. Rebinding any `p._data` replaces the leaf
        array object, so an identity sweep over the param pytree (a few
        hundred `is` checks, no device work) detects the update and
        flushes the cache; holding the previous dict's array references
        makes the identity check sound (no id recycling)."""
        cur = self._fm.params()
        prev = self._prefix_params
        if prev is not None and len(prev) == len(cur) and \
                all(cur[k] is prev.get(k) for k in cur):
            return
        if prev is not None:
            self.flush_prefix_cache()
        self._prefix_params = cur

    def _join(self, s, r):
        self._ensure_state(r.memory)
        if self._prefix is not None:
            self._check_params()
        # idempotent under the retry loop: a half-joined earlier
        # attempt's pages are released before this one allocates, and
        # its pinned adapter row is released before this one acquires
        # (or the row's refcount leaks one per watchdog retry)
        self._release_slot(s)
        prev = int(self._adapter_rows[s])
        if prev:
            self._adapter_rows[s] = 0
            self._release_adapter_row(prev)
        row = self._acquire_adapter(r)
        try:
            tok0 = self._join_inner(s, r, row)
        except Exception:
            self._release_adapter_row(row)
            raise
        self._adapter_rows[s] = row
        return tok0

    def _join_inner(self, s, r, row):
        pad_id = int(r.eos_id) if r.eos_id is not None else 0
        prompt_b, P0, Pb = pad_prompt_row(r.prompt, pad_id)
        self._slot_pages_total[s] = pages_for(
            Pb + r.max_new_tokens + self._spec_overhang(),
            self.page_size)
        res = None
        if self._prefix is not None:
            res = self._prefix.lookup(
                prompt_b[0, :P0], P0, Pb, self._mem_scope(r),
                self._tenant_key(r),
                allow_partial=self._radix_partial_ok())
            kind = res[0] if res is not None else "miss"
            matched = (P0 if kind == "whole"
                       else res[1]["seed_len"] if kind == "partial"
                       else 0)
            self.metrics.record_prefix(kind, matched_tokens=matched,
                                       prompt_tokens=P0)
        if r._trace is not None:
            _rt.on_join_attr(r, prompt_bucket=Pb,
                             prefix_hit=res is not None and
                             res[0] == "whole")
            if self._prefix is not None:
                psz = self.page_size
                _rt.on_prefix_match(
                    r, kind,
                    matched_pages=pages_for(matched, psz) if matched
                    else 0,
                    matched_tokens=matched)
        if res is not None and res[0] == "whole":
            return self._attach_shared(s, r, res[1], prompt_b, P0, Pb)
        chunk = self.prefill_chunk
        if res is not None:
            match = res[1]
            if chunk is not None and \
                    P0 - len(match["pages"]) * self.page_size > chunk:
                # long divergent tail: resume from the matched FULL
                # pages only (round-down — the mid-page j tokens
                # re-prefill inside the first chunk, trading a few
                # tokens of reuse for a page-aligned chunk frontier)
                # and chunk the rest instead of one huge pattach
                return self._chunk_begin(s, r, prompt_b, P0, Pb, row,
                                         matched=match["pages"])
            return self._pattach_join(s, r, match, prompt_b, P0, Pb,
                                      row)
        if chunk is not None and P0 > chunk:
            return self._chunk_begin(s, r, prompt_b, P0, Pb, row)
        return self._prefill_join(s, r, prompt_b, P0, Pb, row)

    def _prefill_join(self, s, r, prompt_b, P0, Pb, row=0):
        import jax.numpy as jnp

        _PT_PREFILL()
        n_pp = pages_for(Pb, self.page_size)
        pages = self._alloc_pages(n_pp)
        fn = self._program(("pjoin", Pb),
                           lambda: self._build_paged_join(Pb))
        try:
            self._state, tok0 = fn(
                self._params(), self._buffers(), self._state,
                jnp.int32(s), jnp.asarray(prompt_b),
                jnp.asarray([P0], jnp.int32), self.driver.join_memory(r),
                jnp.asarray(np.asarray(pages, np.int32)),
                *self._join_adapter_args(row))
        except Exception:
            self._alloc.decref(pages)
            raise
        self._table[s, :n_pp] = pages
        self._index[s] = self.driver.start_index(P0, Pb)
        self.prefill_count += 1
        self._count_cache(state_resets=1, prefill_tokens=P0)
        if r._trace is not None:
            _rt.on_join_attr(r, prefill_tokens=P0)
        # tok0 stays the traced scalar: the trie stores it raw and
        # resolves lazily at the first whole hit; the caller's
        # delivery resolves after the admission round's last dispatch
        if self._prefix is not None:
            self._prefix.insert(prompt_b[0, :P0], P0, Pb,
                                self._mem_scope(r), self._tenant_key(r),
                                pages, tok0)
        self._cow_tail(s, Pb)
        return tok0

    def _pattach_join(self, s, r, match, prompt_b, P0, Pb, row=0):
        """Radix PARTIAL hit: map the matched prefix pages read-only,
        COW the mid-page divergence point (when the match ends inside
        a page), and prefill ONLY the divergent tail through the
        bucketed `pattach` program — prefill FLOPs scale with the
        MISSED tokens, not the prompt. The extended prompt is inserted
        back into the trie, so a conversation tree deepens the shared
        prefix one branch at a time."""
        import jax.numpy as jnp

        _PT_PATTACH()
        psz = self.page_size
        matched = [int(p) for p in match["pages"]]
        m = len(matched)
        j = int(match["j"])
        seed_len = m * psz + j
        n_pp = pages_for(Pb, psz)
        self._ensure_cross()
        self._alloc.incref(matched)
        owned = []       # pages THIS join allocated (released on fail)
        try:
            if j:
                dst = self._alloc_pages(1)[0]
                owned.append(dst)
                fn = self._program(("cow",), self._build_cow)
                self._state = fn(self._state,
                                 jnp.int32(int(match["cow_src"])),
                                 jnp.int32(dst))
                self.metrics.record_cow_copy()
                head = matched + [dst]
            else:
                head = list(matched)
            fresh = self._alloc_pages(n_pp - len(head)) \
                if n_pp > len(head) else []
            owned.extend(fresh)
            full_pages = head + fresh
            n_tail = P0 - seed_len
            Tb = max(2, bucket_size(n_tail))   # >= 2: the tail block
            #                      must take the verify path, not the
            #                      single-token decode path
            Mb = bucket_size(m + (1 if j else 0), minimum=1)
            W = min(self.max_pages, Mb + pages_for(Tb, psz))
            key = ("pattach", Mb, Tb)
            fn = self._program(key,
                               lambda: self._build_pattach(Mb, Tb))
            trow = np.full((1, W), self.num_pages, np.int32)
            k = min(W, n_pp)
            trow[0, :k] = full_pages[:k]
            tail = np.full((1, Tb),
                           int(r.eos_id) if r.eos_id is not None else 0,
                           np.int32)
            tail[0, :n_tail] = np.asarray(prompt_b[0, seed_len:P0],
                                          np.int32)
            self._state, tok0 = fn(
                self._params(), self._buffers(), self._cross_params(),
                self._fm_cross.buffers(), self._state, jnp.int32(s),
                jnp.asarray(trow), jnp.asarray(tail),
                jnp.int32(seed_len), jnp.asarray([P0], jnp.int32),
                jnp.int32(Pb),
                jnp.asarray(np.asarray(r.memory, self._np_dtype)[None]),
                *self._attach_spec_rows(prompt_b, Pb),
                *self._join_adapter_args(row))
        except Exception:
            if owned:
                self._alloc.decref(owned)
            self._alloc.decref(matched)
            raise
        self._table[s, :n_pp] = full_pages
        self._index[s] = Pb
        # insert BEFORE the tail COW so the trie adopts the slot's
        # pages while they are still the canonical prompt pages — the
        # COW then sees the shared refcount and gives the slot its
        # private decode page (same ordering as the cold prefill path)
        self._prefix.insert(prompt_b[0, :P0], P0, Pb, self._mem_scope(r),
                            self._tenant_key(r), full_pages, tok0)
        self._cow_tail(s, Pb)
        return tok0

    def _attach_shared(self, s, r, hit, prompt_b, P0, Pb):
        """Prefix-cache hit: map the shared prompt pages read-only and
        splice only the per-request rows (bias hole, memory, cross-attn
        K/V, cached first token, and the spec history mirror) — ZERO
        self-attention prefill FLOPs for the shared pages. One compiled
        program for every bucket (the bucket boundary rides in as a
        traced scalar; the history row is pre-padded to pool length)."""
        import jax.numpy as jnp

        pages = hit["pages"]
        self._alloc.incref(pages)
        self._ensure_cross()
        fn = self._program(("attach",), self._build_attach)
        try:
            self._state = fn(
                self._cross_params(), self._fm_cross.buffers(),
                self._state, jnp.int32(s), jnp.int32(hit["tok0"]),
                jnp.asarray([P0], jnp.int32), jnp.int32(Pb),
                jnp.asarray(np.asarray(r.memory, self._np_dtype)[None]),
                *self._attach_spec_rows(prompt_b, Pb))
        except Exception:
            self._alloc.decref(pages)
            raise
        self._table[s, :len(pages)] = pages
        self._index[s] = Pb
        self._cow_tail(s, Pb)
        return int(hit["tok0"])

    def _cow_tail(self, s, Pb):
        """Copy-on-write: when the bucket boundary falls mid-page, the
        first decode write lands inside the last prompt page — if that
        page is shared (prefix cache / co-resident holder), give this
        slot a private copy first so the shared original stays
        immutable."""
        import jax.numpy as jnp

        if Pb % self.page_size == 0:
            return
        pi = Pb // self.page_size
        src = int(self._table[s, pi])
        if src < 0 or self._alloc.refcount[src] <= 1:
            return
        dst = self._alloc_pages(1)[0]
        fn = self._program(("cow",), self._build_cow)
        try:
            self._state = fn(self._state, jnp.int32(src),
                             jnp.int32(dst))
        except Exception:
            self._alloc.decref([dst])
            raise
        self._alloc.decref([src])
        self._table[s, pi] = dst
        self.metrics.record_cow_copy()

    # ---- chunked prefill over pages (the pcjoin program) ----
    def _chunk_begin(self, s, r, prompt_b, P0, Pb, row, matched=()):
        """Paged chunk registration: matched full prefix pages (a
        radix partial hit rounded DOWN to the page boundary) map
        read-only up front and seed the chunk frontier; the chunks
        prefill only the divergent tail, page by page. The host index
        tracks the frontier mid-prompt — safe because pending slots
        are excluded from both the decode active mask and the
        on-demand page mapper, and the steps' masked garbage writes
        land at/past the frontier, where the next chunk (or the
        slot's own first decode write) overwrites them before any
        read."""
        self._ensure_cross()
        matched = [int(p) for p in matched]
        if matched:
            self._alloc.incref(matched)
            self._table[s, :len(matched)] = matched
        pos = len(matched) * self.page_size
        self._index[s] = pos
        self._adapter_rows[s] = row
        self._chunking[s] = {"r": r, "prompt_b": prompt_b, "P0": P0,
                             "Pb": Pb, "pos": pos}
        self._pending.add(s)
        self.metrics.record_chunked_join()
        return None   # token 0 arrives with the final chunk

    def _chunk_step(self, s, info):
        import jax.numpy as jnp

        r = info["r"]
        P0, Pb, pos = info["P0"], info["Pb"], info["pos"]
        psz = self.page_size
        Cb, final = self._chunk_bucket(pos, P0)
        end = pos + Cb
        n_have = pos // psz       # chunk frontiers are page-aligned
        n_need = pages_for(end, psz) - n_have
        fresh = self._alloc_pages(n_need) if n_need > 0 else []
        Mb = bucket_size(n_have, minimum=1)
        W = min(self.max_pages, Mb + pages_for(Cb, psz))
        trow = np.full((1, W), self.num_pages, np.int32)
        pages_now = [int(p) for p in self._table[s, :n_have]] + fresh
        k = min(W, len(pages_now))
        trow[0, :k] = pages_now[:k]
        rows = info["prompt_b"][:, pos:end]
        fn = self._program(("pcjoin", Mb, Cb),
                           lambda: self._build_pcjoin(Mb, Cb))
        try:
            self._state, tok0 = fn(
                self._params(), self._buffers(), self._cross_params(),
                self._fm_cross.buffers(), self._state, jnp.int32(s),
                jnp.asarray(trow), jnp.asarray(rows), jnp.int32(pos),
                jnp.asarray([P0], jnp.int32), jnp.int32(Pb),
                jnp.asarray(np.asarray(r.memory, self._np_dtype)[None]),
                *self._attach_spec_rows(info["prompt_b"], Pb),
                *self._join_adapter_args(int(self._adapter_rows[s])))
        except Exception:
            if fresh:
                self._alloc.decref(fresh)
            raise
        if fresh:
            self._table[s, n_have:n_have + len(fresh)] = fresh
        # mid-chunk the frontier sits mid-PROMPT; the final chunk
        # graduates the index to Pb so decode starts past the hole
        self._index[s] = Pb if final else end
        info["pos"] = end
        if final:
            info["tok0"] = tok0
        elif self._prefix is not None:
            # the PR-16 follow-up: every finished chunk extends the
            # request's radix-trie prefix by its full pages, so the
            # work survives a later slot failure (and co-arrivals
            # partial-match the growing prefix immediately)
            self._prefix.insert_prefix(
                info["prompt_b"][0, :end], self._mem_scope(r),
                self._tenant_key(r),
                [int(p) for p in self._table[s, :end // psz]])
        return tok0

    def _chunk_finalize(self, s, info):
        r, P0, Pb = info["r"], info["P0"], info["Pb"]
        self.prefill_count += 1
        if self._prefix is not None:
            pages = [int(p) for p in self._table[s] if p >= 0]
            self._prefix.insert(
                info["prompt_b"][0, :P0], P0, Pb, self._mem_scope(r),
                self._tenant_key(r), pages, info["tok0"])
        self._cow_tail(s, Pb)

    def _build_pcjoin(self, Mb, Cb):
        return self.placement.build(
            ("pcjoin", Mb, Cb), self.layout.pcjoin_body(Mb, Cb),
            has_aux=True)

    # ---- fairness-aware preemption: evict to the prefix cache ----
    def can_preempt(self, s):
        """Mechanics-only eligibility (class policy lives in the
        shaper): a RUNNING slot, not mid-chunk, with token 0 already
        out — its prompt K/V pages are complete, which is what the
        evict-to-trie resume contract parks — on a pool that HAS a
        prefix cache to park them in."""
        r = self.slots[s]
        return (self._prefix is not None and r is not None and
                s not in self._pending and r.state == "RUNNING" and
                len(r.tokens) >= 1)

    def preempt_slot(self, s, now):
        if not self.can_preempt(s):
            return None
        r = self.slots[s]
        _PT_PREEMPT()   # host-side, BEFORE any mutation: an injected
        #                 fault aborts the preemption with the slot,
        #                 its pages, and the queue all untouched
        pad_id = int(r.eos_id) if r.eos_id is not None else 0
        prompt_b, P0, Pb = pad_prompt_row(r.prompt, pad_id)
        pages = []
        for p in self._table[s, :pages_for(Pb, self.page_size)]:
            if p < 0:
                break
            pages.append(int(p))
        # park the prompt K/V in the radix trie (an existing terminal
        # just refreshes its tick; a new one increfs the pages), THEN
        # release the slot: the pages survive via the trie's refs and
        # the resume join rides a zero-FLOP whole-prefix attach
        self._prefix.insert(prompt_b[0, :P0], P0, Pb, self._mem_scope(r),
                            self._tenant_key(r), pages,
                            int(r.tokens[0]))
        if r._trace is not None:
            _rt.on_preempt(r, s, len(r.tokens))
        self._vacate(s)
        r.slot = None
        r.state = "QUEUED"
        # greedy decode is deterministic, so the resumed slot re-emits
        # the tokens the caller already holds bit-identically;
        # _deliver absorbs exactly this many silently
        r._replay = len(r.tokens)
        r._preemptions += 1
        self.metrics.record_preemption()
        return r

    # ---- compiled programs (bodies live in layers.PagedLayout) ----
    def _build_paged_join(self, Pb):
        return self.placement.build(("pjoin", Pb),
                                    self.layout.join_body(Pb),
                                    has_aux=True)

    def _build_pattach(self, Mb, Tb):
        return self.placement.build(("pattach", Mb, Tb),
                                    self.layout.pattach_body(Mb, Tb),
                                    has_aux=True)

    def _build_attach(self):
        return self.placement.build(("attach",),
                                    self.layout.attach_body(),
                                    has_aux=False)

    def _build_cow(self):
        return self.placement.build(("cow",), self.layout.cow_body(),
                                    has_aux=False)

    def _build_paged_step(self, ck):
        return self._build_step(ck)

    # ---- decode: on-demand page mapping + one batched step; the page
    # mapping and index advance are the PagedLayout host hooks the
    # steppers drive (layers.py) ----
    def _evict_oom(self, s, exc, now):
        r = self.slots[s]
        self._vacate(s)
        self.metrics.record_oom_eviction()
        self.metrics.record_error("out_of_pages", exc)
        self.metrics.record_finish("error", len(r.tokens))
        r.finish("error", now, error=exc)
        self._cbs.emit("on_finish", r)

    # ---- zero-warmup startup (paged program set) ----
    def _startup_programs(self, prompt_buckets):
        import jax.numpy as jnp

        S = self.num_slots
        params, buffers, state = self._params(), self._buffers(), \
            self._state
        mem1 = self.driver.warm_memory()
        one = jnp.asarray([1], jnp.int32)
        active = jnp.zeros((S,), bool)
        table0 = jnp.zeros((S, self.max_pages), jnp.int32)
        index0 = jnp.zeros((S,), jnp.int32)
        jad = self._join_adapter_args(0)
        sad = self._startup_adapter_args()
        progs = []
        for Pb in sorted({bucket_size(int(p)) for p in prompt_buckets}):
            n_pp = pages_for(Pb, self.page_size)
            progs.append((
                ("pjoin", Pb),
                lambda Pb=Pb: self._build_paged_join(Pb),
                (params, buffers, state, jnp.int32(0),
                 jnp.zeros((1, Pb), jnp.int32), one, mem1,
                 jnp.zeros((n_pp,), jnp.int32)) + jad))
        if self._prefix is not None:
            self._ensure_cross()
            spec_rows = ((jnp.zeros((1, self._pool_len), jnp.int32),)
                         if self.spec_k else ())
            progs.append((
                ("attach",), self._build_attach,
                (self._cross_params(), self._fm_cross.buffers(), state,
                 jnp.int32(0), jnp.int32(0), one, jnp.int32(1), mem1)
                + spec_rows))
            progs.append((
                ("cow",), self._build_cow,
                (state, jnp.int32(0), jnp.int32(0))))
            if self._radix_partial_ok():
                # partial-attach pairs the radix cache will hit first:
                # a last-page divergence per admitted prompt bucket
                # (matched = all-but-one page, tail = one page)
                psz = self.page_size
                pairs = sorted({
                    (bucket_size(max(1, pages_for(Pb, psz) - 1)),
                     max(2, bucket_size(min(psz, Pb))))
                    for Pb in {bucket_size(int(p))
                               for p in prompt_buckets}})
                for Mb, Tb in pairs:
                    W = min(self.max_pages, Mb + pages_for(Tb, psz))
                    progs.append((
                        ("pattach", Mb, Tb),
                        lambda Mb=Mb, Tb=Tb: self._build_pattach(
                            Mb, Tb),
                        (params, buffers, self._cross_params(),
                         self._fm_cross.buffers(), state, jnp.int32(0),
                         jnp.full((1, W), self.num_pages, jnp.int32),
                         jnp.zeros((1, Tb), jnp.int32), jnp.int32(1),
                         one, jnp.int32(Tb), mem1) + spec_rows + jad))
        if self.prefill_chunk:
            # bucket-length prompts chunk in full-size chunks only,
            # so the precompile surface is one pcjoin per matched-page
            # bucket Mb the chunk walk visits; ragged final chunks
            # compile their smaller bucket on demand
            self._ensure_cross()
            crows = ((jnp.zeros((1, self._pool_len), jnp.int32),)
                     if self.spec_k else ())
            psz = self.page_size
            Cb = self.prefill_chunk
            mbs = set()
            for Pb in {bucket_size(int(p)) for p in prompt_buckets}:
                for pos in range(0, Pb, Cb) if Pb > Cb else ():
                    mbs.add(bucket_size(pos // psz, minimum=1))
            for Mb in sorted(mbs):
                W = min(self.max_pages, Mb + pages_for(Cb, psz))
                progs.append((
                    ("pcjoin", Mb, Cb),
                    lambda Mb=Mb, Cb=Cb: self._build_pcjoin(Mb, Cb),
                    (params, buffers, self._cross_params(),
                     self._fm_cross.buffers(), state, jnp.int32(0),
                     jnp.full((1, W), self.num_pages, jnp.int32),
                     jnp.zeros((1, Cb), jnp.int32), jnp.int32(0),
                     one, jnp.int32(2 * Cb), mem1) + crows + jad))
        if self.spec_k:
            dkey = ("draft",) + self._pool_key
            progs.append((
                dkey, lambda dkey=dkey: self._build_draft(dkey),
                (state["hist"], state["tok"], state["plen"],
                 state["pbk"], index0)))
            vkey = ("pverify",) + self._pool_key
            progs.append((
                vkey, lambda vkey=vkey: self._build_spec_step(vkey),
                (params, buffers, state, table0, index0) + sad +
                (jnp.zeros((S, self.spec_k - 1), jnp.int32), active,
                 active, jnp.int32(self.spec_k))))
        else:
            ck = ("pstep",) + self._pool_key
            progs.append((
                ck, lambda ck=ck: self._build_paged_step(ck),
                (params, buffers, state, table0, index0) + sad +
                (active,)))
        return progs


class ArtifactServingEngine(_EngineBase):
    """Continuous batching over a stateless causal-LM logits callable
    (an inference Program artifact: one int feed [B, S] -> one logits
    fetch [B, S, V], position t reading ids[:, :t+1] only). Program
    artifacts cannot thread a KV cache, so each iteration re-runs every
    active slot's prefix — bucketed to a power of two and batched
    across the S slots in ONE call, so the compile cache stays
    O(log max_len) programs for the whole pool (`shapes` records the
    (S, Lb) combos actually run). New arrivals join mid-flight instead
    of waiting for a full batch drain: this is `Predictor.generate`'s
    serving mode behind `Config.enable_serving_engine()`."""

    def __init__(self, logits_fn, *, num_slots=8, max_len=None,
                 dtype=np.int64, max_joins_per_iter=2, metrics=None,
                 callbacks=(), clock=time.monotonic):
        super().__init__(num_slots, max_joins_per_iter=max_joins_per_iter,
                         metrics=metrics, callbacks=callbacks, clock=clock)
        self._fn = logits_fn
        self.max_len = None if max_len is None else int(max_len)
        self._dtype = np.dtype(dtype)
        self._rows = [None] * self.num_slots   # per-slot id prefix
        self.shapes = set()                    # (S, Lb) combos run

    def admit_check(self, r):
        need = int(r.prompt.shape[0]) + r.max_new_tokens
        if self.max_len is not None and need > self.max_len:
            raise ValueError(f"request needs {need} positions > "
                             f"engine max_len {self.max_len}")

    def _join(self, s, r):
        _PT_PREFILL()
        self._rows[s] = [int(x) for x in r.prompt]
        return None   # token 0 falls out of the next batched pass

    def _evict(self, s):
        self._rows[s] = None

    def _decode_step(self, active):
        S = self.num_slots
        buf, Lb = pad_token_rows(self._rows, pad_id=0,
                                 dtype=self._dtype)
        shape = (S, Lb)
        if shape not in self.shapes:
            self.shapes.add(shape)
            self.trace_counts[("step",) + shape] += 1
        logits = np.asarray(self._fn(buf)[0])
        toks = np.zeros((S,), np.int64)
        for s in range(S):
            if active[s]:
                n = len(self._rows[s])
                t = int(logits[s, n - 1].argmax(-1))
                self._rows[s].append(t)
                toks[s] = t
        return toks, active
