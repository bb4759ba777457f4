"""Serving observability: counters, latency reservoirs, callbacks.

The serving loop is an always-on system — the numbers that matter are
the ones operators alarm on: time-to-first-token (admission + prefill),
per-token decode latency, sustained tokens/s, queue depth (backpressure
headroom), and slot occupancy (batching efficiency). `ServingMetrics`
records all of them with O(1) bounded memory (fixed-size reservoirs)
and serves them through `snapshot()`; `ServingCallback` is the
hapi-`Callback`-style hook surface the engine drives, so user code can
tap the same events (per-request logging, tracing, export to external
metric systems) without touching the engine."""
from __future__ import annotations

import threading
import time

__all__ = ["ServingMetrics", "ServingCallback", "CallbackList",
           "SNAPSHOT_DOCS", "flatten_snapshot", "to_prometheus"]

#: Every key `ServingMetrics.snapshot()` can emit, flattened with "."
#: (reservoir summaries are ONE documented key whose value is the
#: {n, mean, p50, p99, max} dict). This is the schema of record: the
#: README "Observability" table renders from it and
#: tests/test_tracing.py asserts a fully-populated snapshot flattens
#: to EXACTLY these keys — the snapshot cannot drift silently.
SNAPSHOT_DOCS = {
    "requests.submitted": ("counter", "requests accepted by submit()"),
    "requests.completed": ("counter",
                           "finished with eos / length / drain"),
    "requests.rejected": ("counter",
                          "QueueFull backpressure + admission rejects"),
    "requests.cancelled": ("counter", "caller-cancelled requests"),
    "requests.timeouts": ("counter",
                          "deadline evictions (queued or mid-decode)"),
    "requests.failed": ("counter", "finished with reason 'error'"),
    "requests.aborted": ("counter",
                         "finalized by a non-drain shutdown"),
    "errors.count": ("counter", "internal failures recorded anywhere"),
    "errors.retries": ("counter", "retry attempts after a failed op"),
    "errors.evictions_on_error": (
        "counter", "in-flight victims of a failed decode step"),
    "errors.fallbacks": ("counter",
                         "requests degraded to the solo eager path"),
    "errors.last": ("info",
                    "last recorded error {where, type, message, at}"),
    "joins": ("counter", "successful slot joins"),
    "iterations": ("counter", "engine iterations run"),
    "tokens_out": ("counter",
                   "delivered tokens incl. the prefill first token"),
    "tokens_per_s": ("gauge", "decode tokens / decode wall seconds"),
    "ttft_ms": ("summary", "time to first token (submit -> token 0)"),
    "per_token_ms": ("summary", "batched decode-step wall latency"),
    "queue_depth": ("summary", "scheduler depth sampled per iteration"),
    "slot_occupancy": ("summary",
                       "occupied-slot fraction sampled per iteration"),
    # the decode pipeline (PR 32): one step in flight where the loop
    # owns consecutive iterations
    "pipeline.decode_steps": ("counter", "batched decode steps enqueued"),
    "pipeline.steps_ahead": (
        "counter", "decode steps enqueued while the previous step's "
                   "tokens were still unread: their host work hid "
                   "behind the device"),
    "pipeline.depth": ("gauge", "steps that may be in flight unread: 1"),
    "pipeline.series_steps": (
        "info", "steps that found nothing unread, by reason: idle "
                "(the pool stood empty, or run_iteration by hand), "
                "spec, chunk, pending, preempt, retry, host"),
    "pipeline.late_slot_steps": (
        "counter", "slot-steps whose token was dropped where it was "
                   "read: the request had ended (eos found a step "
                   "late, cancelled, past its deadline)"),
    # sharded pools (PR 7) — the section appears once any of these
    # record
    "sharding.prefill_step_ms": (
        "summary", "prefill-slice step: dispatch -> arrays ready"),
    "sharding.decode_step_ms": (
        "summary", "decode-step latency (the per_token_ms reservoir)"),
    "sharding.step_gap_ms": (
        "summary",
        "decode-step inter-arrival co-resident requests see"),
    "sharding.per_shard_occupancy": (
        "gauge", "last-iteration occupancy per dp shard of the pool"),
    "sharding.collective_ms": (
        "counter", "host-timed cross-slice transfer milliseconds"),
    "sharding.collective_events": (
        "counter", "cross-slice transfers (splices, param placement)"),
    "sharding.collective_time_share": (
        "gauge", "collective / (collective + prefill + decode) time"),
    # the pool's caches by kind (PR 33) — the section appears once a
    # paged pool is built
    "cache.bytes": ("gauge", "device state of the pool by kind: paged "
                             "(K/V pages), latent (one row a token a "
                             "block, no heads, no V), ring (window K/V "
                             "rows a slot), "
                             "recurrent (convolution tails and scan "
                             "states a slot), static (cross-attention "
                             "K/V a slot)"),
    "cache.ring_wraps": ("counter", "window rings that wrote their row 0 "
                                    "again (slot-steps x ring layers)"),
    "cache.state_resets": ("counter", "slots taken by a prefill: every "
                                      "kind of the slot's state written "
                                      "whole"),
    "cache.prefill_tokens": ("counter", "prompt positions prefilled"),
    # an expert stack's counters (PR 35): summed over the expert layers
    # of every step and join, from arrays that leave the program with
    # its tokens — the section appears once such a pool is built
    "experts.token_slots": ("counter", "token-slots routed (live tokens "
                                       "x experts a token x expert "
                                       "layers)"),
    "experts.held_slots": ("counter", "token-slots that fell on experts "
                                      "held here"),
    "experts.load_max": ("counter", "the fullest held expert's "
                                    "token-slots, a layer a program"),
    "experts.dropped_slots": ("counter", "held slots less the rows the "
                                         "experts' loops counted: 0"),
    # paged pools (PR 6) — the section appears once a paged engine
    # records
    "paging.pages_in_use": ("gauge", "pages mapped at last iteration"),
    "paging.pages_free": ("gauge", "allocator free pages"),
    "paging.pages_total": ("gauge", "the pool's pages (in use + free)"),
    "paging.page_iterations": (
        "counter", "pages in use, summed over iterations: over any "
                   "interval, its growth / (iterations x pages_total) "
                   "is the mean share of the pool in use"),
    "paging.table_entries_total": (
        "gauge", "page-table entries: slots x max pages a slot, the "
                 "pages one paged decode call could read (its grid is "
                 "slots x ceil(max pages / pages_per_block) steps)"),
    "paging.live_page_iterations": (
        "counter", "written pages of the occupied slots, "
                   "sum of ceil(written / page_size), summed over "
                   "iterations: its growth / (iterations x "
                   "table_entries_total) is the share of the table's "
                   "pages that a paged decode call fetches and "
                   "computes on"),
    "paging.pages_per_block": (
        "gauge", "consecutive logical pages one grid step of the paged "
                 "decode call (K/V or latent kernel) takes, from the pool's "
                 "shapes and page dtype; 1 where the pool takes the gather"),
    "paging.live_block_iterations": (
        "counter", "grid steps of a paged decode call that fetch and "
                   "compute, sum over the occupied slots of "
                   "ceil(written pages / pages_per_block), summed over "
                   "iterations: live_page_iterations' growth over its "
                   "growth x pages_per_block is the share of the page "
                   "rows those steps take that hold written tokens"),
    "paging.prefix_hits": ("counter",
                           "joins served from the prefix cache"),
    "paging.prefix_misses": ("counter", "joins that ran a real prefill"),
    "paging.prefix_hit_rate": ("gauge", "hits / (hits + misses)"),
    "paging.page_waits": ("counter",
                          "admissions deferred on page headroom"),
    "paging.oom_evictions": ("counter", "mid-decode OutOfPages victims"),
    "paging.bytes_per_active_token": (
        "summary", "cache bytes per live token (oversubscription)"),
    # radix prefix cache (PR 16) — the section appears once a paged
    # join consults the trie
    "prefix.whole_hits": ("counter",
                          "joins fully served by cached pages (zero "
                          "prefill FLOPs)"),
    "prefix.partial_hits": ("counter",
                            "joins that matched a prefix and prefilled "
                            "only the divergent tail (pattach)"),
    "prefix.misses": ("counter", "joins that ran a full cold prefill"),
    "prefix.hit_token_ratio": (
        "gauge", "prefix tokens served from cache / prompt tokens "
                 "offered — the prefill-FLOPs savings lever"),
    "prefix.cow_copies": ("counter",
                          "copy-on-write page copies (mid-page "
                          "divergence + shared decode tails)"),
    "prefix.trie_nodes": ("gauge",
                          "radix-trie page nodes at last iteration"),
    "prefix.trie_pages": ("gauge",
                          "physical pages referenced by the trie"),
    "prefix.digests": ("counter",
                       "memory digests the trie's scopes took: one a "
                       "request that brought a memory"),
    "prefix.evictions": ("counter",
                         "trie items evicted (terminals and bare "
                         "leaf pages)"),
    "prefix.evict_visits": ("counter",
                            "eviction-order entries those evictions "
                            "examined, stale ones included"),
    # live HBM ledger (PR 9) — the section appears once the engine
    # registers its memory provider (model-backed engines always do)
    "memory.weights_bytes": (
        "gauge", "param + buffer bytes the pool serves"),
    "memory.pool_bytes": (
        "gauge",
        "KV pool + per-slot row arrays (paged: pages/scales/table)"),
    "memory.adapter_bytes": (
        "gauge", "stacked LoRA bank bytes (0 without an AdapterPool)"),
    "memory.total_bytes": (
        "gauge",
        "weights + pool + adapters: the committed device footprint"),
    "memory.in_use_bytes": (
        "gauge", "weights + rows/pages actually live right now"),
    "memory.budget_bytes": ("gauge", "configured HBM budget (0=unset)"),
    "memory.budget_used_frac": ("gauge", "in_use / budget"),
    "memory.compile_temp_peak_bytes": (
        "gauge", "XLA temp-buffer high-water across compiled programs"),
    "memory.watermark_warnings": (
        "counter", "budget-watermark crossings (warns BEFORE OOM)"),
    # the engine thread's host time by cause — the section
    # appears once an iteration under a tracer session records; every
    # count is over the iterations that did work
    "host.iterations": ("counter", "iterations that did work, accounted "
                                   "under a tracer session"),
    "host.host_ns": ("counter", "their host part: wall less the "
                                "step.readback and iter.tok0 waits"),
    "host.cpu_ns": ("counter", "the engine thread's CPU time in the "
                               "host part"),
    "host.offcpu_ns": ("counter", "host part less cpu: waiting for the "
                                  "GIL, descheduled, page faults"),
    "host.wait_ns": ("counter", "wall time in the step.readback and "
                                "iter.tok0 waits"),
    "host.gc_ns": ("counter", "collector time, on any thread, inside "
                              "those iterations"),
    "host.gc_collections": ("counter", "[generation 0, 1, 2] collections "
                                       "inside those iterations"),
    "host.gc_ns_by_gen": ("counter", "[generation 0, 1, 2] gc_ns"),
    "host.stalls": ("counter", "iterations whose host part passed "
                               "HOST_STALL_NS (20 ms)"),
    "host.stall_host_ns": ("counter", "host_ns of the stalls"),
    "host.stall_cpu_ns": ("counter", "cpu_ns of the stalls"),
    "host.stall_gc_ns": ("counter", "gc_ns of the stalls"),
    "host.spans_dropped": ("counter", "the tracer session's spans "
                                      "overwritten past its ring's "
                                      "capacity, at the last iteration"),
    # goodput (PR 9): how much of the produced work reached callers
    "goodput.useful_tokens": (
        "counter", "tokens of requests that completed (eos/length/drain)"),
    "goodput.wasted_tokens": (
        "counter",
        "partial tokens of evicted/failed/timed-out/cancelled requests"),
    "goodput.warmup_tokens": (
        "counter", "tokens produced inside begin_warmup()/end_warmup()"),
    "goodput.retry_tokens": (
        "counter", "token-slots burned by retried decode attempts"),
    "goodput.ratio": (
        "gauge", "useful / (useful + wasted + warmup + retried + "
                 "rejected-draft)"),
    # speculative decoding (PR 10) — the section appears once a
    # spec-enabled engine records a draft/verify step pair
    "speculation.rounds": ("counter", "draft + verify step pairs run"),
    "speculation.drafts_proposed": (
        "counter", "draft tokens proposed across all spec steps"),
    "speculation.drafts_accepted": (
        "counter", "draft tokens that matched the verify oracle"),
    "speculation.acceptance_rate": (
        "gauge", "drafts_accepted / drafts_proposed"),
    "speculation.accepted_per_step": (
        "summary", "accepted draft tokens per verify step"),
    "speculation.draft_step_ms": (
        "summary", "draft-proposal dispatch wall latency"),
    "speculation.verify_step_ms": (
        "summary", "k-token verify dispatch wall latency"),
    "speculation.wasted_draft_tokens": (
        "counter",
        "rejected drafts — verify lanes burned; in the goodput "
        "denominator"),
    "speculation.effective_k": (
        "gauge", "adaptive batch-wide draft depth the spec stepper "
                 "currently runs at (None until a spec step records)"),
    "speculation.k_shrink_events": (
        "counter", "adaptive-k downshifts (acceptance EMA under the "
                   "low band past the hysteresis patience)"),
    "speculation.k_grow_events": (
        "counter", "adaptive-k upshifts (acceptance EMA over the high "
                   "band past the hysteresis patience)"),
    "speculation.step_ms_by_variant": (
        "info", "per-pool-variant (dense/paged/sharded-*) draft/"
                "verify step-ms p50 split"),
    # multi-tenant serving (PR 15) — the section appears once an
    # adapter-carrying engine records a tenancy event
    "tenancy.tenants": (
        "gauge", "distinct tenants (adapter names + base) served"),
    "tenancy.active_slots_by_tenant": (
        "info", "last-iteration occupied-slot count per tenant"),
    "tenancy.tokens_by_tenant": (
        "info", "delivered tokens per tenant (the fairness input)"),
    "tenancy.adapter_loads": (
        "counter", "adapter bank hot-loads (device writes)"),
    "tenancy.adapter_evictions": (
        "counter", "zero-reference adapters evicted for their row"),
    "tenancy.adapter_hit_rate": (
        "gauge", "acquires served by an already-hot bank row"),
    "tenancy.adapter_waits": (
        "counter", "admissions deferred on OutOfAdapters backpressure"),
    "tenancy.fairness": (
        "gauge", "Jain index over tokens_by_tenant (1.0 = even)"),
    # cold start (PR 11) — the section appears once the engine runs
    # precompile(): startup AOT compile / persistent-cache accounting.
    # Cold-start latency is a production metric: these are the numbers
    # a restart dashboard alarms on.
    "cold_start.time_to_ready_s": (
        "gauge", "precompile() wall seconds until every serving "
                 "program was ready"),
    "cold_start.programs": (
        "gauge", "serving programs readied at startup (join buckets + "
                 "steps + paged attach/cow)"),
    "cold_start.loaded_from_cache": (
        "gauge", "programs deserialized from the persistent AOT "
                 "cache — no compile paid"),
    "cold_start.compiled": (
        "gauge", "programs AOT-compiled fresh at startup (cache "
                 "miss/cold)"),
    "cold_start.cache_errors": (
        "counter", "corrupt/stale cache entries that fell back to a "
                   "fresh compile (never a crash)"),
    "cold_start.warm": (
        "gauge", "1 when every program loaded from cache — the "
                 "zero-compile warm start"),
    "cold_start.first_ttft_ms": (
        "gauge", "TTFT of the very first request after start (the "
                 "number warm vs cold starts A/B)"),
    # traffic shaping (PR 19) — the section appears once a shaping
    # feature records: chunked prefill, preemption/resume, SLO-classed
    # finishes, or WFQ lag published by the ShapingScheduler
    "slo.preemptions": (
        "counter", "batch-class slots evicted to the prefix cache "
                   "under pressure"),
    "slo.resumes": (
        "counter", "preempted requests re-admitted (resume rides the "
                   "prefix cache, not a re-prefill)"),
    "slo.replay_tokens": (
        "counter", "already-delivered tokens a resumed request "
                   "re-absorbed silently"),
    "slo.chunked_prefills": (
        "counter", "joins split into chunked prefill (prompt past the "
                   "prefill_chunk knob)"),
    "slo.chunks": (
        "counter", "prefill chunks dispatched between decode steps"),
    "slo.ttft_attainment": (
        "info", "per-class fraction of finished requests that met "
                "their TTFT target"),
    "slo.tpot_attainment": (
        "info", "per-class fraction of finished requests that met "
                "their TPOT target"),
    "slo.wfq_lag_by_tenant": (
        "info", "per-tenant WFQ virtual-time lag (pending finish tag "
                "minus pool virtual time; 0 = keeping pace)"),
}

_SUMMARY_KEYS = {"n", "mean", "p50", "p99", "max"}

#: a working iteration whose host part passes this is a stall: three
#: iterations of a saturated 64-slot pool, above any iteration of five
#: joins
HOST_STALL_NS = 20_000_000
_LEAF_DICTS = {"errors.last", "pipeline.series_steps",
               "cache.bytes",
               "speculation.step_ms_by_variant",
               "tenancy.active_slots_by_tenant",
               "tenancy.tokens_by_tenant",
               "slo.ttft_attainment", "slo.tpot_attainment",
               "slo.wfq_lag_by_tenant"}


def flatten_snapshot(snap, _prefix=""):
    """Flatten a snapshot() dict to {dotted_key: leaf}. Reservoir
    summaries ({n, mean, p50, p99, max}) and the last-error record stay
    leaves — the flattened key set must equal SNAPSHOT_DOCS for a
    fully-populated snapshot."""
    out = {}
    for k, v in snap.items():
        key = f"{_prefix}{k}"
        if isinstance(v, dict) and key not in _LEAF_DICTS and \
                not set(v) <= _SUMMARY_KEYS:
            out.update(flatten_snapshot(v, key + "."))
        else:
            out[key] = v
    return out


def _prom_escape(s):
    return (str(s).replace("\\", r"\\").replace('"', r"\"")
            .replace("\n", r"\n"))


def to_prometheus(snapshot, tracer=None, prefix="paddle_tpu_serving"):
    """Render a snapshot() (plus, optionally, a `profiler.trace.Tracer`
    session's counters) in the Prometheus text exposition format —
    `tools/metrics_dump.py` is the CLI over this."""
    lines = []

    def head(name, kind, doc):
        lines.append(f"# HELP {name} {doc}")
        lines.append(f"# TYPE {name} "
                     f"{'counter' if kind == 'counter' else 'gauge'}")

    flat = flatten_snapshot(snapshot)
    for key in sorted(flat):
        kind, doc = SNAPSHOT_DOCS.get(key, ("gauge", ""))
        v = flat[key]
        name = prefix + "_" + key.replace(".", "_")
        if v is None:
            continue
        if isinstance(v, dict) and set(v) <= _SUMMARY_KEYS:
            head(name, "gauge", doc)
            for stat in sorted(v):
                lines.append(f'{name}{{stat="{stat}"}} {float(v[stat])}')
        elif kind == "info" and isinstance(v, dict):
            head(name, "gauge", doc)
            labels = ",".join(f'{lk}="{_prom_escape(lv)}"'
                              for lk, lv in sorted(v.items()))
            lines.append(f"{name}{{{labels}}} 1")
        elif isinstance(v, (list, tuple)):
            head(name, kind, doc)
            for i, sv in enumerate(v):
                lines.append(f'{name}{{index="{i}"}} {float(sv)}')
        elif isinstance(v, (int, float)):
            head(name, kind, doc)
            lines.append(f"{name} {float(v)}")
    if tracer is not None:
        name = prefix + "_tracer_events"
        head(name, "counter", "tracer session counters")
        for cname in sorted(tracer.counters):
            lines.append(f'{name}{{counter="{_prom_escape(cname)}"}} '
                         f'{float(tracer.counters[cname])}')
        head(prefix + "_tracer_spans_dropped", "counter",
             "spans overwritten past the ring-buffer capacity")
        lines.append(f"{prefix}_tracer_spans_dropped "
                     f"{float(tracer.dropped)}")
    return "\n".join(lines) + "\n"


def _jain(tokens_by_tenant):
    """Jain's fairness index over per-tenant delivered tokens:
    (sum x)^2 / (n * sum x^2) — 1.0 when every tenant got an equal
    share, 1/n when one tenant took everything. The number the
    multi-tenant scheduler is judged on."""
    xs = [float(v) for v in tokens_by_tenant.values() if v > 0]
    if not xs:
        return 1.0
    s = sum(xs)
    return round((s * s) / (len(xs) * sum(x * x for x in xs)), 4)


class _Reservoir:
    """Bounded sample buffer (ring overwrite) with percentile reads —
    latency distributions over the most recent `cap` observations."""

    def __init__(self, cap=2048):
        self.cap = int(cap)
        self._buf = []
        self._next = 0
        self.count = 0

    def add(self, x):
        x = float(x)
        if len(self._buf) < self.cap:
            self._buf.append(x)
        else:
            self._buf[self._next] = x
            self._next = (self._next + 1) % self.cap
        self.count += 1

    def summary(self, scale=1.0, digits=3):
        import numpy as np

        if not self._buf:
            return {"n": 0}
        a = np.asarray(self._buf, dtype=np.float64) * scale
        return {"n": self.count,
                "mean": round(float(a.mean()), digits),
                "p50": round(float(np.percentile(a, 50)), digits),
                "p99": round(float(np.percentile(a, 99)), digits),
                "max": round(float(a.max()), digits)}


class ServingMetrics:
    """Thread-safe metric sink for the serving runtime. The engine and
    the frontend both record into it; `snapshot()` can be called from
    any thread at any time (monitoring endpoints, tests, the bench)."""

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        # identity wiring that reset() keeps: the ledger provider and
        # its armed budget describe the ENGINE, not a measurement epoch
        self._memory_provider = None
        self.budget_bytes = 0
        self.watermark_frac = 0.9
        self._init_counters()

    # callers hold the lock: __init__ (exempt by construction) and
    # reset() (wraps the call in `with self._lock:`)
    def _init_counters(self):   # analysis: single-threaded
        """(Re)zero every counter, gauge and reservoir. Split out of
        __init__ so reset() can start a fresh measurement epoch without
        touching identity wiring (clock, lock, ledger provider,
        budget)."""
        self.submitted = 0
        self.completed = 0          # finished with "eos" / "length"
        self.rejected = 0           # backpressure (QueueFull)
        self.cancelled = 0
        self.timeouts = 0           # deadline evictions
        self.aborted = 0            # non-drain shutdown
        self.joins = 0
        self.iterations = 0
        self.tokens_out = 0         # every delivered token (incl. the
        #                             prefill-produced first token)
        self.decode_tokens = 0      # tokens out of batched decode steps
        self.decode_time_s = 0.0
        self.decode_steps = 0       # batched decode steps enqueued
        self.steps_ahead = 0        # ... before the last one was read
        self.series_steps = {}      # the others, by reason
        self.late_slot_steps = 0    # tokens read for an ended request
        self.ttft_s = _Reservoir()
        self.token_latency_s = _Reservoir()
        self.queue_depth = _Reservoir(512)
        self.occupancy = _Reservoir(512)
        # fault accounting (per-request isolation + retry layer)
        self.failed = 0             # finished with reason "error"
        self.errors = 0             # recorded internal errors, any kind
        self.retries = 0            # retry attempts after a failure
        self.evictions_on_error = 0  # in-flight requests evicted by a
        #                              decode-step failure
        self.fallbacks = 0          # requests degraded to the eager path
        self.last_error = None      # {"where","type","message","at"}
        # paging accounting (None until a paged engine records — the
        # snapshot only grows a "paging" section for paged pools)
        self.pages_in_use = None    # last-iteration gauge
        self.pages_free = None
        # what the pool's caches did (PR 33): bytes by kind of state
        # (set once the pool is built) and counts summed over iterations
        self.cache_bytes = None
        self.cache_counts = {"ring_wraps": 0, "state_resets": 0,
                             "prefill_tokens": 0}
        self.expert_counts = None   # {name: sum} once a pool has them
        self.page_iterations = 0    # sum over iterations of pages_in_use
        self.live_page_iterations = 0   # ... of the slots' WRITTEN pages
        self.live_block_iterations = 0  # ... of the grid steps over them
        self.pages_per_block = None     # pages a decode grid step takes
        self.table_entries_total = None     # slots x max pages a slot
        self.prefix_hits = 0        # joins served from the prefix cache
        self.prefix_misses = 0      # joins that ran a real prefill
        # radix prefix-cache accounting (PR 16): the snapshot grows a
        # "prefix" section once a join consults the trie
        self._prefix_recorded = False
        self.prefix_whole_hits = 0
        self.prefix_partial_hits = 0
        self.prefix_matched_tokens = 0   # prompt tokens served cached
        self.prefix_prompt_tokens = 0    # prompt tokens offered
        self.cow_copies = 0
        self.trie_nodes = None      # last-iteration gauges
        self.trie_pages = None
        self.trie_counts = {}       # digests, evictions, evict_visits
        self.page_waits = 0         # admissions deferred on page headroom
        self.oom_evictions = 0      # mid-decode OutOfPages victims
        self.bytes_per_token = _Reservoir(512)  # bytes / active token
        # sharded-serving accounting (the snapshot grows a "sharding"
        # section once any of these record — single-chip pools don't
        # pay for keys they never touch). Phases follow the
        # prefill/decode disaggregation split: "prefill" latencies are
        # the prefill-slice step (dispatch -> arrays ready), "decode"
        # rides the existing decode reservoirs; step_gap_s is the
        # decode-step INTER-ARRIVAL co-resident requests see between
        # tokens — the number inline prefill inflates and a
        # disaggregated prefill slice does not.
        self._sharded = False
        self.prefill_step_s = _Reservoir()
        self.step_gap_s = _Reservoir()
        self.collective_s = 0.0     # cross-slice transfers (prefill ->
        #                             decode splices, param placement)
        self.collective_events = 0
        self.shard_occupancy = None  # last-iteration per-dp-shard list
        # live HBM ledger (PR 9): the engine registers a provider that
        # returns {weights_bytes, pool_bytes, in_use_bytes,
        # compile_temp_peak_bytes}; snapshot() formats it into the
        # "memory" section. budget_bytes arms the watermark: crossing
        # watermark_frac * budget bumps watermark_warnings ONCE per
        # excursion (warn before OutOfPages/OOM, not after).
        self.watermark_warnings = 0
        self._above_watermark = False
        # goodput accounting: token-denominated usefulness, classified
        # at finish time (the engines pass each request's token count)
        self.useful_tokens = 0
        self.wasted_tokens = 0
        self.warmup_tokens = 0
        self.retry_tokens = 0
        self._warmup = False
        # speculative decoding (the snapshot grows a "speculation"
        # section once a spec-enabled engine records): device-side
        # acceptance accounting plus the two dispatch latencies of the
        # draft/verify pair; wasted drafts feed the goodput denominator
        self._spec_recorded = False
        self.spec_rounds = 0
        self.drafts_proposed = 0
        self.drafts_accepted = 0
        self.accepted_per_step = _Reservoir(512)
        self.draft_step_s = _Reservoir(512)
        self.verify_step_s = _Reservoir(512)
        # adaptive effective k (the batch-wide draft depth the spec
        # stepper is currently running) + its hysteresis transitions,
        # and the draft/verify latency split keyed by pool variant
        # (dense / paged / sharded-*) so a mixed deployment's spec
        # steps stay attributable
        self.spec_k_eff = None
        self.spec_k_shrinks = 0
        self.spec_k_grows = 0
        self._spec_by_variant = {}
        # multi-tenant serving (the snapshot grows a "tenancy" section
        # once an adapter-carrying engine records): per-tenant token /
        # slot accounting plus the AdapterPool's load/evict/hit-rate
        # counters mirrored by the pool itself
        self._tenancy = False
        self.tokens_by_tenant = {}
        self.tenant_slots = None       # last-iteration gauge
        self.adapter_loads = 0
        self.adapter_evictions = 0
        self.adapter_hits = 0
        self.adapter_misses = 0
        self.adapter_waits = 0
        # cold start (PR 11): the engine's precompile() report — how
        # the pool reached readiness (cache-warm vs compiled) and the
        # first request's TTFT (what a restart actually costs callers)
        self._cold_start = None
        self.first_ttft_s = None
        # the engine thread's host time by cause: recorded per working
        # iteration only while a tracer session is on
        self._host = False
        self.host_iterations = 0
        self.host_ns = 0
        self.host_cpu_ns = 0
        self.host_wait_ns = 0
        self.gc_ns_by_gen = [0, 0, 0]
        self.gc_collections = [0, 0, 0]
        self.stalls = 0
        self.stall_host_ns = 0
        self.stall_cpu_ns = 0
        self.stall_gc_ns = 0
        self.spans_dropped = 0
        # traffic shaping (PR 19): chunked-prefill and preemption
        # counters plus per-SLO-class attainment and the WFQ lag the
        # ShapingScheduler publishes each iteration — the snapshot
        # grows an "slo" section once any of them records
        self._slo = False
        self.preemptions = 0
        self.resumes = 0
        self.replay_tokens = 0
        self.chunked_prefills = 0
        self.chunks = 0
        self.slo_finishes = {}     # class -> {n, ttft_ok, tpot_ok}
        self.wfq_lag = {}          # tenant -> virtual-time lag

    def reset(self):
        """Start a fresh measurement epoch: zero every counter,
        reservoir and gauge while keeping identity wiring (clock, lock,
        ledger provider, HBM budget). Benches and tests call this
        between phases instead of zeroing individual fields by hand."""
        with self._lock:
            self._init_counters()

    # ---- recording (engine / frontend side) ----
    def record_submit(self):
        with self._lock:
            self.submitted += 1

    def record_reject(self):
        with self._lock:
            self.rejected += 1

    def record_join(self):
        with self._lock:
            self.joins += 1

    def set_cache_bytes(self, by_kind):
        """The pool's device state by kind (paged / ring / recurrent /
        static), in bytes."""
        with self._lock:
            self.cache_bytes = {k: int(v) for k, v in by_kind.items()}

    def set_expert_counters(self, names):
        """The served stack has expert layers: `names` are what its
        programs count (the `experts` section appears)."""
        with self._lock:
            if self.expert_counts is None:
                self.expert_counts = {n: 0 for n in names}

    def record_first_token(self, ttft_s):
        with self._lock:
            self.ttft_s.add(ttft_s)
            if self.first_ttft_s is None:
                # the first request ever: the cold-start A/B's number
                self.first_ttft_s = float(ttft_s)

    def record_token(self, tenant=None):
        with self._lock:
            self.tokens_out += 1
            if tenant is not None:
                self._tenancy = True
                self.tokens_by_tenant[tenant] = \
                    self.tokens_by_tenant.get(tenant, 0) + 1

    def record_decode(self, n_tokens, dt_s, late=0):
        """One decode step delivered `n_tokens` across its slots,
        `dt_s` seconds from its enqueue to its tokens read; `late` of
        its tokens were dropped (their request had ended)."""
        with self._lock:
            self.decode_tokens += n_tokens
            self.decode_time_s += dt_s
            self.late_slot_steps += late
            if n_tokens:
                self.token_latency_s.add(dt_s)

    def record_step(self, why):
        """A decode step was enqueued: ahead of the last one's tokens
        (`why` None), or with nothing unread, for the reason `why`."""
        with self._lock:
            self.decode_steps += 1
            if why is None:
                self.steps_ahead += 1
            else:
                self.series_steps[why] = \
                    self.series_steps.get(why, 0) + 1

    def record_finish(self, reason, n_tokens=0):
        """Request finished with `reason`; `n_tokens` (the tokens it
        was delivered) feeds the goodput split: completions count as
        useful, evictions/failures/timeouts as wasted, and anything
        produced inside a warmup window as warmup."""
        with self._lock:
            if reason in ("eos", "length", "drain"):
                self.completed += 1
                if self._warmup:
                    self.warmup_tokens += int(n_tokens)
                else:
                    self.useful_tokens += int(n_tokens)
            else:
                self.wasted_tokens += int(n_tokens)
                if reason == "cancelled":
                    self.cancelled += 1
                elif reason == "timeout":
                    self.timeouts += 1
                elif reason == "error":
                    self.failed += 1
                else:
                    self.aborted += 1

    # ---- goodput / warmup ----
    def begin_warmup(self):
        """Tokens finished until end_warmup() classify as warmup, not
        useful — benches/servers call this around bucket warm loops so
        goodput reflects steady-state serving only."""
        with self._lock:
            self._warmup = True

    def end_warmup(self):
        with self._lock:
            self._warmup = False

    # ---- fault accounting ----
    def record_error(self, where, exc):
        """An internal failure was observed at `where` (slot_join,
        decode_step, stream_cb, callback.*, server_crash, ...): bump
        the counter and keep a last-error snapshot for operators."""
        with self._lock:
            self.errors += 1
            self.last_error = {"where": where,
                               "type": type(exc).__name__,
                               "message": str(exc),
                               "at": self._clock()}

    def record_retry(self, where, n_tokens=0):
        """A failed op is being retried; for decode steps `n_tokens` is
        the active-slot count — the token-slots of work the failed
        attempt burned (goodput's retry term)."""
        with self._lock:
            self.retries += 1
            self.retry_tokens += int(n_tokens)

    def record_eviction_on_error(self, n=1):
        with self._lock:
            self.evictions_on_error += n

    def record_fallback(self):
        with self._lock:
            self.fallbacks += 1

    def record_prefix(self, kind, matched_tokens=0, prompt_tokens=0):
        """A paged join consulted the prefix cache. `kind` is "whole"
        (every prompt page mapped shared, zero prefill), "partial"
        (matched prefix mapped, only the divergent tail prefilled) or
        "miss" (full cold prefill); bools keep the pre-radix contract
        (True = whole). The token counts feed hit_token_ratio — the
        prefill-FLOPs savings the radix cache exists for."""
        if isinstance(kind, bool):
            kind = "whole" if kind else "miss"
        with self._lock:
            self._prefix_recorded = True
            if kind == "whole":
                self.prefix_hits += 1
                self.prefix_whole_hits += 1
            elif kind == "partial":
                self.prefix_hits += 1
                self.prefix_partial_hits += 1
            else:
                self.prefix_misses += 1
            self.prefix_matched_tokens += int(matched_tokens)
            self.prefix_prompt_tokens += int(prompt_tokens)

    def record_cow_copy(self, n=1):
        """A copy-on-write page copy ran (a joiner's decode tail page
        was shared, or a partial hit diverged mid-page)."""
        with self._lock:
            self._prefix_recorded = True
            self.cow_copies += n

    def record_page_wait(self):
        """Admission deferred: not enough free pages for the queue head
        (the OutOfPages backpressure path — the request stays queued)."""
        with self._lock:
            self.page_waits += 1

    def record_oom_eviction(self, n=1):
        with self._lock:
            self.oom_evictions += n

    # ---- multi-tenant accounting (the AdapterPool mirrors its own
    # events here via bind_metrics; the engine records the waits) ----
    def record_adapter_acquire(self, hit):
        """An adapter acquire resolved: hit = an already-hot bank row
        (the adapter cache), miss = a load had to run."""
        with self._lock:
            self._tenancy = True
            if hit:
                self.adapter_hits += 1
            else:
                self.adapter_misses += 1

    def record_adapter_load(self):
        with self._lock:
            self._tenancy = True
            self.adapter_loads += 1

    def record_adapter_eviction(self):
        with self._lock:
            self._tenancy = True
            self.adapter_evictions += 1

    def record_adapter_wait(self):
        """Admission deferred: every adapter row pinned by live slots
        (the OutOfAdapters backpressure path — the request stays
        queued at the head)."""
        with self._lock:
            self._tenancy = True
            self.adapter_waits += 1

    # ---- traffic shaping (PR 19) ----
    def record_chunked_join(self):
        """A join went chunked: the prompt exceeded prefill_chunk, so
        its prefill will interleave with decode steps chunk by chunk."""
        with self._lock:
            self._slo = True
            self.chunked_prefills += 1

    def record_chunk(self):
        """One prefill chunk dispatched between decode steps."""
        with self._lock:
            self._slo = True
            self.chunks += 1

    def record_preemption(self):
        """A batch-class slot was evicted to the prefix cache to free
        capacity for higher-priority work."""
        with self._lock:
            self._slo = True
            self.preemptions += 1

    def record_resume(self):
        """A preempted request re-joined (resume rides the prefix
        cache whole-hit attach — prefill_count proves no re-prefill)."""
        with self._lock:
            self._slo = True
            self.resumes += 1

    def record_replay_token(self):
        """A resumed request re-produced an already-delivered token;
        the engine absorbed it silently (no double delivery)."""
        with self._lock:
            self._slo = True
            self.replay_tokens += 1

    def record_slo_finish(self, name, ttft_s, tpot_s, ttft_target_s,
                          tpot_target_s):
        """An SLO-classed request completed: fold its TTFT/TPOT against
        the class targets into the per-class attainment fractions."""
        with self._lock:
            self._slo = True
            c = self.slo_finishes.setdefault(
                name, {"n": 0, "ttft_ok": 0, "tpot_ok": 0})
            c["n"] += 1
            if float(ttft_s) <= float(ttft_target_s):
                c["ttft_ok"] += 1
            if float(tpot_s) <= float(tpot_target_s):
                c["tpot_ok"] += 1

    def set_wfq_lag(self, lag_by_tenant):
        """The ShapingScheduler's per-tenant WFQ virtual-time lag at
        the last iteration (pending finish tag minus pool virtual
        time; 0 = the tenant is keeping pace with its weight)."""
        with self._lock:
            if lag_by_tenant:
                self._slo = True
            self.wfq_lag = {str(t): round(float(v), 4)
                            for t, v in lag_by_tenant.items()}

    # ---- HBM ledger / MFU accounting (PR 9) ----
    def set_memory_provider(self, provider, budget_bytes=None,
                            watermark_frac=None):
        """Register the engine's ledger closure: `provider()` returns
        {weights_bytes, pool_bytes, in_use_bytes,
        compile_temp_peak_bytes} (or None before the pool exists).
        snapshot() calls it OUTSIDE the metrics lock."""
        with self._lock:
            self._memory_provider = provider
            if budget_bytes is not None:
                self.budget_bytes = int(budget_bytes)
            if watermark_frac is not None:
                self.watermark_frac = float(watermark_frac)

    def check_memory_watermark(self, in_use_bytes):
        """Engine-side liveness check against the configured budget:
        the first crossing of watermark_frac * budget bumps the warning
        counter (and arms hysteresis so a pool hovering at the line
        warns once per excursion, not per iteration). Returns True
        while above the watermark."""
        if self.budget_bytes <= 0:
            return False
        above = in_use_bytes >= self.watermark_frac * self.budget_bytes
        with self._lock:
            if above and not self._above_watermark:
                self.watermark_warnings += 1
            self._above_watermark = above
        return above

    def watermark_exceeded(self):
        """True while the ledger last sat above the armed watermark —
        the shaping scheduler's admission gate reads this to pause
        batch-class admission while the pool nears its HBM budget."""
        with self._lock:
            return self._above_watermark

    def record_host(self, host_ns, cpu_ns, wait_ns, gc_ns, gc_count,
                    spans_dropped):
        """One working iteration's host time by cause
        (`tracing.IterationTrace.close`, under a tracer session only):
        its host part, the thread's CPU time in it, its device waits,
        the collector's nanoseconds and collections by generation, and
        the session's dropped spans so far."""
        gc_total = gc_ns[0] + gc_ns[1] + gc_ns[2]
        with self._lock:
            self._host = True
            self.host_iterations += 1
            self.host_ns += host_ns
            self.host_cpu_ns += cpu_ns
            self.host_wait_ns += wait_ns
            for g in range(3):
                self.gc_ns_by_gen[g] += gc_ns[g]
                self.gc_collections[g] += gc_count[g]
            if host_ns > HOST_STALL_NS:
                self.stalls += 1
                self.stall_host_ns += host_ns
                self.stall_cpu_ns += cpu_ns
                self.stall_gc_ns += gc_total
            self.spans_dropped = int(spans_dropped)

    # ---- cold-start accounting (PR 11) ----
    def record_cold_start(self, report):
        """The engine's precompile() report: {time_to_ready_s,
        programs, loaded_from_cache, compiled, cache_errors, warm}.
        A second call (another precompile pass on the same engine)
        accumulates program counts and keeps the first ready time."""
        with self._lock:
            if self._cold_start is None:
                self._cold_start = dict(report)
            else:
                c = self._cold_start
                for k in ("programs", "loaded_from_cache", "compiled",
                          "cache_errors"):
                    c[k] = c.get(k, 0) + int(report.get(k, 0))
                c["warm"] = int(bool(c.get("warm"))
                                and bool(report.get("warm")))

    # ---- speculative-decoding accounting ----
    def record_spec_step(self, n_active, proposed, accepted, draft_s,
                         verify_s, k_eff=None, variant=None,
                         k_shrinks=None, k_grows=None):
        """One speculative iteration: `proposed` draft tokens went into
        the verify step for the spec-enabled active slots, `accepted`
        of them matched the oracle; `draft_s`/`verify_s` are the two
        dispatch wall times. Rejected drafts are wasted verify lanes —
        they join the goodput denominator. `k_eff` is the adaptive
        batch-wide draft depth this round ran at (with the stepper's
        cumulative shrink/grow transition counts), `variant` the pool
        flavor (dense/paged/sharded-*) keying the per-variant step-ms
        split."""
        with self._lock:
            self._spec_recorded = True
            self.spec_rounds += 1
            self.drafts_proposed += int(proposed)
            self.drafts_accepted += int(accepted)
            if n_active:
                self.accepted_per_step.add(accepted / n_active)
            self.draft_step_s.add(draft_s)
            self.verify_step_s.add(verify_s)
            if k_eff is not None:
                self.spec_k_eff = int(k_eff)
            if k_shrinks is not None:
                self.spec_k_shrinks = int(k_shrinks)
            if k_grows is not None:
                self.spec_k_grows = int(k_grows)
            if variant is not None:
                v = self._spec_by_variant.get(variant)
                if v is None:
                    v = {"draft": _Reservoir(256),
                         "verify": _Reservoir(256)}
                    self._spec_by_variant[variant] = v
                v["draft"].add(draft_s)
                v["verify"].add(verify_s)

    # ---- sharded-serving accounting ----
    def record_step_gap(self, dt_s):
        """Wall time between two consecutive decode-step completions
        while the pool stayed active: per-token latency as co-resident
        requests experience it, join/prefill stalls included."""
        with self._lock:
            self.step_gap_s.add(dt_s)

    def record_prefill_step(self, dt_s):
        """One prefill-slice step completed (disaggregated: dispatch ->
        arrays ready, polled at iteration granularity; inline: the
        blocking join call)."""
        with self._lock:
            self._sharded = True
            self.prefill_step_s.add(dt_s)

    def record_collective(self, dt_s):
        """Host-timed cross-slice communication: a prefill-slice ->
        decode-slice K/V transfer (or a param re-placement). In-program
        collectives are XLA's to schedule and are not visible here;
        this tracks the traffic the ENGINE moves between mesh slices."""
        with self._lock:
            self._sharded = True
            self.collective_s += float(dt_s)
            self.collective_events += 1

    def record_iteration(self, queue_depth, occupancy, pages_in_use=None,
                         pages_free=None, bytes_per_active_token=None,
                         shard_occupancy=None, tenant_slots=None,
                         trie_nodes=None, trie_pages=None,
                         trie_counts=None,
                         live_pages=None, table_entries=None, cache=None,
                         live_blocks=None, pages_per_block=None,
                         experts=None):
        with self._lock:
            self.iterations += 1
            for k, v in (cache or {}).items():
                self.cache_counts[k] = self.cache_counts.get(k, 0) + int(v)
            for k, v in (experts or {}).items():
                self.expert_counts[k] = self.expert_counts.get(k, 0) \
                    + int(v)
            self.queue_depth.add(queue_depth)
            self.occupancy.add(occupancy)
            if tenant_slots is not None:
                self._tenancy = True
                self.tenant_slots = dict(tenant_slots)
            if pages_in_use is not None:
                self.pages_in_use = int(pages_in_use)
                self.page_iterations += self.pages_in_use
            if live_pages is not None:
                self.live_page_iterations += int(live_pages)
                self.table_entries_total = int(table_entries)
            if live_blocks is not None:
                self.live_block_iterations += int(live_blocks)
                self.pages_per_block = int(pages_per_block)
            if pages_free is not None:
                self.pages_free = int(pages_free)
            if trie_nodes is not None:
                self.trie_nodes = int(trie_nodes)
            if trie_pages is not None:
                self.trie_pages = int(trie_pages)
            for k, v in (trie_counts or {}).items():
                self.trie_counts[k] = self.trie_counts.get(k, 0) + int(v)
            if bytes_per_active_token is not None:
                self.bytes_per_token.add(bytes_per_active_token)
            if shard_occupancy is not None:
                self._sharded = True
                self.shard_occupancy = [round(float(x), 3)
                                        for x in shard_occupancy]

    # ---- reading ----
    def snapshot(self):
        # the ledger provider walks engine state — call it OUTSIDE the
        # metrics lock (it must stay free to call metrics methods)
        ledger = None
        if self._memory_provider is not None:
            try:
                ledger = self._memory_provider()
            except Exception:
                ledger = None
        with self._lock:
            tps = (self.decode_tokens / self.decode_time_s
                   if self.decode_time_s > 0 else 0.0)
            mem = None
            if ledger is not None:
                w = int(ledger.get("weights_bytes", 0))
                p = int(ledger.get("pool_bytes", 0))
                a = int(ledger.get("adapter_bytes", 0))
                used = int(ledger.get("in_use_bytes", w + p + a))
                b = self.budget_bytes
                mem = {
                    "weights_bytes": w,
                    "pool_bytes": p,
                    "adapter_bytes": a,
                    "total_bytes": w + p + a,
                    "in_use_bytes": used,
                    "budget_bytes": b,
                    "budget_used_frac":
                        round(used / b, 4) if b > 0 else 0.0,
                    "compile_temp_peak_bytes":
                        int(ledger.get("compile_temp_peak_bytes", 0)),
                    "watermark_warnings": self.watermark_warnings,
                }
            wasted_drafts = self.drafts_proposed - self.drafts_accepted
            good_denom = (self.useful_tokens + self.wasted_tokens +
                          self.warmup_tokens + self.retry_tokens +
                          wasted_drafts)
            return {
                "requests": {"submitted": self.submitted,
                             "completed": self.completed,
                             "rejected": self.rejected,
                             "cancelled": self.cancelled,
                             "timeouts": self.timeouts,
                             "failed": self.failed,
                             "aborted": self.aborted},
                "errors": {"count": self.errors,
                           "retries": self.retries,
                           "evictions_on_error":
                               self.evictions_on_error,
                           "fallbacks": self.fallbacks,
                           "last": self.last_error},
                "joins": self.joins,
                "iterations": self.iterations,
                "tokens_out": self.tokens_out,
                "tokens_per_s": round(tps, 1),
                "ttft_ms": self.ttft_s.summary(scale=1e3),
                "per_token_ms": self.token_latency_s.summary(scale=1e3),
                "queue_depth": self.queue_depth.summary(digits=2),
                "slot_occupancy": self.occupancy.summary(digits=3),
                "pipeline": {
                    "decode_steps": self.decode_steps,
                    "steps_ahead": self.steps_ahead,
                    "depth": 1,
                    "series_steps": dict(self.series_steps),
                    "late_slot_steps": self.late_slot_steps,
                },
                "goodput": {
                    "useful_tokens": self.useful_tokens,
                    "wasted_tokens": self.wasted_tokens,
                    "warmup_tokens": self.warmup_tokens,
                    "retry_tokens": self.retry_tokens,
                    "ratio": round(self.useful_tokens / good_denom, 4)
                    if good_denom else 1.0,
                },
                **({} if not self._tenancy else {"tenancy": {
                    "tenants": len(self.tokens_by_tenant),
                    "active_slots_by_tenant":
                        dict(self.tenant_slots or {}),
                    "tokens_by_tenant": dict(self.tokens_by_tenant),
                    "adapter_loads": self.adapter_loads,
                    "adapter_evictions": self.adapter_evictions,
                    "adapter_hit_rate": round(
                        self.adapter_hits /
                        max(1, self.adapter_hits +
                            self.adapter_misses), 4),
                    "adapter_waits": self.adapter_waits,
                    "fairness": _jain(self.tokens_by_tenant),
                }}),
                **({} if self._cold_start is None else {"cold_start": {
                    "time_to_ready_s":
                        self._cold_start.get("time_to_ready_s", 0.0),
                    "programs": self._cold_start.get("programs", 0),
                    "loaded_from_cache":
                        self._cold_start.get("loaded_from_cache", 0),
                    "compiled": self._cold_start.get("compiled", 0),
                    "cache_errors":
                        self._cold_start.get("cache_errors", 0),
                    "warm": int(bool(self._cold_start.get("warm"))),
                    "first_ttft_ms":
                        None if self.first_ttft_s is None else
                        round(self.first_ttft_s * 1e3, 3),
                }}),
                **({} if not self._spec_recorded else {"speculation": {
                    "rounds": self.spec_rounds,
                    "drafts_proposed": self.drafts_proposed,
                    "drafts_accepted": self.drafts_accepted,
                    "acceptance_rate": round(
                        self.drafts_accepted /
                        max(1, self.drafts_proposed), 4),
                    "accepted_per_step":
                        self.accepted_per_step.summary(digits=3),
                    "draft_step_ms":
                        self.draft_step_s.summary(scale=1e3),
                    "verify_step_ms":
                        self.verify_step_s.summary(scale=1e3),
                    "wasted_draft_tokens": wasted_drafts,
                    "effective_k": self.spec_k_eff,
                    "k_shrink_events": self.spec_k_shrinks,
                    "k_grow_events": self.spec_k_grows,
                    "step_ms_by_variant": {
                        v: {"draft_p50":
                                r["draft"].summary(scale=1e3)
                                .get("p50"),
                            "verify_p50":
                                r["verify"].summary(scale=1e3)
                                .get("p50")}
                        for v, r in self._spec_by_variant.items()},
                }}),
                **({} if mem is None else {"memory": mem}),
                **({} if not self._host else {"host": {
                    "iterations": self.host_iterations,
                    "host_ns": self.host_ns,
                    "cpu_ns": self.host_cpu_ns,
                    "offcpu_ns": self.host_ns - self.host_cpu_ns,
                    "wait_ns": self.host_wait_ns,
                    "gc_ns": sum(self.gc_ns_by_gen),
                    "gc_collections": list(self.gc_collections),
                    "gc_ns_by_gen": list(self.gc_ns_by_gen),
                    "stalls": self.stalls,
                    "stall_host_ns": self.stall_host_ns,
                    "stall_cpu_ns": self.stall_cpu_ns,
                    "stall_gc_ns": self.stall_gc_ns,
                    "spans_dropped": self.spans_dropped,
                }}),
                **({} if not self._sharded else {"sharding": {
                    # prefill-slice vs decode-slice step latency: the
                    # disaggregation split's two phases side by side
                    "prefill_step_ms":
                        self.prefill_step_s.summary(scale=1e3),
                    "decode_step_ms":
                        self.token_latency_s.summary(scale=1e3),
                    "step_gap_ms": self.step_gap_s.summary(scale=1e3),
                    "per_shard_occupancy": self.shard_occupancy,
                    "collective_ms": round(self.collective_s * 1e3, 3),
                    "collective_events": self.collective_events,
                    "collective_time_share": round(
                        self.collective_s /
                        max(1e-9, self.collective_s + self.decode_time_s
                            + sum(self.prefill_step_s._buf)), 4),
                }}),
                **({} if self.cache_bytes is None else {"cache": {
                    "bytes": dict(self.cache_bytes),
                    "ring_wraps": self.cache_counts["ring_wraps"],
                    "state_resets": self.cache_counts["state_resets"],
                    "prefill_tokens": self.cache_counts["prefill_tokens"],
                }}),
                **({} if self.expert_counts is None else {"experts": {
                    "token_slots": self.expert_counts["token_slots"],
                    "held_slots": self.expert_counts["held_slots"],
                    "load_max": self.expert_counts["load_max"],
                    "dropped_slots": self.expert_counts["dropped_slots"],
                }}),
                **({} if self.pages_in_use is None else {"paging": {
                    "pages_in_use": self.pages_in_use,
                    "pages_free": self.pages_free,
                    "pages_total": self.pages_in_use
                    + (self.pages_free or 0),
                    "page_iterations": self.page_iterations,
                    "table_entries_total": self.table_entries_total,
                    "live_page_iterations": self.live_page_iterations,
                    "pages_per_block": self.pages_per_block,
                    "live_block_iterations": self.live_block_iterations,
                    "prefix_hits": self.prefix_hits,
                    "prefix_misses": self.prefix_misses,
                    "prefix_hit_rate": round(
                        self.prefix_hits /
                        max(1, self.prefix_hits + self.prefix_misses),
                        3),
                    "page_waits": self.page_waits,
                    "oom_evictions": self.oom_evictions,
                    "bytes_per_active_token":
                        self.bytes_per_token.summary(digits=1),
                }}),
                **({} if not self._slo else {"slo": {
                    "preemptions": self.preemptions,
                    "resumes": self.resumes,
                    "replay_tokens": self.replay_tokens,
                    "chunked_prefills": self.chunked_prefills,
                    "chunks": self.chunks,
                    "ttft_attainment": {
                        n: round(c["ttft_ok"] / max(1, c["n"]), 4)
                        for n, c in self.slo_finishes.items()},
                    "tpot_attainment": {
                        n: round(c["tpot_ok"] / max(1, c["n"]), 4)
                        for n, c in self.slo_finishes.items()},
                    "wfq_lag_by_tenant": dict(self.wfq_lag),
                }}),
                **({} if not self._prefix_recorded else {"prefix": {
                    "whole_hits": self.prefix_whole_hits,
                    "partial_hits": self.prefix_partial_hits,
                    "misses": self.prefix_misses,
                    "hit_token_ratio": round(
                        self.prefix_matched_tokens /
                        max(1, self.prefix_prompt_tokens), 4),
                    "cow_copies": self.cow_copies,
                    "trie_nodes": self.trie_nodes or 0,
                    "trie_pages": self.trie_pages or 0,
                    "digests": self.trie_counts.get("digests", 0),
                    "evictions": self.trie_counts.get("evictions", 0),
                    "evict_visits": self.trie_counts.get(
                        "evict_visits", 0),
                }}),
            }


class ServingCallback:
    """hapi-style hook surface: subclass, override what you need, pass
    instances to the engine/server. Every hook is a no-op by default;
    hooks run on the engine thread, so keep them cheap."""

    def on_submit(self, request):
        pass

    def on_reject(self, request, reason):
        pass

    def on_join(self, request, slot):
        pass

    def on_token(self, request, token):
        pass

    def on_finish(self, request):
        pass

    def on_iteration(self, stats):
        pass


class CallbackList:
    """Fan-out invoker (mirrors hapi.callbacks.CallbackList): exceptions
    in one hook never take down the serving loop — they are reported to
    `on_error(hook_name, exc)` (the engine routes it into
    ServingMetrics.record_error) instead of vanishing."""

    def __init__(self, callbacks=(), on_error=None):
        self.callbacks = list(callbacks)
        self.on_error = on_error

    def append(self, cb):
        self.callbacks.append(cb)

    def emit(self, name, *args):
        for cb in self.callbacks:
            fn = getattr(cb, name, None)
            if fn is None:
                continue
            try:
                fn(*args)
            except Exception as e:
                if self.on_error is not None:
                    self.on_error(name, e)
