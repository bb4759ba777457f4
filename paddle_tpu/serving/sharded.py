"""Mesh-sharded serving: tp/fsdp-parallel decode over the slot pool.

The single-chip engines (engine.py) bound the servable model by one
device's memory and pin the pool size to one chip. This module runs the
SAME slot lifecycle over a `parallel.mesh` Mesh:

  * **weights** lay out tp/fsdp via `parallel.sharding`'s
    `serving_param_rules` — the default ``"gathered"`` layout shards
    every large weight's output-feature dim (vocab dim for embeddings)
    jointly over (fsdp, tp), so the SPMD partitioner materializes
    activations by all-gather (concatenation), never by partial-sum
    psum: float reduction order is untouched and every request's tokens
    stay BIT-IDENTICAL to the single-chip engine. ``layout="megatron"``
    flips to the canonical TP layout (contraction dims split, psum per
    matmul) where interconnect bandwidth beats the bit-exact contract;
  * **the slot pool** shards its slot axis data-parallel over ``dp``:
    pooled `StaticKVCache` rows / `PagedKVCache` pages + scales,
    per-row write indices, bias rows, memory rows, cross-attn K/V, and
    the paged engine's table/index all carry `PartitionSpec("dp")`
    leading dims, pinned with `with_sharding_constraint` on EVERY carry
    of the decode step — the pool scales with the mesh;
  * **the decode step stays ONE jitted per-pool-config call**: the
    engine bodies are the single-chip ones (engine.py `_*_body`),
    re-wrapped in sharding annotations (`ops.attention.decode_shardings`
    spec-annotates the unchanged decode kernels) — joins, evictions and
    page maps never retrace, proven by the same `trace_counts` keys;
  * **prefill/decode disaggregation** (``prefill="disaggregated"``):
    the dp axis is carved into a decode slice and a prefill slice
    (`DeviceMesh.slice_axis`), prompt prefill runs asynchronously on
    the prefill slice's own weight copy, and the finished K/V is
    spliced into the live pool (`static_kv_splice`/`splice_rows` with
    the pool constraints) once its arrays are ready — a long-prompt
    join no longer blocks the decode step, which shows up directly in
    the `step_gap_ms` (decode-step inter-arrival) metric the
    `serving_sharded` bench A/Bs.

Numerics contract (fp32, ``layout="gathered"``): every request's token
stream bit-matches both the single-chip `ServingEngine` and a solo
`generate_eager` run — tests/test_serving_sharded.py soaks it on the
8-device CPU mesh with ragged arrivals, chaos cells, and the
single-trace-per-bucket proof.
"""
from __future__ import annotations

import time

import numpy as np

from ..core.bucketing import bucket_size, pad_prompt_row
from ..testing import faults
from . import tracing as _rt
from .engine import (PagedServingEngine, ServingEngine, _PT_PREFILL,
                     _tree_bytes)
from .layers import named_program

__all__ = ["ShardedServingEngine", "ShardedPagedServingEngine"]

#: fault point for the disaggregated splice (prefill-slice K/V landing
#: in the live pool) — chaos tests pin per-request isolation on it
_PT_SPLICE = faults.point("serving.prefill_splice")


class ShardedServingEngine(ServingEngine):
    """`ServingEngine` over a device mesh. Use exactly like the
    single-chip engine; extra knobs:

      mesh       parallel.DeviceMesh (default: the installed global
                 mesh). Needs a ``dp`` axis whose size divides
                 `num_slots`; ``fsdp``/``tp`` axes engage weight
                 sharding when present (absent axes are dropped from
                 the rules, so the same engine runs on a dp-only mesh).
      rules      parallel.ShardingRules for the step-net weights
                 (default: `serving_param_rules(layout)`).
      layout     "gathered" (bit-exact, default) | "megatron".
      prefill    "inline" (joins block, single-chip semantics) |
                 "disaggregated" (prompt prefill runs on a dedicated
                 dp slice with its own weight copy; joins splice in
                 asynchronously).
      prefill_dp how many dp rows the prefill slice takes (default 1).

    `paged=True` routes to `ShardedPagedServingEngine` the same way
    `ServingEngine(paged=True)` routes to the paged pool.

    Weights are PLACED at construction: after updating the underlying
    layers call `refresh_params()` to re-place them on the mesh.
    """

    _accepts_sharded_params = True

    def __new__(cls, *args, **kw):
        if cls is ShardedServingEngine and kw.get("paged"):
            return object.__new__(ShardedPagedServingEngine)
        return object.__new__(cls)

    def __init__(self, decoder, embed, project, *, mesh=None, rules=None,
                 layout="gathered", prefill="inline", prefill_dp=1,
                 num_slots=8, max_len=128, **kw):
        from ..parallel.mesh import get_mesh
        from ..parallel.sharding import serving_param_rules

        self._mesh = mesh if mesh is not None else get_mesh()
        self._rules = rules if rules is not None \
            else serving_param_rules(layout)
        self.layout = layout
        if prefill not in ("inline", "disaggregated"):
            raise ValueError(
                f"prefill policy must be 'inline' or 'disaggregated', "
                f"got {prefill!r}")
        self._prefill_policy = prefill
        dp = self._mesh.axis_size("dp")
        if prefill == "disaggregated":
            prefill_dp = int(prefill_dp)
            if dp < prefill_dp + 1:
                raise ValueError(
                    f"disaggregated prefill needs dp >= {prefill_dp + 1} "
                    f"(a decode slice plus {prefill_dp} prefill row(s)); "
                    f"mesh has dp={dp}")
            self._decode_dm = self._mesh.slice_axis(
                "dp", 0, dp - prefill_dp)
            self._prefill_dm = self._mesh.slice_axis(
                "dp", dp - prefill_dp, dp)
        else:
            self._decode_dm = self._mesh
            self._prefill_dm = None
        self._pool_dp = max(1, self._decode_dm.axis_size("dp"))
        if int(num_slots) % self._pool_dp:
            raise ValueError(
                f"num_slots ({num_slots}) must be divisible by the "
                f"decode slice's dp axis ({self._pool_dp}) — the slot "
                f"pool shards over it")
        self._pending_info = {}
        #: seconds a dispatched prefill may stay not-ready before
        #: _poll_pending stops polling and blocks for it (see there)
        self.poll_block_s = 0.5
        super().__init__(decoder, embed, project, num_slots=num_slots,
                         max_len=max_len, **kw)
        self._build_shardings()
        self._place_params()

    # ------------------------------------------------------------------
    # placement
    # ------------------------------------------------------------------
    def _build_shardings(self):
        import jax
        from jax.sharding import PartitionSpec as P

        mesh = self._decode_dm.mesh
        self._ns_pool = jax.sharding.NamedSharding(mesh, P("dp"))
        self._ns_repl = jax.sharding.NamedSharding(mesh, P())

    def _place_params(self):
        """device_put the step-net weights onto the mesh per the layout
        rules (and, when disaggregated, a second copy onto the prefill
        slice). Timed into the collective budget — placement is the
        engine-driven cross-device traffic operators should see."""
        import jax

        from ..parallel.sharding import fitted_sharding, infer_param_specs

        t0 = time.monotonic()
        params = self._fm.params()
        specs = infer_param_specs(params, self._rules)
        self._sparams = {
            n: jax.device_put(v, fitted_sharding(v.shape, specs[n],
                                                 self._decode_dm))
            for n, v in params.items()}
        self._sbuffers = {
            n: jax.device_put(v, self._ns_repl)
            for n, v in self._fm.buffers().items()}
        if self._prefill_dm is not None:
            import jax.sharding as jsh
            from jax.sharding import PartitionSpec as P

            self._pparams = {
                n: jax.device_put(v, fitted_sharding(
                    v.shape, specs[n], self._prefill_dm))
                for n, v in params.items()}
            self._pbuffers = {
                n: jax.device_put(v, jsh.NamedSharding(
                    self._prefill_dm.mesh, P()))
                for n, v in self._fm.buffers().items()}
        self.metrics.record_collective(time.monotonic() - t0)

    def refresh_params(self):
        """Re-place the (possibly updated) layer weights onto the mesh;
        compiled programs are pure and stay cached."""
        self._place_params()
        self._weights_bytes = None   # ledger cache: shapes may change

    # ---- adapter banks on the mesh ----
    def _placed_banks(self):
        """The LoRA banks REPLICATED on the decode mesh (they are tiny
        next to the base weights and every dp shard gathers from
        them), re-placed only when a hot-load bumps the pool version —
        a steady pool pays one int compare per dispatch."""
        pool = self._apool
        cached = getattr(self, "_banks_placed", None)
        if cached is not None and cached[0] == pool.version:
            return cached[1]
        import jax

        placed = jax.device_put(pool.banks(), self._ns_repl)
        self._banks_placed = (pool.version, placed)
        return placed

    def _prefill_banks(self):
        """The banks replicated on the PREFILL slice's mesh (the
        disaggregated prefill program's copy)."""
        pool = self._apool
        cached = getattr(self, "_banks_prefill", None)
        if cached is not None and cached[0] == pool.version:
            return cached[1]
        import jax
        import jax.sharding as jsh
        from jax.sharding import PartitionSpec as P

        placed = jax.device_put(
            pool.banks(),
            jsh.NamedSharding(self._prefill_dm.mesh, P()))
        self._banks_prefill = (pool.version, placed)
        return placed

    def _prefill_adapter_args(self, row):
        if self._apool is None:
            return ()
        import jax.numpy as jnp

        return (jnp.int32(row), self._prefill_banks())

    def _params(self):
        return self._sparams

    def _buffers(self):
        return self._sbuffers

    def weights_bytes(self):
        """GLOBAL logical weight bytes across the mesh: the placed
        decode-slice copy plus, under disaggregation, the prefill
        slice's second copy (each addressable shard holds 1/n of a
        sharded leaf; replicated leaves cost the full size per device
        — the ledger reports the logical total, the number capacity
        planning sums against per-chip HBM)."""
        if self._weights_bytes is None:
            b = _tree_bytes(self._sparams) + _tree_bytes(self._sbuffers)
            if self._prefill_dm is not None:
                b += _tree_bytes(self._pparams) + \
                    _tree_bytes(self._pbuffers)
            self._weights_bytes = b
        return self._weights_bytes

    # ------------------------------------------------------------------
    # sharded compilation + pool-state placement: the ShardedPlacement
    # layer (serving/layers.py) wraps the SAME single-chip bodies in
    # the mesh annotations and lays fresh pool state out over dp
    # ------------------------------------------------------------------
    def _make_placement(self):
        from .layers import ShardedPlacement

        return ShardedPlacement(self)

    def _ensure_state(self, memory):
        if self._state is not None:
            return
        super()._ensure_state(memory)
        self._state = self.placement.place_state(self._state)

    # ------------------------------------------------------------------
    # shard-aware slot policy + gauges
    # ------------------------------------------------------------------
    def _shard_of(self, s):
        return s // (self.num_slots // self._pool_dp)

    def _shard_occupancies(self):
        per = self.num_slots // self._pool_dp
        return [sum(self.slots[g * per + i] is not None
                    for i in range(per)) / per
                for g in range(self._pool_dp)]

    def _choose_slot(self, free):
        """Balance occupancy across the dp shards of the slot axis so
        one mesh row never saturates while another idles."""
        occ = self._shard_occupancies()
        return min(free, key=lambda s: (occ[self._shard_of(s)], s))

    def _iteration_gauges(self):
        gauges = dict(super()._iteration_gauges() or {})
        gauges["shard_occupancy"] = self._shard_occupancies()
        return gauges

    # ------------------------------------------------------------------
    # disaggregated prefill: dispatch on the prefill slice, splice
    # asynchronously into the live pool
    # ------------------------------------------------------------------
    def _join(self, s, r):
        if self._prefill_dm is None:
            return super()._join(s, r)
        return self._dispatch_prefill(s, r)

    def _dispatch_prefill(self, s, r):
        import jax.numpy as jnp

        _PT_PREFILL()
        self._ensure_state(r.memory)
        row = self._acquire_adapter(r)
        pad_id = int(r.eos_id) if r.eos_id is not None else 0
        prompt_b, P0, Pb = pad_prompt_row(r.prompt, pad_id)
        key = ("prefill", Pb)
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._build_prefill(Pb)
            self._compiled[key] = fn
            fn = self._compiled[key]   # the observed wrapper
        mem = np.asarray(r.memory, self._np_dtype)[None]
        try:
            outs = fn(self._pparams, self._pbuffers,
                      jnp.asarray(prompt_b),
                      jnp.asarray([P0], jnp.int32), jnp.asarray(mem),
                      *self._prefill_adapter_args(row))
        except Exception:
            self._release_adapter_row(row)
            raise
        self._adapter_rows[s] = row
        self._pending.add(s)
        self._pending_info[s] = {
            "req": r, "outs": outs, "mem": mem, "Pb": Pb,
            "prompt": np.asarray(prompt_b, np.int32), "P0": P0,
            "t0": time.monotonic()}
        return None   # token 0 is delivered by the splice

    def _build_prefill(self, Pb):
        """The prefill-slice program: the single-chip join's prefill
        half (prompt -> batch-1 K/V + first token), no pool splice —
        it runs on the prefill mesh's own weight copy and its outputs
        travel to the decode slice when ready."""
        import jax
        import jax.numpy as jnp

        from ..text.generation import NEG

        fm = self._fm
        decoder = self._net.decoder
        L = self._pool_len
        key = ("prefill", Pb)
        neg = float(NEG)

        from ..ops import attention as A

        @A.partitioned_trace()   # weights shard over the prefill slice
        def prefill_fn(params, buffers, prompt, length, memory, *ad):
            self.trace_counts[key] += 1  # one per trace = one compile
            kpos = jnp.arange(L, dtype=jnp.int32)
            hole = (kpos[None, :] >= length[:, None]) & \
                (kpos[None, :] < jnp.int32(Pb))
            bias_row = jnp.where(hole, jnp.float32(neg),
                                 jnp.float32(0.0))           # [1, L]
            positions = jnp.arange(Pb, dtype=jnp.int32)[None]
            inc0 = [layer.self_attn.gen_cache(
                None, max_length=Pb, batch_size=1, dtype=memory.dtype)
                for layer in decoder.layers]
            with self._lora_ctx(ad):
                (lg, inc1, static1), _ = fm.apply(
                    params, buffers, None, prompt, positions, memory,
                    training=False, tgt_mask=bias_row[:, :Pb],
                    memory_mask=None, inc=inc0, prefill=True)
            last = jnp.take_along_axis(
                lg, (length - 1)[:, None, None], axis=1)[:, 0]
            tok0 = last.argmax(-1).astype(jnp.int32)[0]
            kvs = [(c.k, c.v) for c in inc1]
            return tok0, kvs, static1, bias_row

        return jax.jit(named_program(key, prefill_fn))

    def _splice_math(self, Pb):
        """The per-entry splice math (no trace counter): land one
        travelled prefill's K/V + bias + memory + first token in the
        pool at the traced slot — `static_kv_splice`/`splice_rows`
        with the pool constraints. Shared verbatim by the single-entry
        splice program and the batched scan over it."""
        import jax
        import jax.numpy as jnp

        from ..nn.layer.transformer import MultiHeadAttention as MHA

        ns, ns1 = self._ns_pool, self._ns_pool
        L = self._pool_len
        spec = bool(self.spec_k)

        def splice(state, slot, tok0, bias_row, kvs, statics,
                   memory, prompt, length):
            new_inc = [MHA.static_kv_splice(pool, slot, k, v,
                                            jnp.int32(Pb),
                                            constraint=(ns, ns1))
                       for pool, (k, v) in zip(state["inc"], kvs)]
            new_static = [
                (MHA.splice_rows(pk, slot, sk, constraint=ns),
                 MHA.splice_rows(pv, slot, sv, constraint=ns))
                for (pk, pv), (sk, sv) in zip(state["static"], statics)]
            out = dict(
                state,
                tok=jax.lax.with_sharding_constraint(
                    jax.lax.dynamic_update_slice(
                        state["tok"], tok0[None], (slot,)), ns),
                bias=MHA.splice_rows(state["bias"], slot, bias_row,
                                     constraint=ns),
                mem=MHA.splice_rows(state["mem"], slot, memory,
                                    constraint=ns),
                inc=new_inc, static=new_static)
            if spec:
                hist_row = jnp.concatenate(
                    [prompt, jnp.zeros((1, L - Pb), jnp.int32)], 1)
                out["hist"] = MHA.splice_rows(state["hist"], slot,
                                              hist_row, constraint=ns)
                out["plen"] = jax.lax.with_sharding_constraint(
                    jax.lax.dynamic_update_slice(
                        state["plen"], length.astype(jnp.int32),
                        (slot,)), ns)
                out["pbk"] = jax.lax.with_sharding_constraint(
                    jax.lax.dynamic_update_slice(
                        state["pbk"], jnp.full((1,), Pb, jnp.int32),
                        (slot,)), ns)
            return out

        return splice

    def _build_splice(self, Pb):
        """The decode-slice half of one disaggregated join, as its own
        program — the single-entry path."""
        import jax

        key = ("splice", Pb)
        math = self._splice_math(Pb)

        def splice_fn(state, slot, tok0, bias_row, kvs, statics,
                      memory, prompt, length):
            self.trace_counts[key] += 1
            return math(state, slot, tok0, bias_row, kvs, statics,
                        memory, prompt, length)

        # the pool carry donates like the rest of the join family (the
        # shared _DONATED_KINDS declaration the PTA102 audit reads) —
        # the splice lands in the pool in place, no whole-pool copy
        return jax.jit(named_program(key, splice_fn),
                       donate_argnums=self._donate_argnums(key))

    def _build_batched_splice(self, Pb, nb):
        """`nb` ready prefills of one bucket land in the pool as ONE
        program: a `lax.scan` of the per-entry splice math over the
        stacked entries. Entry counts bucket to powers of two and the
        pad repeats entry 0 — splicing the same (slot, data) twice is
        idempotent, so padding never corrupts state. One dispatch per
        (bucket, count-bucket) instead of one per request: a join
        burst stops serializing `_poll_pending`."""
        import jax

        key = ("bsplice", Pb, nb)
        math = self._splice_math(Pb)

        def bsplice_fn(state, slots, tok0s, bias_rows, kvss, staticss,
                       memories, prompts, lengths):
            self.trace_counts[key] += 1

            def body(st, xs):
                slot, tok0, bias_row, kvs, statics, memory, prompt, \
                    length = xs
                return math(st, slot, tok0, bias_row, kvs, statics,
                            memory, prompt, length), None

            st, _ = jax.lax.scan(
                body, state, (slots, tok0s, bias_rows, kvss, staticss,
                              memories, prompts, lengths))
            # the per-entry constraints live inside the scan body; pin
            # the final carry too so the program's OUTPUT layout is
            # explicit (the every-carry contract the analyzer audits)
            return self.placement.constrain_state(st)

        return jax.jit(named_program(key, bsplice_fn),
                       donate_argnums=self._donate_argnums(key))

    def _fail_pending_splice(self, s, r, e):
        """Per-request isolation: the failed splice kills THIS
        request's future, frees the slot, pool keeps serving."""
        self._vacate(s)
        r.slot = None
        if r._trace is not None:
            _rt.on_splice_end(r, ok=False, error=e)
        self.metrics.record_error("prefill_splice", e)
        r.fail(e, self.clock())
        self.metrics.record_finish("error", len(r.tokens))
        self._cbs.emit("on_finish", r)

    def _finish_splice(self, s, r, tok0):
        self._pending.discard(s)
        self._pending_info.pop(s, None)
        if r._trace is not None:
            _rt.on_splice_end(r, ok=True)
        self._deliver(r, tok0, self.clock())

    def _splice_one(self, s, info, r, deferred=None):
        """Single ready prefill: the per-bucket splice program. With a
        `deferred` list the first-token resolution is batched out of
        the dispatch path: the (slot, request, traced tok0) triple is
        appended and _poll_pending finishes the whole round after its
        LAST dispatch (one host sync, not one per splice)."""
        import jax
        import jax.numpy as jnp

        Pb = info["Pb"]
        try:
            t1 = time.monotonic()
            moved = jax.device_put(info["outs"], self._ns_repl)
            jax.block_until_ready(moved)
            self.metrics.record_collective(time.monotonic() - t1)
            fn = self._program(("splice", Pb),
                               lambda: self._build_splice(Pb))
            tok0, kvs, statics, bias_row = moved
            self._state = fn(self._state, jnp.int32(s), tok0,
                             bias_row, kvs, statics,
                             jnp.asarray(info["mem"]),
                             jnp.asarray(info["prompt"]),
                             jnp.asarray([info["P0"]], jnp.int32))
        except Exception as e:
            self._fail_pending_splice(s, r, e)
            if not self._carry_alive():
                # the donated carry died mid-splice with no
                # replacement: every co-resident slot is poisoned —
                # all-or-nothing recovery rebuilds the pool
                self._fail_active(e)
            return False
        if deferred is None:
            self._finish_splice(s, r, int(tok0))
        else:
            deferred.append((s, r, tok0))
        return True

    def _splice_batch(self, Pb, ss):
        """>= 2 ready prefills of one bucket: stack their travelled
        outputs, move them to the decode slice in one transfer, and
        land them with ONE scanned program. A dispatch failure fails
        only the batch's requests (the pool keeps serving); the
        fault-point gate already ran per entry, so injected faults
        keep per-request isolation."""
        import jax
        import jax.numpy as jnp

        infos = [self._pending_info[s] for s in ss]
        reqs = [self.slots[s] for s in ss]
        nb = bucket_size(len(ss))
        pad = [0] * (nb - len(ss))        # repeat entry 0: idempotent
        ix = list(range(len(ss))) + pad
        try:
            t1 = time.monotonic()
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[infos[i]["outs"] for i in ix])
            moved = jax.device_put(stacked, self._ns_repl)
            jax.block_until_ready(moved)
            self.metrics.record_collective(time.monotonic() - t1)
            fn = self._program(
                ("bsplice", Pb, nb),
                lambda: self._build_batched_splice(Pb, nb))
            tok0s, kvss, staticss, bias_rows = moved
            slots = jnp.asarray([ss[i] for i in ix], jnp.int32)
            mems = jnp.asarray(np.stack(
                [np.asarray(infos[i]["mem"]) for i in ix]))
            prompts = jnp.asarray(np.stack(
                [infos[i]["prompt"] for i in ix]))
            lengths = jnp.asarray(
                [[infos[i]["P0"]] for i in ix], jnp.int32)
            self._state = fn(self._state, slots, tok0s, bias_rows,
                             kvss, staticss, mems, prompts, lengths)
            toks = np.asarray(tok0s)
        except Exception as e:
            for s, r in zip(ss, reqs):
                self._fail_pending_splice(s, r, e)
            if not self._carry_alive():
                self._fail_active(e)
            return False
        for i, (s, r) in enumerate(zip(ss, reqs)):
            self._finish_splice(s, r, int(toks[i]))
        return True

    def _poll_pending(self, now):
        """Splice every finished prefill into the pool. Runs once per
        iteration; a prefill whose arrays are not ready yet just stays
        pending (the decode step keeps running without it). Ready
        prefills GROUP by prompt bucket: each group past one entry
        lands via the batched-splice program — one dispatch per
        bucket, not one per request."""
        if not self._pending:
            return False
        import jax

        ready = []
        for s in sorted(self._pending):
            if s in self._chunking:
                # mid chunked-prefill, not a disaggregated splice:
                # _advance_chunks owns this slot's pending state
                continue
            info = self._pending_info.get(s)
            r = self.slots[s]
            if info is None or r is None:   # evicted while pending
                self._pending.discard(s)
                self._pending_info.pop(s, None)
                continue
            leaves = jax.tree_util.tree_leaves(info["outs"])
            if not all(getattr(x, "is_ready", lambda: True)()
                       for x in leaves):
                # bounded-wait escape valve: an AOT-precompiled
                # prefill dispatches asynchronously, and on a
                # starved host (1-core box, idle pool spinning this
                # poll) its arrays may never flip ready on their
                # own — past the deadline, block for them. The
                # overlap win is gone by then anyway; liveness wins.
                # When NOTHING is decoding (every occupied slot is
                # itself pending) the poll loop is a pure spin, so
                # there is no overlap to protect: block right away —
                # a fast driver can burn its iteration budget before
                # poll_block_s of wall time ever elapses.
                spin = self.occupancy() == len(self._pending)
                if (not spin and
                        time.monotonic() - info["t0"] < self.poll_block_s):
                    continue
                jax.block_until_ready(info["outs"])
            self.metrics.record_prefill_step(
                time.monotonic() - info["t0"])
            # the fault-point gate fires PER REQUEST before any
            # batching, so an injected splice fault isolates exactly
            # one request whether or not its bucket batches
            try:
                _PT_SPLICE()
            except Exception as e:
                self._fail_pending_splice(s, r, e)
                continue
            ready.append(s)
        groups = {}
        for s in ready:
            groups.setdefault(self._pending_info[s]["Pb"],
                              []).append(s)
        activated = False
        deferred = []   # (slot, request, traced tok0) per single splice
        for Pb, ss in sorted(groups.items()):
            if len(ss) == 1:
                s = ss[0]
                activated |= self._splice_one(
                    s, self._pending_info[s], self.slots[s], deferred)
            else:
                activated |= self._splice_batch(Pb, ss)
        # resolve the round's first tokens after the LAST dispatch —
        # one natural host sync instead of a blocking int() per splice
        for s, r, t in deferred:
            self._finish_splice(s, r, int(t))
        return activated

    def _evict(self, s):
        self._pending.discard(s)
        self._pending_info.pop(s, None)
        super()._evict(s)

    # ------------------------------------------------------------------
    # zero-warmup startup: the sharded program set
    # ------------------------------------------------------------------
    def _program_fingerprint(self):
        # mesh geometry + prefill policy change the compiled programs'
        # layouts: fold them into the persistent-cache identity
        return (f"{super()._program_fingerprint()}|"
                f"dp{self._pool_dp}|{self._prefill_policy}|"
                f"{self.layout}")

    def _startup_programs(self, prompt_buckets):
        progs = super()._startup_programs(prompt_buckets)
        if self._prefill_dm is None:
            return progs
        import jax
        import jax.numpy as jnp

        decoder = self._net.decoder
        M, Dm = self._mem_shape
        dt = jnp.dtype(self._np_dtype)
        mem1 = jnp.zeros((1, M, Dm), dt)
        one = jnp.asarray([1], jnp.int32)
        L = self._pool_len
        state = self._state
        repl = self._ns_repl
        pad = self._prefill_adapter_args(0)
        for Pb in sorted({bucket_size(int(p)) for p in prompt_buckets}):
            progs.append((
                ("prefill", Pb),
                lambda Pb=Pb: self._build_prefill(Pb),
                (self._pparams, self._pbuffers,
                 jnp.zeros((1, Pb), jnp.int32), one, mem1) + pad))
            # the splice half sees the travelled prefill outputs
            # REPLICATED on the decode slice (_poll_pending device_puts
            # them to _ns_repl before the call) — mirror that placement
            # so the AOT executable's input layouts match the hot path
            kvs = [jax.device_put(
                (jnp.zeros((1, ly.self_attn.num_heads, Pb,
                            ly.self_attn.head_dim), dt),) * 2, repl)
                for ly in decoder.layers]
            statics = [jax.device_put(
                (jnp.zeros((1, ly.cross_attn.num_heads, M,
                            ly.cross_attn.head_dim), dt),) * 2, repl)
                for ly in decoder.layers]
            progs.append((
                ("splice", Pb),
                lambda Pb=Pb: self._build_splice(Pb),
                (state, jnp.int32(0),
                 jax.device_put(jnp.int32(0), repl),
                 jax.device_put(jnp.zeros((1, L), jnp.float32), repl),
                 kvs, statics, mem1, jnp.zeros((1, Pb), jnp.int32),
                 one)))
            # the batched-splice program for a 2-burst (larger bursts
            # bucket up and compile on first use): warm-started AND
            # audited by the program analyzer alongside the rest
            stack2 = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda *xs: jnp.stack(xs), t, t)
            progs.append((
                ("bsplice", Pb, 2),
                lambda Pb=Pb: self._build_batched_splice(Pb, 2),
                (state, jnp.zeros((2,), jnp.int32),
                 jax.device_put(jnp.zeros((2,), jnp.int32), repl),
                 jax.device_put(jnp.zeros((2, 1, L), jnp.float32),
                                repl),
                 jax.device_put(stack2(kvs), repl),
                 jax.device_put(stack2(statics), repl),
                 jnp.zeros((2, 1, M, Dm), dt),
                 jnp.zeros((2, 1, Pb), jnp.int32),
                 jnp.ones((2, 1), jnp.int32))))
        return progs

    def _inflight_prefills(self):
        return len(self._pending)


class ShardedPagedServingEngine(ShardedServingEngine, PagedServingEngine):
    """`ShardedServingEngine(..., paged=True)`: the paged pool's host
    bookkeeping (allocator, prefix cache, COW, page tables) is
    unchanged; the DEVICE side shards the page/scale arrays over dp
    alongside the slot-leading state, so cache memory scales with the
    mesh while page mapping stays a traced input that never retraces.
    Page reads/writes are pure selection (gather/scatter), so dp-laid
    pages keep the bit-exactness contract of `kv_dtype=None`.

    Disaggregated prefill is not wired through the paged join yet
    (prefix-attach and COW interleave with allocation host-side);
    constructing with ``prefill="disaggregated"`` raises."""

    def __init__(self, decoder, embed, project, *, prefill="inline",
                 **kw):
        if prefill != "inline":
            raise NotImplementedError(
                "ShardedPagedServingEngine supports prefill='inline' "
                "only (disaggregation of the paged join — prefix "
                "attach + COW — is a follow-up); use the dense "
                "ShardedServingEngine for disaggregated prefill")
        kw.pop("paged", None)
        super().__init__(decoder, embed, project, prefill="inline",
                         **kw)

    def _decode_block_pages(self, storage):
        """1: a partitioned program holds no Pallas kernel; every page
        read here is the gather."""
        return 1

    def _cross_params(self):
        if getattr(self, "_scross", None) is None:
            import jax

            self._scross = {
                n: jax.device_put(v, self._ns_repl)
                for n, v in self._fm_cross.params().items()}
        return self._scross

    def _check_params(self):
        prev = self._prefix_params
        super()._check_params()
        if prev is not None and self._prefix_params is not prev:
            # weights changed: re-place the mesh copies too
            self._scross = None
            self._place_params()
