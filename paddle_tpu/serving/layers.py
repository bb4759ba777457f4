"""Composable pool layers: the serving engines' program families as
orthogonal strategy objects over ONE slot-pool core.

Before this module, each serving capability lived in its own engine
subclass: the paged pool re-implemented the join/step program pair,
the sharded engine re-wrapped the single-chip bodies, and speculative
decoding was wired through the dense pool only — so every new
capability had to be built once per pool variant (and the paged pool
simply rejected `spec_k`). Here the `(dense|paged) x (single|sharded)
x (spec on|off)` grid is three independent axes:

  * **CacheLayout** (`DenseLayout` | `PagedLayout`) owns the pool's
    device-state shape and every traceable program body that touches
    it: state construction, the join/attach/cow programs, the plain
    batched step, and the speculative verify step. The paged layout's
    verify body is the NEW program of this family: a k-token
    `write_tokens` page write (boundary-crossing, grow-only int8
    rescale) + `paged_verify_attention` through the block table.
  * **Placement** (`SinglePlacement` | `ShardedPlacement`) owns how a
    body becomes a compiled program: plain `jax.jit` with the shared
    donation declaration, or the mesh-annotated wrap (decode-kernel
    sharding scope + a `with_sharding_constraint` pin on every
    returned pool carry) — the same body traces identically either
    way, so the trace-count keys never depend on placement.
  * **Stepper** (`PlainStepper` | `SpecStepper`) owns the per-
    iteration host dispatch: which program family runs one decode
    step, how the paged table/index ride in as traced inputs, and the
    adaptive effective-k controller (speculation only).

An engine is the composition `layout x placement x stepper`; the
public classes in engine.py/sharded.py are thin configuration shims.
Every body keeps its `trace_counts[key] += 1` side effect, so one
trace still means one compile wherever the body was built from.
"""
from __future__ import annotations

import time

import numpy as np

__all__ = ["DenseLayout", "PagedLayout", "SinglePlacement",
           "ShardedPlacement", "PlainStepper", "SpecStepper"]


# --------------------------------------------------------------------------
# stack drivers: what a pool layout asks of the model it serves
# --------------------------------------------------------------------------

class DecoderStackDriver:
    """The (decoder, embed, project) triple of `nn.TransformerDecoder`
    with a client-supplied cross-attention `memory`: every layer keeps
    its self-attention K/V in pages and a static cross-attention K/V a
    slot (computed from the memory at the join). A bucket's pad hole
    stays masked for ever and generation writes on from the bucket's
    end."""

    #: counters that leave a program behind its tokens: none
    counts = ()

    def __init__(self, eng):
        self.eng = eng

    def cache_kinds(self):
        return ["paged"] * len(self.eng._net.decoder.layers)

    # ---- what the engine asks about a request's memory ----
    def check_memory(self, r):
        if r.memory is None or r.memory.ndim != 2:
            raise ValueError("ServingEngine requests need a 2-D "
                             "cross-attention memory [M, D]")
        shape = self.eng._mem_shape
        if shape is not None and tuple(r.memory.shape) != shape:
            raise ValueError(
                f"memory shape {tuple(r.memory.shape)} != pool's "
                f"{shape} (fixed by the first join)")

    def pin_memory(self, memory, dtype="float32"):
        """-> (the example memory that fixes the pool, its shape, the
        pool's dtype). `memory` is an [M, D] array or its shape."""
        import jax.numpy as jnp

        if not (hasattr(memory, "ndim") or isinstance(memory, np.ndarray)):
            M, Dm = memory
            memory = np.zeros((int(M), int(Dm)), np.dtype(dtype))
        memory = np.asarray(memory)
        return memory, tuple(memory.shape), jnp.asarray(memory).dtype

    def join_memory(self, r):
        import jax.numpy as jnp

        return jnp.asarray(np.asarray(r.memory, self.eng._np_dtype)[None])

    def warm_memory(self):
        import jax.numpy as jnp

        M, Dm = self.eng._mem_shape
        return jnp.zeros((1, M, Dm), jnp.dtype(self.eng._np_dtype))

    def start_index(self, P0, Pb):
        return Pb

    def settle_page_options(self, prefix_cache, kv_dtype):
        """-> whether the radix prefix cache is on (None: the default)."""
        return True if prefix_cache is None else bool(prefix_cache)

    def count_advance(self, index, n_emit):
        """Cache counts of a decode step that moved `index` by `n_emit`."""

    # ---- the pool's state ----
    def new_slot_state(self, memory, dtype):
        """The per-slot kinds beside the pages."""
        import jax.numpy as jnp

        eng = self.eng
        S, L = eng.num_slots, eng._pool_len
        M, Dm = memory.shape
        return {"bias": jnp.zeros((S, L), jnp.float32),
                "mem": jnp.zeros((S, M, Dm), dtype),
                "static": self.new_static(M, dtype)}

    def new_paged(self, dtype):
        eng = self.eng
        out = []
        for layer in eng._net.decoder.layers:
            c = layer.self_attn.gen_paged_cache(
                eng.num_pages, eng.page_size, eng.num_slots,
                eng.max_pages, dtype, eng.kv_dtype)
            out.append({"k": c.k, "v": c.v, "ks": c.k_scale,
                        "vs": c.v_scale})
        return out

    def new_static(self, M, dtype):
        import jax.numpy as jnp

        out = []
        for layer in self.eng._net.decoder.layers:
            z = jnp.zeros((self.eng.num_slots, layer.cross_attn.num_heads,
                           M, layer.cross_attn.head_dim), dtype)
            out.append((z, z))
        return out

    def page_row_bytes(self, storage, quantized):
        """(bytes of one page over all paged layers, K and V)."""
        import jax.numpy as jnp

        eng = self.eng
        decoder = eng._net.decoder
        h0 = decoder.layers[0].self_attn
        per_buf = h0.num_heads * eng.page_size * h0.head_dim \
            * jnp.dtype(storage).itemsize
        scale_b = h0.num_heads * 4 if quantized else 0
        return 2 * len(decoder.layers) * (per_buf + scale_b)

    def pages_per_block(self, storage):
        """The pages a grid step of the stack's paged decode call takes
        (`ops.attention.paged_decode_block_pages`: from the pool's
        shapes and page dtype alone)."""
        from ..ops import attention as A

        eng = self.eng
        h0 = eng._net.decoder.layers[0].self_attn
        return A.paged_decode_block_pages(
            eng.page_size, h0.num_heads * h0.head_dim, eng.max_pages,
            storage)

    def prefill(self, params, buffers, prompt, length, memory, bias_row,
                Pb, ad):
        """-> (logits [1, Pb, V], {"paged": [(k, v) [1, H, Pb, D]],
        "static": [(k, v)]}: what a join splices into the slot, the
        program's counters: None)."""
        import jax.numpy as jnp

        eng = self.eng
        decoder = eng._net.decoder
        positions = jnp.arange(Pb, dtype=jnp.int32)[None]
        inc0 = [layer.self_attn.gen_cache(
            None, max_length=Pb, batch_size=1, dtype=memory.dtype)
            for layer in decoder.layers]
        with eng._lora_ctx(ad):
            (lg, inc1, static1), _ = eng._fm.apply(
                params, buffers, None, prompt, positions, memory,
                training=False, tgt_mask=bias_row[:, :Pb],
                memory_mask=None, inc=inc0, prefill=True)
        last = jnp.take_along_axis(
            lg, (length - 1)[:, None, None], axis=1)[:, 0]
        return last, {"paged": [(c.k, c.v) for c in inc1],
                      "static": static1}, None

    def step(self, params, buffers, state, table, index, ad, active):
        """One position a slot -> (logits [S, V], the state's lists that
        changed, the program's counters: None)."""
        from . import paging as PG

        eng = self.eng
        inc = [PG.PagedKVCache(pc["k"], pc["v"], pc["ks"], pc["vs"],
                               table, index) for pc in state["paged"]]
        posn = index[:, None]
        with eng._lora_ctx(ad):
            (lg, inc2), _ = eng._fm.apply(
                params, buffers, None, state["tok"][:, None], posn,
                state["mem"], training=False, tgt_mask=state["bias"],
                memory_mask=None, inc=inc, static_kv=state["static"],
                prefill=False)
        return lg[:, 0], {"paged": [
            {"k": c.k, "v": c.v, "ks": c.k_scale, "vs": c.v_scale}
            for c in inc2]}, None


class CausalLMDriver:
    """A decoder-only causal LM that says what each of its blocks keeps
    of a sequence (`cache_kinds()`: "recurrent", "ring", "paged",
    "latent" or None a block) and runs `prefill` and `decode` over that
    state (`text.models.Phi4FlashForCausalLM`, `DeepseekV3ForCausalLM`).
    Requests carry no memory; a prompt sits at positions [0, P0) and
    generation goes on from P0 (no pad hole: a recurrent state cannot
    step over one, and a rotary position counts from 0)."""

    #: the kinds of state a model's `prefill` / `decode` hand over
    KINDS = ("recurrent", "ring", "paged", "latent")

    def __init__(self, eng):
        self.eng = eng
        self.model = eng._net
        self.cfg = self.model.cfg
        kinds = self.cache_kinds()
        self.n_ring = kinds.count("ring")
        #: a recurrent state or a window ring a slot: nothing parks,
        #: snapshots or replays those
        self.keeps_state = any(k in ("recurrent", "ring") for k in kinds)
        #: one latent row a token a block (no K/V page program reads it)
        self.latent = "latent" in kinds
        #: names of the counters that leave a program behind its tokens
        #: (an expert layer's; `snapshot()["experts"]`)
        self.counts = tuple(getattr(self.model, "COUNTS", ()))

    def cache_kinds(self):
        return self.model.cache_kinds()

    # ---- what the engine asks about a request's memory: it has none ----
    def check_memory(self, r):
        if r.memory is not None and r.memory.size:
            raise ValueError(
                f"{type(self.model).__name__} is a decoder without "
                f"memory: its requests carry none (got an array of "
                f"shape {tuple(r.memory.shape)})")

    def pin_memory(self, memory, dtype=None):
        return None, (), self.model.embed_tokens._data.dtype

    def join_memory(self, r):
        return None

    def warm_memory(self):
        return None

    def start_index(self, P0, Pb):
        return P0

    def settle_page_options(self, prefix_cache, kv_dtype):
        """The radix prefix cache and preemption park K/V pages by
        refcount, which has no meaning for a scan state or a ring, and
        no join reads a prefix's latent rows through the table yet: a
        stack that keeps any of those is served with the cache OFF, and
        asking for it, for other page storage or for fewer pages than
        the slots can fill raises."""
        if not (self.keeps_state or self.latent):
            return True if prefix_cache is None else bool(prefix_cache)
        eng = self.eng
        name = type(self.model).__name__
        if self.keeps_state:
            keeps = "a scan state and window rings a slot"
            no_prefix = "a shared prefix's pages do not hold them"
            no_park = (f"{name}'s scan state and rings cannot be parked "
                       f"in pages and resumed")
        else:
            keeps = "one latent row a token a block"
            no_prefix = ("no join reads a prefix's latent rows through "
                         "the page table yet")
            no_park = (f"resuming {name} needs a join over parked latent "
                       f"pages, which no program does yet")
        if prefix_cache:
            raise ValueError(
                f"prefix cache (prefix_cache=True): {name} keeps {keeps}; "
                f"{no_prefix}, so no prefix is reused")
        if kv_dtype is not None:
            raise ValueError(
                f"page storage (kv_dtype={kv_dtype!r}): {name}'s "
                f"pages hold its own dtype; nothing rescales them")
        if eng.num_pages < eng.num_slots * eng.max_pages:
            raise ValueError(
                f"an oversubscribed page pool (num_pages="
                f"{eng.num_pages} < {eng.num_slots} slots x "
                f"{eng.max_pages} pages): a pool that runs dry "
                f"evicts or preempts a slot mid-sequence, and {no_park}")
        return False

    def count_advance(self, index, n_emit):
        if not self.n_ring:
            return
        # a ring wraps where a slot writes row 0 again
        at = index[n_emit > 0]
        self.eng._count_cache(ring_wraps=self.n_ring * int(
            ((at > 0) & (at % self.cfg.sliding_window == 0)).sum()))

    # ---- the pool's state ----
    def new_slot_state(self, memory, dtype):
        """The kinds beside the K/V pages that the stack keeps."""
        kinds = {"ring": self.new_ring(dtype),
                 "recurrent": self.new_recurrent(dtype),
                 "latent": self.new_latent(dtype)}
        return {k: v for k, v in kinds.items() if v}

    def new_paged(self, dtype):
        import jax.numpy as jnp

        eng, cfg = self.eng, self.cfg
        n = self.cache_kinds().count("paged")
        if not n:
            return []
        width = cfg.num_key_value_heads * cfg.head_dim
        buf = jnp.zeros((eng.num_pages + 1, eng.page_size, width), dtype)
        return [{"k": buf, "v": buf, "ks": None, "vs": None}] * n

    def new_latent(self, dtype):
        """ONE page array a latent block, [pages + 1, page_size, row
        width]: a token's row has no head axis and no separate V."""
        import jax.numpy as jnp

        eng = self.eng
        n = self.cache_kinds().count("latent")
        if not n:
            return []
        return [jnp.zeros((eng.num_pages + 1, eng.page_size,
                           self.cfg.latent_row_width), dtype)] * n

    def new_ring(self, dtype):
        import jax.numpy as jnp

        if not self.n_ring:
            return []
        cfg = self.cfg
        z = jnp.zeros((self.eng.num_slots, cfg.sliding_window,
                       cfg.num_key_value_heads * cfg.head_dim), dtype)
        return [(z, z)] * self.n_ring

    def new_recurrent(self, dtype):
        import jax.numpy as jnp

        cfg, S = self.cfg, self.eng.num_slots
        return [(jnp.zeros((S, cfg.d_conv - 1, cfg.d_inner), dtype),
                 jnp.zeros((S, cfg.d_state, cfg.d_inner), jnp.float32))
                for k in self.cache_kinds() if k == "recurrent"]

    def page_row_bytes(self, storage, quantized):
        """Bytes of one page over every block that keeps pages: K and V
        rows of a `paged` block, one row of a `latent` one."""
        import jax.numpy as jnp

        cfg, kinds = self.cfg, self.cache_kinds()
        values = 0
        if "paged" in kinds:
            values += 2 * kinds.count("paged") \
                * cfg.num_key_value_heads * cfg.head_dim
        if self.latent:
            values += kinds.count("latent") * cfg.latent_row_width
        return values * self.eng.page_size * jnp.dtype(storage).itemsize

    def pages_per_block(self, storage):
        """Pages a grid step of the stack's decode kernel takes, from the
        pool's shapes and page dtype alone; 1 where the step gathers."""
        cfg, eng = self.cfg, self.eng
        if "paged" in self.cache_kinds():       # K/V pages, grouped heads
            from ..ops import grouped_decode as GD
            return GD.decode_block_pages(
                eng.page_size, cfg.num_key_value_heads * cfg.head_dim,
                2 * cfg.head_dim, eng.max_pages, storage)
        from ..ops import latent_decode as LD
        return 1 if not self.latent else LD.decode_block_pages(
            eng.page_size, cfg.latent_row_width, cfg.kv_lora_rank,
            cfg.num_attention_heads, eng.max_pages, storage)

    def prefill(self, params, buffers, prompt, length, memory, bias_row,
                Pb, ad):
        (lg, new, counts), _ = self.eng._fm.apply(
            params, buffers, None, training=False, op="prefill",
            args=(prompt, length))
        return lg, new, counts

    def step(self, params, buffers, state, table, index, ad, active):
        (lg, parts, counts), _ = self.eng._fm.apply(
            params, buffers, None, training=False, op="decode",
            args=(state["tok"], index,
                  {k: state[k] for k in self.KINDS if k in state}, table,
                  active))
        return lg, parts, counts


# --------------------------------------------------------------------------
# cache layouts: pool state + the traceable program bodies
# --------------------------------------------------------------------------

class CacheLayout:
    """Base: the engine-agnostic program bodies (the draft proposal is
    pure jnp over per-slot rows — identical for every layout)."""

    def __init__(self, eng):
        self.eng = eng

    def __repr__(self):
        # folded into the persistent program-cache fingerprint: must
        # be stable across processes (no default object address repr).
        # Layouts are parameterless — pool geometry already lives in
        # the engine half of the fingerprint — so the class name is
        # the whole identity.
        return type(self).__name__

    @staticmethod
    def distinct_leaves(state):
        """Donated carries must not alias each other: the cache
        constructors share one zero buffer between K and V halves
        (cheap when the state is only read), but XLA rejects donating
        the same buffer twice in one call — give every repeated leaf
        its own buffer before the state becomes a donated carry."""
        import jax

        seen = set()

        def fix(x):
            if not hasattr(x, "copy"):
                return x
            if id(x) in seen:
                return x.copy()
            seen.add(id(x))
            return x

        return jax.tree_util.tree_map(fix, state)

    # ---- program-family keys ----
    def join_key(self, Pb):
        raise NotImplementedError

    def step_key(self):
        raise NotImplementedError

    def spec_step_key(self):
        raise NotImplementedError

    def draft_key(self):
        return ("draft",) + self.eng._pool_key

    # ---- host hooks the steppers drive ----
    def map_step_pages(self, active, width):
        """Make the next `width` write positions of every slot in
        `active` physically backed (paged: map pages, evicting a
        starved slot under oversubscription). Returns the
        possibly-updated active mask."""
        return active

    def step_extra_args(self):
        """Extra traced inputs the step programs take between the pool
        state and the per-slot masks (paged: the device table + per-
        slot write indices, shipped fresh so mapping never retraces)."""
        return ()

    def row_index(self):
        """Per-slot written-token counts, as a traced input for the
        draft proposal."""
        raise NotImplementedError

    def advance_rows(self, n_emit):
        """Advance host-owned write indices after a step delivered
        `n_emit` tokens per slot (dense carries its indices in-state —
        no-op)."""

    # ---- the draft proposal body (pure jnp, layout-independent) ----
    def draft_body(self, dkey):
        from ..text import speculative as SP

        eng = self.eng
        k, ngram = eng.spec_k, eng.spec_ngram

        def draft_fn(hist, tok, plen, pbk, index):
            eng.trace_counts[dkey] += 1  # one per trace = one compile
            return SP.ngram_propose(hist, tok, plen, pbk, k - 1,
                                    index - pbk, ngram)

        return draft_fn

    @staticmethod
    def _spec_join_rows(jnp, MHA, jax, state, out, prompt, length, Pb,
                        slot, L, constrain=None):
        """The speculation state a join splices alongside the K/V: the
        row's token history mirror (prompt at [0, Pb)), its true
        prompt length, and its bucket — shared by the dense and paged
        join bodies (and the disaggregated splice)."""
        c = constrain if constrain is not None else (lambda x: x)
        hist_row = jnp.concatenate(
            [prompt, jnp.zeros((1, L - prompt.shape[1]), jnp.int32)], 1)
        out["hist"] = c(MHA.splice_rows(state["hist"], slot, hist_row))
        out["plen"] = c(jax.lax.dynamic_update_slice(
            state["plen"], length.astype(jnp.int32), (slot,)))
        out["pbk"] = c(jax.lax.dynamic_update_slice(
            state["pbk"], jnp.full((1,), Pb, jnp.int32), (slot,)))
        return out


class DenseLayout(CacheLayout):
    """The contiguous [S, H, pool_len, D] StaticKVCache pool: every
    slot owns its worst-case rows, write indices live in the carry."""

    def join_key(self, Pb):
        return ("join", Pb)

    def step_key(self):
        return ("step",) + self.eng._pool_key

    def spec_step_key(self):
        return ("sstep",) + self.eng._pool_key

    def row_index(self):
        return self.eng._state["inc"][0].index

    # ---- state ----
    def build_state(self, memory):
        import jax.numpy as jnp

        eng = self.eng
        decoder = eng._net.decoder
        M, Dm = memory.shape
        dtype = jnp.asarray(np.asarray(memory)).dtype
        S, L = eng.num_slots, eng._pool_len
        inc = [layer.self_attn.gen_cache(None, max_length=L,
                                         batch_size=S, dtype=dtype)
               for layer in decoder.layers]
        static = []
        for layer in decoder.layers:
            z = jnp.zeros((S, layer.cross_attn.num_heads, M,
                           layer.cross_attn.head_dim), dtype)
            static.append((z, z))
        state = {
            "tok": jnp.zeros((S,), jnp.int32),
            "bias": jnp.zeros((S, L), jnp.float32),
            "mem": jnp.zeros((S, M, Dm), dtype),
            "inc": inc,
            "static": static,
        }
        if eng.spec_k:
            # the n-gram draft source's token mirror of the cache, plus
            # each slot's true prompt length / bucket for the logical
            # (hole-skipping) history view
            state["hist"] = jnp.zeros((S, L), jnp.int32)
            state["plen"] = jnp.zeros((S,), jnp.int32)
            state["pbk"] = jnp.zeros((S,), jnp.int32)
        return self.distinct_leaves(state)

    def pool_key(self, memory):
        eng = self.eng
        M, Dm = memory.shape
        import jax.numpy as jnp

        dtype = jnp.asarray(np.asarray(memory)).dtype
        return (eng.num_slots, eng._pool_len, M, Dm, str(dtype)) + \
            ((("spec", eng.spec_k, eng.spec_ngram),)
             if eng.spec_k else ()) + eng._adapter_pool_key()

    # ---- the join program (prefill + splice) ----
    # Every join-family body takes the pool `state` as a DONATED carry
    # (engine._DONATED_KINDS): the returned state's leaves are
    # slot-local dynamic-update-slices over the input leaves, which
    # XLA turns into in-place writes on the donated buffers — a join
    # costs its own slot's rows, not a whole-pool copy. Bodies must
    # therefore keep every non-updated leaf IDENTITY-passed (no
    # gratuitous reshapes/casts of untouched pool leaves), or the
    # aliasing degrades back to a copy.
    def join_body(self, Pb):
        import jax
        import jax.numpy as jnp

        from ..nn.layer.transformer import MultiHeadAttention as MHA

        eng = self.eng
        fm = eng._fm
        decoder = eng._net.decoder
        L = eng._pool_len
        spec = bool(eng.spec_k)
        key = self.join_key(Pb)
        neg = eng._neg

        def join_fn(params, buffers, state, slot, prompt, length,
                    memory, *ad):
            eng.trace_counts[key] += 1  # python side effect: one per
            #                             trace = one per compile
            kpos = jnp.arange(L, dtype=jnp.int32)
            hole = (kpos[None, :] >= length[:, None]) & \
                (kpos[None, :] < jnp.int32(Pb))
            bias_row = jnp.where(hole, jnp.float32(neg),
                                 jnp.float32(0.0))           # [1, L]
            positions = jnp.arange(Pb, dtype=jnp.int32)[None]
            inc0 = [layer.self_attn.gen_cache(
                None, max_length=Pb, batch_size=1, dtype=memory.dtype)
                for layer in decoder.layers]
            # `ad` = (adapter id, banks) on adapter-carrying engines:
            # the prefill runs under the tenant's LoRA delta
            with eng._lora_ctx(ad):
                (lg, inc1, static1), _ = fm.apply(
                    params, buffers, None, prompt, positions, memory,
                    training=False, tgt_mask=bias_row[:, :Pb],
                    memory_mask=None, inc=inc0, prefill=True)
            # token 0 conditions on the row's LAST REAL prompt position
            last = jnp.take_along_axis(
                lg, (length - 1)[:, None, None], axis=1)[:, 0]
            tok0 = last.argmax(-1).astype(jnp.int32)[0]
            new_inc = [MHA.static_kv_splice(pool, slot, c.k, c.v,
                                            jnp.int32(Pb))
                       for pool, c in zip(state["inc"], inc1)]
            new_static = [(MHA.splice_rows(pk, slot, sk),
                           MHA.splice_rows(pv, slot, sv))
                          for (pk, pv), (sk, sv) in zip(state["static"],
                                                        static1)]
            new_state = {
                "tok": jax.lax.dynamic_update_slice(
                    state["tok"], tok0[None], (slot,)),
                "bias": MHA.splice_rows(state["bias"], slot, bias_row),
                "mem": MHA.splice_rows(state["mem"], slot, memory),
                "inc": new_inc,
                "static": new_static,
            }
            if spec:
                new_state = self._spec_join_rows(
                    jnp, MHA, jax, state, new_state, prompt, length,
                    Pb, slot, L)
            return new_state, tok0

        return join_fn

    # ---- the chunked-prefill program (verify-mode chunk append) ----
    def cjoin_body(self, Cb):
        """Prefill ONE Cb-token chunk of a prompt straight into the
        slot's pool rows: a batch-1 view of the slot's K/V runs the
        chunk through the verify-mode attention path (multi-token
        write at [seed, seed + Cb), causal read over everything the
        earlier chunks wrote), then splices the view row back — decode
        steps interleave between chunks, so a long prompt never stalls
        co-resident decodes longer than one chunk. One compile per
        CHUNK bucket, never per prompt: seed, true prompt length, and
        the prompt bucket all ride in as traced scalars. Every splice
        is computed from the TRUE final (length, Pb) — re-running a
        chunk is idempotent — and the tok0 lane is CLAMPED into the
        chunk, so only the final chunk's tok0 is meaningful (the host
        ignores the rest). Stale previous-occupant K/V past the chunk
        end is causal-masked until a later chunk or decode write
        replaces it, and the eos-padded tail of the final chunk lands
        inside the [length, Pb) hole the bias row masks forever."""
        import jax
        import jax.numpy as jnp

        from ..nn.layer.transformer import MultiHeadAttention as MHA
        from ..ops import attention as A

        eng = self.eng
        fm = eng._fm
        fm_cross = eng._fm_cross
        L = eng._pool_len
        spec = bool(eng.spec_k)
        ck = ("cjoin", Cb)
        neg = eng._neg

        def cjoin_fn(params, buffers, cparams, cbuffers, state, slot,
                     chunk, seed, length, pb, memory, *rest):
            eng.trace_counts[ck] += 1  # one per trace = one compile
            if spec:
                (hist_row,), ad = rest[:1], rest[1:]
            else:
                hist_row, ad = None, rest
            static1, _ = fm_cross.apply(cparams, cbuffers, None,
                                        memory, training=False)
            kpos = jnp.arange(L, dtype=jnp.int32)
            hole = (kpos[None, :] >= length[:, None]) & \
                (kpos[None, :] < pb)
            bias_row = jnp.where(hole, jnp.float32(neg),
                                 jnp.float32(0.0))           # [1, L]
            # batch-1 view of the slot's rows: the verify-scope write
            # lands the chunk K/V at [seed, seed + Cb) and the causal
            # read sees the earlier chunks already in the row
            inc = [MHA.StaticKVCache(
                jax.lax.dynamic_slice_in_dim(c.k, slot, 1, axis=0),
                jax.lax.dynamic_slice_in_dim(c.v, slot, 1, axis=0),
                seed.reshape(1)) for c in state["inc"]]
            posn = seed + jnp.arange(Cb, dtype=jnp.int32)[None]
            with A.kv_verify_scope(), eng._lora_ctx(ad):
                (lg, inc2), _ = fm.apply(
                    params, buffers, None, chunk, posn, memory,
                    training=False, tgt_mask=bias_row,
                    memory_mask=None, inc=inc, static_kv=static1,
                    prefill=False)
            # the LAST REAL prompt position sits at chunk lane
            # (length - 1 - seed) on the final chunk only; clamp keeps
            # mid-chunk dispatches in-bounds (their tok0 is discarded)
            lane = jnp.clip(length - 1 - seed, 0, Cb - 1)
            last = jnp.take_along_axis(lg, lane[:, None, None],
                                       axis=1)[:, 0]
            tok0 = last.argmax(-1).astype(jnp.int32)[0]
            new_inc = [MHA.static_kv_splice(pool, slot, c.k, c.v, pb)
                       for pool, c in zip(state["inc"], inc2)]
            new_static = [(MHA.splice_rows(pk, slot, sk),
                           MHA.splice_rows(pv, slot, sv))
                          for (pk, pv), (sk, sv) in zip(state["static"],
                                                        static1)]
            out = dict(
                state,
                tok=jax.lax.dynamic_update_slice(
                    state["tok"], tok0[None], (slot,)),
                bias=MHA.splice_rows(state["bias"], slot, bias_row),
                mem=MHA.splice_rows(state["mem"], slot, memory),
                inc=new_inc,
                static=new_static)
            if spec:
                out["hist"] = MHA.splice_rows(state["hist"], slot,
                                              hist_row)
                out["plen"] = jax.lax.dynamic_update_slice(
                    state["plen"], length.astype(jnp.int32), (slot,))
                out["pbk"] = jax.lax.dynamic_update_slice(
                    state["pbk"], pb.reshape(1).astype(jnp.int32),
                    (slot,))
            return out, tok0

        return cjoin_fn

    # ---- the plain batched decode step ----
    def step_body(self, key):
        import jax.numpy as jnp

        from ..nn.layer.transformer import MultiHeadAttention as MHA

        eng = self.eng
        fm = eng._fm

        def step_fn(params, buffers, state, *rest):
            eng.trace_counts[key] += 1  # one per trace = one compile
            *ad, active = rest          # ad = (ids, banks) | ()
            inc = state["inc"]
            posn = inc[0].index[:, None]  # per-SLOT written counts
            with eng._lora_ctx(ad):
                (lg, inc2), _ = fm.apply(
                    params, buffers, None, state["tok"][:, None], posn,
                    state["mem"], training=False,
                    tgt_mask=state["bias"], memory_mask=None, inc=inc,
                    static_kv=state["static"], prefill=False)
            nxt = lg[:, 0].argmax(-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, state["tok"])
            # inactive slots must not creep their write index: their
            # (masked, garbage) write this step gets overwritten before
            # it can ever become visible, but the index itself must
            # stay put so an idle slot never marches toward max_len
            inc2 = [MHA.StaticKVCache(
                c.k, c.v, jnp.where(active, c.index, old.index))
                for c, old in zip(inc2, inc)]
            return dict(state, tok=nxt, inc=inc2), nxt

        return step_fn

    # ---- the speculative verify step (draft acceptance + rollback) ----
    def spec_step_body(self, vkey):
        import jax.numpy as jnp

        from ..nn.layer.transformer import MultiHeadAttention as MHA
        from ..ops import attention as A
        from ..text import speculative as SP
        from ..text.decode import greedy_accept

        eng = self.eng
        fm = eng._fm
        k = eng.spec_k

        def sstep_fn(params, buffers, state, *rest):
            eng.trace_counts[vkey] += 1  # one per trace = one compile
            *ad, drafts, active, spec_on, k_eff = rest
            inc = state["inc"]
            idx0 = inc[0].index
            # a spec=False slot's drafts are forced unmatched (-1 never
            # equals a vocab token), so it accepts exactly one oracle
            # token per step; lanes past the adaptive effective k are
            # force-rejected the same way — shrinking/regrowing k NEVER
            # changes a shape, so it never retraces
            lane = jnp.arange(k - 1, dtype=jnp.int32)[None, :]
            live = spec_on[:, None] & (lane < k_eff - 1)
            drafts = jnp.where(live, drafts, -1)
            fed = jnp.concatenate([state["tok"][:, None], drafts], 1)
            posn = idx0[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
            with A.kv_verify_scope(), eng._lora_ctx(ad):
                (lg, inc2), _ = fm.apply(
                    params, buffers, None, fed, posn, state["mem"],
                    training=False, tgt_mask=state["bias"],
                    memory_mask=None, inc=inc,
                    static_kv=state["static"], prefill=False)
            preds = lg.argmax(-1).astype(jnp.int32)
            n_match, emit = greedy_accept(drafts, preds)
            n_emit = jnp.where(active, n_match + 1, 0).astype(jnp.int32)
            # acceptance rollback on active rows, index pin on the rest
            # (the same inactive-slot contract as the plain step)
            new_idx = SP.rollback_index(inc2[0].index, k, n_match,
                                        active)
            inc3 = [MHA.StaticKVCache(c.k, c.v, new_idx) for c in inc2]
            corr = jnp.take_along_axis(preds, n_match[:, None],
                                       axis=1)[:, 0]
            nxt = jnp.where(active, corr, state["tok"])
            new_state = dict(
                state, tok=nxt, inc=inc3,
                hist=SP.write_hist(state["hist"], fed, idx0))
            return new_state, (emit, n_emit)

        return sstep_fn


class PagedLayout(CacheLayout):
    """The global fixed-size page pool with host-owned indirection:
    write indices and the page table ride in as traced inputs every
    step, so mapping/rollback are pure host index arithmetic."""

    def join_key(self, Pb):
        return ("pjoin", Pb)

    def step_key(self):
        return ("pstep",) + self.eng._pool_key

    def spec_step_key(self):
        return ("pverify",) + self.eng._pool_key

    def row_index(self):
        import jax.numpy as jnp

        return jnp.asarray(self.eng._index.astype(np.int32))

    def map_step_pages(self, active, width):
        from .paging import OutOfPages

        eng = self.eng
        psz = eng.page_size
        now = eng.clock()
        # map the page(s) the next `width` write positions need; under
        # oversubscription a dry pool evicts the starved slot with its
        # partial tokens (the pool itself keeps serving). Speculative
        # steps write the FULL fixed-k block (force-rejected tail
        # included), so every page the block touches must be mapped.
        # Pending slots (mid chunked-prefill) are not in `active`:
        # their index sits mid-PROMPT, the pages there are the chunk
        # programs' to map, and a dry pool must never OOM-evict a
        # half-prefilled slot on a decode step it does not even
        # participate in.
        active = active.copy()
        for s in np.flatnonzero(active):
            i0 = int(eng._index[s])
            for pi in range(i0 // psz, (i0 + width - 1) // psz + 1):
                if eng._table[s, pi] < 0:
                    try:
                        eng._table[s, pi] = eng._alloc_pages(1)[0]
                    except OutOfPages as e:
                        eng._evict_oom(s, e, now)
                        active[s] = False
                        break
        return active

    def step_extra_args(self):
        import jax.numpy as jnp

        eng = self.eng
        return (eng._device_table(),
                jnp.asarray(eng._index.astype(np.int32)))

    def advance_rows(self, n_emit):
        eng = self.eng
        n_emit = np.asarray(n_emit, np.int64)
        eng.driver.count_advance(eng._index, n_emit)
        eng._index += n_emit.astype(eng._index.dtype)

    # ---- state ----
    def build_state(self, memory):
        """The pool's device state, by what each layer of the served
        stack keeps of a sequence (`driver.cache_kinds()`): `paged` (K/V
        page arrays through the engine's table and allocator), `latent`
        (ONE page array a block through the same table: a token's row is
        `[c_kv | k_pe]`, no heads, no V), `ring` (the last `window` K/V
        rows a slot), `recurrent` (a convolution tail and a float32 scan
        state a slot), and, for a stack with a
        client-supplied memory, `static` (its cross-attention K/V a
        slot) with the memory and the pad-hole bias rows. A join writes
        every kind whole for its slot, so nothing of the slot's last
        request shows."""
        import jax.numpy as jnp

        eng = self.eng
        drv = eng.driver
        S, L = eng.num_slots, eng._pool_len
        dtype = jnp.dtype(eng._np_dtype)
        state = {"tok": jnp.zeros((S,), jnp.int32),
                 **drv.new_slot_state(memory, dtype),
                 "paged": drv.new_paged(dtype)}
        if eng.spec_k:
            state["hist"] = jnp.zeros((S, L), jnp.int32)
            state["plen"] = jnp.zeros((S,), jnp.int32)
            state["pbk"] = jnp.zeros((S,), jnp.int32)
        return self.distinct_leaves(state)

    def pool_key(self, memory):
        import jax.numpy as jnp

        eng = self.eng
        M, Dm = eng._mem_shape or (0, 0)
        dtype = jnp.dtype(eng._np_dtype)
        return (eng.num_slots, eng._pool_len, M, Dm, str(dtype),
                eng.page_size, eng.num_pages, str(eng.kv_dtype)) + \
            ((("spec", eng.spec_k, eng.spec_ngram),)
             if eng.spec_k else ()) + eng._adapter_pool_key()

    # ---- the paged join program (prefill into pages) ----
    def join_body(self, Pb):
        import jax
        import jax.numpy as jnp

        from ..nn.layer.transformer import MultiHeadAttention as MHA
        from . import paging as PG

        eng = self.eng
        drv = eng.driver
        L = eng._pool_len
        spec = bool(eng.spec_k)
        ck = self.join_key(Pb)
        neg = eng._neg

        def join_fn(params, buffers, state, slot, prompt, length,
                    memory, page_ids, *ad):
            eng.trace_counts[ck] += 1  # one per trace = one compile
            kpos = jnp.arange(L, dtype=jnp.int32)
            hole = (kpos[None, :] >= length[:, None]) & \
                (kpos[None, :] < jnp.int32(Pb))
            bias_row = jnp.where(hole, jnp.float32(neg),
                                 jnp.float32(0.0))           # [1, L]
            last, new, counts = drv.prefill(
                params, buffers, prompt, length, memory, bias_row, Pb, ad)
            tok0 = last.argmax(-1).astype(jnp.int32)[0]
            new_paged = []
            for pc, (k, v) in zip(state["paged"], new.get("paged", ())):
                cache = PG.PagedKVCache(pc["k"], pc["v"], pc["ks"],
                                        pc["vs"], None, None)
                cache = MHA.paged_prompt_splice(cache, page_ids, k, v)
                new_paged.append({"k": cache.k, "v": cache.v,
                                  "ks": cache.k_scale,
                                  "vs": cache.v_scale})
            new_state = {
                "tok": jax.lax.dynamic_update_slice(
                    state["tok"], tok0[None], (slot,)),
                "paged": new_paged,
            }
            # the per-slot kinds: every buffer of the slot's row written
            # whole (a ring and a scan state start from this prompt
            # alone, whatever the slot held)
            for kind in ("static", "ring", "recurrent"):
                if kind in state:
                    new_state[kind] = [
                        tuple(MHA.splice_rows(buf, slot, rows)
                              for buf, rows in zip(pool, fresh))
                        for pool, fresh in zip(state[kind], new[kind])]
            if "latent" in state:
                # a block's rows [1, Pb, W] into its one page array
                new_state["latent"] = [
                    PG.write_prompt_pages(pages, None, page_ids,
                                          rows[:, None], False)[0]
                    for pages, rows in zip(state["latent"],
                                           new["latent"])]
            if "bias" in state:
                new_state["bias"] = MHA.splice_rows(state["bias"], slot,
                                                    bias_row)
                new_state["mem"] = MHA.splice_rows(state["mem"], slot,
                                                   memory)
            if spec:
                new_state = self._spec_join_rows(
                    jnp, MHA, jax, state, new_state, prompt, length,
                    Pb, slot, L)
            # an expert stack's counters leave behind token 0, to be
            # read where it is read
            return new_state, tok0 if counts is None else \
                jnp.concatenate([tok0[None], counts])

        return join_fn

    # ---- the prefix-attach program (zero-prefill shared join) ----
    def attach_body(self):
        import jax
        import jax.numpy as jnp

        from ..nn.layer.transformer import MultiHeadAttention as MHA

        eng = self.eng
        fm_cross = eng._fm_cross
        L = eng._pool_len
        spec = bool(eng.spec_k)
        ck = ("attach",)
        neg = eng._neg

        def attach_fn(cparams, cbuffers, state, slot, tok0, length,
                      pb, memory, *spec_rows):
            eng.trace_counts[ck] += 1
            static1, _ = fm_cross.apply(cparams, cbuffers, None,
                                        memory, training=False)
            kpos = jnp.arange(L, dtype=jnp.int32)
            hole = (kpos[None, :] >= length[:, None]) & \
                (kpos[None, :] < pb)                 # pb traced: one
            #                                          compile, all
            #                                          buckets
            bias_row = jnp.where(hole, jnp.float32(neg),
                                 jnp.float32(0.0))
            new_static = [(MHA.splice_rows(pk, slot, sk),
                           MHA.splice_rows(pv, slot, sv))
                          for (pk, pv), (sk, sv) in zip(state["static"],
                                                        static1)]
            out = dict(
                state,
                tok=jax.lax.dynamic_update_slice(
                    state["tok"], tok0[None], (slot,)),
                bias=MHA.splice_rows(state["bias"], slot, bias_row),
                mem=MHA.splice_rows(state["mem"], slot, memory),
                static=new_static)
            if spec:
                # the prompt tokens ride in pre-padded to the full
                # pool length, so the attach program stays ONE compile
                # for every bucket (pb is already traced)
                (hist_row,) = spec_rows
                out["hist"] = MHA.splice_rows(state["hist"], slot,
                                              hist_row)
                out["plen"] = jax.lax.dynamic_update_slice(
                    state["plen"], length.astype(jnp.int32), (slot,))
                out["pbk"] = jax.lax.dynamic_update_slice(
                    state["pbk"], pb.reshape(1).astype(jnp.int32),
                    (slot,))
            return out

        return attach_fn

    def cow_body(self):
        from . import paging as PG

        eng = self.eng
        ck = ("cow",)

        def cow_fn(state, src, dst):
            eng.trace_counts[ck] += 1
            new_paged = []
            for pc in state["paged"]:
                k, ks = PG.copy_page(pc["k"], pc["ks"], src, dst)
                v, vs = PG.copy_page(pc["v"], pc["vs"], src, dst)
                new_paged.append({"k": k, "v": v, "ks": ks, "vs": vs})
            return dict(state, paged=new_paged)

        return cow_fn

    # ---- the partial-attach program (radix hit: tail-only prefill) ----
    def pattach_body(self, Mb, Tb):
        """Prefill ONLY a prompt's divergent tail, seeded by trie-
        matched pages: the Tb-bucketed tail runs as ONE verify-mode
        block through the page pool itself — `write_tokens` lands the
        tail K/V at the seed boundary through a WIDTH-CLIPPED table row
        ([1, Mb + pages_for(Tb)]) and `paged_verify_attention` reads
        the matched seed K/V back through the same row, so attention
        cost scales with the HIT size, not the full pool. One compile
        per (matched-pages bucket, tail bucket) pair: seed length,
        slot, and true prompt length are traced scalars, so hit depth
        never retraces. Rides the same decode-sharding scope and LoRA
        context as the verify step, so sharded / spec / adapter cells
        inherit it unchanged."""
        import jax
        import jax.numpy as jnp

        from ..nn.layer.transformer import MultiHeadAttention as MHA
        from ..ops import attention as A
        from . import paging as PG

        eng = self.eng
        fm = eng._fm
        fm_cross = eng._fm_cross
        L = eng._pool_len
        psz = eng.page_size
        W = min(eng.max_pages, int(Mb) + PG.pages_for(Tb, psz))
        spec = bool(eng.spec_k)
        ck = ("pattach", Mb, Tb)
        neg = eng._neg

        def pattach_fn(params, buffers, cparams, cbuffers, state, slot,
                       trow, tail, seed_len, length, pb, memory, *rest):
            eng.trace_counts[ck] += 1  # one per trace = one compile
            if spec:
                (hist_row,), ad = rest[:1], rest[1:]
            else:
                hist_row, ad = None, rest
            static1, _ = fm_cross.apply(cparams, cbuffers, None,
                                        memory, training=False)
            kpos = jnp.arange(L, dtype=jnp.int32)
            hole = (kpos[None, :] >= length[:, None]) & \
                (kpos[None, :] < pb)
            bias_row = jnp.where(hole, jnp.float32(neg),
                                 jnp.float32(0.0))           # [1, L]
            # batch-1 paged view through the clipped table row: the
            # verify-scope write lands tail K/V at positions
            # [seed_len, seed_len + Tb) and the verify read gathers
            # only the W mapped pages (bias clipped to match)
            inc = [PG.PagedKVCache(pc["k"], pc["v"], pc["ks"],
                                   pc["vs"], trow, seed_len.reshape(1))
                   for pc in state["paged"]]
            posn = seed_len + jnp.arange(Tb, dtype=jnp.int32)[None]
            with A.kv_verify_scope(), eng._lora_ctx(ad):
                (lg, inc2), _ = fm.apply(
                    params, buffers, None, tail, posn, memory,
                    training=False, tgt_mask=bias_row[:, :W * psz],
                    memory_mask=None, inc=inc, static_kv=static1,
                    prefill=False)
            # token 0 conditions on the LAST REAL prompt position,
            # which sits at tail lane (length - 1 - seed_len)
            last = jnp.take_along_axis(
                lg, (length - 1 - seed_len)[:, None, None],
                axis=1)[:, 0]
            tok0 = last.argmax(-1).astype(jnp.int32)[0]
            new_paged = [{"k": c.k, "v": c.v, "ks": c.k_scale,
                          "vs": c.v_scale} for c in inc2]
            new_static = [(MHA.splice_rows(pk, slot, sk),
                           MHA.splice_rows(pv, slot, sv))
                          for (pk, pv), (sk, sv) in zip(state["static"],
                                                        static1)]
            out = dict(
                state,
                tok=jax.lax.dynamic_update_slice(
                    state["tok"], tok0[None], (slot,)),
                bias=MHA.splice_rows(state["bias"], slot, bias_row),
                mem=MHA.splice_rows(state["mem"], slot, memory),
                static=new_static,
                paged=new_paged)
            if spec:
                out["hist"] = MHA.splice_rows(state["hist"], slot,
                                              hist_row)
                out["plen"] = jax.lax.dynamic_update_slice(
                    state["plen"], length.astype(jnp.int32), (slot,))
                out["pbk"] = jax.lax.dynamic_update_slice(
                    state["pbk"], pb.reshape(1).astype(jnp.int32),
                    (slot,))
            return out, tok0

        return pattach_fn

    # ---- the chunked-prefill program (verify-mode chunk append) ----
    def pcjoin_body(self, Mb, Cb):
        """Prefill ONE Cb-token chunk of a prompt into the slot's
        pages: like `pattach_body` the chunk runs as a verify-mode
        block through a WIDTH-CLIPPED table row ([1, Mb +
        pages_for(Cb)]) — `write_tokens` lands the chunk K/V at the
        seed boundary and the verify read gathers only the pages the
        chunk can see, so attention cost scales with the SEED, not the
        pool. One compile per (seed-pages bucket, chunk bucket) pair,
        never per prompt: seed, slot, true length, and bucket are
        traced scalars. The trie-matched seed of a radix PARTIAL hit
        rides the same program (seed pages mapped read-only into the
        clipped row), so a chunk extends the matched node chunk by
        chunk. tok0's lane is CLAMPED into the chunk: only the final
        chunk's value is read by the host; every splice is computed
        from the TRUE final (length, Pb), so chunks are idempotent."""
        import jax
        import jax.numpy as jnp

        from ..nn.layer.transformer import MultiHeadAttention as MHA
        from ..ops import attention as A
        from . import paging as PG

        eng = self.eng
        fm = eng._fm
        fm_cross = eng._fm_cross
        L = eng._pool_len
        psz = eng.page_size
        W = min(eng.max_pages, int(Mb) + PG.pages_for(Cb, psz))
        spec = bool(eng.spec_k)
        ck = ("pcjoin", Mb, Cb)
        neg = eng._neg

        def pcjoin_fn(params, buffers, cparams, cbuffers, state, slot,
                      trow, chunk, seed, length, pb, memory, *rest):
            eng.trace_counts[ck] += 1  # one per trace = one compile
            if spec:
                (hist_row,), ad = rest[:1], rest[1:]
            else:
                hist_row, ad = None, rest
            static1, _ = fm_cross.apply(cparams, cbuffers, None,
                                        memory, training=False)
            kpos = jnp.arange(L, dtype=jnp.int32)
            hole = (kpos[None, :] >= length[:, None]) & \
                (kpos[None, :] < pb)
            bias_row = jnp.where(hole, jnp.float32(neg),
                                 jnp.float32(0.0))           # [1, L]
            inc = [PG.PagedKVCache(pc["k"], pc["v"], pc["ks"],
                                   pc["vs"], trow, seed.reshape(1))
                   for pc in state["paged"]]
            posn = seed + jnp.arange(Cb, dtype=jnp.int32)[None]
            with A.kv_verify_scope(), eng._lora_ctx(ad):
                (lg, inc2), _ = fm.apply(
                    params, buffers, None, chunk, posn, memory,
                    training=False, tgt_mask=bias_row[:, :W * psz],
                    memory_mask=None, inc=inc, static_kv=static1,
                    prefill=False)
            # the LAST REAL prompt position sits at chunk lane
            # (length - 1 - seed) on the final chunk only; clamp keeps
            # mid-chunk dispatches in-bounds (their tok0 is discarded)
            lane = jnp.clip(length - 1 - seed, 0, Cb - 1)
            last = jnp.take_along_axis(lg, lane[:, None, None],
                                       axis=1)[:, 0]
            tok0 = last.argmax(-1).astype(jnp.int32)[0]
            new_paged = [{"k": c.k, "v": c.v, "ks": c.k_scale,
                          "vs": c.v_scale} for c in inc2]
            new_static = [(MHA.splice_rows(pk, slot, sk),
                           MHA.splice_rows(pv, slot, sv))
                          for (pk, pv), (sk, sv) in zip(state["static"],
                                                        static1)]
            out = dict(
                state,
                tok=jax.lax.dynamic_update_slice(
                    state["tok"], tok0[None], (slot,)),
                bias=MHA.splice_rows(state["bias"], slot, bias_row),
                mem=MHA.splice_rows(state["mem"], slot, memory),
                static=new_static,
                paged=new_paged)
            if spec:
                out["hist"] = MHA.splice_rows(state["hist"], slot,
                                              hist_row)
                out["plen"] = jax.lax.dynamic_update_slice(
                    state["plen"], length.astype(jnp.int32), (slot,))
                out["pbk"] = jax.lax.dynamic_update_slice(
                    state["pbk"], pb.reshape(1).astype(jnp.int32),
                    (slot,))
            return out, tok0

        return pcjoin_fn

    # ---- the plain batched decode step (through the page table) ----
    def step_body(self, ck):
        import jax.numpy as jnp

        eng = self.eng
        drv = eng.driver

        def step_fn(params, buffers, state, table, index, *rest):
            eng.trace_counts[ck] += 1  # one per trace = one compile
            *ad, active = rest          # ad = (ids, banks) | ()
            lg, parts, counts = drv.step(params, buffers, state, table,
                                         index, ad, active)
            nxt = lg.argmax(-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, state["tok"])
            # an expert stack's counters leave behind the tokens, in the
            # one array the host reads
            return dict(state, tok=nxt, **parts), nxt if counts is None \
                else jnp.concatenate([nxt, counts])

        return step_fn

    # ---- the paged speculative verify step ----
    def spec_step_body(self, vkey):
        import jax.numpy as jnp

        from ..ops import attention as A
        from ..text import speculative as SP
        from ..text.decode import greedy_accept

        eng = self.eng
        fm = eng._fm
        k = eng.spec_k

        def pverify_fn(params, buffers, state, table, index, *rest):
            eng.trace_counts[vkey] += 1  # one per trace = one compile
            from . import paging as PG

            *ad, drafts, active, spec_on, k_eff = rest
            # force-reject the opted-out rows and the lanes past the
            # adaptive effective k (-1 never equals a vocab token): k
            # changes ride the SAME fixed-k compiled program
            lane = jnp.arange(k - 1, dtype=jnp.int32)[None, :]
            live = spec_on[:, None] & (lane < k_eff - 1)
            drafts = jnp.where(live, drafts, -1)
            fed = jnp.concatenate([state["tok"][:, None], drafts], 1)
            posn = index[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
            inc = [PG.PagedKVCache(pc["k"], pc["v"], pc["ks"],
                                   pc["vs"], table, index)
                   for pc in state["paged"]]
            with A.kv_verify_scope(), eng._lora_ctx(ad):
                (lg, inc2), _ = fm.apply(
                    params, buffers, None, fed, posn, state["mem"],
                    training=False, tgt_mask=state["bias"],
                    memory_mask=None, inc=inc,
                    static_kv=state["static"], prefill=False)
            preds = lg.argmax(-1).astype(jnp.int32)
            n_match, emit = greedy_accept(drafts, preds)
            n_emit = jnp.where(active, n_match + 1, 0).astype(jnp.int32)
            corr = jnp.take_along_axis(preds, n_match[:, None],
                                       axis=1)[:, 0]
            nxt = jnp.where(active, corr, state["tok"])
            # rollback is pure index arithmetic and the index is HOST-
            # owned (a traced input, not a carry): the stepper adds
            # n_emit per row; rejected tokens sit masked behind it and
            # their already-mapped pages are simply rewritten next
            # round — no page frees on reject
            new_paged = [{"k": c.k, "v": c.v, "ks": c.k_scale,
                          "vs": c.v_scale} for c in inc2]
            new_state = dict(
                state, tok=nxt, paged=new_paged,
                hist=SP.write_hist(state["hist"], fed, index))
            return new_state, (emit, n_emit)

        return pverify_fn


# --------------------------------------------------------------------------
# placements: how a body becomes a compiled program
# --------------------------------------------------------------------------

def named_program(key, body):
    """`body` traced under `jax.named_scope(key[0])` and named
    `key[0]`: the compiled module is `jit_<kind>` (`jit_pstep`,
    `jit_pjoin`, ...), the host's dispatch event
    `PjitFunction(<kind>)` (`PjitFunction(jit(<kind>))` once precompiled
    ahead of time), and every device operation's `op_name`
    starts `jit(<kind>)/<kind>/`, so a profiler trace tells the pool's
    programs apart whatever their bodies are called."""
    import jax

    kind = key[0]

    def program(*args):
        with jax.named_scope(kind):
            return body(*args)

    program.__name__ = program.__qualname__ = kind
    return program


class SinglePlacement:
    """Plain `jax.jit` with the engine's shared donation declaration —
    the single-chip build path every engine used before placement was
    an axis. The declaration now spans the WHOLE program matrix (the
    step family AND the join family), so every body's pool carry is a
    slot-local in-place update, never a whole-pool copy; the engine's
    guarded-retry path owns the failure semantics the donation
    sharpens (see engine._DONATED_KINDS)."""

    def __init__(self, eng):
        self.eng = eng

    def build(self, key, body, has_aux=True):
        import jax

        return jax.jit(named_program(key, body),
                       donate_argnums=self.eng._donate_argnums(key))


class ShardedPlacement:
    """Mesh-annotated builds: the SAME single-chip body traced under
    the decode-kernel sharding scope, every returned pool carry pinned
    to the dp slot layout, donation per the shared declaration. Also
    owns the pool-state placement (device_put onto the decode mesh)."""

    def __init__(self, eng):
        self.eng = eng

    def _decode_specs(self):
        ns = self.eng._ns_pool
        return {"q": ns, "kv": ns, "pages": ns, "out": ns}

    def constrain_state(self, state):
        """Pin PartitionSpec('dp') on every pool carry (slot-leading
        leaves; the paged page/scale arrays shard their page axis the
        same way), replicating nothing implicitly — the every-carry
        contract."""
        import jax

        from ..nn.layer.transformer import MultiHeadAttention as MHA

        c = lambda x: jax.lax.with_sharding_constraint(  # noqa: E731
            x, self.eng._ns_pool)
        out = dict(state)
        for k in ("tok", "bias", "mem", "hist", "plen", "pbk"):
            if k in out:
                out[k] = c(out[k])
        if "inc" in out:
            out["inc"] = [MHA.StaticKVCache(c(cc.k), c(cc.v),
                                            c(cc.index))
                          for cc in out["inc"]]
        if "static" in out:
            out["static"] = [(c(sk), c(sv)) for sk, sv in out["static"]]
        if "paged" in out:
            out["paged"] = [
                {"k": c(pc["k"]), "v": c(pc["v"]),
                 "ks": None if pc["ks"] is None else c(pc["ks"]),
                 "vs": None if pc["vs"] is None else c(pc["vs"])}
                for pc in out["paged"]]
        return out

    def build(self, key, body, has_aux=True):
        """jit a single-chip engine body with the sharded annotations:
        decode kernels constrained via `decode_shardings`, every
        returned carry pinned to the pool layout, the step-family
        state carry donated per the shared `_donate_argnums`
        declaration (same donation audit as the single-chip builds)."""
        import jax

        from ..ops import attention as A

        specs = self._decode_specs()

        def fn(*args):
            with A.decode_shardings(specs):
                out = body(*args)
            if has_aux:
                st, aux = out
                return self.constrain_state(st), aux
            return self.constrain_state(out)

        return jax.jit(named_program(key, fn),
                       donate_argnums=self.eng._donate_argnums(key))

    def place_state(self, state):
        """Lay the freshly-built pool state out on the decode mesh:
        slot-leading leaves shard over dp (the KV pool is REBUILT with
        `gen_cache`'s sharded constructors so the zeros never
        materialize on one device)."""
        import jax

        eng = self.eng
        L, S = eng._pool_len, eng.num_slots
        dtype = state["mem"].dtype
        decoder = eng._net.decoder
        ns = eng._ns_pool
        out = dict(state)
        for k in ("tok", "bias", "mem", "hist", "plen", "pbk"):
            if k in state:
                out[k] = jax.device_put(state[k], ns)
        out["static"] = [
            (jax.device_put(sk, ns), jax.device_put(sv, ns))
            for sk, sv in state["static"]]
        if "inc" in state:
            out["inc"] = [layer.self_attn.gen_cache(
                None, max_length=L, batch_size=S, dtype=dtype,
                kv_sharding=ns, index_sharding=ns)
                for layer in decoder.layers]
        if "paged" in state:
            # pad the page-row count to a dp multiple so the page axis
            # lays out evenly; rows past the trash row (num_pages) are
            # never referenced by any table entry — pure padding
            rows = eng.num_pages + 1
            padded = -(-rows // eng._pool_dp) * eng._pool_dp
            paged = []
            for layer in decoder.layers:
                cc = layer.self_attn.gen_paged_cache(
                    padded - 1, eng.page_size, S, eng.max_pages,
                    dtype, eng.kv_dtype, page_sharding=ns)
                paged.append({"k": cc.k, "v": cc.v, "ks": cc.k_scale,
                              "vs": cc.v_scale})
            out["paged"] = paged
        return CacheLayout.distinct_leaves(out)


# --------------------------------------------------------------------------
# steppers: the per-iteration decode dispatch
# --------------------------------------------------------------------------

class PlainStepper:
    """One token per slot per iteration: ONE batched program dispatch
    over the active mask."""

    #: the next step's inputs are known without this step's tokens: the
    #: token it consumes stays on the device (`state["tok"]`), every
    #: active slot's write index advances by exactly one, and the page
    #: it writes is mapped by the host from those indices. So `decode`
    #: leaves its tokens unread and the engine may enqueue the next
    #: step before it reads them.
    ahead = True

    def __init__(self, eng):
        self.eng = eng

    def decode(self, active):
        import jax.numpy as jnp

        eng = self.eng
        lay = eng.layout
        it = eng._iter_trace      # engine-track spans, None when off
        if it is not None:
            sp = it.begin("step.map_pages")
        active = lay.map_step_pages(active, 1)
        if it is not None:
            it.end(sp)
        if not active.any():
            return np.zeros((eng.num_slots,), np.int64), active
        if it is not None:
            sp = it.begin("step.enqueue")
        key = lay.step_key()
        fn = eng._program(key, lambda: eng._build_step(key))
        eng._state, toks = fn(eng._params(), eng._buffers(),
                              eng._state, *lay.step_extra_args(),
                              *eng._adapter_args(),
                              jnp.asarray(active))
        lay.advance_rows(active.astype(np.int64))
        if it is not None:
            it.end(sp)
        return toks, active


class SpecStepper:
    """Draft-verify: two dispatches deliver up to k tokens per slot,
    plus the adaptive effective-k controller — batch-wide, driven by
    the acceptance-rate gauge with hysteresis. `k_eff` rides into the
    fixed-k verify program as a traced scalar (lanes past it are
    force-rejected in-program), so shrinking or regrowing k NEVER
    retraces; the retrace-sentinel soaks hold this with adaptation
    exercised."""

    #: the next write indices depend on this step's `n_emit`, and the
    #: draft is read before the verify is enqueued: always in series
    ahead = False

    def __init__(self, eng):
        self.eng = eng
        self.k_eff = eng.spec_k
        self.k_shrink_events = 0
        self.k_grow_events = 0
        self._ema = None
        self._low_rounds = 0
        self._high_rounds = 0

    def _adapt(self, on_count, accepted):
        """Hysteresis: the acceptance-rate EMA must sit below/above the
        band for `spec_adapt_patience` consecutive rounds before k
        shrinks/regrows one step — a single unlucky round never
        thrashes the ladder."""
        eng = self.eng
        if not eng.spec_adapt or not on_count:
            return
        lanes = on_count * max(1, self.k_eff - 1)
        rate = accepted / lanes
        a = eng.spec_adapt_alpha
        self._ema = rate if self._ema is None else \
            (1 - a) * self._ema + a * rate
        if self._ema < eng.spec_adapt_low and self.k_eff > 2:
            self._low_rounds += 1
            self._high_rounds = 0
            if self._low_rounds >= eng.spec_adapt_patience:
                self.k_eff -= 1
                self.k_shrink_events += 1
                self._low_rounds = 0
                self._ema = None   # fresh window at the new k
        elif self._ema > eng.spec_adapt_high and \
                self.k_eff < eng.spec_k:
            self._high_rounds += 1
            self._low_rounds = 0
            if self._high_rounds >= eng.spec_adapt_patience:
                self.k_eff += 1
                self.k_grow_events += 1
                self._high_rounds = 0
                self._ema = None
        else:
            self._low_rounds = self._high_rounds = 0

    def decode(self, active):
        import jax
        import jax.numpy as jnp

        eng = self.eng
        lay = eng.layout
        it = eng._iter_trace      # engine-track spans, None when off
        if it is not None:
            sp = it.begin("step.map_pages")
        # the verify write is the FULL fixed-k block (force-rejected
        # tail included), so the paged pool maps every page it touches
        active = lay.map_step_pages(active, eng.spec_k)
        if it is not None:
            it.end(sp)
        if not active.any():
            S, k = eng.num_slots, eng.spec_k
            return (np.zeros((S, k), np.int64),
                    np.zeros((S,), np.int64)), active
        spec_on = np.asarray(
            [r is not None and getattr(r, "spec", True)
             for r in eng.slots], bool)
        st = eng._state
        n_active = int(active.sum())
        on = active & spec_on
        on_count = int(on.sum())
        proposed = on_count * (self.k_eff - 1)
        dkey = lay.draft_key()
        fn = eng._program(dkey, lambda: eng._build_draft(dkey))
        if it is not None:
            sp = it.begin("decode.draft", n_active=n_active,
                          proposed=proposed)
        t0 = time.perf_counter()
        drafts = fn(st["hist"], st["tok"], st["plen"], st["pbk"],
                    lay.row_index())
        jax.block_until_ready(drafts)
        t1 = time.perf_counter()
        if it is not None:
            it.end(sp)
            sp_verify = it.begin("decode.verify", n_active=n_active,
                                 proposed=proposed)
            sp = it.begin("step.enqueue")
        vkey = lay.spec_step_key()
        fn = eng._program(vkey, lambda: eng._build_spec_step(vkey))
        eng._state, (emit, n_emit) = fn(
            eng._params(), eng._buffers(), eng._state,
            *lay.step_extra_args(), *eng._adapter_args(), drafts,
            jnp.asarray(active), jnp.asarray(spec_on),
            jnp.int32(self.k_eff))
        if it is not None:
            it.end(sp)
            sp = it.begin_wait("step.readback")
        emit = np.asarray(emit)
        n_emit = np.asarray(n_emit)
        t2 = time.perf_counter()
        if it is not None:
            it.end_wait(sp)
        lay.advance_rows(n_emit)
        accepted = int(np.maximum(n_emit[on] - 1, 0).sum()) \
            if on_count else 0
        self._adapt(on_count, accepted)
        eng.metrics.record_spec_step(
            n_active, proposed, accepted, t1 - t0, t2 - t1,
            k_eff=self.k_eff, variant=eng._pool_variant(),
            k_shrinks=self.k_shrink_events,
            k_grows=self.k_grow_events)
        if it is not None:
            it.end(sp_verify, accepted=accepted)
        return (emit, n_emit), active
