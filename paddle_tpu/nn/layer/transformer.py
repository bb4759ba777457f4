"""Transformer layers.

Reference parity: python/paddle/nn/layer/transformer.py (MultiHeadAttention,
TransformerEncoder/Decoder, Transformer). TPU-native design: attention goes
through ops/attention.py — a pallas flash kernel on TPU, XLA composition
elsewhere — with head-batched (B, H, S, D) layout feeding the MXU.
"""
from __future__ import annotations

import collections

from .. import functional as F
from .common import Dropout, LayerList, LayerNorm, Linear
from .layers import Layer, run_block


class MultiHeadAttention(Layer):
    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])
    # decode-engine cache: preallocated [B, H, max_len, D] K/V buffers
    # plus the lockstep int32 write index ([B], every row equal — the
    # leading dim makes it a valid lax.scan carry AND lets beam search
    # tile/regather it like any other state leaf). Leaves are raw jax
    # arrays, NOT Tensors: the whole point is to ride jitted scans.
    StaticKVCache = collections.namedtuple("StaticKVCache",
                                           ["k", "v", "index"])

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None,
                 vdim=None, need_weights=False, weight_attr=None,
                 bias_attr=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        self.dropout = dropout
        self.need_weights = need_weights
        kdim = kdim or embed_dim
        vdim = vdim or embed_dim
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _split_heads(self, x):
        b, s, _ = x.shape
        return x.reshape([b, s, self.num_heads, self.head_dim]).transpose(
            [0, 2, 1, 3])

    def forward(self, query, key=None, value=None, attn_mask=None,
                cache=None, segment_ids=None):
        key = query if key is None else key
        value = key if value is None else value
        if cache is None:
            # transpose-free path: [B, S, h, d] operands — the head
            # transpose folds into the attention einsums (1.3x on the
            # short-seq XLA path; flash transposes internally when it
            # engages). segment_ids: packed-varlen feed (several LoD
            # sequences per row); rides to the segment-masked flash
            # kernel through the sdpa dispatcher.
            b, s, _ = query.shape
            q = self.q_proj(query).reshape(
                [b, s, self.num_heads, self.head_dim])
            k = self.k_proj(key).reshape(
                [b, key.shape[1], self.num_heads, self.head_dim])
            v = self.v_proj(value).reshape(
                [b, value.shape[1], self.num_heads, self.head_dim])
            out = F.scaled_dot_product_attention(q, k, v, attn_mask,
                                                 self.dropout,
                                                 training=self.training,
                                                 layout="BSHD",
                                                 segment_ids=segment_ids)
            out = out.reshape([b, s, self.num_heads * self.head_dim])
            return self.out_proj(out)
        q = self._split_heads(self.q_proj(query))
        k = self._split_heads(self.k_proj(key))
        v = self._split_heads(self.v_proj(value))
        from ...serving.paging import PagedKVCache

        # the KV-cache paths run inside the serving programs: the scope
        # tells the cache write, the kernel and the copies that feed it
        # from every other operation of the program in a profiler trace
        if isinstance(cache, (PagedKVCache, self.StaticKVCache)):
            import jax

            attend = (self._paged_kv_attention
                      if isinstance(cache, PagedKVCache)
                      else self._static_kv_attention)
            with jax.named_scope("attn"):
                out, cache = attend(q, k, v, attn_mask, cache)
            return self.out_proj(out), cache
        if isinstance(cache, self.StaticCache):
            k, v = cache.k, cache.v
        else:
            from ...tensor import ops as T

            k = T.concat([cache.k, k], axis=2)
            v = T.concat([cache.v, v], axis=2)
            cache = self.Cache(k, v)
        out = F.scaled_dot_product_attention(q, k, v, attn_mask,
                                             self.dropout,
                                             training=self.training)
        b, h, s, d = out.shape
        out = out.transpose([0, 2, 1, 3]).reshape([b, s, h * d])
        out = self.out_proj(out)
        if not isinstance(cache, self.StaticCache):
            return out, cache
        return out

    def _static_kv_attention(self, q, k, v, attn_mask, cache):
        """Preallocated-cache attention (inference-only, raw jnp — the
        static path exists to run inside jitted decode scans, outside
        the autograd tape). The new K/V block lands at the write index
        via lax.dynamic_update_slice; queries see written positions
        only (position mask), composed with an optional [B, max_len]
        (or [B, 1, 1, max_len]) additive key bias for padded-prompt
        holes. Contract: a multi-token write (S > 1) is the PREFILL of
        an empty cache — it attends within the prompt block itself on
        the regular flash-capable path; S == 1 is a decode step through
        the flash-decode kernel. Inside `ops.attention.kv_verify_scope`
        a multi-token write is instead a speculative-decoding VERIFY
        block: the S tokens land at each row's OWN write offset (per-row
        vmapped writes, the decode-step layout) and attend causally
        within the block via `verify_attention` — rolling the write
        index back afterwards is the caller's acceptance logic."""
        import jax
        import jax.numpy as jnp

        from ...core.tensor import Tensor
        from ...ops import attention as A

        def raw(x):
            return x._data if isinstance(x, Tensor) else jnp.asarray(x)

        qd, kd, vd = raw(q), raw(k), raw(v)
        kbuf, vbuf, idx = raw(cache.k), raw(cache.v), raw(cache.index)
        b, h, s, d = qd.shape
        idx = (idx if idx.ndim else idx[None]).astype(jnp.int32)
        z = jnp.int32(0)
        verify = s > 1 and A.in_kv_verify_scope()
        if s == 1 or verify:
            # decode step (or a verify block): per-ROW write positions —
            # the serving slot pool holds requests at independent
            # offsets; lockstep batches (DecodeEngine) are the all-equal
            # special case. The same vmapped dynamic_update_slice covers
            # one token or a k-token verify block.
            def _write(buf, new, i):
                return jax.lax.dynamic_update_slice(buf, new, (z, i, z))

            kbuf = jax.vmap(_write)(kbuf, kd.astype(kbuf.dtype), idx)
            vbuf = jax.vmap(_write)(vbuf, vd.astype(vbuf.dtype), idx)
        else:
            # multi-token prefill of an empty cache: lockstep by
            # contract, one dynamic_update_slice covers every row
            pos = idx[0]
            kbuf = jax.lax.dynamic_update_slice(
                kbuf, kd.astype(kbuf.dtype), (z, z, pos, z))
            vbuf = jax.lax.dynamic_update_slice(
                vbuf, vd.astype(vbuf.dtype), (z, z, pos, z))
        new_cache = MultiHeadAttention.StaticKVCache(
            kbuf, vbuf, (idx + s).astype(jnp.int32))
        mask = None if attn_mask is None else raw(attn_mask)
        if mask is not None and mask.ndim > 2:
            mask = mask.reshape(mask.shape[0], mask.shape[-1])
        if s == 1:
            out = A.decode_attention(qd, kbuf, vbuf, idx + 1, bias=mask)
        elif verify:
            out = A.verify_attention(qd, kbuf, vbuf, idx + s, bias=mask)
        else:
            bias4 = None if mask is None else \
                mask.astype(jnp.float32)[:, None, None, :]
            out = A.sdpa(qd, kd, vd, bias4, is_causal=True)
        out = jnp.swapaxes(out, 1, 2).reshape(b, s, h * d)
        return Tensor._wrap(out), new_cache

    def _paged_kv_attention(self, q, k, v, attn_mask, cache):
        """Decode attention through a paged pool (serving-only, raw
        jnp): the single token's K/V is quantized and scattered into
        the physical page the slot's table maps for its write position
        (rescaling an int8 page whose scale it outranges), then the
        query attends over the pages — through the scalar-prefetched
        page table in the pallas kernel on TPU, or a gathered dense
        logical view on the XLA fallback path (bit-identical to the
        dense StaticKVCache when pages keep the compute dtype).
        Contract: decode steps only (S == 1 query token); prompt
        prefill runs on the regular flash path into a dense batch-1
        cache whose pages the serving join scatters separately."""
        import jax.numpy as jnp

        from ...core.tensor import Tensor
        from ...ops import attention as A
        from ...serving import paging as PG

        def raw(x):
            return x._data if isinstance(x, Tensor) else jnp.asarray(x)

        qd, kd, vd = raw(q), raw(k), raw(v)
        b, h, s, d = qd.shape
        verify = s > 1 and A.in_kv_verify_scope()
        if s != 1 and not verify:
            raise ValueError(
                "PagedKVCache attention is decode-only (one query "
                "token per slot); prefill goes through the join path, "
                "and a multi-token speculative verify block rides "
                "ops.attention.kv_verify_scope")
        idx = raw(cache.index).astype(jnp.int32)
        table = raw(cache.table).astype(jnp.int32)
        if verify:
            # speculative verify block: the s tokens land at each
            # slot's own offset, crossing page boundaries as they
            # fall; the caller's acceptance logic rolls the per-slot
            # index back afterwards (no page frees on reject)
            kp, ks = PG.write_tokens(cache.k, cache.k_scale, table,
                                     idx, kd)
            vp, vs = PG.write_tokens(cache.v, cache.v_scale, table,
                                     idx, vd)
        else:
            kp, ks = PG.write_token(cache.k, cache.k_scale, table, idx,
                                    kd[:, :, 0, :])
            vp, vs = PG.write_token(cache.v, cache.v_scale, table, idx,
                                    vd[:, :, 0, :])
        new_cache = PG.PagedKVCache(kp, vp, ks, vs, table,
                                    (idx + s).astype(jnp.int32))
        mask = None if attn_mask is None else raw(attn_mask)
        if mask is not None and mask.ndim > 2:
            mask = mask.reshape(mask.shape[0], mask.shape[-1])
        if verify:
            out = A.paged_verify_attention(qd, kp, vp, ks, vs, table,
                                           idx + s, bias=mask)
        else:
            out = A.paged_decode_attention(qd, kp, vp, ks, vs, table,
                                           idx + 1, bias=mask)
        out = jnp.swapaxes(out, 1, 2).reshape(b, s, h * d)
        return Tensor._wrap(out), new_cache

    def gen_paged_cache(self, num_pages, page_size, num_slots,
                        max_pages, dtype, kv_dtype=None,
                        page_sharding=None):
        """Per-layer paged pool: zeroed [num_pages + 1, page_size,
        H * D] K/V page arrays (token rows, the heads side by side;
        the +1 row is the trash page inactive slots' masked writes land
        on), [num_pages + 1, 1, H] per-(page, head) scales when
        kv_dtype is int8, an unmapped (trash-clipped) table and zero
        write indices. The serving engine owns the host-side
        PageAllocator / page table; this just shapes the device state.
        `page_sharding`: optional NamedSharding laying the page axis
        out across the mesh (the sharded engine's data-parallel page
        pool); page reads/writes stay pure selection, so placement
        never changes the math."""
        import jax.numpy as jnp

        from ...serving import paging as PG

        storage, quantized = PG.resolve_kv_dtype(kv_dtype, dtype)
        buf = jnp.zeros((int(num_pages) + 1, int(page_size),
                         self.num_heads * self.head_dim), storage)
        sc = jnp.zeros((int(num_pages) + 1, 1, self.num_heads),
                       jnp.float32) if quantized else None
        if page_sharding is not None:
            import jax

            buf = jax.device_put(buf, page_sharding)
            if sc is not None:
                sc = jax.device_put(sc, page_sharding)
        return PG.PagedKVCache(
            buf, buf, sc, sc,
            jnp.full((int(num_slots), int(max_pages)), int(num_pages),
                     jnp.int32),
            jnp.zeros((int(num_slots),), jnp.int32))

    @staticmethod
    def paged_prompt_splice(cache, page_ids, k_new, v_new):
        """Slot JOIN for paged pools: scatter a prefilled [1, H, P, D]
        K/V block into the physical pages `page_ids` (traced int32
        [ceil(P / page_size)]), quantizing per page on the way in.
        Like `static_kv_splice`, every operand that varies per join is
        traced, so joining any slot at any admitted prompt length
        reuses one compiled program per prompt bucket."""
        from ...serving import paging as PG

        quantized = cache.k_scale is not None
        kp, ks = PG.write_prompt_pages(cache.k, cache.k_scale, page_ids,
                                       k_new, quantized)
        vp, vs = PG.write_prompt_pages(cache.v, cache.v_scale, page_ids,
                                       v_new, quantized)
        return cache._replace(k=kp, v=vp, k_scale=ks, v_scale=vs)

    @staticmethod
    def static_kv_splice(cache, slot, k_new, v_new, n_written,
                         constraint=None):
        """Slot JOIN for pooled serving caches: write a prefilled
        [1, H, P, D] K/V block into row `slot` of a pooled [S, H, L, D]
        StaticKVCache (P <= L) and set that row's write index to
        `n_written`, leaving every other slot's buffers and index
        untouched. `slot` and `n_written` are traced int32 scalars, so
        joining ANY slot at ANY admitted prompt length reuses one
        compiled program — slot join never retraces. `constraint`:
        optional (kv_NamedSharding, index_NamedSharding) pinning the
        spliced pool back onto its mesh layout (the sharded engine's
        slot-on-data carry contract)."""
        import jax
        import jax.numpy as jnp

        z = jnp.int32(0)
        slot = jnp.asarray(slot, jnp.int32)
        k = jax.lax.dynamic_update_slice(
            cache.k, k_new.astype(cache.k.dtype), (slot, z, z, z))
        v = jax.lax.dynamic_update_slice(
            cache.v, v_new.astype(cache.v.dtype), (slot, z, z, z))
        index = jax.lax.dynamic_update_slice(
            cache.index,
            jnp.asarray(n_written, jnp.int32).reshape(1), (slot,))
        if constraint is not None:
            kv_ns, idx_ns = constraint
            k = jax.lax.with_sharding_constraint(k, kv_ns)
            v = jax.lax.with_sharding_constraint(v, kv_ns)
            index = jax.lax.with_sharding_constraint(index, idx_ns)
        return MultiHeadAttention.StaticKVCache(k, v, index)

    @staticmethod
    def splice_rows(buf, slot, rows, constraint=None):
        """Row splice for any pooled per-slot buffer ([S, ...]): write
        `rows` ([1, ...], trailing dims <= buf's) at row `slot` (traced
        int32). Used for the serving pool's cross-attention StaticCache
        K/V, pad-bias rows, and memory rows on slot join. `constraint`:
        optional NamedSharding pinned on the result."""
        import jax
        import jax.numpy as jnp

        z = jnp.int32(0)
        start = (jnp.asarray(slot, jnp.int32),) + (z,) * (buf.ndim - 1)
        out = jax.lax.dynamic_update_slice(
            buf, rows.astype(buf.dtype), start)
        if constraint is not None:
            out = jax.lax.with_sharding_constraint(out, constraint)
        return out

    def gen_cache(self, key, value=None, type=None, max_length=None,
                  batch_size=None, dtype=None, kv_sharding=None,
                  index_sharding=None):
        """Cache constructors. type=StaticCache precomputes K/V from
        `key` (cross-attention). max_length=N preallocates a
        StaticKVCache of [B, H, N, D] zero buffers + a zero write index
        — the decode-engine carry; B/dtype default to key's.
        `kv_sharding`/`index_sharding`: optional NamedShardings placing
        the pooled buffers straight onto a mesh (slot axis
        data-parallel in the sharded serving engine) instead of a
        single device."""
        if max_length is not None:
            import jax.numpy as jnp

            b = batch_size if batch_size is not None else key.shape[0]
            if dtype is None:
                dtype = self.q_proj.param_dtype
            buf = jnp.zeros(
                (int(b), self.num_heads, int(max_length), self.head_dim),
                dtype)
            idx = jnp.zeros((int(b),), jnp.int32)
            if kv_sharding is not None:
                import jax

                buf = jax.device_put(buf, kv_sharding)
                if index_sharding is not None:
                    idx = jax.device_put(idx, index_sharding)
            return self.StaticKVCache(buf, buf, idx)
        if type == MultiHeadAttention.StaticCache:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value if value is not None
                                              else key))
            return self.StaticCache(k, v)
        from ...tensor import ops as T

        b = key.shape[0]
        k = T.zeros([b, self.num_heads, 0, self.head_dim], key.dtype)
        v = T.zeros([b, self.num_heads, 0, self.head_dim], key.dtype)
        return self.Cache(k, v)


#: module-level aliases for the class-scoped cache namedtuples: their
#: __qualname__ is the bare typename, so pickle resolves them as
#: attributes of THIS module — the persistent AOT compile cache
#: (paddle_tpu.tuning.aot_cache) pickles PyTreeDefs that reference
#: them when serializing the engines' compiled programs
Cache = MultiHeadAttention.Cache
StaticCache = MultiHeadAttention.StaticCache
StaticKVCache = MultiHeadAttention.StaticKVCache


class GroupedQueryAttention(Layer):
    """Causal self-attention with `num_heads` query heads over
    `num_kv_heads` key-value heads of `head_dim` (each serves num_heads /
    num_kv_heads query heads), softmax at scale head_dim^-1/2, no bias,
    no positional term. q_proj [hidden, num_heads x head_dim], k_proj and
    v_proj [hidden, num_kv_heads x head_dim], o_proj back to hidden."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim):
        super().__init__()
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.q_proj = Linear(hidden_size, self.num_heads * self.head_dim,
                             bias_attr=False)
        self.k_proj = Linear(hidden_size, self.num_kv_heads * self.head_dim,
                             bias_attr=False)
        self.v_proj = Linear(hidden_size, self.num_kv_heads * self.head_dim,
                             bias_attr=False)
        self.o_proj = Linear(self.num_heads * self.head_dim, hidden_size,
                             bias_attr=False)

    def forward(self, x):
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             layout="BSHD")
        return self.o_proj(out.reshape(
            [b, s, self.num_heads * self.head_dim]))


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 moe_experts=0, moe_capacity_factor=1.25):
        super().__init__()
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(
            d_model, nhead, attn_dropout if attn_dropout is not None
            else dropout, weight_attr=weight_attr, bias_attr=bias_attr)
        if moe_experts:
            from .moe import MoELayer

            self.moe = MoELayer(d_model, dim_feedforward,
                                num_experts=moe_experts,
                                capacity_factor=moe_capacity_factor,
                                activation=activation)
            self.linear1 = self.linear2 = None
        else:
            self.moe = None
            self.linear1 = Linear(d_model, dim_feedforward, weight_attr,
                                  bias_attr)
            self.linear2 = Linear(dim_feedforward, d_model, weight_attr,
                                  bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout_act = Dropout(act_dropout if act_dropout is not None
                                   else dropout)
        self.activation = getattr(F, activation)

    def forward(self, src, src_mask=None, cache=None, segment_ids=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask,
                                 segment_ids=segment_ids)
        else:
            src, cache = self.self_attn(src, src, src, src_mask, cache)
        src = residual + self.dropout1(src)
        if not self.normalize_before:
            src = self.norm1(src)
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        if self.moe is not None:
            src = self.moe(src)
        else:
            src = self.linear2(self.dropout_act(self.activation(
                self.linear1(src))))
        src = residual + self.dropout2(src)
        if not self.normalize_before:
            src = self.norm2(src)
        return src if cache is None else (src, cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        import copy

        self.layers = LayerList(
            [encoder_layer] + [copy.deepcopy(encoder_layer)
                               for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None, cache=None, segment_ids=None):
        output = src
        new_caches = []
        for i, layer in enumerate(self.layers):
            if cache is None:
                output = run_block(layer, output, src_mask,
                                   segment_ids=segment_ids)
            else:
                output, c = layer(output, src_mask, cache[i])
                new_caches.append(c)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        self.normalize_before = normalize_before
        ad = attn_dropout if attn_dropout is not None else dropout
        self.self_attn = MultiHeadAttention(d_model, nhead, ad)
        self.cross_attn = MultiHeadAttention(d_model, nhead, ad)
        self.linear1 = Linear(d_model, dim_feedforward)
        self.linear2 = Linear(dim_feedforward, d_model)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.dropout_act = Dropout(act_dropout if act_dropout is not None
                                   else dropout)
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
            incremental_cache = None
        else:
            tgt, incremental_cache = self.self_attn(tgt, tgt, tgt, tgt_mask,
                                                    cache[0])
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
            static_cache = None
        else:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask, cache[1])
            static_cache = cache[1]
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.dropout_act(self.activation(
            self.linear1(tgt))))
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        if cache is None:
            return tgt
        return tgt, (incremental_cache, static_cache)

    def gen_cache(self, memory, max_length=None, batch_size=None,
                  dtype=None):
        if max_length is not None:
            incremental = self.self_attn.gen_cache(
                memory, max_length=max_length, batch_size=batch_size,
                dtype=dtype)
        else:
            incremental = self.self_attn.gen_cache(memory)
        static = self.cross_attn.gen_cache(
            memory, type=MultiHeadAttention.StaticCache)
        return incremental, static


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        import copy

        self.layers = LayerList(
            [decoder_layer] + [copy.deepcopy(decoder_layer)
                               for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        output = tgt
        new_caches = []
        for i, layer in enumerate(self.layers):
            if cache is None:
                output = layer(output, memory, tgt_mask, memory_mask)
            else:
                output, c = layer(output, memory, tgt_mask, memory_mask,
                                  cache[i])
                new_caches.append(c)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip=False, max_length=None,
                  batch_size=None, dtype=None):
        return [layer.gen_cache(memory, max_length=max_length,
                                batch_size=batch_size, dtype=dtype)
                for layer in self.layers]

    def generate(self, memory, embed, project, **kwargs):
        """Fused autoregressive generation on the static KV-cache path:
        prefill through the flash-capable prompt pass, then the whole
        decode as ONE jitted lax.scan (greedy or beam) with
        StaticKVCache as carry. embed/project: the token-embedding and
        logits-projection Layers around this decoder stack. See
        paddle_tpu.text.generation.DecodeEngine for the full contract
        (bucketing, max_new_tokens, prompts)."""
        from ...text.generation import DecodeEngine

        eng = getattr(self, "_decode_engine", None)
        if eng is None or eng.embed_ref is not embed \
                or eng.project_ref is not project:
            eng = DecodeEngine(self, embed, project)
            self._decode_engine = eng
        return eng.generate(memory, **kwargs)


class Transformer(Layer):
    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        self.d_model = d_model
        self.nhead = nhead
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before)
            norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers,
                                              norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before)
            norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers,
                                              norm)

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    @staticmethod
    def generate_square_subsequent_mask(length):
        import jax.numpy as jnp

        from ...core.tensor import Tensor

        m = jnp.where(jnp.tril(jnp.ones((length, length), bool)), 0.0, -1e9)
        return Tensor._wrap(m.astype(jnp.float32))
