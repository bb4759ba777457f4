"""Paged KV-cache subsystem: allocator invariants, paged-vs-dense
bit-match, shared-prefix reuse (zero re-prefill), copy-on-write
isolation, quantized pages, OutOfPages backpressure, and chaos
leak-freedom.

Numerics contract under test: with fp32 pages the paged pool's greedy
decode is BIT-IDENTICAL to the dense StaticKVCache pool (the gathered
logical view reproduces the dense buffer exactly, masked softmax width
included, because the pool's max_len is a page multiple); a
shared-prefix join maps cached pages with zero prefill FLOPs
(`prefill_count` + the absence of a `serving.prefill` fault-point hit
prove it) and still bit-matches a cold prefill.
"""
import numpy as np
import pytest

from paddle_tpu import nn
from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                             TransformerDecoderLayer)
from paddle_tpu.serving import (OutOfPages, PageAllocator,
                                PagedServingEngine, PrefixCache,
                                Request, Scheduler, ServingEngine)
from paddle_tpu.serving import paging as PG
from paddle_tpu.testing import faults


# ----------------------------------------------------------------------
# allocator: refcount / free-list invariants
# ----------------------------------------------------------------------

def test_allocator_basic_and_out_of_pages():
    a = PageAllocator(4, 16)
    p = a.alloc(3)
    assert len(set(p)) == 3 and a.pages_free == 1
    with pytest.raises(OutOfPages, match="free of 4"):
        a.alloc(2)
    a.incref(p[:1])
    a.decref(p)                      # p[0] survives on its second ref
    assert a.pages_free == 3 and a.refcount[p[0]] == 1
    a.decref(p[:1])
    assert a.pages_free == 4
    with pytest.raises(RuntimeError, match="decref on free"):
        a.decref(p[:1])
    a.check()


def test_allocator_random_soak_invariants():
    """Random alloc / incref / decref soak: free + referenced always
    partitions the pool, OutOfPages never corrupts state, and draining
    every reference returns the allocator to all-free."""
    rs = np.random.RandomState(7)
    a = PageAllocator(32, 16)
    held = []                        # flat multiset of references held
    for step in range(2000):
        op = rs.randint(3)
        if op == 0:
            n = int(rs.randint(1, 6))
            try:
                pages = a.alloc(n)
            except OutOfPages:
                assert a.pages_free < n
            else:
                held.extend(pages)
        elif op == 1 and held:
            p = held[rs.randint(len(held))]
            a.incref([p])
            held.append(p)
        elif op == 2 and held:
            i = rs.randint(len(held))
            a.decref([held.pop(i)])
        if step % 100 == 0:
            a.check()
            assert a.pages_in_use == len(set(held))
    while held:
        a.decref([held.pop()])
    a.check()
    assert a.pages_free == 32


def test_prefix_cache_lru_and_reclaim():
    a = PageAllocator(8, 16)
    c = PrefixCache(a, capacity=2)
    keys = []
    for i in range(3):
        pages = a.alloc(2)
        k = ("k", i)
        c.insert(k, pages, tok0=i, n_prompt=1, Pb=2)
        a.decref(pages)              # cache now holds the only ref
        keys.append(k)
    # capacity 2: the oldest entry was dropped, its pages freed
    assert len(c) == 2 and a.pages_free == 8 - 4
    assert c.peek(keys[0]) is None and c.peek(keys[2]) is not None
    assert c.reclaim(6)              # drops LRU entries until 6 free
    assert a.pages_free >= 6
    c.flush()
    a.check()
    assert a.pages_free == 8


def test_prefix_cache_reinsert_refreshes_lru():
    """A re-inserted (resident) prefix is HOT: it must move to the MRU
    end, so the next capacity eviction takes the genuinely coldest
    entry instead."""
    a = PageAllocator(8, 16)
    c = PrefixCache(a, capacity=2)
    pages = {}
    for i in range(2):
        pages[i] = a.alloc(1)
        c.insert(("k", i), pages[i], tok0=i, n_prompt=1, Pb=1)
        a.decref(pages[i])
    c.insert(("k", 0), pages[0], tok0=0, n_prompt=1, Pb=1)  # re-insert
    p2 = a.alloc(1)
    c.insert(("k", 2), p2, tok0=2, n_prompt=1, Pb=1)
    a.decref(p2)
    assert c.peek(("k", 0)) is not None      # refreshed: survived
    assert c.peek(("k", 1)) is None          # true LRU evicted
    c.flush()
    a.check()
    assert a.pages_free == 8


# ----------------------------------------------------------------------
# the radix prefix trie (host-side unit cells; no engine)
# ----------------------------------------------------------------------

def _radix(n_pages=32, psz=4, capacity=64, mid_page="round_down"):
    a = PageAllocator(n_pages, psz)
    return a, PG.RadixPrefixCache(a, capacity=capacity, page_size=psz,
                                  mid_page=mid_page)


def _radix_insert(a, trie, tokens, P0, Pb, memory=None, tenant=None,
                  tok0=7):
    """Alloc the prompt bucket's pages, insert, drop the caller refs —
    the trie then holds the only references (like a drained slot)."""
    pages = a.alloc(PG.pages_for(Pb, trie.page_size))
    trie.insert(tokens, P0, Pb, trie.mem_digest(memory), tenant, pages,
                tok0)
    a.decref(pages)
    return pages


def test_radix_trie_longest_prefix_whole_and_partial():
    a, trie = _radix()
    toks = (0, 3, 5, 7, 2, 9, 4, 11, 6, 13)          # P0=10, Pb=16
    pages = _radix_insert(a, trie, toks, 10, 16)
    # whole hit: every page back, in page order, with the cached tok0
    kind, ent = trie.lookup(toks, 10, 16)
    assert kind == "whole"
    assert ent["pages"] == list(pages) and ent["tok0"] == 7
    assert ent["n_prompt"] == 10 and ent["Pb"] == 16
    # page-aligned divergence: first 2 pages (8 tokens) shared
    div = toks[:8] + (14, 8, 12)                      # P0=11
    kind, ent = trie.lookup(div, 11, 16)
    assert kind == "partial"
    assert ent["pages"] == list(pages[:2])
    assert ent["j"] == 0 and ent["seed_len"] == 8
    # unrelated prompt (no shared token at all): a miss
    assert trie.lookup((1, 15, 14, 2), 4, 4) is None
    assert (trie.whole_hits, trie.partial_hits, trie.misses) == (1, 1, 1)
    assert trie.hits == 2 and 0 < trie.hit_rate < 1
    trie.flush()
    a.check()
    assert a.pages_free == 32


def test_radix_trie_mid_page_cow_divergence_and_backoff():
    # mid_page="cow" preserves the sub-page extension path: the trie
    # hands back the split page as a COW source + in-page length j
    a, trie = _radix(mid_page="cow")
    full = (0, 3, 5, 7, 2, 9, 4, 11, 6, 13)           # P0=10, Pb=16
    pages = _radix_insert(a, trie, full, 10, 16, tok0=5)
    # divergence INSIDE page 1 (matches 6 of its 8 tokens)
    mid = full[:6] + (15, 8, 12, 10)                  # P0=10
    kind, ent = trie.lookup(mid, 10, 16)
    assert kind == "partial"
    assert ent["pages"] == [pages[0]] and ent["j"] == 2
    assert ent["cow_src"] == pages[1] and ent["seed_len"] == 6
    # all-real-tokens-matched but no terminal (shorter prompt): back
    # off one page so the attach has a tail; the dropped page
    # re-emerges as the COW source with j = page_size - 1
    kind, ent = trie.lookup(full[:8], 8, 8)
    assert kind == "partial"
    assert ent["pages"] == [pages[0]] and ent["j"] == 3
    assert ent["cow_src"] == pages[1] and ent["seed_len"] == 7
    assert trie.stats()["rounded_down"] == 0
    trie.flush()
    a.check()
    assert a.pages_free == 32


def test_radix_trie_mid_page_round_down_default():
    """Default policy: a mid-page match rounds DOWN to the page
    boundary — no COW source, the partial page re-prefills with the
    divergent tail (the sub-page copy measurably loses on CPU)."""
    a, trie = _radix()
    assert trie.mid_page == "round_down"
    full = (0, 3, 5, 7, 2, 9, 4, 11, 6, 13)           # P0=10, Pb=16
    pages = _radix_insert(a, trie, full, 10, 16, tok0=5)
    # divergence INSIDE page 1: the match truncates to page 0's edge
    mid = full[:6] + (15, 8, 12, 10)                  # P0=10
    kind, ent = trie.lookup(mid, 10, 16)
    assert kind == "partial"
    assert ent["pages"] == [pages[0]]
    assert ent["j"] == 0 and ent["cow_src"] is None
    assert ent["seed_len"] == 4
    # back-off case: all real tokens matched, no terminal — rounding
    # down the dropped page's re-emergence leaves one full page
    kind, ent = trie.lookup(full[:8], 8, 8)
    assert kind == "partial"
    assert ent["pages"] == [pages[0]]
    assert ent["j"] == 0 and ent["cow_src"] is None
    assert ent["seed_len"] == 4
    # a one-page prompt that would only match sub-page: now a miss
    # (re-prefilling < page_size tokens beats a page copy)
    assert trie.lookup(full[:3] + (15,), 4, 4) is None
    st = trie.stats()
    assert st["rounded_down"] == 3
    # peek is side-effect free: the counter must not move
    trie.peek(mid, 10, 16)
    assert trie.stats()["rounded_down"] == 3
    # bad policy value rejected loudly
    with pytest.raises(ValueError):
        PG.RadixPrefixCache(a, page_size=4, mid_page="maybe")
    trie.flush()
    a.check()
    assert a.pages_free == 32


def test_radix_trie_leaf_first_lru_eviction_and_reclaim():
    a, trie = _radix(capacity=2)
    pre = (0, 3, 5, 7)                                # one shared page
    tails = [(2, 9), (4, 11), (6, 13)]
    for i, t in enumerate(tails):
        _radix_insert(a, trie, pre + t, 6, 8, tok0=i)
    # capacity 2: the OLDEST terminal went, the shared interior page
    # survives (it still serves partial matches for the evictee)
    assert len(trie) == 2
    kind, _ = trie.lookup(pre + tails[0], 6, 8)
    assert kind == "partial"                          # downgraded
    assert trie.lookup(pre + tails[2], 6, 8)[0] == "whole"
    st = trie.stats()
    assert st["terminals"] == 2 and st["nodes"] >= 1
    assert st["pages"] == a.pages_in_use
    # page pressure: reclaim drops cold leaves until enough are free
    assert trie.reclaim(a.pages_free + 2)
    assert trie.stats()["pages"] == a.pages_in_use
    trie.flush()
    a.check()
    assert a.pages_free == 32


def test_radix_trie_tenant_scopes_and_generation_bump():
    a, trie = _radix()
    toks = (0, 3, 5, 7, 2, 9)
    _radix_insert(a, trie, toks, 6, 8, tenant=("lora", 0))
    assert trie.lookup(toks, 6, 8, tenant=("lora", 0))[0] == "whole"
    # other scopes never see it: base traffic, another adapter
    assert trie.lookup(toks, 6, 8) is None
    assert trie.lookup(toks, 6, 8, tenant=("other", 0)) is None
    # peek with a bumped generation: a miss, and side-effect free
    assert trie.peek(toks, 6, 8, tenant=("lora", 1)) is None
    assert trie.lookup(toks, 6, 8, tenant=("lora", 0))[0] == "whole"
    # lookup with the bumped generation DROPS the stale subtree
    assert trie.lookup(toks, 6, 8, tenant=("lora", 1)) is None
    assert trie.stats()["pages"] == 0
    a.check()
    assert a.pages_free == 32
    # memory digest scoping: same tokens, different cross-attn memory
    m1 = np.ones((2, 4), "f4")
    m2 = np.zeros((2, 4), "f4")
    _radix_insert(a, trie, toks, 6, 8, memory=m1)
    assert trie.lookup(toks, 6, 8, scope=trie.mem_digest(m1))[0] == \
        "whole"
    assert trie.lookup(toks, 6, 8, scope=trie.mem_digest(m2)) is None
    trie.flush()
    a.check()


class _LoggingAllocator(PageAllocator):
    """Logs every decref: two tries driven alike in lockstep hand out
    the same page ids, so equal logs mean the same victims in the same
    order."""

    def __init__(self, *a):
        super().__init__(*a)
        self.log = []

    def decref(self, pages):
        self.log.append(tuple(int(p) for p in pages))
        super().decref(pages)


class _ScanEvictionTrie(PG.RadixPrefixCache):
    """The trie with PR 35's eviction: a scan of every node for each
    victim, least tick first, first met on a tie. The oracle the kept
    eviction order must agree with, victim for victim."""

    def _evict_one(self):
        best = None                   # (tick, kind, ...)
        stack = list(self._roots.items())
        while stack:
            key, node = stack.pop()
            stack.extend((key, c) for c in node.children.values())
            for tkey, ent in node.terminals.items():
                if best is None or ent["tick"] < best[0]:
                    best = (ent["tick"], "terminal", node, tkey)
            if (node.parent is not None and not node.children
                    and not node.terminals):
                if best is None or node.tick < best[0]:
                    best = (node.tick, "node", node, key)
        if best is None:
            return False
        self.evictions += 1
        if best[1] == "terminal":
            _, _, node, tkey = best
            ent = node.terminals.pop(tkey)
            self.allocator.decref(ent["pages"])
            self._n_terminals -= 1
            self._n_pages -= len(ent["pages"])
        else:
            _, _, node, key = best
            del node.parent.children[node.tokens]
            self.allocator.decref([node.page])
            self._n_nodes -= 1
            self._n_pages -= 1
            parent = node.parent
            if (parent.parent is None and not parent.children
                    and not parent.terminals):
                self._roots.pop(key, None)
        return True


@pytest.mark.parametrize("mid_page", ["round_down", "cow"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_radix_trie_eviction_order_matches_full_scan(seed, mid_page):
    """A seeded trace of inserts (short prompts and 17-32-page ones,
    sharing preambles), chunk-style prefix inserts, lookups (bursts of
    them too), peeks, reclaims, tenant drops, generation bumps and
    flushes, over two
    memory scopes, drives the trie and the full-scan oracle in
    lockstep: every result, every gauge and every page released
    (victims and their order) agree, and both allocators stay
    clean."""
    psz, n_pages = 4, 640
    rs = np.random.RandomState(seed)
    sides = []
    for cls in (PG.RadixPrefixCache, _ScanEvictionTrie):
        a = _LoggingAllocator(n_pages, psz)
        sides.append((a, cls(a, capacity=12, page_size=psz,
                             mid_page=mid_page)))
    scopes = [sides[0][1].mem_digest(m) for m in
              (None, np.arange(8, dtype="f4").reshape(2, 4))]
    preambles = [tuple(int(t) for t in rs.randint(0, 3, 32 * psz))
                 for _ in range(3)]
    gen, seen = 0, []

    def prompt():
        long = rs.rand() < 0.3
        n_pp = int(rs.randint(17, 33) if long else rs.randint(1, 5))
        P0 = int(rs.randint((n_pp - 1) * psz + 1, n_pp * psz + 1))
        keep = int(rs.randint(0, P0 + 1))
        pre = preambles[rs.randint(len(preambles))]
        toks = pre[:keep] + tuple(
            int(t) for t in rs.randint(0, 3, P0 - keep))
        Pb = (n_pp + int(rs.randint(0, 2))) * psz
        return toks, P0, Pb

    def both(fn):
        out = [fn(a, trie) for a, trie in sides]
        assert out[0] == out[1]
        return out[0]

    def insert(a, trie, toks, P0, Pb, scope, tenant, prefix_only):
        n = PG.pages_for(Pb, psz)
        if a.pages_free < n:
            trie.reclaim(n)
        if a.pages_free < n:
            return None
        pages = a.alloc(n)
        if prefix_only:
            trie.insert_prefix(toks, scope, tenant,
                               pages[:P0 // psz])
        else:
            trie.insert(toks, P0, Pb, scope, tenant, pages, tok0=P0)
        a.decref(pages)
        return pages

    for step in range(500):
        op = rs.rand()
        scope = scopes[rs.randint(2)]
        tenant = None if rs.rand() < 0.7 else ("lora", gen)
        if op < 0.4 or not seen:
            toks, P0, Pb = prompt()
            prefix_only = rs.rand() < 0.15
            both(lambda a, t: insert(a, t, toks, P0, Pb, scope, tenant,
                                     prefix_only))
            seen.append((toks, P0, Pb, scope, tenant))
        elif op < 0.7:
            toks, P0, Pb, scope, tenant = seen[rs.randint(len(seen))]
            if rs.rand() < 0.3:
                toks = toks[:-1] + ((toks[-1] + 1) % 3,)
            fn = "lookup" if rs.rand() < 0.8 else "peek"
            both(lambda a, t: getattr(t, fn)(toks, P0, Pb, scope=scope,
                                             tenant=tenant))
        elif op < 0.75:
            # a hot prompt: a burst of hits leaves stale order entries
            # enough to make the trie rebuild its heap
            toks, P0, Pb, scope, tenant = seen[-1]
            for _ in range(100):
                both(lambda a, t: t.lookup(toks, P0, Pb, scope=scope,
                                           tenant=tenant))
        elif op < 0.9:
            n = int(rs.randint(1, 200))
            both(lambda a, t: (t.reclaim(a.pages_free + n), a.pages_free))
        elif op < 0.95:
            both(lambda a, t: t.drop_tenant("lora"))
        elif op < 0.97:
            gen += 1
        else:
            both(lambda a, t: t.flush())
        both(lambda a, t: ({k: v for k, v in t.stats().items()
                            if k not in ("digests", "evict_visits")},
                           a.pages_free, len(a.log)))
    both(lambda a, t: t.reclaim(n_pages))
    assert sides[0][0].log == sides[1][0].log
    assert sides[0][1].evictions > 50
    for a, trie in sides:
        a.check()
        trie.flush()
        assert a.pages_free == n_pages


def test_radix_trie_eviction_visits_stay_bounded():
    """Evicting from a trie of 3,000 items (1,000 two-page prompts,
    each looked up again, so stale order entries abound) examines a
    handful of order entries per victim, never the trie's size."""
    psz, n = 4, 1000
    a = PageAllocator(3 * n, psz)
    trie = PG.RadixPrefixCache(a, capacity=2 * n, page_size=psz)
    rs = np.random.RandomState(5)
    prompts = [(i,) + tuple(int(t) for t in rs.randint(0, 9, 2 * psz - 1))
               for i in range(n)]
    for toks in prompts:
        pages = a.alloc(2)
        trie.insert(toks, 2 * psz, 2 * psz, "", None, pages, tok0=0)
        a.decref(pages)
    for i in rs.permutation(n):
        assert trie.lookup(prompts[i], 2 * psz, 2 * psz)[0] == "whole"
    st = trie.stats()
    assert st["nodes"] + st["terminals"] == 3 * n
    assert trie.reclaim(a.n_pages // 2)
    st = trie.stats()
    assert st["evictions"] >= n // 2
    assert st["evict_visits"] <= 3 * st["evictions"]
    trie.flush()
    a.check()
    assert trie.stats()["nodes"] == 0 and a.pages_free == a.n_pages


# ----------------------------------------------------------------------
# page math: quantization round-trips
# ----------------------------------------------------------------------

def test_page_roundtrip_exact_fp32_bf16():
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    chunks = jnp.asarray(rs.randn(3, 16, 2 * 8).astype("f4"))
    q32, s32 = PG.quantize_chunks(chunks, jnp.float32, False)
    assert s32 is None
    np.testing.assert_array_equal(np.asarray(q32), np.asarray(chunks))
    qb, sb = PG.quantize_chunks(chunks, jnp.bfloat16, False)
    assert sb is None
    np.testing.assert_array_equal(
        np.asarray(qb.astype(jnp.float32)),
        np.asarray(chunks.astype(jnp.bfloat16).astype(jnp.float32)))


def test_page_roundtrip_int8_within_tolerance():
    """Symmetric per-(page, head) int8: |dequant - x| <= scale / 2."""
    import jax.numpy as jnp

    rs = np.random.RandomState(1)
    chunks = jnp.asarray((rs.randn(4, 16, 2 * 8) * 3).astype("f4"))
    q, s = PG.quantize_chunks(chunks, jnp.int8, True, 2)
    assert q.dtype == jnp.int8 and s.shape == (4, 1, 2)
    assert q.shape == chunks.shape
    s_lanes = jnp.repeat(s, 8, axis=-1)       # a head's scale on its D
    deq = q.astype(jnp.float32) * s_lanes
    err = np.asarray(jnp.abs(deq - chunks))
    bound = np.asarray(s_lanes / 2) + 1e-7
    assert (err <= bound).all()
    # all-zero pages quantize with scale 1 (no divide-by-zero)
    qz, sz = PG.quantize_chunks(jnp.zeros((1, 16, 2 * 8)), jnp.int8,
                                True, 2)
    assert float(jnp.abs(qz).max()) == 0 and float(sz.min()) == 1.0


def test_gather_pages_reproduces_dense_exactly():
    import jax.numpy as jnp

    rs = np.random.RandomState(2)
    S, H, psz, mp, D = 3, 2, 16, 4, 8
    dense = rs.randn(S, H, mp * psz, D).astype("f4")
    table = np.arange(S * mp, dtype=np.int32).reshape(S, mp)
    pages = np.zeros((S * mp + 1, psz, H * D), "f4")
    for s in range(S):
        for p in range(mp):
            # a page is psz token rows, a row the heads side by side
            pages[table[s, p]] = dense[
                s, :, p * psz:(p + 1) * psz, :].transpose(
                    1, 0, 2).reshape(psz, H * D)
    g = PG.gather_pages(jnp.asarray(pages), None, jnp.asarray(table), H,
                        jnp.float32)
    np.testing.assert_array_equal(np.asarray(g), dense)


# (S, H, psz, mp, D) and the written length of every slot. 0 is an
# inactive slot (its table row is all trash, as the pool maps it); the
# others cover one token, a page boundary, mid-page and all `mp` pages.
# The wide shape's heads are two lane tiles each, so the per-head sums
# pair lanes across tiles.
# "shared" is the pool's shape with the prefix cache's sharing in the
# table: two slots map ONE physical first page, and a third maps a
# `copy_page` duplicate of it (the copy-on-write a joiner decodes into).
# "cell" is the benchmark pool's page: 16 heads of 64 on 1,024 lanes.
# A grid step takes a block of P pages (`_paged_block_pages`): "blocks"
# is a table of 11 pages that a block of 8 does not divide, the written
# lengths at the block's edges (nothing, one token, a page, one short of
# the block, the block, one past it, the whole table); "wide" is two
# blocks of 2 over 3 pages.
_FLASH_SHAPES = {
    "pool": ((5, 2, 16, 4, 8), [0, 1, 16, 33, 64]),
    "shared": ((5, 2, 16, 4, 8), [0, 1, 16, 33, 64]),
    "wide": ((2, 4, 32, 3, 256), [40, 96]),
    "cell": ((3, 16, 16, 3, 64), [0, 17, 48]),
    "blocks": ((7, 2, 8, 11, 64), [0, 1, 8, 63, 64, 65, 88]),
}
_FLASH_BLOCK_PAGES = {"pool": 4, "shared": 4, "wide": 2, "cell": 2,
                      "blocks": 8}


@pytest.mark.parametrize("shape", sorted(_FLASH_SHAPES))
@pytest.mark.parametrize("with_bias", [True, False],
                         ids=["bias", "nobias"])
@pytest.mark.parametrize("kv", ["float32", "bfloat16", "int8"])
def test_paged_flash_decode_interpret_parity(kv, with_bias, shape):
    """The scalar-prefetch page-table kernel (interpret mode on CPU)
    matches the gathered XLA reference over a shuffled table: fp32,
    bf16 and int8 pages (widened / dequantized in-kernel), with and
    without a key bias."""
    import jax.numpy as jnp

    from paddle_tpu.ops import attention as A

    (S, H, psz, mp, D), lens = _FLASH_SHAPES[shape]
    assert A._paged_kernel_fits(psz, H * D) == (
        shape in ("wide", "cell", "blocks"))
    assert A._paged_block_pages(psz, H * D, 1, mp, kv) == \
        _FLASH_BLOCK_PAGES[shape]
    rs = np.random.RandomState(3)
    N = S * mp + 2
    table = rs.permutation(N)[:S * mp].reshape(S, mp).astype(np.int32)
    for s, n in enumerate(lens):
        table[s, PG.pages_for(n, psz):] = N         # unmapped -> trash
    pages = jnp.asarray(rs.randn(N + 1, psz, H * D).astype("f4"))
    if shape == "shared":
        spare = table[4, 0]
        table[4, 0] = table[3, 0]                   # mapped twice
        pages, _ = PG.copy_page(pages, None, jnp.int32(table[3, 0]),
                                jnp.int32(spare))
        table[2, 0] = spare                         # the COW duplicate
    tbl = jnp.asarray(table)
    q = jnp.asarray(rs.randn(S, H, 1, D).astype("f4"))
    length = jnp.asarray(lens, jnp.int32)
    bias = None
    if with_bias:
        bias = rs.randn(S, mp * psz).astype("f4") * 0.1
        bias[:, 2:5] = -1e9         # a bucketed prompt's pad hole
        bias = jnp.asarray(bias)
    pages, scales = PG.quantize_chunks(pages, kv, kv == "int8", H)
    dense = PG.gather_pages(pages, scales, tbl, H, jnp.float32)
    ref = np.asarray(A.decode_attention_reference(q, dense, dense, length,
                                                  bias))
    out = np.asarray(A.paged_flash_decode(q, pages, pages, scales, scales,
                                          tbl, length, bias,
                                          interpret=True))
    assert out.shape == (S, H, 1, D) and np.isfinite(out).all()
    live = np.asarray(lens) > 0
    # a slot that holds nothing has no reference (a softmax over no
    # key); the kernel's answer for it is an exact, finite zero
    assert not out[~live].any()
    np.testing.assert_allclose(out[live], ref[live], rtol=1e-5, atol=2e-6)


# ----------------------------------------------------------------------
# the paged serving pool
# ----------------------------------------------------------------------

def _small_stack(seed=7, D=32, H=2, V=17, layers=2):
    np.random.seed(seed)
    layer = TransformerDecoderLayer(D, H, 64, dropout=0.0)
    dec = TransformerDecoder(layer, layers)
    dec.eval()
    embed = nn.Embedding(V, D)
    proj = nn.Linear(D, V)
    return dec, embed, proj, D, V


def _mk_request(rs, D, V, pmax=6, nmax=10, **kw):
    P = int(rs.randint(1, pmax + 1))
    prompt = rs.randint(2, V, (P,)).astype(np.int32)
    prompt[0] = 0
    mem_seed = int(prompt.sum()) * 131 + P
    mem = np.random.RandomState(mem_seed).randn(4, D).astype("f4")
    n = int(rs.randint(2, nmax + 1))
    return Request(prompt, mem, max_new_tokens=n, eos_id=1, **kw)


def _drive(eng, reqs, max_iterations=5000):
    sched = Scheduler(max_queue=len(reqs) + 8)
    for r in reqs:
        sched.submit(r)
    eng.serve_until_idle(sched, max_iterations=max_iterations)
    return [r.result(timeout=5) for r in reqs]


def _specs(seed, n, D, V):
    rs = np.random.RandomState(seed)
    return [(_mk_request(rs, D, V).prompt, _mk_request(rs, D, V).memory)
            for _ in range(n)]


def test_paged_bitmatch_dense_greedy_fp32():
    """fp32 pages: every request through the paged pool bit-matches the
    dense StaticKVCache pool — including repeats served from the prefix
    cache — and the compile cache stays one program per bucket/config."""
    stack = _small_stack(seed=21)
    dec, embed, proj, D, V = stack
    rs = np.random.RandomState(22)
    base = [_mk_request(rs, D, V) for _ in range(10)]
    specs = [(r.prompt, r.memory, r.max_new_tokens) for r in base]
    specs += specs[:3]               # repeats -> prefix-cache hits

    def mk_reqs():
        return [Request(p.copy(), m, max_new_tokens=n, eos_id=1)
                for p, m, n in specs]

    dense = ServingEngine(dec, embed, proj, num_slots=4, max_len=32)
    res_d = _drive(dense, mk_reqs())
    paged = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                          paged=True, page_size=16, num_pages=24)
    assert isinstance(paged, PagedServingEngine)
    res_p = _drive(paged, mk_reqs())
    for a, b in zip(res_d, res_p):
        assert a.ok and b.ok
        assert a.finish_reason == b.finish_reason
        np.testing.assert_array_equal(a.tokens, b.tokens)
    steps = {k: v for k, v in paged.trace_counts.items()
             if k[0] == "pstep"}
    joins = {k: v for k, v in paged.trace_counts.items()
             if k[0] == "pjoin"}
    assert len(steps) == 1 and set(steps.values()) == {1}, steps
    assert set(joins.values()) == {1}, joins
    assert paged.metrics.prefix_hits >= 3
    # drained pool: only prefix-cache pages still held; flush -> empty
    paged.flush_prefix_cache()
    paged._alloc.check()
    assert paged._alloc.pages_free == paged.num_pages


def test_shared_prefix_join_zero_prefill_bitmatch():
    """A repeated (prompt, memory) joins from the prefix cache: ZERO
    prefill FLOPs (prefill_count frozen AND the serving.prefill fault
    point records no hit) and the output is bit-identical to the cold
    prefill's."""
    dec, embed, proj, D, V = _small_stack(seed=31)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        paged=True, page_size=16, num_pages=16)
    rs = np.random.RandomState(32)
    r1 = _mk_request(rs, D, V, nmax=8)
    cold = _drive(eng, [r1])[0]
    assert cold.ok and eng.prefill_count == 1
    assert eng.metrics.prefix_misses == 1
    # the repeat: count serving.prefill hits while it joins (an armed
    # never-firing plan makes the registry count hits)
    r2 = Request(r1.prompt.copy(), r1.memory,
                 max_new_tokens=r1.max_new_tokens, eos_id=1)
    with faults.inject("serving.prefill", on="nth", n=10 ** 9):
        warm = _drive(eng, [r2])[0]
        hits = faults.hit_counts().get("serving.prefill", 0)
    assert warm.ok
    assert hits == 0                 # zero prefill work for the join
    assert eng.prefill_count == 1    # still only the cold one
    assert eng.metrics.prefix_hits == 1
    np.testing.assert_array_equal(cold.tokens, warm.tokens)


def test_cow_isolation_between_prefix_sharers():
    """Two co-resident requests sharing a prompt whose bucket ends
    mid-page (Pb < page_size) both decode-write into what was the
    shared tail page: copy-on-write gives each a private copy, outputs
    bit-match solo dense runs, and the shared original stays immutable
    (a third joiner still reuses it bit-exactly)."""
    dec, embed, proj, D, V = _small_stack(seed=41)
    prompt = np.asarray([0, 3, 5], np.int32)     # bucket 4 < page 16
    mem = np.random.RandomState(5).randn(4, D).astype("f4")

    def reqs(n):
        return [Request(prompt.copy(), mem, max_new_tokens=12,
                        eos_id=None) for _ in range(n)]

    dense = ServingEngine(dec, embed, proj, num_slots=2, max_len=32)
    want = _drive(dense, reqs(1))[0]
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        paged=True, page_size=16, num_pages=16,
                        max_joins_per_iter=2)
    got = _drive(eng, reqs(2))       # co-resident: joined same iter
    for res in got:
        assert res.ok
        np.testing.assert_array_equal(res.tokens, want.tokens)
    assert eng.metrics.prefix_hits == 1   # second shared the pages
    assert eng.prefill_count == 1
    late = _drive(eng, reqs(1))[0]   # shared page still pristine
    np.testing.assert_array_equal(late.tokens, want.tokens)
    assert eng.prefill_count == 1


def test_paged_kv_dtypes_serve_within_tolerance():
    """bf16 and int8 pages: the pool still serves every request to
    completion; on this tiny stack the greedy tokens match the fp32
    run (quantization error far below the logit margins)."""
    dec, embed, proj, D, V = _small_stack(seed=51)
    rs = np.random.RandomState(52)
    base = [_mk_request(rs, D, V) for _ in range(6)]
    specs = [(r.prompt, r.memory, r.max_new_tokens) for r in base]

    def run(kv_dtype):
        eng = ServingEngine(dec, embed, proj, num_slots=3, max_len=32,
                            paged=True, page_size=16, num_pages=24,
                            kv_dtype=kv_dtype)
        return _drive(eng, [Request(p.copy(), m, max_new_tokens=n,
                                    eos_id=1) for p, m, n in specs])

    ref = run(None)
    for dtype in ("bf16", "int8"):
        res = run(dtype)
        assert all(r.ok for r in res)
        same = sum(
            int(len(a.tokens) == len(b.tokens)
                and (np.asarray(a.tokens) == np.asarray(b.tokens)).all())
            for a, b in zip(ref, res))
        assert same >= len(specs) - 1, (dtype, same)


def test_out_of_pages_backpressure_defers_not_fails():
    """Satellite: admission on free-page headroom. Long requests (2
    pages each) against a 4-page pool: at most 2 run concurrently, the
    rest WAIT (page_waits > 0), nobody fails, nobody is OOM-evicted
    (reserve_decode_frac=1 is a no-OOM guarantee)."""
    dec, embed, proj, D, V = _small_stack(seed=61)
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                        paged=True, page_size=16, num_pages=4,
                        prefix_cache=False)
    rs = np.random.RandomState(62)
    reqs = [Request(np.asarray([0, 2 + i, 3], np.int32),
                    rs.randn(4, D).astype("f4"), max_new_tokens=20,
                    eos_id=None) for i in range(6)]
    res = _drive(eng, reqs)
    assert all(r.ok for r in res), [r.finish_reason for r in res]
    snap = eng.metrics.snapshot()
    assert snap["paging"]["page_waits"] >= 1
    assert snap["paging"]["oom_evictions"] == 0
    assert snap["slot_occupancy"]["max"] <= 0.5
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages


def test_oversubscription_oom_evicts_with_partials():
    """reserve_decode_frac < 1 admits more than the pool can hold; when
    pages run dry mid-decode the starved slot is evicted with its
    partial tokens and an OutOfPages cause, and the pool keeps
    serving."""
    dec, embed, proj, D, V = _small_stack(seed=71)
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                        paged=True, page_size=16, num_pages=4,
                        prefix_cache=False, reserve_decode_frac=0.0)
    rs = np.random.RandomState(72)
    reqs = [Request(np.asarray([0, 2 + i], np.int32),
                    rs.randn(4, D).astype("f4"), max_new_tokens=24,
                    eos_id=None) for i in range(4)]
    res = _drive(eng, reqs)
    evicted = [r for r in res if r.finish_reason == "error"]
    done = [r for r in res if r.ok]
    assert evicted and done
    for r in evicted:
        assert isinstance(r.error, OutOfPages)
        assert len(r.tokens) >= 1    # partials delivered
    snap = eng.metrics.snapshot()
    assert snap["paging"]["oom_evictions"] == len(evicted)
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages


def test_paged_admit_check_reports_page_granular_limit():
    dec, embed, proj, D, V = _small_stack(seed=81)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=30,
                        paged=True, page_size=16)
    assert eng.max_len == 32         # rounded up to a page multiple
    rs = np.random.RandomState(82)
    bad = Request(np.zeros(10, np.int32), rs.randn(4, D).astype("f4"),
                  max_new_tokens=30)
    with pytest.raises(ValueError, match=r"max_len 32.*2 pages x 16"):
        eng.admit_check(bad)


def test_paging_metrics_gauges_in_snapshot():
    dec, embed, proj, D, V = _small_stack(seed=91)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        paged=True, page_size=16, num_pages=8)
    rs = np.random.RandomState(92)
    res = _drive(eng, [_mk_request(rs, D, V) for _ in range(3)])
    assert all(r.ok for r in res)
    snap = eng.metrics.snapshot()
    pg = snap["paging"]
    assert pg["pages_in_use"] + pg["pages_free"] == 8
    assert pg["prefix_hits"] + pg["prefix_misses"] == 3
    assert 0.0 <= pg["prefix_hit_rate"] <= 1.0
    assert pg["bytes_per_active_token"]["n"] >= 1
    assert pg["bytes_per_active_token"]["max"] > 0


def test_one_memory_digest_per_request():
    """The memory's scope key is taken once a request, at the admission
    gate's peek, and carried through lookup and insert; two requests
    that bring the SAME memory array still take one digest each (the
    key is per request, never cached by array identity)."""
    dec, embed, proj, D, V = stack = _small_stack(seed=93)
    eng = _paged_radix_engine(stack)
    rs = np.random.RandomState(94)
    r1 = _mk_request(rs, D, V)
    assert eng._admission_gate(r1)
    assert eng._prefix.stats()["digests"] == 1
    assert _drive(eng, [r1])[0].ok
    assert eng._prefix.stats()["digests"] == 1
    r2 = Request(r1.prompt.copy(), r1.memory, max_new_tokens=3, eos_id=1)
    assert _drive(eng, [r2])[0].ok
    st = eng._prefix.stats()
    assert st["digests"] == 2 and eng._prefix.whole_hits == 1
    pf = eng.metrics.snapshot()["prefix"]
    assert (pf["digests"], pf["evictions"], pf["evict_visits"]) == \
        (2, st["evictions"], st["evict_visits"])


def test_weight_update_invalidates_prefix_cache():
    """Prefix-cache entries hold model-derived state (prompt K/V pages,
    cached tok0): rebinding any param's `_data` must flush them, so a
    repeated prompt after a weight update re-prefills and bit-matches
    the UPDATED model instead of replaying stale pages (the
    params-as-arguments contract the compiled programs already obey)."""
    dec, embed, proj, D, V = _small_stack(seed=111)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        paged=True, page_size=16, num_pages=16)
    rs = np.random.RandomState(112)
    r1 = _mk_request(rs, D, V, nmax=8)
    assert _drive(eng, [r1])[0].ok

    def repeat():
        return Request(r1.prompt.copy(), r1.memory,
                       max_new_tokens=r1.max_new_tokens, eos_id=1)

    for p in list(dec.parameters()) + list(embed.parameters()) \
            + list(proj.parameters()):
        p._data = p._data * 0.5
    got = _drive(eng, [repeat()])[0]
    assert eng.prefill_count == 2    # stale entry flushed, re-prefilled
    dense = ServingEngine(dec, embed, proj, num_slots=2, max_len=32)
    want = _drive(dense, [repeat()])[0]
    np.testing.assert_array_equal(got.tokens, want.tokens)
    # unchanged weights: the refreshed entry serves hits again
    assert _drive(eng, [repeat()])[0].ok
    assert eng.prefill_count == 2


# ----------------------------------------------------------------------
# radix partial reuse through the pool (the pattach program family)
# ----------------------------------------------------------------------

def _paged_radix_engine(stack, **kw):
    dec, embed, proj, D, V = stack
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 96)
    return ServingEngine(dec, embed, proj, paged=True, **kw)


def test_partial_prefix_attach_prefills_tail_only_bitmatch():
    """A prompt sharing a page-aligned preamble with a cached one
    joins through `pattach`: ZERO full-prefill work (the
    serving.prefill fault point stays silent, prefill_count frozen)
    and the tokens bit-match a cold engine's."""
    stack = _small_stack(seed=121)
    mem = np.random.RandomState(6).randn(4, stack[3]).astype("f4")
    pre = [0, 3, 7, 11, 2, 9, 4, 13]
    pA = np.asarray(pre + [5, 8], np.int32)           # P0=10
    pB = np.asarray(pre + [6, 10, 12], np.int32)      # shares 2 pages

    def mk(p):
        return Request(p.copy(), mem, max_new_tokens=8, eos_id=1)

    def cold(p):
        e = _paged_radix_engine(stack, prefix_cache=False)
        return _drive(e, [mk(p)])[0]

    eng = _paged_radix_engine(stack)
    a = _drive(eng, [mk(pA)])[0]
    with faults.inject("serving.prefill", on="nth", n=10 ** 9):
        b = _drive(eng, [mk(pB)])[0]
        hits = faults.hit_counts().get("serving.prefill", 0)
    assert a.ok and b.ok
    assert hits == 0 and eng.prefill_count == 1   # tail-only pattach
    m = eng.metrics
    assert m.prefix_partial_hits == 1 and m.prefix_whole_hits == 0
    np.testing.assert_array_equal(a.tokens, cold(pA).tokens)
    np.testing.assert_array_equal(b.tokens, cold(pB).tokens)
    pat = {k: v for k, v in eng.trace_counts.items()
           if k[0] == "pattach"}
    assert len(pat) == 1 and set(pat.values()) == {1}, pat
    eng.flush_prefix_cache()
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages


def test_branching_conversation_soak_partial_reuse_bitmatch():
    """Branching conversations (one 12-token preamble, forks at page
    depths 12 and 16 plus a mid-page fork): every request bit-matches
    the dense oracle; DISTINCT hit lengths that bucket alike share ONE
    compiled pattach program (no retrace across hit lengths — the
    trace counter stays at one compile per bucket pair); the allocator
    is leak-free after a flush."""
    stack = _small_stack(seed=131)
    D = stack[3]
    mem = np.random.RandomState(7).randn(4, D).astype("f4")
    pre = [0, 3, 7, 11, 2, 9, 4, 13, 5, 8, 15, 6]     # 3 full pages
    t1 = [10, 2, 14, 3]                               # pages 12..16
    specs = [
        pre + t1 + [5, 9],        # cold prefill; inserts 4 full pages
        pre + [12, 6, 4],         # fork @12: seed 12 -> pattach (4, 4)
        pre + t1 + [7, 11, 2],    # fork @16: seed 16 -> pattach (4, 4)
        pre + t1 + [5, 9],        # exact repeat: whole hit
        pre[:6] + [8, 14, 2, 5],  # mid-page fork @6: COW + pattach
        pre + [13, 5, 10],        # fork @12 again, other tail
    ]
    specs = [np.asarray(p, np.int32) for p in specs]

    def mk_reqs():
        return [Request(p.copy(), mem, max_new_tokens=6, eos_id=1)
                for p in specs]

    dense = ServingEngine(*stack[:3], num_slots=4, max_len=64)
    want = _drive(dense, mk_reqs())
    # radix_mid_page="cow" pins the sub-page COW path this test
    # exercises (the default rounds mid-page matches down instead)
    eng = _paged_radix_engine(stack, max_len=64, radix_mid_page="cow")
    got = _drive(eng, mk_reqs())
    for w, g in zip(want, got):
        assert w.ok and g.ok
        np.testing.assert_array_equal(g.tokens, w.tokens)
    m = eng.metrics
    assert m.prefix_partial_hits >= 3 and m.prefix_whole_hits >= 1
    assert m.cow_copies >= 1                  # the mid-page fork
    pat = {k: v for k, v in eng.trace_counts.items()
           if k[0] == "pattach"}
    assert pat and set(pat.values()) == {1}, pat
    # strictly more partial joins than compiled pattach programs:
    # different hit lengths reused the same (matched, tail) buckets
    assert m.prefix_partial_hits > len(pat)
    snap = m.snapshot()["prefix"]
    assert snap["hit_token_ratio"] > 0.3
    assert snap["trie_nodes"] >= 1 and snap["trie_pages"] >= 1
    eng.flush_prefix_cache()
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages


def test_round_down_policy_serves_mid_page_fork_without_cow():
    """The DEFAULT mid-page policy: the same branching traffic
    bit-matches the dense oracle with ZERO divergence-point COW
    copies — the mid-page fork's match rounds down to the page
    boundary and the partial page re-prefills with the tail (the
    trie's `rounded_down` counter proves the policy fired)."""
    stack = _small_stack(seed=131)
    D = stack[3]
    mem = np.random.RandomState(7).randn(4, D).astype("f4")
    pre = [0, 3, 7, 11, 2, 9, 4, 13, 5, 8, 15, 6]     # 3 full pages
    specs = [pre + [10, 2, 14, 3, 5, 9],  # cold prefill
             pre[:6] + [8, 14, 2, 5],     # mid-page fork @6 -> rounds
             #                              down to the page-4 boundary
             pre + [12, 6, 4]]            # page-aligned fork @12
    specs = [np.asarray(p, np.int32) for p in specs]

    def mk_reqs():
        return [Request(p.copy(), mem, max_new_tokens=6, eos_id=1)
                for p in specs]

    dense = ServingEngine(*stack[:3], num_slots=4, max_len=64)
    want = _drive(dense, mk_reqs())
    eng = _paged_radix_engine(stack, max_len=64)
    got = _drive(eng, mk_reqs())
    for w, g in zip(want, got):
        assert w.ok and g.ok
        np.testing.assert_array_equal(g.tokens, w.tokens)
    m = eng.metrics
    assert m.prefix_partial_hits == 2          # both forks still hit
    assert m.cow_copies == 0                   # no divergence COW
    assert eng._prefix.stats()["rounded_down"] >= 1
    # no cow program was ever compiled on this traffic
    assert not any(k[0] == "cow" for k in eng.trace_counts)
    eng.flush_prefix_cache()
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages


def test_quantized_pool_keeps_whole_hits_only():
    """int8 pages store LOSSY K/V: a pattach tail would attend to the
    stored seed while a cold prefill attends to full precision, so
    partial reuse is gated off — shared-prefix prompts miss (full
    prefill), exact repeats still whole-hit."""
    stack = _small_stack(seed=141)
    mem = np.random.RandomState(8).randn(4, stack[3]).astype("f4")
    pre = [0, 3, 7, 11, 2, 9, 4, 13]
    pA = np.asarray(pre + [5, 8], np.int32)
    pB = np.asarray(pre + [6, 10], np.int32)
    eng = _paged_radix_engine(stack, kv_dtype="int8")

    def mk(p):
        return Request(p.copy(), mem, max_new_tokens=4, eos_id=1)

    assert all(r.ok for r in _drive(eng, [mk(pA)]))
    assert all(r.ok for r in _drive(eng, [mk(pB)]))   # no partial
    assert all(r.ok for r in _drive(eng, [mk(pA)]))   # whole hit
    m = eng.metrics
    assert m.prefix_partial_hits == 0
    assert m.prefix_whole_hits == 1 and eng.prefill_count == 2
    assert not any(k[0] == "pattach" for k in eng.trace_counts)


def test_adapter_generation_bump_drops_tenant_subtree():
    """Adapter traffic caches under a per-(name, generation) subtree;
    re-registering the adapter bumps the generation and EAGERLY drops
    the stale pages (AdapterPool.on_invalidate), so the next join
    re-prefills against the new weights."""
    from paddle_tpu.serving import AdapterPool

    stack = _small_stack(seed=151)
    dec, embed, proj, D, V = stack
    pool = AdapterPool(dec, capacity=2, rank=4)
    pool.register_random("t1", seed=1)
    eng = _paged_radix_engine(stack, adapters=pool)
    mem = np.random.RandomState(9).randn(4, D).astype("f4")
    p = np.asarray([0, 3, 7, 11, 2, 9], np.int32)

    def mk():
        return Request(p.copy(), mem, max_new_tokens=4, eos_id=1,
                       adapter="t1")

    assert _drive(eng, [mk()])[0].ok
    assert eng._prefix.stats()["pages"] >= 1
    pool.register_random("t1", seed=2)        # generation bump
    assert eng._prefix.stats()["pages"] == 0  # eager drop
    assert _drive(eng, [mk()])[0].ok
    assert eng.prefill_count == 2             # re-prefilled, no stale
    eng._alloc.check()


# ----------------------------------------------------------------------
# chaos: fault injection + leak-freedom
# ----------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_slot_join_faults_leak_free():
    """serving.slot_join / serving.prefill raises under paging: failed
    joins release their pages, survivors bit-match the dense oracle,
    and after the soak + a prefix flush the free list is back to its
    initial state (no page leaks)."""
    stack = _small_stack(seed=101)
    dec, embed, proj, D, V = stack
    rs = np.random.RandomState(102)
    base = [_mk_request(rs, D, V) for _ in range(16)]
    specs = [(r.prompt, r.memory, r.max_new_tokens) for r in base]

    dense = ServingEngine(dec, embed, proj, num_slots=4, max_len=32)
    oracle = {}
    for res, (p, m, n) in zip(
            _drive(dense, [Request(p.copy(), m, max_new_tokens=n,
                                   eos_id=1) for p, m, n in specs]),
            specs):
        key = tuple(p.tolist())
        # repeated prompts differ only in max_new_tokens: greedy is
        # deterministic, so keep the longest stream as the oracle
        if len(res.tokens) > len(oracle.get(key, ())):
            oracle[key] = np.asarray(res.tokens)

    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                        paged=True, page_size=16, num_pages=24,
                        max_attempts=2, backoff_base_s=0.0)
    sched = Scheduler(max_queue=64)
    reqs = [Request(p.copy(), m, max_new_tokens=n, eos_id=1)
            for p, m, n in specs]
    for r in reqs:
        sched.submit(r)
    plans = [("serving.slot_join", dict(on="every", k=7)),
             ("serving.prefill", dict(on="nth", n=5)),
             ("serving.prefill", dict(on="nth", n=6)),  # pair ->
             #                                            one join dies
             ("serving.decode_step", dict(on="nth", n=11)),
             ("serving.decode_step", dict(on="nth", n=12))]  # eviction
    injs = [faults.inject(name, **kw) for name, kw in plans]
    try:
        eng.serve_until_idle(sched, max_iterations=5000)
    finally:
        faults.reset()
    for inj, (name, _) in zip(injs, plans):
        assert inj.fired >= 1, f"{name} never fired"
    n_ok = 0
    for r in reqs:
        assert r.future.done()
        try:
            res = r.result(timeout=0)
        except faults.InjectedFault:
            continue
        want = oracle[tuple(r.prompt.tolist())]
        np.testing.assert_array_equal(res.tokens,
                                      want[:len(res.tokens)])
        n_ok += res.ok
    assert n_ok >= 1
    # leak-freedom: drained pool + flushed prefix cache = all free.
    # (a decode-step eviction resets the pool, which already flushed)
    eng.flush_prefix_cache()
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages
    # the pool still serves, bit-exactly, after the chaos
    fresh = [Request(p.copy(), m, max_new_tokens=n, eos_id=1)
             for p, m, n in specs[:4]]
    res = _drive(eng, fresh)
    for r, res1 in zip(fresh, res):
        assert res1.ok
        want = oracle[tuple(r.prompt.tolist())]
        np.testing.assert_array_equal(res1.tokens,
                                      want[:len(res1.tokens)])


@pytest.mark.chaos
def test_chaos_pattach_fault_retries_and_leak_free():
    """serving.pattach raises mid-join: the failed partial attach
    releases every page it took (matched refs AND fresh/COW allocs),
    the request RETRIES to a clean completion that bit-matches a cold
    engine, and the free list ends pristine."""
    stack = _small_stack(seed=161)
    mem = np.random.RandomState(10).randn(4, stack[3]).astype("f4")
    pre = [0, 3, 7, 11, 2, 9, 4, 13]
    pA = np.asarray(pre + [5, 8], np.int32)
    pB = np.asarray(pre + [6, 10, 12], np.int32)

    def mk(p):
        return Request(p.copy(), mem, max_new_tokens=6, eos_id=1)

    want = _drive(_paged_radix_engine(stack, prefix_cache=False),
                  [mk(pB)])[0]
    eng = _paged_radix_engine(stack, max_attempts=2, backoff_base_s=0.0)
    assert _drive(eng, [mk(pA)])[0].ok
    inj = faults.inject("serving.pattach", on="nth", n=1)
    try:
        got = _drive(eng, [mk(pB)])[0]
    finally:
        faults.reset()
    assert inj.fired == 1                     # the fault really hit
    assert got.ok                             # retried to completion
    np.testing.assert_array_equal(got.tokens, want.tokens)
    assert eng.metrics.prefix_partial_hits == 2   # failed + retried
    eng.flush_prefix_cache()
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages
