"""Speculative decoding on the PAGED serving pool + the composable
pool layers.

Covers: the paged verify kernel's interpret-mode parity (fp32 + int8
pages) against gather + the dense verify reference; `write_tokens`'s
k-wide page writes (boundary crossing, grow-only int8 rescale)
matching k sequential `write_token`s exactly; the
`PagedServingEngine(spec_k=)` ragged soak BIT-matching solo
`generate_eager` with the retrace sentinel armed and the allocator
leak-free at drain; the prefix-attach path carrying the speculation
history row; the adaptive effective-k controller (hysteresis
transitions, snapshot gauges, never-retraces under adaptation); the
sharded paged spec cell; the batched pending-splice dispatch; and the
full (dense|paged) x (single|sharded) x (spec on|off) grid proof
(slow-marked; the per-cell tests above are its tier-1 core).
"""
import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401  (jax config side effects)
from paddle_tpu import nn
from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                             TransformerDecoderLayer)
from paddle_tpu.serving import (Request, Scheduler, ServingEngine,
                                retrace_sentinel)
from paddle_tpu.text.generation import bucket_size, generate_eager


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


def _eager_reference(stack, r, max_new):
    import jax.numpy as jnp

    dec, embed, proj, D, V = stack
    toks, lens = generate_eager(
        dec, embed, proj, jnp.asarray(r.memory[None]),
        jnp.asarray(r.prompt[None]),
        jnp.asarray([r.prompt.shape[0]], jnp.int32), bos_id=0,
        eos_id=1, max_new_tokens=max_new,
        pad_prompt_to=bucket_size(r.prompt.shape[0]))
    return np.asarray(toks)[0]


def _drive(eng, sched, max_iterations=3000):
    it = 0
    while sched.depth() > 0 or eng.occupancy() > 0:
        eng.run_iteration(sched)
        it += 1
        assert it < max_iterations
    return it


def _assert_bitmatch(stack, reqs, max_new=10):
    for r in reqs:
        res = r.result(timeout=5)
        assert res.ok, (res.finish_reason, res.error)
        ref = _eager_reference(stack, r, max_new)
        np.testing.assert_array_equal(res.tokens,
                                      ref[:len(res.tokens)])


def _assert_leak_free(eng):
    eng.flush_prefix_cache()
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages


# ----------------------------------------------------------------------
# kernel layer: paged verify parity + k-wide page writes
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype,T,with_bias,mp", [
    ("f32", 4, True, 4), ("f32", 2, False, 4), ("int8", 4, True, 4),
    ("bf16", 4, True, 4), ("bf16", 2, False, 4), ("int8", 2, False, 4),
    ("f32", 16, True, 4),       # a tail prefill's block: over two pages
    # a table of 11 pages in grid steps of 8 (which do not divide it),
    # the written lengths at the block's edges
    ("f32", 4, True, 11), ("f32", 4, False, 11), ("int8", 4, True, 11),
    ("int8", 4, False, 11), ("bf16", 4, True, 11), ("f32", 16, True, 11),
])
def test_paged_flash_verify_interpret_parity(kv_dtype, T, with_bias, mp):
    """The page-table verify kernel (interpret mode on CPU) must
    reproduce gather + the dense verify reference — fp32 exactly to
    float tolerance, bf16 and int8 pages through the same widening and
    per-(page, head) dequant. The written lengths (after the T-token
    write) cover the block alone, a page boundary, one past it, and the
    whole table; over the long table, where a grid step takes a block
    of P = 8 pages: one short of the block, the block, one past it."""
    import jax.numpy as jnp

    from paddle_tpu.ops import attention as A
    from paddle_tpu.serving.paging import quantize_chunks, resolve_kv_dtype

    rs = np.random.RandomState(0)
    h, d, psz = 2, 8, 8
    L = mp * psz
    lens = ([T, 2 * psz, 2 * psz + 1, L] if mp == 4 else
            [T, max(T, psz), 8 * psz - 1, 8 * psz, 8 * psz + 1, L])
    assert A._paged_block_pages(psz, h * d, T, mp, "float32") == min(mp, 8)
    S = len(lens)
    n_pages = S * mp
    storage, quantized = resolve_kv_dtype(
        None if kv_dtype == "f32" else kv_dtype, jnp.float32)
    kp, ks = quantize_chunks(
        jnp.asarray(rs.randn(n_pages + 1, psz, h * d), jnp.float32),
        storage, quantized, h)
    vp, vs = quantize_chunks(
        jnp.asarray(rs.randn(n_pages + 1, psz, h * d), jnp.float32),
        storage, quantized, h)
    table = jnp.asarray(
        rs.permutation(n_pages).reshape(S, mp), jnp.int32)
    length = jnp.asarray(lens, jnp.int32)
    q = jnp.asarray(rs.randn(S, h, T, d), jnp.float32)
    bias = (jnp.asarray(rs.randn(S, L), jnp.float32) * 0.1
            if with_bias else None)
    out_k = A.paged_flash_verify(q, kp, vp, ks, vs, table, length,
                                 bias=bias, interpret=True)
    kd = A.paged_gather_kv(kp, ks, table, h, q.dtype)
    vd = A.paged_gather_kv(vp, vs, table, h, q.dtype)
    out_r = A.verify_attention_reference(q, kd, vd, length, bias=bias)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


def test_paged_verify_attention_cpu_fallback_is_reference():
    """Off-TPU the dispatcher must be the gather + reference
    composition BIT-exactly (the paged spec pool's bit-match
    contract rides on it)."""
    import jax.numpy as jnp

    from paddle_tpu.ops import attention as A

    rs = np.random.RandomState(1)
    S, h, d, psz, mp, T = 2, 2, 8, 8, 2, 3
    n_pages = S * mp
    kp = jnp.asarray(rs.randn(n_pages + 1, psz, h * d), jnp.float32)
    vp = jnp.asarray(rs.randn(n_pages + 1, psz, h * d), jnp.float32)
    table = jnp.asarray(
        rs.permutation(n_pages).reshape(S, mp), jnp.int32)
    length = jnp.asarray([7, 12], jnp.int32)
    q = jnp.asarray(rs.randn(S, h, T, d), jnp.float32)
    out = A.paged_verify_attention(q, kp, vp, None, None, table,
                                   length)
    kd = A.paged_gather_kv(kp, None, table, h, q.dtype)
    vd = A.paged_gather_kv(vp, None, table, h, q.dtype)
    ref = A.verify_attention_reference(q, kd, vd, length)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_write_tokens_page_crossing_and_int8_rescale():
    """The k-wide write must equal k sequential single-token writes
    exactly — page-boundary crossing included — and int8 pages must
    inherit the grow-only rescale (a big later token re-rescales the
    block's earlier tokens)."""
    import jax.numpy as jnp

    from paddle_tpu.serving import paging as PG

    rs = np.random.RandomState(2)
    S, h, d, psz, mp, T = 3, 2, 4, 8, 4, 5
    n_pages = S * mp
    pages = jnp.asarray(rs.randn(n_pages + 1, psz, h * d), jnp.float32)
    table = jnp.asarray(
        rs.permutation(n_pages).reshape(S, mp), jnp.int32)
    toks = jnp.asarray(rs.randn(S, h, T, d), jnp.float32)
    # crosses a psz=8 boundary on every row (offsets 5..9 etc.)
    idx = jnp.asarray([5, 14, 27], jnp.int32)
    got, _ = PG.write_tokens(pages, None, table, idx, toks)
    want = pages
    for j in range(T):
        want, _ = PG.write_token(want, None, table, idx + j,
                                 toks[:, :, j, :])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the tokens sit where the logical view reads them: positions
    # idx .. idx + T - 1 of each slot, everything else untouched
    dense = np.asarray(PG.gather_pages(got, None, table, h, jnp.float32))
    before = np.array(PG.gather_pages(pages, None, table, h,
                                      jnp.float32))
    for s_, i0 in enumerate(np.asarray(idx)):
        np.testing.assert_array_equal(dense[s_, :, i0:i0 + T],
                                      np.asarray(toks)[s_])
        before[s_, :, i0:i0 + T] = np.asarray(toks)[s_]
    np.testing.assert_array_equal(dense, before)
    # int8: identical to the sequential composition, and the scale
    # GROWS when a later token outranges the page
    qp = jnp.zeros((n_pages + 1, psz, h * d), jnp.int8)
    sc = jnp.full((n_pages + 1, 1, h), 0.01, jnp.float32)
    big = toks.at[:, :, T - 1, :].mul(100.0)
    got_q, got_s = PG.write_tokens(qp, sc, table, idx, big)
    want_q, want_s = qp, sc
    for j in range(T):
        want_q, want_s = PG.write_token(want_q, want_s, table, idx + j,
                                        big[:, :, j, :])
    np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want_q))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    assert float(jnp.max(got_s)) > 0.01   # grow-only rescale engaged


# ----------------------------------------------------------------------
# the paged speculative pool
# ----------------------------------------------------------------------

def test_paged_spec_soak_bitmatch_sentinel_leakfree():
    """Ragged requests (spec opt-out mixed in) through a speculative
    PAGED pool: every request bit-matches its solo eager run, draft +
    pverify compiled once each (retrace sentinel armed, adaptive k
    enabled), acceptance counters consistent, allocator leak-free at
    drain."""
    stack = _small_stack(seed=31)
    dec, embed, proj, D, V = stack
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                        paged=True, page_size=8, spec_k=4)
    retrace_sentinel(eng).__enter__()   # disarmed by conftest teardown
    sched = Scheduler(max_queue=64)
    rs = np.random.RandomState(32)
    reqs = [_mk_request(rs, D, V, spec=(i % 4 != 0)) for i in range(14)]
    for r in reqs[:6]:
        sched.submit(r)
    it, submitted = 0, 6
    while submitted < len(reqs) or sched.depth() > 0 or \
            eng.occupancy() > 0:
        eng.run_iteration(sched)
        it += 1
        if submitted < len(reqs) and it % 2 == 0:
            sched.submit(reqs[submitted])
            submitted += 1
        assert it < 1000
    _assert_bitmatch(stack, reqs)
    snap = eng.metrics.snapshot()
    spec = snap["speculation"]
    assert spec["rounds"] >= 1
    assert 0 <= spec["drafts_accepted"] <= spec["drafts_proposed"]
    assert spec["effective_k"] in range(2, 5)
    assert "paged" in spec["step_ms_by_variant"]
    # compile-count contract: ONE draft + ONE pverify program
    assert len([k for k in eng.trace_counts if k[0] == "draft"]) == 1
    assert len([k for k in eng.trace_counts if k[0] == "pverify"]) == 1
    assert not any(k[0] == "pstep" for k in eng.trace_counts)
    _assert_leak_free(eng)


def test_paged_spec_prefix_attach_carries_history():
    """Prefix-cache hits on the spec pool: the zero-prefill attach
    path must land the speculation history row too, so a slot joined
    via attach proposes drafts from its real prompt — and still
    bit-matches eager."""
    stack = _small_stack(seed=41)
    dec, embed, proj, D, V = stack
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                        paged=True, page_size=8, spec_k=4)
    rs = np.random.RandomState(42)
    prompt = rs.randint(2, V, (5,)).astype(np.int32)
    prompt[0] = 0
    mem = rs.randn(4, D).astype("f4")
    reqs = [Request(prompt.copy(), mem, max_new_tokens=8, eos_id=1)
            for _ in range(4)]
    sched = Scheduler(max_queue=16)
    for r in reqs:
        sched.submit(r)
    _drive(eng, sched)
    assert eng.metrics.prefix_hits >= 1      # the attach path ran
    assert ("attach",) in eng.trace_counts
    _assert_bitmatch(stack, reqs)
    _assert_leak_free(eng)


def test_paged_spec_oversubscribed_oom_evicts_and_pool_survives():
    """Under oversubscription the spec pool's k-wide write maps pages
    ahead; a dry pool evicts the starved slot with partials and the
    pool keeps serving — and the drain stays leak-free."""
    stack = _small_stack(seed=51)
    dec, embed, proj, D, V = stack
    eng = ServingEngine(dec, embed, proj, num_slots=3, max_len=32,
                        paged=True, page_size=8, num_pages=8,
                        spec_k=4, reserve_decode_frac=0.0,
                        prefix_cache=False)
    sched = Scheduler(max_queue=16)
    rs = np.random.RandomState(52)
    reqs = [_mk_request(rs, D, V, pmax=4, nmax=14) for _ in range(6)]
    for r in reqs:
        sched.submit(r)
    _drive(eng, sched, max_iterations=4000)
    done = [r.result(timeout=5) for r in reqs]
    assert all(res.finish_reason is not None for res in done)
    ok = [res for res in done if res.ok]
    assert ok, "pool served nothing"
    _assert_bitmatch(stack, [r for r, res in zip(reqs, done)
                             if res.ok], max_new=14)
    eng._alloc.check()
    assert eng._alloc.pages_free == eng.num_pages


# ----------------------------------------------------------------------
# adaptive effective k
# ----------------------------------------------------------------------

def test_adaptive_k_hysteresis_transitions():
    """The controller's unit contract: sustained low acceptance
    shrinks k one step per patience window, sustained high acceptance
    regrows it, in-band rounds reset both counters (no thrash)."""
    dec, embed, proj, D, V = _small_stack(seed=61)
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        spec_k=4, spec_adapt_patience=2,
                        spec_adapt_low=0.2, spec_adapt_high=0.6,
                        spec_adapt_alpha=1.0)
    st = eng.stepper
    assert st.k_eff == 4
    for _ in range(2):                 # 0 acceptance, patience 2
        st._adapt(on_count=2, accepted=0)
    assert st.k_eff == 3 and st.k_shrink_events == 1
    for _ in range(4):
        st._adapt(on_count=2, accepted=0)
    assert st.k_eff == 2 and st.k_shrink_events == 2
    for _ in range(10):                # floor: never below 2
        st._adapt(on_count=2, accepted=0)
    assert st.k_eff == 2
    for _ in range(2):                 # full acceptance -> regrow
        st._adapt(on_count=2, accepted=2 * (st.k_eff - 1))
    assert st.k_eff == 3 and st.k_grow_events == 1
    # in-band rounds reset the windows: no transition
    k0 = st.k_eff
    for _ in range(8):
        st._adapt(on_count=2, accepted=int(0.4 * 2 * (k0 - 1)))
    assert st.k_eff == k0
    # disabled controller never moves
    eng2 = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                         spec_k=4, spec_adapt=False)
    for _ in range(10):
        eng2.stepper._adapt(on_count=2, accepted=0)
    assert eng2.stepper.k_eff == 4


def test_adaptive_k_shrinks_end_to_end_never_retraces():
    """Forced-always-low thresholds shrink k to the floor mid-serve:
    the shrink rides the SAME compiled pverify/sstep program (sentinel
    armed), output stays bit-exact, and the snapshot reports the
    transitions."""
    stack = _small_stack(seed=71)
    dec, embed, proj, D, V = stack
    eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=32,
                        paged=True, page_size=8, spec_k=4,
                        spec_adapt_low=1.1, spec_adapt_high=2.0,
                        spec_adapt_patience=1)
    retrace_sentinel(eng).__enter__()   # disarmed by conftest teardown
    sched = Scheduler(max_queue=32)
    rs = np.random.RandomState(72)
    reqs = [_mk_request(rs, D, V, nmax=12) for _ in range(8)]
    for r in reqs:
        sched.submit(r)
    _drive(eng, sched)
    _assert_bitmatch(stack, reqs, max_new=12)
    st = eng.stepper
    assert st.k_eff == 2 and st.k_shrink_events == 2
    spec = eng.metrics.snapshot()["speculation"]
    assert spec["effective_k"] == 2
    assert spec["k_shrink_events"] == 2
    assert spec["k_grow_events"] == 0
    assert len([k for k in eng.trace_counts
                if k[0] == "pverify"]) == 1
    _assert_leak_free(eng)


@pytest.mark.parametrize("k", [2, 8])
def test_paged_spec_k_range_bitmatch(k):
    """The spec_k ladder ends: k=2 (one draft) and k=8 (the widest
    shipped depth) both serve the paged pool bit-identical to eager
    with leak-free drains."""
    stack = _small_stack(seed=91 + k)
    dec, embed, proj, D, V = stack
    eng = ServingEngine(dec, embed, proj, num_slots=2, max_len=32,
                        paged=True, page_size=8, spec_k=k)
    sched = Scheduler(max_queue=8)
    rs = np.random.RandomState(92 + k)
    reqs = [_mk_request(rs, D, V, pmax=4, nmax=8) for _ in range(4)]
    for r in reqs:
        sched.submit(r)
    _drive(eng, sched)
    _assert_bitmatch(stack, reqs, max_new=8)
    _assert_leak_free(eng)


# ----------------------------------------------------------------------
# the full 8-cell grid proof (slow; per-cell tier-1 tests above +
# tests/test_serving*.py cover every cell individually)
# ----------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("sharded", [False, True])
@pytest.mark.parametrize("spec", [False, True])
def test_full_grid_bitmatch_and_leakfree(paged, sharded, spec):
    """(dense|paged) x (single|sharded) x (spec on|off): every cell
    serves the same ragged workload BIT-identical to generate_eager,
    with the retrace sentinel armed and (paged) the allocator
    leak-free at drain — speculation/paging/sharding are orthogonal
    layers over one slot-pool substrate."""
    import jax

    if sharded and len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    stack = _small_stack(seed=81)
    dec, embed, proj, D, V = stack
    kw = dict(num_slots=2, max_len=32)
    if paged:
        kw.update(paged=True, page_size=8)
    if spec:
        kw.update(spec_k=4)
    if sharded:
        from paddle_tpu.parallel import init_mesh
        from paddle_tpu.serving import ShardedServingEngine

        eng = ShardedServingEngine(dec, embed, proj,
                                   mesh=init_mesh(dp=2, fsdp=2, tp=2),
                                   **kw)
    else:
        eng = ServingEngine(dec, embed, proj, **kw)
    retrace_sentinel(eng).__enter__()   # disarmed by conftest teardown
    sched = Scheduler(max_queue=16)
    rs = np.random.RandomState(82)
    reqs = [_mk_request(rs, D, V, pmax=4, nmax=6) for _ in range(5)]
    for r in reqs:
        sched.submit(r)
    _drive(eng, sched)
    _assert_bitmatch(stack, reqs, max_new=6)
    if paged:
        _assert_leak_free(eng)
