"""The grouped-head paged-decode kernel: a decode step's differential
attention (`ops.diff_attention`) over key and value pages read straight
through the page table, with no gathered view.

K and V pages are `[N + 1, psz, Hkv d]` (row N the trash row), a token's
row all key-value heads side by side, which is also `Hkv / 2` wide heads
of `2 d` lanes. The widened queries `[S, groups, G, 2 d]`
(`diff_attention.widen_queries`) go in as ONE block-diagonal matrix a
slot, transposed: `[Hkv d, groups x G]`, column (g, j) holding query j
of wide head g on that head's lanes and zeros elsewhere. So a block of
page rows scores every query head in one product with the rows as they
lie, `[rows, Hkv d] x [Hkv d, groups x G]`, the rows on the M side; the
value product sums the same rows, and each head keeps its own wide
head's lanes at the end: `diff_attention.dense`'s math, a block at a
time, and its output.

`paged_grouped_decode` is the `pallas_call` (`name="paged_grouped_decode"`),
on the chip or under the TPU interpreter; `kernel_fits` says which pools it
takes; `decode_block_pages` is what the serving engine reports as
`paging.pages_per_block` for a stack with grouped-head pages. Only
`diff_attention.paged_reader` on the chip and that report import this
module, and nothing here builds an array, traces or compiles before it is
called.
"""
from __future__ import annotations

import functools

#: VMEM bytes of the four buffers of pages a grid step holds (K and V of
#: the block it computes on, and of the one whose copies run under it): at
#: the Phi-4-mini-flash pool's pages (16 rows of 1,280 bfloat16, 40 KiB)
#: 32 pages a block, 512 rows. Alone on one v5e at that pool (64 slots of
#: 256 pages, 1,616 rows written a slot on average) a call took 1.06 /
#: 0.90 / 0.82 / 0.93 ms at 8 / 16 / 32 / 64 pages a block
_GROUPED_BUFFER_BYTES = 2**23


def kernel_fits(psz, width, wide_head, dtype):
    """Pools the kernel takes, from the shapes alone: page rows that tile
    (`psz` a sublane multiple, the row `width` a lane multiple), wide
    heads of 128 lanes, and a page whose four buffers (K and V, two each)
    fit `_GROUPED_BUFFER_BYTES`. Every other pool takes the gather and
    `dense`."""
    import numpy as np

    page = psz * width * np.dtype(dtype).itemsize
    return (psz % 8 == 0 and width % 128 == 0 and wide_head == 128
            and 4 * page <= _GROUPED_BUFFER_BYTES)


def block_pages(psz, width, mp, dtype):
    """`P`, the consecutive logical pages of a slot one grid step takes:
    the largest power of two no more than the slot's `mp` pages whose
    four buffers ([2, P, psz, width] as stored, K and V) fit
    `_GROUPED_BUFFER_BYTES`."""
    import numpy as np

    page = psz * width * np.dtype(dtype).itemsize
    p = 1
    while 2 * p <= mp and 4 * (2 * p) * page <= _GROUPED_BUFFER_BYTES:
        p *= 2
    return p


def decode_block_pages(psz, width, wide_head, mp, dtype):
    """Pages a grid step of the decode call takes over a pool of these
    shapes; 1 for a pool the kernel does not take (the gather)."""
    return (block_pages(psz, width, mp, dtype)
            if kernel_fits(psz, width, wide_head, dtype) else 1)


def _grouped_decode_call(S, W, M, G, wide, mp, psz, P, scale, interpret,
                         dtype, out_dtype):
    """The kernel on a grid of (slot, block of `P` consecutive logical
    pages). The page table and the written lengths ride scalar prefetch;
    both pools stay in HBM (`pl.ANY`) and a step copies its block's
    WRITTEN pages itself, a K page and a V page a page of the table, into
    one of two VMEM buffers each. Every step that works starts the copies
    of the next written block (the slot's next, else block 0 of the next
    slot that holds anything) before it waits for its own, so a fetch
    runs under the step before it, across slots too; only the call's
    first written block starts its own. A block wholly past the written
    pages fetches nothing and computes nothing. The buffers start as
    zeros; in a slot's last written block the rows past its length (the
    last page's unwritten rows, the pages not fetched) are masked in the
    scores and zeroed in the values, so whatever they hold adds exact
    zeros.

    Scores and weights are float32, as `dense`'s; the weights go into the
    value product in the rows' dtype, and the sum divides the product at
    the last block (`nn.layer.mla._exp_and_sum` says why a composition
    must). The running max and sum ([1, M], M = groups x G query heads)
    and the accumulator, `[W, M]` (the value rows transposed, the heads
    on the lanes), live in VMEM scratch. At the last block every head keeps its own wide head's
    `wide` lanes; the output is `[wide, M]` a slot. Both grid axes are
    "arbitrary": the buffers' turn and the copies in flight pass from
    slot to slot (SMEM)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .attention import _z

    ksem, vsem = _z(), np.int32(1)      # the K and the V copies'
    rows = P * psz
    nblk = -(-mp // P)
    groups = W // wide
    i32, f32 = jnp.int32, jnp.float32

    def kernel(tbl_ref, len_ref, q_ref, kpool, vpool, o_ref, m_sc, l_sc,
               acc_sc, kbuf, vbuf, sem, state):
        si = pl.program_id(0)
        bi = pl.program_id(1)
        start = bi * i32(rows)
        n_valid = len_ref[si]

        def written_pages(slot):    # lengths are >= 0: one `div`
            return jax.lax.div(len_ref[slot] + i32(psz - 1), i32(psz))

        def next_written_slot(slot):            # S where none is left
            return jax.lax.while_loop(
                lambda t: (t < i32(S)) & (len_ref[jnp.minimum(
                    t, i32(S - 1))] <= 0),
                lambda t: t + i32(1), slot)

        def copies(slot, blk, b, go):
            # the block's written pages, a K and a V page a table entry:
            # started where `go` (unrolled, one guarded pair a page),
            # else waited for; a wait reads the copy's size alone, so a
            # full block is waited for once a pool
            first = blk * i32(P)
            n = jnp.minimum(written_pages(slot) - first, i32(P))
            if go:
                for j in range(P):
                    @pl.when(i32(j) < n)
                    def _():
                        pid = tbl_ref[slot, first + i32(j)]
                        pltpu.make_async_copy(
                            kpool.at[pid], kbuf.at[b, np.int32(j)],
                            sem.at[ksem, b]).start()
                        pltpu.make_async_copy(
                            vpool.at[pid], vbuf.at[b, np.int32(j)],
                            sem.at[vsem, b]).start()
                return

            @pl.when(n == i32(P))
            def _():
                pltpu.make_async_copy(kpool.at[pl.ds(_z(), P)], kbuf.at[b],
                                      sem.at[ksem, b]).wait()
                pltpu.make_async_copy(vpool.at[pl.ds(_z(), P)], vbuf.at[b],
                                      sem.at[vsem, b]).wait()

            @pl.when(n < i32(P))
            def _():
                def page(j, carry):
                    pltpu.make_async_copy(kpool.at[_z()], kbuf.at[b, _z()],
                                          sem.at[ksem, b]).wait()
                    pltpu.make_async_copy(vpool.at[_z()], vbuf.at[b, _z()],
                                          sem.at[vsem, b]).wait()
                    return carry

                jax.lax.fori_loop(i32(0), n, page, i32(0))

        @pl.when((si == 0) & (bi == 0))
        def _first():
            kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
            vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
            state[0] = i32(0)       # the buffer whose turn it is
            state[1] = i32(0)       # are this block's copies in flight?

        @pl.when(bi == 0)
        def _init():
            m_sc[...] = jnp.full(m_sc.shape, -1e30, f32)
            l_sc[...] = jnp.zeros(l_sc.shape, f32)
            acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

        def attend(b, last):
            k = kbuf[b].reshape(rows, W)
            v = vbuf[b].reshape(rows, W)
            st = jnp.dot(k, q_ref[...],
                         preferred_element_type=f32) * f32(scale)
            if last:
                kpos = start + jax.lax.broadcasted_iota(i32, (rows, M), 0)
                st = jnp.where(kpos < n_valid, st, f32(-1e30))
                vpos = start + jax.lax.broadcasted_iota(i32, (rows, W), 0)
                v = jnp.where(vpos < n_valid, v, jnp.zeros_like(v))
            m_prev = m_sc[...]                                # (1, M)
            m_new = jnp.maximum(m_prev, st.max(axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pt = jnp.exp(st - m_new)                          # (rows, M)
            l_sc[...] = alpha * l_sc[...] + pt.sum(axis=0, keepdims=True)
            m_sc[...] = m_new
            acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
                v, pt.astype(v.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=f32)                   # (W, M)

        @pl.when(start < n_valid)
        def _compute():
            b, ahead = state[0], state[1]
            state[0] = i32(1) - b
            state[1] = i32(1)

            def fetch(i, at):
                slot, blk = at
                more = (blk + i32(1)) * i32(P) < written_pages(slot)
                slot = jax.lax.select(more, slot,
                                      next_written_slot(slot + i32(1)))
                blk = jax.lax.select(more, blk + i32(1), i32(0))

                @pl.when(slot < i32(S))
                def _():
                    copies(slot, blk, (b + i + ahead) & i32(1), True)

                return jnp.minimum(slot, i32(S - 1)), blk

            # the block after this one; before it this one itself, where
            # no step started it (the call's first written block)
            jax.lax.fori_loop(i32(0), i32(2) - ahead, fetch,
                              (si, bi - i32(1) + ahead))
            copies(si, bi, b, False)
            tail = start + i32(rows) > n_valid

            @pl.when(tail)
            def _():
                attend(b, True)

            @pl.when(jnp.logical_not(tail))
            def _():
                attend(b, False)

        @pl.when(bi == nblk - 1)
        def _finish():
            # a slot of length 0 never computes: l = 0, and the floor
            # keeps its output finite
            res = acc_sc[...] / jnp.maximum(l_sc[...], f32(1e-30))
            col = jax.lax.broadcasted_iota(i32, (wide, M), 1)
            out = jnp.zeros((wide, M), f32)
            for g in range(groups):      # head (g, j) is column g G + j
                out = jnp.where((col >= g * G) & (col < (g + 1) * G),
                                res[g * wide:(g + 1) * wide], out)
            o_ref[...] = out.astype(o_ref.dtype)

    def slot_ix(si, bi, *_):
        return (si, _z(), _z())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(S, nblk),
        in_specs=[pl.BlockSpec((None, W, M), slot_ix),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, wide, M), slot_ix),
        scratch_shapes=[pltpu.VMEM((1, M), jnp.float32),
                        pltpu.VMEM((1, M), jnp.float32),
                        pltpu.VMEM((W, M), jnp.float32),
                        pltpu.VMEM((2, P, psz, W), np.dtype(dtype)),
                        pltpu.VMEM((2, P, psz, W), np.dtype(dtype)),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, wide, M), np.dtype(out_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_grouped_decode")


@functools.lru_cache(maxsize=None)
def _grouped_decode_jit(P, scale, interpret):
    """The call as ONE jitted function a (block, scale, interpret): a
    stack's reading layers call it with the same shapes, so the second on
    reads the first's trace instead of tracing the body again. XLA
    inlines it."""
    import jax
    import jax.numpy as jnp

    def grouped_decode(qw, k_pages, v_pages, table, n_keys):
        S, g, G, wide = qw.shape
        _, psz, W = k_pages.shape
        M = g * G
        call = _grouped_decode_call(S, W, M, G, wide, table.shape[1], psz,
                                    P, scale, interpret, k_pages.dtype,
                                    qw.dtype)
        # [S, W, M]: query (g, j) on wide head g's lanes of a full row
        own = jnp.eye(g, dtype=k_pages.dtype)[None, :, None, :, None]
        rows = (qw.astype(k_pages.dtype)[:, :, :, None, :] * own)
        qt = jnp.swapaxes(rows.reshape(S, M, W), 1, 2)
        o = call(jnp.asarray(table, jnp.int32),
                 jnp.asarray(n_keys, jnp.int32), qt, k_pages, v_pages)
        return jnp.swapaxes(o, 1, 2).reshape(S, g, G, wide)

    return jax.jit(grouped_decode)


def paged_grouped_decode(qw, k_pages, v_pages, table, n_keys, scale,
                         interpret=False):
    """Widened queries qw [S, groups, G, 2 d] against each slot's first
    `n_keys` [S] rows read through `table` [S, max_pages] from `k_pages`
    and `v_pages` [N + 1, psz, Hkv d] (trash-clipped: an inactive slot
    reads the trash row) -> [S, groups, G, 2 d] in qw's dtype:
    `diff_attention.dense` over the gathered rows."""
    _, psz, W = k_pages.shape
    P = block_pages(psz, W, table.shape[1], k_pages.dtype)
    return _grouped_decode_jit(P, float(scale), bool(interpret))(
        qw, k_pages, v_pages, table, n_keys)
