"""The latent paged-decode kernel: multi-head latent attention's absorbed
decode step (DeepSeek-V2 / V3, arXiv:2405.04434) read straight through the
page table.

A latent page array is `[N + 1, psz, W]` (row N the trash row), a token's
row `[c_kv (rank) | rotated k_pe | zeros]` with no head axis. The absorbed
query `qa` [S, H, rank + rot] = `[q_nope . W_uk | q_pe]` scores every
written row of its slot, and the softmax weights sum `c_kv` of the SAME
rows: one read of a row serves both products, and the H query heads are
the M side of both (an MXU product where K/V pages, with a head axis, give
a vector one). `W_uk`, `W_uv` and the output projection stay with the
layer (`nn.LatentAttention.absorbed_paged`).

`paged_latent_decode` is the `pallas_call` (`name="paged_latent_decode"`),
on the chip or under the TPU interpreter; `kernel_opens` says whether a
trace takes it; `decode_block_pages` is what the serving engine reports as
`paging.pages_per_block` for a latent stack. Only a layer that holds
latent pages imports this module, and nothing here builds an array,
traces or compiles before it is called.
"""
from __future__ import annotations

import functools

#: VMEM bytes of the two buffers of pages a grid step holds (the block it
#: computes on and the one whose copies run under it): at the DeepSeek-V3
#: pool's pages (16 rows of 640 bfloat16, 20 KiB) 32 pages a block, 512
#: rows. Alone on one v5e at that pool (64 slots of 384 pages, 3.0-5.0k
#: rows written a slot) this kernel takes 0.84 ms a call against 2.30 for
#: the gather and two products it replaces; an earlier form of it took
#: 1.60 / 1.39 / 0.87 / 1.01 ms at 8 / 16 / 32 / 64 pages a block
_LATENT_BUFFER_BYTES = 2**21


def kernel_fits(psz, width, rank, heads, dtype):
    """Pools the kernel takes, from the shapes alone: page rows that tile
    (`psz` a sublane multiple, the row `width` and `rank` lane multiples,
    the heads whole lanes: they sit on the lanes of the scores) and a page
    whose two buffers fit `_LATENT_BUFFER_BYTES`. Every other pool takes
    the gather and `absorbed`."""
    import numpy as np

    page = psz * width * np.dtype(dtype).itemsize
    return (psz % 8 == 0 and width % 128 == 0 and rank % 128 == 0
            and rank <= width and heads % 128 == 0
            and 2 * page <= _LATENT_BUFFER_BYTES)


def block_pages(psz, width, mp, dtype):
    """`P`, the consecutive logical pages of a slot one grid step takes:
    the largest power of two no more than the slot's `mp` pages whose two
    buffers ([2, P, psz, width] as stored) fit `_LATENT_BUFFER_BYTES`."""
    import numpy as np

    page = psz * width * np.dtype(dtype).itemsize
    p = 1
    while 2 * p <= mp and 2 * (2 * p) * page <= _LATENT_BUFFER_BYTES:
        p *= 2
    return p


def decode_block_pages(psz, width, rank, heads, mp, dtype):
    """Pages a grid step of the decode call takes over a latent pool of
    these shapes; 1 for a pool the kernel does not take (the gather)."""
    return (block_pages(psz, width, mp, dtype)
            if kernel_fits(psz, width, rank, heads, dtype) else 1)


def kernel_opens(pages, rank, heads):
    """Whether this trace takes the kernel over `pages`: on the chip, a
    pool it fits, and a trace in which the attention dispatchers may take
    their kernels (`ops.attention._flash_usable`: not under
    `PT_FLASH_ATTENTION=0`, not in a program the partitioner splits)."""
    from . import attention as A

    _, psz, width = pages.shape
    return (A._on_tpu() and kernel_fits(psz, width, rank, heads,
                                        pages.dtype)
            and A._flash_usable())


def _latent_decode_call(S, H, W, rank, mp, psz, P, scale, interpret,
                        dtype):
    """The kernel on a grid of (slot, block of `P` consecutive logical
    pages). The page table and the written lengths ride scalar prefetch;
    the pool stays in HBM (`pl.ANY`) and a step copies its block's
    WRITTEN pages itself, a page a DMA through table[slot, page], into one
    of two VMEM buffers. Every step that works starts the copies of the
    next written block (the slot's next, else block 0 of the next slot
    that holds anything) before it waits for its own, so a fetch runs
    under the step before it, across slots too; only the call's first
    written block starts its own. A block wholly past the written pages
    fetches nothing and computes nothing. In the last written block the
    pages past the last written one are not fetched: they keep what the
    buffer held (zeros at first, then older pages: finite), their rows are
    masked, and they add exact zeros.

    The query comes transposed, [W, H] a slot (closed with zeros to the
    row's width), so that a block's scores are [rows, H]: keys on the
    sublanes, the heads on the lanes, and the softmax's reductions over
    keys are element-wise across vregs. Scores and weights in float32, as
    the composition's; the weights go into the value product in the rows'
    dtype, and the sum divides the product at the last block
    (`nn.layer.mla._exp_and_sum` says why a composition must). The
    running max and sum ([1, H]) and the transposed accumulator ([rank,
    H]) live in VMEM scratch; the output is [rank, H] a slot in the rows'
    dtype. Both grid axes are "arbitrary": the buffers' turn and the
    copies in flight pass from slot to slot (SMEM)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .attention import _z

    rows = P * psz
    nblk = -(-mp // P)
    i32, f32 = jnp.int32, jnp.float32

    def kernel(tbl_ref, len_ref, q_ref, pool, o_ref, m_sc, l_sc, acc_sc,
               buf, sem, state):
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
            # the block's written pages, a page a copy: started where
            # `go`, else waited for. The starts are unrolled, one guarded
            # start a page (started in a loop, the call took 1.21 ms
            # against 0.84 on a v5e); a wait reads the copy's size alone,
            # so a full block is waited for once
            first = blk * i32(P)
            n = jnp.minimum(written_pages(slot) - first, i32(P))
            if go:
                for j in range(P):
                    @pl.when(i32(j) < n)
                    def _():
                        pltpu.make_async_copy(
                            pool.at[tbl_ref[slot, first + i32(j)]],
                            buf.at[b, np.int32(j)], sem.at[b]).start()
                return

            @pl.when(n == i32(P))
            def _():
                pltpu.make_async_copy(pool.at[pl.ds(_z(), P)], buf.at[b],
                                      sem.at[b]).wait()

            @pl.when(n < i32(P))
            def _():
                def page(j, carry):
                    pltpu.make_async_copy(pool.at[_z()], buf.at[b, _z()],
                                          sem.at[b]).wait()
                    return carry

                jax.lax.fori_loop(i32(0), n, page, i32(0))

        @pl.when((si == 0) & (bi == 0))
        def _first():
            # the rows a short block leaves unfetched must hold numbers
            buf[...] = jnp.zeros(buf.shape, buf.dtype)
            state[0] = i32(0)       # the buffer whose turn it is
            state[1] = i32(0)       # are this block's copies in flight?

        @pl.when(bi == 0)
        def _init():
            m_sc[...] = jnp.full(m_sc.shape, -1e30, f32)
            l_sc[...] = jnp.zeros(l_sc.shape, f32)
            acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

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
            kv = buf[b].reshape(rows, W)
            st = jnp.dot(kv, q_ref[...],
                         preferred_element_type=f32) * f32(scale)
            kpos = start + jax.lax.broadcasted_iota(i32, (rows, H), 0)
            st = jnp.where(kpos < n_valid, st, f32(-1e30))
            m_prev = m_sc[...]                                # (1, H)
            m_new = jnp.maximum(m_prev, st.max(axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pt = jnp.exp(st - m_new)                          # (rows, H)
            l_sc[...] = alpha * l_sc[...] + pt.sum(axis=0, keepdims=True)
            acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
                kv[:, :rank], pt.astype(kv.dtype),
                (((0,), (0,)), ((), ())), preferred_element_type=f32)
            m_sc[...] = m_new

        @pl.when(bi == nblk - 1)
        def _finish():
            # a slot of length 0 never computes: l = 0, and the floor
            # keeps its output finite
            o_ref[...] = (acc_sc[...] / jnp.maximum(
                l_sc[...], f32(1e-30))).astype(o_ref.dtype)

    def slot_ix(si, bi, *_):
        return (si, _z(), _z())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(S, nblk),
        in_specs=[pl.BlockSpec((None, W, H), slot_ix),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, rank, H), slot_ix),
        scratch_shapes=[pltpu.VMEM((1, H), jnp.float32),
                        pltpu.VMEM((1, H), jnp.float32),
                        pltpu.VMEM((rank, H), jnp.float32),
                        pltpu.VMEM((2, P, psz, W), np.dtype(dtype)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2,), jnp.int32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, rank, H), np.dtype(dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_latent_decode")


@functools.lru_cache(maxsize=None)
def _latent_decode_jit(P, rank, scale, interpret):
    """The call as ONE jitted function a (block, rank, scale, interpret):
    a stack's layers call it with the same shapes, so the second layer on
    reads the first's trace instead of tracing the body again. XLA
    inlines it."""
    import jax
    import jax.numpy as jnp

    def latent_decode(qa, pages, table, n_keys):
        S, H, width = qa.shape
        _, psz, W = pages.shape
        call = _latent_decode_call(S, H, W, rank, table.shape[1], psz, P,
                                   scale, interpret, pages.dtype)
        # [S, W, H]: the query closed with zeros to the row's width
        qt = jnp.swapaxes(jnp.pad(qa.astype(pages.dtype),
                                  ((0, 0), (0, 0), (0, W - width))), 1, 2)
        o = call(jnp.asarray(table, jnp.int32),
                 jnp.asarray(n_keys, jnp.int32), qt, pages)
        return jnp.swapaxes(o, 1, 2)

    return jax.jit(latent_decode)


def paged_latent_decode(qa, pages, table, n_keys, rank, scale,
                        interpret=False):
    """qa [S, H, width <= W] against each slot's first `n_keys` [S] rows
    read through `table` [S, max_pages] from `pages` [N + 1, psz, W]
    (trash-clipped: an inactive slot reads the trash row) -> [S, H, rank]
    in the pages' dtype: softmax(scale qa . row[:width]) summed over
    row[:rank], normalised."""
    _, psz, W = pages.shape
    P = block_pages(psz, W, table.shape[1], pages.dtype)
    return _latent_decode_jit(P, int(rank), float(scale), bool(interpret))(
        qa, pages, table, n_keys)
