"""Differential attention over grouped key-value heads (the attention of
the SambaY decoders, arXiv:2507.06607 after arXiv:2410.05258).

The `Hq` query heads are `Hq / 2` pairs (q1 = head 2p, q2 = head 2p + 1),
the `Hkv` key heads `Hkv / 2` pairs likewise, the value heads of a pair
joined side by side: V_g = [v_2g | v_2g+1], `2 d` wide. Pair p reads group
g = p // (Hq / Hkv):

    A1 = softmax(q1 k1^T / sqrt(d)) V_g      A2 = softmax(q2 k2^T / sqrt(d)) V_g
    o_p = (1 - lambda_init) RMSNorm_2d(A1 - lambda A2)

How it is computed here: a key row of `Hkv x d` lanes is also a row of
`Hkv / 2` heads of `2 d` lanes, [k_2g | k_2g+1], and a query head padded
with zeros onto the half its key head holds ([q | 0] or [0 | q]) has the
same logits against the wide head as against its own key head. So both
softmaxes of every pair are ONE grouped-query attention with `Hkv / 2`
key-value heads of `2 d` and `G = 2 Hq / Hkv` query heads each, at scale
d^-1/2; the pairs are combined afterwards (`combine`). Softmax, lambda
and the norm's statistics are float32.
"""
from __future__ import annotations

import math


def lambda_init(layer_idx):
    return 0.8 - 0.6 * math.exp(-0.3 * layer_idx)


def widen_queries(q, kv_heads):
    """q [..., Hq, d] -> [..., Hkv / 2, G, 2 d]: group g's query heads
    (heads g G .. g G + G - 1, in order), each on the half of the wide
    head's lanes that its key head holds (even heads are q1: the first
    half)."""
    import jax.numpy as jnp

    hq, d = q.shape[-2:]
    groups = kv_heads // 2
    gsz = hq // groups
    q = q.reshape(q.shape[:-2] + (groups, gsz // 2, 2, d))
    z = jnp.zeros_like(q[..., 0, :])
    first = jnp.concatenate([q[..., 0, :], z], -1)     # q1: [q | 0]
    second = jnp.concatenate([z, q[..., 1, :]], -1)    # q2: [0 | q]
    return jnp.stack([first, second], -2).reshape(
        q.shape[:-4] + (groups, gsz, 2 * d))


def lam(lq1, lk1, lq2, lk2, layer_idx):
    """lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, float32."""
    import jax.numpy as jnp

    f32 = jnp.float32
    return (jnp.exp(jnp.sum(lq1.astype(f32) * lk1.astype(f32)))
            - jnp.exp(jnp.sum(lq2.astype(f32) * lk2.astype(f32)))
            + f32(lambda_init(layer_idx)))


def combine(att, lam_value, subln_w, layer_idx, eps=1e-5):
    """att [..., groups, G, 2 d], the attention of every widened query
    head -> [..., Hq d]: o_p of every pair, side by side."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    g, gsz, w = att.shape[-3:]
    a = att.astype(f32).reshape(att.shape[:-2] + (gsz // 2, 2, w))
    diff = a[..., 0, :] - lam_value * a[..., 1, :]
    diff = diff * jax.lax.rsqrt((diff * diff).mean(-1, keepdims=True)
                                + f32(eps))
    out = diff * subln_w.astype(f32) * f32(1.0 - lambda_init(layer_idx))
    return out.reshape(att.shape[:-3] + (g * (gsz // 2) * w,)).astype(
        att.dtype)


def _wide(k, kv_heads):
    """[..., Hkv d] rows -> [..., Hkv / 2, 2 d] wide heads (a reshape)."""
    return k.reshape(k.shape[:-1] + (kv_heads // 2,
                                     2 * k.shape[-1] // kv_heads))


def causal(q, k, v, kv_heads, window=None):
    """Prefill. q [b, s, Hq, d]; k, v rows [b, s, Hkv d]. Every position
    attends to itself and what precedes it, the last `window` positions
    only where given. Returns [b, s, Hkv / 2, G, 2 d]. The full form goes
    through `sdpa_bshd` (the flash kernel by its gates); the windowed one
    is a composition over blocks of `window` queries against their own and
    the previous block of keys."""
    import jax
    import jax.numpy as jnp

    from . import attention as A

    b, s, hq, d = q.shape
    groups = kv_heads // 2
    gsz = hq // groups
    scale = 1.0 / math.sqrt(d)
    qw = widen_queries(q, kv_heads)                    # [b, s, g, G, 2d]
    kw, vw = _wide(k, kv_heads), _wide(v, kv_heads)    # [b, s, g, 2d]
    if window is None or window >= s:
        out = A.sdpa_bshd(qw.reshape(b, s, groups * gsz, 2 * d), kw, vw,
                          None, True, scale)
        return out.reshape(b, s, groups, gsz, 2 * d)
    pad = -s % window
    if pad:
        qw = jnp.pad(qw, ((0, 0), (0, pad)) + ((0, 0),) * 3)
        kw = jnp.pad(kw, ((0, 0), (0, pad)) + ((0, 0),) * 2)
        vw = jnp.pad(vw, ((0, 0), (0, pad)) + ((0, 0),) * 2)
    nb = (s + pad) // window
    qb = qw.reshape(b, nb, window, groups, gsz, 2 * d)

    def with_prev(t):      # [b, s, g, w] -> [b, nb, 2 window, g, w]
        t = t.reshape(b, nb, window, groups, 2 * d)
        prev = jnp.pad(t[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * 3)
        return jnp.concatenate([prev, t], 2)

    kb, vb = with_prev(kw), with_prev(vw)
    logits = jnp.einsum("bnqgmd,bnkgd->bngmqk", qb, kb,
                        preferred_element_type=jnp.float32) \
        * jnp.float32(scale)
    # column c of a block's keys is position (n - 1) window + c, row r of
    # its queries position n window + r: row r sees columns r + 1 .. r +
    # window
    r = jnp.arange(window, dtype=jnp.int32)[:, None]
    c = jnp.arange(2 * window, dtype=jnp.int32)[None, :]
    keep = (c > r) & (c <= r + window)
    first = (jnp.arange(nb, dtype=jnp.int32) == 0)[:, None, None]
    keep = keep[None] & ~(first & (c < window)[None])   # no block before 0
    logits = jnp.where(keep[None, :, None, None], logits,
                       jnp.float32(-1e30))
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngmqk,bnkgd->bnqgmd", p.astype(vb.dtype), vb,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(b, s + pad, groups, gsz, 2 * d)[:, :s]


def dense(q, k_rows, v_rows, kv_heads, n_keys):
    """One query position a row against token-major key and value rows
    ([b, L, Hkv d]: a window ring, a prompt, the pages gathered), of which
    the first `n_keys` [b] are valid: q [b, Hq, d] -> [b, groups, G, 2d].

    The rows are read AS THEY LIE: every widened query is laid on its own
    wide head's lanes of a full row and zeros elsewhere, so the logits of
    all Hq heads are one product a slot, [Hq, Hkv d] x [Hkv d, L], and the
    values one more, of which each head keeps its own wide head's lanes.
    Ten times the multiply-adds of the per-head products and no copy: a
    view of [.., Hkv / 2, 2 d] minor dimensions is a relayout of all the
    rows (a reshape and a copy, 5 ms each a step at 64 slots x 4096
    positions; head-major rings made their row writes copy the rings: my
    chip runs, PR 33), and reading the rows is what bounds a decode step."""
    import jax
    import jax.numpy as jnp

    qw = widen_queries(q, kv_heads)                    # [b, g, G, 2d]
    b, g, gsz, w = qw.shape
    own = jnp.eye(g, dtype=qw.dtype)[None, :, None, :, None]
    rows = (qw[:, :, :, None, :] * own).reshape(b, g * gsz, g * w)
    logits = jnp.einsum("bmk,blk->bml", rows, k_rows,
                        preferred_element_type=jnp.float32) \
        * jnp.float32(1.0 / math.sqrt(q.shape[-1]))
    valid = jnp.arange(k_rows.shape[1], dtype=jnp.int32)[None] \
        < jnp.asarray(n_keys, jnp.int32)[:, None]
    logits = jnp.where(valid[:, None, :], logits, jnp.float32(-1e30))
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bml,blk->bmk", p.astype(v_rows.dtype), v_rows,
                     preferred_element_type=jnp.float32)
    out = (out.reshape(b, g, gsz, g, w)
           * own.astype(jnp.float32)).sum(3)
    return out.astype(q.dtype)


def paged_reader(k_pages, v_pages, table, kv_heads):
    """How the layers of ONE decode step read the same pages ([N + 1,
    psz, Hkv d], `table` [S, max_pages]): a function (q [S, Hq, d],
    n_keys [S]) -> [S, groups, G, 2d]. On the chip, in a trace where the
    attention dispatchers may take their kernels
    (`ops.attention._flash_usable`: not under `PT_FLASH_ATTENTION=0`, not
    in a program the partitioner splits) and over a pool the kernel fits,
    every layer reads its slots' written pages through the table
    (`ops.grouped_decode`) and no view is gathered. Elsewhere the dense
    view is gathered ONCE here and every layer attends to it (`dense`):
    the gather is the pool's size in traffic, and eight layers share
    it."""
    from . import attention as A

    S, psz, hd = table.shape[0], k_pages.shape[1], k_pages.shape[-1]
    if A._on_tpu() and A._flash_usable():
        from . import grouped_decode as GD

        if GD.kernel_fits(psz, hd, 2 * hd // kv_heads, k_pages.dtype):
            def read(q, n_keys):
                return GD.paged_grouped_decode(
                    widen_queries(q, kv_heads), k_pages, v_pages, table,
                    n_keys, 1.0 / math.sqrt(q.shape[-1]))

            return read
    k_rows = k_pages[table].reshape(S, -1, hd)
    v_rows = v_pages[table].reshape(S, -1, hd)
    return lambda q, n_keys: dense(q, k_rows, v_rows, kv_heads, n_keys)
