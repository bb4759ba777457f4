"""Multi-head latent attention (DeepSeek-V2 / V3, arXiv:2405.04434) as a
Layer. Inference only: the methods take and return raw arrays and record
no gradient.

    c_q = RMSNorm(u W_qa);  q = c_q W_qb, a head [q_nope | q_pe]
    [c | k_pe] = u W_kva;   c_kv = RMSNorm(c)
    a head's [k_nope | v] = c_kv W_kvb;  q_pe, k_pe rotated (`ops.rope`)
    s = (q_nope . k_nope + q_pe . k_pe) scale, causal, softmax in float32
    o = concat_h(P v) W_o

What a token leaves behind is ONE row, `[c_kv | rotated k_pe]`
(`row_width` values, no head axis), and the attention has two forms over
such rows: `causal` up-projects keys and values from them (a join: every
row is read by every later position of its own sequence), `absorbed` and
`absorbed_paged` (through the page table) fold W_kvb into the query and the
output and read the rows as stored (a decode step: one query, many rows).
"""
from __future__ import annotations

from .. import initializer as I
from .layers import Layer
from .ssm import _held


def rms(x, weight, eps):
    """RMSNorm over the last axis: float32 statistics, x's dtype out."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True)
                           + jnp.float32(eps))
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def _exp_and_sum(scores):
    """(exp(s - max), its sum) over the last axis, float32. The weighted
    sum of values is divided by the sum AFTER the product: normalising
    the [.., keys] weights first makes the chip's compiler fuse the max
    into a full-width reduce-window, ten times the step's other work
    (PERF.md section 6, PR 35)."""
    import jax.numpy as jnp

    e = jnp.exp(scores - scores.max(-1, keepdims=True))
    return e, e.sum(-1, keepdims=True)


class LatentAttention(Layer):
    """`num_heads` heads of `qk_nope + qk_rope` wide queries and keys and
    `v_head_dim` wide values over a `kv_lora_rank` wide latent. `rope`:
    theta, factor, original, beta_fast, beta_slow, mscale_all_dim (YaRN;
    factor 1 is plain rotary). `row_pad` zero values close a cached row
    (a lane multiple for the pool that holds it)."""

    #: scores of this many bytes (float32) stand at once in `causal`
    SCORE_BYTES = 1 << 29

    def __init__(self, hidden_size, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim, eps=1e-6,
                 rope=None, row_pad=0, dtype="float32"):
        super().__init__()
        from ...ops import rope as R

        self.num_heads = h = int(num_heads)
        self.rank, self.nope = int(kv_lora_rank), int(qk_nope_head_dim)
        self.rot, self.vdim = int(qk_rope_head_dim), int(v_head_dim)
        self.eps, self.row_pad = float(eps), int(row_pad)
        rope = dict(rope or {})
        factor = float(rope.get("factor", 1.0))
        self.inv_freq = R.yarn_inv_freq(
            self.rot, float(rope.get("theta", 10000.0)), factor,
            int(rope.get("original", 4096)), rope.get("beta_fast", 32),
            rope.get("beta_slow", 1))
        #: mscale == mscale_all_dim in the published configs, so the
        #: tables carry no factor and the softmax scale carries mscale^2
        self.scale = (self.nope + self.rot) ** -0.5 * R.yarn_mscale(
            factor, float(rope.get("mscale_all_dim", 0.0))) ** 2
        x = I.XavierUniform()
        self.q_a = _held(self, (hidden_size, q_lora_rank), x, dtype)
        self.q_a_norm = _held(self, (q_lora_rank,), I.Constant(1.0), dtype)
        self.q_b = _held(self, (q_lora_rank, h * (self.nope + self.rot)),
                         x, dtype)
        self.kv_a = _held(self, (hidden_size, self.rank + self.rot), x,
                          dtype)
        self.kv_a_norm = _held(self, (self.rank,), I.Constant(1.0), dtype)
        self.kv_b = _held(self, (self.rank, h * (self.nope + self.vdim)),
                          x, dtype)
        self.o = _held(self, (h * self.vdim, hidden_size), x, dtype)

    @property
    def row_width(self):
        """Values a token's cached row holds, its padding included."""
        return self.rank + self.rot + self.row_pad

    def project(self, a, positions):
        """a [..., hidden] at `positions` [...] -> (q [..., H, nope + rot]
        with its rotary slice rotated, row [..., row_width] = [c_kv |
        rotated k_pe | 0 ...])."""
        import jax.numpy as jnp

        from ...ops import rope as R

        cos, sin = R.table(positions, self.inv_freq)
        q = rms(a @ self.q_a._data, self.q_a_norm._data, self.eps) \
            @ self.q_b._data
        q = q.reshape(a.shape[:-1] + (self.num_heads, self.nope + self.rot))
        q = jnp.concatenate([
            q[..., :self.nope],
            R.rotate(q[..., self.nope:], cos[..., None, :],
                     sin[..., None, :])], -1)
        ckv = a @ self.kv_a._data
        parts = [rms(ckv[..., :self.rank], self.kv_a_norm._data, self.eps),
                 R.rotate(ckv[..., self.rank:], cos, sin)]
        if self.row_pad:
            parts.append(jnp.zeros(a.shape[:-1] + (self.row_pad,), a.dtype))
        return q, jnp.concatenate(parts, -1)

    def _w_kvb(self):
        """(W_uk [rank, H, nope], W_uv [rank, H, vdim])."""
        w = self.kv_b._data.reshape(self.rank, self.num_heads,
                                    self.nope + self.vdim)
        return w[..., :self.nope], w[..., self.nope:]

    def causal(self, q, rows):
        """Unabsorbed, a join: q [b, s, H, nope + rot] and the same
        positions' rows [b, s, row_width] -> [b, s, hidden]. Keys and
        values are up-projected from the rows."""
        import jax.numpy as jnp

        b, s, h, _ = q.shape
        kv = (rows[..., :self.rank] @ self.kv_b._data).reshape(
            b, s, h, self.nope + self.vdim)
        k_pe = rows[..., self.rank:self.rank + self.rot]
        k = jnp.concatenate([
            kv[..., :self.nope],
            jnp.broadcast_to(k_pe[:, :, None], (b, s, h, self.rot))], -1)
        v = kv[..., self.nope:]
        o = self._flash_causal(q, k, v)
        if o is None:
            o = self._composed_causal(q, k, v)
        return o.reshape(b, s, h * self.vdim) @ self.o._data

    def _flash_causal(self, q, k, v, interpret=False):
        """The flash kernel where its gates open (`ops.attention.
        _flash_plan`: the chip, a long enough, block-tileable sequence):
        it has one head size, so queries, keys and values are closed with
        zeros to the next 128 lanes (192 and 128 -> 256: the products
        ignore the zeros, the output's are cut). None elsewhere."""
        import jax.numpy as jnp

        from ...ops import attention as A

        b, s, h, d = q.shape
        wide = -(-d // 128) * 128
        if A._flash_plan(s, s, wide, None, b, h,
                         dtype=str(q.dtype)) is A._NO_FLASH:
            return None

        def bhsd(t):
            t = jnp.pad(t, ((0, 0),) * 3 + ((0, wide - t.shape[-1]),))
            return jnp.swapaxes(t, 1, 2)

        o = A.flash_attention(bhsd(q), bhsd(k), bhsd(v), None, True,
                              self.scale, interpret=interpret)
        return jnp.swapaxes(o, 1, 2)[..., :self.vdim]

    def _composed_causal(self, q, k, v):
        """The XLA composition: a group of heads' scores at a time."""
        import jax
        import jax.numpy as jnp

        f32 = jnp.float32
        b, s, h, _ = q.shape
        pos = jnp.arange(s, dtype=jnp.int32)
        keep = pos[None, :] <= pos[:, None]
        scale = f32(self.scale)

        def heads(args):
            qg, kg, vg = args                       # [b, s, g, .]
            sc = jnp.einsum("bqgd,bkgd->bgqk", qg, kg,
                            preferred_element_type=f32) * scale
            e, total = _exp_and_sum(jnp.where(keep, sc, f32(-1e30)))
            o = jnp.einsum("bgqk,bkgd->bqgd", e.astype(vg.dtype), vg,
                           preferred_element_type=f32)
            return (o / jnp.moveaxis(total, 1, 2)).astype(vg.dtype)

        g = max(1, min(h, self.SCORE_BYTES // (4 * b * s * s)))
        while h % g:
            g -= 1
        if g == h:
            return heads((q, k, v))

        def split(t):                   # [b, s, H, d] -> [H/g, b, s, g, d]
            return jnp.moveaxis(
                t.reshape(b, s, h // g, g, t.shape[-1]), 2, 0)

        return jnp.moveaxis(jax.lax.map(heads, (split(q), split(k),
                                                split(v))), 0, 2)

    def _absorb_query(self, q):
        """q [S, H, nope + rot] -> [S, H, rank + rot] = [q_nope . W_uk |
        q_pe]: the query that scores a row as stored."""
        import jax.numpy as jnp

        w_uk, _ = self._w_kvb()
        return jnp.concatenate([
            jnp.einsum("shd,chd->shc", q[..., :self.nope], w_uk),
            q[..., self.nope:]], -1)

    def _absorbed_out(self, o):
        """The heads' normalised sums of c_kv [S, H, rank] -> [S, hidden]:
        W_uv, then the output projection."""
        import jax.numpy as jnp

        _, w_uv = self._w_kvb()
        o = jnp.einsum("shc,chd->shd", o, w_uv)
        return o.reshape(o.shape[0], -1) @ self.o._data

    def absorbed(self, q, rows, n_keys):
        """Absorbed, a decode step: q [S, H, nope + rot] against each
        slot's cached rows [S, L, row_width], of which the first
        `n_keys` [S] are written -> [S, hidden]. W_uk goes into the
        query and W_uv onto the output: the rows are read as stored."""
        import jax.numpy as jnp

        f32 = jnp.float32
        width = self.rank + self.rot
        qa = self._absorb_query(q)                         # [S, H, width]
        sc = jnp.einsum("shc,slc->shl", qa, rows[..., :width],
                        preferred_element_type=f32) * f32(self.scale)
        live = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] \
            < n_keys[:, None]
        e, total = _exp_and_sum(jnp.where(live[:, None], sc, f32(-1e30)))
        o = jnp.einsum("shl,slc->shc", e.astype(rows.dtype),
                       rows[..., :self.rank], preferred_element_type=f32)
        return self._absorbed_out((o / total).astype(rows.dtype))

    def absorbed_paged(self, q, pages, table, n_keys, interpret=False):
        """`absorbed` over the rows as the pool holds them: pages [N + 1,
        psz, row_width] read through `table` [S, max_pages]. The
        `paged_latent_decode` kernel (`ops.latent_decode`) where its gates
        open, or under the TPU interpreter where `interpret`: it reads
        each written row once, for score and value. Elsewhere the slots'
        whole tables are gathered and `absorbed` runs over them."""
        from ...ops import latent_decode as LD

        if not (interpret or LD.kernel_opens(pages, self.rank,
                                             self.num_heads)):
            rows = pages[table].reshape(table.shape[0], -1, pages.shape[-1])
            return self.absorbed(q, rows, n_keys)
        return self._absorbed_out(LD.paged_latent_decode(
            self._absorb_query(q), pages, table, n_keys, self.rank,
            self.scale, interpret))
