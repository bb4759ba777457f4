"""State-space (Mamba-2) operators: the causal depthwise convolution and
the selective scan in its chunked, state-space-duality form.

The recurrence, per head with state `h` of shape [p, n]:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        y_t = C_t . h_t + D x_t

`ssd_scan` computes it `chunk` positions at a time (Dao & Gu, "Transformers
are SSMs", 2024): inside a chunk the outputs are matrix products (C B^T
masked by the decay, times x), each chunk leaves one state (B^T x), and a
recurrence over the chunks' states carries what earlier chunks contribute.
Nothing is kept per position: autodiff keeps one [p, n] state a chunk and
head, and each group of heads is recomputed in the backward pass
(`jax.checkpoint`), so the [chunk, chunk] decay matrices of one group of
heads are live at a time.

What stays float32 whatever the operands' dtype: dt, A, the cumulative
decays and their exponentials, every chunk's state and the recurrence over
them. The matrix products take operands in the dtype of `x` and accumulate
in float32.
"""
from __future__ import annotations

import functools


def causal_conv1d(x, weight, bias=None):
    """Depthwise causal convolution along the sequence: x [b, s, c],
    weight [c, k], bias [c] -> [b, s, c], y_t = sum_j w_j x_{t-k+1+j}.
    Accumulates in float32 and returns x's dtype."""
    import jax.numpy as jnp

    k = weight.shape[1]
    s = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    acc = sum(xp[:, j:j + s].astype(jnp.float32) * w[:, j]
              for j in range(k))
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return acc.astype(x.dtype)


def _ssd_group(x, dt, a, b_mat, c_mat, chunk):
    """The chunked scan of the heads that share one B and C: x [b, s, h,
    p], dt [b, s, h] float32, a [h] float32 (negative), b_mat and c_mat
    [b, s, n]; s a multiple of `chunk`. Returns y [b, s, h, p] without the
    D x term."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    nc = s // chunk
    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = b_mat.reshape(bsz, nc, chunk, n)
    cc = c_mat.reshape(bsz, nc, chunk, n)
    # head-major from here on, so that the [L, L] matrices of a head are
    # the two minor dimensions
    dth = jnp.moveaxis(dtc, 3, 2)                  # [b, nc, h, L]
    cs = jnp.cumsum(dth * a[:, None], axis=3)      # <= 0

    # inside a chunk: y_l = sum_{s<=l} (C_l . B_s) exp(cs_l - cs_s) dt_s x_s
    scores = jnp.einsum("bcln,bcsn->bcls", cc, bc,
                        preferred_element_type=f32)
    seg = cs[..., :, None] - cs[..., None, :]      # [b, nc, h, l, s]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    m = (scores[:, :, None] * decay * dth[..., None, :]).astype(x.dtype)
    y = jnp.einsum("bchls,bcshp->bclhp", m, xc,
                   preferred_element_type=f32)

    # each chunk's own state: sum_s exp(cs_last - cs_s) dt_s x_s (x) B_s
    w = jnp.exp(cs[..., -1:] - cs) * dth           # [b, nc, h, L]
    xw = (xc.astype(f32) * jnp.moveaxis(w, 2, 3)[..., None]).astype(
        x.dtype)
    own = jnp.einsum("bclhp,bcln->bchpn", xw, bc,
                     preferred_element_type=f32)
    total = jnp.exp(cs[..., -1])                   # [b, nc, h]

    # across chunks: the state a chunk starts from
    def carry(state, inp):
        own_c, total_c = inp
        return state * total_c[..., None, None] + own_c, state

    _, start = jax.lax.scan(
        carry, jnp.zeros((bsz, h, p, n), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(total, 1, 0)))
    start = jnp.moveaxis(start, 0, 1)                      # [b,nc,h,p,n]
    y_in = jnp.einsum("bcln,bchpn->bclhp", cc, start.astype(x.dtype),
                      preferred_element_type=f32)
    y = y + y_in * jnp.moveaxis(jnp.exp(cs), 2, 3)[..., None]
    return y.reshape(bsz, s, h, p)


def ssd_scan(x, dt, a, b_mat, c_mat, d=None, chunk=128):
    """The selective scan of a Mamba-2 mixer. x [b, s, h, p]; dt [b, s, h]
    (after softplus), a [h] (negative), d [h]: float32; b_mat, c_mat
    [b, s, g, n] with h a multiple of g (a group's B and C serve h / g
    heads). Any s: the tail is padded with dt = 0, which neither decays
    nor feeds the state. Returns y [b, s, h, p] in x's dtype."""
    import jax
    import jax.numpy as jnp

    bsz, s, h, p = x.shape
    g = b_mat.shape[2]
    hg = h // g
    pad = -s % chunk
    if pad:
        def widen(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        x, dt, b_mat, c_mat = (widen(t) for t in (x, dt, b_mat, c_mat))
    dt = dt.astype(jnp.float32)
    a = a.astype(jnp.float32)

    def by_group(t):       # [b, s, g * k, ...] -> [g, b, s, k, ...]
        return jnp.moveaxis(
            t.reshape(t.shape[:2] + (g, t.shape[2] // g) + t.shape[3:]),
            2, 0)

    one = jax.checkpoint(functools.partial(_ssd_group, chunk=chunk))
    y = jax.lax.map(
        lambda args: one(*args),
        (by_group(x), by_group(dt), a.reshape(g, hg),
         jnp.moveaxis(b_mat, 2, 0), jnp.moveaxis(c_mat, 2, 0)))
    y = jnp.moveaxis(y, 0, 2).reshape(bsz, s + pad, h, p)[:, :s]
    if d is not None:
        y = y + x[:, :s].astype(jnp.float32) * d.astype(
            jnp.float32)[:, None]
    return y.astype(x.dtype)


def mamba2_mix(zxbcdt, conv_w, conv_b, dt_bias, a_log, d, norm_w, *,
               num_heads, head_dim, n_groups, state_size, chunk, eps):
    """Everything of a Mamba-2 mixer between its two projections.
    zxbcdt [b, s, d_inner + (d_inner + 2 g n) + h] is in_proj's output,
    split [z | xBC | dt]; returns the gated, group-normalised y
    [b, s, d_inner] for out_proj. dt_bias, a_log and d are float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    bsz, s, _ = zxbcdt.shape
    d_inner = num_heads * head_dim
    gn = n_groups * state_size
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * gn]
    dt = zxbcdt[..., 2 * d_inner + 2 * gn:]
    xbc = jax.nn.silu(causal_conv1d(xbc, conv_w, conv_b).astype(
        f32)).astype(zxbcdt.dtype)
    x = xbc[..., :d_inner].reshape(bsz, s, num_heads, head_dim)
    b_mat = xbc[..., d_inner:d_inner + gn].reshape(
        bsz, s, n_groups, state_size)
    c_mat = xbc[..., d_inner + gn:].reshape(bsz, s, n_groups, state_size)
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    y = ssd_scan(x, dt, -jnp.exp(a_log.astype(f32)), b_mat, c_mat,
                 d, chunk).reshape(bsz, s, d_inner)
    # the gate comes before the norm; statistics over groups of
    # d_inner / n_groups channels, in float32
    y = y.astype(f32) * jax.nn.silu(z.astype(f32))
    yg = y.reshape(bsz, s, n_groups, d_inner // n_groups)
    yg = yg * jax.lax.rsqrt((yg * yg).mean(-1, keepdims=True)
                            + jnp.float32(eps))
    return (yg.reshape(bsz, s, d_inner)
            * norm_w.astype(f32)).astype(zxbcdt.dtype)
