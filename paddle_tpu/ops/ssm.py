"""State-space operators: the causal depthwise convolution, the Mamba-2
selective scan in its chunked, state-space-duality form, and the Mamba-1
selective scan (`selective_scan`, `selective_step`: a decay per channel
and state, so no matrix form; chunked, and one position at a time).

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


# --------------------------------------------------------------------------
# Mamba-1: a decay per (channel, state) pair
#
#     H_t = exp(dt_t A) * H_{t-1} + (dt_t x_t) (x) B_t     y_t = H_t C_t + D x_t
#
# with H [c, n], A [c, n] (negative), dt_t and x_t [c], B_t and C_t [n].
# dt, A, H and every decay are float32 whatever the operands' dtype. The
# state is HELD transposed, [n, c]: the c channels (thousands) lie on the
# lanes and the n states (16) on the sublanes, so no tile is padded.
# --------------------------------------------------------------------------

#: lanes of a channel block and positions of a time block of the kernel
_SCAN_LANES, _SCAN_BLOCK = 128, 128
#: positions a chunk of the composition that runs where the kernel does
#: not (the CPU): its states are [b, chunk, n, c] float32
_SCAN_CHUNK = 64


@functools.lru_cache(maxsize=None)
def _selective_scan_call(bsz, s, c, n, interpret):
    """The scan as a Pallas kernel: one grid step a (row, block of 128
    channels, block of 128 positions), the positions in order. The state
    of the block's channels, [n, 128] float32 (two registers at n = 16),
    lives in VMEM scratch across the time blocks; a position reads its dt
    and x rows and its B and C columns (handed in already spread over the
    128 lanes, [s, n, 128]) and writes one y row. Nothing of size s x n x
    c exists anywhere, which is what the composition below moves ten
    times over."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    lanes, lc = _SCAN_LANES, _SCAN_BLOCK
    nt = s // lc

    def z():
        return np.int32(0)

    def kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, h_ref,
               state):
        ti = pl.program_id(2)

        @pl.when(ti == 0)
        def _start():
            state[...] = h0_ref[...]

        a = a_ref[...]                                       # [n, 128]

        def eight(i, h):
            t0 = pl.multiple_of(i * jnp.int32(8), 8)
            dt8 = dt_ref[pl.ds(t0, 8), :]
            x8 = x_ref[pl.ds(t0, 8), :]
            rows = []
            for r in range(8):
                dt_t = dt8[r:r + 1, :]                       # [1, 128]
                h = jnp.exp(dt_t * a) * h \
                    + (dt_t * x8[r:r + 1, :]) * b_ref[t0 + jnp.int32(r)]
                rows.append(jnp.sum(h * c_ref[t0 + jnp.int32(r)], axis=0,
                                    keepdims=True))
            y_ref[pl.ds(t0, 8), :] = jnp.concatenate(rows, axis=0)
            return h

        h = jax.lax.fori_loop(jnp.int32(0), jnp.int32(lc // 8), eight,
                              state[...])
        state[...] = h

        @pl.when(ti == nt - 1)
        def _finish():
            h_ref[...] = h

    rows_spec = pl.BlockSpec((None, lc, lanes),
                             lambda bi, ci, ti: (bi, ti, ci))
    cols_spec = pl.BlockSpec((None, lc, n, lanes),
                             lambda bi, ci, ti: (bi, ti, z(), z()))
    state_spec = pl.BlockSpec((None, n, lanes),
                              lambda bi, ci, ti: (bi, z(), ci))
    return pl.pallas_call(
        kernel,
        grid=(bsz, c // lanes, nt),
        in_specs=[rows_spec, rows_spec,
                  pl.BlockSpec((n, lanes), lambda bi, ci, ti: (z(), ci)),
                  cols_spec, cols_spec, state_spec],
        out_specs=[rows_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, c), f32),
                   jax.ShapeDtypeStruct((bsz, n, c), f32)],
        scratch_shapes=[pltpu.VMEM((n, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="selective_scan")


def selective_scan_kernel_chosen(c, n, interpret=False):
    """Whether the scan goes through the Pallas kernel: on a TPU, by the
    switch every kernel of `ops/attention.py` has, where the channels tile
    the lanes and the states the sublanes."""
    from . import attention as A

    return interpret or (A._on_tpu() and A._flash_usable()
                         and c % _SCAN_LANES == 0 and n % 8 == 0)


def selective_scan(x, dt, a, b_mat, c_mat, d=None, h0=None, length=None,
                   interpret=False):
    """The selective scan of a Mamba-1 mixer over a sequence. x [b, s, c];
    dt [b, s, c] (after softplus) and a [c, n] float32; b_mat, c_mat
    [b, s, n]; d [c]; h0 [b, n, c] the state to start from (zeros).
    `length` [b]: positions at or past it neither decay nor feed the state
    (dt = 0 there), so the returned state is the one position length - 1
    left. Any s: the tail is padded the same way. Returns (y [b, s, c] in
    x's dtype, H [b, n, c] float32).

    Where `selective_scan_kernel_chosen`, the Pallas kernel, 128 positions
    a time block (a chosen kernel that fails raises); elsewhere a
    composition `_SCAN_CHUNK` positions at a time: inside a
    chunk an associative scan over (decay, input) pairs, and a recurrence
    over the chunks carries the one state a chunk leaves."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    bsz, s, c = x.shape
    n = a.shape[-1]
    kernel = selective_scan_kernel_chosen(c, n, interpret)
    chunk = _SCAN_BLOCK if kernel else _SCAN_CHUNK
    dt = dt.astype(f32)
    if length is not None:
        keep = jnp.arange(s, dtype=jnp.int32)[None] < jnp.asarray(
            length, jnp.int32).reshape(-1, 1)
        dt = jnp.where(keep[..., None], dt, f32(0.0))
    pad = -s % chunk
    if pad:
        def widen(t):
            return jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        x, dt, b_mat, c_mat = (widen(t) for t in (x, dt, b_mat, c_mat))
    nc = (s + pad) // chunk
    a_t = a.astype(f32).T                                   # [n, c]
    h0 = jnp.zeros((bsz, n, c), f32) if h0 is None else h0.astype(f32)
    if kernel:
        def spread(t):     # [b, s, n] -> [b, s, n, 128]: a column a lane
            return jnp.broadcast_to(t.astype(f32)[..., None],
                                    t.shape + (_SCAN_LANES,))
        y, h = _selective_scan_call(bsz, s + pad, c, n, interpret)(
            x.astype(f32), dt, a_t, spread(b_mat), spread(c_mat), h0)
        y = y[:, :s]
        if d is not None:
            y = y + x[:, :s].astype(f32) * d.astype(f32)
        return y.astype(x.dtype), h

    def chunks(t):         # [b, s, k] -> [nc, b, chunk, k]
        return jnp.moveaxis(t.reshape(bsz, nc, chunk, t.shape[-1]), 1, 0)

    def merge(left, right):
        # (decay, input) of two runs of positions, the left one first
        return (left[0] * right[0], right[0] * left[1] + right[1])

    def one(h, inp):
        xc, dtc, bc, cc = inp
        decay = jnp.exp(dtc[:, :, None, :] * a_t)           # [b, L, n, c]
        fed = (dtc * xc.astype(f32))[:, :, None, :] \
            * bc.astype(f32)[..., None]
        run_decay, run_fed = jax.lax.associative_scan(
            merge, (decay, fed), axis=1)
        states = run_decay * h[:, None] + run_fed           # H_t
        y = (states * cc.astype(f32)[..., None]).sum(2)     # [b, L, c]
        return states[:, -1], y

    h, y = jax.lax.scan(one, h0, tuple(
        chunks(t) for t in (x, dt, b_mat, c_mat)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, s + pad, c)[:, :s]
    if d is not None:
        y = y + x[:, :s].astype(f32) * d.astype(f32)
    return y.astype(x.dtype), h


def selective_step(x, dt, a, b_vec, c_vec, d, h):
    """One position of the same recurrence for every row of a batch:
    x, dt [b, c]; a [c, n]; b_vec, c_vec [b, n]; d [c] or None; h
    [b, n, c] float32. Returns (y [b, c] in x's dtype, the new h)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    dt = dt.astype(f32)
    xf = x.astype(f32)
    h = jnp.exp(dt[:, None, :] * a.astype(f32).T) * h.astype(f32) \
        + (dt * xf)[:, None, :] * b_vec.astype(f32)[..., None]
    y = (h * c_vec.astype(f32)[..., None]).sum(1)
    if d is not None:
        y = y + xf * d.astype(f32)
    return y.astype(x.dtype), h
