"""Sparse-expert operators: top-k routing over all experts, and the part
of the result that the experts HELD HERE give, with no token dropped
whatever the routing.

A layer that holds `count` of the model's experts (expert parallelism: one
rank's share) routes over all of them and computes only its own experts'
terms. How many token-slots fall on a held expert is known only at run
time, and can be anything from none to every token. `routed_experts` keeps
shapes static without a capacity: the slots are sorted by expert, and each
held expert runs a loop over ITS rows, `TILE_ROWS` at a time, whose trip count
is the run-time count of its rows. A tile gathers its tokens, multiplies
through the expert's two matrices and scatter-adds the weighted result, so
no buffer is sized for a load, expected or worst. The backward pass is
written by hand (reverse-mode autodiff cannot go through a loop whose
length is a run-time value): the same loops, the hidden activation
recomputed a tile at a time. An expert is `W_out act(...)`: `"relu2"`,
relu(W_in x)^2 over two matrices (NemotronH), or `"swiglu"`, silu(W_gate
x) * (W_in x) over three (DeepSeek-V3). The chosen scores are normalised;
the choice may be limited to the best groups of experts (`n_group`,
`topk_group`: DeepSeek-V3's device-limited routing).
"""
from __future__ import annotations

import functools


def route_top_k(scores, correction_bias, top_k, scaling, n_group=1,
                topk_group=1):
    """scores [T, E] float32 in (0, 1): choose the `top_k` largest of
    `scores + correction_bias` per token, weigh them by their own score
    over the chosen scores' sum, times `scaling`. With `n_group` > 1 the
    experts lie in `n_group` equal groups and the choice is limited to
    the `topk_group` groups with the largest sum of their two best biased
    scores (the others' biased scores are set to 0, as the published
    code does). Returns (expert ids [T, k] int32, weights [T, k]
    float32); gradients reach `scores` through the weights only."""
    import jax
    import jax.numpy as jnp

    biased = jax.lax.stop_gradient(scores) + correction_bias
    if n_group > 1:
        grouped = biased.reshape(biased.shape[0], n_group, -1)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
        _, best = jax.lax.top_k(group_score, topk_group)
        keep = (best[..., None] == jnp.arange(
            n_group, dtype=best.dtype)).any(1)               # [T, groups]
        biased = jnp.where(keep[..., None], grouped,
                           jnp.float32(0.0)).reshape(biased.shape)
    _, idx = jax.lax.top_k(biased, top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    chosen = chosen / (chosen.sum(-1, keepdims=True) + jnp.float32(1e-20))
    return idx.astype(jnp.int32), chosen * jnp.float32(scaling)


def plan_held(idx, first, count, valid=None):
    """Where the token-slots of the held experts [first, first + count) lie.
    idx [T, k] int32 expert ids; `valid` [T] bool leaves the other
    tokens' slots out (a bucket's padding, an empty slot of a pool).
    Returns (order [T k] int32: the slots sorted by held expert, slots of
    experts not held last; starts [count] and counts [count] int32: each
    held expert's run in `order`)."""
    import jax.numpy as jnp

    local = idx.reshape(-1) - jnp.int32(first)
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & jnp.repeat(valid, idx.shape[-1])
    key = jnp.where(held, local, jnp.int32(count))
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = (key[:, None] == jnp.arange(count, dtype=jnp.int32)).sum(
        0, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    return order, starts.astype(jnp.int32), counts


def relu2(h):
    """The experts' activation, relu(h)^2, on float32 `h`."""
    import jax.numpy as jnp

    r = jnp.maximum(h, jnp.float32(0.0))
    return r * r


def swiglu(g, u):
    """The gated activation, silu(g) * u, on float32 `g` and `u`."""
    import jax

    return jax.nn.silu(g) * u


#: activation -> (the function of the input products, how many there are)
ACTIVATIONS = {"relu2": (relu2, 1), "swiglu": (swiglu, 2)}


def is_gated(activation):
    """Whether an expert of this activation holds a `w_gate`."""
    return ACTIVATIONS[activation][1] == 2


def _tile_rows(order, start, n_rows, j, tile, top_k, n_tokens):
    """Rows [j tile, (j + 1) tile) of an expert's run: (slot ids, token
    ids with rows past the run pointed at `n_tokens`, out of bounds, so
    that gathers fill and scatters drop; the rows' validity)."""
    import jax
    import jax.numpy as jnp

    at = j * jnp.int32(tile)
    slots = jax.lax.dynamic_slice(order, (start + at,), (tile,))
    valid = at + jnp.arange(tile, dtype=jnp.int32) < n_rows
    slots = jnp.where(valid, slots, jnp.int32(0))
    tokens = jnp.where(valid, slots // jnp.int32(top_k),
                       jnp.int32(n_tokens))
    return slots, tokens, valid


def _n_tiles(n_rows, tile):
    import jax.numpy as jnp

    return (n_rows + jnp.int32(tile - 1)) // jnp.int32(tile)


@functools.lru_cache(maxsize=None)
def _routed_fn(tile, top_k, activation):
    import functools as ft
    import operator

    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    act = ACTIVATIONS[activation][0]

    def padded(order):
        # a tile that starts inside `order` may end past it
        return jnp.concatenate([order, jnp.zeros((tile,), jnp.int32)])

    def forward(x, weights, w_ins, w_out, order, starts, counts):
        n_tokens = x.shape[0]
        order = padded(order)
        flat_w = weights.reshape(-1)

        def expert(out, per):
            wis, wo, start, n_rows = per

            def one_tile(j, carry):
                out, visited = carry
                slots, tokens, valid = _tile_rows(
                    order, start, n_rows, j, tile, top_k, n_tokens)
                xt = jnp.take(x, tokens, axis=0, mode="fill",
                              fill_value=0)
                h = act(*(jnp.dot(xt, wi, preferred_element_type=f32)
                          for wi in wis))
                y = jnp.dot(h.astype(x.dtype), wo,
                            preferred_element_type=f32)
                y = y * jnp.where(valid, flat_w[slots], f32(0.0))[:, None]
                return (out.at[tokens].add(y, mode="drop"),
                        visited + valid.sum(dtype=jnp.int32))

            return jax.lax.fori_loop(
                jnp.int32(0), _n_tiles(n_rows, tile), one_tile,
                (out, jnp.int32(0)))

        out, visited = jax.lax.scan(expert, jnp.zeros(x.shape, f32),
                                    (w_ins, w_out, starts, counts))
        return out.astype(x.dtype), visited

    def backward(res, cotangents):
        g = cotangents[0]               # the rows' count has no gradient
        x, weights, w_ins, w_out, order, starts, counts = res
        n_tokens = x.shape[0]
        order = padded(order)
        flat_w = weights.reshape(-1)

        def expert(carry, per):
            wis, wo, start, n_rows = per

            def one_tile(j, carry):
                dx, dflat, dwis, dwo = carry
                slots, tokens, valid = _tile_rows(
                    order, start, n_rows, j, tile, top_k, n_tokens)
                xt = jnp.take(x, tokens, axis=0, mode="fill",
                              fill_value=0)
                gt = jnp.take(g, tokens, axis=0, mode="fill",
                              fill_value=0)
                h, act_vjp = jax.vjp(act, *(
                    jnp.dot(xt, wi, preferred_element_type=f32)
                    for wi in wis))
                h = h.astype(x.dtype)
                y = jnp.dot(h, wo, preferred_element_type=f32)
                wt = jnp.where(valid, flat_w[slots], f32(0.0))
                # rows past the run carry slot 0: their gradient is 0
                dflat = dflat.at[slots].add(
                    jnp.where(valid, (y * gt.astype(f32)).sum(-1),
                              f32(0.0)))
                gy = (gt.astype(f32) * wt[:, None]).astype(x.dtype)
                dwo = dwo + jnp.dot(h.T, gy, preferred_element_type=f32)
                dpres = tuple(d.astype(x.dtype) for d in act_vjp(jnp.dot(
                    gy, wo.T, preferred_element_type=f32)))
                dwis = tuple(
                    dwi + jnp.dot(xt.T, d, preferred_element_type=f32)
                    for dwi, d in zip(dwis, dpres))
                dx = dx.at[tokens].add(ft.reduce(operator.add, (
                    jnp.dot(d, wi.T, preferred_element_type=f32)
                    for d, wi in zip(dpres, wis))), mode="drop")
                return dx, dflat, dwis, dwo

            dx, dflat = carry
            dx, dflat, dwis, dwo = jax.lax.fori_loop(
                jnp.int32(0), _n_tiles(n_rows, tile), one_tile,
                (dx, dflat, tuple(jnp.zeros(wi.shape, f32) for wi in wis),
                 jnp.zeros(wo.shape, f32)))
            return (dx, dflat), (
                tuple(dwi.astype(wi.dtype) for dwi, wi in zip(dwis, wis)),
                dwo.astype(wo.dtype))

        (dx, dflat), (dw_ins, dw_out) = jax.lax.scan(
            expert, (jnp.zeros(x.shape, f32),
                     jnp.zeros(flat_w.shape, f32)),
            (w_ins, w_out, starts, counts))
        return (dx.astype(x.dtype),
                dflat.reshape(weights.shape).astype(weights.dtype),
                dw_ins, dw_out, None, None, None)

    @jax.custom_vjp
    def routed(x, weights, w_ins, w_out, order, starts, counts):
        return forward(x, weights, w_ins, w_out, order, starts, counts)

    routed.defvjp(
        lambda *a: (forward(*a), a), backward)
    return routed


#: rows of one held expert that a step of its loop gathers and multiplies
TILE_ROWS = 256


def routed_experts(x, weights, w_in, w_out, order, starts, counts,
                   w_gate=None, activation="relu2"):
    """sum over the held experts e of weight[t, e] W_out[e] act_e(x[t])
    for the tokens routed to them, act_e = relu(W_in[e] x)^2 (`"relu2"`)
    or silu(W_gate[e] x) * (W_in[e] x) (`"swiglu"`): x [T, D]; weights
    [T, k] float32 (of every chosen expert, held or not); w_in (and
    w_gate) [count, D, F], w_out [count, F, D]; (order, starts, counts)
    from `plan_held`. Returns
    ([T, D] in x's dtype: zero rows for tokens none of whose experts is
    held; visited [count] int32: the rows of each held expert that its
    loop gathered and multiplied, counted tile by tile inside the loop, so
    `counts - visited` is what a run dropped: 0). Differentiable in x,
    weights, w_in, w_gate, w_out."""
    gated = is_gated(activation)
    if gated != (w_gate is not None):
        raise ValueError(f"activation {activation!r} takes "
                         f"{'a' if gated else 'no'} w_gate")
    top_k = weights.shape[-1]
    return _routed_fn(TILE_ROWS, int(top_k), activation)(
        x, weights, (w_gate, w_in) if gated else (w_in,), w_out, order,
        starts, counts)
