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
recomputed a tile at a time. The experts' activation is relu(h)^2 and the
chosen scores are normalised: the one model that uses this states both,
and `text.models.NemotronHConfig` refuses any other.
"""
from __future__ import annotations

import functools


def route_top_k(scores, correction_bias, top_k, scaling):
    """scores [T, E] float32 in (0, 1): choose the `top_k` largest of
    `scores + correction_bias` per token, weigh them by their own score
    over the chosen scores' sum, times `scaling`. Returns (expert ids
    [T, k] int32, weights [T, k] float32); gradients reach `scores`
    through the weights only."""
    import jax
    import jax.numpy as jnp

    _, idx = jax.lax.top_k(
        jax.lax.stop_gradient(scores) + correction_bias, top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    chosen = chosen / (chosen.sum(-1, keepdims=True) + jnp.float32(1e-20))
    return idx.astype(jnp.int32), chosen * jnp.float32(scaling)


def plan_held(idx, first, count):
    """Where the token-slots of the held experts [first, first + count) lie.
    idx [T, k] int32 expert ids. Returns (order [T k] int32: the slots
    sorted by held expert, slots of experts not held last; starts [count]
    and counts [count] int32: each held expert's run in `order`)."""
    import jax.numpy as jnp

    local = idx.reshape(-1) - jnp.int32(first)
    held = (local >= 0) & (local < count)
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
def _routed_fn(tile, top_k):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def padded(order):
        # a tile that starts inside `order` may end past it
        return jnp.concatenate([order, jnp.zeros((tile,), jnp.int32)])

    def forward(x, weights, w_in, w_out, order, starts, counts):
        n_tokens = x.shape[0]
        order = padded(order)
        flat_w = weights.reshape(-1)

        def expert(out, per):
            wi, wo, start, n_rows = per

            def one_tile(j, carry):
                out, visited = carry
                slots, tokens, valid = _tile_rows(
                    order, start, n_rows, j, tile, top_k, n_tokens)
                xt = jnp.take(x, tokens, axis=0, mode="fill",
                              fill_value=0)
                h = relu2(jnp.dot(xt, wi, preferred_element_type=f32))
                y = jnp.dot(h.astype(x.dtype), wo,
                            preferred_element_type=f32)
                y = y * jnp.where(valid, flat_w[slots], f32(0.0))[:, None]
                return (out.at[tokens].add(y, mode="drop"),
                        visited + valid.sum(dtype=jnp.int32))

            return jax.lax.fori_loop(
                jnp.int32(0), _n_tiles(n_rows, tile), one_tile,
                (out, jnp.int32(0)))

        out, visited = jax.lax.scan(expert, jnp.zeros(x.shape, f32),
                                    (w_in, w_out, starts, counts))
        return out.astype(x.dtype), visited

    def backward(res, cotangents):
        g = cotangents[0]               # the rows' count has no gradient
        x, weights, w_in, w_out, order, starts, counts = res
        n_tokens = x.shape[0]
        order = padded(order)
        flat_w = weights.reshape(-1)

        def expert(carry, per):
            wi, wo, start, n_rows = per

            def one_tile(j, carry):
                dx, dflat, dwi, dwo = carry
                slots, tokens, valid = _tile_rows(
                    order, start, n_rows, j, tile, top_k, n_tokens)
                xt = jnp.take(x, tokens, axis=0, mode="fill",
                              fill_value=0)
                gt = jnp.take(g, tokens, axis=0, mode="fill",
                              fill_value=0)
                h, act_vjp = jax.vjp(
                    relu2, jnp.dot(xt, wi, preferred_element_type=f32))
                h = h.astype(x.dtype)
                y = jnp.dot(h, wo, preferred_element_type=f32)
                wt = jnp.where(valid, flat_w[slots], f32(0.0))
                # rows past the run carry slot 0: their gradient is 0
                dflat = dflat.at[slots].add(
                    jnp.where(valid, (y * gt.astype(f32)).sum(-1),
                              f32(0.0)))
                gy = (gt.astype(f32) * wt[:, None]).astype(x.dtype)
                dwo = dwo + jnp.dot(h.T, gy, preferred_element_type=f32)
                dpre = act_vjp(jnp.dot(
                    gy, wo.T, preferred_element_type=f32))[0].astype(x.dtype)
                dwi = dwi + jnp.dot(xt.T, dpre,
                                    preferred_element_type=f32)
                dx = dx.at[tokens].add(
                    jnp.dot(dpre, wi.T, preferred_element_type=f32),
                    mode="drop")
                return dx, dflat, dwi, dwo

            dx, dflat = carry
            dx, dflat, dwi, dwo = jax.lax.fori_loop(
                jnp.int32(0), _n_tiles(n_rows, tile), one_tile,
                (dx, dflat, jnp.zeros(wi.shape, f32),
                 jnp.zeros(wo.shape, f32)))
            return (dx, dflat), (dwi.astype(wi.dtype),
                                 dwo.astype(wo.dtype))

        (dx, dflat), (dw_in, dw_out) = jax.lax.scan(
            expert, (jnp.zeros(x.shape, f32),
                     jnp.zeros(flat_w.shape, f32)),
            (w_in, w_out, starts, counts))
        return (dx.astype(x.dtype),
                dflat.reshape(weights.shape).astype(weights.dtype),
                dw_in, dw_out, None, None, None)

    @jax.custom_vjp
    def routed(x, weights, w_in, w_out, order, starts, counts):
        return forward(x, weights, w_in, w_out, order, starts, counts)

    routed.defvjp(
        lambda *a: (forward(*a), a), backward)
    return routed


#: rows of one held expert that a step of its loop gathers and multiplies
TILE_ROWS = 256


def routed_experts(x, weights, w_in, w_out, order, starts, counts):
    """sum over the held experts e of weight[t, e] W_out[e] relu(W_in[e]
    x[t])^2 for the tokens routed to them: x [T, D]; weights [T, k] float32
    (of every chosen expert, held or not); w_in [count, D, F], w_out
    [count, F, D]; (order, starts, counts) from `plan_held`. Returns
    ([T, D] in x's dtype: zero rows for tokens none of whose experts is
    held; visited [count] int32: the rows of each held expert that its
    loop gathered and multiplied, counted tile by tile inside the loop, so
    `counts - visited` is what a run dropped: 0). Differentiable in x,
    weights, w_in, w_out."""
    top_k = weights.shape[-1]
    return _routed_fn(TILE_ROWS, int(top_k))(
        x, weights, w_in, w_out, order, starts, counts)
