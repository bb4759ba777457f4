"""Plain reference of the NemotronH causal language model (`model_type`
"nemotron_h": Mamba-2 mixers, sparse experts with a shared expert,
grouped-query attention), its loss and, by `jax.grad` of that loss, its
gradients. float32, matmul precision "highest", `jax.numpy` only: no
kernel, no chunked scan, no sorted dispatch.

Reads the program's own parameters and buffers (one flat dict by name)
and the benchmark configuration's keys (`configs/nemotron3_nano_ep16.json`:
the source's names, plus `router_experts`, the router's published width,
and `experts_held` = [first, count]). Leaves nothing anywhere.

For each character of `hybrid_override_pattern`, x <- x + mixer(RMSNorm(x)):

- M: [z | xBC | dt] = in_proj(u); xBC = silu(causal depthwise conv(xBC) +
  b); [x | B | C] = xBC; dt = softplus(dt + dt_bias); A = -exp(A_log);
  h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t, y_t = C_t . h_t + D x_t, as
  a `lax.scan` over positions; y = group RMSNorm(y * silu(z));
  out_proj(y).
- E: s = sigmoid(W_r u) over all `router_experts`; the `num_experts_per_tok`
  largest of s + b are chosen; w = routed_scaling_factor x s[chosen] /
  sum(s[chosen]); y = sum over the HELD experts e, by a dense loop with a
  mask, of w_e W2_e relu(W1_e u)^2, plus the shared expert W2_s relu(W1_s
  u)^2. What experts that are not held would add is left out, as in the
  program.
- *: q (num_attention_heads x head_dim), k and v (num_key_value_heads x
  head_dim) repeated to the query heads, causal softmax at head_dim^-1/2
  as the masked [s, s] composition, queries `q_block` at a time; o_proj.
  No rotary or other positional term.

Then a final RMSNorm and the untied head. `loss_along` is the loss and
its derivative along one direction (what the job holds the timed step's
gradient to), `adamw_first_update` the optimizer's first update, plain.
`dtype` computes everything,
the float32 quantities included, in another precision, for the readings
that place the cell's limits (PERF.md): bfloat16 throughout, or an 8-bit
float, which is a storage format: parameters and the residual stream after
every block are rounded to it and the arithmetic is float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def relu2(x):
    return jnp.maximum(x, 0) ** 2


def selective_scan(x, dt, a, bm, cm):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t, y_t = C_t . h_t, one
    position at a time: x [b, s, h, p], dt [b, s, h], a [h], bm and cm
    [b, s, h, n] (a group's B and C already repeated to its heads)."""
    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        return state, (state * c_t[:, :, None]).sum(-1)

    _, y = jax.lax.scan(
        step, jnp.zeros(x.shape[:1] + x.shape[2:] + bm.shape[-1:], x.dtype),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1)


def mamba(p, n, u, cfg):
    h, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, ns = cfg["n_groups"], cfg["ssm_state_size"]
    k = cfg["conv_kernel"]
    d_in = h * hd
    b, s, _ = u.shape
    zxbcdt = u @ p[n + "in_proj.weight"]
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * g * ns], -1)
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    w = p[n + "conv1d.weight"]
    xbc = sum(pad[:, j:j + s] * w[:, j] for j in range(k))
    xbc = jax.nn.silu(xbc + p[n + "conv1d.bias"])
    x, bm, cm = jnp.split(xbc, [d_in, d_in + g * ns], -1)
    x = x.reshape(b, s, h, hd)
    bm = jnp.repeat(bm.reshape(b, s, g, ns), h // g, axis=2)
    cm = jnp.repeat(cm.reshape(b, s, g, ns), h // g, axis=2)
    dt = jax.nn.softplus(dt + p[n + "dt_bias"])            # [b, s, h]
    a = -jnp.exp(p[n + "A_log"])

    y = selective_scan(x, dt, a, bm, cm)
    y = y + x * p[n + "D"][:, None]
    y = y.reshape(b, s, d_in) * jax.nn.silu(z)
    yg = y.reshape(b, s, g, d_in // g)
    yg = yg * jax.lax.rsqrt((yg * yg).mean(-1, keepdims=True)
                            + cfg["layer_norm_epsilon"])
    return (yg.reshape(b, s, d_in) * p[n + "norm.weight"]) \
        @ p[n + "out_proj.weight"]


def router(p, n, u, cfg):
    """(scores over all experts, the chosen experts' ids): s = sigmoid(W_r
    u); the `num_experts_per_tok` largest of s + b."""
    s = jax.nn.sigmoid(u @ p[n + "gate.weight"].T)
    bias = p.get(n + "gate.e_score_correction_bias", 0.0)
    return s, jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])[1]


def experts(p, n, u, cfg, held=None, shared=True):
    """`held` = (first, count) overrides the configuration's share (the
    share test sums the shares); `shared=False` leaves the shared expert
    out."""
    first, count = held or cfg["experts_held"]
    s, idx = router(p, n, u, cfg)
    chosen = jnp.take_along_axis(s, idx, -1)
    w = cfg["routed_scaling_factor"] * chosen / chosen.sum(-1, keepdims=True)
    y = jnp.zeros_like(u)
    for j in range(count):
        w_e = (w * (idx == first + j)).sum(-1, keepdims=True)
        y = y + w_e * (relu2(u @ p[n + "experts.weight_in"][j])
                       @ p[n + "experts.weight_out"][j])
    if shared:
        y = y + relu2(u @ p[n + "shared_experts.up_proj.weight"]) \
            @ p[n + "shared_experts.down_proj.weight"]
    return y


def attention(p, n, u, cfg, q_block=512):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    b, s, _ = u.shape
    q = (u @ p[n + "q_proj.weight"]).reshape(b, s, hq, d)
    k = (u @ p[n + "k_proj.weight"]).reshape(b, s, hkv, d)
    v = (u @ p[n + "v_proj.weight"]).reshape(b, s, hkv, d)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    # one block of queries at a time (`lax.map`: the [q_block, s] scores
    # of one block are live, in the forward-mode pass too); rows past s
    # pad the last block and are cut off
    blocks = -(-s // q_block)
    q = jnp.pad(q, ((0, 0), (0, blocks * q_block - s), (0, 0), (0, 0)))

    def one(args):
        qb, at = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        rows = at + jnp.arange(q_block)
        sc = jnp.where(rows[:, None] >= jnp.arange(s)[None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)

    out = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(b, blocks, q_block, hq, d), 1, 0),
        jnp.arange(blocks) * q_block))
    return jnp.moveaxis(out, 0, 1).reshape(b, blocks * q_block, hq * d)[
        :, :s] @ p[n + "o_proj.weight"]


MIXERS = {"M": mamba, "E": experts, "*": attention}


def logits(params, ids, cfg, dtype=jnp.float32):
    eps = cfg["layer_norm_epsilon"]
    store = jnp.dtype(dtype)
    compute = store if store.itemsize > 1 else jnp.dtype(jnp.float32)

    def held_as(t):
        return jnp.asarray(t).astype(store).astype(compute)

    with jax.default_matmul_precision("highest"):
        p = {k: held_as(v) for k, v in params.items()}
        x = p["embeddings.weight"][ids]
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            n = f"layers.{i}."
            x = held_as(x + MIXERS[kind](p, n + "mixer.", rms_norm(
                x, p[n + "norm.weight"], eps), cfg))
        return rms_norm(x, p["norm_f.weight"], eps) @ p["lm_head.weight"]


def loss(params, ids, labels, cfg, dtype=jnp.float32):
    """Mean next-token cross-entropy over every position; `labels` are the
    ids shifted by one by the caller."""
    lp = jax.nn.log_softmax(logits(params, ids, cfg, dtype).astype(
        jnp.float32), -1)
    return -jnp.take_along_axis(lp, labels[..., None], -1).mean()


def loss_along(params, tangent, ids, labels, cfg):
    """(loss, its derivative along `tangent`): forward-mode through the
    reference as it stands, so nothing is kept per position and the
    recurrence stays a scan over positions at any length. `tangent` has a
    direction for every floating entry of `params` that the loss is
    differentiated in; entries it lacks (a routing bias) are held."""
    held = {k: v for k, v in params.items() if k not in tangent}
    moved = {k: params[k] for k in tangent}
    return jax.jvp(lambda p: loss({**held, **p}, ids, labels, cfg),
                   (moved,), (tangent,))


def adamw_first_update(p0, grad, lr, weight_decay, decayed,
                       beta1=0.9, beta2=0.999, eps=1e-8):
    """Plain AdamW, the first update from zero moments, float32, one
    array: m = (1 - beta1) g, v = (1 - beta2) g^2, both corrected by their
    (1 - beta^1), so the step is g / (|g| + eps); decoupled decay where
    `decayed`. Returns the parameter after it."""
    m_hat = (1.0 - beta1) * grad / (1.0 - beta1)
    v_hat = (1.0 - beta2) * grad * grad / (1.0 - beta2)
    step = m_hat / (jnp.sqrt(v_hat) + eps)
    if decayed:
        step = step + weight_decay * p0
    return p0 - jnp.float32(lr) * step
