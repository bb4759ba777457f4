"""Plain reference of Phi-4-mini-flash-reasoning's forward pass
(`phi4flash`; the SambaY decoder of arXiv:2507.06607): the equations in
straightforward jax.numpy, float32, matmul precision "highest". A full
forward over the whole sequence of ONE request: no kernel, cache, ring,
page or batching, and nothing imported from paddle_tpu.

For a token's vector x at position t, block l of L (half = L / 2):

    h = x + Mixer_l(LN(x));  y = h + W2 (u * silu(g)),  [u; g] = W1 LN'(h)
    logits = LN_f(y_L-1) E^T, E the embedding table

- l even, l <= half, Mamba-1: [xs; z] = W_in a; xs = silu(conv_causal(xs)
  + b); [d; B; C] = W_x xs; D_t = softplus(W_dt d + b_dt); A = -exp(A_log);
  H_t = exp(D_t A) * H_t-1 + (D_t xs_t) (x) B_t; s_t = H_t C_t + D xs_t;
  output W_out (s_t * silu(z_t)). Block `half` publishes the memory m_t = s_t.
- l odd, l < half: differential attention over keys j, 0 <= t - j < window.
- l = half + 1: differential attention over all j <= t; its K and V are
  what the cross layers read.
- l even, l > half, gated memory unit: W_out (m_t * silu(W_in a)).
- l odd, l > half + 1, cross attention: q = W_q a + b_q only, K and V of
  block half + 1, over all j <= t.
- differential attention at block l: query heads in pairs (2p, 2p + 1),
  key heads in pairs (2g, 2g + 1), V_g = [v_2g | v_2g+1]; pair p reads
  g = p // (Hq / Hkv). A1 = softmax(q1 k1^T / sqrt(d)) V_g, A2 likewise;
  lambda_init = 0.8 - 0.6 exp(-0.3 l); lambda = exp(lq1 . lk1) -
  exp(lq2 . lk2) + lambda_init; o_p = (1 - lambda_init) RMSNorm(A1 -
  lambda A2) * w; the o_p side by side go through the output projection.
- no positional term of any kind.

The scan is a loop over positions: a Python loop (`scan="loop"`, what
tier-1 compares the program with) or the same body under `lax.scan`
(`scan="lax"`, for the cell's 4096 positions). On the chip
(`token_margins`) the reference takes the served weights' values and
widens each where it is used, so a layer's float32 copy lives only while
the layer runs; attention goes pair by pair and the logits 256 positions
at a time, so that it fits beside the pool.

`round_to` (for the reading that places the cell's limit, PERF.md): every
matrix product's operands rounded to that dtype first, e.g.
"float8_e4m3fn", the nearest precision below the configuration's bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_NEG = -1e30
F32 = jnp.float32


def kind(cfg, i):
    half = int(cfg["num_hidden_layers"]) // 2
    if i % 2 == 0:
        return "mamba" if i <= half else "gmu"
    return "swa" if i < half else "full" if i == half + 1 else "xattn"


def _sizes(cfg):
    a = cfg["assumed"]
    hidden = int(cfg["hidden_size"])
    d = int(a.get("head_dim") or hidden // int(cfg["num_attention_heads"]))
    return dict(hq=int(cfg["num_attention_heads"]),
                hkv=int(cfg["num_key_value_heads"]), d=d,
                di=int(a["expand"]) * hidden, n=int(a["d_state"]),
                r=int(a["dt_rank"]), k=int(a["d_conv"]),
                window=int(cfg["sliding_window"]),
                eps=float(cfg["layer_norm_eps"]))


class _Math:
    """Matrix products at float32 (operands rounded first where the
    reading asks for a lower precision)."""

    def __init__(self, round_to=None):
        self.round_to = None if round_to is None else jnp.dtype(round_to)

    def r(self, x):
        x = jnp.asarray(x, F32)
        return x if self.round_to is None else \
            x.astype(self.round_to).astype(F32)

    def mm(self, x, w):
        return self.r(x) @ self.r(w)


def layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * jnp.asarray(w, F32) \
        + jnp.asarray(b, F32)


def mamba(p, n, a, z, m, scan):
    """a [L, hidden] -> (out [L, hidden], s [L, d_inner])."""
    di, ns, r, k = z["di"], z["n"], z["r"], z["k"]
    length = a.shape[0]
    xz = m.mm(a, p[n + "in_proj"])
    xs, gate = xz[:, :di], xz[:, di:]
    w = jnp.asarray(p[n + "conv_weight"], F32)              # [di, k]
    xp = jnp.pad(xs, ((k - 1, 0), (0, 0)))
    xs = jax.nn.silu(sum(xp[j:j + length] * w[:, j] for j in range(k))
                     + jnp.asarray(p[n + "conv_bias"], F32))
    dbc = m.mm(xs, p[n + "x_proj"])
    dt = jax.nn.softplus(m.mm(dbc[:, :r], p[n + "dt_proj"])
                         + jnp.asarray(p[n + "dt_bias"], F32))
    b_mat, c_mat = dbc[:, r:r + ns], dbc[:, r + ns:]
    a_mat = -jnp.exp(jnp.asarray(p[n + "A_log"], F32))      # [di, n]

    def position(h, inp):
        dt_t, x_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * a_mat) * h \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    h = jnp.zeros((di, ns), F32)
    if scan == "loop":
        ys = []
        for t in range(length):
            h, y = position(h, (dt[t], xs[t], b_mat[t], c_mat[t]))
            ys.append(y)
        s = jnp.stack(ys)
    else:
        _, s = jax.lax.scan(position, h, (dt, xs, b_mat, c_mat))
    s = s + jnp.asarray(p[n + "D"], F32) * xs
    return m.mm(s * jax.nn.silu(gate), p[n + "out_proj"]), s


def diff_attention(p, n, layer, q, k, v, keep, z, m, by_pair):
    """q [L, Hq, d]; k, v [L, Hkv, d]; keep [L, L] bool (row t, key j)
    -> [L, hidden] after the output projection."""
    hq, hkv, d = z["hq"], z["hkv"], z["d"]
    rep = hq // hkv
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(jnp.asarray(p[n + "lambda_q1"], F32)
                           * jnp.asarray(p[n + "lambda_k1"], F32)))
           - jnp.exp(jnp.sum(jnp.asarray(p[n + "lambda_q2"], F32)
                             * jnp.asarray(p[n + "lambda_k2"], F32)))
           + lam_init)
    sub_w = jnp.asarray(p[n + "subln_weight"], F32)

    def soft(qh, kh, vg):
        s = m.mm(qh, kh.T) / math.sqrt(d)
        s = jnp.where(keep, s, _NEG)
        return m.mm(jax.nn.softmax(s, -1), vg)

    def pair(args):
        q1, q2, k1, k2, vg = args
        o = soft(q1, k1, vg) - lam * soft(q2, k2, vg)       # [L, 2d]
        o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + 1e-5)
        return (1.0 - lam_init) * o * sub_w

    g_of = jnp.arange(hq // 2) // rep
    kp = jnp.moveaxis(k, 1, 0)                              # [Hkv, L, d]
    vp = jnp.moveaxis(v, 1, 0)
    vg = jnp.concatenate([vp[0::2], vp[1::2]], -1)          # [Hkv/2, L, 2d]
    qp = jnp.moveaxis(q, 1, 0)
    args = (qp[0::2], qp[1::2], kp[0::2][g_of], kp[1::2][g_of], vg[g_of])
    if by_pair:
        o = jax.lax.map(pair, args)                         # [P, L, 2d]
    else:
        o = jnp.stack([pair(tuple(a[i] for a in args))
                       for i in range(hq // 2)])
    o = jnp.moveaxis(o, 0, 1).reshape(q.shape[0], hq * d)
    return m.mm(o, p[n + "out_weight"]) + jnp.asarray(p[n + "out_bias"], F32)


def hidden_states(p, tokens, n_valid, cfg, scan="loop", round_to=None,
                  by_pair=False):
    """[L] tokens (padded past n_valid) -> [L, hidden] before the final
    norm. Positions at or past n_valid come after every real one, so the
    causal mask alone keeps them out of what the real ones read."""
    z, m = _sizes(cfg), _Math(round_to)
    length = tokens.shape[0]
    pos = jnp.arange(length)
    causal = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
    window = causal & (pos[:, None] - pos[None, :] < z["window"])
    half = int(cfg["num_hidden_layers"]) // 2
    x = jnp.asarray(p["embed_tokens"], F32)[tokens]
    memory = kv = None
    for i in range(int(cfg["num_hidden_layers"])):
        n = f"layers.{i}."
        a = layer_norm(x, p[n + "input_layernorm.weight"],
                       p[n + "input_layernorm.bias"], z["eps"])
        what = kind(cfg, i)
        mx = n + "mixer."
        if what == "mamba":
            out, s = mamba(p, mx, a, z, m, scan)
            if i == half:
                memory = s
        elif what == "gmu":
            out = m.mm(memory * jax.nn.silu(m.mm(a, p[mx + "in_proj"])),
                       p[mx + "out_proj"])
        else:
            y = m.mm(a, p[mx + "in_weight"]) \
                + jnp.asarray(p[mx + "in_bias"], F32)
            qd, kvd = z["hq"] * z["d"], z["hkv"] * z["d"]
            q = y[:, :qd].reshape(length, z["hq"], z["d"])
            if what != "xattn":
                k = y[:, qd:qd + kvd].reshape(length, z["hkv"], z["d"])
                v = y[:, qd + kvd:].reshape(length, z["hkv"], z["d"])
                if what == "full":
                    kv = (k, v)
            else:
                k, v = kv
            out = diff_attention(p, mx, i, q, k, v,
                                 window if what == "swa" else causal,
                                 z, m, by_pair)
        h = x + out
        ug = m.mm(layer_norm(h, p[n + "post_attention_layernorm.weight"],
                             p[n + "post_attention_layernorm.bias"],
                             z["eps"]), p[n + "fc1"])
        w = ug.shape[-1] // 2
        x = h + m.mm(ug[:, :w] * jax.nn.silu(ug[:, w:]), p[n + "fc2"])
    return x


def logits_of(p, y, cfg, round_to=None):
    z, m = _sizes(cfg), _Math(round_to)
    return m.mm(layer_norm(y, p["final_layernorm.weight"],
                           p["final_layernorm.bias"], z["eps"]),
                jnp.asarray(p["embed_tokens"], F32).T)


def sequence_logits(p, tokens, n_valid, cfg, scan="loop", round_to=None):
    """[L] tokens -> [L, V] float32: logits at position t predict the
    token at t + 1."""
    with jax.default_matmul_precision("highest"):
        return logits_of(p, hidden_states(p, tokens, n_valid, cfg, scan,
                                          round_to), cfg, round_to)


def token_margins(params, tokens, n_valid, memory, cfg):
    """For a batch of requests ([B, L] tokens = prompt + generated, padded;
    [B] valid lengths; `memory` is what the harness's drivers pass every
    serving reference and is not read: this decoder has none): at every
    position t, how far the reference logit of the token that FOLLOWS lies
    under the position's largest, in standard deviations of that
    position's logits, and whether it is the argmax. One request at a
    time, attention a pair of heads at a time, logits `block` positions at
    a time."""
    block = 256
    round_to = cfg.get("check", {}).get("reference_round_to")
    length = tokens.shape[1]
    pad = -length % block
    with jax.default_matmul_precision("highest"):
        def one(args):
            toks, n = args
            y = hidden_states(params, toks, n, cfg, "lax", round_to, True)
            nxt = jnp.roll(toks, -1)
            yb = jnp.pad(y, ((0, pad), (0, 0))).reshape(-1, block,
                                                        y.shape[-1])
            nb = jnp.pad(nxt, (0, pad)).reshape(-1, block)

            def rows(args):
                yy, nn = args
                lg = logits_of(params, yy, cfg, round_to)
                chosen = jnp.take_along_axis(lg, nn[:, None], 1)[:, 0]
                return ((lg.max(-1) - chosen) / lg.std(-1),
                        lg.argmax(-1) == nn)

            short, top = jax.lax.map(rows, (yb, nb))
            return short.reshape(-1)[:length], top.reshape(-1)[:length]

        # a Python loop over the requests, not lax.map: inside a loop the
        # widening of every weight is loop-invariant, and hoisted out of
        # it all 15.4 GB of float32 weights would stand at once
        outs = [one((tokens[i], n_valid[i]))
                for i in range(tokens.shape[0])]
        return (jnp.stack([o[0] for o in outs]),
                jnp.stack([o[1] for o in outs]))
