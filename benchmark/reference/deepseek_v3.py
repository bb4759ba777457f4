"""Plain reference of DeepSeek-V3's forward pass (`deepseek_v3`;
arXiv:2412.19437, the layer equations of its published modeling code): the
equations in straightforward jax.numpy, float32, matmul precision
"highest". A full forward over the whole sequence of ONE request: no
kernel, cache, page, absorbed product or batching, and nothing imported
from paddle_tpu.

For a token's vector u at position t, block l:

    x = u + Attn(RMSNorm(u));  y = x + FFN_l(RMSNorm'(x))
    logits = RMSNorm_f(y_last) W_head          (untied head)

- Attn, every block: c_q = RMSNorm(a W_qa); a head's [q_nope | q_pe] =
  c_q W_qb; [c | k_pe] = a W_kva; c_kv = RMSNorm(c); a head's [k_nope | v]
  = c_kv W_kvb; q_pe and k_pe rotated (k_pe once, shared by the heads);
  s = (q_nope . k_nope + q_pe . k_pe) scale over keys j <= t, softmax,
  o = concat_h(P v) W_o. No biases.
- Rotary with YaRN on the `qk_rope_head_dim` slice: f_i = theta^(-2i/d);
  the served frequency is f_i / factor blended with f_i by a linear ramp
  between the dimensions where `original_max_position_embeddings`
  positions make beta_fast and beta_slow rotations (floored, ceiled,
  clipped to [0, 1]); the pair (2i, 2i + 1) turns by t f_i'. scale =
  (nope + rope)^-0.5 x (0.1 mscale_all_dim ln factor + 1)^2.
- FFN_l, l < first_k_dense_replace: down(silu(gate a) * up a).
- FFN_l otherwise: s = sigmoid(a W_g^T) over all n_routed_experts; b = s +
  e_score_correction_bias; a group's score = the sum of its two largest
  b; the topk_group best groups stay and b elsewhere is 0; chosen =
  top-k(b); w = routed_scaling_factor s[chosen] / (sum s[chosen] +
  1e-20); y = sum over chosen AND held e of w_e down_e(silu(gate_e a) *
  up_e a) + shared(a).

Departures, each the configuration's own (`benchmark/configs/
deepseek_v3_ep16.json`): the experts this chip HOLDS (`experts_held`) give
their terms and the others' are left out, as in the program (the guide's
share of a deployment); the vocabulary is the slice; the correction bias
is read from `params` where a test hands it over and is zeros otherwise
(the harness hands the reference parameters only, and the builder leaves
the buffer at zeros); the multi-token prediction module is not built.

On the chip (`token_margins`) the reference takes the served weights'
values and widens each where it is used; attention goes head by head
(`by_head`: a scan that adds each head's part of the output projection),
the held experts one at a time, and the logits 256 positions at a time, so
that it fits beside the pool.

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


def rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * jnp.asarray(w, F32)


def yarn_frequencies(cfg):
    """[d / 2] served frequencies of the rotary slice (numpy-free)."""
    d, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    f = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    rs = cfg.get("rope_scaling")
    if not rs:
        return jnp.asarray(f, F32)
    orig = rs["original_max_position_embeddings"]

    def dim_of(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    out = []
    for i, fi in enumerate(f):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(fi / rs["factor"] * ramp + fi * (1.0 - ramp))
    return jnp.asarray(out, F32)


def rope_table(positions, cfg):
    """positions [L] -> (cos, sin) [L, d / 2]."""
    ang = jnp.asarray(positions, F32)[:, None] * yarn_frequencies(cfg)
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    """The pair (2i, 2i + 1) of x [..., d] turned by its angle; the
    layout stays interleaved (the program de-interleaves: dot products of
    two vectors rotated alike do not see the order)."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def softmax_scale(cfg):
    rs = cfg.get("rope_scaling") or {}
    m = 1.0
    if rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def attention(p, n, a, keep, cos, sin, cfg, m, by_head):
    """a [L, hidden] -> [L, hidden]; keep [L, L] bool (row t, key j)."""
    h, nope = int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"])
    rot, vd = int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
    rank, eps = int(cfg["kv_lora_rank"]), float(cfg["rms_norm_eps"])
    scale = softmax_scale(cfg)
    c_q = rms_norm(m.mm(a, p[n + "q_a"]), p[n + "q_a_norm"], eps)
    ckv = m.mm(a, p[n + "kv_a"])
    c_kv = rms_norm(ckv[:, :rank], p[n + "kv_a_norm"], eps)
    k_pe = rotate(ckv[:, rank:], cos, sin)

    def head(w_q, w_kv, w_o):
        q = m.mm(c_q, w_q)                                # [L, nope + rot]
        kv = m.mm(c_kv, w_kv)                             # [L, nope + vd]
        s = (m.mm(q[:, :nope], kv[:, :nope].T)
             + m.mm(rotate(q[:, nope:], cos, sin), k_pe.T)) * scale
        pr = jax.nn.softmax(jnp.where(keep, s, _NEG), -1)
        return m.mm(m.mm(pr, kv[:, nope:]), w_o)

    def per_head(w, width):           # [in, H width] -> [H, in, width]
        return jnp.moveaxis(w.reshape(w.shape[0], h, width), 1, 0)

    w_q = per_head(p[n + "q_b"], nope + rot)
    w_kv = per_head(p[n + "kv_b"], nope + vd)
    w_o = p[n + "o"].reshape(h, vd, -1)
    if by_head:
        out, _ = jax.lax.scan(
            lambda acc, w: (acc + head(*w), None),
            jnp.zeros(a.shape, F32), (w_q, w_kv, w_o))
        return out
    return sum(head(w_q[i], w_kv[i], w_o[i]) for i in range(h))


def gated(m, a, w_gate, w_up, w_down):
    return m.mm(jax.nn.silu(m.mm(a, w_gate)) * m.mm(a, w_up), w_down)


def route(p, n, a, cfg, m):
    """-> (chosen expert ids [L, k], weights [L, k])."""
    e, k = int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"])
    groups, keep_g = int(cfg["n_group"]), int(cfg["topk_group"])
    s = jax.nn.sigmoid(m.mm(a, jnp.asarray(p[n + "gate.weight"], F32).T))
    bias = p.get(n + "gate.e_score_correction_bias")
    b = s if bias is None else s + jnp.asarray(bias, F32)
    if groups > 1:
        g = b.reshape(-1, groups, e // groups)
        g_score = jax.lax.top_k(g, 2)[0].sum(-1)
        _, best = jax.lax.top_k(g_score, keep_g)
        kept = (best[..., None] == jnp.arange(groups)).any(1)
        b = jnp.where(kept[..., None], g, 0.0).reshape(-1, e)
    _, idx = jax.lax.top_k(b, k)
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, float(cfg["routed_scaling_factor"]) * chosen / (
        chosen.sum(-1, keepdims=True) + 1e-20)


def expert_layer(p, n, a, cfg, m):
    """Every token through every HELD expert, weighed by what the router
    gave it (0 where it was not chosen), plus the shared expert."""
    first, count = cfg["experts_held"]
    idx, w = route(p, n, a, cfg, m)

    def one(acc, per):
        w_gate, w_up, w_down, e = per
        mine = ((idx == e) * w).sum(-1, keepdims=True)          # [L, 1]
        return acc + mine * gated(m, a, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(one, jnp.zeros(a.shape, F32), (
        p[n + "experts.weight_gate"], p[n + "experts.weight_in"],
        p[n + "experts.weight_out"],
        first + jnp.arange(count, dtype=idx.dtype)))
    sh = n + "shared_experts."
    return y + gated(m, a, p[sh + "gate_proj.weight"],
                     p[sh + "up_proj.weight"], p[sh + "down_proj.weight"])


def hidden_states(p, tokens, n_valid, cfg, round_to=None, by_head=False):
    """[L] tokens (padded past n_valid) -> [L, hidden] before the final
    norm. Positions at or past n_valid come after every real one, so the
    causal mask alone keeps them out of what the real ones read."""
    m, eps = _Math(round_to), float(cfg["rms_norm_eps"])
    pos = jnp.arange(tokens.shape[0])
    keep = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
    cos, sin = rope_table(pos, cfg)
    x = jnp.asarray(p["embed_tokens"], F32)[tokens]
    for i in range(int(cfg["num_hidden_layers"])):
        n = f"layers.{i}."
        x = x + attention(
            p, n + "self_attn.",
            rms_norm(x, p[n + "input_layernorm.weight"], eps), keep, cos,
            sin, cfg, m, by_head)
        a = rms_norm(x, p[n + "post_attention_layernorm.weight"], eps)
        if i < int(cfg["first_k_dense_replace"]):
            x = x + gated(m, a, p[n + "gate_proj"], p[n + "up_proj"],
                          p[n + "down_proj"])
        else:
            x = x + expert_layer(p, n + "mlp.", a, cfg, m)
    return x


def logits_of(p, y, cfg, round_to=None):
    return _Math(round_to).mm(
        rms_norm(y, p["norm.weight"], float(cfg["rms_norm_eps"])),
        p["lm_head"])


def sequence_logits(p, tokens, n_valid, cfg, round_to=None):
    """[L] tokens -> [L, V] float32: logits at position t predict the
    token at t + 1."""
    with jax.default_matmul_precision("highest"):
        return logits_of(p, hidden_states(p, tokens, n_valid, cfg,
                                          round_to), cfg, round_to)


def token_margins(params, tokens, n_valid, memory, cfg):
    """For a batch of requests ([B, L] tokens = prompt + generated, padded;
    [B] valid lengths; `memory` is what the harness's drivers pass every
    serving reference and is not read: this decoder has none): at every
    position t, how far the reference logit of the token that FOLLOWS lies
    under the position's largest, in standard deviations of that
    position's logits, and whether it is the argmax. One request at a
    time, attention a head at a time, logits `block` positions at a
    time."""
    block = 256
    round_to = cfg.get("check", {}).get("reference_round_to")
    length = tokens.shape[1]
    pad = -length % block
    with jax.default_matmul_precision("highest"):
        def one(toks, n):
            y = hidden_states(params, toks, n, cfg, round_to, True)
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
        # it all the float32 weights would stand at once
        outs = [one(tokens[i], n_valid[i]) for i in range(tokens.shape[0])]
        return (jnp.stack([o[0] for o in outs]),
                jnp.stack([o[1] for o in outs]))
