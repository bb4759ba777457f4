"""Operations and bytes that serving Phi-4-mini-flash-reasoning REQUIRES,
counted from the configuration's shapes and the work asked for (live
tokens, heads, widths), not from what an implementation moves: the
yardstick of `mfu.phi` and `step_hbm_share.phi`.

Counting rules as benchmark/costs.py: a multiply-add is 2; an [m, k] x
[k, n] product is 2 m k n; element-wise work, softmax, norms, the
convolution, the scan's per-position update (element-wise over [d_inner,
n]) and the embedding lookup count 0. Per token, forward only.

- every block: feed-forward 2 H 2F + 2 F H.
- Mamba: in_proj 2 H 2 d_i, x_proj 2 d_i (r + 2 n), dt_proj 2 r d_i,
  out_proj 2 d_i H; the scan's read-out H_t C_t 2 d_i n.
- window / full attention: Wqkv 2 H (Hq + 2 Hkv) d, output 2 (Hq d) H;
  the two softmaxes of a pair: scores 2 Hq d k, values 2 Hq (2 d) k over
  the k keys the position reads (its window, or its whole prefix).
- memory unit: 2 H d_i + 2 d_i H.
- cross attention: Wq 2 H Hq d, output 2 (Hq d) H, attention as above.
- head 2 H V.

A generated token goes through every block. A prompt token goes through
blocks 0 .. 17's state-leaving part only: the 9 Mamba and 8 window blocks
whole, block 17's K and V projections (2 H 2 Hkv d) and nothing after; the
one position a join takes through the rest counts as a generated token.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _z(cfg):
    a = cfg["assumed"]
    h = cfg["hidden_size"]
    return dict(h=h, f=cfg["intermediate_size"], v=cfg["vocab_size"],
                hq=cfg["num_attention_heads"],
                hkv=cfg["num_key_value_heads"], d=a["head_dim"],
                di=a["expand"] * h, n=a["d_state"], r=a["dt_rank"],
                k=a["d_conv"], w=cfg["sliding_window"],
                layers=cfg["num_hidden_layers"])


def counts(cfg):
    """Blocks by kind: (mamba, swa, full, gmu, xattn)."""
    half = cfg["num_hidden_layers"] // 2
    return dict(mamba=half // 2 + 1, swa=half // 2, full=1,
                gmu=half // 2 - 1, xattn=half // 2 - 1)


def _ffn(z):
    return 2 * z["h"] * 2 * z["f"] + 2 * z["f"] * z["h"]


def _mamba(z):
    return (2 * z["h"] * 2 * z["di"] + 2 * z["di"] * (z["r"] + 2 * z["n"])
            + 2 * z["r"] * z["di"] + 2 * z["di"] * z["h"]
            + 2 * z["di"] * z["n"])


def _attend(z, keys):
    return 2 * z["hq"] * z["d"] * keys + 2 * z["hq"] * 2 * z["d"] * keys


def _self_attn(z, keys):
    qd = z["hq"] * z["d"]
    return 2 * z["h"] * (qd + 2 * z["hkv"] * z["d"]) + 2 * qd * z["h"] \
        + _attend(z, keys)


def decode_flops_per_token(cfg, context):
    """One generated token at `context` positions written (itself
    included)."""
    z, c = _z(cfg), counts(cfg)
    qd = z["hq"] * z["d"]
    return (z["layers"] * _ffn(z) + c["mamba"] * _mamba(z)
            + c["swa"] * _self_attn(z, min(context, z["w"]))
            + _self_attn(z, context)
            + c["gmu"] * (2 * z["h"] * z["di"] + 2 * z["di"] * z["h"])
            + c["xattn"] * (2 * z["h"] * qd + 2 * qd * z["h"]
                            + _attend(z, context))
            + 2 * z["h"] * z["v"])


def prefill_flops_per_token(cfg, prompt_len):
    """One prompt position of a join of `prompt_len` positions: the
    blocks that leave state (window attention reads min(t + 1, w) keys:
    the mean over the prompt is taken)."""
    z, c = _z(cfg), counts(cfg)
    w = min(z["w"], prompt_len)
    mean_keys = (w * (w + 1) / 2 + (prompt_len - w) * w) / prompt_len
    return ((c["mamba"] + c["swa"]) * _ffn(z) + c["mamba"] * _mamba(z)
            + c["swa"] * _self_attn(z, mean_keys)
            + 2 * z["h"] * 2 * z["hkv"] * z["d"])


def weight_bytes(cfg):
    """Every parameter once, as held (bfloat16 but for A_log, D and the
    dt bias, lambda vectors: float32)."""
    z, c = _z(cfg), counts(cfg)
    h, di, qd, kvd = z["h"], z["di"], z["hq"] * z["d"], z["hkv"] * z["d"]
    norms = 2 * 2 * h
    mamba = BF16 * (h * 2 * di + di * z["k"] + di + di * (z["r"] + 2 * z["n"])
                    + z["r"] * di + di * h) + F32 * (di + di * z["n"] + di)
    lam = F32 * 4 * z["d"] + BF16 * 2 * z["d"]
    attn = BF16 * (h * (qd + 2 * kvd) + qd + 2 * kvd + qd * h + h) + lam
    cross = BF16 * (h * qd + qd + qd * h + h) + lam
    gmu = BF16 * (h * di + di * h)
    block = BF16 * (h * 2 * z["f"] + z["f"] * h + norms)
    return (z["layers"] * block + c["mamba"] * mamba
            + (c["swa"] + c["full"]) * attn + c["xattn"] * cross
            + c["gmu"] * gmu + BF16 * (z["v"] * h + 2 * h))


def kv_row_bytes(cfg):
    """One position's K and V rows of one layer."""
    z = _z(cfg)
    return 2 * z["hkv"] * z["d"] * BF16


def step_bytes(cfg, slots, context):
    """What one decode step over `slots` occupied slots at a mean of
    `context` written positions must read and write: the weights once;
    block 17's keys and values of every live position once for each of
    the `1 + xattn` layers that attend to them (no chip holds them
    between layers: 64 slots x 1.5k positions are 0.5 GB) and one row
    written; each window layer's ring read (min(context, w) rows) and one
    row written; each Mamba block's tail and state read and written."""
    z, c = _z(cfg), counts(cfg)
    row = kv_row_bytes(cfg)
    paged = slots * ((1 + c["xattn"]) * context * row + row)
    ring = slots * c["swa"] * (min(context, z["w"]) * row + row)
    state = slots * c["mamba"] * 2 * (
        (z["k"] - 1) * z["di"] * BF16 + z["di"] * z["n"] * F32)
    return weight_bytes(cfg) + paged + ring + state


def selective_scan_call(cfg, asked):
    """(operations, bytes) of ONE call of the `selective_scan` kernel: a
    Mamba block's scan over a join's prompt of `asked["prompt_len"]`
    positions (the window's mean; the bucket's padding is not work asked
    for). Operations: the read-out H_t C_t, 2 d_i n a position (the
    update is element-wise and counts 0, so the kernel is bound by what it
    moves, or by its own loop). Bytes: x in and y out in bfloat16, dt in
    float32, a channel and position; B and C rows."""
    z = _z(cfg)
    p = asked["prompt_len"]
    return (p * 2 * z["di"] * z["n"],
            p * (z["di"] * (BF16 + F32 + BF16) + 2 * z["n"] * BF16))
