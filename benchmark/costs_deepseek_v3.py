"""Operations and bytes that serving this chip's share of DeepSeek-V3
REQUIRES, counted from the configuration's shapes and the work asked for
(live tokens, cached rows, experts held), not from what an implementation
moves: the yardstick of `mfu.dsv3` and `step_hbm_share.dsv3`.

Counting rules as benchmark/costs.py: a multiply-add is 2; an [m, k] x
[k, n] product is 2 m k n; element-wise work, softmax, norms, the rotary
turn and the embedding lookup count 0. Per token, forward only.

- latent attention, every block: W_qa 2 H r_q, W_qb 2 r_q Hq (d_n + d_r),
  W_kva 2 H (r_kv + d_r), W_o 2 Hq d_v H, and W_kvb once, 2 r_kv Hq (d_n +
  d_v): a join up-projects each position's keys and values with it, a
  decode step folds its two halves into the query and onto the output.
- attention over cached rows. Absorbed (a generated token): 2 Hq ((r_kv +
  d_r) + r_kv) a row, the score against [c_kv | k_pe] and the weighted
  sum of c_kv. Unabsorbed (a prompt position): 2 Hq ((d_n + d_r) + d_v) a
  key, over the (P + 1) / 2 keys a position of a P-long join reads on
  average.
- dense feed-forward (leading blocks): 3 x 2 H F.
- expert layer: router 2 H E; the shared expert 3 x 2 H F_e on every
  token; the routed experts HELD here, k x held / E of one a token on
  average (0.5 at 8 of 256 with 16 held): 3 x 2 H F_e each.
- head 2 H V over the vocabulary slice, a generated token.

A prompt position goes through every block and not the head; the one
position a join takes through the head counts as a generated token.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _z(cfg):
    first, held = cfg["experts_held"]
    dense = cfg["first_k_dense_replace"]
    return dict(
        h=cfg["hidden_size"], f=cfg["intermediate_size"],
        fe=cfg["moe_intermediate_size"], v=cfg["vocab_size"],
        hq=cfg["num_attention_heads"], rq=cfg["q_lora_rank"],
        rkv=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        e=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        held=held, shared=cfg["n_shared_experts"],
        layers=cfg["num_hidden_layers"], dense=dense,
        moe=cfg["num_hidden_layers"] - dense,
        pad=cfg["assumed"]["latent_row_pad"])


def _attn_params(z):
    """The seven attention matrices of a block (norms apart)."""
    return (z["h"] * z["rq"] + z["rq"] * z["hq"] * (z["dn"] + z["dr"])
            + z["h"] * (z["rkv"] + z["dr"])
            + z["rkv"] * z["hq"] * (z["dn"] + z["dv"])
            + z["hq"] * z["dv"] * z["h"])


def _ffn_flops(z):
    """Feed-forward operations of all blocks, one token."""
    routed = z["k"] * z["held"] / z["e"]
    return (z["dense"] * 3 * 2 * z["h"] * z["f"]
            + z["moe"] * (2 * z["h"] * z["e"]
                          + (z["shared"] + routed) * 3 * 2 * z["h"]
                          * z["fe"]))


def decode_flops_per_token(cfg, context):
    """One generated token at `context` positions written (itself
    included): absorbed attention over the slot's latent rows."""
    z = _z(cfg)
    rows = 2 * z["hq"] * ((z["rkv"] + z["dr"]) + z["rkv"]) * context
    return (z["layers"] * (2 * _attn_params(z) + rows) + _ffn_flops(z)
            + 2 * z["h"] * z["v"])


def prefill_flops_per_token(cfg, prompt_len):
    """One prompt position of a join of `prompt_len` positions:
    unabsorbed, causal."""
    z = _z(cfg)
    keys = (prompt_len + 1) / 2
    rows = 2 * z["hq"] * ((z["dn"] + z["dr"]) + z["dv"]) * keys
    return z["layers"] * (2 * _attn_params(z) + rows) + _ffn_flops(z)


def weight_bytes(cfg):
    """Every parameter once, as held: bfloat16 but for the routers
    (float32)."""
    z = _z(cfg)
    h = z["h"]
    attn = _attn_params(z) + z["rq"] + z["rkv"]
    block = attn + 2 * h
    dense = 3 * h * z["f"]
    moe = (z["held"] + z["shared"]) * 3 * h * z["fe"]
    return (BF16 * (z["layers"] * block + z["dense"] * dense
                    + z["moe"] * moe + 2 * z["v"] * h + h)
            + F32 * z["moe"] * z["e"] * h)


def kv_row_bytes(cfg):
    """One position's latent row of one layer as the pool stores it:
    [c_kv | k_pe] and the zeros that close it to a lane multiple."""
    z = _z(cfg)
    return (z["rkv"] + z["dr"] + z["pad"]) * BF16


def step_bytes(cfg, slots, context):
    """What one decode step over `slots` occupied slots at a mean of
    `context` written positions must read and write: every held matrix
    once (the held experts' too: at 2 token-slots an expert nearly all
    are chosen each step), the embedding's `slots` rows and not its
    table, and in each layer the slots' written latent rows once and one
    row written (no chip keeps them between layers: 64 slots x 3.7k rows
    are 0.3 GB a layer)."""
    z = _z(cfg)
    row = kv_row_bytes(cfg)
    return (weight_bytes(cfg) - BF16 * z["v"] * z["h"]
            + slots * BF16 * z["h"]
            + slots * z["layers"] * (context * row + row))
