"""Operations the NemotronH share's forward and backward passes REQUIRE
per trained token, counted from the configuration's shapes and not from
the implementation: the yardstick of `mfu.nemo`.

Reads the configuration's keys under the source's names, `router_experts`
(the router's width) and `n_routed_experts` (the experts held here);
leaves nothing anywhere. Counting rules as benchmark/costs.py: a
multiply-add is 2; an [m, k] x [k, n] product is 2 m k n; element-wise
work, softmax, norms, the convolution, the gather and the embedding lookup
count 0; backward is twice forward; nothing recomputed counts.

Forward, per token:

- M: in_proj 2 H (2 d_inner + 2 g n + h) + out_proj 2 d_inner H; the scan
  in its chunked form at chunk L, per position: the scores C B^T
  2 L g n, the scores times x 2 L d_inner, the chunk's state 2 d_inner n,
  the entering state's part of y 2 d_inner n. The [L, L] products are
  counted whole, not their causal half: a chunk's products are not split.
- *: q, k, v, o projections 2 H (2 hq d + 2 hkv d); causal attention
  2 x 2 hq d (s + 1) / 2 (each position attends its prefix: half the
  square).
- E: router 2 H E; shared expert 2 x 2 H F_s; routed experts
  top_k x (held / E) x 2 x 2 H F in expectation under uniform routing
  (the share of token-slots that fall on held experts; the counters report
  what the run really routed).
- head 2 H V; the embedding lookup 0.
"""
from __future__ import annotations


def forward_flops_per_token(cfg, seq_len):
    """{kind of layer or "head": operations of ONE such layer per token}."""
    hid = cfg["hidden_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, chunk = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    d_inner = h * p
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    routed_share = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                    / cfg["router_experts"])
    return {
        "M": (2 * hid * (2 * d_inner + 2 * g * n + h) + 2 * d_inner * hid
              + 2 * chunk * n * g + 2 * chunk * d_inner
              + 4 * d_inner * n),
        "*": (2 * hid * (2 * hq * d + 2 * hkv * d)
              + 4 * hq * d * (seq_len + 1) / 2),
        "E": (2 * hid * cfg["router_experts"]
              + 4 * hid * cfg["moe_shared_expert_intermediate_size"]
              + routed_share * 4 * hid * cfg["moe_intermediate_size"]),
        "head": 2 * hid * cfg["vocab_size"],
    }


def train_flops_per_token(cfg, seq_len):
    """Forward + backward (twice forward) of one token through the
    pattern's layers and the head."""
    per = forward_flops_per_token(cfg, seq_len)
    pattern = cfg["hybrid_override_pattern"]
    return 3 * (sum(per[kind] for kind in pattern) + per["head"])
