"""Operations and bytes of ONE call of the `paged_latent_decode` kernel: a
layer's absorbed attention of a decode step over the slots' written latent
rows, counted from the configuration's shapes and the work asked for, not
from what an implementation moves: the yardstick of
`latent_decode_roofline.dsv3`.

Counting rules as benchmark/costs.py: a multiply-add is 2; softmax counts
0. At the window's mean occupied slots and mean written rows a slot:

- operations: 2 Hq ((r_kv + d_r) + r_kv) a written row: the score against
  [c_kv | k_pe] and the weighted sum of c_kv, for every query head
  (`costs_deepseek_v3.decode_flops_per_token`'s attention term, one layer).
- bytes: the written rows, rounded up to whole pages, as the pool stores
  them (`costs_deepseek_v3.kv_row_bytes`, the row's closing zeros
  included: a page is read whole); the absorbed query [Hq, r_kv + d_r] in
  and the normalised sums [Hq, r_kv] out, bfloat16, a slot.
"""
from __future__ import annotations

import math

from benchmark import costs_deepseek_v3 as C


def latent_decode_call(cfg, asked):
    z = C._z(cfg)
    slots, context = asked["slots"], asked["context"]
    page = cfg["pool"]["page_size"]
    hq, rkv, dr = z["hq"], z["rkv"], z["dr"]
    ops = slots * context * 2 * hq * ((rkv + dr) + rkv)
    rows = slots * math.ceil(context / page) * page
    nbytes = (rows * C.kv_row_bytes(cfg)
              + slots * hq * ((rkv + dr) + rkv) * C.BF16)
    return ops, nbytes
