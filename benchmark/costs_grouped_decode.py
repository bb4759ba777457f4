"""Operations and bytes of ONE call of the `paged_grouped_decode` kernel: a
reading layer's differential attention of a Phi-4-mini-flash decode step
over the slots' written key and value rows of block 17, counted from the
configuration's shapes and the work asked for, not from what an
implementation moves: the yardstick of `grouped_decode_roofline.phi`.

Counting rules as benchmark/costs.py: a multiply-add is 2; softmax counts
0. At the window's mean occupied slots and mean written rows a slot:

- operations: the two softmaxes of every pair over a written row, as
  `costs_phi4_flash` counts them for `mfu.phi` (`_attend`): scores 2 Hq d,
  values 2 Hq (2 d), the values of a pair being 2 d wide. The zeros of
  the block-diagonal queries are not work asked for.
- bytes: the written rows, rounded up to whole pages, a K and a V row
  each as the pool stores them (`costs_phi4_flash.kv_row_bytes`: Hkv d
  bfloat16 values each; a page is read whole); the queries [Hq, d] in and
  the heads' outputs [Hq, 2 d] out, bfloat16, a slot.
"""
from __future__ import annotations

import math

from benchmark import costs_phi4_flash as C


def grouped_decode_call(cfg, asked):
    z = C._z(cfg)
    slots, context = asked["slots"], asked["context"]
    page = cfg["pool"]["page_size"]
    ops = slots * C._attend(z, context)
    rows = slots * math.ceil(context / page) * page
    nbytes = (rows * C.kv_row_bytes(cfg)
              + slots * z["hq"] * (z["d"] + 2 * z["d"]) * C.BF16)
    return ops, nbytes
