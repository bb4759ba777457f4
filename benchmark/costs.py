"""Operations and bytes the algorithms REQUIRE, computed from shapes. These
are the yardstick of `mfu.train` and of the roofline metrics; a PR that
claims a gain cannot change them.

Counting rules: a multiply-add is 2 operations; an [m, k] x [k, n] matmul
is 2 m k n; element-wise work, softmax, layer norms and embedding lookups
count 0 (they are not what the peak measures); backward is twice forward
(gradients to inputs and to weights); nothing recomputed is counted."""
from __future__ import annotations


def ernie_train_flops_per_token(cfg, seq_len):
    """Forward + backward of one token of the ERNIE encoder at `seq_len`
    (full, unmasked attention: each token attends seq_len keys).

    Per layer, forward: Q, K, V and output projections 4 x 2 h^2; the
    feed-forward 2 x 2 h i; attention scores and weighted values 2 x 2 s h.
    The pooler and classifier run once per sequence and are left out
    (under 0.01 % at s = 128)."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    forward = cfg["num_hidden_layers"] * (8 * h * h + 4 * h * i
                                          + 4 * seq_len * h)
    return 3 * forward
