"""Model FLOP/s utilization of a training cell: the operations forward and
backward passes REQUIRE per token (benchmark/costs.py, from the
configuration's shapes, no recomputation) x tokens/s of the window / (chips
x the chip's bf16 peak from peaks.json). An end-to-end utilization, not a
kernel's roofline share."""
from benchmark import costs


def read(facts):
    if "peaks" not in facts:
        return None      # the CPU rehearsal has no peak to compare with
    per_token = costs.ernie_train_flops_per_token(
        facts["config"], facts["traffic"]["seq_len"])
    return 100.0 * per_token * facts["tokens_per_s"] / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])
