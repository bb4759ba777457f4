"""Model FLOP/s utilization of a training cell whose driver left the
operations a trained token REQUIRES in `facts["train_flops_per_token"]`
(computed by the configuration's own cost file from its shapes, forward
and backward, no recomputation): that x tokens/s of the window / (chips x
the chip's bf16 peak from peaks.json). An end-to-end utilization, not a
kernel's roofline share. None on the CPU rehearsal (no peak to compare
with) and where the driver left no count."""


def read(facts):
    if "peaks" not in facts or "train_flops_per_token" not in facts:
        return None
    return 100.0 * facts["train_flops_per_token"] * facts["tokens_per_s"] / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])
