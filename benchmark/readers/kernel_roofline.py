"""A kernel's share, in %, of its roofline: the least time the chip's peaks
allow one call (the larger of operations / bf16 peak and bytes / bandwidth,
both from the cost file's `call` function at what the window's counters say
a call was asked for) over the device time one call took (`kernel_time`).
None where no operation has the kernel's name, there is no device trace, or
the window's counters are missing."""
import importlib

from benchmark.readers import kernel_time, serve_facts


def read(facts, kernel, costs, call, program=None):
    means = serve_facts.window_means(facts)
    prompt = serve_facts.delta(facts, "cache.prefill_tokens")
    joins = serve_facts.delta(facts, "cache.state_resets")
    if "peaks" not in facts or means is None or not prompt or not joins:
        return None
    ms = kernel_time.read(facts, kernel, program)
    if not ms:
        return None
    asked = {"slots": means[0], "context": means[1],
             "prompt_len": prompt / joins}
    ops, nbytes = getattr(importlib.import_module("benchmark." + costs),
                          call)(facts["config"], asked)
    floor_s = max(ops / facts["peaks"]["bf16_flops_per_s"],
                  nbytes / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor_s / (ms * 1e-3)
