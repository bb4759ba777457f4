"""Share, in %, of the chip's bf16 peak that the window's REQUIRED
operations make: generated tokens x the operations one needs at the
window's mean context + prompt positions prefilled x theirs (both from the
configuration's cost file, named by `costs`), over window seconds x the
peak. An end-to-end utilization of the whole serving loop (joins and steps,
host gaps included), not a kernel's roofline share. None on the CPU
rehearsal and where the program has no `cache` counters."""
import importlib

from benchmark.readers import serve_facts


def read(facts, costs):
    means = serve_facts.window_means(facts)
    prompt = serve_facts.delta(facts, "cache.prefill_tokens")
    joins = serve_facts.delta(facts, "cache.state_resets")
    if "peaks" not in facts or means is None or prompt is None \
            or not joins:
        return None
    cost = importlib.import_module("benchmark." + costs)
    cfg = facts["config"]
    _, context = means
    generated = facts["end_to_end"]["serve_tokens_per_s"] * facts["window_s"]
    flops = generated * cost.decode_flops_per_token(cfg, context) \
        + prompt * cost.prefill_flops_per_token(cfg, prompt / joins)
    return 100.0 * flops / (facts["window_s"]
                            * facts["peaks"]["bf16_flops_per_s"])
