"""config -> Phi4FlashForCausalLM and the paged ServingEngine over it: the
same engine, server, scheduler and page allocator as `paged_pool`, with the
model served whole and requests that carry no memory. Every engine option
the file does not name stays at its default (for this model: prefix cache
off, and nothing that would snapshot or replay a state).

The import below is at the top on purpose: a checkout without the model
fails here at once, with an ImportError, before any device is touched."""
from __future__ import annotations

from paddle_tpu.text.models import (Phi4FlashConfig,            # noqa: F401
                                    Phi4FlashForCausalLM)


def model_config(cfg):
    a = cfg["assumed"]
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "sliding_window", "mb_per_layer",
            "layer_norm_eps", "hidden_act", "tie_word_embeddings",
            "mlp_bias", "lm_head_bias")
    return Phi4FlashConfig(
        **{k: cfg[k] for k in keys}, head_dim=a["head_dim"],
        d_state=a["d_state"], d_conv=a["d_conv"], expand=a["expand"],
        dt_rank=a["dt_rank"], dtype=a["dtype"])


def build(cfg, seed, devices, callbacks=()):
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine

    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = Phi4FlashForCausalLM(model_config(cfg))
    model.eval()
    pool = {k: v for k, v in cfg["pool"].items()
            if k in ("paged", "num_slots", "max_len", "page_size")}
    return ServingEngine(model, callbacks=callbacks, **pool)


def pool_health(engine):
    """`paged_pool.pool_health`'s checks on the stopped pool (nothing
    failed, retried or fell back; every program traced once; no page
    leaked), and this model's own: every request admitted took a slot
    whose state was written whole (`state_resets` equals the joins), and
    no join went another way than the prefill."""
    snap = engine.metrics.snapshot()
    err = snap["errors"]
    traces = {str(k): v for k, v in engine.trace_counts.items()}
    engine._alloc.check()
    free, total = int(engine._alloc.pages_free), int(engine._alloc.n_pages)
    cache = snap.get("cache", {})
    ok = ((err["count"], err["retries"], err["fallbacks"]) == (0, 0, 0)
          and bool(traces) and all(v == 1 for v in traces.values())
          and free == total
          and cache.get("state_resets") == snap["joins"]
          == engine.prefill_count)
    return ok, {"errors": err["count"], "retries": err["retries"],
                "fallbacks": err["fallbacks"], "last_error": err["last"],
                "programs_traced_once": traces, "pages_free": free,
                "n_pages": total, "joins": snap["joins"],
                "state_resets": cache.get("state_resets"),
                "cache": cache}
