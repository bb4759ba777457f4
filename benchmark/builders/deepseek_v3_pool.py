"""config -> DeepseekV3ForCausalLM and the paged ServingEngine over it: the
same engine, server, scheduler and page allocator as `paged_pool` and
`phi4_flash_pool`, with the model served whole (this chip's share of it)
and requests that carry no memory. Every engine option the file does not
name stays at its default (for this model: prefix cache off).

The import below is at the top on purpose: a checkout without the model
fails here at once, with an ImportError, before any device is touched."""
from __future__ import annotations

from paddle_tpu.text.models import (DeepseekV3Config,           # noqa: F401
                                    DeepseekV3ForCausalLM)

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
    "n_shared_experts", "n_routed_experts", "routed_scaling_factor",
    "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
    "qk_nope_head_dim", "n_group", "topk_group", "num_experts_per_tok",
    "first_k_dense_replace", "norm_topk_prob", "scoring_func",
    "topk_method", "moe_layer_freq", "hidden_act", "rms_norm_eps",
    "rope_theta", "rope_scaling", "attention_bias", "tie_word_embeddings",
    "num_nextn_predict_layers", "experts_held")


def model_config(cfg):
    a = cfg["assumed"]
    return DeepseekV3Config(
        **{k: cfg[k] for k in MODEL_KEYS},
        latent_row_pad=a["latent_row_pad"], dtype=a["dtype"])


def build(cfg, seed, devices, callbacks=()):
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine

    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = DeepseekV3ForCausalLM(model_config(cfg))
    model.eval()
    pool = {k: v for k, v in cfg["pool"].items()
            if k in ("paged", "num_slots", "max_len", "page_size")}
    return ServingEngine(model, callbacks=callbacks, **pool)


def pool_health(engine):
    """`paged_pool.pool_health`'s checks on the stopped pool (nothing
    failed, retried or fell back; every program traced once; no page
    leaked), and this model's own: every request admitted took a slot by
    a prefill, the experts' loops reached every held token-slot
    (`dropped_slots` 0 over the whole run), and steps went ahead of the
    last one's tokens."""
    snap = engine.metrics.snapshot()
    err = snap["errors"]
    traces = {str(k): v for k, v in engine.trace_counts.items()}
    engine._alloc.check()
    free, total = int(engine._alloc.pages_free), int(engine._alloc.n_pages)
    cache, experts = snap.get("cache", {}), snap.get("experts", {})
    ok = ((err["count"], err["retries"], err["fallbacks"]) == (0, 0, 0)
          and bool(traces) and all(v == 1 for v in traces.values())
          and free == total
          and cache.get("state_resets") == snap["joins"]
          == engine.prefill_count
          and experts.get("dropped_slots") == 0
          and experts.get("held_slots", 0) > 0)
    return ok, {"errors": err["count"], "retries": err["retries"],
                "fallbacks": err["fallbacks"], "last_error": err["last"],
                "programs_traced_once": traces, "pages_free": free,
                "n_pages": total, "joins": snap["joins"],
                "cache": cache, "experts": experts}
