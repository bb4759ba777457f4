"""config -> (decoder, embed, project) and the paged ServingEngine over it.

A copy of `chip_smoke.make_stack` (proved on the chip in PR 21) that takes
its sizes from the configuration file. Every engine option the file does
not name stays at its default: FIFO scheduler, no speculation, LoRA, int8
or chunked prefill, prefix cache on."""
from __future__ import annotations


def build_stack(cfg, seed):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)

    paddle.seed(int(seed) & 0x7FFFFFFF)
    layer = TransformerDecoderLayer(
        cfg["d_model"], cfg["decoder_attention_heads"],
        cfg["decoder_ffn_dim"], dropout=cfg["dropout"],
        activation=cfg["activation_function"],
        normalize_before=cfg["normalize_before"])
    dec = TransformerDecoder(layer, cfg["decoder_layers"])
    dec.eval()
    return (dec, nn.Embedding(cfg["vocab_size"], cfg["d_model"]),
            nn.Linear(cfg["d_model"], cfg["vocab_size"]))


def build(cfg, seed, devices, callbacks=()):
    from paddle_tpu.serving import ServingEngine

    pool = {k: v for k, v in cfg["pool"].items()
            if k in ("paged", "num_slots", "max_len", "page_size")}
    return ServingEngine(*build_stack(cfg, seed), callbacks=callbacks,
                         **pool)


def pool_health(engine):
    """The no-hidden-failure checks on a stopped paged pool, as
    chip_smoke.pool_health: nothing failed, retried or fell back, every
    program was traced once, and no page leaked."""
    snap = engine.metrics.snapshot()
    err = snap["errors"]
    traces = {str(k): v for k, v in engine.trace_counts.items()}
    engine.flush_prefix_cache()
    engine._alloc.check()
    free, total = int(engine._alloc.pages_free), int(engine._alloc.n_pages)
    ok = ((err["count"], err["retries"], err["fallbacks"]) == (0, 0, 0)
          and bool(traces) and all(v == 1 for v in traces.values())
          and free == total)
    return ok, {"errors": err["count"], "retries": err["retries"],
                "fallbacks": err["fallbacks"], "last_error": err["last"],
                "programs_traced_once": traces, "pages_free": free,
                "n_pages": total}
