"""config -> SpmdTrainer over ErnieForSequenceClassification.

A copy of `chip_smoke.make_trainer` (proved on the chip in PR 21) that
takes its sizes from the configuration file instead of a table in code.
The original stays in chip_smoke.py as the bring-up check."""
from __future__ import annotations


def softmax_ce(logits, labels):
    import jax
    import jax.numpy as jnp

    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.take_along_axis(lp, labels[:, None], -1).mean()


def build(cfg, seed, devices):
    import paddle_tpu as paddle
    from paddle_tpu.optimizer import functional as fopt
    from paddle_tpu.parallel import SpmdTrainer, init_mesh
    from paddle_tpu.text import ErnieConfig, ErnieForSequenceClassification

    if cfg["hidden_act"] != "gelu":
        raise ValueError("ErnieModel's feed-forward is gelu")
    t = cfg["trainer"]
    paddle.seed(int(seed) & 0x7FFFFFFF)
    net = ErnieForSequenceClassification(ErnieConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        hidden_dropout=cfg["hidden_dropout_prob"],
        attn_dropout=cfg["attention_probs_dropout_prob"],
        num_classes=t["num_classes"]))
    mesh = init_mesh(dp=1, devices=list(devices[:1]))
    return SpmdTrainer(net, softmax_ce, fopt.adamw(t["lr"]), mesh=mesh,
                       compute_dtype=t["compute_dtype"])
