"""config + mesh layout -> SpmdTrainer over ErnieForSequenceClassification
on a mesh of several chips: `ernie_trainer`'s model, loss and optimizer
(that file fixes one chip), laid out over `init_mesh(dp=, tp=)` with
`COMMON_TP_RULES`, as `chip_smoke.py --four-chips` proved in PR 21.

Reads the configuration as `ernie_trainer` does and `layout` = {"dp": n,
"tp": m} from the traffic mix that names this builder. Leaves nothing in
`run.facts`."""
from __future__ import annotations

from benchmark.builders.ernie_trainer import softmax_ce


def build(cfg, seed, devices, layout):
    import paddle_tpu as paddle
    from paddle_tpu.optimizer import functional as fopt
    from paddle_tpu.parallel import COMMON_TP_RULES, SpmdTrainer, init_mesh
    from paddle_tpu.text import ErnieConfig, ErnieForSequenceClassification

    if cfg["hidden_act"] != "gelu":
        raise ValueError("ErnieModel's feed-forward is gelu")
    t = cfg["trainer"]
    dp, tp = int(layout["dp"]), int(layout["tp"])
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
    mesh = init_mesh(dp=dp, tp=tp, devices=list(devices[:dp * tp]))
    return SpmdTrainer(net, softmax_ce, fopt.adamw(t["lr"]), mesh=mesh,
                       rules=COMMON_TP_RULES,
                       compute_dtype=t["compute_dtype"])
