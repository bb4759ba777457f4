"""config -> SpmdTrainer over NemotronHForCausalLM, one chip's share.

Reads the configuration's keys under the source's names, plus
`router_experts` (the router's published width; `n_routed_experts` is how
many are held here) and `experts_held` = [first, count], and `trainer`
(lr, warmup_steps, weight_decay, compute_dtype, balance). The trainer
recomputes block by block (`remat=True`), adds no auxiliary loss, decays
matrices only, and starts from a balanced router (`balance_routing`,
through the trainer's public `fm`, `cast_params` and `set_buffer`).
Leaves nothing in `run.facts`: the driver does."""
from __future__ import annotations

MODEL_KEYS = (
    "vocab_size", "hidden_size", "hybrid_override_pattern",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "time_step_min", "time_step_max",
    "time_step_floor", "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob", "mlp_hidden_act", "layer_norm_epsilon")


def next_token_ce(logits, labels):
    """Mean cross-entropy over every position, float32; the labels are the
    ids shifted by one by the job."""
    import jax
    import jax.numpy as jnp

    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.take_along_axis(lp, labels[..., None], -1).mean()


def decays(path):
    """Weight decay on matrices only: `path` is a parameter's name."""
    return path.rstrip("']").endswith(("weight", "weight_in", "weight_out")) \
        and "norm" not in path and "conv1d" not in path


def warm_up(peak, steps):
    """The learning rate of update number `count` (from 0): linear from
    peak / steps to `peak` over `steps` updates, then `peak`."""
    def rate(count):
        import jax.numpy as jnp

        return jnp.float32(peak) * jnp.minimum(
            (count.astype(jnp.float32) + 1.0) / jnp.float32(steps),
            jnp.float32(1.0))

    return rate


def model_config(cfg):
    from paddle_tpu.text import NemotronHConfig

    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    first, count = cfg["experts_held"]
    if count != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts is the number of experts held")
    return NemotronHConfig(
        n_routed_experts=cfg["router_experts"],
        experts_held=(first, count), **{k: cfg[k] for k in MODEL_KEYS})


def build(cfg, seed, devices):
    import paddle_tpu as paddle
    from paddle_tpu.optimizer import functional as fopt
    from paddle_tpu.parallel import SpmdTrainer, init_mesh
    from paddle_tpu.text import NemotronHForCausalLM

    t = cfg["trainer"]
    paddle.seed(int(seed) & 0x7FFFFFFF)
    net = NemotronHForCausalLM(model_config(cfg))
    mesh = init_mesh(dp=1, devices=list(devices[:1]))
    tx = fopt.adamw(warm_up(t["lr"], t["warmup_steps"]),
                    weight_decay=t["weight_decay"], decay_mask=decays)
    tr = SpmdTrainer(net, next_token_ce, tx, mesh=mesh, remat=True,
                     compute_dtype=t["compute_dtype"], moe_aux_weight=0.0)
    balance_routing(tr, cfg, seed)
    return tr


def balance_bias(bias, loads, rate):
    """One step of the correction bias toward equal load, the published
    aux-loss-free balancing in proportional form: an expert that drew more
    than the mean of `loads` has its bias lowered by `rate` x log(load /
    mean), one that drew less raised. Numpy in, numpy out; only the choice
    of experts moves, never their weights."""
    import numpy as np

    loads = np.asarray(loads, np.float64) + 1.0
    return (np.asarray(bias, np.float64)
            - rate * np.log(loads / loads.mean())).astype(np.float32)


def balance_routing(tr, cfg, seed):
    """Steer every expert layer's correction bias until its experts draw
    equal loads, as the published recipe's aux-loss-free balancing leaves a
    checkpoint: `trainer.balance.passes` forward passes, each on a new
    seeded batch of the check's shape, each followed by one `balance_bias`
    step at `trainer.balance.rate`. A random router is far from balanced
    (its fullest expert draws two to four times the mean, and the share of
    slots that falls on the held experts swings by a tenth between seeds,
    which the step time follows); a job that continues pre-training starts
    balanced. Says what it reached."""
    import jax
    import numpy as np

    from benchmark.traffic_gen import BatchMaker
    from benchmark.util import say

    plan = cfg["trainer"]["balance"]
    b, s = cfg["check"]["eval_batch"]
    batches = BatchMaker({"batch": b, "seq_len": s}, int(seed) + 2,
                         cfg["vocab_size"], 2)
    with tr.mesh.mesh:
        forward = jax.jit(lambda p, bufs, ids: tr.fm.apply(
            tr.cast_params(p), bufs, None, ids, training=False)[1])
    loads = {}
    for _ in range(int(plan["passes"])):
        after = forward(tr.params, tr.buffers,
                        tr.shard_batch(batches.next()[0])[0])
        for name, load in after.items():
            if not name.endswith("expert_load_val"):
                continue
            bias = name.replace("expert_load_val",
                                "gate.e_score_correction_bias")
            loads[name] = np.asarray(load)
            tr.set_buffer(bias, balance_bias(
                np.asarray(tr.buffers[bias]), loads[name], plan["rate"]))
    say(routing_balanced={
        n.rsplit(".", 1)[0]: round(float(v.max() / v.mean()), 3)
        for n, v in sorted(loads.items())},
        what="fullest expert over the mean, all experts, last pass",
        passes=plan["passes"], rate=plan["rate"])
