"""NemotronH on the CPU at tiny widths, against the plain reference
(benchmark/reference/nemotron_h.py: float32, the recurrence position by
position, the experts as a dense masked loop, attention as the masked
softmax composition): each mixer and the whole model, forward and
gradients; the chunked scan at lengths that are and are not multiples of
the chunk; routing at its extremes with no slot dropped; the share test;
that a dropped term is seen. (That block-wise recomputation lowers the
step's scratch is tests/test_tpu_compile.py's: the CPU compiler drops the
barriers that keep a recomputation apart from the first computation.)"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.ops import ssm
from paddle_tpu.optimizer import functional as fopt
from paddle_tpu.parallel import SpmdTrainer, init_mesh
from paddle_tpu.text import NemotronHConfig, NemotronHForCausalLM

from benchmark.reference import nemotron_h as ref

CFG = dict(vars(NemotronHConfig.tiny()), experts_held=(2, 4))


def _cfg(**kw):
    return NemotronHConfig.tiny(experts_held=(2, 4), **kw)


def _state(layer, prefix=""):
    return {prefix + n: t._data for n, t in layer.state_dict().items()}


def _x(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


def _close(a, b, tol=2e-5):
    scale = float(jnp.abs(b).max()) + 1e-12
    assert float(jnp.abs(a - b).max()) <= tol * scale, (
        float(jnp.abs(a - b).max()), scale)


def _layer_fn(layer):
    """A pure function of (x, parameters by name) through the Layer."""
    from paddle_tpu.parallel import functionalize

    fm = functionalize(layer)

    def fn(x, params):
        return fm.apply(params, fm.buffers(), None, x, training=True)[0]

    return fn, fm.params()


@pytest.fixture(autouse=True)
def _highest_and_small_tiles(monkeypatch):
    """Full precision, and expert loops of several steps at tiny sizes."""
    monkeypatch.setattr(moe_ops, "TILE_ROWS", 8)
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------------------ chunked scan

@pytest.mark.parametrize("s", [32, 27, 5])
def test_chunked_scan_equals_the_recurrence(s):
    b, h, p, g, n = 2, 4, 8, 2, 16
    x = _x((b, s, h, p), 1)
    dt = jax.nn.softplus(_x((b, s, h), 2))
    a = -jnp.exp(_x((h,), 3))
    bm, cm, d = _x((b, s, g, n), 4), _x((b, s, g, n), 5), _x((h,), 6)
    args = (x, dt, a, bm, cm, d)

    def recurrence(x, dt, a, bm, cm, d):
        rep = h // g
        return ref.selective_scan(x, dt, a, jnp.repeat(bm, rep, 2),
                                  jnp.repeat(cm, rep, 2)) + x * d[:, None]

    _close(ssm.ssd_scan(*args, chunk=8), recurrence(*args))

    def sq(fn):
        return lambda *t: (fn(*t) ** 2).sum()

    got = jax.grad(sq(lambda *t: ssm.ssd_scan(*t, chunk=8)),
                   range(6))(*args)
    want = jax.grad(sq(recurrence), range(6))(*args)
    for u, v in zip(got, want):
        _close(u, v)


# ------------------------------------------------------------- the mixers

def _mixer_against_reference(layer, ref_fn, hidden=32, seq=20):
    fn, params = _layer_fn(layer)
    x = _x((2, seq, hidden), 7)

    def ref_of(x, params):
        return ref_fn({"m." + n: v for n, v in {
            **_state(layer), **params}.items()}, "m.", x, CFG)

    _close(fn(x, params), ref_of(x, params))
    got = jax.jit(jax.grad(lambda x, p: (fn(x, p) ** 2).sum(), (0, 1)))(
        x, params)
    want = jax.jit(jax.grad(lambda x, p: (ref_of(x, p) ** 2).sum(),
                            (0, 1)))(x, params)
    _close(got[0], want[0])
    for n in params:
        _close(got[1][n], want[1][n])


def test_mamba_mixer_equals_the_reference():
    paddle.seed(11)
    c = _cfg()
    _mixer_against_reference(nn.Mamba2Mixer(
        c.hidden_size, c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
        c.ssm_state_size, c.conv_kernel, c.chunk_size,
        c.layer_norm_epsilon), ref.mamba)


def _experts(held=(2, 4), num=8, k=3, shared=24, **kw):
    return nn.SparseMoELayer(32, 16, num, k, shared_d_ff=shared,
                             routed_scaling=2.5, experts_held=held, **kw)


def test_expert_layer_equals_the_reference():
    paddle.seed(12)
    _mixer_against_reference(_experts(), ref.experts)


def test_grouped_query_attention_equals_the_reference():
    paddle.seed(13)
    c = _cfg()
    _mixer_against_reference(nn.GroupedQueryAttention(
        c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
        c.head_dim), ref.attention)


# ------------------------------------------------------------ whole model

def _ce(logits, labels):
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.take_along_axis(lp, labels[..., None], -1).mean()


def _trainer(remat, seed=3, **kw):
    paddle.seed(seed)
    net = NemotronHForCausalLM(_cfg())
    mesh = init_mesh(dp=1, devices=jax.devices()[:1])
    return SpmdTrainer(net, _ce, fopt.adamw(1e-3), mesh=mesh, remat=remat,
                       moe_aux_weight=0.0, **kw)


def _batch(seq=20):
    ids = np.random.RandomState(0).randint(0, CFG["vocab_size"], (2, seq + 1))
    return ids[:, :-1], ids[:, 1:]


@pytest.mark.parametrize("remat", [False, True])
def test_model_loss_and_every_gradient_equal_the_reference(remat):
    tr = _trainer(remat)
    ids, labels = _batch()
    state = {**tr.params, **tr.buffers}
    _close(jnp.asarray(tr.eval_step((ids,))),
           ref.logits(state, jnp.asarray(ids), CFG))
    (loss, bufs), grads = jax.jit(jax.value_and_grad(
        tr._forward_loss, has_aux=True))(
            tr.params, tr.buffers, jax.random.PRNGKey(0),
            (jnp.asarray(ids),), jnp.asarray(labels))
    want = jax.jit(jax.value_and_grad(lambda p: ref.loss(
        {**p, **tr.buffers}, jnp.asarray(ids), jnp.asarray(labels), CFG)))(
            tr.params)
    assert float(loss) == pytest.approx(float(want[0]), rel=1e-6)
    assert set(grads) == set(tr.params)
    for n in grads:
        _close(grads[n], want[1][n])
    dropped = [float(v) for n, v in bufs.items()
               if n.endswith("dropped_slots_val")]
    assert dropped == [0.0, 0.0]
    # and it trains through the one path: SpmdTrainer.step
    first = float(tr.step((ids,), labels))
    for _ in range(6):
        last = float(tr.step((ids,), labels))
    assert last < first - 0.3


def test_float32_parameters_stay_float32_under_compute_dtype():
    tr = _trainer(True, compute_dtype="bfloat16")
    cast = tr.cast_params(tr.params)
    kept = {n for n, v in cast.items() if v.dtype == jnp.float32}
    assert kept == tr._keep_f32 and kept
    assert {n.rsplit(".", 1)[-1] for n in kept} == {
        "A_log", "D", "dt_bias", "weight"}
    assert all(n.endswith("gate.weight") for n in kept
               if n.endswith("weight"))
    ids, labels = _batch()
    assert np.isfinite(float(tr.step((ids,), labels)))


# ------------------------------------------------- routing at its extremes

def _steered(layer, chosen):
    """Make every token choose exactly `chosen`: the correction bias
    steers the choice and leaves the weights alone."""
    bias = np.full((layer.num_experts,), -10.0, np.float32)
    bias[list(chosen)] = 10.0
    layer.gate.e_score_correction_bias._data = jnp.asarray(bias)


@pytest.mark.parametrize("chosen,held_slots,max_over_mean", [
    ((4, 8, 9, 10, 11, 12), 1, 4.0),    # all tokens on ONE held expert
    ((8, 9, 10, 11, 12, 13), 0, 0.0),   # none on any held expert
    ((4, 5, 6, 7, 8, 9), 4, 1.0),       # every held expert, every token
])
def test_no_slot_is_dropped_whatever_the_routing(chosen, held_slots,
                                                 max_over_mean):
    paddle.seed(14)
    layer = _experts(held=(4, 4), num=16, k=6)
    _steered(layer, chosen)
    x = _x((2, 20, 32), 8)
    y = layer(paddle.to_tensor(np.asarray(x)))._data
    cfg = dict(CFG, num_experts_per_tok=6, experts_held=(4, 4))
    _close(y, ref.experts(_state(layer, "m."), "m.", x, cfg))
    count = {n: float(layer._buffers[n]._data) for n in layer.COUNTERS}
    assert count["dropped_slots_val"] == 0.0
    assert count["routed_slots_val"] == 40 * held_slots
    if held_slots:
        assert count["load_max_val"] / count["load_mean_val"] == \
            pytest.approx(max_over_mean)
    else:       # only the shared expert is left
        _close(y, ref.experts(_state(layer, "m."), "m.", x,
                              dict(cfg, experts_held=(4, 0))))


def test_sixteen_shares_and_the_shared_expert_once_give_the_uncut_layer():
    paddle.seed(15)
    whole = _experts(held=None, num=32, k=6)
    x = _x((2, 20, 32), 9)
    xt = paddle.to_tensor(np.asarray(x))
    cfg = dict(CFG, num_experts_per_tok=6)
    total = jnp.zeros_like(x)
    for i in range(16):
        share = _experts(held=(2 * i, 2), num=32, k=6, shared=0)
        share.gate.weight._data = whole.gate.weight._data
        for name in ("weight_in", "weight_out"):
            getattr(share.experts, name)._data = getattr(
                whole.experts, name)._data[2 * i:2 * i + 2]
        part = share(xt)._data
        _close(part, ref.experts(_state(share, "m."), "m.", x, cfg,
                                 held=(2 * i, 2), shared=False))
        total = total + part
    total = total + ref.experts(_state(whole, "m."), "m.", x, cfg,
                                held=(0, 0))
    _close(total, whole(xt)._data)
    _close(total, ref.experts(_state(whole, "m."), "m.", x,
                              dict(cfg, experts_held=(0, 32))))


# ------------------------------------------------ a dropped term is seen

@pytest.mark.parametrize("term", ["shared_expert", "D_x", "scaling_2.5",
                                  "gate"])
def test_a_dropped_term_is_seen(term):
    tr = _trainer(False, seed=4)
    ids, _ = _batch()
    got = jnp.asarray(tr.eval_step((ids,)))
    state = {**tr.params, **tr.buffers}
    cfg = dict(CFG)
    if term == "shared_expert":
        n = "layers.1.mixer.shared_experts.down_proj.weight"
        state[n] = jnp.zeros_like(state[n])
    elif term == "D_x":
        state["layers.0.mixer.D"] = jnp.zeros_like(state["layers.0.mixer.D"])
    elif term == "scaling_2.5":
        cfg["routed_scaling_factor"] = 1.0
    else:       # without the gate, z has no part in the result
        n = "layers.0.mixer.in_proj.weight"
        d_inner = CFG["mamba_num_heads"] * CFG["mamba_head_dim"]
        state[n] = state[n].at[:, :d_inner].set(0.0)
    want = ref.logits({**tr.params, **tr.buffers}, jnp.asarray(ids), CFG)
    off = ref.logits(state, jnp.asarray(ids), cfg)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert float(jnp.abs(got - off).max()) > 1e-2


def test_plan_held_sorts_the_slots_of_held_experts_first():
    idx = jnp.asarray([[5, 2, 9], [3, 2, 7], [2, 4, 0]], jnp.int32)
    order, starts, counts = moe_ops.plan_held(idx, 2, 3)
    assert counts.tolist() == [3, 1, 1] and starts.tolist() == [0, 3, 4]
    assert order[:5].tolist() == [1, 4, 6, 3, 7]


def test_the_dropped_slot_counter_counts_what_the_loops_visit(monkeypatch):
    """`dropped_slots_val` is held slots less the rows the loops counted
    while gathering them: a loop that stops one tile short (planted here)
    shows in the counter and in the result; the loop as written drops
    none, with every token on one held expert (several tiles)."""
    paddle.seed(14)
    layer = _experts(held=(4, 4), num=16, k=6)
    _steered(layer, (4, 8, 9, 10, 11, 12))
    x = _x((2, 20, 32), 8)
    xt = paddle.to_tensor(np.asarray(x))
    cfg = dict(CFG, num_experts_per_tok=6, experts_held=(4, 4))
    want = ref.experts(_state(layer, "m."), "m.", x, cfg)
    _close(layer(xt)._data, want)
    assert float(layer._buffers["dropped_slots_val"]._data) == 0.0
    whole = moe_ops._n_tiles
    monkeypatch.setattr(moe_ops, "_n_tiles",
                        lambda n, tile: jnp.maximum(whole(n, tile) - 1, 0))
    short = layer(xt)._data
    # 40 rows on expert 4 at 8 a tile: the last tile's 8 rows are lost
    assert float(layer._buffers["dropped_slots_val"]._data) == 8.0
    assert float(layer._buffers["routed_slots_val"]._data) == 40.0
    assert float(jnp.abs(short - want).max()) > 1e-3


def test_balancing_the_correction_bias_evens_the_loads():
    """A common component in the tokens makes a random router favour a few
    experts; steps of the builder's `balance_bias` on the loads the layer
    reports bring the fullest expert down to the mean, and move no
    weight."""
    from benchmark.builders.nemotron_h_trainer import balance_bias

    paddle.seed(16)
    layer = _experts(held=(0, 4), num=16, k=3)
    rs = np.random.RandomState(3)
    x = paddle.to_tensor((rs.randn(4, 64, 32) + 2.0 * rs.randn(32)).astype(
        "float32"))
    skew = []
    for _ in range(40):
        layer(x)
        loads = np.asarray(layer._buffers["expert_load_val"]._data)
        assert loads.sum() == 4 * 64 * 3
        skew.append(loads.max() / loads.mean())
        bias = layer.gate.e_score_correction_bias
        bias._data = jnp.asarray(balance_bias(
            np.asarray(bias._data), loads, 0.02))
    assert skew[0] > 2.0 and min(skew[-5:]) < 1.4, (skew[0], skew[-5:])
