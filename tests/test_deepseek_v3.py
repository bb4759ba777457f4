"""DeepseekV3ForCausalLM (multi-head latent attention with YaRN rotary
positions, group-limited sigmoid-routed gated experts, a share of the
experts held) against the plain reference, and served through
ServingEngine's latent pages. CPU, float32, the tiny preset: one dense and
two expert layers, 8 groups of which 4 are chosen, 4 of 16 experts held."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.ops import moe, rope
from paddle_tpu.parallel.functional import functionalize
from paddle_tpu.serving import Request, Scheduler, ServingEngine
from paddle_tpu.text.models import DeepseekV3Config, DeepseekV3ForCausalLM

from benchmark.reference import deepseek_v3 as R

VOCAB = 96


def _ref_cfg(cfg):
    d = dict(vars(cfg))
    d["experts_held"] = list(cfg.experts_held)
    return d


def _build(seed=3, **kw):
    paddle.seed(seed)
    m = DeepseekV3ForCausalLM(DeepseekV3Config.tiny(**kw))
    m.eval()
    return m


def _params(model):
    fm = functionalize(model)
    return {**fm.params(), **fm.buffers()}


@pytest.fixture(scope="module")
def model():
    return _build()


def _ref_logits(model, seq):
    return np.asarray(R.sequence_logits(
        _params(model), jnp.asarray(seq, jnp.int32), len(seq),
        _ref_cfg(model.cfg)))


# ---------------------------------------------------------------- the model
def test_logits_match_the_reference(model):
    ids = np.random.RandomState(0).randint(0, VOCAB, (2, 21)).astype(np.int32)
    got = np.asarray(model(jnp.asarray(ids))._data)
    for b in range(2):
        np.testing.assert_allclose(got[b], _ref_logits(model, ids[b]),
                                   atol=1e-4, rtol=0)


def test_logits_match_with_a_steering_bias(model):
    """A non-zero correction bias moves the choice of experts in the
    program and the reference alike."""
    rs = np.random.RandomState(5)
    gates = [b.mlp.gate for b in model.layers if not b.dense]
    old = [g.e_score_correction_bias._data for g in gates]
    try:
        for g in gates:
            g.e_score_correction_bias._data = jnp.asarray(
                rs.uniform(-0.3, 0.3, (16,)), jnp.float32)
        ids = rs.randint(0, VOCAB, (1, 17)).astype(np.int32)
        got = np.asarray(model(jnp.asarray(ids))._data)[0]
        np.testing.assert_allclose(got, _ref_logits(model, ids[0]),
                                   atol=1e-4, rtol=0)
    finally:
        for g, o in zip(gates, old):
            g.e_score_correction_bias._data = o


def test_absorbed_attention_equals_unabsorbed(model):
    """The decode form over the rows themselves gives what the join's
    up-projected keys and values give, position by position."""
    attn = model.layers[1].self_attn
    rs = np.random.RandomState(1)
    a = jnp.asarray(rs.randn(2, 13, 32), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(13, dtype=jnp.int32), (2, 13))
    q, rows = attn.project(a, pos)
    want = np.asarray(attn.causal(q, rows))
    for t in (0, 5, 12):
        got = attn.absorbed(q[:, t], rows, jnp.full((2,), t + 1, jnp.int32))
        np.testing.assert_allclose(np.asarray(got), want[:, t], atol=1e-5,
                                   rtol=0)


def test_causal_attention_by_head_groups_equals_all_heads(model,
                                                          monkeypatch):
    attn = model.layers[0].self_attn
    rs = np.random.RandomState(2)
    a = jnp.asarray(rs.randn(1, 16, 32), jnp.float32)
    q, rows = attn.project(a, jnp.arange(16, dtype=jnp.int32)[None])
    whole = np.asarray(attn.causal(q, rows))
    monkeypatch.setattr(type(attn), "SCORE_BYTES", 4 * 16 * 16 * 2)
    np.testing.assert_allclose(np.asarray(attn.causal(q, rows)), whole,
                               atol=1e-6, rtol=0)


def test_the_flash_join_path_equals_the_composition(model, monkeypatch):
    """On the chip a long join's attention goes through the flash kernel
    with queries, keys and values closed with zeros to one head size
    (16 and 8 -> 128 here): the kernel interpreted, against the
    head-group composition."""
    from paddle_tpu.ops import attention as A

    attn = model.layers[0].self_attn
    rs = np.random.RandomState(8)
    a = jnp.asarray(rs.randn(1, 256, 32), jnp.float32)
    q, rows = attn.project(a, jnp.arange(256, dtype=jnp.int32)[None])
    assert attn._flash_causal(q, q, q[..., :8]) is None    # off the chip
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setenv("PT_FLASH_MIN_SEQ", "128")
    kv = (rows[..., :16] @ attn.kv_b._data).reshape(1, 256, 4, 16)
    k = jnp.concatenate([kv[..., :8], jnp.broadcast_to(
        rows[:, :, None, 16:24], (1, 256, 4, 8))], -1)
    got = attn._flash_causal(q, k, kv[..., 8:], interpret=True)
    assert got.shape == (1, 256, 4, 8)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(attn._composed_causal(q, k, kv[..., 8:])), atol=2e-5)


def test_rotary_table_matches_the_reference_past_original():
    """YaRN's blended frequencies against the reference's own formula, at
    the published sizes and at positions past original_max_position."""
    cfg = {"qk_rope_head_dim": 64, "rope_theta": 10000,
           "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                            "mscale": 1, "mscale_all_dim": 1,
                            "original_max_position_embeddings": 4096,
                            "type": "yarn"}}
    inv = rope.yarn_inv_freq(64, 10000.0, 40, 4096, 32, 1)
    np.testing.assert_allclose(inv, np.asarray(R.yarn_frequencies(cfg)),
                               rtol=1e-6)
    # the ramp: fast dimensions kept, slow ones divided by the factor
    plain = rope.yarn_inv_freq(64, 10000.0)
    low, high = rope.yarn_correction_range(64, 10000.0, 4096, 32, 1)
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(inv[:low + 1], plain[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(inv[high:], plain[high:] / 40, rtol=1e-6)
    pos = np.asarray([0, 1, 4095, 4096, 5000, 6143], np.int32)
    cos, sin = rope.table(jnp.asarray(pos), inv)
    rc, rsn = R.rope_table(jnp.asarray(pos), cfg)
    np.testing.assert_allclose(np.asarray(cos), np.asarray(rc), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin), np.asarray(rsn), atol=1e-6)
    assert abs(rope.yarn_mscale(40, 1) - 1.3689) < 1e-4
    assert abs(R.softmax_scale(dict(cfg, qk_nope_head_dim=128))
               - 192 ** -0.5 * rope.yarn_mscale(40, 1) ** 2) < 1e-9


def test_rotation_keeps_dot_products_of_the_interleaved_form():
    rs = np.random.RandomState(3)
    x, y = rs.randn(5, 8).astype(np.float32), rs.randn(5, 8).astype(
        np.float32)
    inv = rope.yarn_inv_freq(8, 10000.0, 40, 8, 32, 1)
    pos = jnp.asarray([0, 3, 9, 40, 100])
    cos, sin = rope.table(pos, inv)
    got = (np.asarray(rope.rotate(jnp.asarray(x), cos, sin))
           * np.asarray(rope.rotate(jnp.asarray(y), cos, sin))).sum(-1)
    want = (np.asarray(R.rotate(jnp.asarray(x), cos, sin))
            * np.asarray(R.rotate(jnp.asarray(y), cos, sin))).sum(-1)
    np.testing.assert_allclose(got, want, atol=1e-5)


# ------------------------------------------------------------- the routing
def test_group_limited_top_k():
    """A token's experts lie in its `topk_group` best groups (by the sum
    of each group's two best biased scores); the bias steers the choice
    and not the weight."""
    rs = np.random.RandomState(7)
    scores = jnp.asarray(rs.uniform(0.05, 0.95, (64, 32)), jnp.float32)
    zero = jnp.zeros((32,), jnp.float32)
    idx, w = moe.route_top_k(scores, zero, 4, 2.5, n_group=8, topk_group=3)
    idx, w = np.asarray(idx), np.asarray(w)
    s = np.asarray(scores)
    g_score = np.sort(s.reshape(64, 8, 4), -1)[..., -2:].sum(-1)
    best = np.argsort(-g_score, -1)[:, :3]
    for t in range(64):
        assert set(idx[t] // 4) <= set(best[t])
        kept = np.where(np.isin(np.arange(32) // 4, best[t]), s[t], 0.0)
        assert set(idx[t]) == set(np.argsort(-kept)[:4])
        np.testing.assert_allclose(
            w[t], 2.5 * s[t, idx[t]] / s[t, idx[t]].sum(), rtol=1e-5)
    # without groups: plain top-k
    idx0, _ = moe.route_top_k(scores, zero, 4, 2.5)
    assert (np.sort(np.asarray(idx0), -1)
            == np.sort(np.argsort(-s, -1)[:, :4], -1)).all()
    # a bias that lifts one expert of a weak group pulls tokens to it,
    # and each chosen expert still weighs by its unbiased score
    bias = np.zeros((32,), np.float32)
    bias[5] = 2.0
    idx1, w1 = moe.route_top_k(scores, jnp.asarray(bias), 4, 2.5,
                               n_group=8, topk_group=3)
    idx1, w1 = np.asarray(idx1), np.asarray(w1)
    assert (idx1 == 5).any(-1).all() and not (idx == 5).any(-1).all()
    for t in range(64):
        np.testing.assert_allclose(
            w1[t], 2.5 * s[t, idx1[t]] / s[t, idx1[t]].sum(), rtol=1e-5)


def _dense_masked(x, weights, idx, first, w_gate, w_in, w_out, act):
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_in.shape[0]):
        mine = ((idx == first + e) * weights).sum(-1, keepdims=True)
        pre = [x @ w_in[e]] if w_gate is None else [x @ w_gate[e],
                                                    x @ w_in[e]]
        out = out + mine * (act(*pre) @ w_out[e])
    return out


@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
def test_routed_experts_forward_and_gradients(activation):
    """`routed_experts` against a dense masked loop over the held experts:
    forward and every gradient, the gated form and relu2 as it was."""
    rs = np.random.RandomState(11)
    T, D, F, E, k, first, count = 40, 12, 10, 8, 3, 2, 4
    x = jnp.asarray(rs.randn(T, D), jnp.float32)
    scores = jax.nn.sigmoid(jnp.asarray(rs.randn(T, E), jnp.float32))
    w_in = jnp.asarray(rs.randn(count, D, F) * 0.3, jnp.float32)
    w_gate = jnp.asarray(rs.randn(count, D, F) * 0.3, jnp.float32) \
        if activation == "swiglu" else None
    w_out = jnp.asarray(rs.randn(count, F, D) * 0.3, jnp.float32)
    act = moe.ACTIVATIONS[activation][0]

    def ours(x, scores, w_in, w_gate, w_out):
        idx, w = moe.route_top_k(scores, jnp.zeros((E,)), k, 2.5)
        order, starts, counts = moe.plan_held(idx, first, count)
        y, visited = moe.routed_experts(x, w, w_in, w_out, order, starts,
                                        counts, w_gate=w_gate,
                                        activation=activation)
        return y, (visited, counts)

    def dense(x, scores, w_in, w_gate, w_out):
        idx, w = moe.route_top_k(scores, jnp.zeros((E,)), k, 2.5)
        return _dense_masked(x, w, idx, first, w_gate, w_in, w_out, act)

    y, (visited, counts) = ours(x, scores, w_in, w_gate, w_out)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense(
        x, scores, w_in, w_gate, w_out)), atol=1e-5)
    assert (np.asarray(visited) == np.asarray(counts)).all()
    args = (0, 1, 2, 4) if w_gate is None else (0, 1, 2, 3, 4)
    g1 = jax.grad(lambda *a: (ours(*a)[0] ** 2).sum(), args)(
        x, scores, w_in, w_gate, w_out)
    g2 = jax.grad(lambda *a: (dense(*a) ** 2).sum(), args)(
        x, scores, w_in, w_gate, w_out)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    with pytest.raises(ValueError, match="w_gate"):
        moe.routed_experts(x, scores[:, :k], w_in, w_out,
                           *moe.plan_held(jnp.zeros((T, k), jnp.int32),
                                          0, count),
                           w_gate=None if w_gate is not None else w_in,
                           activation=activation)


def test_invalid_tokens_are_left_out_of_the_plan():
    idx = jnp.asarray([[0, 1], [1, 2], [0, 3]], jnp.int32)
    valid = jnp.asarray([True, False, True])
    _, _, counts = moe.plan_held(idx, 0, 2, valid)
    assert np.asarray(counts).tolist() == [2, 1]
    _, _, counts = moe.plan_held(idx, 0, 2)
    assert np.asarray(counts).tolist() == [2, 2]


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of every share of the
    experts plus the shared expert ONCE equal the uncut layer."""
    def layer(held):
        paddle.seed(21)
        lay = nn.SparseMoELayer(32, 16, 16, 4, shared_d_ff=16,
                                routed_scaling=2.5, activation="swiglu",
                                n_group=8, topk_group=4, dtype="float32")
        if held is not None:
            first, count = held
            lay.experts_held = held
            e = lay.experts
            for name in ("weight_in", "weight_gate", "weight_out"):
                p = getattr(e, name)
                p._data = p._data[first:first + count]
        return lay

    x = jnp.asarray(np.random.RandomState(4).randn(2, 9, 32), jnp.float32)
    whole = layer(None)
    y_all, c_all = whole.mix(x)
    shared = whole.shared_experts.mix(x, moe.swiglu)
    total = shared
    held_slots = 0
    for first in range(0, 16, 4):
        y, c = layer((first, 4)).mix(x)
        total = total + (y - shared)
        held_slots += int(c[1])
        assert int(c[3]) == 0 and int(c[0]) == 2 * 9 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_all),
                               atol=1e-5)
    assert held_slots == int(c_all[1]) == 2 * 9 * 4


# ---------------------------------------------------------------- serving
def _serve(model, specs, **kw):
    eng = ServingEngine(model, paged=True, num_slots=3, max_len=32,
                        page_size=4, **kw)
    sch = Scheduler(max_queue=16)
    reqs = []
    for prompt, n_new in specs:
        r = Request(prompt, None, max_new_tokens=n_new, eos_id=None)
        sch.submit(r)
        reqs.append(r)
    eng.serve_until_idle(sch)
    return eng, reqs


def _specs(seed, n):
    rs = np.random.RandomState(seed)
    return [(rs.randint(0, VOCAB, (rs.randint(3, 9),)).astype(np.int32),
             int(rs.randint(4, 10))) for _ in range(n)]


def test_prefill_then_decode_through_pages_matches_the_full_forward():
    """Joins (unabsorbed) and steps (absorbed, through the page table,
    with slots reused) against the full forward's logits: tapped where
    the programs compute them."""
    model = _build(latent_row_pad=8)
    seen = []
    plain = model._logits

    def tapped(y):
        lg = plain(y)
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), lg)
        return lg

    model._logits = tapped
    eng, reqs = _serve(model, _specs(0, 5))
    model._logits = plain
    assert eng.driver.cache_kinds() == ["latent"] * 3
    for r in reqs:
        res = r.future.result()
        assert res.ok
        seq = np.concatenate([r.prompt, np.asarray(res.tokens)])
        want = np.asarray(model(jnp.asarray(seq[None]))._data)[0]
        p = len(r.prompt)
        assert list(res.tokens) == list(want[p - 1:-1].argmax(-1))
        # every logits row the served path computed for this request is
        # one of the full forward's rows
        for t in range(p - 1, len(seq) - 1):
            best = min(np.abs(row - want[t]).max()
                       for lg in seen for row in lg)
            assert best < 1e-4
    # one latent page array a layer; 24 values a token a layer
    st = eng._state
    assert st["paged"] == [] and len(st["latent"]) == 3
    assert all(pg.shape == (eng.num_pages + 1, 4, 16 + 8 + 8)
               for pg in st["latent"])
    snap = eng.metrics.snapshot()
    assert snap["cache"]["bytes"]["latent"] == \
        3 * (eng.num_pages + 1) * 4 * 32 * 4
    assert snap["cache"]["bytes"]["paged"] == 0
    assert snap["cache"]["state_resets"] == 5
    assert snap["cache"]["prefill_tokens"] == sum(len(r.prompt)
                                                  for r in reqs)
    assert eng._page_bytes == 3 * 4 * 32 * 4
    # leak-free, each program traced once, steps went ahead
    eng._alloc.check()
    assert eng._alloc.pages_free == eng._alloc.n_pages
    assert all(v == 1 for v in eng.trace_counts.values())
    assert snap["pipeline"]["steps_ahead"] >= \
        snap["pipeline"]["decode_steps"] - 2


def test_expert_counters_leave_with_the_tokens():
    model = _build()
    eng, reqs = _serve(model, _specs(1, 4))
    ex = eng.metrics.snapshot()["experts"]
    tokens = sum(len(r.prompt) + len(r.future.result().tokens) - 1
                 for r in reqs)
    # live tokens x 4 experts a token x 2 expert layers: a join's bucket
    # padding and a step's empty slots are not routed
    assert ex["token_slots"] == tokens * 4 * 2
    assert 0 < ex["held_slots"] < ex["token_slots"]
    assert ex["dropped_slots"] == 0
    assert ex["load_max"] >= ex["held_slots"] / 4
    assert eng.driver.counts == DeepseekV3ForCausalLM.COUNTS


def test_the_counters_ride_the_token_arrays():
    """No further array leaves a program: a step returns [slots + 4]
    int32 (its tokens, then the counts), a join [1 + 4]."""
    model = _build()
    eng = ServingEngine(model, paged=True, num_slots=2, max_len=16,
                        page_size=4)
    eng._ensure_state(None)
    shapes = {}
    for key, build, args in eng._startup_programs((8,)):
        _, out = jax.eval_shape(build(), *args)
        shapes[key[0]] = (out.shape, str(out.dtype))
    assert shapes == {"pjoin": ((1 + 4,), "int32"),
                      "pstep": ((2 + 4,), "int32")}


def test_scopes_are_in_the_programs():
    model = _build()
    eng = ServingEngine(model, paged=True, num_slots=2, max_len=16,
                        page_size=4)
    eng._ensure_state(None)
    for key, build, args in eng._startup_programs((8,)):
        text = build().lower(*args).as_text(debug_info=True)
        for scope in DeepseekV3ForCausalLM.SCOPES:
            assert scope in text, (key, scope)


@pytest.mark.parametrize("kw, named", [
    (dict(prefix_cache=True), "prefix cache"),
    (dict(prefill_chunk=8), "chunked prefill"),
    (dict(num_pages=5), "oversubscribed page pool"),
    (dict(spec_k=2), "speculative decoding"),
    (dict(adapters=object()), "LoRA tenants"),
    (dict(kv_dtype="int8"), "page storage"),
    (dict(quantize="int8"), "int8 weights"),
    (dict(eager_fallback=True), "eager fallback"),
])
def test_refused_options_raise_by_name(model, kw, named):
    with pytest.raises(ValueError, match=named):
        ServingEngine(model, paged=True, num_slots=2, max_len=16,
                      page_size=4, **kw)


def test_refused_constructions_raise_by_name(model):
    with pytest.raises(ValueError, match="paged=True"):
        ServingEngine(model, num_slots=2, max_len=16)
    with pytest.raises(ValueError, match="whole causal LM"):
        ServingEngine(model, object(), object(), paged=True)
    from paddle_tpu.parallel import init_mesh
    from paddle_tpu.serving import ShardedServingEngine

    init_mesh(dp=2, devices=jax.devices("cpu")[:2])
    with pytest.raises(ValueError, match="sharded engine"):
        ShardedServingEngine(model, None, None, paged=True, num_slots=2,
                             max_len=16, page_size=4)
    with pytest.raises(ValueError, match="prediction module"):
        DeepseekV3Config.tiny(num_nextn_predict_layers=1)
    with pytest.raises(ValueError, match="mscale"):
        DeepseekV3Config.tiny(rope_scaling={"type": "yarn", "factor": 40,
                                            "mscale": 1,
                                            "mscale_all_dim": 0.5})
    with pytest.raises(ValueError, match="groups"):
        nn.SparseMoELayer(8, 8, 16, 8, n_group=8, topk_group=2)
    with pytest.raises(ValueError, match="activation"):
        nn.SparseMoELayer(8, 8, 16, 2, activation="gelu")
