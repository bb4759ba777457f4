"""Tests of what PR 35 added to the benchmark (CPU; `python -m pytest
benchmark/tests -q`): the published widths are unchanged and `reduced`
lists the cuts; the cost file against a hand count; the expert-counter
reader on facts made by hand; and the cell's check,
`_serving.reference_check` over what a threaded `ServingServer` really
served at the rehearsal's sizes, held against planted faults: the rotation
on the wrong slice of a query head, `mscale^2` left out of the softmax
scale, an un-normed latent in the page, the group limit ignored,
`routed_scaling_factor` left out, the rows read through another slot's
page table, and a stale `k_pe` in the row a step writes.
`test_benchmark.py` already runs every cell's rehearsal with and without
`--trace`."""
from __future__ import annotations

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import costs_deepseek_v3 as C, util        # noqa: E402
from benchmark.drivers import _serving                      # noqa: E402

CELL = "deepseek_v3_ep16.reason_long_saturated"
#: the catalog row's `config` (architectures.jsonl, DeepSeek-V3)
CATALOG = dict(
    attention_bias=False, ep_size=1, first_k_dense_replace=3,
    hidden_act="silu", hidden_size=7168, intermediate_size=18432,
    kv_lora_rank=512, max_position_embeddings=163840,
    model_type="deepseek_v3", moe_intermediate_size=2048, moe_layer_freq=1,
    n_group=8, n_routed_experts=256, n_shared_experts=1,
    norm_topk_prob=True, num_attention_heads=128, num_experts_per_tok=8,
    num_hidden_layers=61, num_key_value_heads=128,
    num_nextn_predict_layers=1, q_lora_rank=1536, qk_nope_head_dim=128,
    qk_rope_head_dim=64, rms_norm_eps=1e-06,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=1,
                      mscale_all_dim=1,
                      original_max_position_embeddings=4096, type="yarn"),
    rope_theta=10000, routed_scaling_factor=2.5, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=4, topk_method="noaux_tc",
    v_head_dim=128, vocab_size=129280)
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "vocab_size": 16160, "num_nextn_predict_layers": 0}


def test_the_published_widths_are_kept_and_the_cuts_listed():
    cfg = util.load_json("configs", "deepseek_v3_ep16.json")
    assert sorted(cfg["reduced"]) == sorted([*REDUCED, "experts_held"])
    for k, v in CATALOG.items():
        assert cfg[k] == REDUCED.get(k, v), k
    assert cfg["experts_held"] == [0, 16]
    assert cfg["published"]["vocab_size"] == CATALOG["vocab_size"]
    # the floors of the guide's section 4: four expert layers after the
    # dense one, at least 8 experts held, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["experts_held"][1] >= 8
    assert cfg["vocab_size"] * 8 >= CATALOG["vocab_size"]
    pool = cfg["pool"]
    assert (pool["num_slots"], pool["max_len"], pool["page_size"]) == \
        (64, 6144, 16)
    mix = util.load_json("traffic", "reason_long_backlog.json")
    assert mix["prompt_len"]["max"] + mix["new_tokens"]["max"] \
        <= pool["max_len"]


def test_costs_against_a_hand_count():
    cfg = util.resized(util.load_json("configs", "deepseek_v3_ep16.json"),
                       False)
    attn = 7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 \
        + 16384 * 7168                                       # 187.1 M
    assert abs(attn - 187.1e6) < 0.1e6
    norms = 1536 + 512 + 2 * 7168
    dense = 3 * 7168 * 18432
    expert = 3 * 7168 * 2048
    router = 256 * 7168
    n = 5 * (attn + norms) + dense + 4 * (17 * expert + router) \
        + 2 * 16160 * 7168 + 7168
    assert abs(n - 4.566e9) < 2e6            # the issue's 4.566 B
    assert C.weight_bytes(cfg) == 2 * (n - 4 * router) + 4 * 4 * router
    # a generated token at 3,700 rows: every attention matrix once, the
    # absorbed products over the rows, the dense layer, shared + 0.5
    # routed experts a token, the head over the slice
    rows = 2 * 128 * (576 + 512) * 3700
    want = 5 * (2 * attn + rows) + 2 * dense \
        + 4 * (2 * router + 1.5 * 2 * expert) + 2 * 7168 * 16160
    assert C.decode_flops_per_token(cfg, 3700) == want
    pre = 5 * (2 * attn + 2 * 128 * (192 + 128) * (3072 + 1) / 2) \
        + 2 * dense + 4 * (2 * router + 1.5 * 2 * expert)
    assert C.prefill_flops_per_token(cfg, 3072) == pre
    # 64 slots x 3.7k rows: a third of a TFLOP of attention a step
    assert 0.32e12 < 64 * 5 * rows < 0.34e12
    assert C.kv_row_bytes(cfg) == 1280        # 576 values + 64 of padding
    need = C.step_bytes(cfg, 64, 3700)
    assert need == C.weight_bytes(cfg) - 2 * 16160 * 7168 + 64 * 2 * 7168 \
        + 64 * 5 * (3700 * 1280 + 1280)
    assert 10e9 < need < 11e9


def test_the_expert_reader_on_facts_made_by_hand():
    from benchmark.readers import expert_counts

    cfg = util.resized(util.load_json("configs", "deepseek_v3_ep16.json"),
                       False)
    snap = lambda t, h, m, d: {"experts": {                    # noqa: E731
        "token_slots": t, "held_slots": h, "load_max": m,
        "dropped_slots": d}}
    facts = {"config": cfg, "snapshot_open": snap(1000, 60, 10, 0),
             "snapshot_close": snap(9000, 560, 60, 0)}
    assert expert_counts.read(facts, "routed_share") == 6.25
    assert expert_counts.read(facts, "load_max_over_mean") == \
        pytest.approx(50 * 16 / 500)
    assert expert_counts.read(facts, "dropped_slots") == 0
    # a program without the counters (the parent of PR 35): nothing
    bare = dict(facts, snapshot_open={"iterations": 1},
                snapshot_close={"iterations": 2})
    for what in ("routed_share", "load_max_over_mean", "dropped_slots"):
        assert expert_counts.read(bare, what) is None
    with pytest.raises(ValueError):
        expert_counts.read(facts, "other")


# ---------------------------------------------------------------------------
# the cell's check against planted faults
# ---------------------------------------------------------------------------

def _serve(monkeypatch, plant=None, seed=5):
    """Serve the rehearsal's traffic through a threaded ServingServer and
    run the cell's own check over what resolved: (ok, worst shortfall in
    sigma, share of tokens that are the reference's argmax)."""
    from paddle_tpu.serving import ServingServer

    cell, cfg, mix = util.load_cell(CELL, True)
    builder = util.load_module("builders", cfg["builder"])
    engine = builder.build(cfg, seed, None)
    if plant:
        plant(engine, monkeypatch)
    server = ServingServer(engine, max_queue=cfg["pool"]["max_queue"])
    maker = _serving.RequestMaker(mix, seed, cfg["vocab_size"], (0,))
    recs = []
    for _ in range(10):
        prompt, mem, n_new = maker.next()
        rec = _serving.Rec(0.0, prompt, mem, n_new)
        rec.req = server.submit(prompt, mem, max_new_tokens=n_new,
                                eos_id=None)
        recs.append(rec)
    for rec in recs:
        rec.req.future.result(timeout=600)
    server.shutdown(drain=True, timeout=60)
    said = []
    monkeypatch.setattr(_serving, "say", lambda **kw: said.append(kw))
    run = types.SimpleNamespace(
        config=cfg, traffic=mix, seed=seed,
        reference=util.load_module("reference", cfg["reference"]))
    pool = types.SimpleNamespace(engine=engine, mem_shape=(0,))
    ok = _serving.reference_check(run, pool, recs)
    health_ok, _ = builder.pool_health(engine)
    line = said[-1]
    return ok and health_ok, line["worst_shortfall_sigma"], \
        line["argmax_match_share"]


def test_the_check_passes_on_the_sound_program(monkeypatch):
    ok, worst, share = _serve(monkeypatch)
    assert ok and worst == 0.0 and share == 1.0


def _attn(engine):
    return [blk.self_attn for blk in engine._net.layers]


def _project(self, a, positions, wrong_slice=False, norm_latent=True):
    """`LatentAttention.project` with a fault switched on."""
    import jax.numpy as jnp

    from paddle_tpu.nn.layer.mla import rms
    from paddle_tpu.ops import rope as R

    cos, sin = R.table(positions, self.inv_freq)
    q = rms(a @ self.q_a._data, self.q_a_norm._data, self.eps) \
        @ self.q_b._data
    q = q.reshape(a.shape[:-1] + (self.num_heads, self.nope + self.rot))
    lo = 0 if wrong_slice else self.nope
    q = jnp.concatenate([
        q[..., :lo],
        R.rotate(q[..., lo:lo + self.rot], cos[..., None, :],
                 sin[..., None, :]), q[..., lo + self.rot:]], -1)
    ckv = a @ self.kv_a._data
    c = ckv[..., :self.rank]
    parts = [rms(c, self.kv_a_norm._data, self.eps) if norm_latent else c,
             R.rotate(ckv[..., self.rank:], cos, sin),
             jnp.zeros(a.shape[:-1] + (self.row_pad,), a.dtype)]
    return q, jnp.concatenate(parts, -1)


def _wrong_slice(engine, mp):
    mp.setattr(type(_attn(engine)[0]), "project",
               lambda self, a, p: _project(self, a, p, wrong_slice=True))


def _raw_latent(engine, mp):
    mp.setattr(type(_attn(engine)[0]), "project",
               lambda self, a, p: _project(self, a, p, norm_latent=False))


def _no_mscale(engine, mp):
    for attn in _attn(engine):
        attn.scale = (attn.nope + attn.rot) ** -0.5


def _no_group_limit(engine, mp):
    for blk in engine._net.layers:
        if not blk.dense:
            blk.mlp.n_group = 1


def _no_routed_scaling(engine, mp):
    for blk in engine._net.layers:
        if not blk.dense:
            blk.mlp.routed_scaling = 1.0


def _another_slots_table(engine, mp):
    import jax.numpy as jnp

    cls = type(_attn(engine)[0])
    absorbed = cls.absorbed
    mp.setattr(cls, "absorbed", lambda self, q, rows, n: absorbed(
        self, q, jnp.roll(rows, 1, 0), n))


def _stale_k_pe(engine, mp):
    """A step writes the row's c_kv and leaves its k_pe as the page held
    it (zeros, or a finished request's)."""
    from paddle_tpu.serving import paging as PG

    rank = _attn(engine)[0].rank
    write = PG.write_token

    def stale(pages, scales, table, index, tok):
        new, _ = write(pages, scales, table, index, tok)
        return new.at[..., rank:].set(pages[..., rank:]), None

    mp.setattr(PG, "write_token", stale)


#: fault -> how it is planted. Readings at the rehearsal's sizes (my CPU
#: runs, PR 35; worst shortfall in sigma / share of tokens that stay the
#: reference's argmax) stand beside each in the configuration's `check`.
FAULTS = {
    "rotation_on_the_wrong_slice": _wrong_slice,
    "mscale_squared_left_out": _no_mscale,
    "unnormed_latent_in_the_page": _raw_latent,
    "group_limit_ignored": _no_group_limit,
    "routed_scaling_factor_left_out": _no_routed_scaling,
    "rows_through_another_slots_table": _another_slots_table,
    "stale_k_pe_in_a_written_row": _stale_k_pe,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_check_against_a_planted_fault(monkeypatch, fault):
    ok, worst, share = _serve(monkeypatch, FAULTS[fault])
    print(f"\nplanted {fault}: ok {ok}, worst shortfall {worst:.3f} sigma, "
          f"argmax share {share:.3f}")
    limit = util.load_cell(CELL, True)[1]["check"]["margin_sigma"]
    assert not ok and worst > limit, (fault, worst, share)
