"""Tests of what PR 33 added to the benchmark (CPU; `python -m pytest
benchmark/tests -q`): the published widths are unchanged; the cost file
against a hand count; and the cell's check, `_serving.reference_check`
over what a threaded `ServingServer` really served at the rehearsal's
sizes, held against planted faults: a window two positions too wide (528
for 512), lambda of the wrong layer, the memory taken from an earlier
Mamba block (14 for 16), a ring row read one step stale, a scan state
rounded to bfloat16, a scan that starts from a state that is not zero (a
slot reused without a reset), and the pages read through another slot's
table. `test_benchmark.py` already runs every cell's rehearsal with and
without `--trace`."""
from __future__ import annotations

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import costs_phi4_flash as C, util          # noqa: E402
from benchmark.drivers import _serving                      # noqa: E402

PHI = "phi4_mini_flash.reason_saturated"
CATALOG = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560,
    intermediate_size=10240, layer_norm_eps=1e-05,
    max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash",
    num_attention_heads=40, num_hidden_layers=32, num_key_value_heads=20,
    resid_pdrop=0, sliding_window=512, tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False, vocab_size=200064)


def test_the_published_configuration_is_unchanged():
    cfg = util.load_json("configs", "phi4_mini_flash.json")
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["reduced"] == []
    a = cfg["assumed"]
    assert (a["head_dim"], a["d_state"], a["d_conv"], a["expand"],
            a["dt_rank"], a["dtype"]) == (64, 16, 4, 2, 160, "bfloat16")
    pool = cfg["pool"]
    assert (pool["num_slots"], pool["max_len"], pool["page_size"]) == \
        (64, 4096, 16)


def test_every_line_of_prose_in_the_declaration_fits():
    """The driver refuses a `why`, a `layer` or a `source` of more than 200
    characters before any run (a configuration's 202 cost PR 33 a check)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        decl = json.load(f)
    lines = [(e["name"], k, e[k])
             for part in ("configs", "workloads", "per_layer")
             for e in decl[part] for k in ("why", "layer", "source")
             if k in e]
    assert len(lines) > len(decl["per_layer"])
    bad = [(n, k, len(s)) for n, k, s in lines
           if not (1 <= len(s) <= 200 and s.isascii() and s.isprintable())]
    assert not bad, bad


def test_costs_against_a_hand_count():
    cfg = util.resized(util.load_json("configs", "phi4_mini_flash.json"),
                       False)
    assert C.counts(cfg) == dict(mamba=9, swa=8, full=1, gmu=7, xattn=7)
    # 3.852 B parameters, all but 9 x (A_log, D, dt bias) and the lambda
    # vectors in two bytes
    mamba = 2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 \
        + 5120 + 5120 * 16 + 5120 + 5120 * 2560
    attn = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    cross = 2560 * 2560 + 2560 + 2560 * 2560 + 2560 + 4 * 64 + 128
    gmu = 2 * 2560 * 5120
    block = 2560 * 20480 + 10240 * 2560 + 4 * 2560
    n = 9 * mamba + 9 * attn + 7 * cross + 7 * gmu + 32 * block \
        + 200064 * 2560 + 2 * 2560
    assert abs(n - 3.852e9) < 2e6
    f32 = 9 * (5120 * 16 + 2 * 5120) + 16 * 4 * 64
    assert C.weight_bytes(cfg) == 2 * (n - f32) + 4 * f32
    # a generated token at 1536 positions: every block's feed-forward,
    # the mixers, the head; attention over 512 keys (window) or 1536
    ffn = 2 * 2560 * 20480 + 2 * 10240 * 2560
    m = 2 * 2560 * 10240 + 2 * 5120 * 192 + 2 * 160 * 5120 \
        + 2 * 5120 * 2560 + 2 * 5120 * 16
    att = lambda keys: 2 * 2560 * keys + 2 * 40 * 128 * keys  # noqa: E731
    proj = 2 * 2560 * 5120 + 2 * 2560 * 2560
    want = 32 * ffn + 9 * m + 8 * (proj + att(512)) + proj + att(1536) \
        + 7 * 2 * 2 * 2560 * 5120 + 7 * (4 * 2560 * 2560 + att(1536)) \
        + 2 * 2560 * 200064
    assert C.decode_flops_per_token(cfg, 1536) == want
    assert 2 * 3.85e9 < want < 2 * 4.1e9     # every weight once, and attention
    # a decode step of 64 slots at 1536 positions: the weights, then 8
    # reads of 1536 rows of 5,120 B a slot, 8 rings, 9 states
    row = 2 * 1280 * 2
    assert C.kv_row_bytes(cfg) == row
    need = C.step_bytes(cfg, 64, 1536)
    assert need == C.weight_bytes(cfg) + 64 * (8 * 1536 * row + row) \
        + 64 * 8 * (512 * row + row) \
        + 64 * 9 * 2 * (3 * 5120 * 2 + 5120 * 16 * 4)
    assert 12.5e9 < need < 13.5e9


# ---------------------------------------------------------------------------
# the cell's check against planted faults
# ---------------------------------------------------------------------------

def _serve(monkeypatch, plant=None, cfg_edit=None, seed=5):
    """Serve the rehearsal's traffic through a threaded ServingServer and
    run the cell's own check over what resolved: (ok, worst shortfall in
    sigma, share of tokens that are the reference's argmax)."""
    from paddle_tpu.serving import ServingServer

    cell, cfg, mix = util.load_cell(PHI, True)
    builder = util.load_module("builders", cfg["builder"])
    served_cfg = dict(cfg)
    if cfg_edit:
        served_cfg.update(cfg_edit)
    engine = builder.build(served_cfg, seed, None)
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


def _wrong_lambda(engine, mp):
    for blk in engine._net.layers:
        if blk.kind in ("swa", "full", "xattn"):
            blk.mixer.layer_idx = max(blk.idx - 4, 0)


def _early_memory(engine, mp):
    engine._net.cfg.memory_layer -= 2


def _stale_ring(engine, mp):
    def stale(ring, rows, at, k, v):
        kept, _ = type(engine._net)._ring_step(ring, rows, at, k, v)
        return kept, ring          # reads the ring as the LAST step left it

    mp.setattr(engine._net, "_ring_step", stale, raising=False)


def _bf16_state(engine, mp):
    import jax.numpy as jnp

    from paddle_tpu.ops import ssm

    scan, step = ssm.selective_scan, ssm.selective_step

    def rounded(h):
        return h.astype(jnp.bfloat16).astype(jnp.float32)

    mp.setattr(ssm, "selective_scan",
               lambda *a, **k: (lambda y, h: (y, rounded(h)))(*scan(*a, **k)))
    mp.setattr(ssm, "selective_step",
               lambda *a: (lambda y, h: (y, rounded(h)))(*step(*a)))


def _state_not_zeroed(engine, mp):
    import jax.numpy as jnp

    from paddle_tpu.ops import ssm

    scan = ssm.selective_scan

    def from_a_left_over_state(x, dt, a, b_mat, c_mat, d=None, h0=None,
                               length=None):
        h0 = jnp.full((x.shape[0],) + a.shape[::-1], 0.5, jnp.float32)
        return scan(x, dt, a, b_mat, c_mat, d, h0, length)

    mp.setattr(ssm, "selective_scan", from_a_left_over_state)


def _another_slots_table(engine, mp):
    import jax.numpy as jnp

    from paddle_tpu.ops import diff_attention as DA

    reader = DA.paged_reader
    mp.setattr(DA, "paged_reader", lambda kp, vp, table, *a, **k: reader(
        kp, vp, jnp.roll(table, 1, 0), *a, **k))


#: fault -> (how it is planted, the served model's configuration edits,
#: whether the cell's check sees it at random weights). Readings at the
#: rehearsal's sizes (my CPU runs, PR 33), worst shortfall in sigma /
#: share of tokens that stay the reference's argmax: window 2.78 / 0.62,
#: lambda 1.38 / 0.68, stale ring 1.92 / 0.62, state not zeroed 3.65 /
#: 0.16, another slot's table 1.28 / 0.52, against a limit of 0.5. NOT
#: seen: the memory taken two blocks early moves 6 % of the tokens, each
#: to a near-tie (0.093 sigma): with random weights the Mamba blocks'
#: outputs are much alike; a scan state rounded to bfloat16 moves no token
#: at all. Both stand in the configuration's `assumed` and PERF.md
#: section 7; tier-1 holds both (logits against the reference to 1e-4).
#: At the published widths on the chip (my chip runs, PR 33, review
#: round) the early memory IS seen, 1.80 sigma / 0.44 and not correct;
#: the bfloat16 state is not, 0.083 / 0.94.
FAULTS = {
    "window_two_too_wide": (None, {"sliding_window": 10}, True),
    "lambda_of_another_layer": (_wrong_lambda, None, True),
    "memory_from_an_earlier_block": (_early_memory, None, False),
    "ring_row_one_step_stale": (_stale_ring, None, True),
    "scan_state_in_bfloat16": (_bf16_state, None, False),
    "scan_state_not_zeroed": (_state_not_zeroed, None, True),
    "pages_through_another_slots_table": (_another_slots_table, None, True),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_check_against_a_planted_fault(monkeypatch, fault):
    plant, edit, seen = FAULTS[fault]
    ok, worst, share = _serve(monkeypatch, plant, edit)
    print(f"\nplanted {fault}: ok {ok}, worst shortfall {worst:.3f} sigma, "
          f"argmax share {share:.3f}")
    assert ok == (not seen), (fault, worst, share)
    if seen:
        assert worst > 2 * 0.5      # twice the limit, not a near miss


# ---------------------------------------------------------------------------
# the readers PR 33 adds, on facts made by hand
# ---------------------------------------------------------------------------

def _facts():
    cfg = util.resized(util.load_json("configs", "phi4_mini_flash.json"),
                       False)
    snap = lambda its, live, pre, resets: {                    # noqa: E731
        "iterations": its,
        "paging": {"live_page_iterations": live},
        "cache": {"prefill_tokens": pre, "state_resets": resets}}
    return {
        "config": cfg, "window_s": 50.0,
        "peaks": util.load_json("peaks.json")["devices"]["TPU v5 lite"],
        "end_to_end": {"serve_tokens_per_s": 1500.0},
        # 1000 iterations of 50 ms, 64 slots full, 96 pages a slot live
        "occupancy": [64] * 1000,
        "iteration_t": [0.05 * i for i in range(1001)],
        "iteration_busy": np.ones(1001, bool),
        "snapshot_open": snap(100, 0, 10_000, 10),
        "snapshot_close": snap(1100, 1000 * 64 * 96, 87_000, 80),
    }


def test_the_new_readers_on_facts_made_by_hand():
    from benchmark.readers import serve_facts, serve_mfu, step_hbm_share

    facts = _facts()
    slots, context = serve_facts.window_means(facts)
    assert (slots, context) == (64.0, 96 * 16)
    cfg = facts["config"]
    flops = 1500.0 * 50 * C.decode_flops_per_token(cfg, 1536) \
        + 77_000 * C.prefill_flops_per_token(cfg, 1100)
    assert serve_mfu.read(facts, "costs_phi4_flash") == \
        pytest.approx(100 * flops / (50 * 197e12))
    assert 5 < serve_mfu.read(facts, "costs_phi4_flash") < 15
    assert step_hbm_share.read(facts, "costs_phi4_flash") == \
        pytest.approx(100 * C.step_bytes(cfg, 64, 1536) / (0.05 * 819e9))
    from benchmark.readers import kernel_roofline, kernel_time

    mp = pytest.MonkeyPatch()
    mp.setattr(kernel_time, "read", lambda facts, kernel, program=None: 3.0)
    try:
        ops, nbytes = C.selective_scan_call(cfg, {"prompt_len": 1100.0})
        assert (ops, nbytes) == (1100 * 2 * 5120 * 16,
                                 1100 * (5120 * 8 + 64))
        got = kernel_roofline.read(
            facts, "selective_scan", "costs_phi4_flash",
            "selective_scan_call", "jit_pjoin")
        assert got == pytest.approx(100 * (nbytes / 819e9) / 3.0e-3)
        assert 0 < got < 100
        mp.setattr(kernel_time, "read", lambda *a, **k: None)
        assert kernel_roofline.read(
            facts, "selective_scan", "costs_phi4_flash",
            "selective_scan_call") is None
    finally:
        mp.undo()
    # a program without the `cache` block (the parent of PR 33), a run
    # without peaks (the CPU rehearsal): nothing to read, and no error
    bare = dict(facts, snapshot_open={"iterations": 1},
                snapshot_close={"iterations": 2})
    assert serve_mfu.read(bare, "costs_phi4_flash") is None
    assert step_hbm_share.read(bare, "costs_phi4_flash") is None
    no_peak = {k: v for k, v in facts.items() if k != "peaks"}
    assert serve_mfu.read(no_peak, "costs_phi4_flash") is None
    assert step_hbm_share.read(no_peak, "costs_phi4_flash") is None
