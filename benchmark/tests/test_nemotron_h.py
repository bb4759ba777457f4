"""Tests of what PR 27 added to the benchmark (CPU; `python -m pytest
benchmark/tests -q`): the NemotronH reference against the program at full
precision on the tiny preset, and that a dropped term moves the result
past the cell's limits; the job's `expert_layer` and `first_update` checks
against planted faults (a step that updates nothing, a gradient of twice
the size, a router rounded to bfloat16, an expert loop one tile short);
`costs_nemotron_h` against a hand count; the traced
rehearsals of the two new cells report their own metrics (the four-chip
cell on four virtual CPU devices). `test_benchmark.py` already runs every
cell's rehearsal with and without `--trace`."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import costs_nemotron_h, util  # noqa: E402
from benchmark.drivers import train_lm_job  # noqa: E402

NEMO = "nemotron3_nano_ep16.pretrain_b2_s8192"
DP2TP2 = "ernie_base.finetune_dp2tp2"


def test_the_published_widths_are_unchanged():
    cfg = util.load_json("configs", "nemotron3_nano_ep16.json")
    published = dict(
        hidden_size=2688, head_dim=128, num_attention_heads=32,
        num_key_value_heads=2, mamba_num_heads=64, mamba_head_dim=64,
        n_groups=8, ssm_state_size=128, conv_kernel=4, chunk_size=128,
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        num_experts_per_tok=6, routed_scaling_factor=2.5,
        intermediate_size=1856, expand=2, router_experts=128)
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"])
    assert (cfg["hybrid_override_pattern"], cfg["num_hidden_layers"],
            cfg["n_routed_experts"], cfg["experts_held"],
            cfg["vocab_size"]) == ("MEMEM*EME", 9, 8, [0, 8], 16384)
    assert cfg["published"]["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])


def test_required_operations_against_a_hand_count():
    cfg = util.resized(util.load_json("configs",
                                      "nemotron3_nano_ep16.json"), False)
    per = costs_nemotron_h.forward_flops_per_token(cfg, 8192)
    # Mamba: in_proj 2688 x 10304, out_proj 4096 x 2688; the scan
    assert per["M"] == 2 * 2688 * 10304 + 2 * 4096 * 2688 + (
        2 * 128 * 128 * 8 + 2 * 128 * 4096 + 4 * 4096 * 128)
    # attention: q and o 2688 x 4096, k and v 2688 x 256; half the square
    assert per["*"] == 2 * 2688 * (2 * 4096 + 2 * 256) + (
        4 * 32 * 128 * 8193 / 2)
    # experts: router, shared expert, 6 x 8 / 128 of a routed expert
    assert per["E"] == 2 * 2688 * 128 + 4 * 2688 * 3712 + (
        6 * 8 / 128) * 4 * 2688 * 1856
    assert per["head"] == 2 * 2688 * 16384
    total = costs_nemotron_h.train_flops_per_token(cfg, 8192)
    assert total == 3 * (4 * per["M"] + per["*"] + 4 * per["E"]
                         + per["head"])
    assert 2.14e9 < total < 2.16e9
    assert 77e6 < 2 * 2688 * 10304 + 2 * 4096 * 2688 < 78e6


def test_reference_equals_the_program_and_sees_a_dropped_term():
    import jax
    import jax.numpy as jnp

    cfg = util.resized(util.load_json("configs",
                                      "nemotron3_nano_ep16.json"), True)
    cfg["trainer"] = dict(cfg["trainer"], compute_dtype="float32")
    tr = util.load_module("builders", "nemotron_h_trainer").build(
        cfg, 11, jax.devices())
    ref = util.load_module("reference", "nemotron_h")
    ids = train_lm_job.TokenBatches({"batch": 2, "seq_len": 24}, 3,
                                    cfg["vocab_size"]).next()[0]
    state = {**tr.params, **tr.buffers}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(tr.eval_step((ids,)), np.float32)
        want = np.asarray(ref.logits(state, jnp.asarray(ids), cfg))
        assert np.abs(got - want).max() < 1e-4
        chk = cfg["check"]
        assert train_lm_job.logits_errors(got, want, chk["logits_atol"])[
            "rel_rms_err"] < 1e-4
        # (the routed experts of ONE layer move the tiny preset's logits
        # by 2 %, under the limit: tier-1's full-precision tests see them)
        for dropped in ("layers.0.mixer.D",
                        "layers.1.mixer.shared_experts.down_proj.weight",
                        "layers.2.mixer.v_proj.weight"):
            p = dict(state)
            p[dropped] = jnp.zeros_like(p[dropped])
            off = np.asarray(ref.logits(p, jnp.asarray(ids), cfg))
            err = train_lm_job.logits_errors(off, want, chk["logits_atol"])
            assert err["rel_rms_err"] > chk["rel_rms_max"], (dropped, err)
    # the reference held in the precision below the configuration's fails
    # the same limit; in the configuration's own it passes
    def reading(dtype):
        low = np.asarray(ref.logits(state, jnp.asarray(ids), cfg,
                                    dtype=jnp.dtype(dtype)), np.float32)
        return train_lm_job.logits_errors(low, want, chk["logits_atol"])

    assert reading("float8_e4m3fn")["rel_rms_err"] > chk["rel_rms_max"]
    assert reading("bfloat16")["rel_rms_err"] < chk["rel_rms_max"]


def _tiny_run(seed=11, **check):
    """What the job's checks are handed, at the tiny preset in bfloat16
    as the cell computes."""
    import types

    import jax

    cfg = util.resized(util.load_json("configs",
                                      "nemotron3_nano_ep16.json"), True)
    cfg["check"] = dict(cfg["check"], **check)
    run = types.SimpleNamespace(
        config=cfg, seed=seed,
        reference=util.load_module("reference", "nemotron_h"))
    tr = util.load_module("builders", "nemotron_h_trainer").build(
        cfg, seed, jax.devices())
    return run, tr


def _said(capsys, check):
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return [ln for ln in lines if ln.get("check") == check][-1]


@pytest.mark.parametrize("fault", [None, "no_update", "twice_the_gradient"])
def test_first_update_check_against_planted_faults(fault, capsys):
    run, tr = _tiny_run()
    ids, labels = train_lm_job.TokenBatches(
        {"batch": 2, "seq_len": 24}, 5, run.config["vocab_size"]).next()
    if fault == "no_update":        # moments move, the parameters do not
        import jax

        whole = tr.step

        def step(inputs, y):
            kept = jax.device_get(tr.params)
            loss = whole(inputs, y)
            tr.params = jax.device_put(kept)
            return loss

        tr.step = step
    elif fault == "twice_the_gradient":
        loss_fn = tr.loss_fn
        tr.loss_fn = lambda out, y: 2.0 * loss_fn(out, y)
    ok, _ = train_lm_job.first_update_check(run, tr, ids, labels)
    said = _said(capsys, "first_update")
    assert ok is (fault is None), said
    if fault is None:
        assert said["update_rel_err"] < 0.01
        assert abs(said["grad_projection"] - 1.0) < 0.05
        assert abs(said["step_loss"] - said["reference_loss"]) < 0.01
    elif fault == "no_update":
        assert said["update_rel_err"] == pytest.approx(1.0, abs=1e-3)
    else:
        assert said["grad_projection"] == pytest.approx(0.5, abs=0.03)


@pytest.mark.parametrize("fault", [None, "bfloat16_router",
                                   "loop_one_tile_short"])
def test_expert_layer_check_against_planted_faults(fault, capsys,
                                                   monkeypatch):
    from paddle_tpu.ops import moe

    run, tr = _tiny_run(eval_batch=[4, 512])
    monkeypatch.setattr(moe, "TILE_ROWS", 64)
    if fault == "bfloat16_router":  # nothing is kept float32 any more
        tr._keep_f32 = frozenset()
    elif fault == "loop_one_tile_short":
        whole = moe._n_tiles
        monkeypatch.setattr(moe, "_n_tiles", lambda n, tile: whole(
            n, tile) - (n > 0))
    ok = train_lm_job.expert_layer_check(run, tr)
    said = _said(capsys, "expert_layer")
    assert ok is (fault is None), said
    if fault == "bfloat16_router":
        assert said["as_started"]["router_disagreement"] > \
            run.config["check"]["router_disagreement_max"]
        assert said["all_slots_held"]["dropped_slots"] == 0
    elif fault == "loop_one_tile_short":
        assert said["all_slots_held"]["dropped_slots"] > 0
        assert said["one_held_expert"]["rel_rms_err"] > \
            run.config["check"]["expert_rel_rms_max"]
    else:
        assert said["one_held_expert"]["load_max_over_mean"] == \
            pytest.approx(run.config["experts_held"][1])
        assert said["as_started"]["router_disagreement"] == 0.0


def _rehearse(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3000000019", "--seconds", "2", "--trace", str(trace),
         "--rehearse"], capture_output=True, text=True, env=env,
        timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stdout


def test_traced_rehearsal_of_the_pretraining_cell_reports_its_counters():
    line, out = _rehearse(NEMO, 1)
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # what the host and the program's counters give is reported on the
    # CPU too; what needs a device trace or a peak is left out there
    assert {"step_ms.nemo", "dispatch_ms.nemo", "routed_share.nemo",
            "expert_load_max_over_mean.nemo", "dropped_slots.nemo",
            "programs_compiled"} <= set(got)
    assert got["dropped_slots.nemo"]["value"] == 0.0
    assert 0.0 < got["routed_share.nemo"]["value"] <= 100.0
    assert '"check": "no_dropped_slot", "ok": true' in out
    assert '"check": "expert_layer", "ok": true' in out
    assert '"check": "first_update", "ok": true' in out


def test_rehearsal_of_the_four_chip_cell_runs_on_four_devices():
    line, out = _rehearse(DP2TP2, 1)
    assert line["correct"] is True and line["device"]["count"] == 4
    assert {"step_ms.dp2tp2", "dispatch_ms.dp2tp2",
            "programs_compiled"} <= set(line["metrics"])
    mix = util.load_json("traffic", "glue_s128_dp2tp2.json")
    assert mix["mesh"] == {"dp": 2, "tp": 2}
