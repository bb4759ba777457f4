"""Tests of the latent decode kernel's benchmark metrics (CPU; `python -m
pytest benchmark/tests -q`): `costs_latent_decode.latent_decode_call`
against a hand count, `latent_decode_roofline.dsv3` on facts made by
hand, and the three metric files' readers and arguments."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark import costs_latent_decode as L, util          # noqa: E402
from benchmark.readers import kernel_roofline                  # noqa: E402

CELL = "deepseek_v3_ep16.reason_long_saturated"


def _cfg():
    return util.resized(util.load_json("configs", "deepseek_v3_ep16.json"),
                        False)


def test_one_call_against_a_hand_count():
    """64 slots of 3,700 written rows: 2 x 128 x (576 + 512) operations
    a row, 66 GFLOP; 232 pages of 16 rows of 1,280 B a slot plus the
    bfloat16 query and output, 0.32 GB: the bytes bind, 0.39 ms at
    819 GB/s."""
    ops, nbytes = L.latent_decode_call(_cfg(), {"slots": 64,
                                                "context": 3700})
    assert ops == 64 * 3700 * 2 * 128 * (576 + 512)
    assert 65e9 < ops < 67e9
    assert nbytes == 64 * 232 * 16 * 1280 + 64 * 128 * (576 + 512) * 2
    assert 0.31e9 < nbytes < 0.33e9
    assert nbytes / 819e9 > ops / 197e12


def test_the_roofline_reads_the_call_against_its_time(monkeypatch):
    from benchmark.readers import kernel_time

    cfg = _cfg()
    monkeypatch.setattr(kernel_time, "read",
                        lambda facts, kernel, program=None: 0.8)
    snap = {"iterations": 0, "paging": {"live_page_iterations": 0},
            "cache": {"prefill_tokens": 0, "state_resets": 0}}
    close = {"iterations": 100,
             "paging": {"live_page_iterations": 100 * 64 * 232},
             "cache": {"prefill_tokens": 3072, "state_resets": 1}}
    facts = {"config": cfg, "occupancy": [64] * 100,
             "snapshot_open": snap, "snapshot_close": close,
             "peaks": {"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9}}
    doc = util.load_json("layer_metrics", "latent_decode_roofline.dsv3.json")
    got = kernel_roofline.read(facts, **doc["args"])
    _, nbytes = L.latent_decode_call(cfg, {"slots": 64,
                                           "context": 232 * 16})
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.8e-3)
    assert 40 < got < 60


@pytest.mark.parametrize("name, reader", [
    ("latent_decode_ms.dsv3", "kernel_time"),
    ("latent_decode_roofline.dsv3", "kernel_roofline"),
    ("block_fill_share.dsv3", "snapshot_ratio"),
])
def test_the_metric_files(name, reader):
    doc = util.load_json("layer_metrics", name + ".json")
    assert doc["reader"] == reader and doc["workloads"] == [CELL]
    assert doc["moves"] == "serve_tokens_per_s"
    entry = [m for m in util.manifest()["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    assert {k: doc[k] for k in entry[0]} == entry[0]
    if reader != "snapshot_ratio":
        assert doc["args"]["kernel"] == "paged_latent_decode"
        assert doc["args"]["program"] == "jit_pstep"
    else:
        with open(os.path.join(BENCH, "layer_metrics",
                               "block_fill_share.sat.json")) as f:
            assert doc["args"] == json.load(f)["args"]
