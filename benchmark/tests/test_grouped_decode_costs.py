"""Tests of the grouped decode kernel's benchmark metrics (CPU; `python -m
pytest benchmark/tests -q`): `costs_grouped_decode.grouped_decode_call`
against a hand count, `grouped_decode_roofline.phi` on facts made by hand,
and the three metric files' readers and arguments."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark import costs_grouped_decode as G, util         # noqa: E402
from benchmark.readers import kernel_roofline                  # noqa: E402

CELL = "phi4_mini_flash.reason_saturated"


def _cfg():
    return util.resized(util.load_json("configs", "phi4_mini_flash.json"),
                        False)


def test_one_call_against_a_hand_count():
    """64 slots of 1,650 written rows: 40 heads of 64 score a row (2 x 40
    x 64) and sum its pair's 128-wide values (2 x 40 x 128), 1.6 GFLOP;
    104 pages of 16 rows a slot, a K and a V row of 20 x 64 bfloat16
    each, plus the bfloat16 queries in and outputs out, 0.55 GB: the
    bytes bind, 0.67 ms at 819 GB/s."""
    ops, nbytes = G.grouped_decode_call(_cfg(), {"slots": 64,
                                                 "context": 1650})
    assert ops == 64 * 1650 * (2 * 40 * 64 + 2 * 40 * 128)
    assert 1.6e9 < ops < 1.7e9
    assert nbytes == 64 * 104 * 16 * 2 * 2560 + 64 * 40 * (64 + 128) * 2
    assert 0.54e9 < nbytes < 0.55e9
    assert nbytes / 819e9 > ops / 197e12


def test_the_roofline_reads_the_call_against_its_time(monkeypatch):
    """At the cell's sizes and a call of 0.95 ms, the low end of what
    the kernel is expected to take, the share reads 70 %: under 100."""
    from benchmark.readers import kernel_time

    cfg = _cfg()
    monkeypatch.setattr(kernel_time, "read",
                        lambda facts, kernel, program=None: 0.95)
    snap = {"iterations": 0, "paging": {"live_page_iterations": 0},
            "cache": {"prefill_tokens": 0, "state_resets": 0}}
    close = {"iterations": 100,
             "paging": {"live_page_iterations": 100 * 64 * 104},
             "cache": {"prefill_tokens": 1024, "state_resets": 1}}
    facts = {"config": cfg, "occupancy": [64] * 100,
             "snapshot_open": snap, "snapshot_close": close,
             "peaks": {"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9}}
    doc = util.load_json("layer_metrics", "grouped_decode_roofline.phi.json")
    got = kernel_roofline.read(facts, **doc["args"])
    _, nbytes = G.grouped_decode_call(cfg, {"slots": 64,
                                            "context": 104 * 16})
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.95e-3)
    assert 60 < got < 100


@pytest.mark.parametrize("name, reader", [
    ("grouped_decode_ms.phi", "kernel_time"),
    ("grouped_decode_roofline.phi", "kernel_roofline"),
    ("block_fill_share.phi", "snapshot_ratio"),
])
def test_the_metric_files(name, reader):
    doc = util.load_json("layer_metrics", name + ".json")
    assert doc["reader"] == reader and doc["workloads"] == [CELL]
    assert doc["moves"] == "serve_tokens_per_s"
    entry = [m for m in util.manifest()["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    assert {k: doc[k] for k in entry[0]} == entry[0]
    if reader != "snapshot_ratio":
        assert doc["args"]["kernel"] == "paged_grouped_decode"
        assert doc["args"]["program"] == "jit_pstep"
    else:
        with open(os.path.join(BENCH, "layer_metrics",
                               "block_fill_share.sat.json")) as f:
            assert doc["args"] == json.load(f)["args"]
