"""Tests of `readers/snapshot_per.py` and the six metrics that read the
engine thread's host time by cause (run with the rest by
`python -m pytest benchmark/tests -q`, on the CPU): a plain ratio of
differenced counters, None where a key is missing (a program without
the `host` section) or the window holds no iteration, and the earlier
line with the window's deltas of the whole `host` section."""
from __future__ import annotations

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import util  # noqa: E402

NEW = [f"{m}.{c}" for c in ("sat", "chat")
       for m in ("host_cpu_ms_per_iter", "host_offcpu_ms_per_iter",
                 "gc_ms_per_iter")]


def read(name, facts):
    doc = util.load_json("layer_metrics", name + ".json")
    return util.load_module("readers", doc["reader"]).read(
        facts, **doc.get("args", {}))


def host(it, cpu, off, gc, stalls=0, dropped=0):
    return {"iterations": 10 * it, "host": {
        "iterations": it, "host_ns": cpu + off, "cpu_ns": cpu,
        "offcpu_ns": off, "wait_ns": 7, "gc_ns": gc,
        "gc_collections": [it, 1, 0], "gc_ns_by_gen": [gc - 5, 5, 0],
        "stalls": stalls, "stall_host_ns": 30_000_000 * stalls,
        "stall_cpu_ns": 2_000_000 * stalls, "stall_gc_ns": 0,
        "spans_dropped": dropped}}


FACTS = {"snapshot_open": host(100, 400_000_000, 50_000_000, 1_000_000),
         "snapshot_close": host(1100, 5_400_000_000, 550_000_000,
                                1_300_000, stalls=3, dropped=40)}


def test_six_metrics_in_two_cells():
    docs = [util.load_json("layer_metrics", n + ".json") for n in NEW]
    manifest = {m["name"]: m for m in util.manifest()["per_layer"]}
    for d in docs:
        assert d["reader"] == "snapshot_per" and d["unit"] == "ms"
        assert d["source"] == "program_counter"
        assert d["layer"] == "Engine iteration"
        assert d["args"]["per"] == "host.iterations"
        assert d["args"]["scale"] == 1e-6
        assert {k: d[k] for k in manifest[d["name"]]} == manifest[d["name"]]
        cell = d["workloads"]
        assert cell == (["bart_large_dec.decode_saturated"]
                        if d["name"].endswith(".sat")
                        else ["bart_large_dec.chat_steady"])


def test_a_plain_ratio_of_differenced_counters():
    assert read("host_cpu_ms_per_iter.sat", FACTS) == pytest.approx(5.0)
    assert read("host_offcpu_ms_per_iter.sat", FACTS) == pytest.approx(0.5)
    assert read("gc_ms_per_iter.chat", FACTS) == pytest.approx(0.0003)


def test_a_missing_key_or_no_iteration_reads_none():
    parent = copy.deepcopy(FACTS)
    for s in parent.values():
        del s["host"]
    still = {"snapshot_open": FACTS["snapshot_open"],
             "snapshot_close": FACTS["snapshot_open"]}
    for name in NEW:
        for gone in ({}, parent, still,
                     {"snapshot_open": FACTS["snapshot_open"]}):
            assert read(name, gone) is None


def test_the_earlier_line_gives_the_windows_host_deltas(capsys):
    read("host_cpu_ms_per_iter.sat", FACTS)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1 and lines[0]["of"] == "host.cpu_ns"
    d = lines[0]["snapshot_deltas"]["host"]
    assert d["iterations"] == 1000 and d["stalls"] == 3
    assert d["stall_host_ns"] == 90_000_000
    assert d["stall_cpu_ns"] == 6_000_000 and d["stall_gc_ns"] == 0
    assert d["gc_collections"] == [1000, 0, 0]
    assert d["gc_ns_by_gen"] == [300_000, 0, 0]
    assert d["spans_dropped"] == 40
