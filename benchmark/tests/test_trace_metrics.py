"""Tests of the per-layer readers PR 25 added (run with the rest by
`python -m pytest benchmark/tests -q`, on the CPU):

- each device-trace reader gives the recorded number on
  `small_trace_named.json`, a cut of two real chip traces, and the numbers
  that can be counted by hand there come out as counted;
- the span and counter readers on hand-made `facts`;
- every reader returns None on facts without its source: a run without a
  trace, and a trace of a program that has no names (the parent of PR 25);
  a trace that was reduced and cannot be read again raises.
"""
from __future__ import annotations

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark import named_trace, trace_reduce, util  # noqa: E402

NEW = ("paged_decode_ms.sat", "pool_copy_share.sat",
       "flash_prefill_ms.steady", "pjoin_share.steady",
       "host_ms_per_iter.sat", "host_ms_per_iter.steady",
       "admit_share.steady", "pages_in_use_share.sat",
       "pages_in_use_share.steady", "next_key_ms.train", "shard_ms.train",
       "enqueue_ms.train", "idle_attributed.sat", "idle_attributed.steady",
       "idle_attributed.train")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "small_trace_named.json")) as f:
        return json.load(f)


def read(name, facts):
    doc = util.load_json("layer_metrics", name + ".json")
    return util.load_module("readers", doc["reader"]).read(
        facts, **doc.get("args", {}))


def test_the_fifteen_metrics_exist_with_at_most_six_readers():
    docs = [util.load_json("layer_metrics", n + ".json") for n in NEW]
    assert len({d["name"] for d in docs}) == 15
    assert {d["reader"] for d in docs} == {
        "kernel_time", "program_share", "engine_span", "snapshot_ratio",
        "xplane_span", "idle_by_span"}
    assert {d["layer"] for d in docs} == {
        "Kernels", "Pool layers", "Engine iteration", "Trainer", "Device"}


def test_a_trace_is_loaded_once_and_only_where_one_was_reduced(tmp_path):
    import jax
    import jax.numpy as jnp

    d = str(tmp_path / "trace")
    f = jax.jit(lambda x: x @ x + 1)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("train.enqueue", span_id=8):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    # the CPU rehearsal: run.py reduced nothing, so nothing is read
    facts = {"trace_dir": d, "device": None}
    assert named_trace.planes_of(facts) is None
    assert read("enqueue_ms.train", facts) is None
    # a reduced trace is read again through trace_reduce, once
    facts = {"trace_dir": d, "device": {"busy_s": 1.0}}
    planes = named_trace.planes_of(facts)
    assert planes is named_trace.planes_of(facts)
    assert planes == trace_reduce.load_xplane(d)
    assert read("enqueue_ms.train", facts) > 0
    assert read("next_key_ms.train", facts) is None       # no such event
    # a CPU trace has no device plane: the device readers leave out
    for name in ("paged_decode_ms.sat", "pool_copy_share.sat",
                 "idle_attributed.sat"):
        assert read(name, facts) is None
    # and one that cannot be read again is an error, not a silence
    with pytest.raises(FileNotFoundError):
        read("enqueue_ms.train", {"trace_dir": str(tmp_path / "none"),
                                  "device": {"busy_s": 1.0}})


@pytest.mark.parametrize("name", [n for n in NEW if n not in (
    "host_ms_per_iter.sat", "host_ms_per_iter.steady",
    "admit_share.steady", "pages_in_use_share.sat",
    "pages_in_use_share.steady", "idle_attributed.sat")])
def test_trace_readers_on_the_recorded_cut(recorded, name):
    part = "training" if name.endswith(".train") else "serving"
    got = read(name, {"named_planes": recorded[part]["planes"]})
    assert got == pytest.approx(recorded[part]["expected"][name])


def test_the_recorded_cut_by_hand(recorded, capsys):
    planes = recorded["serving"]["planes"]
    table, runs = named_trace.ops_by_program(planes)
    assert runs["jit_pstep"] == runs["jit_pjoin"] == 1
    # one pstep in the cut: twelve kernel calls, one a layer, found by name
    assert table[("jit_pstep", "paged_flash_decode")][1] == 12
    # flash_fwd is in pjoin only
    assert [k for k in table if k[1] == "flash_fwd"] == [
        ("jit_pjoin", "flash_fwd")]
    # the pool copies of pstep: 72 = 12 layers x (K, V) x three changes
    # of layout, most of which carry no name of the program's
    assert table[("jit_pstep", "copy")][1] == 72
    # self times add up to busy, as in trace_reduce
    red = trace_reduce.reduce(planes)
    assert sum(s for s, _ in table.values()) == pytest.approx(
        red["busy_s"])
    # idle time: every gap, split by the innermost annotation
    gaps = named_trace.idle_gaps(planes)
    idle = sum(e - s for s, e in gaps) * 1e-9
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
    capsys.readouterr()
    share = read("idle_attributed.steady", {"named_planes": planes})
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    by = line["idle_by_span"]
    assert line["idle_s"] == pytest.approx(idle)
    assert by["step.readback"] > 0 and line["not_in_a_span_s"] > 0
    assert "iteration" not in by             # a root says nothing
    assert share == pytest.approx(100 * sum(by.values()) / idle)
    assert sum(by.values()) + line["not_in_a_span_s"] == \
        pytest.approx(idle)
    # an idle spin of the engine, which the profiler has seen although
    # the tracer drops it, attributes nothing: put one after the window
    w1 = trace_reduce.window_of(planes)[1]
    spun = copy.deepcopy(planes)
    trace_reduce.host_planes(spun)[0]["lines"][0]["events"] += [
        ["iteration", w1 + 100, 1000], ["iter.harvest", w1 + 200, 800]]
    doc = util.load_json("layer_metrics", "idle_attributed.steady.json")
    reader = util.load_module("readers", doc["reader"])
    for work, more in ((doc["args"]["work"], 0.0), (None, 800e-9)):
        capsys.readouterr()
        reader.read({"named_planes": spun}, **dict(doc["args"], work=work))
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["idle_s"] == pytest.approx(idle + 1100e-9)
        assert sum(line["idle_by_span"].values()) == pytest.approx(
            sum(by.values()) + more)


def test_idle_goes_to_the_innermost_annotation(capsys):
    """A gap inside a child cuts parent and child to the same piece; it
    is the child's."""
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%a = f32[8] add(x, y)", 0, 10], ["%b = f32[8] add(x, y)", 40,
                                               10],
            ["%c = f32[8] add(x, y)", 90, 10]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["iteration", 5, 90], ["decode.step", 8, 80],
            ["step.readback", 12, 30], ["other", 0, 100]]}]}]
    assert named_trace.idle_gaps(planes) == [(10, 40), (50, 90)]
    share = util.load_module("readers", "idle_by_span").read(
        {"named_planes": planes}, roots=["iteration"],
        spans=r"^(decode|step)\.")
    by = json.loads(capsys.readouterr().out.strip().splitlines()[-1])[
        "idle_by_span"]
    # [10, 40]: decode.step 10-12, step.readback 12-40;
    # [50, 90]: decode.step 50-88, iteration 88-90
    assert by == pytest.approx({"step.readback": 28e-9,
                                "decode.step": 40e-9})
    assert share == pytest.approx(100 * 68 / 70)
    assert list(named_trace.clipped([(5, 12), (20, 25), (95, 120)],
                                    "x", 10, 100)) == [
        ("x", 10, 12), ("x", 20, 25), ("x", 95, 100)]


def span_facts():
    """Two iterations inside the window and one that starts before it."""
    spans = [("iteration", 0, 0.5, 0.9),          # before the window
             ("iteration", 0, 1.0, 1.3), ("iter.admit", 0, 1.01, 1.05),
             ("join", 7, 1.02, 1.04), ("iter.tok0", 0, 1.05, 1.07),
             ("decode.step", 0, 1.08, 1.29),
             ("step.readback", 0, 1.10, 1.28),
             ("iteration", 0, 1.4, 1.8), ("iter.admit", 0, 1.41, 1.42),
             ("iter.tok0", 0, 1.42, 1.42), ("decode.step", 0, 1.45, 1.79),
             ("step.readback", 0, 1.50, 1.78),
             ("queue", 9, 0.0, 5.0)]
    return {"spans": spans, "iteration_t": [0.95, 1.3, 1.8, 1.9]}


def test_engine_span_on_hand_made_facts():
    facts = span_facts()
    # (0.3 - 0.18 - 0.02) + (0.4 - 0.28 - 0) over two iterations
    assert read("host_ms_per_iter.sat", facts) == pytest.approx(110.0)
    assert read("host_ms_per_iter.steady", facts) == pytest.approx(110.0)
    # (0.04 + 0.02 + 0.01 + 0) / (0.3 + 0.4)
    assert read("admit_share.steady", facts) == pytest.approx(10.0)
    for gone in ({}, {"spans": [], "iteration_t": [0, 1]},
                 {"spans": [s for s in facts["spans"]
                            if s[0] != "iteration"],
                  "iteration_t": facts["iteration_t"]}):
        assert read("host_ms_per_iter.sat", gone) is None
        assert read("admit_share.steady", gone) is None


def test_snapshot_ratio_on_hand_made_facts():
    snap = lambda it, pi: {"iterations": it, "paging": {  # noqa: E731
        "pages_total": 4096, "page_iterations": pi, "pages_in_use": 1}}
    facts = {"snapshot_open": snap(100, 50000),
             "snapshot_close": snap(300, 50000 + 200 * 512)}
    assert read("pages_in_use_share.sat", facts) == pytest.approx(12.5)
    assert read("pages_in_use_share.steady", facts) == pytest.approx(12.5)
    old = copy.deepcopy(facts)              # the parent: no such counter
    for s in old.values():
        del s["paging"]["page_iterations"]
    for gone in ({}, old, {"snapshot_open": snap(5, 1),
                           "snapshot_close": snap(5, 1)}):
        assert read("pages_in_use_share.sat", gone) is None


@pytest.mark.parametrize("name", NEW)
def test_every_reader_returns_none_without_its_source(recorded, name):
    assert read(name, {}) is None               # --trace 0: no trace
    assert read(name, {"named_planes": None}) is None
    # a trace of a program without the names: kernels, programs and
    # annotations as the parent of PR 25 has them
    planes = copy.deepcopy(recorded["training" if name.endswith(".train")
                                    else "serving"]["planes"])
    mine = ("iteration", "iter.", "step.", "decode.", "join", "train.")
    for p in planes:
        for ln in p["lines"]:
            for ev in ln["events"]:
                ev[0] = (ev[0].replace("paged_flash_decode", "step_fn")
                         .replace("flash_fwd", "join_fn")
                         .replace("jit_pstep", "jit_step_fn")
                         .replace("jit_pjoin", "jit_join_fn"))
            if p["name"].startswith("/host:"):
                ln["events"] = [ev for ev in ln["events"]
                                if not ev[0].startswith(mine)]
    assert read(name, {"named_planes": planes}) is None
