"""Tests of the benchmark itself, run by `python -m pytest benchmark/tests -q`
on the CPU (they are not part of tier-1: the benchmark's contract lets a
benchmark PR add files under its own directories only).

- every name and unit in BENCHMARK.json and under benchmark/ is well formed,
  every name resolves to a file, and the two views of cells and metrics
  (BENCHMARK.json; workloads/ and layer_metrics/) agree;
- traffic from one seed is identical twice, differs for another seed, and
  every seed sends the same multiset of sizes and gaps;
- trace_reduce gives the recorded numbers on the small recorded trace;
- the references equal the program at full precision on the CPU, and a
  dropped term moves the result past the stated tolerance;
- a `--rehearse` run of each driver ends in a contract-shaped line.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import costs, trace_reduce, traffic_gen, util  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


manifest = util.manifest


def cells():
    return [util.load_json("workloads", n + ".json")
            for n in util.names_in("workloads")]


def layer_docs():
    return [util.load_json("layer_metrics", n + ".json")
            for n in util.names_in("layer_metrics")]


# ---------------------------------------------------------------- manifest

def test_manifest_keys_and_limits():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark"] and m["command"][1].startswith(
        "benchmark/")
    assert 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert p["source"] in SOURCES
    assert any(e["name"] == "setup_s" for e in m["end_to_end"])


def test_names_and_units_are_well_formed():
    m = manifest()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    names += [w[k] for w in m["workloads"] for k in ("config", "traffic")]
    names += [r for c in m["configs"] for r in c["reduced"]]
    for kind in ("workloads", "layer_metrics", "configs", "traffic"):
        names += util.names_in(kind)
    for kind in ("builders", "drivers", "readers", "reference"):
        names += [n for n in util.names_in(kind, ".py")]
    for n in names:
        assert NAME.match(n), n
    for x in m["end_to_end"] + m["per_layer"] + layer_docs():
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
    for dirpath, _, files in os.walk(BENCH):
        if "__pycache__" in dirpath:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.-]+$", f), os.path.join(dirpath, f)


def test_every_name_resolves():
    for cell in cells():
        cfg = util.load_json("configs", cell["config"] + ".json")
        mix = util.load_json("traffic", cell["traffic"] + ".json")
        for kind, name in (("builders", cfg["builder"]),
                           ("reference", cfg["reference"]),
                           ("drivers", mix["driver"])):
            assert os.path.isfile(os.path.join(BENCH, kind, name + ".py"))
    for doc in layer_docs():
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           doc["reader"] + ".py"))
    for c in manifest()["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


def test_the_two_views_agree():
    m = manifest()
    by_file = {c["name"]: {k: c[k] for k in ("config", "traffic", "chips",
                                             "why")} for c in cells()}
    by_manifest = {w["name"]: {k: w[k] for k in ("config", "traffic",
                                                 "chips", "why")}
                   for w in m["workloads"]}
    assert by_file == by_manifest
    keys = ("name", "unit", "better", "source", "layer", "moves",
            "workloads")
    from_files = sorted(({k: d[k] for k in keys if k in d}
                         for d in layer_docs()), key=lambda d: d["name"])
    assert from_files == sorted(m["per_layer"], key=lambda d: d["name"])
    for c in m["configs"]:
        doc = util.load_json("configs", c["name"] + ".json")
        assert (doc["source"], doc["reduced"]) == (c["source"], c["reduced"])
    # every cell reports setup_s, another end-to-end metric and a per-layer
    # one; a per-layer metric moves a metric each of its cells reports
    reported = {c["name"]: set(c["end_to_end"]) for c in cells()}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for cell, mets in reported.items():
        assert "setup_s" in mets and len(mets) >= 2 and mets <= set(e2e)
        for name in mets:
            assert cell in e2e[name].get("workloads", [cell])
        assert any(cell in p.get("workloads", [cell])
                   for p in m["per_layer"])
    for name, e in e2e.items():
        for cell in e.get("workloads", reported):
            assert name in reported[cell], (name, cell)
    for p in m["per_layer"]:
        for cell in p.get("workloads", reported):
            assert p["moves"] in reported[cell], (p["name"], cell)
    layers = {}
    for p in m["per_layer"]:
        layers.setdefault(p["layer"].lower(), set()).add(p["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_unknown_device_kind_is_an_error():
    assert util.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert util.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        util.peak_for("TPU v9 imaginary")


# ----------------------------------------------------------------- traffic

@pytest.mark.parametrize("mix_name", ["short_backlog", "long_prompt_poisson"])
def test_serving_traffic_is_seeded_and_seed_keeps_the_work(mix_name):
    mix = util.resized(util.load_json("traffic", mix_name + ".json"), False)

    def sent(seed, n=300):
        mk = traffic_gen.RequestMaker(mix, seed, 50265, (4, 8))
        return [mk.next() for _ in range(n)]

    big = 3000000019            # over 2**31, as the driver's seeds are
    a, b, c = sent(big), sent(big), sent(7)
    for (p1, m1, n1), (p2, m2, n2) in zip(a, b):
        assert np.array_equal(p1, p2) and np.array_equal(m1, m2) and n1 == n2
    assert any(not np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    s1, s2 = (traffic_gen.request_sizes(mix, s) for s in (big, 7))
    assert not np.array_equal(s1, s2)
    assert sorted(map(tuple, s1)) == sorted(map(tuple, s2))
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert s1[:, 0].min() >= lo and s1[:, 0].max() <= hi
    assert s1[:, 1].min() >= mix["new_tokens"]["min"]
    assert s1[:, 1].max() <= mix["new_tokens"]["max"]
    from paddle_tpu.core.bucketing import bucket_size
    assert {bucket_size(int(p)) for p in s1[:, 0]} <= set(
        mix["prompt_buckets"])


def test_arrivals_are_the_mix_own_and_fill_the_span():
    arr = util.load_json("traffic", "long_prompt_poisson.json")["arrivals"]
    a = traffic_gen.arrival_offsets(arr, 30.0)
    assert np.array_equal(a, traffic_gen.arrival_offsets(arr, 30.0))
    assert not np.array_equal(a, traffic_gen.arrival_offsets(arr, 30.0, 7))
    other = traffic_gen.arrival_offsets(dict(arr, gaps_seed=5), 30.0)
    assert not np.array_equal(a, other)
    assert len(a) == round(arr["rate_per_s"] * 30.0)
    assert a[0] == 0.0 and a[-1] < 30.0 and (np.diff(a) > 0).all()
    gaps = np.diff(np.append(a, 30.0))
    assert 0.7 < gaps.std() / gaps.mean() < 1.3        # exponential: cv 1
    bursty = traffic_gen.arrival_offsets(
        dict(arr, process="gamma", cv=3.0), 30.0)
    g = np.diff(np.append(bursty, 30.0))
    assert len(bursty) == len(a) and g.std() / g.mean() > 1.8


def test_training_batches_are_seeded():
    job = {"batch": 4, "seq_len": 16}
    a, b, c = (traffic_gen.BatchMaker(job, s, 512, 2) for s in (5, 5, 6))
    (ia, la), (ib, lb), (ic, _) = a.next(), b.next(), c.next()
    assert np.array_equal(ia, ib) and np.array_equal(la, lb)
    assert not np.array_equal(ia, ic) and ia.shape == (4, 16)
    assert not np.array_equal(a.next()[0], ia)      # a new batch every step


# ------------------------------------------------------------ trace_reduce

def test_trace_reduce_on_the_small_recorded_trace():
    with open(os.path.join(BENCH, "small_trace.json")) as f:
        doc = json.load(f)
    want = doc["expected"]
    red = trace_reduce.reduce(doc["planes"])
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert red["idle_share"] == pytest.approx(want["idle_share"])
    assert red["devices"] == 1
    for name, sec in want["self_seconds"].items():
        assert red["ops"][name][0] == pytest.approx(sec), name
    assert sum(s for s, _ in red["ops"].values()) == pytest.approx(
        red["busy_s"])                       # self times add up to busy
    assert red["device_ops"][0][0] == want["top_op"]
    assert red["idle_gaps"][0] == [want["gaps"][0][0], pytest.approx(
        want["gaps"][0][1])]
    assert sorted(g[0] for g in red["idle_gaps"]) == sorted(
        g[0] for g in want["gaps"])
    sec, calls = trace_reduce.op_time(red, r"paged_flash_decode")
    assert (sec, calls) == (pytest.approx(2e-6), 1)
    assert trace_reduce.op_time(red, r"no_such_kernel") == (0, 0)
    with pytest.raises(ValueError):
        trace_reduce.reduce([p for p in doc["planes"]
                             if not p["name"].startswith("/device")])


def test_flops_per_token_of_ernie_base():
    cfg = util.load_json("configs", "ernie_base.json")
    per_token = costs.ernie_train_flops_per_token(cfg, 128)
    # 6 x 85 M encoder matmul weights + attention at s = 128
    assert per_token == 3 * 12 * (8 * 768 ** 2 + 4 * 768 * 3072
                                  + 4 * 128 * 768)
    assert 0.50e9 < per_token < 0.54e9


# -------------------------------------------------------------- references

def test_ernie_reference_equals_the_program_and_sees_a_dropped_term():
    import jax
    import jax.numpy as jnp

    cfg = util.resized(util.load_json("configs", "ernie_base.json"), True)
    cfg["trainer"] = dict(cfg["trainer"], compute_dtype="float32")
    tr = util.load_module("builders", "ernie_trainer").build(
        cfg, 11, jax.devices())
    ref = util.load_module("reference", "ernie_base")
    ids = traffic_gen.BatchMaker({"batch": 2, "seq_len": 16}, 3,
                                 cfg["vocab_size"], 2).next()[0]
    got = np.asarray(tr.eval_step((ids,)), np.float32)
    want = np.asarray(ref.logits(tr.params, jnp.asarray(ids), cfg))
    assert np.abs(got - want).max() < 1e-4
    atol = cfg["check"]["logits_atol"]
    for dropped in ("ernie.embeddings.position_embeddings.weight",
                    "ernie.encoder.layers.1.linear2.weight",
                    "ernie.encoder.layers.0.self_attn.v_proj.weight"):
        p = dict(tr.params)
        p[dropped] = jnp.zeros_like(p[dropped])
        off = np.asarray(ref.logits(p, jnp.asarray(ids), cfg))
        assert np.abs(off - want).max() > atol, dropped


# ---------------------------------------------------------------- the runs

def contract_line(out):
    line = json.loads(out.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    return line


@pytest.mark.parametrize("cell", [c["name"] for c in cells()])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_in_a_contract_line(cell, trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3000000019", "--seconds", "2", "--trace", str(trace),
         "--rehearse"], capture_output=True, text=True, env=env,
        timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = contract_line(r.stdout)
    doc = util.load_json("workloads", cell + ".json")
    m = manifest()
    if trace:
        allowed = {p["name"] for p in m["per_layer"]
                   if cell in p.get("workloads", [cell])}
        assert set(line["metrics"]) <= allowed
        assert "programs_compiled" in line["metrics"]
    else:
        assert set(line["metrics"]) == set(doc["end_to_end"])
        units = {e["name"]: e["unit"] for e in m["end_to_end"]}
        assert all(v["unit"] == units[k]
                   for k, v in line["metrics"].items())


def test_without_a_tpu_there_is_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "ernie_base.finetune", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
