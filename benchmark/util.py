"""Small helpers every part of the benchmark shares. Nothing here touches
JAX: importing this file starts nothing."""
from __future__ import annotations

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name, rehearse):
    """(cell, configuration, traffic mix) of a cell, found by name, the
    last two with their `rehearse` overrides folded in or dropped."""
    cell = load_json("workloads", name + ".json")
    return (cell,
            resized(load_json("configs", cell["config"] + ".json"), rehearse),
            resized(load_json("traffic", cell["traffic"] + ".json"),
                    rehearse))


def load_module(kind, name):
    """The module `benchmark/<kind>/<name>.py`, found by name. A later PR
    adds a file; nothing here lists what exists."""
    if not os.path.isfile(os.path.join(HERE, kind, name + ".py")):
        raise FileNotFoundError(f"benchmark/{kind}/{name}.py does not exist")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(f"benchmark.{kind}.{name}")


def names_in(kind, ext=".json"):
    d = os.path.join(HERE, kind)
    return sorted(f[:-len(ext)] for f in os.listdir(d)
                  if f.endswith(ext) and not f.startswith("_"))


def say(**record):
    """One JSON object on a line of its own: everything but the result."""
    print(json.dumps(record, default=float), flush=True)


def percentile(values, q):
    """Linear-interpolated percentile; None for an empty sample."""
    import numpy as np

    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def peak_for(device_kind):
    peaks = load_json("peaks.json")["devices"]
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json: add its published peaks "
                       f"with their source, do not default")
    return peaks[device_kind]


def resized(doc, rehearse):
    """A config or traffic document with its `rehearse` overrides folded
    in (tiny sizes for the CPU tests) or dropped (the real run)."""
    out = {k: v for k, v in doc.items() if k != "rehearse"}
    if rehearse:
        for k, v in doc.get("rehearse", {}).items():
            if isinstance(v, dict) and isinstance(out.get(k), dict):
                out[k] = {**out[k], **v}
            else:
                out[k] = v
    return out
