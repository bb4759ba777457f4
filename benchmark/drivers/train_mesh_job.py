"""`train_job`'s fine-tuning job on a mesh of several chips: the same
loop, checks and facts, over a trainer whose layout the traffic mix names.

Reads from the mix, besides what `train_job` reads: `mesh` ({"dp": 2,
"tp": 2}) and `builder` (a builder whose `build` takes the layout as a
fourth argument; the configuration's own builder fixes one chip). Leaves
in `run.facts` what `train_job` leaves, and `mesh`.

The CPU rehearsal of a four-chip cell needs four virtual devices: asked
of XLA here, before JAX starts its backend, and only under `--rehearse`."""
from __future__ import annotations

import functools
import os
import sys
import types

from benchmark import util
from benchmark.drivers import train_job

if "--rehearse" in sys.argv and "host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")


def run(run):
    layout = run.traffic["mesh"]
    build = util.load_module("builders", run.traffic["builder"]).build
    run.builder = types.SimpleNamespace(
        build=functools.partial(build, layout=layout))
    run.facts["mesh"] = layout
    return train_job.run(run)
