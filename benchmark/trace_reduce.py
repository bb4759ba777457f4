"""From a profiler trace to numbers: device busy time, idle share, the
operations that took most time, the longest idle gaps and what the host
was doing in each, and the time of operations matching a name.

The reduction works on a neutral form, so that it can be checked on a small
recorded trace (`small_trace.json`, beside this file) without a device:

    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops",
                          "events": [[name, start_ns, duration_ns], ...]}]},
              {"name": "/host:CPU", "lines": [...]}]

`load_xplane` turns the `.xplane.pb` the JAX profiler writes into that form
with nothing but JAX (`jax.profiler.ProfileData`).

Definitions, so that every PR computes the same number the same way:
- a device plane is one whose name starts with `/device:TPU:`; its
  operations are the events of its line `XLA Ops` (if a plane has no such
  line, of every line but the step and module summaries);
- the window is the span from the earliest start to the latest end over
  the events of device AND host planes: the host records the slice's first
  and last calls, so an idle device at either end still counts as idle;
- busy is the length of the union of a device's operation intervals inside
  the window, averaged over the device planes; idle share is 1 - busy /
  window;
- an operation's time is its SELF time: its duration less the part its
  nested children cover (a `while` holds the operations of its body on
  the same line), so times add up to busy and nothing counts twice;
- a gap is a maximal interval of the window in which no operation runs on
  the first device; its label is the name of the shortest host event that
  covers at least half of it, else of the one that overlaps it longest,
  else `unattributed`.
"""
from __future__ import annotations

import glob
import os
import re

_SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                  "Framework Name Scope", "Source code")


def load_xplane(trace_dir):
    """The newest `.xplane.pb` under a profiler log directory -> planes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        if not (plane.name.startswith("/device:")
                or plane.name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


_HLO = re.compile(r"^%?(?P<id>[^\s=]+) = (?P<rest>.*)$", re.S)
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_OPCODE = re.compile(r"[\s)]([a-z][a-z0-9-]*)\(")
_KIND = re.compile(r"kind=k(\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name):
    """The trace names a device operation by its whole HLO line (hundreds
    of characters). Keep what identifies it: its id, its opcode (or fusion
    kind, or custom-call target) and its first output shape, as in
    `fusion.9 Loop f32[30522,768]`. A name that is not an HLO line is kept
    (cut to 80 characters)."""
    m = _HLO.match(name)
    if m is None:
        return name[:80]
    rest = m.group("rest")
    shape = _SHAPE.search(rest)
    what = _TARGET.search(rest) or _KIND.search(rest) or _OPCODE.search(rest)
    parts = [m.group("id")]
    if what:
        parts.append(what.group(1))
    if shape:
        parts.append(shape.group(0))
    return " ".join(parts)[:80]


def device_planes(planes):
    return [p for p in planes if p["name"].startswith("/device:TPU:")]


def host_planes(planes):
    return [p for p in planes if p["name"].startswith("/host:")]


def op_events(plane, full=None):
    """[(short name, start_ns, end_ns)] of a device plane's operations;
    `full`, if given, collects one whole name per short name."""
    lines = [ln for ln in plane["lines"] if ln["name"] == "XLA Ops"] or \
        [ln for ln in plane["lines"] if ln["name"] not in _SUMMARY_LINES]
    out = []
    for ln in lines:
        for n, s, d in ln["events"]:
            if d > 0:
                short = short_name(n)
                if full is not None:
                    full.setdefault(short, n)
                out.append((short, s, s + d))
    out.sort(key=lambda e: (e[1], -e[2]))
    return out


def merged(intervals):
    """Union of (start, end) intervals, as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """{name: [self seconds, calls]} from events sorted by (start, -end):
    a nested event's duration is taken off the event that holds it."""
    out = {}
    stack = []       # [name, end, self_ns]

    def close(item):
        rec = out.setdefault(item[0], [0.0, 0])
        rec[0] += item[2] * 1e-9
        rec[1] += 1

    for name, s, e in events:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    while stack:
        close(stack.pop())
    return out


def window_of(planes):
    starts, ends = [], []
    for p in device_planes(planes) + host_planes(planes):
        for ln in p["lines"]:
            for _, s, d in ln["events"]:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        raise ValueError("the trace holds no event")
    return min(starts), max(ends)


def label_gap(hosts, g0, g1):
    """Of the host events that cover at least half the gap, the shortest
    (a thread's long-lived outer event covers every gap and says nothing);
    if none covers half, the one that overlaps it longest."""
    half = 0.5 * (g1 - g0)
    best, best_key = "unattributed", None
    for p in hosts:
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                overlap = min(g1, s + d) - max(g0, s)
                if overlap <= 0:
                    continue
                key = (1, -d) if overlap >= half else (0, overlap)
                if best_key is None or key > best_key:
                    best, best_key = name, key
    return best


def reduce(planes, top=10):
    """The whole reduction; see the module docstring for the definitions.
    Raises if no operation ran on a device: such a trace proves nothing."""
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no /device:TPU: plane; planes: "
                         + ", ".join(p["name"] for p in planes))
    w0, w1 = window_of(planes)
    busy, ops, full = [], {}, {}
    first_busy = None
    for p in devs:
        events = op_events(p, full)
        union = merged((max(s, w0), min(e, w1)) for _, s, e in events)
        busy.append(sum(e - s for s, e in union) * 1e-9)
        if first_busy is None:
            first_busy = union
        for name, (sec, calls) in self_times(events).items():
            rec = ops.setdefault(name, [0.0, 0])
            rec[0] += sec / len(devs)
            rec[1] += calls
    busy_s = sum(busy) / len(busy)
    if busy_s <= 0:
        raise ValueError("no operation ran on the device in the trace")
    edges = [w0] + [t for iv in first_busy for t in iv] + [w1]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:top]
    hosts = host_planes(planes)
    window_s = (w1 - w0) * 1e-9
    by_kind = {}
    for name, (sec, _) in ops.items():
        parts = name.split(" ")
        kind = parts[1] if len(parts) > 1 else parts[0]
        by_kind[kind] = by_kind.get(kind, 0.0) + sec
    return {
        "by_kind": sorted(by_kind.items(), key=lambda kv: -kv[1]),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": len(devs),
        "ops": ops,
        "full_names": full,
        "device_ops": [[n, s] for n, (s, _) in sorted(
            ops.items(), key=lambda kv: -kv[1][0])[:top]],
        "idle_gaps": [[label_gap(hosts, g0, g1), d * 1e-9]
                      for d, g0, g1 in gaps],
    }


def op_time(reduced, pattern):
    """(self seconds, calls) of the operations whose name matches the
    regular expression, per device; (0, 0) if none does."""
    rx = re.compile(pattern)
    sec = calls = 0
    for name, (s, c) in reduced["ops"].items():
        if rx.search(name):
            sec += s
            calls += c
    return sec, calls // max(reduced["devices"], 1)
