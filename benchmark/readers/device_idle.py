"""Idle share of the device over the traced slice, from the profiler's
trace (benchmark/trace_reduce.py): 1 - union of device-op intervals /
slice."""


def read(facts):
    device = facts.get("device")
    return None if device is None else 100.0 * device["idle_share"]
