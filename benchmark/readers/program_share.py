"""Share, in %, of the device's busy time in the traced slice that goes to
the operations of one compiled program (`program`: the module's name,
`jit_pstep`, which PR 25 makes the kind of the pool's program), or to one
kind of operation inside it (`kind`: `copy`). An operation belongs to the
program whose `XLA Modules` event holds it in time, so the copies the
compiler put in, which carry no name of the program's, count too
(benchmark/README_tracing.md). Self times, so shares add up to 100. An
earlier line gives the program's runs and its share by kind of operation.
None where no such program ran (the parent of PR 25 names none) or there is
no device trace (`--trace 0`, the CPU rehearsal)."""
from benchmark import named_trace
from benchmark.util import say


def read(facts, program, kind=None):
    planes = named_trace.planes_of(facts)
    found = planes and named_trace.ops_by_program(planes)
    if not found or program not in found[1]:
        return None
    table, runs = found
    busy = sum(s for s, _ in table.values())
    mine = {k[1]: s for k, (s, _) in table.items() if k[0] == program}
    say(program=program, runs=runs[program],
        percent_of_busy_by_kind={k: 100.0 * s / busy for k, s in sorted(
            mine.items(), key=lambda kv: -kv[1])[:8]})
    hit = sum(mine.values()) if kind is None else mine.get(kind)
    return None if hit is None else 100.0 * hit / busy
