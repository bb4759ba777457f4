"""Device time of a Pallas kernel found by the name its `pallas_call` gives
it (`name="paged_flash_decode"`: the HLO instruction is
`%paged_flash_decode.<n>`, whatever its shapes), optionally only inside one
compiled program (`program`: the module's name, `jit_pjoin`), from the
profiler trace of the slice: mean self time of one call, in ms. An earlier
line gives the calls, and how many a run of each program makes. None where
no operation has the name (the parent of PR 25 names no kernel) or there is
no device trace (`--trace 0`, the CPU rehearsal)."""
from benchmark import named_trace
from benchmark.util import say


def read(facts, kernel, program=None):
    planes = named_trace.planes_of(facts)
    found = planes and named_trace.ops_by_program(planes)
    if not found:
        return None
    table, runs = found
    hit = {k: v for k, v in table.items() if k[1] == kernel
           and program in (None, k[0])}
    calls = sum(c for _, c in hit.values())
    if not calls:
        return None
    total = sum(s for s, _ in hit.values())
    say(kernel=kernel, program=program, calls=calls, seconds=total,
        calls_a_run={str(k[0]): c / runs[k[0]]
                     for k, (_, c) in hit.items() if k[0] in runs})
    return 1e3 * total / calls
