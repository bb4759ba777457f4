"""Jaxpr-level program analyzer: the compiled-program half of the
static checker.

Walks the closed jaxpr of every serving/decode program an engine's
`precompile()` enumeration (`_startup_programs`) would ready — plus the
fused optimizer step — WITHOUT compiling anything (`jitted.trace(*args)`
is a pure trace), and lints the invariants the runtime sentinels can
only catch when a lucky code path trips them:

  PTA101  large baked-in constants (closed-over arrays: a changed value
          retraces AND keeps a resident duplicate per program)
  PTA102  un-donated large carries — an input whose shape/dtype round-
          trips to an output; without `donate_argnums` XLA must copy it
          (for the serving pool: the whole KV cache) every dispatch
  PTA103  dtype-promotion surprises: float-widening converts and any
          float64 appearing in a program
  PTA104  host callbacks / transfers inside the jitted body
  PTA105  (sharded programs) carries with no `with_sharding_constraint`
          coverage — the every-carry contract of serving/sharded.py

Tracing happens under `trace.suppress_observation()` with the owner's
trace counter restored, so analyzing a LIVE engine never trips the
retrace sentinel or skews session counters (the same discipline as
profiler.costs' deliberate re-lower).
"""
from __future__ import annotations

import numpy as np

from ..profiler import trace as _trace
from .findings import Finding

__all__ = ["analyze_program", "analyze_engine",
           "analyze_fused_optimizer", "engine_programs"]

#: primitives that call back into the host / move data across the
#: host-device boundary from inside a compiled body
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "outside_call", "infeed", "outfeed",
})

_FLOATS = ("bfloat16", "float16", "float32", "float64")


def _nbytes(aval):
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * \
            np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _kind_of(key):
    return key[0] if isinstance(key, tuple) and key else str(key)


def _trace_restoring(owner, key, jitted, args):
    """jitted.trace(*args) with observation suppressed and the owner's
    trace counter restored — the body's `trace_counts[key] += 1` side
    effect must not look like a compile to sentinels/sessions."""
    counter = getattr(owner, "trace_counts", None)
    with _trace.suppress_observation():
        before = None if counter is None else counter[key]
        try:
            return jitted.trace(*args)
        finally:
            if counter is not None:
                counter[key] = before


def _flat_donated(traced):
    """Per-flat-invar donated flags, aligned with jaxpr invar order."""
    import jax

    try:
        info = traced.args_info
    except Exception:
        return None
    leaves = jax.tree_util.tree_leaves(
        info, is_leaf=lambda x: hasattr(x, "donated"))
    return [bool(getattr(x, "donated", False)) for x in leaves]


def _flat_argnums(args):
    """argnum per flat leaf, aligned with jaxpr invar order."""
    import jax

    out = []
    for i, a in enumerate(args):
        out.extend([i] * len(jax.tree_util.tree_leaves(a)))
    return out


def analyze_program(key, jitted, args, *, owner="program",
                    sharded=False, large_bytes=1 << 20,
                    declared_donated=(), owner_obj=None):
    """Lint ONE compiled program. `jitted` is the jax.jit-wrapped
    callable (an engine `build()` result), `args` example arguments
    shaped exactly like the runtime calls. `declared_donated` marks
    argnums the caller donates by contract even where the live wrapper
    skips it (backends without aliasing support). Returns findings."""
    kind = _kind_of(key)
    where = f"{owner}:{key!r}"
    traced = _trace_restoring(owner_obj, key, jitted, args)
    closed = traced.jaxpr
    jaxpr = closed.jaxpr
    findings = []

    # ---- PTA101: large baked-in constants ----
    for c in closed.consts:
        size = getattr(c, "size", None)
        dt = getattr(c, "dtype", None)
        if size is None or dt is None:
            continue
        nb = int(size) * np.dtype(str(dt)).itemsize
        if nb >= large_bytes:
            findings.append(Finding(
                "PTA101", where,
                f"program bakes in a {nb}-byte constant "
                f"{getattr(c, 'shape', ())}:{dt} — pass it as an "
                f"argument (a changed value retraces; the literal "
                f"stays resident per executable)",
                baseline_key=f"{owner}:{kind}:const"))

    # ---- PTA102: un-donated large carries ----
    donated = _flat_donated(traced) or [False] * len(jaxpr.invars)
    try:
        explicit = set(traced.donate_argnums)
    except Exception:
        explicit = set()
    explicit |= set(declared_donated)
    argnums = _flat_argnums(args)
    out_sigs = {}
    for v in jaxpr.outvars:
        av = getattr(v, "aval", None)
        if av is not None:
            out_sigs[(tuple(av.shape), str(av.dtype))] = \
                out_sigs.get((tuple(av.shape), str(av.dtype)), 0) + 1
    undonated = {}
    for i, v in enumerate(jaxpr.invars):
        av = getattr(v, "aval", None)
        if av is None or _nbytes(av) < large_bytes:
            continue
        sig = (tuple(av.shape), str(av.dtype))
        if sig not in out_sigs:
            continue                      # not a carry (params etc.)
        argnum = argnums[i] if i < len(argnums) else -1
        if donated[i] or argnum in explicit:
            continue
        undonated.setdefault(argnum, []).append(
            f"{sig[1]}{list(sig[0])}")
    for argnum, leaves in sorted(undonated.items()):
        findings.append(Finding(
            "PTA102", where,
            f"arg {argnum} carries {len(leaves)} large un-donated "
            f"buffer(s) that round-trip to outputs "
            f"(e.g. {leaves[0]}) — donate_argnums would alias them "
            f"in place instead of copying per dispatch",
            baseline_key=f"{owner}:{kind}:arg{argnum}"))

    # ---- PTA103 / PTA104: eqn sweep ----
    f64_hit = False
    for eqn in jaxpr.eqns:
        prim = str(eqn.primitive)
        if prim in CALLBACK_PRIMITIVES:
            cb = eqn.params.get("callback", "")
            findings.append(Finding(
                "PTA104", where,
                f"host primitive `{prim}` inside the compiled body "
                f"({cb!r}) — a host sync on every dispatch",
                baseline_key=f"{owner}:{kind}:{prim}"))
        if prim == "convert_element_type":
            src = str(eqn.invars[0].aval.dtype) \
                if getattr(eqn.invars[0], "aval", None) is not None \
                else "?"
            dst = str(eqn.params.get("new_dtype", "?"))
            if src in _FLOATS and dst in _FLOATS and \
                    _FLOATS.index(dst) > _FLOATS.index(src) and \
                    np.dtype(dst).itemsize > np.dtype(src).itemsize:
                findings.append(Finding(
                    "PTA103", where,
                    f"float widening {src} -> {dst} inside the "
                    f"program — check for a weak-type / mixed-"
                    f"precision promotion surprise",
                    baseline_key=f"{owner}:{kind}:promote:"
                                 f"{src}->{dst}"))
        if not f64_hit:
            for v in tuple(eqn.outvars):
                av = getattr(v, "aval", None)
                if av is not None and str(av.dtype) == "float64":
                    f64_hit = True
                    findings.append(Finding(
                        "PTA103", where,
                        "float64 value inside the program (x64 "
                        "upcast — 2x memory + off the TPU fast path)",
                        baseline_key=f"{owner}:{kind}:f64"))
                    break

    # ---- PTA105: sharding-constraint coverage over carries ----
    if sharded:
        constrained = set()
        for eqn in jaxpr.eqns:
            inp_hit = any(str(v) in constrained for v in eqn.invars
                          if not isinstance(v, (int, float)))
            if str(eqn.primitive) == "sharding_constraint" or inp_hit:
                for v in eqn.outvars:
                    constrained.add(str(v))
        invar_ids = {str(v) for v in jaxpr.invars}
        in_sigs = set()
        for v in jaxpr.invars:
            av = getattr(v, "aval", None)
            if av is not None:
                in_sigs.add((tuple(av.shape), str(av.dtype)))
        for idx, v in enumerate(jaxpr.outvars):
            av = getattr(v, "aval", None)
            if av is None or _nbytes(av) < large_bytes:
                continue
            sig = (tuple(av.shape), str(av.dtype))
            if sig not in in_sigs:
                continue                  # fresh output, not a carry
            if str(v) in invar_ids:
                continue                  # passthrough keeps its layout
            if str(v) not in constrained:
                findings.append(Finding(
                    "PTA105", where,
                    f"sharded program returns carry out[{idx}] "
                    f"{sig[1]}{list(sig[0])} with no "
                    f"with_sharding_constraint coverage — its layout "
                    f"is left to the partitioner",
                    baseline_key=f"{owner}:{kind}:out{idx}"))
    return findings


def engine_programs(engine, memory=(4, 32), *, dtype="float32",
                    prompt_buckets=(8,)):
    """The `(key, build, example_args)` enumeration `precompile()`
    readies, with the pool pinned exactly the way precompile pins it
    (memory shape tuple or example array) — but nothing compiled."""
    if hasattr(memory, "ndim") or isinstance(memory, np.ndarray):
        mem = np.asarray(memory)
    else:
        M, Dm = memory
        mem = np.zeros((int(M), int(Dm)), np.dtype(dtype))
    engine._ensure_state(mem)
    return engine._startup_programs(prompt_buckets)


def analyze_engine(engine, memory=(4, 32), *, dtype="float32",
                   prompt_buckets=(8,), large_bytes=4096):
    """Run `analyze_program` over every program of one engine's pool
    config. `large_bytes` defaults low enough that the tiny CI stacks'
    KV pools count as large (production pools are GBs)."""
    sharded = bool(getattr(engine, "_accepts_sharded_params", False))
    owner = type(engine).__name__
    findings = []
    for key, build, args in engine_programs(
            engine, memory, dtype=dtype, prompt_buckets=prompt_buckets):
        findings.extend(analyze_program(
            key, build(), args, owner=owner, sharded=sharded,
            large_bytes=large_bytes,
            declared_donated=engine._donate_argnums(key),
            owner_obj=engine))
    return findings


def analyze_fused_optimizer(large_bytes=4096, n=64):
    """Lint the fused whole-model optimizer step (optimizer/fused.py):
    build one Adam step over a small dense bag and audit it like a
    serving program. Donation is audited against the module's DECLARED
    `intended_donation()` — the live wrapper skips donation only where
    the backend can't alias, which is a capability gap, not a defect."""
    import jax.numpy as jnp

    from .. import optimizer as opt_mod
    from ..nn.layer.layers import Parameter
    from ..optimizer import fused

    rs = np.random.RandomState(0)
    params = [Parameter(rs.randn(n, n).astype("f4"), name=f"p{i}")
              for i in range(2)]
    opt = opt_mod.Adam(0.01, parameters=params)
    specs = []
    slot_lists = []
    for p in params:
        slots = opt._slots(p, opt._rule_slot_spec(p))
        slot_lists.append(tuple(slots[k] for k in opt._fused_slots))
        specs.append((tuple(p._data.shape), str(p._data.dtype),
                      str(p._data.dtype), 1.0, 0.0, False))
    fn = fused._build(opt, specs, None)
    grads = tuple(jnp.asarray(rs.randn(n, n).astype("f4"))
                  for _ in params)
    args = (tuple(p._data for p in params), grads, tuple(slot_lists),
            np.float32(0.01), np.int32(0))
    return analyze_program(
        ("fused_opt", "adam", n), fn, args, owner="FusedOptimizerStep",
        large_bytes=large_bytes,
        declared_donated=fused.intended_donation())
