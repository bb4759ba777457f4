"""Cost/memory accounting over every jitted program the engines own.

PR 8 made the serving stack *traceable* (who did what, when); this
module makes it *accountable* (what did it cost). Three views, all
keyed by the SAME cache keys the engines' `trace_counts` counters and
the retrace sentinel already use — cost, compile, and trace records
join on one identity:

  * **Program costs** — while an accounting session is armed
    (`accounting_scope()`), every detected trace+compile re-lowers the
    program AOT and records XLA's `cost_analysis()` (flops, bytes
    accessed) and `memory_analysis()` (argument/output/temp/generated
    bytes) into the session's `CostBook`. When the backend returns
    nothing (or capture is disabled), the owner's `cost_hint(key)` —
    analytic flops/bytes for the known decode/prefill/join shapes —
    fills in, tagged ``source="analytic"``.
  * **DeviceSpec / MFU** — `mfu(flops, dt, spec)` and
    `bw_util(bytes, dt, spec)` turn per-step costs into
    model-flops-utilization and bandwidth-utilization gauges against a
    device roofline. `CPU_SPEC` ships for deterministic tests; real
    TPU generations are tabled in `DEVICE_SPECS` and `detect_spec()`
    picks by `device_kind`.
  * **HBM ledger plumbing** — `temp_high_water()` exposes the compile
    temp-buffer high-water across the book, which
    `ServingMetrics.snapshot()["memory"]` reports next to the
    weights/pool footprint the engines compute (see
    `ServingEngine.memory_ledger`).

Discipline (same as profiler.trace): a disarmed hot path pays ONE
module-global read (`costs._BOOK is None`). Armed capture happens only
at trace time — never on warm calls — and suppresses counter
observation during its deliberate re-lower so the retrace sentinel
stays silent.
"""
from __future__ import annotations

import contextlib
import threading

from . import trace as _trace

__all__ = [
    "DeviceSpec", "ProgramCost", "CostBook", "CPU_SPEC",
    "DEVICE_SPECS", "detect_spec", "start_accounting",
    "end_accounting", "accounting_scope", "book", "mfu", "bw_util",
    "temp_high_water", "transformer_decode_flops",
    "transformer_prefill_flops", "capture_compiled",
]


class DeviceSpec:
    """Peak-rate roofline for one accelerator generation: the
    denominators of the MFU / bandwidth-utilization gauges plus the HBM
    capacity the memory ledger budgets against."""

    __slots__ = ("name", "peak_flops", "peak_bytes_per_s", "hbm_bytes")

    def __init__(self, name, peak_flops, peak_bytes_per_s, hbm_bytes):
        self.name = name
        self.peak_flops = float(peak_flops)
        self.peak_bytes_per_s = float(peak_bytes_per_s)
        self.hbm_bytes = int(hbm_bytes)

    def as_dict(self):
        return {"name": self.name,
                "peak_tflops": round(self.peak_flops / 1e12, 3),
                "peak_gbps": round(self.peak_bytes_per_s / 1e9, 1),
                "hbm_gb": round(self.hbm_bytes / 2**30, 1)}

    def __repr__(self):
        return (f"DeviceSpec({self.name!r}, "
                f"{self.peak_flops / 1e12:.2f} TFLOP/s, "
                f"{self.peak_bytes_per_s / 1e9:.0f} GB/s)")


#: NOMINAL single-core CPU roofline — a fixed constant, not a
#: measurement, so MFU numbers in tests are deterministic functions of
#: (flops, dt). ~one AVX2 core: 8 lanes x 2 FMA ports x 2 flops @ 3GHz.
CPU_SPEC = DeviceSpec("cpu", 96e9, 40e9, 16 * 2**30)

#: per-chip published peaks (bf16 matmul flops, HBM bandwidth, HBM),
#: keyed by the `device_kind` string jax reports for the chip.
#: "TPU v5 lite" is what a v5e reports; its peaks are Google Cloud's
#: "TPU v5e" system-architecture page: 197 TFLOP/s bf16, 819 GB/s,
#: 16 GiB HBM per chip.
DEVICE_SPECS = {
    "cpu": CPU_SPEC,
    "TPU v2": DeviceSpec("TPU v2", 22.5e12, 700e9, 8 * 2**30),
    "TPU v3": DeviceSpec("TPU v3", 61.5e12, 900e9, 16 * 2**30),
    "TPU v4": DeviceSpec("TPU v4", 137.5e12, 1228e9, 32 * 2**30),
    "TPU v5 lite": DeviceSpec("TPU v5 lite", 197e12, 819e9, 16 * 2**30),
    "TPU v5p": DeviceSpec("TPU v5p", 229.5e12, 2765e9, 95 * 2**30),
}


def detect_spec():
    """Spec for jax's default device by its `device_kind`. A device the
    table does not know is an error: a utilisation gauge against the
    wrong peak is worse than none."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind in DEVICE_SPECS:
        return DEVICE_SPECS[kind]
    raise LookupError(
        f"no peak-rate row for device kind {kind!r} in "
        f"profiler.costs.DEVICE_SPECS ({sorted(DEVICE_SPECS)}); add one "
        f"with its published peaks and their source")


def mfu(flops, dt_s, spec):
    """Model-flops-utilization: achieved flop rate / the spec peak."""
    if dt_s <= 0:
        return 0.0
    return flops / dt_s / spec.peak_flops


def bw_util(bytes_accessed, dt_s, spec):
    """Achieved memory traffic / the spec's peak HBM bandwidth."""
    if dt_s <= 0:
        return 0.0
    return bytes_accessed / dt_s / spec.peak_bytes_per_s


# ----------------------------------------------------------------------
# analytic transformer costs (the CPU-safe fallback + hint vocabulary)
# ----------------------------------------------------------------------

def transformer_decode_flops(n_params, batch, kv_len, n_layers,
                             n_heads, head_dim, mem_len=0):
    """One decode step over `batch` rows: 2 flops per (dense param,
    row) for the matmul stack, plus attention reads over `kv_len` live
    keys (QK^T + AV = 4 per key position per head dim) and `mem_len`
    cross-attention keys."""
    dense = 2.0 * float(n_params) * batch
    attn = 4.0 * n_layers * batch * n_heads * head_dim * \
        (kv_len + mem_len)
    return dense + attn


def transformer_prefill_flops(n_params, batch, prompt_len, n_layers,
                              n_heads, head_dim, mem_len=0):
    """Prefill over a `prompt_len`-token (bucketed) prompt: the dense
    stack touches every token; self-attention is causal quadratic."""
    dense = 2.0 * float(n_params) * batch * prompt_len
    attn = 4.0 * n_layers * batch * n_heads * head_dim * \
        (prompt_len * (prompt_len + 1) / 2.0 + prompt_len * mem_len)
    return dense + attn


class ProgramCost:
    """Cost/memory record for ONE compiled program (one cache key)."""

    __slots__ = ("owner", "key", "flops", "bytes_accessed",
                 "argument_bytes", "output_bytes", "temp_bytes",
                 "generated_code_bytes", "compile_s", "source")

    def __init__(self, owner, key, *, flops=0.0, bytes_accessed=0.0,
                 argument_bytes=0, output_bytes=0, temp_bytes=0,
                 generated_code_bytes=0, compile_s=0.0, source="xla"):
        self.owner = owner
        self.key = key
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.argument_bytes = int(argument_bytes)
        self.output_bytes = int(output_bytes)
        self.temp_bytes = int(temp_bytes)
        self.generated_code_bytes = int(generated_code_bytes)
        self.compile_s = float(compile_s)
        self.source = source

    def as_dict(self):
        return {"owner": self.owner, "key": _trace._key_str(self.key),
                "flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "argument_bytes": self.argument_bytes,
                "output_bytes": self.output_bytes,
                "temp_bytes": self.temp_bytes,
                "generated_code_bytes": self.generated_code_bytes,
                "compile_s": round(self.compile_s, 4),
                "source": self.source}

    def __repr__(self):
        return (f"ProgramCost({self.owner}:{self.key!r}, "
                f"{self.flops:.3g} flops, "
                f"{self.bytes_accessed:.3g} B, {self.source})")


class CostBook:
    """Thread-safe {(owner_name, cache_key): ProgramCost} — the armed
    accounting session's sink. Keys are the engines' jit-cache /
    trace_counts keys verbatim, so cost records join the retrace
    sentinel's counters and the tracer's compile spans on one
    identity."""

    def __init__(self, spec=None, capture_xla=True):
        self.spec = spec if spec is not None else detect_spec()
        #: False: skip the AOT re-lower+compile and record analytic
        #: hints only (arming mid-serve without paying a second compile
        #: per not-yet-captured key)
        self.capture_xla = bool(capture_xla)
        self._lock = threading.Lock()
        self._costs = {}
        self.compiles = 0

    def get(self, owner_name, key):
        with self._lock:
            return self._costs.get((owner_name, key))

    def put(self, cost):
        with self._lock:
            self._costs[(cost.owner, cost.key)] = cost
        return cost

    def keys(self):
        with self._lock:
            return list(self._costs)

    def costs(self):
        with self._lock:
            return list(self._costs.values())

    def temp_high_water(self):
        """Peak XLA temp-buffer bytes across every recorded program:
        the compile-cache contribution to the HBM ledger (programs
        don't run concurrently, so the max — not the sum — is what the
        allocator must hold in reserve)."""
        with self._lock:
            return max((c.temp_bytes for c in self._costs.values()),
                       default=0)

    def report(self):
        """Rows sorted by flops, heaviest first (tools render this)."""
        with self._lock:
            rows = sorted(self._costs.values(),
                          key=lambda c: -c.flops)
        return [c.as_dict() for c in rows]


# ----------------------------------------------------------------------
# the armed accounting session
# ----------------------------------------------------------------------

#: the ONE global the hot paths read; None = accounting disarmed
_BOOK = None
_LOCK = threading.Lock()


def book():
    """The armed CostBook, or None."""
    return _BOOK


def _cost_from_compiled(owner_name, key, compiled, compile_s):
    """Pull XLA's cost/memory analyses off an ALREADY-compiled
    executable (no lowering, no trace). Shared by the warm-path
    re-lower capture and the startup precompile capture — AOT-loaded
    programs never compile through the observer, so precompile hands
    them here directly. Returns None when the backend can't answer."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict) or "flops" not in ca:
        return None
    cost = ProgramCost(
        owner_name, key,
        flops=ca.get("flops", 0.0),
        bytes_accessed=ca.get("bytes accessed", 0.0),
        compile_s=compile_s, source="xla")
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        cost.argument_bytes = int(
            getattr(ma, "argument_size_in_bytes", 0))
        cost.output_bytes = int(getattr(ma, "output_size_in_bytes", 0))
        cost.temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0))
        cost.generated_code_bytes = int(
            getattr(ma, "generated_code_size_in_bytes", 0))
    return cost


def _extract_xla(owner, key, fn, args, kw, compile_s):
    """AOT re-lower+compile the jitted `fn` at the observed call's
    arguments and pull XLA's cost/memory analyses. The deliberate
    second trace runs under `suppress_observation` with the trace
    counter restored, so neither the retrace sentinel nor session
    counters see it. Returns None when the backend can't answer."""
    counter = getattr(owner, "trace_counts", None)
    with _trace.suppress_observation():
        before = None if counter is None else counter[key]
        try:
            compiled = fn.lower(*args, **kw).compile()
        except Exception:
            return None
        finally:
            if counter is not None:
                counter[key] = before
    return _cost_from_compiled(type(owner).__name__, key, compiled,
                               compile_s)


def capture_compiled(owner, key, compiled, compile_s=0.0):
    """Record one startup-precompiled program into the armed book
    (no-op when accounting is disarmed). The engines' precompile()
    path calls this for every readied program — including
    cache-DESERIALIZED executables, which never pass through the
    compile observer because they never compile — so a warm start
    still arrives with a fully populated cost book. Falls back to the
    owner's analytic hint exactly like the warm-path capture."""
    bk = _BOOK
    if bk is None:
        return None
    name = type(owner).__name__
    if bk.get(name, key) is not None:
        return bk.get(name, key)
    cost = None
    if bk.capture_xla:
        cost = _cost_from_compiled(name, key, compiled, compile_s)
    if cost is None:
        cost = analytic_cost(owner, key, compile_s=compile_s)
    if cost is not None:
        bk.put(cost)
    return cost


def analytic_cost(owner, key, compile_s=0.0):
    """The owner's `cost_hint(key)` as a ProgramCost (source
    "analytic"), or None when the owner declines the key."""
    hint = getattr(owner, "cost_hint", None)
    if hint is None:
        return None
    try:
        h = hint(key)
    except Exception:
        return None
    if h is None:
        return None
    return ProgramCost(type(owner).__name__, key,
                       flops=h.get("flops", 0.0),
                       bytes_accessed=h.get("bytes_accessed", 0.0),
                       temp_bytes=h.get("temp_bytes", 0),
                       argument_bytes=h.get("argument_bytes", 0),
                       compile_s=compile_s, source="analytic")


def _on_compile(owner, key, fn, args, kw, t0, t1):
    bk = _BOOK
    if bk is None:
        return
    bk.compiles += 1
    name = type(owner).__name__
    if bk.get(name, key) is not None:
        return
    cost = None
    if bk.capture_xla:
        cost = _extract_xla(owner, key, fn, args, kw, t1 - t0)
    if cost is None:
        cost = analytic_cost(owner, key, compile_s=t1 - t0)
    if cost is not None:
        bk.put(cost)


def cost_for(owner, key):
    """The armed book's record for (owner, key), materializing the
    analytic fallback on first ask (programs compiled BEFORE arming
    have no capture; the hint keeps the MFU gauges live without
    forcing a recompile). None when disarmed or unknowable."""
    bk = _BOOK
    if bk is None:
        return None
    name = type(owner).__name__
    c = bk.get(name, key)
    if c is None:
        c = analytic_cost(owner, key)
        if c is not None:
            bk.put(c)
    return c


def start_accounting(spec=None, capture_xla=True, book=None):
    """Arm the module-wide accounting session: every trace+compile in
    any `trace.JitCache` is captured into the returned CostBook, and
    the engines' per-step MFU/goodput gauges start recording. One
    session at a time."""
    global _BOOK
    with _LOCK:
        if _BOOK is not None:
            raise RuntimeError("a cost-accounting session is already "
                               "armed; end_accounting() it first")
        _BOOK = book if book is not None else \
            CostBook(spec=spec, capture_xla=capture_xla)
        _trace.add_compile_hook(_on_compile)
        return _BOOK


def end_accounting():
    """Disarm; returns the CostBook (or None if nothing was armed)."""
    global _BOOK
    with _LOCK:
        bk = _BOOK
        _BOOK = None
        _trace.remove_compile_hook(_on_compile)
        return bk


@contextlib.contextmanager
def accounting_scope(spec=None, capture_xla=True):
    bk = start_accounting(spec=spec, capture_xla=capture_xla)
    try:
        yield bk
    finally:
        end_accounting()


def temp_high_water():
    """Compile temp high-water of the armed book (0 when disarmed)."""
    bk = _BOOK
    return 0 if bk is None else bk.temp_high_water()


def reset():
    """Disarm unconditionally (conftest teardown symmetry)."""
    global _BOOK
    with _LOCK:
        _BOOK = None
        _trace.remove_compile_hook(_on_compile)
