"""Versioned on-disk table of tuned kernel configs, keyed by device.

The flash kernels' block shapes were hand-picked constants
(`_pick_blocks`'s 512-first ladder, `_pick_decode_splits`'s ~512-token
splits). This table makes them *data*: `ops/attention.py` consults
`tuning.lookup(kernel, key)` at trace time and falls back to the old
heuristics on a miss — the committed default table's entries equal the
heuristic outputs exactly (tests pin this), so an untuned device is
bit-identical to the pre-tuning kernels, and a device-specific sweep
(tools/autotune.py) can override them without touching kernel code.

Schema (JSON, atomic tmp+os.replace writes):

    {"version": 1,
     "devices": {
       "any":      {"flash_fwd": {"d64/sq1024/sk1024/float32":
                                  {"block_q": 512, "block_k": 512,
                                   "source": "fallback"}}},
       "TPU v5e":  {"flash_decode": {"d64/L2048/float32":
                                     {"split_k": 4, "step_us": 41.2,
                                      "source": "sweep"}}}}}

Lookup order: exact `device_kind` first, then the `"any"` tier (the
committed fallback entries live there). Key tuples are joined with
"/" — sequence lengths are bucketed to powers of two (`seq_bucket`)
so the table stays O(log n) rows per kernel.

Kernels and their tunable knobs:

    flash_fwd / flash_bwd   {"block_q", "block_k"}   (fwd and bwd tune
                            independently; bwd defaults to fwd blocks)
    flash_decode            {"split_k"}
    flash_verify            {"split_k"}
    paged_flash_decode      {"kernel": bool}  — dispatch-level: force
                            the XLA gather path on devices where the
                            scalar-prefetch kernel loses (the grid is
                            (slot, block of pages), the block from
                            the shapes alone: no shape knob exists)
    paged_flash_verify      {"kernel": bool, "split_k"} — the paged
                            speculative verify: kernel-on (grid fixed
                            by the pages) or gather + the dense verify
                            dispatch at the tuned split_k
    int8_matmul             {"block_m", "block_n"} — the scaled-int8
                            weight matmul's tile shape (keys
                            (d_in bucket, d_out bucket, dtype))
    lora_matmul             {"kernel": bool} — dispatch-level: the
                            gathered-LoRA scalar-prefetch kernel vs
                            the XLA gathered einsum (keys
                            (d bucket, rank, dtype))

Env switches: ``PT_TUNING=0`` disables every lookup (pure heuristics,
zero table reads); ``PT_TUNING_TABLE=/path.json`` layers an extra
table over the committed default (its entries win).
"""
from __future__ import annotations

import json
import os
import threading

__all__ = ["TuningTable", "TableError", "KERNELS", "seq_bucket",
           "get_table", "set_table", "lookup", "reset",
           "current_device_kind", "committed_table_path"]

KERNELS = ("flash_fwd", "flash_bwd", "flash_decode", "flash_verify",
           "paged_flash_decode", "paged_flash_verify", "int8_matmul",
           "lora_matmul")

#: knob names each kernel's config may carry (schema validation:
#: unknown keys are tolerated — forward compat — but a config missing
#: every knob is meaningless and rejected at put() time)
KERNEL_KNOBS = {
    "flash_fwd": ("block_q", "block_k"),
    "flash_bwd": ("block_q", "block_k"),
    "flash_decode": ("split_k",),
    "flash_verify": ("split_k",),
    "paged_flash_decode": ("kernel",),
    "paged_flash_verify": ("kernel", "split_k"),
    "int8_matmul": ("block_m", "block_n"),
    "lora_matmul": ("kernel",),
}

#: bump when the key layout or knob semantics change: a mismatched
#: table is IGNORED (heuristic fallback), never misread
TABLE_VERSION = 1


class TableError(ValueError):
    """Malformed / version-mismatched tuning table."""


def seq_bucket(n):
    """Power-of-two bucket for sequence-length key components (same
    policy as core.bucketing.bucket_size, inlined so the table has no
    package dependencies)."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def key_str(parts):
    """Canonical string form of a key tuple: 'd64/sq1024/float32'."""
    if isinstance(parts, str):
        return parts
    return "/".join(str(p) for p in parts)


def current_device_kind():
    """jax's device_kind for the default device — the table's device
    tier. A device that cannot be asked is an error, not the CPU."""
    import jax

    return jax.devices()[0].device_kind


class TuningTable:
    """{device_kind: {kernel: {key_str: config}}} with atomic JSON
    persistence. Thread-safe for concurrent lookup/put (the serving
    engines consult it at trace time)."""

    def __init__(self, devices=None):
        self._lock = threading.Lock()
        self._devices = {}
        for dev, kernels in (devices or {}).items():
            for kern, entries in kernels.items():
                for k, cfg in entries.items():
                    self.put(kern, k, cfg, device_kind=dev,
                             _validate=False)

    # ---- access ----
    def lookup(self, kernel, key, device_kind=None):
        """The tuned config for (kernel, key) — exact device tier
        first, then 'any'. Returns None on a miss (caller falls back
        to its heuristic)."""
        ks = key_str(key)
        if device_kind is None:
            device_kind = current_device_kind()
        with self._lock:
            for tier in (device_kind, "any"):
                cfg = self._devices.get(tier, {}).get(kernel, {}) \
                    .get(ks)
                if cfg is not None:
                    return dict(cfg)
        return None

    def put(self, kernel, key, config, device_kind="any",
            _validate=True):
        """Install one entry. `config` keeps extra metadata fields
        (step_us, source, ...) alongside the knobs."""
        if _validate:
            if kernel not in KERNELS:
                raise TableError(f"unknown kernel {kernel!r} (one of "
                                 f"{KERNELS})")
            knobs = KERNEL_KNOBS[kernel]
            if not any(k in config for k in knobs):
                raise TableError(
                    f"config for {kernel!r} names none of its knobs "
                    f"{knobs}: {config!r}")
        with self._lock:
            self._devices.setdefault(str(device_kind), {}) \
                .setdefault(str(kernel), {})[key_str(key)] = dict(config)

    def merge(self, other):
        """Layer `other`'s entries over this table (other wins)."""
        for dev, kernels in other.as_dict()["devices"].items():
            for kern, entries in kernels.items():
                for k, cfg in entries.items():
                    self.put(kern, k, cfg, device_kind=dev,
                             _validate=False)
        return self

    def entries(self, device_kind=None, kernel=None):
        """Flat [(device_kind, kernel, key_str, config)] rows (the CLI
        renders these)."""
        out = []
        with self._lock:
            for dev, kernels in sorted(self._devices.items()):
                if device_kind is not None and dev != device_kind:
                    continue
                for kern, ent in sorted(kernels.items()):
                    if kernel is not None and kern != kernel:
                        continue
                    for k, cfg in sorted(ent.items()):
                        out.append((dev, kern, k, dict(cfg)))
        return out

    def __len__(self):
        return len(self.entries())

    # ---- persistence ----
    def as_dict(self):
        with self._lock:
            return {"version": TABLE_VERSION,
                    "devices": {d: {k: {kk: dict(c)
                                        for kk, c in e.items()}
                                    for k, e in kernels.items()}
                                for d, kernels in self._devices.items()}}

    def save(self, path):
        """Atomic write: tmp in the target dir, then os.replace — a
        torn write can never leave a half-table behind (the
        CheckpointManager staging discipline)."""
        payload = json.dumps(self.as_dict(), indent=1, sort_keys=True)
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(d, f".{os.path.basename(path)}.tmp-{os.getpid()}")
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path):
        """Parse + version-check a table file. Raises TableError on a
        malformed/mismatched file — get_table() catches it and falls
        back to heuristics with a warning, never crashing a serve."""
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, ValueError) as e:
            raise TableError(f"unreadable tuning table {path}: {e}")
        if not isinstance(raw, dict) or \
                raw.get("version") != TABLE_VERSION:
            raise TableError(
                f"tuning table {path} version "
                f"{raw.get('version') if isinstance(raw, dict) else '?'}"
                f" != {TABLE_VERSION}")
        devices = raw.get("devices")
        if not isinstance(devices, dict):
            raise TableError(f"tuning table {path} has no devices map")
        return cls(devices)


# ----------------------------------------------------------------------
# the module-wide table the kernels consult
# ----------------------------------------------------------------------

def committed_table_path():
    """The in-repo default table (fallback entries == the hand-picked
    constants; sweeps merge device tiers into it via tools/autotune.py
    --merge)."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tables", "default.json")


_LOCK = threading.Lock()
_UNSET = object()
_TABLE = _UNSET
_WARNED = set()


def _warn_once(tag, msg):
    if tag in _WARNED:
        return
    _WARNED.add(tag)
    import warnings

    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _load_default():
    table = TuningTable()
    try:
        table.merge(TuningTable.load(committed_table_path()))
    except TableError as e:
        _warn_once("default", f"committed tuning table unusable "
                              f"({e}); kernel heuristics apply")
    extra = os.environ.get("PT_TUNING_TABLE")
    if extra:
        try:
            table.merge(TuningTable.load(extra))
        except TableError as e:
            _warn_once("env", f"PT_TUNING_TABLE unusable ({e}); "
                              f"entry ignored")
    return table


def get_table():
    """The active TuningTable (lazily loaded; None when PT_TUNING=0)."""
    global _TABLE
    if os.environ.get("PT_TUNING", "1") == "0":
        return None
    t = _TABLE
    if t is _UNSET:
        with _LOCK:
            if _TABLE is _UNSET:
                _TABLE = _load_default()
            t = _TABLE
    return t


def set_table(table):
    """Install a table explicitly (tests / after a sweep). None means
    re-load lazily on next use."""
    global _TABLE
    with _LOCK:
        _TABLE = table if table is not None else _UNSET


def reset():
    """Back to lazy default loading (test teardown symmetry)."""
    set_table(None)


def lookup(kernel, key, device_kind=None):
    """The one call sites make: tuned config dict, or None (use the
    heuristic). One env read + two dict hits on the hot path; returns
    None unconditionally under PT_TUNING=0."""
    t = get_table()
    if t is None:
        return None
    return t.lookup(kernel, key, device_kind=device_kind)
