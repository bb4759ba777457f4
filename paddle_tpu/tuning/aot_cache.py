"""Persistent AOT compilation cache: serialized XLA executables on disk.

Every engine restart used to pay the full jit-warmup tax — one
trace+compile per serving program (join per prompt bucket, the batched
decode step, the spec draft/verify pair, the paged attach/cow) before
the first token could flow. `AotCompileCache` persists each compiled
program (via `jax.experimental.serialize_executable`) into a cache
directory with a CRC-manifested index, so `ServingEngine.precompile()`
on a restarted server *deserializes* every program instead of
recompiling it: the retrace sentinel sees ZERO compile spans before
the first token.

Layout (all writes staged tmp + os.replace — the CheckpointManager
atomicity discipline; a torn write can never leave a half entry that
parses):

    <dir>/MANIFEST.json          {"version", "fingerprint", "entries":
                                  {digest: {"key", "crc32", "size"}}}
    <dir>/entries/<digest>.bin   pickle((payload, in_tree, out_tree,
                                         device_ids))

Robustness contract (chaos-tested): a torn/corrupt entry (CRC
mismatch), a version- or environment-mismatched manifest, or an
unpicklable blob NEVER crashes startup — the entry counts as a miss
(`stats["corrupt"]` / `stats["stale"]`), the program compiles fresh,
and a store refreshes the entry. The `tuning.cache_load` fault point
lets tests corrupt the blob in flight.

Cache identity: entries are only valid for the exact environment that
wrote them — `env_fingerprint()` pins jax/jaxlib versions, backend,
device kind and device count; the engines additionally fold a model
fingerprint (param/buffer names, shapes, dtypes) and the pool config
into each entry key, so two different models sharing one cache dir
can never collide.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import zlib

from ..testing import faults

__all__ = ["AotCompileCache", "CacheCorrupt", "env_fingerprint",
           "model_fingerprint"]

#: armed by chaos tests to corrupt/raise/delay on every cache-entry
#: read (payload = the raw entry bytes, pre-CRC-check)
_PT_CACHE_LOAD = faults.point("tuning.cache_load")

#: bump when the entry payload format changes: old caches read as
#: stale (recompile + overwrite), never as garbage
CACHE_SCHEMA = 2


class CacheCorrupt(RuntimeError):
    """A cache entry failed its CRC / unpickle — internal signal; the
    public load() surface converts it into a miss + counter."""


def env_fingerprint():
    """Everything a serialized executable is only valid for."""
    import jax
    import jaxlib

    devs = jax.devices()
    kind, n = devs[0].device_kind, len(devs)
    return {"schema": CACHE_SCHEMA,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "device_kind": kind,
            "n_devices": n}


def model_fingerprint(params, buffers=None):
    """sha256 over sorted (name, shape, dtype) of a param/buffer set:
    two models with different weight SHAPES can never share an entry
    (values don't matter — weights are runtime arguments)."""
    h = hashlib.sha256()
    for tree in (params, buffers or {}):
        for name in sorted(tree):
            v = tree[name]
            v = getattr(v, "_data", v)
            h.update(f"{name}:{getattr(v, 'shape', ())}:"
                     f"{getattr(v, 'dtype', '?')};".encode())
    return h.hexdigest()[:16]


class AotCompileCache:
    """One cache directory. Thread-safe; counters in `stats` make the
    cold-start metrics exact:

        loaded   entries deserialized (no compile paid)
        saved    entries written
        misses   keys with no (valid) entry
        corrupt  CRC/unpickle failures that fell back to compile
        stale    manifest version/fingerprint mismatches discarded
    """

    MANIFEST = "MANIFEST.json"

    def __init__(self, path):
        self.path = str(path)
        self._entries_dir = os.path.join(self.path, "entries")
        self._lock = threading.Lock()
        self._fp = env_fingerprint()
        self.stats = {"loaded": 0, "saved": 0, "misses": 0,
                      "corrupt": 0, "stale": 0}
        self._manifest = self._read_manifest()

    # ---- manifest ----
    def _manifest_path(self):
        return os.path.join(self.path, self.MANIFEST)

    def _read_manifest(self):   # analysis: single-threaded
        # construction-time only: no second thread can hold the cache
        # while __init__ is still populating it
        try:
            with open(self._manifest_path()) as f:
                raw = json.load(f)
        except (OSError, ValueError):
            return {}
        if not isinstance(raw, dict) or \
                raw.get("fingerprint") != self._fp:
            # another jax/device/schema wrote this cache: every entry
            # is unloadable here — start empty; stores will rebuild
            # the manifest under the current fingerprint
            if isinstance(raw, dict) and raw.get("entries"):
                self.stats["stale"] += len(raw["entries"])
            return {}
        ent = raw.get("entries")
        return dict(ent) if isinstance(ent, dict) else {}

    def _write_manifest(self):
        os.makedirs(self.path, exist_ok=True)
        payload = json.dumps({"version": CACHE_SCHEMA,
                              "fingerprint": self._fp,
                              "entries": self._manifest},
                             indent=1, sort_keys=True)
        tmp = os.path.join(self.path,
                           f".{self.MANIFEST}.tmp-{os.getpid()}")
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, self._manifest_path())

    @staticmethod
    def _digest(key_str):
        return hashlib.sha256(key_str.encode()).hexdigest()[:32]

    def __len__(self):
        with self._lock:
            return len(self._manifest)

    def keys(self):
        with self._lock:
            return sorted(m["key"] for m in self._manifest.values())

    # ---- load / store ----
    def load(self, key_str):
        """The deserialized executable for `key_str`, or None (miss /
        corrupt / stale — counted, never raised)."""
        dg = self._digest(key_str)
        with self._lock:
            meta = self._manifest.get(dg)
        if meta is None or meta.get("key") != key_str:
            with self._lock:
                self.stats["misses"] += 1
            return None
        try:
            with open(os.path.join(self._entries_dir, dg + ".bin"),
                      "rb") as f:
                blob = f.read()
            blob = _PT_CACHE_LOAD(payload=blob)
            if zlib.crc32(blob) != meta.get("crc32") or \
                    len(blob) != meta.get("size"):
                raise CacheCorrupt(
                    f"entry {dg} failed its CRC/size check "
                    f"(torn write or bit rot)")
            payload, in_tree, out_tree, device_ids = pickle.loads(blob)
            import jax
            from jax.experimental import serialize_executable as se

            # load for the devices the program was compiled for: left
            # to its default, jax binds the executable to EVERY local
            # device and a one-device program then refuses its inputs
            by_id = {d.id: d for d in jax.devices()}
            out = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
            with self._lock:
                self.stats["loaded"] += 1
            return out
        except faults.InjectedFault:
            raise
        except Exception:
            # torn entry / undeserializable executable: drop it from
            # the manifest so the refreshed store isn't shadowed
            with self._lock:
                self.stats["corrupt"] += 1
                self._manifest.pop(dg, None)
                try:
                    self._write_manifest()
                except OSError:
                    pass
            return None

    def store(self, key_str, compiled):
        """Serialize + persist one compiled program. Returns True on
        success; False (counted nowhere fatal) when this executable
        type can't serialize (e.g. some multi-device assemblies) or
        the disk write fails — precompile still proceeded, only the
        NEXT start pays that program's compile again."""
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            blob = pickle.dumps((payload, in_tree, out_tree, device_ids))
        except Exception:
            return False
        dg = self._digest(key_str)
        try:
            os.makedirs(self._entries_dir, exist_ok=True)
            tmp = os.path.join(self._entries_dir,
                               f".{dg}.tmp-{os.getpid()}")
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, os.path.join(self._entries_dir,
                                         dg + ".bin"))
            with self._lock:
                self._manifest[dg] = {"key": key_str,
                                      "crc32": zlib.crc32(blob),
                                      "size": len(blob)}
                self._write_manifest()
        except OSError:
            return False
        with self._lock:
            self.stats["saved"] += 1
        return True
