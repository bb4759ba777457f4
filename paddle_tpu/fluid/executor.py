"""Static-graph Executor: whole-block lowering to one XLA computation.

Reference parity: fluid/executor.py:474 (Executor, run :915) and the C++
interpreter executor.cc:180/428. TPU-native design (SURVEY.md §3.1): instead
of the per-op hot loop, `run()` traces every op lowering (fluid/lowering.py)
under jax.jit into ONE fused XLA computation, cached per (program version,
feed signature). Persistable vars (parameters, optimizer state) live in a
Scope as device-resident jax arrays and are donated to the jitted call so
optimizer updates alias buffers across steps (donate_argnums — the
TPU-native equivalent of in-place ParamOut).
"""
from __future__ import annotations

import numpy as np

from ..core.dtypes import convert_dtype
from ..core.place import CPUPlace
from ..core.tensor import Tensor
from . import lowering
from .framework import Parameter, Program, default_main_program


class _ScopeVar:
    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return self

    # tensor-protocol shims (pybind tensor parity)
    def set(self, value, place=None):
        import jax.numpy as jnp

        self._scope._values[self._name] = jnp.asarray(np.asarray(value))

    def __array__(self, dtype=None):
        arr = np.asarray(self._scope._values[self._name])
        return arr.astype(dtype) if dtype else arr

    def shape(self):
        return list(self._scope._values[self._name].shape)


class Scope:
    """framework/scope.h:46 parity: name → value map (flat; hierarchical
    scopes collapse under whole-block lowering)."""

    def __init__(self):
        self._values = {}

    def var(self, name):
        self._values.setdefault(name, None)
        return _ScopeVar(self, name)

    def find_var(self, name):
        if name in self._values:
            return _ScopeVar(self, name)
        return None

    def set_value(self, name, value):
        import jax.numpy as jnp

        self._values[name] = value if not isinstance(value, np.ndarray) \
            else jnp.asarray(value)

    def get_value(self, name):
        return self._values.get(name)

    def drop_kids(self):
        pass


_global_scope = Scope()


def global_scope():
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def guard():
        global _global_scope
        old = _global_scope
        _global_scope = scope
        try:
            yield
        finally:
            _global_scope = old

    return guard()


class Executor:
    def __init__(self, place=None):
        self.place = place if place is not None else CPUPlace()
        self._cache = {}

    # ------------------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            feed_var_name="feed", fetch_var_name="fetch",
            return_numpy=True, use_program_cache=True):
        from .transpiler import PServerProgram

        if isinstance(program, PServerProgram):
            # listen_and_serv parity: exe.run(pserver_program) blocks
            # serving the native PS until interrupted
            return program.serve(blocking=True)
        program = program or default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or _global_scope

        # non-iterable GeneratorLoader/PyReader pull (reader.py:1150
        # start/reset protocol): a STARTED loader bound to this program
        # supplies the feed vars the caller did not; exhaustion raises
        # EOFException for the reference catch-and-reset loop
        for loader in getattr(program, "_py_readers", ()):
            if loader._started:
                pulled = loader._next_feed()
                for k, v in pulled.items():
                    feed.setdefault(k, v)

        fetch_names = [f.name if hasattr(f, "name") else f
                       for f in fetch_list]

        blk = program.global_block()
        persist_names = [v.name for v in blk.vars.values()
                         if v.persistable]

        # materialize feeds as jnp arrays
        import jax
        import jax.numpy as jnp

        from ..core.lod import LOD_OUTER_SUFFIX, LOD_SUFFIX, LoDTensor

        feed_vals = self._materialize_feeds(blk, feed)

        # ensure persistables exist (startup program must have run)
        persist_vals = {}
        for n in persist_names:
            val = scope._values.get(n)
            if val is not None:
                persist_vals[n] = val

        sig = (program._uid, program._version,
               tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in feed_vals.items())),
               tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in persist_vals.items())),
               tuple(fetch_names))
        compiled = self._cache.get(sig) if use_program_cache else None
        if compiled is None:
            compiled = self._compile(program, list(feed_vals),
                                     persist_names, fetch_names)
            if use_program_cache:
                self._cache[sig] = compiled

        program._seed_counter += 1
        key = jax.random.fold_in(jax.random.PRNGKey(
            (program.random_seed or 0) * 100003 + program._seed_counter),
            program._rng_tag())
        fetches, fetch_lods, new_persist = compiled(persist_vals, feed_vals,
                                                    key)

        scope._values.update(new_persist)

        # transpiler-installed hooks (PS grad push/param pull, LocalSGD
        # averaging) run at the jit boundary — SURVEY §7.4: RPC never
        # lives inside the XLA program
        for hook in getattr(program, "_run_hooks", ()):  # noqa: B007
            hook(self, program, scope)

        out = []
        for name, v in zip(fetch_names, fetches):
            lens = fetch_lods.get(name + LOD_SUFFIX)
            if lens is not None:
                if return_numpy:
                    # reference parity (executor.py as_numpy): padded rows
                    # past each sequence's length are garbage — force the
                    # caller to take the LoDTensor instead of wrong data
                    raise RuntimeError(
                        f"fetch var {name!r} is a sequence (LoD) tensor; "
                        f"pass return_numpy=False and use the returned "
                        f"LoDTensor's recursive_sequence_lengths()")
                outer = []
                j = 0
                while f"{name}{LOD_OUTER_SUFFIX}{j}" in fetch_lods:
                    outer.append(np.asarray(
                        fetch_lods[f"{name}{LOD_OUTER_SUFFIX}{j}"]).tolist())
                    j += 1
                out.append(LoDTensor.from_padded(np.asarray(v),
                                                 np.asarray(lens), outer))
            elif return_numpy:
                out.append(np.asarray(v))
            else:
                out.append(Tensor._wrap(v))
        return out

    # ------------------------------------------------------------------
    def _materialize_feeds(self, blk, feed):
        import jax
        import jax.numpy as jnp

        from ..core.lod import LOD_OUTER_SUFFIX, LOD_SUFFIX, LoDTensor

        feed_vals = {}
        for k, v in feed.items():
            if (isinstance(v, tuple) and len(v) == 2
                    and getattr(blk.vars.get(k), "lod_level", 0)):
                # dataset-engine lod slot: (flat values, level offsets)
                # — the native datafeed's wire form (dataset.py
                # _iter_batches); repack as a LoDTensor at the edge.
                # Guarded on the TARGET VAR being lod-typed so an
                # ordinary 2-tuple feed still densifies via np.asarray
                vals, offs = v
                offs = np.asarray(offs)
                if (offs.ndim == 1 and offs.size >= 1
                        and np.issubdtype(offs.dtype, np.integer)):
                    vals = np.asarray(vals)
                    v = LoDTensor(vals.reshape(int(offs[-1]), -1),
                                  lod=[offs.tolist()])
            if isinstance(v, Tensor):
                feed_vals[k] = v._data
            elif isinstance(v, LoDTensor) and v.lod_level > 0:
                # pad+mask canonicalization at the edge (SURVEY §7.1):
                # device sees [B, T, ...] + int32 lengths companion;
                # outer nesting levels ride as offset-array companions
                padded, lens = v.to_padded()
                want = blk.vars.get(k)
                if want is not None and want.dtype is not None:
                    padded = padded.astype(want.dtype)
                feed_vals[k] = jnp.asarray(padded)
                feed_vals[k + LOD_SUFFIX] = jnp.asarray(lens)
                for j, level in enumerate(v.lod()[:-1]):
                    feed_vals[f"{k}{LOD_OUTER_SUFFIX}{j}"] = \
                        jnp.asarray(np.asarray(level, np.int32))
            elif isinstance(v, jax.Array):
                # device-resident feed: reuse without a host round-trip
                # (buffered_reader.cc role — callers pre-place hot batches)
                want = blk.vars.get(k)
                if want is not None and want.dtype is not None and \
                        str(v.dtype) != str(jnp.dtype(want.dtype)):
                    v = v.astype(want.dtype)
                feed_vals[k] = v
            else:
                arr = np.asarray(v)
                want = blk.vars.get(k)
                if want is not None and want.dtype is not None:
                    arr = arr.astype(want.dtype)
                feed_vals[k] = jnp.asarray(arr)
        return feed_vals

    def run_n(self, program=None, feed=None, fetch_list=None, n=1,
              scope=None, return_numpy=True):
        """Run the program n times as ONE jitted lax.scan over the
        persistable state (params + optimizer slots) — a single device
        dispatch instead of n, so per-call dispatch latency amortizes
        n-fold (the ParallelExecutor run-loop role, TPU-native). The
        same feed is applied every step; fetches come from the LAST
        step.

        Falls back to n sequential run() calls when the program carries
        run-hooks (PS push/pull RPC must happen at every step boundary,
        host-side)."""
        from ..core.lod import LoDTensor

        program = program or default_main_program()
        scope = scope or _global_scope
        feed = feed or {}
        has_lod_feed = any(isinstance(v, LoDTensor) and v.lod_level > 0
                           for v in feed.values())
        if n <= 1 or has_lod_feed or getattr(program, "_run_hooks", ()):
            # sequence feeds and per-step host hooks (PS RPC) keep the
            # step-by-step path; run() handles their canonicalization
            out = None
            for _ in range(max(int(n), 1)):
                out = self.run(program, feed, fetch_list, scope=scope,
                               return_numpy=return_numpy)
            return out
        import jax

        fetch_list = fetch_list or []
        fetch_names = [f.name if hasattr(f, "name") else f
                       for f in fetch_list]
        blk = program.global_block()
        persist_names = [v.name for v in blk.vars.values()
                         if v.persistable]
        feed_vals = self._materialize_feeds(blk, feed)
        persist_vals = {nm: scope._values[nm] for nm in persist_names
                        if scope._values.get(nm) is not None}
        if len(persist_vals) != len(persist_names):
            # optimizer slots (moments, lr counters) materialize on the
            # first run; they must be IN the scan carry or every step
            # would re-zero them. One regular run populates the scope.
            out = self.run(program, feed, fetch_list, scope=scope,
                           return_numpy=return_numpy)
            n -= 1
            if n < 1:
                return out
            persist_vals = {nm: scope._values[nm]
                            for nm in persist_names
                            if scope._values.get(nm) is not None}
        sig = ("scan", n, program._uid, program._version,
               tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in feed_vals.items())),
               tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in persist_vals.items())),
               tuple(fetch_names))
        compiled = self._cache.get(sig)
        if compiled is None:
            compiled = self._compile_scan(program, list(feed_vals),
                                          sorted(persist_vals),
                                          fetch_names, n)
            self._cache[sig] = compiled
        program._seed_counter += 1
        key = jax.random.fold_in(jax.random.PRNGKey(
            (program.random_seed or 0) * 100003 + program._seed_counter),
            program._rng_tag())
        fetches, new_persist = compiled(persist_vals, feed_vals, key)
        scope._values.update(new_persist)
        out = []
        for name, v in zip(fetch_names, fetches):
            out.append(np.asarray(v) if return_numpy
                       else Tensor._wrap(v))
        return out

    def _compile_scan(self, program, feed_names, persist_names,
                      fetch_names, n):
        import jax
        import jax.lax as lax

        blk = program.global_block()
        ops = list(blk.ops)

        def step(persist, feed, rng_key):
            from ..core.lod import LOD_SUFFIX

            env = dict(persist)
            env.update(feed)
            ctx = lowering.LowerCtx(env, rng_key, training=True,
                                    program=program,
                                    base_env={**persist, **feed})
            for op in ops:
                if op.type in ("feed", "fetch"):
                    continue
                lowering.lower_op(ctx, op)
            for m in fetch_names:  # trace-time check, zero runtime cost
                if any(k.startswith(m + LOD_SUFFIX) for k in env):
                    raise NotImplementedError(
                        f"run_n: fetch var {m!r} is a sequence (LoD) "
                        f"tensor; use run() per step for LoD fetches")
            new_persist = {m: env[m] for m in persist_names}
            return new_persist, tuple(env[m] for m in fetch_names)

        def execute_n(persist, feed, rng_key):
            keys = jax.random.split(rng_key, n)

            def body(carry, k):
                new_p, _ = step(carry, feed, k)  # fetches unused: DCE'd
                return new_p, ()

            # scan n-1 steps, then one unrolled final step for the
            # fetches — stacking per-step fetch values as scan ys would
            # allocate O(n) device memory only to keep the last slice
            persist, _ = lax.scan(body, persist, keys[:-1])
            persist, fetches = step(persist, feed, keys[-1])
            return fetches, persist

        return jax.jit(execute_n, donate_argnums=(0,))

    # ------------------------------------------------------------------
    def _compile(self, program, feed_names, persist_names, fetch_names):
        import jax

        blk = program.global_block()
        ops = list(blk.ops)

        def execute(persist, feed, rng_key):
            # every op runs eagerly in program order; jax_autodiff lowerings
            # re-trace their (pruned) forward slice inside value_and_grad
            # and publish the in-trace values back — XLA CSE/DCE dedupes
            # the overlap, so the double tracing costs compile time only
            env = dict(persist)
            env.update(feed)
            ctx = lowering.LowerCtx(env, rng_key, training=True,
                                    program=program,
                                    base_env={**persist, **feed})
            for op in ops:
                if op.type in ("feed", "fetch"):
                    continue
                lowering.lower_op(ctx, op)
            fetches = tuple(env[n] for n in fetch_names)
            # sequence-typed fetches carry their lengths (and outer-lod)
            # companions out so the host can re-pack a LoDTensor
            from ..core.lod import LOD_SUFFIX

            fetch_lods = {}
            for n in fetch_names:
                for k in env:
                    # covers both the lengths companion (@@LOD) and the
                    # outer-nesting companions (@@LODO<j>)
                    if k.startswith(n + LOD_SUFFIX):
                        fetch_lods[k] = env[k]
            new_persist = {n: env[n] for n in persist_names if n in env}
            return fetches, fetch_lods, new_persist

        # donate the persistable dict: optimizer state updates alias buffers
        return jax.jit(execute, donate_argnums=(0,))

    # legacy parity helpers ------------------------------------------------
    def close(self):
        pass

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           dump_fields=None, dump_fields_path=None):
        """dump_fields/dump_fields_path: per-INSTANCE feature dump for
        ads debugging (trainer_desc.proto:39-42 dump_fields/dump_param,
        DeviceWorker::DumpField role): every listed var's per-row
        values are appended to <dump_fields_path>/part-0, one line per
        instance: `<step>_<row>\\tname:n:v1 v2 ...`."""
        from .dataset_runner import run_from_dataset

        return run_from_dataset(self, program, dataset, fetch_list,
                                fetch_info, print_period,
                                dump_fields=dump_fields,
                                dump_fields_path=dump_fields_path)

    def infer_from_dataset(self, *args, **kwargs):
        return self.train_from_dataset(*args, **kwargs)


def _lower_block_callable(program, feed_names, fetch_names, scope=None):
    """(fn, ordered_feed_names): fn(*feed_arrays) -> tuple(fetch_arrays),
    persistables captured as constants. Inference-mode lowering used for
    StableHLO export (paddle.inference Predictor.export_stablehlo)."""
    scope = scope or _global_scope
    blk = program.global_block()
    persist_vals = {v.name: scope._values[v.name]
                    for v in blk.vars.values()
                    if v.persistable and v.name in scope._values}
    ops = list(blk.ops)

    def fn(*feed_arrays):
        import jax

        env = dict(persist_vals)
        env.update(zip(feed_names, feed_arrays))
        ctx = lowering.LowerCtx(env, jax.random.PRNGKey(0), training=False,
                                program=program)
        for op in ops:
            if op.type in ("feed", "fetch"):
                continue
            lowering.lower_op(ctx, op)
        return tuple(env[n] for n in fetch_names)

    return fn, list(feed_names)
