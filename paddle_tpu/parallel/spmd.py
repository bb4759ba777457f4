"""SPMD train step: the TPU-native ParallelExecutor.

Reference parity: framework/parallel_executor.cc + details/ (SSA graph over
devices, AllReduceOpHandle per grad, grad bucketing via
fuse_all_reduce_op_pass, overlap of compute and comm by the threaded
executors) and the meta-optimizer rewrites (recompute → jax.remat, gradient
merge → lax.scan microbatch loop, AMP → bf16 compute dtype). TPU-native
design: ONE jitted function owns forward+backward+update for the whole step;
parameters, optimizer state, and batch are laid out by NamedShardings and XLA
inserts/fuses/overlaps every collective (ICI) — grad bucketing and comm
scheduling come from the compiler's latency-hiding scheduler, not from
hand-built op handles.
"""
from __future__ import annotations

from typing import Callable, Optional

from ..optimizer import functional as fopt
from ..profiler import RecordEvent
from .functional import functionalize
from .mesh import DeviceMesh, get_mesh
from .sharding import (ShardingRules, batch_sharding, infer_param_specs,
                       named_sharding)


class SpmdTrainer:
    """Owns sharded (params, opt_state, buffers) and a compiled train step.

    loss_fn(outputs, labels) -> scalar, over raw jax arrays.
    Batches are (inputs_tuple, labels) of raw arrays / np arrays.

    `remat=True` recomputes block by block: the forward pass is traced
    under `nn.layer.layers.recompute_blocks`, in which every stack that
    runs its blocks through `run_block` (TransformerEncoder,
    NemotronHForCausalLM) puts each block under `jax.checkpoint`. Between
    blocks only the residual stream is kept, so the step's peak falls by
    about the activations of all blocks but one. A model with no such
    stack is left as it is.

    `compute_dtype` casts every floating parameter for the forward and
    backward passes, except those a layer marked `keep_float32` (decay
    rates, a router).
    """

    def __init__(self, layer, loss_fn: Callable, optimizer,
                 mesh: Optional[DeviceMesh] = None,
                 rules: Optional[ShardingRules] = None,
                 remat: bool = False, grad_accum: int = 1,
                 compute_dtype=None, donate: bool = True,
                 batch_axes=("dp",), moe_aux_weight: float = 0.01):
        import jax

        self.mesh = mesh or get_mesh()
        self.moe_aux_weight = float(moe_aux_weight)
        self.fm = functionalize(layer)
        self.loss_fn = loss_fn
        self.tx = optimizer if isinstance(optimizer, fopt.Transform) \
            else fopt.from_eager(optimizer)
        self.remat = remat
        self.grad_accum = int(grad_accum)
        self.compute_dtype = compute_dtype
        self.batch_axes = batch_axes
        self._step_fn = None
        self._eval_fn = None
        self._steps = 0   # calls of step(): the profiler's step number

        params = self.fm.params()
        buffers = self.fm.buffers()
        self._keep_f32 = frozenset(
            n for n in params if getattr(
                self.fm._tensors[n], "optimize_attr", {}).get("keep_float32"))
        self.param_specs = infer_param_specs(params, rules)
        self.param_shardings = {
            n: named_sharding(s, self.mesh)
            for n, s in self.param_specs.items()}
        self._repl = named_sharding((), self.mesh)

        # place initial state onto the mesh
        self.params = {
            n: jax.device_put(v, self.param_shardings[n])
            for n, v in params.items()}
        self.buffers = {
            n: jax.device_put(v, self._repl) for n, v in buffers.items()}
        self._opt_shardings = None
        with self.mesh.mesh:
            self.opt_state = jax.jit(
                self.tx.init,
                out_shardings=self._opt_state_shardings())(self.params)
        self._rng = None
        self._donate = donate

    def _opt_state_shardings(self):
        """Optimizer slots inherit their parameter's sharding (the free
        ZeRO-lite: a tp/ep-sharded param gets tp/ep-sharded moments).
        Computed once and cached."""
        import jax

        if self._opt_shardings is not None:
            return self._opt_shardings

        def shard_like(tree):
            if isinstance(tree, dict):
                return {n: self.param_shardings.get(n, self._repl)
                        for n in tree}
            return jax.tree_util.tree_map(lambda _: self._repl, tree)

        probe = jax.eval_shape(self.tx.init, self.params)
        if hasattr(probe, "_fields"):  # NamedTuple of slots
            out = type(probe)(*[
                shard_like(getattr(probe, f)) if isinstance(
                    getattr(probe, f), dict) else self._repl
                for f in probe._fields])
        else:
            out = jax.tree_util.tree_map(lambda _: self._repl, probe)
        self._opt_shardings = out
        return out

    # ------------------------------------------------------------------
    def _cast(self, t):
        return t.astype(self.compute_dtype) if hasattr(
            t, "dtype") and "float" in str(t.dtype) else t

    def cast_params(self, params):
        """`params` as the forward pass takes them: cast to
        `compute_dtype`, except those a layer marked `keep_float32`."""
        if self.compute_dtype is None:
            return params
        return {n: v if n in self._keep_f32 else self._cast(v)
                for n, v in params.items()}

    def set_buffer(self, name, value):
        """Replace buffer `name` (a routing bias, a running statistic) by
        `value`, placed as the step holds its buffers."""
        import jax

        if name not in self.buffers:
            raise KeyError(name)
        self.buffers[name] = jax.device_put(value, self._repl)

    def _forward_loss(self, params, buffers, rng, inputs, labels):
        from ..nn.layer.layers import recompute_blocks

        params = self.cast_params(params)
        if self.compute_dtype is not None:
            # float INPUTS too (conv images etc.): mixed f32xbf16 operands
            # are an error for lax.conv and silently promote elsewhere
            inputs = tuple(self._cast(x) for x in inputs)

        with recompute_blocks(self.remat):
            out, new_buf = self.fm.apply(params, buffers, rng, *inputs,
                                         training=True)
        loss = self.loss_fn(out, labels)
        if hasattr(loss, "_data"):  # paddle Tensor from a paddle loss fn
            loss = loss._data
        total = loss.astype("float32").mean()
        # MoE load-balance pressure: every MoELayer publishes its aux
        # loss through the buffer channel (nn/layer/moe.py) — remat- and
        # jit-safe because buffers are RETURNED, not side-stored
        if self.moe_aux_weight:
            import jax.numpy as jnp

            aux = [v for n, v in new_buf.items()
                   if n.endswith("aux_loss_val")]
            if aux:
                total = total + jnp.float32(self.moe_aux_weight) * sum(
                    a.astype("float32").reshape(()) for a in aux)
        return total, new_buf

    def _build_step(self):
        import jax
        import jax.numpy as jnp

        from ..ops import attention as _attn

        accum = self.grad_accum

        # on a mesh of several devices XLA partitions this program, and
        # it cannot partition a Pallas kernel: attention then takes its
        # XLA composition
        # named for the profiler: the module is `jit_train_step`, and
        # every device operation's `op_name` is under `train_step/fwd_bwd`
        # or `train_step/optimizer`
        @_attn.partitioned_trace(self.mesh.mesh.size > 1)
        def train_step(params, opt_state, buffers, rng, inputs, labels):
            with jax.named_scope("train_step"):
                with jax.named_scope("fwd_bwd"):
                    loss, buffers, grads = fwd_bwd(
                        params, buffers, rng, inputs, labels)
                with jax.named_scope("optimizer"):
                    new_params, new_opt = self.tx.update(
                        params, grads, opt_state)
            return new_params, new_opt, buffers, loss

        def fwd_bwd(params, buffers, rng, inputs, labels):
            grad_fn = jax.value_and_grad(self._forward_loss, has_aux=True)

            if accum > 1:
                # gradient merge (optimizer.py:4994 GradientMergeOptimizer):
                # microbatch scan, grads averaged in fp32
                def micro(carry, mb):
                    g_acc, l_acc, bufs, key = carry
                    key, sub = jax.random.split(key)
                    (loss, bufs), grads = grad_fn(
                        params, bufs, sub, mb[:-1], mb[-1])
                    g_acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32) / accum,
                        g_acc, grads)
                    return (g_acc, l_acc + loss / accum, bufs, key), None

                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                mb_stack = tuple(
                    x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
                    for x in tuple(inputs) + (labels,))
                (grads, loss, buffers, _), _ = jax.lax.scan(
                    micro, (g0, jnp.zeros((), jnp.float32), buffers, rng),
                    mb_stack)
                grads = jax.tree_util.tree_map(
                    lambda g, p: g.astype(p.dtype), grads, params)
            else:
                (loss, buffers), grads = grad_fn(
                    params, buffers, rng, tuple(inputs), labels)
            return loss, buffers, grads

        train_step.__name__ = train_step.__qualname__ = "train_step"
        self._raw_step = train_step

        in_shardings = (
            self.param_shardings,
            self._opt_state_shardings(),
            {n: self._repl for n in self.buffers},
            self._repl,
            None, None,  # data: let jit take what step() receives
        )
        out_shardings = (
            self.param_shardings,
            self._opt_state_shardings(),
            {n: self._repl for n in self.buffers},
            self._repl,
        )
        donate = (0, 1, 2) if self._donate else ()
        with self.mesh.mesh:
            self._step_fn = jax.jit(
                train_step, in_shardings=in_shardings,
                out_shardings=out_shardings, donate_argnums=donate)
        return self._step_fn

    # ------------------------------------------------------------------
    def shard_batch(self, *arrays):
        """Place host batch arrays onto the mesh, leading dim over dp."""
        import jax
        import jax.numpy as jnp

        out = []
        for a in arrays:
            arr = jnp.asarray(a)
            out.append(jax.device_put(
                arr, batch_sharding(self.mesh, self.batch_axes)))
        return tuple(out)

    def step(self, inputs, labels, rng=None):
        """One update. Its host phases are profiler annotations (no
        tracer session needed): `train.step` with the step's number,
        and under it `train.next_key` (drawing the step's key),
        `train.shard` (the batch onto the mesh) and `train.enqueue`
        (the call of the compiled step). Nothing is read back here:
        the job reads the loss when it wants it."""
        if self._step_fn is None:
            self._build_step()
        self._steps += 1
        with RecordEvent("train.step", "step", step_num=self._steps):
            if rng is None:
                from ..core import random as _random

                with RecordEvent("train.next_key", "step"):
                    rng = _random.next_key()
            inputs = tuple(inputs) if isinstance(inputs, (list, tuple)) \
                else (inputs,)
            with RecordEvent("train.shard", "step"):
                data = self.shard_batch(*inputs, labels)
            inputs, labels = data[:-1], data[-1]
            with RecordEvent("train.enqueue", "step"):
                self.params, self.opt_state, self.buffers, loss = \
                    self._step_fn(self.params, self.opt_state,
                                  self.buffers, rng, inputs, labels)
        return loss

    def run_steps(self, inputs, labels, n_steps, rng=None):
        """Run n_steps updates on one batch inside a single jitted lax.scan
        (the TPU-native inner training loop: one dispatch, zero host
        round-trips between steps). Returns the final loss."""
        import jax

        if rng is None:
            from ..core import random as _random

            rng = _random.next_key()
        inputs = tuple(inputs) if isinstance(inputs, (list, tuple)) \
            else (inputs,)
        data = self.shard_batch(*inputs, labels)
        inputs, labels = data[:-1], data[-1]

        key = f"_loop_{n_steps}"
        loop = self.__dict__.get(key)
        if loop is None:
            if self._step_fn is None:
                self._build_step()
            raw_step = self._raw_step

            def run(params, opt_state, buffers, rng, inp, lab):
                def body(carry, key_t):
                    params, opt_state, buffers = carry
                    params, opt_state, buffers, loss = raw_step(
                        params, opt_state, buffers, key_t, inp, lab)
                    return (params, opt_state, buffers), loss

                keys = jax.random.split(rng, n_steps)
                (params, opt_state, buffers), losses = jax.lax.scan(
                    body, (params, opt_state, buffers), keys)
                return params, opt_state, buffers, losses[-1]

            with self.mesh.mesh:
                loop = jax.jit(run, donate_argnums=(0, 1, 2))
            self.__dict__[key] = loop
        self.params, self.opt_state, self.buffers, loss = loop(
            self.params, self.opt_state, self.buffers, rng, inputs, labels)
        return loss

    def run_epoch(self, batches, rng=None, chunk=8):
        """Drive many (inputs_tuple, labels) batches through the compiled
        step with device-resident double-buffered input: batches are
        stacked `chunk` at a time, each stack's H2D transfer is issued
        asynchronously while the previous stack's jitted lax.scan runs
        (reference operators/reader/buffered_reader.cc role). Returns the
        last loss. TPU-native shape: one dispatch per chunk, transfers
        overlapped by XLA's async device_put."""
        import jax
        import numpy as np

        if rng is None:
            from ..core import random as _random

            rng = _random.next_key()

        key = f"_epoch_{chunk}"
        loop = self.__dict__.get(key)
        if loop is None:
            if self._step_fn is None:
                self._build_step()
            raw_step = self._raw_step

            def run(params, opt_state, buffers, rng, stack):
                def body(carry, xs):
                    params, opt_state, buffers, rng = carry
                    rng, sub = jax.random.split(rng)
                    params, opt_state, buffers, loss = raw_step(
                        params, opt_state, buffers, sub, xs[:-1], xs[-1])
                    return (params, opt_state, buffers, rng), loss

                (params, opt_state, buffers, rng), losses = jax.lax.scan(
                    body, (params, opt_state, buffers, rng), stack)
                return params, opt_state, buffers, rng, losses[-1]

            with self.mesh.mesh:
                loop = jax.jit(run, donate_argnums=(0, 1, 2))
            self.__dict__[key] = loop

        tail = []

        def stacks():
            buf = []
            for inputs, labels in batches:
                inputs = tuple(inputs) if isinstance(inputs, (list, tuple)) \
                    else (inputs,)
                buf.append(tuple(np.asarray(x) for x in inputs)
                           + (np.asarray(labels),))
                if len(buf) == chunk:
                    yield tuple(np.stack([b[i] for b in buf])
                                for i in range(len(buf[0])))
                    buf = []
            tail.extend(buf)  # leftover < chunk: run via single steps

        from ..io import DevicePrefetcher
        from .sharding import batch_sharding

        sh = batch_sharding(self.mesh, self.batch_axes, leading=1)
        loss = None
        pf = DevicePrefetcher(stacks(), sharding=sh, depth=2)
        try:
            for stack in pf:
                self.params, self.opt_state, self.buffers, rng, loss = \
                    loop(self.params, self.opt_state, self.buffers, rng,
                         stack)
        finally:
            pf.close()
        # tail batches below `chunk` go through the already-compiled
        # single-step path (a per-tail-size scan would compile anew)
        for b in tail:
            loss = self.step(b[:-1], b[-1])
        return loss

    def eval_step(self, inputs):
        import jax

        if self._eval_fn is None:
            def eval_step(params, buffers, inputs):
                with jax.named_scope("eval_step"):
                    params = self.cast_params(params)
                    out, _ = self.fm.apply(params, buffers, None,
                                           *inputs, training=False)
                return out

            with self.mesh.mesh:
                self._eval_fn = jax.jit(eval_step)
        inputs = tuple(inputs) if isinstance(inputs, (list, tuple)) \
            else (inputs,)
        return self._eval_fn(self.params, self.buffers,
                             self.shard_batch(*inputs))

    def sync_to_layer(self):
        """Write the trained state back into the eager Layer."""
        self.fm.load(self.params, self.buffers)


def spmd_data_parallel(layer, loss_fn, optimizer, **kw):
    """Convenience: pure-DP trainer over every visible device — the direct
    replacement for CompiledProgram.with_data_parallel."""
    return SpmdTrainer(layer, loss_fn, optimizer, **kw)
