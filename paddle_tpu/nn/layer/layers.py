"""Layer: the dygraph module system.

Reference parity: python/paddle/fluid/dygraph/layers.py:63 (Layer,
__call__ :678 with forward pre/post hooks, sublayers/parameters traversal,
state_dict/set_state_dict) and ParamAttr (fluid/param_attr.py). TPU-native
design: parameters are eager Tensors (jax arrays) registered on the module
tree; the functional state_dict view doubles as the pytree handed to jitted
train steps and to pjit shardings.
"""
from __future__ import annotations

import collections
import contextlib
from typing import Iterator

import numpy as np

from ...core.dtypes import convert_dtype, get_default_dtype
from ...core.tensor import Tensor
from .. import initializer as I


class ParamAttr:
    """fluid/param_attr.py:31 parity."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, do_model_average=True,
                 need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if attr is False:
            return False
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        raise TypeError(f"bad param attr {attr!r}")


class Parameter(Tensor):
    """A trainable Tensor (framework.py:5053 Parameter parity)."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "need_clip")

    def __init__(self, data, trainable=True, name=None, learning_rate=1.0,
                 regularizer=None, need_clip=True):
        super().__init__(data, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": learning_rate}
        self.regularizer = regularizer
        self.need_clip = need_clip


def keep_float32(param):
    """Mark a parameter that a trainer's `compute_dtype` must not round
    (a decay rate, a router): `SpmdTrainer` hands it to the forward pass
    as it is held. Returns the parameter."""
    param.optimize_attr["keep_float32"] = True
    return param


#: trace-scoped: the forward pass being traced recomputes each block of
#: a layer stack in the backward pass instead of keeping its activations
_RECOMPUTE_BLOCKS = [False]


@contextlib.contextmanager
def recompute_blocks(on=True):
    """Scope a functional forward pass (`SpmdTrainer(remat=True)`) in which
    every stack that runs its blocks through `run_block` puts each under
    `jax.checkpoint`: between blocks only the residual stream is kept, and
    a block's activations live only while its own backward pass runs."""
    prev = _RECOMPUTE_BLOCKS[0]
    _RECOMPUTE_BLOCKS[0] = bool(on)
    try:
        yield
    finally:
        _RECOMPUTE_BLOCKS[0] = prev


def run_block(block, x, *args, **kwargs):
    """`block(x, *args, **kwargs)` for one block of a stack; under
    `recompute_blocks`, inside `jax.checkpoint`. `x` is the one Tensor the
    block is differentiated through as an argument; parameters are closed
    over. What the block would leave behind goes through the checkpoint
    as a value, so no tracer of the inner trace outlives it: the buffers
    it writes (an expert layer's counters) are returned and written back
    outside, and its random ops (dropout) draw from a key split off the
    step's scoped key outside and scoped anew inside."""
    if not _RECOMPUTE_BLOCKS[0]:
        return block(x, *args, **kwargs)
    import jax

    from ...core import random as _random

    def run(raw, key):
        with _random.scoped_key(key):
            out = block(Tensor._wrap(raw), *args, **kwargs)
        return out._data, {n: b._data for n, b in block.named_buffers()}

    # None outside a scoped region: the block then draws from the eager
    # chain, whose keys are no tracers
    out, written = jax.checkpoint(run)(x._data, _random._scoped_next())
    for n, b in block.named_buffers():
        b._data = written[n]
    return Tensor._wrap(out)


_name_counters = collections.defaultdict(int)


def _unique_name(prefix):
    _name_counters[prefix] += 1
    return f"{prefix}_{_name_counters[prefix] - 1}"


class Layer:
    def __init__(self, name_scope=None, dtype=None):
        self._parameters = collections.OrderedDict()
        self._sub_layers = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self.training = True
        self._dtype = dtype or get_default_dtype()
        self._full_name = _unique_name(
            name_scope or type(self).__name__.lower())

    # ---------------- parameter creation ----------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = dtype or self._dtype
        if default_initializer is None:
            default_initializer = I.Constant(0.0) if is_bias else \
                I.XavierUniform()
        init = attr.initializer or default_initializer
        data = init(shape, dtype)
        p = Parameter(data, trainable=attr.trainable,
                      name=attr.name or _unique_name("param"),
                      learning_rate=attr.learning_rate,
                      regularizer=attr.regularizer,
                      need_clip=attr.need_clip)
        return p

    def add_parameter(self, name, parameter):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # ---------------- attribute magic ----------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ first")
            params[name] = value
            for d in (layers, buffers):
                if d is not None and name in d:
                    del d[name]
            object.__setattr__(self, name, value)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ first")
            layers[name] = value
            object.__setattr__(self, name, value)
        else:
            if params is not None and name in params and value is None:
                params[name] = None
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    # ---------------- traversal ----------------
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (f"{prefix}.{name}" if prefix else name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for n, p in layer.named_parameters(sub_prefix, True):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(sub_prefix, True)

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        for name, l in self._sub_layers.items():
            if l is not None:
                yield name, l

    def sublayers(self, include_self=False):
        out = [self] if include_self else []
        for l in self.children():
            out.extend(l.sublayers(include_self=True))
        return out

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, l in self.named_children():
            sub = f"{prefix}.{name}" if prefix else name
            yield from l.named_sublayers(sub, include_self=True)

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    def full_name(self):
        return self._full_name

    # ---------------- modes ----------------
    def train(self):
        self.training = True
        for l in self.children():
            l.train()
        return self

    def eval(self):
        self.training = False
        for l in self.children():
            l.eval()
        return self

    # ---------------- state dict ----------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = collections.OrderedDict() if destination is None else \
            destination
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            dest[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix.rstrip(".")):
            short = name.rsplit(".", 1)[-1]
            if short not in self._non_persistable_buffer_names:
                dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, t in own.items():
            if name in state_dict:
                v = state_dict[name]
                arr = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
                t.set_value(arr)
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # ---------------- hooks ----------------
    def register_forward_pre_hook(self, hook):
        h = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[h._id] = hook
        return h

    def register_forward_post_hook(self, hook):
        h = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[h._id] = hook
        return h

    # ---------------- call ----------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            res = hook(self, args)
            if res is not None:
                args = res if isinstance(res, tuple) else (res,)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, args, out)
            if res is not None:
                out = res
        return out

    # ---------------- dtype / device movement ----------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = convert_dtype(dtype)
            for p in self.parameters():
                p._data = p._data.astype(dt)
            for b in self.buffers():
                if b is not None and hasattr(b, "_data") and \
                        np.issubdtype(np.dtype(b._data.dtype), np.floating):
                    b._data = b._data.astype(dt)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    # ---------------- functional bridge (TPU-native extra) ----------------
    def raw_state(self):
        """Pytree of raw jax arrays {name: array} — the functional view used
        by jitted train steps and pjit shardings."""
        return {k: v._data for k, v in self.state_dict().items()}

    def load_raw_state(self, tree):
        sd = self.state_dict()
        for k, v in tree.items():
            sd[k]._data = v


class HookRemoveHelper:
    _next = [0]

    def __init__(self, store):
        self._store = store
        self._id = HookRemoveHelper._next[0]
        HookRemoveHelper._next[0] += 1

    def remove(self):
        self._store.pop(self._id, None)
