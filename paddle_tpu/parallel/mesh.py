"""Device mesh management.

Reference parity: the places/device lists handed to ParallelExecutor
(parallel_executor.cc:539 NCCL init over places) and the ring/topology config
in platform/nccl_helper.h:185 NCCLCommunicator (inter/exter rings). TPU-native
design: a single global named Mesh over jax.devices(); rings/hierarchies are
XLA's problem (ICI topology-aware collectives), so the whole "comm registry"
is one object.
"""
from __future__ import annotations

import collections
from typing import Optional, Sequence

import numpy as np

_CANONICAL = ("dp", "fsdp", "pp", "tp", "sp", "ep")

_current: list = [None]


class DeviceMesh:
    """Thin wrapper over jax.sharding.Mesh that remembers axis roles."""

    def __init__(self, mesh, axis_names: Sequence[str]):
        self.mesh = mesh
        self.axis_names = tuple(axis_names)

    @property
    def shape(self):
        return dict(self.mesh.shape)

    @property
    def devices(self):
        """The mesh's device ndarray (axis order = axis_names)."""
        return self.mesh.devices

    def axis_size(self, name: str) -> int:
        return self.mesh.shape.get(name, 1) if name in self.mesh.axis_names \
            else 1

    def slice_axis(self, name: str, start, stop) -> "DeviceMesh":
        """Sub-mesh over a contiguous [start, stop) slab of one axis —
        the prefill/decode disaggregation split: the serving engine
        carves the dp axis into a decode slice and a prefill slice, so
        prompt prefill executes on devices the decode step never
        touches. The returned mesh keeps every axis name (the sliced
        axis shrinks to stop - start) so one ShardingRules table serves
        both slices."""
        from jax.sharding import Mesh

        if name not in self.mesh.axis_names:
            raise ValueError(f"mesh has no axis {name!r}: "
                             f"{self.mesh.axis_names}")
        ax = self.mesh.axis_names.index(name)
        idx = [slice(None)] * len(self.mesh.axis_names)
        idx[ax] = slice(int(start), int(stop))
        sub = self.mesh.devices[tuple(idx)]
        if sub.size == 0:
            raise ValueError(
                f"empty {name!r} slice [{start}, {stop}) of axis size "
                f"{self.mesh.shape[name]}")
        return DeviceMesh(Mesh(sub, self.mesh.axis_names),
                          self.axis_names)

    def __enter__(self):
        self.mesh.__enter__()
        return self

    def __exit__(self, *exc):
        return self.mesh.__exit__(*exc)

    def __repr__(self):
        return f"DeviceMesh({self.shape})"


def init_mesh(dp: int = 1, pp: int = 1, tp: int = 1, sp: int = 1,
              ep: int = 1, fsdp: Optional[int] = None,
              devices=None) -> DeviceMesh:
    """Build and install the global mesh. Axis sizes must multiply to the
    device count. Axes of size 1 are kept (named collectives over them are
    no-op-cheap and keep user programs shape-stable across topologies).
    The `fsdp` axis (weight-storage sharding between dp and pp — the
    serving engines' data x fsdp x tp layout) joins the mesh only when
    explicitly requested, so dp/pp/tp-only programs keep their shape."""
    import jax
    from jax.sharding import Mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    sizes = collections.OrderedDict(
        [("dp", dp), ("pp", pp), ("tp", tp), ("sp", sp), ("ep", ep)])
    if fsdp is not None:
        sizes = collections.OrderedDict(
            [("dp", dp), ("fsdp", fsdp), ("pp", pp), ("tp", tp),
             ("sp", sp), ("ep", ep)])
    total = int(np.prod(list(sizes.values())))
    if total != len(devices):
        raise ValueError(
            f"mesh {dict(sizes)} needs {total} devices, have {len(devices)}")
    arr = np.array(devices).reshape(tuple(sizes.values()))
    mesh = Mesh(arr, tuple(sizes.keys()))
    dm = DeviceMesh(mesh, tuple(sizes.keys()))
    _current[0] = dm
    return dm


def auto_mesh(n_devices: Optional[int] = None, *, want_pp=False,
              want_tp=True, want_sp=False, want_ep=False) -> DeviceMesh:
    """Factor the device count into a sensible (dp, pp, tp, sp, ep) mesh.
    Policy: tp gets up to 2 (up to 4 if many devices), pp gets 2 when asked
    and available, sp/ep get 2 when asked, the rest goes to dp."""
    import jax

    n = len(jax.devices()) if n_devices is None else int(n_devices)
    rem = n
    sizes = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1}

    def take(axis, k):
        nonlocal rem
        if rem % k == 0 and rem >= k:
            sizes[axis] = k
            rem //= k

    if want_pp and rem % 2 == 0:
        take("pp", 2)
    if want_tp and rem % 2 == 0:
        take("tp", 4 if rem % 4 == 0 and rem >= 8 else 2)
    if want_sp and rem % 2 == 0:
        take("sp", 2)
    if want_ep and rem % 2 == 0:
        take("ep", 2)
    sizes["dp"] = rem
    return init_mesh(**sizes)


def get_mesh() -> DeviceMesh:
    if _current[0] is None:
        # default: pure data parallel over every visible device
        import jax

        return init_mesh(dp=len(jax.devices()))
    return _current[0]


def mesh_axis_size(name: str) -> int:
    return get_mesh().axis_size(name)


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with vma checking off: our per-device bodies use
    untracked collectives."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
