"""The Pallas kernels of the two main paths, compiled for a TPU v5e that
is described, not attached — at the shapes `chip_smoke.py` runs them at
(ERNIE-base attention b8 h12 s1024 d64; the Transformer-base paged pool
of 16 slots x 1024 positions, 8 heads of 64, 16-token pages).

Interpret mode cannot see what the chip's compiler refuses: a slice off
the tiling, too much fast memory, a literal Mosaic cannot legalize.
These compiles can, at no chip time, and they are what guards the
kernels now that no run-time probe falls back to the XLA reference.
Nothing runs, so results are the business of the interpret-mode parity
tests and of chip_smoke.py's kernel phase.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may load the TPU's library, the suite runs under
several xdist workers, and each of them imports this file. The kernel
entry points are compiled directly — the dispatchers ask
`jax.default_backend()`, see the CPU and take the reference.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import attention as A
from paddle_tpu.ops import quant as Q

# the smoke's shapes
FLASH = dict(b=8, h=12, s=1024, d=64)
POOL = dict(S=16, h=8, L=1024, d=64, T=4, psz=16)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Compile `fn` for the described chip from (shape, dtype) pairs;
    returns the compiled text, which must hold the Mosaic kernel."""
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("dropout", [0.0, 0.1])
def test_flash_attention_fwd_bwd_compiles(one_chip, dtype, dropout):
    b, h, s, d = (FLASH[k] for k in "bhsd")
    qkv = ((b, h, s, d), jnp.dtype(dtype))

    def loss(q, k, v, bias, seed):
        out = A.flash_attention(q, k, v, bias, True, None,
                                dropout_p=dropout,
                                dropout_seed=seed if dropout else None)
        return out.astype(jnp.float32).sum()

    compiled = _compile(jax.value_and_grad(loss, (0, 1, 2)), one_chip,
                        qkv, qkv, qkv, ((b, s), jnp.float32),
                        ((1,), jnp.int32))
    # fwd + dQ + dK/dV
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("has_bias", [False, True])
def test_flash_decode_and_verify_compile(one_chip, has_bias):
    S, h, L, d, T = (POOL[k] for k in ("S", "h", "L", "d", "T"))
    kv = ((S, h, L, d), jnp.float32)
    bias = ((S, L), jnp.float32) if has_bias else None
    _compile(A.flash_decode, one_chip, ((S, h, 1, d), jnp.float32), kv,
             kv, ((S,), jnp.int32), bias)
    _compile(A.flash_verify, one_chip, ((S, h, T, d), jnp.float32), kv,
             kv, ((S,), jnp.int32), bias)


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("T", [1, POOL["T"]])
def test_paged_flash_kernels_compile(one_chip, kv_dtype, T):
    S, h, L, d, psz = (POOL[k] for k in ("S", "h", "L", "d", "psz"))
    mp = L // psz
    pages = ((S * mp + 1, h, psz, d), jnp.dtype(kv_dtype))
    scale = (((S * mp + 1, h, 1, 1), jnp.float32)
             if kv_dtype == "int8" else None)
    fn = A.paged_flash_decode if T == 1 else A.paged_flash_verify
    compiled = _compile(fn, one_chip, ((S, h, T, d), jnp.float32), pages,
                        pages, scale, scale, ((S, mp), jnp.int32),
                        ((S,), jnp.int32), ((S, L), jnp.float32))
    # the pool of the smoke must fit the chip with room for the model
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * 2**30


# the benchmark's pool (`bart_large_dec`: 64 slots x 1024 positions,
# 16 heads of 64, 16-token float32 pages, a pad bias)
BENCH_POOL = dict(S=64, h=16, L=1024, d=64, psz=16)
# `temp_size_in_bytes` of this call before the kernel merged over pages
# itself (PR 26's parent, same compile): the two lane-padded page pools
# plus the per-page partials
BENCH_POOL_TEMP_BEFORE = 1107751936


def test_paged_flash_decode_compiles_at_benchmark_shapes(one_chip):
    S, h, L, d, psz = (BENCH_POOL[k] for k in ("S", "h", "L", "d", "psz"))
    mp = L // psz
    pages = ((S * mp + 1, h, psz, d), jnp.float32)
    compiled = _compile(A.paged_flash_decode, one_chip,
                        ((S, h, 1, d), jnp.float32), pages, pages, None,
                        None, ((S, mp), jnp.int32), ((S,), jnp.int32),
                        ((S, L), jnp.float32))
    calls = [ln for ln in compiled.as_text().splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1
    # one output, the merged [S, h, 1, d]: no `mp`-long partials
    result = calls[0].split("custom-call(")[0].split("=", 1)[1].strip()
    assert result.startswith(f"f32[{S},{h},1,{d}]"), result
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        BENCH_POOL_TEMP_BEFORE


@pytest.mark.parametrize("m", [16, 2048])
def test_int8_matmul_kernel_compiles(one_chip, m):
    d, n = 512, 2048
    bm, bn = Q._pick_int8_blocks_heuristic(m, n)
    _compile(Q._int8_matmul_call(m, d, n, bm, bn, False), one_chip,
             ((m, d), jnp.float32), ((d, n), jnp.int8),
             ((1, n), jnp.float32))


def test_lora_gather_kernel_compiles(one_chip):
    b, s, d, r, n_out, n = 16, 1, 512, 8, 512, 4
    _compile(Q._lora_gather_call(b, s, d, r, n_out, False), one_chip,
             ((b,), jnp.int32), ((b, s, d), jnp.float32),
             ((n, d, r), jnp.float32), ((n, r, n_out), jnp.float32))


def test_kernel_failure_on_tpu_backend_raises(monkeypatch):
    """No dispatcher may swallow a kernel's failure and hand back the
    XLA reference: on a TPU backend that would hide a kernel that no
    longer lowers behind a run that "works", slower. (Needs no
    topology: the gate is steered from here and the kernel entry point
    is made to fail.)"""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)

    def boom(*a, **k):
        raise RuntimeError("mosaic refused the kernel")

    q = jnp.zeros((1, 2, 1, 64), jnp.float32)
    kv = jnp.zeros((1, 2, 256, 64), jnp.float32)
    n = jnp.asarray([3], jnp.int32)
    monkeypatch.setattr(A, "flash_decode", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        A.decode_attention(q, kv, kv, n)
    monkeypatch.setattr(A, "flash_verify", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        A.verify_attention(jnp.zeros((1, 2, 4, 64)), kv, kv, n)
    pages = jnp.zeros((17, 2, 16, 64), jnp.float32)
    table = jnp.asarray(np.arange(16).reshape(1, 16), jnp.int32)
    monkeypatch.setattr(A, "paged_flash_decode", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        A.paged_decode_attention(q, pages, pages, None, None, table, n)
    monkeypatch.setattr(A, "paged_flash_verify", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        A.paged_verify_attention(jnp.zeros((1, 2, 4, 64)), pages, pages,
                                 None, None, table, n)
    monkeypatch.setattr(Q, "_int8_matmul_call", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        Q.int8_matmul(jnp.zeros((8, 128)), jnp.zeros((128, 128), jnp.int8),
                      jnp.ones((128,)))
    monkeypatch.setattr(Q, "_lora_gather_call", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        Q.lora_delta(jnp.zeros((2, 1, 128)), jnp.zeros((3, 128, 8)),
                     jnp.zeros((3, 8, 128)), jnp.asarray([0, 1]))


def test_partitioned_trace_takes_the_xla_composition(monkeypatch):
    """The chip's compiler refuses a Mosaic kernel in a program the SPMD
    partitioner splits ("cannot be automatically partitioned", seen when
    the sharded pool was first compiled for a v5e:2x2). That is a
    placement the code can observe, so it is a gate, not a failure: in
    a `partitioned_trace` (the mesh-sharded train step) or under
    `decode_shardings` (every program of the sharded serving pool) the
    dispatchers take the XLA composition and never touch the kernel."""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)

    def boom(*a, **k):
        raise AssertionError("kernel taken in a partitioned trace")

    for name in ("flash_attention", "flash_decode", "paged_flash_decode"):
        monkeypatch.setattr(A, name, boom)
    q = jnp.full((1, 2, 1024, 64), 0.5, jnp.float32)
    q1 = jnp.zeros((1, 2, 1, 64), jnp.float32)
    kv = jnp.zeros((1, 2, 256, 64), jnp.float32)
    pages = jnp.zeros((17, 2, 16, 64), jnp.float32)
    table = jnp.asarray(np.arange(16).reshape(1, 16), jnp.int32)
    n = jnp.asarray([3], jnp.int32)
    assert A._flash_usable()
    for scope in (A.partitioned_trace(), A.decode_shardings({"q": None})):
        with scope:
            assert not A._flash_usable()
            assert A.sdpa(q, q, q, is_causal=True).shape == q.shape
            assert A.decode_attention(q1, kv, kv, n).shape == q1.shape
            assert A.paged_decode_attention(
                q1, pages, pages, None, None, table, n).shape == q1.shape
    with A.partitioned_trace(False):        # a one-device mesh
        assert A._flash_usable()
    assert A._flash_usable()
