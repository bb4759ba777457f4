"""Quantized and gathered matmul primitives for multi-tenant serving.

Two kernel families, both shaped by TPP's low-precision-primitive
argument (PAPERS.md) and dispatched through the same TuningTable
discipline as the attention kernels:

  * **int8 weight matmul** — the large dense weights (QKV / out-proj /
    FFN / embedding-vocab) stored as symmetric per-output-channel int8
    with fp32 scales, dequantized on the way into the MXU:
    ``y = (x @ q) * scale``. The compute dtype is preserved (the
    accumulate runs fp32), so quantization error is the weight-rounding
    error only. On TPU a blocked pallas kernel (block_m x block_n
    tiles, tuned) reads the int8 tiles straight from HBM — 4x less
    weight traffic per step, which is the whole win on a
    bandwidth-bound decode; elsewhere the XLA reference computes the
    identical math.
  * **gathered LoRA matmul** — the per-slot low-rank adapter delta of
    the multi-tenant serving pool: stacked ``A [n_adapters, d_in, r]``
    / ``B [n_adapters, r, d_out]`` banks, per-row adapter ids as a
    traced int32 input (adapter switches never retrace — the page-table
    trick), and the delta for every row computed as ONE batched
    ``(x @ A[ids]) @ B[ids]`` gather-matmul. Row id 0 is the base
    model: its bank rows stay zero, so opted-out requests ride the
    same program with an exactly-zero delta. On TPU the pallas kernel
    scalar-prefetches the ids and dereferences them in the A/B
    BlockSpec index maps (each grid row DMAs only its own adapter's
    bank rows); elsewhere the gathered einsum reference runs — and its
    batch-leading layout is row-invariant on XLA CPU, which is what
    makes pooled adapter decode token-identical to a solo batch-1 run.

The adapter ids + banks reach the Linear layers through a trace-scoped
context (`lora_scope`) rather than threaded signatures: the serving
step bodies receive them as ordinary traced arguments and open the
scope around the functionalized net apply, so the layers below need no
plumbing and the hook costs one dict read when disarmed.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np

__all__ = ["quantize_int8_weight", "int8_matmul", "int8_matmul_reference",
           "int8_gather", "lora_delta", "lora_delta_reference",
           "lora_scope", "current_lora", "merge_lora_weight"]

_QMAX = 127.0

#: block ladders the int8 matmul kernel tiles from (the TuningTable's
#: candidate sets draw from these; see tuning.autotune)
INT8_BLOCK_M = (256, 128, 64, 32, 16, 8)
INT8_BLOCK_N = (512, 384, 256, 128)


def _jnp():
    import jax.numpy as jnp

    return jnp


# --------------------------------------------------------------------------
# weight quantization (pure jnp; host-side one-shot at engine build)
# --------------------------------------------------------------------------

def quantize_int8_weight(w):
    """Symmetric per-output-channel int8: ``w [..., d_out]`` ->
    ``(q int8, scale f32 [d_out])`` with ``scale = amax(|col|) / 127``
    (1.0 for all-zero columns so dequant never divides by zero) — the
    same amax/127 policy as the paged KV int8 pages, per weight column
    instead of per page."""
    jnp = _jnp()

    w32 = jnp.asarray(w).astype(jnp.float32)
    red = tuple(range(w32.ndim - 1))
    amax = jnp.max(jnp.abs(w32), axis=red)
    scale = jnp.where(amax > 0, amax / _QMAX,
                      jnp.float32(1.0)).astype(jnp.float32)
    q = jnp.clip(jnp.round(w32 / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale


# --------------------------------------------------------------------------
# int8 matmul: reference, pallas kernel, dispatcher
# --------------------------------------------------------------------------

def int8_matmul_reference(x, q, scale, bias=None):
    """``(x @ q) * scale [+ bias]`` with an fp32 accumulate, cast back
    to x's dtype. Scaling AFTER the matmul keeps the contraction in
    int8-feedable form (the MXU shape TPP argues for); per-output-
    channel scales make the two orders algebraically identical."""
    import jax.numpy as jnp

    acc = jnp.matmul(x.astype(jnp.float32), q.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    acc = acc * scale
    out = acc.astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


def _pick_int8_blocks_heuristic(m, n):
    """Hand-picked (block_m, block_n) for the int8 matmul kernel: the
    largest ladder entries that tile the operand — the committed-
    fallback source of truth for the int8_matmul tuning-table entries
    (tuning.autotune.fallback_config mirrors this function)."""
    def _one(s, ladder):
        for b in ladder:
            if s % b == 0:
                return min(b, s)
        return s
    return _one(int(m), INT8_BLOCK_M), _one(int(n), INT8_BLOCK_N)


def _int8_matmul_call(m, d, n, bm, bn, interpret):
    """The blocked int8 matmul kernel: grid (m/bm, n/bn), each step an
    (bm, d) x (d, bn) MXU tile with the int8 weight tile upcast in
    VMEM and the per-column scale applied to the fp32 accumulator."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .attention import _z

    def kernel(x_ref, q_ref, s_ref, o_ref):
        acc = jnp.dot(x_ref[...].astype(jnp.float32),
                      q_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
        o_ref[...] = acc * s_ref[...]

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, _z())),
            pl.BlockSpec((d, bn), lambda i, j: (_z(), j)),
            pl.BlockSpec((1, bn), lambda i, j: (_z(), j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret, name="int8_matmul")


def _tuned_int8_blocks(m, d, n, dtype, block_m=None, block_n=None):
    """Tuned (block_m, block_n) — explicit overrides win, then the
    table keyed (d bucket, n bucket, dtype), then the heuristic; a
    tuned entry that does not tile THESE dims falls back too (same
    discipline as _pick_blocks)."""
    from .attention import _seq_bucket, _tuned

    if block_m is not None or block_n is not None:
        hb_m, hb_n = _pick_int8_blocks_heuristic(m, n)
        return (min(int(block_m), m) if block_m else hb_m,
                min(int(block_n), n) if block_n else hb_n)
    cfg = _tuned("int8_matmul", (_seq_bucket(d), _seq_bucket(n),
                                 str(dtype)))
    if cfg is not None:
        try:
            bm = min(int(cfg["block_m"]), m)
            bn = min(int(cfg["block_n"]), n)
        except (KeyError, TypeError, ValueError):
            bm = bn = 0
        if bm > 0 and bn > 0 and m % bm == 0 and n % bn == 0:
            return bm, bn
    return _pick_int8_blocks_heuristic(m, n)


def int8_matmul(x, q, scale, bias=None, interpret=False, block_m=None,
                block_n=None):
    """Scaled int8 matmul dispatch: ``x [..., d_in] @ q int8 [d_in,
    d_out] * scale [d_out]``. The blocked pallas kernel on TPU (or
    under interpret=True for CPU parity tests); the XLA reference —
    bit-identical math, fp32 accumulate — elsewhere."""
    import jax.numpy as jnp

    from .attention import _flash_usable, _on_tpu

    d, n = q.shape
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= int(s)
    use_kernel = interpret or (_on_tpu() and _flash_usable()
                               and m >= 8 and n % 128 == 0)
    if use_kernel:
        bm, bn = _tuned_int8_blocks(m, d, n, x.dtype, block_m, block_n)
        call = _int8_matmul_call(m, d, n, bm, bn, interpret)
        acc = call(x.reshape(m, d).astype(jnp.float32), q,
                   scale.reshape(1, n))
        out = acc.astype(x.dtype).reshape(lead + (n,))
        if bias is not None:
            out = out + bias.astype(out.dtype)
        return out
    return int8_matmul_reference(x, q, scale, bias)


def int8_gather(ids, q, scale, dtype):
    """Embedding-vocab lookup over an int8 table: gather the id rows
    and apply the per-output-channel scale — the embedding is the
    one-hot matmul special case of `int8_matmul`, and a gather IS its
    int8 kernel (no dequantized [V, D] copy ever materializes)."""
    import jax.numpy as jnp

    rows = jnp.take(q, ids, axis=0).astype(jnp.float32)
    return (rows * scale).astype(dtype)


# --------------------------------------------------------------------------
# gathered LoRA matmul: reference, pallas kernel, dispatcher
# --------------------------------------------------------------------------

def lora_delta_reference(x, A, B, ids):
    """The batched per-row adapter delta: ``(x @ A[ids]) @ B[ids]``,
    fp32 accumulate, cast back to x's dtype. ``x [b, s, d_in]``,
    ``A [n, d_in, r]``, ``B [n, r, d_out]``, ``ids [b] int32``. Row 0
    of the banks is all-zero (the base model), so id 0 contributes an
    exactly-zero delta through the same program."""
    import jax.numpy as jnp

    Ag = jnp.take(A, ids, axis=0)                    # [b, d_in, r]
    Bg = jnp.take(B, ids, axis=0)                    # [b, r, d_out]
    x32 = x.astype(jnp.float32)
    xa = jnp.einsum("bsd,bdr->bsr", x32, Ag.astype(jnp.float32))
    out = jnp.einsum("bsr,bro->bso", xa, Bg.astype(jnp.float32))
    return out.astype(x.dtype)


def _lora_dispatch_heuristic():
    """Hand-picked dispatch config for the gathered LoRA matmul: the
    scalar-prefetch kernel on (TPU only; the CPU fallback is the
    gathered einsum either way). The committed-fallback source of
    truth for the lora_matmul tuning-table entries."""
    return {"kernel": True}


def _lora_gather_call(b, s, d, r, n_out, interpret):
    """The gathered LoRA kernel: grid (b,) with the per-row adapter
    ids scalar-prefetched — each grid row's A/B BlockSpec index maps
    dereference ids[i] to DMA only that adapter's bank rows (the
    paged-decode table trick applied to weight banks)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .attention import _z

    def kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
        xa = jnp.dot(x_ref[...].astype(jnp.float32),
                     a_ref[...].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        o_ref[...] = jnp.dot(xa, b_ref[...].astype(jnp.float32),
                             preferred_element_type=jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b,),
        in_specs=[
            pl.BlockSpec((None, s, d), lambda i, ids: (i, _z(), _z())),
            pl.BlockSpec((None, d, r),
                         lambda i, ids: (ids[i], _z(), _z())),
            pl.BlockSpec((None, r, n_out),
                         lambda i, ids: (ids[i], _z(), _z())),
        ],
        out_specs=pl.BlockSpec((None, s, n_out),
                               lambda i, ids: (i, _z(), _z())))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, n_out), jnp.float32),
        interpret=interpret, name="lora_gather")


def lora_delta(x, A, B, ids, interpret=False):
    """Gathered-LoRA dispatch: the scalar-prefetch pallas kernel on
    TPU (tuned on/off per (d, r, dtype) — or under interpret=True for
    CPU parity tests); the gathered einsum reference elsewhere."""
    import jax.numpy as jnp

    from .attention import (_flash_usable, _on_tpu, _seq_bucket,
                            _tuned)

    b, s, d = x.shape
    _, _, r = A.shape
    n_out = B.shape[-1]
    cfg = _tuned("lora_matmul", (_seq_bucket(d), int(r), str(x.dtype)))
    if cfg is None:
        cfg = _lora_dispatch_heuristic()
    use_kernel = interpret or (
        _on_tpu() and _flash_usable() and r % 8 == 0
        and bool(cfg.get("kernel", True)))
    if use_kernel:
        out = _lora_gather_call(b, s, d, r, n_out, interpret)(
            jnp.asarray(ids, jnp.int32), x, A, B)
        return out.astype(x.dtype)
    return lora_delta_reference(x, A, B, ids)


def merge_lora_weight(w, wA, wB):
    """``W + A @ B`` — the merged-weight equivalent of the factored
    delta (B pre-scaled by alpha/r, the AdapterPool storage
    convention). The multi-tenant acceptance tests serve the factored
    path and compare against a solo engine running this merge."""
    import jax.numpy as jnp

    w = jnp.asarray(w)
    return (w.astype(jnp.float32) +
            jnp.asarray(wA, jnp.float32) @ jnp.asarray(wB, jnp.float32)
            ).astype(w.dtype)


# --------------------------------------------------------------------------
# the trace-scoped adapter context the serving step bodies open
# --------------------------------------------------------------------------

_LORA_STATE = threading.local()


@contextlib.contextmanager
def lora_scope(ids, banks):
    """Make (per-row adapter ids, [(A, B), ...] banks) visible to the
    Linear layers under this trace scope. `ids` is a traced int32 [b]
    aligned with the batch rows of every Linear input; `banks` is
    indexed by each target layer's installed `_lora_idx`. Re-entrant
    (the previous scope is restored on exit); reading the scope when
    none is open returns None — the zero-cost disarmed path."""
    prev = getattr(_LORA_STATE, "ctx", None)
    _LORA_STATE.ctx = (ids, banks)
    try:
        yield
    finally:
        _LORA_STATE.ctx = prev


def current_lora():
    """The active (ids, banks) pair, or None outside any lora_scope."""
    return getattr(_LORA_STATE, "ctx", None)
