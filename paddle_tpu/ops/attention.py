"""Attention kernels: XLA composition + (on TPU) Pallas flash-attention
kernels, forward AND backward. Reference parity: the fused multihead
attention of operators/fused/multihead_matmul_op.* and
math/bert_encoder_functor.cu — re-designed TPU-first as blockwise
online-softmax kernels (flash attention) instead of translated CUDA.

Layout: (batch, heads, seq, head_dim) throughout.

Backward is a real flash backward (no S×S probability matrix is ever
materialized): the forward saves only the output and the per-row
logsumexp; dQ/dK/dV recompute probabilities blockwise in VMEM. Padded
batches stay on the flash path via a key-position bias (the (B, 1, 1, S)
additive mask every NLP batch uses); full (B, H, Sq, Sk) masks fall back
to the XLA reference.

Packed/varlen batches (LoD-native): multiple ragged sequences packed
into one row ride the flash path via per-token SEGMENT IDS
(core/lod.py pack_padded produces them). Ids must be non-decreasing
along the token axis of each row — the packed layout guarantees it —
which makes the set of keys a query block may see a CONTIGUOUS token
range; both the forward and both backward kernels turn that range into
fori_loop bounds, so fully-cross-segment blocks are never visited at
all (the same block-level early-out the causal path applies to future
blocks). Visited blocks apply the token-level same-segment mask
unconditionally: predicating it away with lax.cond measured ~1.5x
SLOWER under Mosaic (see _causal_apply), so boundary and interior
blocks share one body. Dropout, key-position bias and causal compose
with segments; `sdpa`/`sdpa_bshd` route automatically whenever segment
metadata is present.

Decode mode (autoregressive serving): `decode_attention` takes ONE
query token per row against a preallocated KV cache ([b, h, max_len,
d]) with a traced written-token count — on TPU a split-K flash-decode
kernel (`flash_decode`) spreads the cache length across the grid and
merges per-split partial softmaxes in XLA; elsewhere the
`decode_attention_reference` composition applies the same length mask
densely. Interpret-mode CPU parity mirrors the training kernels.
"""
from __future__ import annotations

import contextlib
import functools
import math
import os


def _jnp():
    import jax.numpy as jnp

    return jnp


def _prob_dropout(probs, dropout_p, dropout_key):
    """Attention-probability dropout (the reference multihead attention's
    dropout on the softmax output). f32 probability — see kernels.dropout."""
    import jax
    import jax.numpy as jnp

    if not dropout_p or dropout_key is None:
        return probs
    keep = jax.random.bernoulli(dropout_key, jnp.float32(1.0 - dropout_p),
                                probs.shape)
    return jnp.where(keep, probs / (1.0 - dropout_p), 0.0)


def sdpa_reference(q, k, v, mask=None, is_causal=False, scale=None,
                   dropout_p=0.0, dropout_key=None):
    """Plain XLA attention: always correct, runs anywhere, XLA fuses it."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("...qd,...kd->...qk", q, k) * s
    if is_causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((qlen, klen), bool), klen - qlen)
        logits = jnp.where(cmask, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    probs = _prob_dropout(probs, dropout_p, dropout_key)
    return jnp.einsum("...qk,...kd->...qd", probs.astype(q.dtype), v)


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() != "cpu"


def _kv_bias(mask, b, h, sk):
    """Normalize a mask to a key-position additive bias [b, sk] if it only
    varies over (batch, key) — the padded-batch case. Returns None if the
    mask is richer (per-head or per-query) and needs the reference path."""
    import jax.numpy as jnp

    if mask is None:
        return None
    m = mask
    if m.dtype == jnp.bool_:
        m = jnp.where(m, jnp.float32(0.0), jnp.float32(-1e30))
    # accepted shapes: (b, sk), (b, 1, sk), (b, 1, 1, sk), (1/b, 1, 1, sk)
    shp = m.shape
    if shp[-1] != sk:
        return None
    lead = shp[:-1]
    if any(d != 1 for d in lead[1:]):
        return None
    if len(lead) >= 1 and lead[0] not in (1, b):
        return None
    m = m.reshape((lead[0] if lead else 1, sk)).astype(jnp.float32)
    if m.shape[0] == 1:
        m = jnp.broadcast_to(m, (b, sk))
    return m



def segment_bias(segment_ids, kv_segment_ids=None):
    """Additive f32 [b, 1, sq, sk] attention bias from per-token segment
    ids ([b, sq] / [b, sk] int): 0 within a segment, -1e30 across. The
    XLA-composition equivalent of the in-kernel segment mask — the
    fallback paths and the parity tests both use it."""
    import jax.numpy as jnp

    seg_q = jnp.asarray(segment_ids)
    seg_k = seg_q if kv_segment_ids is None else jnp.asarray(kv_segment_ids)
    eq = seg_q[:, :, None] == seg_k[:, None, :]
    return jnp.where(eq, jnp.float32(0.0), jnp.float32(-1e30))[:, None]


def _z():
    """Typed zero for BlockSpec index maps: under `jax_enable_x64`
    (on package-wide) a bare python ``0`` stages a weak int64 next to
    the int32 grid indices (func.return (i32, i32, i64)); an
    int32-typed literal is the correct typing. numpy (not jnp) on
    purpose: a jnp scalar is a jax Array, and index maps must not
    capture Array constants (it also breaks under
    jax.ensure_compile_time_eval)."""
    import numpy as np

    return np.int32(0)


# --------------------------------------------------------------------------
# forward kernel: out + logsumexp (residual for the flash backward)
# --------------------------------------------------------------------------

def _drop_consts(dropout_p):
    """(uint32 keep-threshold, f32 1/keep) — numpy-typed on purpose:
    weak python literals widen to 64 bits under `jax_enable_x64`, which
    the kernels must never see."""
    import numpy as np

    thresh = np.uint32(min(int(round(dropout_p * 2.0 ** 32)), 2 ** 32 - 1))
    return thresh, np.float32(1.0 / (1.0 - dropout_p))


def _check_drop_grid(sk, block_k):
    """The second PRNG seed word packs (qi, ki) as qi*4096 + ki, which
    is injective only while ki < 4096. ki indexes key blocks, so the
    bound is static at kernel-build time — enforce it instead of
    silently wrapping (ADVICE r05 low)."""
    nk = sk // block_k
    if nk > 4096:
        raise ValueError(
            f"flash dropout block addressing needs sk/block_k <= 4096 "
            f"(got {nk}); raise block_k or disable in-kernel dropout")


def _block_bits(pltpu, seed_ref, bh, qi, ki, block_q, block_k):
    """Counter-style dropout bits for one (qi, ki) logits block: reseed
    the on-core PRNG with (seed, bh, qi, ki) then draw — the SAME tuple
    (not stream order) addresses the block, so the dQ kernel (ki inner
    loop) and the dK/dV kernel (qi inner loop) regenerate identical
    masks. Reference role: dropout_op.cc composed after the softmax of
    multihead attention."""
    import jax.numpy as jnp

    # Mosaic supports at most TWO seed words: fold bh into the first
    # NON-additively (odd-constant multiply — a plain seed+bh made
    # (seed, head) and (seed+1, head-1) collide, ADVICE r05) and pack
    # (qi, ki) injectively into the second (ki < 4096 enforced by
    # _check_drop_grid at kernel-build time)
    pltpu.prng_seed(seed_ref[0] + bh * jnp.int32(-1640531527),
                    qi * jnp.int32(4096) + ki)
    bits = pltpu.prng_random_bits((block_q, block_k))
    if bits.dtype != jnp.uint32:
        bits = pltpu.bitcast(bits, jnp.uint32)
    return bits


def _hash_bits(jnp, jax, seed, bh, qi, ki, block_q, block_k):
    """Interpret-mode stand-in for _block_bits: a pure-jnp counter hash
    over (seed, bh, qi, ki, row, col) — the Mosaic PRNG has no CPU
    lowering. Same addressing contract (the tuple, not stream order,
    identifies the block) so fwd and both bwd kernels regenerate
    identical masks; `dropout_keep_reference` reproduces these exact
    bits host-side, which is what lets the CPU test suite check flash
    dropout against an XLA composition BIT-FOR-BIT."""
    r = jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 1)
    x = (seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         ^ bh.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
         ^ qi.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
         ^ ki.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    x = x ^ (r * jnp.uint32(0x165667B1)) ^ (c * jnp.uint32(0x9E3779B9))
    # murmur3 fmix32 finalizer
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def dropout_keep_reference(seed, b, h, sq, sk, block_q, block_k,
                           dropout_p):
    """Host/numpy replica of the INTERPRET-mode in-kernel dropout keep
    mask, [b*h, sq, sk] bool — feeds the XLA reference composition in
    tests so segment-masked flash with dropout ON can be checked for
    exact parity on CPU. (The compiled TPU path draws from the Mosaic
    PRNG instead; its statistics are checked on the chip by
    chip_smoke.py's kernel phase.)"""
    import numpy as np

    thresh = np.uint32(min(int(round(dropout_p * 2.0 ** 32)),
                           2 ** 32 - 1))
    nq, nk = sq // block_q, sk // block_k
    keep = np.empty((b * h, sq, sk), bool)
    r = np.arange(block_q, dtype=np.uint32)[:, None]
    c = np.arange(block_k, dtype=np.uint32)[None, :]
    with np.errstate(over="ignore"):
        for bh in range(b * h):
            for qi in range(nq):
                for ki in range(nk):
                    x = (np.uint32(seed) * np.uint32(0x9E3779B9)
                         ^ np.uint32(bh) * np.uint32(0x85EBCA6B)
                         ^ np.uint32(qi) * np.uint32(0xC2B2AE35)
                         ^ np.uint32(ki) * np.uint32(0x27D4EB2F))
                    x = x ^ (r * np.uint32(0x165667B1)) \
                        ^ (c * np.uint32(0x9E3779B9))
                    x = x ^ (x >> np.uint32(16))
                    x = x * np.uint32(0x85EBCA6B)
                    x = x ^ (x >> np.uint32(13))
                    x = x * np.uint32(0xC2B2AE35)
                    x = x ^ (x >> np.uint32(16))
                    keep[bh, qi * block_q:(qi + 1) * block_q,
                         ki * block_k:(ki + 1) * block_k] = x >= thresh
    return keep


def _causal_apply(jax, jnp, dmat, qi, ki, block_q, block_k, logits):
    """Mask logits[r, c] where (global row) < (global col). dmat =
    row-iota - col-iota is hoisted OUT of the k loop; per block only a
    scalar offset compare + select remains. Measured (tools/
    tune_flash.py, seq1024): predicating the select away entirely with
    lax.cond made every combo ~1.5x SLOWER (Mosaic serializes around
    scf.if), so the mask applies unconditionally."""
    off = ki * jnp.int32(block_k) - qi * jnp.int32(block_q)
    return jnp.where(dmat >= off, logits, jnp.float32(-1e30))


def _flash_fwd_kernels(b, h, sq, sk, d, s, is_causal, has_bias, block_q,
                       block_k, dtype, interpret=False, dropout_p=0.0,
                       has_segs=False):
    import jax
    import jax.numpy as jnp

    from jax.experimental import pallas as pl

    nq = sq // block_q
    nk = sk // block_k
    has_drop = dropout_p > 0.0
    if has_drop:
        from jax.experimental.pallas import tpu as pltpu

        _check_drop_grid(sk, block_k)
        thresh, inv_keep = _drop_consts(dropout_p)

        def draw_bits(seed_ref, bh, qi, ki):
            if interpret:  # Mosaic PRNG has no CPU lowering
                return _hash_bits(jnp, jax, seed_ref[0], bh, qi, ki,
                                  block_q, block_k)
            return _block_bits(pltpu, seed_ref, bh, qi, ki,
                               block_q, block_k)

    def kernel(*refs):
        refs = list(refs)
        if has_drop:
            seed_ref = refs.pop(0)
        if has_segs:
            # inputs run (q, k, v, bias?, qseg, kseg), outputs (o, lse)
            kseg_ref = refs.pop(-3)
            qseg_ref = refs.pop(-3)
        if has_bias:
            q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref = refs
        else:
            q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        bh = pl.program_id(0)
        qi = pl.program_id(1)
        # operands stay in their NATIVE dtype (bf16 x bf16 -> f32 MXU
        # accumulation); the softmax scale folds into the [bq, d] query
        # block ONCE instead of a [bq, bk] logits multiply per k block
        sf = jnp.float32(s)
        qb = (q_ref[...].astype(jnp.float32) * sf).astype(q_ref.dtype)
        if is_causal:
            dmat = (jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 0)
                    - jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 1))
        if has_segs:
            # monotone ids make valid keys one contiguous token range:
            # everything with an id in [min(qseg), max(qseg)] — turn it
            # into block-loop bounds (block-level early-out; same trick
            # as the causal future-block skip)
            qsegc = qseg_ref[...]                 # (block_q, 1) int32
            qmin, qmax = qsegc.min(), qsegc.max()
            kseg_all = kseg_ref[...]              # (sk, 1) int32
            lo_tok = jnp.sum((kseg_all < qmin).astype(jnp.int32))
            hi_tok = jnp.sum((kseg_all <= qmax).astype(jnp.int32))
            seg_lo = lo_tok // jnp.int32(block_k)
            seg_hi = (hi_tok + jnp.int32(block_k - 1)) \
                // jnp.int32(block_k)

        def make_body(masked):
            def body(ki, carry):
                acc, m_prev, l_prev = carry
                kb = k_ref[pl.ds(ki * block_k, block_k), :]
                vb = v_ref[pl.ds(ki * block_k, block_k), :]
                logits = jnp.dot(qb, kb.T,
                                 preferred_element_type=jnp.float32)
                if has_bias:
                    bias = bias_ref[pl.ds(ki * block_k, block_k), 0]
                    logits = logits + bias[None, :]
                if has_segs:
                    ksb = kseg_ref[pl.ds(ki * block_k, block_k), 0]
                    logits = jnp.where(qsegc == ksb[None, :], logits,
                                       jnp.float32(-1e30))
                if masked:
                    logits = _causal_apply(jax, jnp, dmat, qi, ki,
                                           block_q, block_k, logits)
                m_cur = jnp.maximum(m_prev,
                                    logits.max(axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_cur)
                p = jnp.exp(logits - m_cur)
                # softmax normalizer accumulates the RAW probabilities;
                # dropout applies to the normalized output, which
                # divides by l at the end — only the acc matmul sees
                # the mask
                l_cur = l_prev * alpha + p.sum(axis=-1, keepdims=True)
                if has_drop:
                    bits = draw_bits(seed_ref, bh, qi, ki)
                    p = jnp.where(bits >= thresh, p * inv_keep,
                                  jnp.float32(0.0))
                acc = (acc * alpha
                       + jnp.dot(p.astype(qb.dtype), vb,
                                 preferred_element_type=jnp.float32))
                return acc, m_cur, l_cur
            return body

        acc0 = jnp.zeros((block_q, d), jnp.float32)
        m0 = jnp.full((block_q, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        carry0 = (acc0, m0, l0)
        if has_segs:
            hi = seg_hi
            if is_causal:
                k_hi = (qi + 1) * block_q
                hi = jnp.minimum(
                    hi, (k_hi + block_k - 1) // jnp.int32(block_k))
            acc, m_f, l_f = jax.lax.fori_loop(
                seg_lo, hi, make_body(is_causal), carry0)
        elif is_causal and block_q == block_k:
            # diagonal split: interior blocks [0, qi) need no mask at
            # all (measured VPU cost); only the diagonal block does
            carry = jax.lax.fori_loop(jnp.int32(0), qi,
                                      make_body(False), carry0)
            acc, m_f, l_f = make_body(True)(qi, carry)
        elif is_causal:
            k_hi = (qi + 1) * block_q
            nk_eff = (k_hi + block_k - 1) // jnp.int32(block_k)
            acc, m_f, l_f = jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(nk_eff), make_body(True), carry0)
        else:
            acc, m_f, l_f = jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(nk), make_body(False), carry0)
        l_safe = jnp.maximum(l_f, jnp.float32(1e-30))
        o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[...] = m_f + jnp.log(l_safe)   # (block_q, 1)

    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda bh, qi, *_: (bh, qi, _z())),
        pl.BlockSpec((None, sk, d), lambda bh, qi, *_: (bh, _z(), _z())),
        pl.BlockSpec((None, sk, d), lambda bh, qi, *_: (bh, _z(), _z())),
    ]
    if has_bias:
        # per-row tensors carry a trailing unit dim: the TPU lowering
        # requires the last two block dims be (8k, 128k) or equal the
        # array dims — (rows, 1) satisfies that where a 1-D row block
        # cannot
        in_specs.append(
            pl.BlockSpec((None, sk, 1), lambda bh, qi, *_: (bh, _z(), _z())))
    if has_segs:
        # q segs blocked with the query; k segs whole-row (the loop
        # bounds reduce over them before any key block is touched)
        in_specs.append(
            pl.BlockSpec((None, block_q, 1),
                         lambda bh, qi, *_: (bh, qi, _z())))
        in_specs.append(
            pl.BlockSpec((None, sk, 1), lambda bh, qi, *_: (bh, _z(), _z())))
    out_specs = [
        pl.BlockSpec((None, block_q, d), lambda bh, qi, *_: (bh, qi, _z())),
        pl.BlockSpec((None, block_q, 1), lambda bh, qi, *_: (bh, qi, _z())),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b * h, sq, d), dtype),
        jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
    ]
    if has_drop:
        from jax.experimental.pallas import tpu as pltpu

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * h, nq),
            in_specs=in_specs, out_specs=out_specs)
        return pl.pallas_call(kernel, grid_spec=grid_spec,
                              out_shape=out_shape, interpret=interpret,
                              name="flash_fwd")
    return pl.pallas_call(
        kernel,
        grid=(b * h, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="flash_fwd",
    )


def _segs_bh(segment_ids, h, s, what):
    """[b, s] int segment ids -> [b*h, s, 1] int32 kernel operand."""
    import jax.numpy as jnp

    seg = jnp.asarray(segment_ids).astype(jnp.int32)
    if seg.ndim != 2 or seg.shape[1] != s:
        raise ValueError(
            f"{what} segment_ids must be [batch, {s}], got {seg.shape}")
    return jnp.repeat(seg, h, axis=0)[:, :, None]


def flash_attention_fwd(q, k, v, bias=None, is_causal=False, scale=None,
                        block_q=256, block_k=256, interpret=False,
                        dropout_p=0.0, seed=None, segment_ids=None,
                        kv_segment_ids=None):
    """Returns (out [b,h,sq,d], lse [b*h, sq, 1]). bias: [b, sk] additive.
    dropout_p > 0 needs `seed` (int32[1]): in-kernel counter-addressed
    probability dropout on the normalized attention weights.
    segment_ids ([b, sq] int, NON-DECREASING along tokens — the packed
    layout from core/lod.pack_padded) restricts attention to same-segment
    tokens with a block-level early-out; kv_segment_ids defaults to
    segment_ids (self-attention packing)."""
    import jax.numpy as jnp

    b, h, sq, d = q.shape
    sk = k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash kernels need block-tileable lengths; got sq={sq}, "
            f"sk={sk} with blocks ({block_q}, {block_k}) — use "
            f"flash_attention() which falls back to the XLA reference")
    if dropout_p and seed is None:
        raise ValueError("flash dropout needs a seed (int32[1] array)")
    if is_causal and sq != sk:
        raise ValueError(
            "flash kernels mask causal start-aligned (row >= col); the "
            "reference semantics for sq != sk align the diagonal at the "
            "END — use flash_attention(), which falls back")
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    has_segs = segment_ids is not None
    call = _flash_fwd_kernels(b, h, sq, sk, d, s, is_causal,
                              bias is not None, block_q, block_k, q.dtype,
                              interpret, dropout_p, has_segs)
    lead = (seed,) if dropout_p else ()
    args = [qr, kr, vr]
    if bias is not None:
        args.append(jnp.repeat(bias, h, axis=0)[:, :, None])  # [b*h,sk,1]
    if has_segs:
        args.append(_segs_bh(segment_ids, h, sq, "query"))
        args.append(_segs_bh(
            segment_ids if kv_segment_ids is None else kv_segment_ids,
            h, sk, "key"))
    out, lse = call(*lead, *args)
    return out.reshape(b, h, sq, d), lse          # lse: [b*h, sq, 1]


def flash_attention_tpu(q, k, v, is_causal=False, scale=None,
                        block_q=256, block_k=256):
    """Forward-only entry (kept for callers that don't differentiate)."""
    sq, sk = q.shape[2], k.shape[2]
    if (sq % min(block_q, sq) or sk % min(block_k, sk)
            or (is_causal and sq != sk)):
        return sdpa_reference(q, k, v, None, is_causal, scale)
    out, _ = flash_attention_fwd(q, k, v, None, is_causal, scale,
                                 block_q, block_k)
    return out


# --------------------------------------------------------------------------
# backward kernels: dQ (grid over q blocks) and dK/dV (grid over k blocks)
# --------------------------------------------------------------------------

def flash_attention_bwd(q, k, v, bias, out, lse, g, is_causal, scale,
                        block_q=256, block_k=256, interpret=False,
                        dropout_p=0.0, seed=None, segment_ids=None,
                        kv_segment_ids=None):
    import jax
    import jax.numpy as jnp

    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    sk = k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = sq // block_q
    nk = sk // block_k
    has_bias = bias is not None
    has_segs = segment_ids is not None
    has_drop = dropout_p > 0.0
    if has_drop:
        from jax.experimental.pallas import tpu as pltpu

        _check_drop_grid(sk, block_k)
        thresh, inv_keep = _drop_consts(dropout_p)
        # dropout composes AFTER the softmax: O = (D∘P)V with
        # D = mask/keep. delta = rowsum(dO∘O) still equals
        # rowsum(P∘(D∘dP_raw)), so the correction term is unchanged;
        # the kernels regenerate D per block from (seed, bh, qi, ki)
        # and apply it to dP (and to P for dV).

        def draw_bits(seed_ref, bh, qi, ki):
            if interpret:  # Mosaic PRNG has no CPU lowering
                return _hash_bits(jnp, jax, seed_ref[0], bh, qi, ki,
                                  block_q, block_k)
            return _block_bits(pltpu, seed_ref, bh, qi, ki,
                               block_q, block_k)

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    orr = out.reshape(b * h, sq, d)
    gr = g.reshape(b * h, sq, d)
    # D_i = rowsum(dO_i * O_i) — the softmax-correction term
    # (kept (b*h, sq, 1): see the fwd block-constraint note)
    delta = (gr.astype(jnp.float32) * orr.astype(jnp.float32)).sum(
        -1, keepdims=True)
    bias_bh = jnp.repeat(bias, h, axis=0)[:, :, None] if has_bias \
        else None
    if has_segs:
        qseg_bh = _segs_bh(segment_ids, h, sq, "query")
        kseg_bh = _segs_bh(
            segment_ids if kv_segment_ids is None else kv_segment_ids,
            h, sk, "key")

    def dq_kernel(*refs):
        refs = list(refs)
        if has_drop:
            seed_ref = refs.pop(0)
        if has_segs:
            # inputs end (..., qseg, kseg); the single output dq trails
            kseg_ref = refs.pop(-2)
            qseg_ref = refs.pop(-2)
        if has_bias:
            (q_ref, k_ref, v_ref, b_ref, g_ref, lse_ref, dl_ref,
             dq_ref) = refs
        else:
            q_ref, k_ref, v_ref, g_ref, lse_ref, dl_ref, dq_ref = refs
        bh = pl.program_id(0)
        qi = pl.program_id(1)
        sf = jnp.float32(s)
        # scale folded into the query block, SAME side as the forward
        # so the recomputed logits match the saved lse bit-for-bit
        qb = (q_ref[...].astype(jnp.float32) * sf).astype(q_ref.dtype)
        gb = g_ref[...]
        lse_b = lse_ref[...]                      # (block_q, 1)
        dl_b = dl_ref[...]
        if is_causal:
            dmat = (jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 0)
                    - jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 1))
        if has_segs:
            # same contiguous-range early-out as the forward
            qsegc = qseg_ref[...]
            qmin, qmax = qsegc.min(), qsegc.max()
            kseg_all = kseg_ref[...]
            lo_tok = jnp.sum((kseg_all < qmin).astype(jnp.int32))
            hi_tok = jnp.sum((kseg_all <= qmax).astype(jnp.int32))
            seg_lo = lo_tok // jnp.int32(block_k)
            seg_hi = (hi_tok + jnp.int32(block_k - 1)) \
                // jnp.int32(block_k)

        def make_body(masked):
            def body(ki, acc):
                kb = k_ref[pl.ds(ki * block_k, block_k), :]
                vb = v_ref[pl.ds(ki * block_k, block_k), :]
                logits = jnp.dot(qb, kb.T,
                                 preferred_element_type=jnp.float32)
                if has_bias:
                    bb = b_ref[pl.ds(ki * block_k, block_k), 0]
                    logits = logits + bb[None, :]
                if has_segs:
                    ksb = kseg_ref[pl.ds(ki * block_k, block_k), 0]
                    logits = jnp.where(qsegc == ksb[None, :], logits,
                                       jnp.float32(-1e30))
                if masked:
                    logits = _causal_apply(jax, jnp, dmat, qi, ki,
                                           block_q, block_k, logits)
                p = jnp.exp(logits - lse_b)
                dp = jnp.dot(gb, vb.T,
                             preferred_element_type=jnp.float32)
                if has_drop:
                    bits = draw_bits(seed_ref, bh, qi, ki)
                    dp = jnp.where(bits >= thresh, dp * inv_keep,
                                   jnp.float32(0.0))
                ds = p * (dp - dl_b)
                # dq = (ds*s) @ kb = ds @ (s*kb): scale the [bk, d]
                # operand, not the [bq, bk] ds
                kbs = (kb.astype(jnp.float32) * sf).astype(kb.dtype)
                return acc + jnp.dot(ds.astype(qb.dtype), kbs,
                                     preferred_element_type=jnp.float32)
            return body

        acc0 = jnp.zeros((block_q, d), jnp.float32)
        if has_segs:
            hi = seg_hi
            if is_causal:
                hi = jnp.minimum(
                    hi, ((qi + 1) * block_q + block_k - 1)
                    // jnp.int32(block_k))
            acc = jax.lax.fori_loop(seg_lo, hi, make_body(is_causal),
                                    acc0)
        elif is_causal and block_q == block_k:
            acc = jax.lax.fori_loop(jnp.int32(0), qi,
                                    make_body(False), acc0)
            acc = make_body(True)(qi, acc)
        elif is_causal:
            nk_eff = ((qi + 1) * block_q + block_k - 1) \
                // jnp.int32(block_k)
            acc = jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(nk_eff), make_body(True), acc0)
        else:
            acc = jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(nk), make_body(False), acc0)
        dq_ref[...] = acc.astype(dq_ref.dtype)

    dq_in = [
        pl.BlockSpec((None, block_q, d), lambda bh, qi, *_: (bh, qi, _z())),
        pl.BlockSpec((None, sk, d), lambda bh, qi, *_: (bh, _z(), _z())),
        pl.BlockSpec((None, sk, d), lambda bh, qi, *_: (bh, _z(), _z())),
    ]
    if has_bias:
        dq_in.append(pl.BlockSpec((None, sk, 1),
                                  lambda bh, qi, *_: (bh, _z(), _z())))
    dq_in += [
        pl.BlockSpec((None, block_q, d), lambda bh, qi, *_: (bh, qi, _z())),
        pl.BlockSpec((None, block_q, 1), lambda bh, qi, *_: (bh, qi, _z())),
        pl.BlockSpec((None, block_q, 1), lambda bh, qi, *_: (bh, qi, _z())),
    ]
    if has_segs:
        dq_in.append(pl.BlockSpec((None, block_q, 1),
                                  lambda bh, qi, *_: (bh, qi, _z())))
        dq_in.append(pl.BlockSpec((None, sk, 1),
                                  lambda bh, qi, *_: (bh, _z(), _z())))
    dq_args = [qr, kr, vr] + ([bias_bh] if has_bias else []) + \
        [gr, lse, delta] + ([qseg_bh, kseg_bh] if has_segs else [])
    dq_out_spec = pl.BlockSpec((None, block_q, d),
                               lambda bh, qi, *_: (bh, qi, _z()))
    dq_out_shape = jax.ShapeDtypeStruct((b * h, sq, d), q.dtype)
    if has_drop:
        dq_grid = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * h, nq),
            in_specs=dq_in, out_specs=dq_out_spec)
        dq = pl.pallas_call(dq_kernel, grid_spec=dq_grid,
                            out_shape=dq_out_shape,
                            interpret=interpret,
                            name="flash_bwd_dq")(seed, *dq_args)
    else:
        dq = pl.pallas_call(
            dq_kernel, grid=(b * h, nq), in_specs=dq_in,
            out_specs=dq_out_spec,
            out_shape=dq_out_shape,
            interpret=interpret,
            name="flash_bwd_dq",
        )(*dq_args)

    def dkv_kernel(*refs):
        refs = list(refs)
        if has_drop:
            seed_ref = refs.pop(0)
        if has_segs:
            # inputs end (..., qseg, kseg); 2-3 outputs (dk, dv, db?)
            n_out = 3 if has_bias else 2
            kseg_ref = refs.pop(-(n_out + 1))
            qseg_ref = refs.pop(-(n_out + 1))
        if has_bias:
            (q_ref, k_ref, v_ref, b_ref, g_ref, lse_ref, dl_ref,
             dk_ref, dv_ref, db_ref) = refs
        else:
            (q_ref, k_ref, v_ref, g_ref, lse_ref, dl_ref, dk_ref,
             dv_ref) = refs
        bh = pl.program_id(0)
        ki = pl.program_id(1)
        kb = k_ref[...]
        vb = v_ref[...]
        sf = jnp.float32(s)
        if has_bias:
            bb = b_ref[...][:, 0]
        if is_causal:
            dmat = (jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 0)
                    - jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 1))
        if has_segs:
            # mirror of the dq early-out: queries that can see THIS key
            # block are those with ids in [min(kseg), max(kseg)]
            ksegc = kseg_ref[...]                 # (block_k, 1)
            ksb_row = ksegc[:, 0]
            kmin, kmax = ksegc.min(), ksegc.max()
            qseg_all = qseg_ref[...]              # (sq, 1)
            lo_tok = jnp.sum((qseg_all < kmin).astype(jnp.int32))
            hi_tok = jnp.sum((qseg_all <= kmax).astype(jnp.int32))
            seg_qlo = lo_tok // jnp.int32(block_q)
            seg_qhi = (hi_tok + jnp.int32(block_q - 1)) \
                // jnp.int32(block_q)

        def make_body(masked):
            def body(qi, carry):
                dk_acc, dv_acc, db_acc = carry
                qb = q_ref[pl.ds(qi * block_q, block_q), :]
                gb = g_ref[pl.ds(qi * block_q, block_q), :]
                lse_b = lse_ref[pl.ds(qi * block_q, block_q), :]
                dl_b = dl_ref[pl.ds(qi * block_q, block_q), :]
                # qbs matches the fwd's scale-folded query block, so
                # the recomputed logits agree with the saved lse; it
                # also IS s*qb, which the dk matmul needs
                qbs = (qb.astype(jnp.float32) * sf).astype(qb.dtype)
                logits = jnp.dot(qbs, kb.T,
                                 preferred_element_type=jnp.float32)
                if has_bias:
                    logits = logits + bb[None, :]
                if has_segs:
                    qsb = qseg_ref[pl.ds(qi * block_q, block_q), :]
                    logits = jnp.where(qsb == ksb_row[None, :], logits,
                                       jnp.float32(-1e30))
                if masked:
                    logits = _causal_apply(jax, jnp, dmat, qi, ki,
                                           block_q, block_k, logits)
                p = jnp.exp(logits - lse_b)
                dp = jnp.dot(gb, vb.T,
                             preferred_element_type=jnp.float32)
                if has_drop:
                    bits = draw_bits(seed_ref, bh, qi, ki)
                    keep = bits >= thresh
                    pd = jnp.where(keep, p * inv_keep, jnp.float32(0.0))
                    dp = jnp.where(keep, dp * inv_keep, jnp.float32(0.0))
                else:
                    pd = p
                dv_acc = dv_acc + jnp.dot(
                    pd.astype(kb.dtype).T, gb,
                    preferred_element_type=jnp.float32)
                dlogits = p * (dp - dl_b)   # d loss/d (q.k*s + bias)
                db_acc = db_acc + dlogits.sum(axis=0)
                # dk = (dlogits*s)^T @ qb = dlogits^T @ (s*qb) = ^T@qbs
                dk_acc = dk_acc + jnp.dot(
                    dlogits.astype(kb.dtype).T, qbs,
                    preferred_element_type=jnp.float32)
                return dk_acc, dv_acc, db_acc
            return body

        z = jnp.zeros((block_k, d), jnp.float32)
        zb = jnp.zeros((block_k,), jnp.float32)
        carry0 = (z, z, zb)
        if has_segs:
            lo = seg_qlo
            if is_causal:
                lo = jnp.maximum(lo, (ki * block_k)
                                 // jnp.int32(block_q))
            outs = jax.lax.fori_loop(lo, seg_qhi, make_body(is_causal),
                                     carry0)
        elif is_causal and block_q == block_k:
            # diagonal block at qi == ki needs the mask; everything
            # after it does not
            carry = make_body(True)(ki, carry0)
            outs = jax.lax.fori_loop(ki + jnp.int32(1), jnp.int32(nq),
                                     make_body(False), carry)
        elif is_causal:
            q_lo = (ki * block_k) // jnp.int32(block_q)
            outs = jax.lax.fori_loop(
                jnp.int32(q_lo), jnp.int32(nq), make_body(True), carry0)
        else:
            outs = jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(nq), make_body(False), carry0)
        dk_acc, dv_acc, db_acc = outs
        dk_ref[...] = dk_acc.astype(dk_ref.dtype)
        dv_ref[...] = dv_acc.astype(dv_ref.dtype)
        if has_bias:
            db_ref[...] = db_acc[:, None]

    dkv_in = [
        pl.BlockSpec((None, sq, d), lambda bh, ki, *_: (bh, _z(), _z())),
        pl.BlockSpec((None, block_k, d), lambda bh, ki, *_: (bh, ki, _z())),
        pl.BlockSpec((None, block_k, d), lambda bh, ki, *_: (bh, ki, _z())),
    ]
    if has_bias:
        dkv_in.append(
            pl.BlockSpec((None, block_k, 1),
                         lambda bh, ki, *_: (bh, ki, _z())))
    dkv_in += [
        pl.BlockSpec((None, sq, d), lambda bh, ki, *_: (bh, _z(), _z())),
        pl.BlockSpec((None, sq, 1), lambda bh, ki, *_: (bh, _z(), _z())),
        pl.BlockSpec((None, sq, 1), lambda bh, ki, *_: (bh, _z(), _z())),
    ]
    if has_segs:
        dkv_in.append(pl.BlockSpec((None, sq, 1),
                                   lambda bh, ki, *_: (bh, _z(), _z())))
        dkv_in.append(pl.BlockSpec((None, block_k, 1),
                                   lambda bh, ki, *_: (bh, ki, _z())))
    dkv_args = [qr, kr, vr] + ([bias_bh] if has_bias else []) + \
        [gr, lse, delta] + ([qseg_bh, kseg_bh] if has_segs else [])
    out_specs = [
        pl.BlockSpec((None, block_k, d), lambda bh, ki, *_: (bh, ki, _z())),
        pl.BlockSpec((None, block_k, d), lambda bh, ki, *_: (bh, ki, _z())),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
        jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
    ]
    if has_bias:
        out_specs.append(pl.BlockSpec((None, block_k, 1),
                                      lambda bh, ki, *_: (bh, ki, _z())))
        out_shape.append(jax.ShapeDtypeStruct((b * h, sk, 1),
                                              jnp.float32))
    # this kernel keeps a head's whole q, dO, lse and delta rows in fast
    # memory (double-buffered; a [sq, 1] float32 row pads to 128 lanes):
    # past about 4k positions that is more than the compiler's default
    # scoped limit (16 MiB of the v5e's 128), so ask for what it needs
    rows = 2 * (2 * sq * d * q.dtype.itemsize + 2 * sq * 128 * 4)
    extra = {}
    if rows > 12 * 2 ** 20 and not interpret:
        from jax.experimental.pallas import tpu as pltpu

        extra["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=rows + 8 * 2 ** 20)
    if has_drop:
        dkv_grid = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * h, nk),
            in_specs=dkv_in, out_specs=out_specs)
        outs = pl.pallas_call(dkv_kernel, grid_spec=dkv_grid,
                              out_shape=out_shape,
                              interpret=interpret,
                              name="flash_bwd_dkv", **extra)(seed, *dkv_args)
    else:
        outs = pl.pallas_call(
            dkv_kernel, grid=(b * h, nk), in_specs=dkv_in,
            out_specs=out_specs, out_shape=out_shape,
            interpret=interpret,
            name="flash_bwd_dkv", **extra,
        )(*dkv_args)
    if has_bias:
        dk, dv, db_bh = outs
        # bias is per (batch, key): sum the head axis
        dbias = db_bh[:, :, 0].reshape(b, h, sk).sum(axis=1).astype(
            bias.dtype)
    else:
        dk, dv = outs
        dbias = None

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d), dbias)


# --------------------------------------------------------------------------
# differentiable flash attention + dispatch
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _flash_diff_fn(is_causal, scale, has_bias, interpret, dropout_p,
                   block_q, block_k, has_segs=False, block_q_bwd=None,
                   block_k_bwd=None):
    import jax

    # the backward kernels may tile differently from the forward (the
    # tuning table keys them separately: dQ/dKV have their own VMEM
    # pressure) — EXCEPT under in-kernel dropout, where the counter
    # addressing is (seed, bh, qi, ki) BLOCK indices: fwd and bwd must
    # regenerate identical masks, so flash_attention pins bwd == fwd
    # blocks whenever dropout_p > 0
    bq_b = block_q if block_q_bwd is None else block_q_bwd
    bk_b = block_k if block_k_bwd is None else block_k_bwd

    @jax.custom_vjp
    def f(q, k, v, bias, qseg, kseg, seed):
        out, _ = flash_attention_fwd(q, k, v, bias, is_causal, scale,
                                     block_q, block_k, interpret,
                                     dropout_p, seed, qseg, kseg)
        return out

    def fwd(q, k, v, bias, qseg, kseg, seed):
        out, lse = flash_attention_fwd(q, k, v, bias, is_causal, scale,
                                       block_q, block_k, interpret,
                                       dropout_p, seed, qseg, kseg)
        return out, (q, k, v, bias, qseg, kseg, seed, out, lse)

    def bwd(res, g):
        q, k, v, bias, qseg, kseg, seed, out, lse = res
        dq, dk, dv, dbias = flash_attention_bwd(q, k, v, bias, out, lse,
                                                g, is_causal, scale,
                                                bq_b, bk_b,
                                                interpret, dropout_p,
                                                seed, qseg, kseg)
        return dq, dk, dv, dbias, None, None, None

    f.defvjp(fwd, bwd)
    return f


def _tuned(kernel, key):
    """Consult the autotuned kernel-config table (paddle_tpu.tuning).
    Returns the config dict or None on a miss; an unreadable table file
    is the table layer's to report (it warns and serves heuristics)."""
    from ..tuning import table as _tt

    return _tt.lookup(kernel, key)


def _seq_bucket(n):
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def _pick_blocks_heuristic(sq, sk, block_q=None, block_k=None):
    """The hand-picked block ladder, measured on TPU v5e (tools/
    tune_flash.py sweep over {128,256,512,1024}^2 at seq
    1024/2048/4096): 512x512 wins every config — 1.06x/2.96x/3.10x vs
    the XLA fused reference fwd+bwd. EQUAL blocks also enable the
    diagonal-split causal path (interior blocks skip the mask select
    entirely), worth ~10% alone. Lengths not divisible by 512 take the
    largest 128-multiple that divides them (1280 -> 256, 768 -> 384)
    so flash still engages. This is the committed-fallback source of
    truth: the default tuning table's entries are GENERATED from it
    (tuning.autotune.fallback_config), so untuned == pre-tuning."""
    def _one(s, override):
        if override is not None:
            return min(override, s)
        for b in (512, 384, 256, 128):
            if s % b == 0 or b >= s:
                return min(b, s)
        return min(128, s)
    return _one(sq, block_q), _one(sk, block_k)


def _pick_blocks(sq, sk, block_q=None, block_k=None, head_dim=None,
                 dtype=None, kernel="flash_fwd"):
    """Block sizes for the flash fwd/bwd kernels: explicit overrides
    win; otherwise the autotuned table (keyed (head_dim, sq bucket,
    sk bucket, dtype), device-tiered) is consulted, and a miss — or a
    tuned entry that does not tile THESE lengths — falls back to the
    hand-picked heuristic. The _flash_plan divisibility gate derives
    from this function — one source of truth either way."""
    if block_q is None and block_k is None and head_dim is not None:
        cfg = _tuned(kernel, (int(head_dim), _seq_bucket(sq),
                              _seq_bucket(sk), str(dtype)))
        if cfg is not None:
            try:
                bq = min(int(cfg["block_q"]), sq)
                bk = min(int(cfg["block_k"]), sk)
            except (KeyError, TypeError, ValueError):
                bq = bk = 0
            if bq > 0 and bk > 0 and sq % bq == 0 and sk % bk == 0:
                return bq, bk
    return _pick_blocks_heuristic(sq, sk, block_q, block_k)


def flash_attention(q, k, v, bias=None, is_causal=False, scale=None,
                    interpret=False, block_q=None, block_k=None,
                    dropout_p=0.0, dropout_seed=None, segment_ids=None,
                    kv_segment_ids=None):
    """Differentiable flash attention (fwd+bwd pallas). bias: optional
    [b, sk] additive key bias (padding masks). dropout_p: in-kernel
    probability dropout on the attention weights, addressed by
    (dropout_seed, bh, qi, ki) so fwd and both bwd kernels regenerate
    identical masks. segment_ids: optional [b, sq] int per-token packed
    segment ids (non-decreasing per row — core/lod.pack_padded layout);
    attention is restricted to same-segment tokens with a block-level
    early-out, so fully-cross-segment blocks cost nothing. Sequence
    lengths that do not tile into blocks fall back to the XLA reference
    (the blockwise grid would silently truncate the tail otherwise)."""
    sq, sk = q.shape[2], k.shape[2]
    d, dt = q.shape[-1], str(q.dtype)
    explicit = block_q is not None or block_k is not None
    block_q, block_k = _pick_blocks(sq, sk, block_q, block_k,
                                    head_dim=d, dtype=dt)
    if explicit or dropout_p:
        # explicit overrides apply to both passes; dropout pins bwd ==
        # fwd (the counter-addressed bits are block-indexed)
        bq_bwd, bk_bwd = block_q, block_k
    else:
        bq_bwd, bk_bwd = _pick_blocks(sq, sk, None, None, head_dim=d,
                                      dtype=dt, kernel="flash_bwd")
    if (sq % block_q or sk % block_k
            or (is_causal and sq != sk)):
        # fallbacks: non-tileable lengths, and causal with sq != sk —
        # the kernels' causal mask is start-aligned (row >= col) while
        # the reference aligns the diagonal at the END for cross
        # shapes; rather than be silently wrong, use the reference
        # (r05 review finding: both old and new kernels mis-masked
        # cross-shape causal)
        if dropout_p and dropout_seed is None:
            raise ValueError(
                "flash dropout needs dropout_seed (int32[1])")
        import jax

        mask4 = None if bias is None else bias[:, None, None, :]
        if segment_ids is not None:
            sb = segment_bias(segment_ids, kv_segment_ids)
            mask4 = sb if mask4 is None else mask4 + sb
        key = (jax.random.fold_in(jax.random.PRNGKey(0),
                                  dropout_seed[0])
               if dropout_p else None)
        return sdpa_reference(q, k, v, mask4, is_causal, scale,
                              dropout_p, key)
    if dropout_p and dropout_seed is None:
        raise ValueError("flash dropout needs dropout_seed (int32[1])")
    f = _flash_diff_fn(is_causal, scale, bias is not None, interpret,
                       float(dropout_p), block_q, block_k,
                       segment_ids is not None, bq_bwd, bk_bwd)
    return f(q, k, v, bias, segment_ids, kv_segment_ids, dropout_seed)


#: trace-scoped flag: the program being traced is split over more than
#: one device by XLA's SPMD partitioner (a mesh-sharded train step, the
#: sharded serving pool). A Mosaic kernel cannot be partitioned
#: automatically — the compiler refuses it ("wrap the call in a
#: shard_map") — while the XLA composition can, so under this scope
#: every dispatcher takes the composition. Trace-time only, like
#: `decode_shardings`, which implies it.
_PARTITIONED = [False]


@contextlib.contextmanager
def partitioned_trace(on=True):
    """Scope a jit trace whose program the SPMD partitioner will split
    across devices (see `_PARTITIONED`); also usable as a decorator on
    the traced function. `on=False` is a no-op, so a caller can pass
    `mesh.size > 1`."""
    prev = _PARTITIONED[0]
    _PARTITIONED[0] = prev or bool(on)
    try:
        yield
    finally:
        _PARTITIONED[0] = prev


def _flash_usable():
    """Whether a dispatcher may take its Pallas kernel in this trace:
    not under `PT_FLASH_ATTENTION=0` (the XLA reference everywhere) and
    not in a program the SPMD partitioner splits (see `_PARTITIONED`).
    There is no run-time probe: a kernel that fails to lower or run on
    a TPU backend raises where it is called, and
    tests/test_tpu_compile.py compiles each kernel for the chip."""
    return (os.environ.get("PT_FLASH_ATTENTION", "auto") != "0"
            and not _PARTITIONED[0] and _DECODE_SPECS[0] is None)


def sdpa_reference_bshd(q, k, v, mask=None, is_causal=False, scale=None,
                        dropout_p=0.0, dropout_key=None):
    """XLA attention over [batch, seq, heads, head_dim] operands: the
    head transpose folds into the einsum's dimension numbers instead of
    materializing (measured 1.3x on the ERNIE-block attention stack vs
    explicit BHSD transposes). Output is [B, S, H, D]."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * s
    if is_causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((qlen, klen), bool), klen - qlen)
        logits = jnp.where(cmask, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    probs = _prob_dropout(probs, dropout_p, dropout_key)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


_NO_FLASH = object()


def _seed_from_key(key):
    """int32[1] kernel seed from a jax PRNG key (typed or raw), folding
    ALL key words (odd-multiply + xor chain, no extra RNG draw). The
    old code took only the FIRST word — the threefry HIGH word, which
    is zero for every PRNGKey(n) with n < 2^32, so plain per-step keys
    all mapped to seed 0 (ADVICE r05 medium). For such keys the fold
    reduces to the low word; distinct keys give distinct seeds."""
    import jax
    import jax.numpy as jnp

    try:
        data = jax.random.key_data(key)
    except Exception:
        data = key
    data = jnp.ravel(data)
    acc = jax.lax.bitcast_convert_type(data[:1], jnp.uint32).reshape(-1)
    for i in range(1, int(data.shape[0])):
        w = jax.lax.bitcast_convert_type(data[i:i + 1],
                                         jnp.uint32).reshape(-1)
        acc = acc * jnp.uint32(0x9E3779B9) ^ w
    return jax.lax.bitcast_convert_type(acc[:1], jnp.int32)


def _flash_plan(seq_q, seq_k, head_dim, mask, batch, heads,
                dropout_p=0.0, dropout_key=None, dtype=None):
    """All the flash-dispatch gates in one place: TPU backend, long
    enough sequence, block-divisible lengths, head_dim small enough, a
    mask reducible to a key-position bias, and the kernel importable.
    Prob-dropout runs IN-KERNEL (counter-addressed bits) and needs the
    caller's dropout_key. Returns the key-position bias to pass to the
    kernel (None when maskless), or the _NO_FLASH sentinel when flash
    cannot run. `dtype` keeps the divisibility gate consulting the
    SAME tuning-table entry flash_attention will pick blocks from."""
    min_flash_len = int(os.environ.get("PT_FLASH_MIN_SEQ", "512"))
    if dropout_p and dropout_key is None:
        return _NO_FLASH
    bq, bk = _pick_blocks(seq_q, seq_k, head_dim=head_dim, dtype=dtype)
    if not (_on_tpu() and head_dim <= 256
            and seq_q >= min_flash_len
            and seq_q % bq == 0 and seq_k % bk == 0):
        return _NO_FLASH
    bias = None
    if mask is not None:
        bias = _kv_bias(mask, batch, heads, seq_k)
        if bias is None:
            return _NO_FLASH
    if not _flash_usable():
        return _NO_FLASH
    return bias


def _with_segment_mask(mask, segment_ids, bshd=False):
    """Fold packed segment ids into a dense additive mask for the XLA
    reference paths (broadcasts over heads and, via [b,1,sq,sk], both
    layouts)."""
    import jax.numpy as jnp

    if segment_ids is None:
        return mask
    sb = segment_bias(segment_ids)
    if mask is None:
        return sb
    m = mask
    if m.dtype == jnp.bool_:
        m = jnp.where(m, jnp.float32(0.0), jnp.float32(-1e30))
    return m + sb


def sdpa_bshd(q, k, v, mask=None, is_causal=False, scale=None,
              dropout_p=0.0, dropout_key=None, segment_ids=None):
    """sdpa over [B, S, H, D] operands. Flash engages at seq >=
    PT_FLASH_MIN_SEQ_BSHD (default 1024). Measured in-model (ERNIE b8
    seq1024, bench `ernie_long`, r05 kernel with 512x512 blocks +
    diagonal-split causal): flash 1.22x vs the XLA fused path at
    dropout 0, and 1.56x at dropout 0.1 — the XLA path materializes +
    draws RNG for the full [B,H,S,S] prob tensor while the kernel's
    counter-addressed in-kernel bits are ~free. (r04's kernel LOST
    in-model at 1024, 0.94x, which is why the old default was 8192;
    the r05 block-tuning flipped it.)

    segment_ids ([B, S] int, packed-layout monotone rows) routes the
    PACKED flash path: same-segment masking in-kernel with block-level
    early-out; the packed gate uses PT_FLASH_MIN_SEQ (512) rather than
    the BSHD in-model threshold because the packed kernel also SKIPS
    cross-segment blocks — it wins earlier."""
    import jax.numpy as jnp

    if q.ndim == 4 and k.shape[2] != q.shape[2]:
        # grouped-query attention: fewer key-value heads than query heads.
        # K and V are repeated to the query heads before either path (the
        # kernels index one K/V per query head); the repeat's transpose
        # sums dK and dV over each group
        group = q.shape[2] // k.shape[2]
        if group * k.shape[2] != q.shape[2]:
            raise ValueError(f"{q.shape[2]} query heads are not a "
                             f"multiple of {k.shape[2]} key-value heads")
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    if q.ndim == 4:
        if segment_ids is None:
            env = "PT_FLASH_MIN_SEQ_BSHD_DROP" if dropout_p else \
                "PT_FLASH_MIN_SEQ_BSHD"
            min_bshd = int(os.environ.get(env, "1024"))
            too_short = q.shape[1] < min_bshd
        else:
            too_short = False
        bias = (_NO_FLASH if too_short else
                _flash_plan(q.shape[1], k.shape[1], q.shape[-1], mask,
                            q.shape[0], q.shape[2], dropout_p,
                            dropout_key, dtype=str(q.dtype)))
        if bias is not _NO_FLASH:
            seed = _seed_from_key(dropout_key) if dropout_p else None
            out = flash_attention(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2), bias, is_causal, scale,
                dropout_p=dropout_p, dropout_seed=seed,
                segment_ids=segment_ids)
            return jnp.swapaxes(out, 1, 2)
    return sdpa_reference_bshd(q, k, v,
                               _with_segment_mask(mask, segment_ids),
                               is_causal, scale, dropout_p, dropout_key)


# --------------------------------------------------------------------------
# decode-mode attention: one query token against a static KV cache
# --------------------------------------------------------------------------

#: active decode-sharding annotation (trace-scoped): {"q"/"kv"/"out":
#: jax.sharding.NamedSharding}. The sharded serving engine wraps its
#: step/join traces in `decode_shardings(...)` so the UNCHANGED decode
#: kernels get `with_sharding_constraint` pinned on their operands —
#: the TPP/TVM shape of the win: the hot kernel stays put while the
#: layout/distribution layer moves around it.
_DECODE_SPECS = [None]


@contextlib.contextmanager
def decode_shardings(specs):
    """Scope a {'q': NamedSharding, 'kv': ..., 'out': ...} annotation
    over a jit trace; every `decode_attention` /
    `paged_decode_attention` call traced inside constrains its operands
    and output accordingly. No-op (and zero-cost) when unset."""
    prev = _DECODE_SPECS[0]
    _DECODE_SPECS[0] = dict(specs) if specs else None
    try:
        yield
    finally:
        _DECODE_SPECS[0] = prev


def _constrain_decode(x, what):
    specs = _DECODE_SPECS[0]
    if specs is None or x is None:
        return x
    ns = specs.get(what)
    if ns is None:
        return x
    import jax

    return jax.lax.with_sharding_constraint(x, ns)


def decode_attention_reference(q, k, v, length, bias=None, scale=None):
    """XLA reference for single-token decode attention against a
    preallocated cache. q [b, h, 1, d]; k/v [b, h, L, d] where L is the
    cache's max_length; `length` (traced int32 scalar or [b]) marks how
    many cache slots hold real tokens — key positions >= length are
    masked out; bias: optional [b, L] additive key bias (padded-prompt
    holes). Always correct, runs anywhere; the flash_decode kernel is
    checked against THIS composition in interpret mode on CPU."""
    import jax.numpy as jnp

    b, h, sq, d = q.shape
    L = k.shape[2]
    length = jnp.asarray(length, jnp.int32)
    kpos = jnp.arange(L, dtype=jnp.int32)
    valid = kpos[None, :] < (length.reshape(-1, 1) if length.ndim
                             else length.reshape(1, 1))
    m = jnp.where(valid, jnp.float32(0.0), jnp.float32(-1e30))
    if m.shape[0] == 1:
        m = jnp.broadcast_to(m, (b, L))
    if bias is not None:
        m = m + jnp.asarray(bias, jnp.float32)
    return sdpa_reference(q, k, v, m[:, None, None, :], False, scale)


def _pick_decode_splits_heuristic(L):
    """Hand-picked split-K ladder: prefer ~512-token splits (the
    MXU-util sweet spot for a (1, d) x (split, d) decode dot). The
    committed-fallback source of truth for the flash_decode /
    flash_verify tuning-table entries."""
    for n in (8, 4, 2):
        if L % n == 0 and (L // n) % 128 == 0 and L // n >= 512:
            return n
    return 1


def _split_legal(L, n):
    """Each split must stay a lane-friendly 128-multiple."""
    return n >= 1 and L % n == 0 and (L // n) % 128 == 0


def _pick_decode_splits(L, split_k=None, head_dim=None, dtype=None,
                        kernel="flash_decode", T=None):
    """Split-K factor over the cache length: an explicit `split_k`
    wins (sanitized down to the nearest legal factor); otherwise the
    autotuned table (keyed (head_dim, L bucket, dtype[, T]),
    device-tiered) is consulted, and a miss — or an entry illegal for
    THIS L — falls back to the hand-picked ~512-token ladder."""
    if split_k is not None:
        n = max(1, int(split_k))
        while L % n or (L // n) % 128:
            n -= 1
        return max(1, n)
    if head_dim is not None:
        key = (int(head_dim), _seq_bucket(L), str(dtype))
        if kernel == "flash_verify":
            key = key + (int(T if T is not None else 1),)
        cfg = _tuned(kernel, key)
        if cfg is not None:
            try:
                n = int(cfg["split_k"])
            except (KeyError, TypeError, ValueError):
                n = 0
            if _split_legal(L, n):
                return n
    return _pick_decode_splits_heuristic(L)


def _flash_decode_call(b, h, L, d, s, n_splits, has_bias, interpret):
    import jax
    import jax.numpy as jnp

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    split = L // n_splits

    def kernel(len_ref, *refs):
        if has_bias:
            q_ref, k_ref, v_ref, bias_ref, o_ref, m_ref, l_ref = refs
        else:
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
        si = pl.program_id(1)
        start = si * jnp.int32(split)
        # per-ROW written count: the serving slot pool decodes rows at
        # independent cache offsets (len_ref is [b]; lockstep batches
        # are the all-equal special case)
        n_valid = len_ref[pl.program_id(0) // jnp.int32(h)]

        @pl.when(start < n_valid)
        def _compute():
            sf = jnp.float32(s)
            qb = (q_ref[...].astype(jnp.float32) * sf).astype(
                q_ref.dtype)                      # (1, d)
            kb = k_ref[...]                        # (split, d)
            vb = v_ref[...]
            logits = jnp.dot(qb, kb.T,
                             preferred_element_type=jnp.float32)
            kpos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, split), 1)
            logits = jnp.where(kpos < n_valid, logits,
                               jnp.float32(-1e30))
            if has_bias:
                logits = logits + bias_ref[...][:, 0][None, :]
            m = logits.max(axis=-1, keepdims=True)          # (1, 1)
            p = jnp.exp(logits - m)
            l = p.sum(axis=-1, keepdims=True)
            acc = jnp.dot(p.astype(qb.dtype), vb,
                          preferred_element_type=jnp.float32)
            o_ref[...] = acc
            m_ref[...] = m
            l_ref[...] = l

        @pl.when(start >= n_valid)
        def _skip():
            # split entirely past the written cache region: contribute
            # an exact zero to the combine (m=-1e30 -> alpha underflows)
            o_ref[...] = jnp.zeros((1, d), jnp.float32)
            m_ref[...] = jnp.full((1, 1), -1e30, jnp.float32)
            l_ref[...] = jnp.zeros((1, 1), jnp.float32)

    in_specs = [
        pl.BlockSpec((None, 1, d), lambda bh, si, *_: (bh, _z(), _z())),
        pl.BlockSpec((None, split, d), lambda bh, si, *_: (bh, si, _z())),
        pl.BlockSpec((None, split, d), lambda bh, si, *_: (bh, si, _z())),
    ]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((None, split, 1),
                         lambda bh, si, *_: (bh, si, _z())))
    out_specs = [
        pl.BlockSpec((None, None, 1, d),
                     lambda bh, si, *_: (bh, si, _z(), _z())),
        pl.BlockSpec((None, None, 1, 1),
                     lambda bh, si, *_: (bh, si, _z(), _z())),
        pl.BlockSpec((None, None, 1, 1),
                     lambda bh, si, *_: (bh, si, _z(), _z())),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b * h, n_splits, 1, d), jnp.float32),
        jax.ShapeDtypeStruct((b * h, n_splits, 1, 1), jnp.float32),
        jax.ShapeDtypeStruct((b * h, n_splits, 1, 1), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b * h, n_splits),
        in_specs=in_specs, out_specs=out_specs)
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=out_shape, interpret=interpret,
                          name="flash_decode")


def flash_decode(q, k, v, length, bias=None, scale=None, split_k=None,
                 interpret=False):
    """Pallas flash-decode: one query token per row against the cached
    K/V, split-K over the cache length so a long cache still spreads
    across the grid (a single (1, L) row otherwise leaves the chip
    idle). Per-split partial (acc, m, l) merge in XLA with the standard
    logsumexp combine. `length` is the written-token count (int32,
    traced; a scalar for lockstep batches or [b] for the serving slot
    pool, where every row decodes at its own offset); splits entirely
    past a row's count are skipped in-kernel."""
    import jax.numpy as jnp

    b, h, sq, d = q.shape
    if sq != 1:
        raise ValueError(f"flash_decode takes a single query token, got "
                         f"sq={sq} — prefill runs on the regular flash "
                         f"path")
    L = k.shape[2]
    n_splits = _pick_decode_splits(L, split_k, head_dim=d,
                                   dtype=str(q.dtype))
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qr = q.reshape(b * h, 1, d)
    kr = k.reshape(b * h, L, d)
    vr = v.reshape(b * h, L, d)
    len_arr = jnp.broadcast_to(
        jnp.asarray(length, jnp.int32).reshape(-1), (b,))
    call = _flash_decode_call(b, h, L, d, s, n_splits, bias is not None,
                              interpret)
    args = [qr, kr, vr]
    if bias is not None:
        args.append(jnp.repeat(jnp.asarray(bias, jnp.float32), h,
                               axis=0)[:, :, None])
    acc, m, l = call(len_arr, *args)               # [b*h, ns, 1, ...]
    m_star = m.max(axis=1, keepdims=True)
    alpha = jnp.exp(m - m_star)
    num = (acc * alpha).sum(axis=1)                # [b*h, 1, d]
    den = jnp.maximum((l * alpha).sum(axis=1), 1e-30)
    return (num / den).astype(q.dtype).reshape(b, h, 1, d)


def decode_attention(q, k, v, length, bias=None, scale=None, split_k=None,
                     interpret=False):
    """Decode-attention dispatch: the split-K pallas kernel on TPU (or
    under interpret=True for CPU parity tests), the XLA reference
    composition where a gate says so (another backend, a cache too
    short or off the 128 tiling, a partitioned trace). A kernel that
    is chosen and fails raises."""
    L = k.shape[2]
    q = _constrain_decode(q, "q")
    k = _constrain_decode(k, "kv")
    v = _constrain_decode(v, "kv")
    use_kernel = interpret or (
        _on_tpu() and q.shape[-1] <= 256 and L >= 256 and L % 128 == 0
        and _flash_usable())
    if use_kernel:
        return _constrain_decode(
            flash_decode(q, k, v, length, bias, scale, split_k,
                         interpret), "out")
    return _constrain_decode(
        decode_attention_reference(q, k, v, length, bias, scale), "out")


# --------------------------------------------------------------------------
# verify-mode attention: a k-token draft-verify block against the cache
# --------------------------------------------------------------------------

#: trace-scoped flag: speculative decoding's verify step feeds S > 1
#: query tokens through `MultiHeadAttention._static_kv_attention`, which
#: otherwise reserves multi-token calls for the PREFILL of an empty
#: cache. Arming the scope switches the multi-token branch to the
#: per-row verify write + `verify_attention` (causal-within-the-block
#: against each row's own cache offset). Trace-time only, like
#: `decode_shardings` — zero cost when unset.
_KV_VERIFY = [False]


@contextlib.contextmanager
def kv_verify_scope():
    """Scope a jit trace so multi-token StaticKVCache attention means
    DRAFT-VERIFY (per-row offsets, causal block) instead of prefill."""
    prev = _KV_VERIFY[0]
    _KV_VERIFY[0] = True
    try:
        yield
    finally:
        _KV_VERIFY[0] = prev


def in_kv_verify_scope():
    return _KV_VERIFY[0]


def verify_attention_reference(q, k, v, length, bias=None, scale=None):
    """XLA reference for the speculative-decoding VERIFY step: T query
    tokens per row (the pending token + T-1 draft tokens), just written
    into the cache at each row's own offset. q [b, h, T, d]; k/v
    [b, h, L, d]; `length` ([b] or scalar int32, traced) is the written
    count AFTER the T-token write, so query i sits at absolute position
    length - T + i and may see key positions <= its own (causal within
    the block, everything before it in the cache). bias: optional
    [b, L] additive key bias (padded-prompt holes). With T == 1 this is
    exactly `decode_attention_reference`; the flash_verify kernel is
    checked against THIS composition in interpret mode on CPU."""
    import jax.numpy as jnp

    b, h, T, d = q.shape
    L = k.shape[2]
    length = jnp.asarray(length, jnp.int32)
    length = jnp.broadcast_to(length.reshape(-1), (b,))
    kpos = jnp.arange(L, dtype=jnp.int32)
    qpos = (length[:, None] - jnp.int32(T)) + \
        jnp.arange(T, dtype=jnp.int32)[None, :]          # [b, T]
    valid = kpos[None, None, :] <= qpos[:, :, None]      # [b, T, L]
    m = jnp.where(valid, jnp.float32(0.0), jnp.float32(-1e30))
    if bias is not None:
        m = m + jnp.asarray(bias, jnp.float32)[:, None, :]
    return sdpa_reference(q, k, v, m[:, None], False, scale)


def _flash_verify_call(b, h, L, d, T, s, n_splits, has_bias, interpret):
    """Split-K verify kernel: the flash_decode grid with a (T, d) query
    block instead of (1, d); in-kernel masking keeps key position j
    visible to query row i only while j <= row i's absolute position
    (n_valid - T + i)."""
    import jax
    import jax.numpy as jnp

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    split = L // n_splits

    def kernel(len_ref, *refs):
        if has_bias:
            q_ref, k_ref, v_ref, bias_ref, o_ref, m_ref, l_ref = refs
        else:
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
        si = pl.program_id(1)
        start = si * jnp.int32(split)
        n_valid = len_ref[pl.program_id(0) // jnp.int32(h)]

        # every query sees keys < n_valid only, so splits entirely past
        # the written region contribute an exact zero to the combine
        @pl.when(start < n_valid)
        def _compute():
            sf = jnp.float32(s)
            qb = (q_ref[...].astype(jnp.float32) * sf).astype(
                q_ref.dtype)                      # (T, d)
            kb = k_ref[...]                        # (split, d)
            vb = v_ref[...]
            logits = jnp.dot(qb, kb.T,
                             preferred_element_type=jnp.float32)
            kpos = start + jax.lax.broadcasted_iota(
                jnp.int32, (T, split), 1)
            qpos = (n_valid - jnp.int32(T)) + jax.lax.broadcasted_iota(
                jnp.int32, (T, split), 0)
            logits = jnp.where(kpos <= qpos, logits,
                               jnp.float32(-1e30))
            if has_bias:
                logits = logits + bias_ref[...][:, 0][None, :]
            m = logits.max(axis=-1, keepdims=True)          # (T, 1)
            p = jnp.exp(logits - m)
            # a query row fully masked WITHIN an active split (its
            # position precedes the split) leaves m = -1e30 and p = 1s;
            # the XLA combine's alpha = exp(m - m_star) flushes that
            # split's contribution to an exact zero — every row's own
            # position guarantees some split holds a finite m
            l = p.sum(axis=-1, keepdims=True)
            o_ref[...] = jnp.dot(p.astype(qb.dtype), vb,
                                 preferred_element_type=jnp.float32)
            m_ref[...] = m
            l_ref[...] = l

        @pl.when(start >= n_valid)
        def _skip():
            o_ref[...] = jnp.zeros((T, d), jnp.float32)
            m_ref[...] = jnp.full((T, 1), -1e30, jnp.float32)
            l_ref[...] = jnp.zeros((T, 1), jnp.float32)

    in_specs = [
        pl.BlockSpec((None, T, d), lambda bh, si, *_: (bh, _z(), _z())),
        pl.BlockSpec((None, split, d), lambda bh, si, *_: (bh, si, _z())),
        pl.BlockSpec((None, split, d), lambda bh, si, *_: (bh, si, _z())),
    ]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((None, split, 1),
                         lambda bh, si, *_: (bh, si, _z())))
    out_specs = [
        pl.BlockSpec((None, None, T, d),
                     lambda bh, si, *_: (bh, si, _z(), _z())),
        pl.BlockSpec((None, None, T, 1),
                     lambda bh, si, *_: (bh, si, _z(), _z())),
        pl.BlockSpec((None, None, T, 1),
                     lambda bh, si, *_: (bh, si, _z(), _z())),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b * h, n_splits, T, d), jnp.float32),
        jax.ShapeDtypeStruct((b * h, n_splits, T, 1), jnp.float32),
        jax.ShapeDtypeStruct((b * h, n_splits, T, 1), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b * h, n_splits),
        in_specs=in_specs, out_specs=out_specs)
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=out_shape, interpret=interpret,
                          name="flash_verify")


def flash_verify(q, k, v, length, bias=None, scale=None, split_k=None,
                 interpret=False):
    """Pallas verify kernel: T query tokens per row against the cached
    K/V, split-K over the cache length exactly like `flash_decode`; the
    per-split partial (acc, m, l) merge in XLA with the standard
    logsumexp combine. `length` [b] (or scalar, traced) is the written
    count AFTER the block write — per-row, the serving slot pool's
    layout."""
    import jax.numpy as jnp

    b, h, T, d = q.shape
    L = k.shape[2]
    n_splits = _pick_decode_splits(L, split_k, head_dim=d,
                                   dtype=str(q.dtype),
                                   kernel="flash_verify", T=T)
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qr = q.reshape(b * h, T, d)
    kr = k.reshape(b * h, L, d)
    vr = v.reshape(b * h, L, d)
    len_arr = jnp.broadcast_to(
        jnp.asarray(length, jnp.int32).reshape(-1), (b,))
    call = _flash_verify_call(b, h, L, d, T, s, n_splits,
                              bias is not None, interpret)
    args = [qr, kr, vr]
    if bias is not None:
        args.append(jnp.repeat(jnp.asarray(bias, jnp.float32), h,
                               axis=0)[:, :, None])
    acc, m, l = call(len_arr, *args)               # [b*h, ns, T, ...]
    m_star = m.max(axis=1, keepdims=True)
    alpha = jnp.exp(m - m_star)
    num = (acc * alpha).sum(axis=1)                # [b*h, T, d]
    den = jnp.maximum((l * alpha).sum(axis=1), 1e-30)
    return (num / den).astype(q.dtype).reshape(b, h, T, d)


def verify_attention(q, k, v, length, bias=None, scale=None,
                     split_k=None, interpret=False):
    """Verify-attention dispatch: the split-K pallas kernel on TPU (or
    under interpret=True for CPU parity tests), the XLA reference
    composition everywhere else — the same gates as
    `decode_attention`."""
    L = k.shape[2]
    q = _constrain_decode(q, "q")
    k = _constrain_decode(k, "kv")
    v = _constrain_decode(v, "kv")
    use_kernel = interpret or (
        _on_tpu() and q.shape[-1] <= 256 and L >= 256 and L % 128 == 0
        and _flash_usable())
    if use_kernel:
        return _constrain_decode(
            flash_verify(q, k, v, length, bias, scale, split_k,
                         interpret), "out")
    return _constrain_decode(
        verify_attention_reference(q, k, v, length, bias, scale), "out")


# --------------------------------------------------------------------------
# paged decode attention: one query token against a paged KV cache
# --------------------------------------------------------------------------

def paged_gather_kv(pages, scales, table, num_heads, compute_dtype):
    """Dense [S, H, L, D] logical view of a paged cache ([N+1, psz,
    H * D] pages — token rows, the heads side by side on the minor
    axis — indexed by a [S, max_pages] int32 table), dequantized via
    the per-(page, head) [N+1, 1, H] scales when present. The XLA
    fallback read for `paged_decode_attention`; garbage gathered through
    trash-clipped table entries is hidden by the written-length mask
    downstream."""
    import jax.numpy as jnp

    S, mp = table.shape
    _, psz, hd = pages.shape
    h = int(num_heads)
    g = pages[table].reshape(S, mp, psz, h, hd // h)
    if scales is not None:
        g = g.astype(jnp.float32) * scales[table][..., None]
    return jnp.transpose(g, (0, 3, 1, 2, 4)).reshape(
        S, h, mp * psz, hd // h).astype(compute_dtype)


#: float32 bytes of one page ([psz, H * D]) the paged kernels take, and
#: of the [T * P * psz, H * D] block the T query rows make of the P pages
#: of a grid step: a step holds the K and V blocks (P pages each, two
#: buffers each, at most `_PAGED_PAGE_BYTES` a buffer as stored) and a
#: handful of block-sized float32 temporaries in VMEM, 16 MiB scoped on a
#: v5e core
_PAGED_PAGE_BYTES = 2**20
_PAGED_BLOCK_BYTES = 2**22
#: the most pages a grid step takes. The kernel alone on a v5e at the
#: benchmark's pool (64 slots x 64 pages of 16 x 1,024 float32; chip
#: runs of PR 34, ms a call; a page a step read 0.610 / 0.801 / 2.189):
#:   pages a step    every slot empty    451 pages written    all 4,096
#:        4               0.090               0.220              1.127
#:        8               0.059               0.197              0.944
#:       16               0.040               0.225              0.880
#: A larger block halves the grid's own steps and doubles the rows that
#: a slot's last block computes past its length: 8 is where pools of
#: 3-16 written pages a slot read least. (The same blocks as index-
#: mapped operands of the pool, 2 P + 2 block specs a step, read 0.80 /
#: 0.91 / 1.45 at 8: every operand's index map and copy test is paid
#: each step, written or not, so the kernel makes its own copies.)
_PAGED_BLOCK_PAGES = 8


def _paged_kernel_fits(psz, hd, T=1):
    """The pools the paged kernels take, decided here from the shapes
    alone: rows that tile (`psz` a sublane multiple, `hd` = heads x head
    size a lane multiple), a page and a block of `T` query rows against
    it that the step's working set holds in VMEM (a step of one page is
    the least `_paged_block_pages` gives). Every other pool takes the
    XLA gather composition."""
    page = 4 * psz * hd
    return (psz % 8 == 0 and hd % 128 == 0 and page <= _PAGED_PAGE_BYTES
            and T * page <= _PAGED_BLOCK_BYTES)


def _paged_block_pages(psz, hd, T, mp, dtype):
    """`P`, the consecutive logical pages of a slot that one grid step of
    the paged kernels takes, from the shapes and the page dtype alone:
    the largest power of two that is no more than the slot's `mp` pages
    nor `_PAGED_BLOCK_PAGES`, keeps the float32 block of the `T` query
    rows against the `P` pages ([T * P * psz, hd]) within
    `_PAGED_BLOCK_BYTES`, and keeps a K or V block as stored within
    `_PAGED_PAGE_BYTES` a buffer. So a verify call of many rows takes
    fewer pages a step than a decode call, and a pool of wider rows
    fewer still; 1 is the kernel of one page a step. The call builder
    and the engine's `paging.pages_per_block` both read it here."""
    import numpy as np

    stored = psz * hd * np.dtype(dtype).itemsize
    p = 1
    while (2 * p <= min(mp, _PAGED_BLOCK_PAGES)
           and T * 2 * p * 4 * psz * hd <= _PAGED_BLOCK_BYTES
           and 2 * p * stored <= _PAGED_PAGE_BYTES):
        p *= 2
    return p


def paged_decode_block_pages(psz, hd, mp, dtype):
    """The pages a grid step of the decode call takes over a pool of
    these shapes (`_paged_block_pages` at one query row); 1 for a pool
    whose shapes send it to the gather. What the serving engine reports
    as `paging.pages_per_block`."""
    return (_paged_block_pages(psz, hd, 1, mp, dtype)
            if _paged_kernel_fits(psz, hd) else 1)


def _dot_indicator(x, ind):
    """float32 x [n, k] times a 0/1 matrix `ind` [k, m] (bfloat16), to
    float32 rounding: x goes in as three bfloat16 pieces that sum to it
    exactly, so every product is exact and the MXU's float32
    accumulation is the only rounding. One product at default
    precision would round x to bfloat16 (8 bits); asking the MXU for
    full precision splits `ind` as well and takes twice the passes."""
    import jax.numpy as jnp

    n = x.shape[0]
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    y = jnp.dot(jnp.concatenate([hi, mid, lo], axis=0), ind,
                preferred_element_type=jnp.float32)
    return y[:n] + y[n:2 * n] + y[2 * n:]


def _paged_flash_call(S, h, mp, psz, d, T, s, has_scale, has_bias,
                      interpret, dtype):
    """Both paged kernels: one grid step per (slot, block of `P`
    consecutive logical pages), `P` = `_paged_block_pages`: a grid of
    (S, ceil(mp / P)). A step takes the block whole — `P x psz` token
    rows of all heads — against the slot's `T` query rows (one is
    `paged_flash_decode`; more are `paged_flash_verify`: the pending
    token plus the drafts, causal within the block: key j stays visible
    to row t only while j <= n_valid - T + t). The page table and the
    written lengths ride scalar prefetch; the pool stays in HBM
    (`pl.ANY`) and the kernel copies a block's WRITTEN pages itself, a
    page a DMA through table[slot, page], the loop's bound read from
    the slot's length, into one of two VMEM buffers (a pool's scales,
    a value a (page, head), and the key bias come in the slot's logical
    coordinates, a block a step; XLA gathers the scales through the
    table, [S, mp, H]): every step that works starts its successor's
    copies (the slot's next written block, else block 0 of the next
    slot that holds anything) before it waits for its own, so a fetch
    runs under the step before it, across slots too; only the call's
    first written block starts its own. So a block wholly past the
    written pages neither fetches nor computes (`pl.when` skips it: such
    a step costs the grid's own 0.14 us); in the slot's last written
    block the pages past the last written one are not fetched, keep
    what the buffer held (zeros at first, then older pages: finite) and
    are masked by the causal test, adding exact zeros. An `mp` that `P`
    does not divide needs nothing more: the rows past it are past every
    length. The block axis is the
    reduction: running m and l ([T, H], a value a head) and acc
    ([T, H * d]) live in VMEM scratch and the normalised output is
    written at the last block. Both axes are "arbitrary": the buffers'
    turn and the copies in flight pass from slot to slot (SMEM). The `T` rows
    and the `P` pages go through every product as ONE block
    ([T * P * psz, H * d]), so the body traces to the same equations at
    any `T` and any `P`. With the heads on the lanes a head's logit is a
    sum over its own `d` lanes: a product with a thin 0/1 matrix
    ([H * d, H]), and one back ([H, H * d]) spreads a head's weight
    over its lanes — `_dot_indicator`, float32 sums of float32 products
    throughout. `interpret` runs the same body, copies and semaphores
    under the TPU interpreter (`pltpu.InterpretParams`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hd = h * d
    P = _paged_block_pages(psz, hd, T, mp, dtype)
    rows = P * psz
    nblk = -(-mp // P)
    i32 = jnp.int32

    def kernel(tbl_ref, len_ref, q_ref, *refs):
        refs = list(refs)
        pools = refs[:2]
        refs = refs[2:]
        if has_scale:
            ks_ref, vs_ref = refs[:2]
            refs = refs[2:]
        if has_bias:
            bias_ref = refs[0]
            refs = refs[1:]
        (o_ref, m_sc, l_sc, acc_sc, sum_sc, spread_sc, *bufs, sem,
         state) = refs
        si = pl.program_id(0)
        bi = pl.program_id(1)
        start = bi * i32(rows)
        n_valid = len_ref[si]

        def written_pages(slot):    # lengths are >= 0: one `div`
            return jax.lax.div(len_ref[slot] + i32(psz - 1), i32(psz))

        def next_written_slot(slot):            # S where none is left
            return jax.lax.while_loop(
                lambda t: (t < i32(S)) & (len_ref[jnp.minimum(
                    t, i32(S - 1))] <= 0),
                lambda t: t + i32(1), slot)

        def copies(slot, blk, buf, go):
            # the block's written pages, a page a copy: started where
            # `go`, else waited for
            first = blk * i32(P)

            def page(j, carry):
                # (a wait reads the copy's size alone, not its source)
                row = tbl_ref[slot, first + j] if go else _z()
                for c, (pool, vm) in enumerate(zip(pools, bufs)):
                    cp = pltpu.make_async_copy(
                        pool.at[row], vm.at[buf, j],
                        sem.at[np.int32(c), buf])
                    cp.start() if go else cp.wait()
                return carry

            jax.lax.fori_loop(
                i32(0), jnp.minimum(written_pages(slot) - first, i32(P)),
                page, i32(0))

        @pl.when((si == 0) & (bi == 0))
        def _first():
            # the rows a short block leaves unfetched must hold numbers
            for vm in bufs:
                vm[...] = jnp.zeros(vm.shape, vm.dtype)
            state[0] = i32(0)       # the buffer whose turn it is
            state[1] = i32(0)       # are this block's copies in flight?

        @pl.when(bi == 0)
        def _init():
            m_sc[...] = jnp.full((T, h), -1e30, jnp.float32)
            l_sc[...] = jnp.zeros((T, h), jnp.float32)
            acc_sc[...] = jnp.zeros((T, hd), jnp.float32)
            # which lanes are which head's: [hd, h] sums a head's lanes,
            # [h, hd] spreads a head's value back over them. Built once
            # a slot, not once a block (128 vregs of compares)
            for ref in (sum_sc, spread_sc):
                ax = ref.shape.index(hd)
                lane = jax.lax.broadcasted_iota(jnp.int32, ref.shape, ax)
                head = jax.lax.broadcasted_iota(jnp.int32, ref.shape,
                                                1 - ax)
                ref[...] = (jax.lax.div(lane, i32(d)) == head
                            ).astype(jnp.bfloat16)

        def over_lanes(x):                      # (n, h) -> (n, hd)
            return _dot_indicator(x, spread_sc[...])

        def block(vm, buf, scale_ref=None):     # (rows, hd), float32
            xb = vm[buf].astype(jnp.float32)
            if scale_ref is not None:           # dequantize in-kernel
                xb = xb * over_lanes(scale_ref[...])[:, None, :]
            return xb.reshape(rows, hd)

        # a block entirely past the written region adds an exact zero
        @pl.when(start < n_valid)
        def _compute():
            buf, ahead = state[0], state[1]
            state[0] = i32(1) - buf
            state[1] = i32(1)

            def fetch(i, at):
                # start the copies of the written block after `at`: the
                # slot's next, else block 0 of the next slot that holds
                # anything
                slot, blk = at
                more = (blk + i32(1)) * i32(P) < written_pages(slot)
                slot = jax.lax.select(more, slot,
                                      next_written_slot(slot + i32(1)))
                blk = jax.lax.select(more, blk + i32(1), i32(0))

                @pl.when(slot < i32(S))
                def _():
                    copies(slot, blk, (buf + i + ahead) & i32(1), True)

                return jnp.minimum(slot, i32(S - 1)), blk

            # the block after this one; before it this one itself, where
            # no step started it (the call's first written block)
            jax.lax.fori_loop(i32(0), i32(2) - ahead, fetch,
                              (si, bi - i32(1) + ahead))
            copies(si, bi, buf, False)
            kb = block(bufs[0], buf, ks_ref if has_scale else None)
            vb = block(bufs[1], buf, vs_ref if has_scale else None)
            qb = q_ref[...].astype(jnp.float32) * jnp.float32(s)
            # per-head logits of every (row, key) pair: (T, rows, h)
            logits = _dot_indicator(
                (qb[:, None, :] * kb[None]).reshape(T * rows, hd),
                sum_sc[...]).reshape(T, rows, h)
            kpos = start + jax.lax.broadcasted_iota(
                jnp.int32, (T, rows, h), 1)
            qpos = (n_valid - i32(T)) + jax.lax.broadcasted_iota(
                jnp.int32, (T, rows, h), 0)
            logits = jnp.where(kpos <= qpos, logits, jnp.float32(-1e30))
            if has_bias:
                logits = logits + bias_ref[...][None]
            # a row whose own position precedes the block keeps m_prev
            # (block 0 gave every row a finite one) and adds exact zeros
            m_prev = m_sc[...]                            # (T, h)
            m_new = jnp.maximum(m_prev, logits.max(axis=1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new[:, None, :])       # (T, rows, h)
            l_sc[...] = alpha * l_sc[...] + p.sum(axis=1)
            # p and alpha over their heads' lanes in one product
            wide = over_lanes(jnp.concatenate(
                [p.reshape(T * rows, h), alpha], axis=0))
            pv = wide[:T * rows].reshape(T, rows, hd)
            acc_sc[...] = wide[T * rows:] * acc_sc[...] + (
                pv * vb[None]).sum(axis=1)
            m_sc[...] = m_new

        @pl.when(bi == nblk - 1)
        def _finish():
            # a slot of length 0 (inactive, trash-mapped) never
            # computes: l = 0, and the floor keeps its output finite
            o_ref[...] = acc_sc[...] / over_lanes(
                jnp.maximum(l_sc[...], jnp.float32(1e-30)))

    def q_ix(si, bi, *_):
        return (si, _z(), _z())

    def logical_ix(si, bi, tbl, lens):
        # scales [S, blocks, P, h] and bias [S, blocks x rows, 1] live
        # in LOGICAL per-slot coordinates: block by (slot, logical
        # block), no table dereference, clamped to the slot's last
        # written block (resident: no copy); the heads and the query
        # rows of a step share the bias
        last = jnp.maximum(lens[si] - i32(1), i32(0)) // i32(rows)
        return (si, jnp.minimum(bi, last), _z())

    pool = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((None, T, hd), q_ix), pool, pool]
    if has_scale:
        in_specs += [pl.BlockSpec(
            (None, None, P, h),
            lambda *a: logical_ix(*a) + (_z(),))] * 2
    if has_bias:
        in_specs.append(pl.BlockSpec((None, rows, 1), logical_ix))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(S, nblk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, T, hd), q_ix),
        scratch_shapes=[pltpu.VMEM((T, h), jnp.float32),
                        pltpu.VMEM((T, h), jnp.float32),
                        pltpu.VMEM((T, hd), jnp.float32),
                        pltpu.VMEM((hd, h), jnp.bfloat16),
                        pltpu.VMEM((h, hd), jnp.bfloat16),
                        pltpu.VMEM((2, P, psz, hd), jnp.dtype(dtype)),
                        pltpu.VMEM((2, P, psz, hd), jnp.dtype(dtype)),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)])
    call = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, T, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_flash_decode" if T == 1 else "paged_flash_verify")

    def whole_blocks(x, n):
        # [S, mp * n, ...] -> [S, nblk * P * n, ...]: zeros past `mp`
        # pages where `P` does not divide them
        return jnp.pad(x, ((0, 0), (0, (nblk * P - mp) * n))
                       + ((0, 0),) * (x.ndim - 2))

    def paged_call(table, length, q, k_pages, v_pages, *rest):
        args = [q, k_pages, v_pages]
        if has_scale:
            args += [whole_blocks(sc[table][:, :, 0], 1).reshape(
                S, nblk, P, h) for sc in rest[:2]]
        if has_bias:
            args.append(whole_blocks(rest[-1], psz))
        return call(table, length, *args)

    return paged_call


@functools.lru_cache(maxsize=None)
def _paged_flash_jit(scale, interpret):
    """The call of a paged kernel as ONE jitted function a (scale,
    interpret): a stack's layers call it with the same shapes, so the
    second layer on reads the first's trace and lowering instead of
    tracing the kernel body again — a third of what `pstep` and
    `pattach` cost to trace at twelve layers. XLA inlines it."""
    import jax
    import jax.numpy as jnp

    def paged_flash(q, k_pages, v_pages, k_scale, v_scale, table, length,
                    bias):
        S, h, T, d = q.shape
        mp = table.shape[1]
        psz = k_pages.shape[1]
        s = scale if scale is not None else 1.0 / math.sqrt(d)
        call = _paged_flash_call(S, h, mp, psz, d, T, s,
                                 k_scale is not None, bias is not None,
                                 interpret, k_pages.dtype)
        # the query and the output in the pages' row form, [S, T, H * d]
        args = [jnp.swapaxes(q, 1, 2).reshape(S, T, h * d), k_pages,
                v_pages]
        if k_scale is not None:
            args += [k_scale, v_scale]
        if bias is not None:
            args.append(jnp.asarray(bias, jnp.float32)[:, :, None])
        out = call(jnp.asarray(table, jnp.int32),
                   jnp.asarray(length, jnp.int32), *args)
        return jnp.swapaxes(out.reshape(S, T, h, d), 1, 2).astype(q.dtype)

    return jax.jit(paged_flash)


def paged_flash_decode(q, k_pages, v_pages, k_scale, v_scale, table,
                       length, bias=None, scale=None, interpret=False):
    """Pallas paged decode: one query token per slot against K/V
    gathered THROUGH the page table — no dense materialization, no
    per-page partials: the merge over pages happens in the kernel. q
    [S, h, 1, d]; pages [N+1, psz, h * d] (+1 = trash row); table
    [S, max_pages] int32 (trash-clipped); length [S] written counts;
    k_scale/v_scale optional [N+1, 1, h] per-(page, head) dequant
    scales; bias optional [S, L] additive key bias in logical
    coordinates."""
    if q.shape[2] != 1:
        raise ValueError("paged_flash_decode takes a single query "
                         "token per slot")
    return _paged_flash_jit(scale, interpret)(
        q, k_pages, v_pages, k_scale, v_scale, table, length, bias)


def paged_decode_attention(q, k_pages, v_pages, k_scale, v_scale, table,
                           length, bias=None, scale=None,
                           interpret=False):
    """Paged decode-attention dispatch: the page-table pallas kernel on
    TPU (or under interpret=True for CPU parity tests); elsewhere
    gather the pages into the dense logical view and run the exact XLA
    reference — with same-dtype pages the gathered buffer reproduces
    the dense StaticKVCache bit-for-bit, which is what makes paged
    serving bit-identical to the dense pool on the fallback path."""
    h, d = q.shape[1], q.shape[-1]
    psz = k_pages.shape[1]
    q = _constrain_decode(q, "q")
    k_pages = _constrain_decode(k_pages, "pages")
    v_pages = _constrain_decode(v_pages, "pages")
    use_kernel = interpret or (
        _on_tpu() and _paged_kernel_fits(psz, h * d) and _flash_usable())
    if use_kernel and not interpret:
        # dispatch-level tuning knob: the kernel's block of pages comes
        # from the shapes, but a device tier can force the XLA gather
        # path where the kernel loses
        cfg = _tuned("paged_flash_decode", (d, psz, str(k_pages.dtype)))
        if cfg is not None and not cfg.get("kernel", True):
            use_kernel = False
    if use_kernel:
        return _constrain_decode(
            paged_flash_decode(q, k_pages, v_pages, k_scale, v_scale,
                               table, length, bias, scale, interpret),
            "out")
    kd = paged_gather_kv(k_pages, k_scale, table, h, q.dtype)
    vd = paged_gather_kv(v_pages, v_scale, table, h, q.dtype)
    return _constrain_decode(
        decode_attention_reference(q, kd, vd, length, bias, scale),
        "out")


# --------------------------------------------------------------------------
# paged verify attention: a k-token draft-verify block against a paged
# KV cache — the speculative-decoding step of the paged serving pool
# --------------------------------------------------------------------------

def _paged_verify_heuristic():
    """Hand-picked dispatch config for `paged_verify_attention`: the
    scalar-prefetch kernel on, gather-fallback split untouched (0 =
    let `verify_attention` pick). The committed-fallback source of
    truth for the paged_flash_verify tuning-table entries."""
    return {"kernel": True, "split_k": 0}


def paged_flash_verify(q, k_pages, v_pages, k_scale, v_scale, table,
                       length, bias=None, scale=None, interpret=False):
    """Pallas paged verify: T query tokens per slot (the pending token
    plus T-1 drafts, just written through the page table at each
    slot's own offset) against K/V read THROUGH the table — the decode
    kernel's grid with a [T, h * d] query block, causal within the
    block, merged over pages in the kernel. q [S, h, T, d]; pages
    [N+1, psz, h * d] (+1 = trash row); table [S, max_pages] int32
    (trash-clipped); length [S] written counts AFTER the T-token write;
    k_scale/v_scale optional [N+1, 1, h] per-(page, head) dequant
    scales; bias optional [S, L] additive key bias in logical
    coordinates."""
    return _paged_flash_jit(scale, interpret)(
        q, k_pages, v_pages, k_scale, v_scale, table, length, bias)


def paged_verify_attention(q, k_pages, v_pages, k_scale, v_scale,
                           table, length, bias=None, scale=None,
                           interpret=False):
    """Paged verify-attention dispatch: the page-table pallas kernel on
    TPU (or under interpret=True for CPU parity tests); elsewhere
    gather the pages into the dense logical view and run the exact
    `verify_attention` composition — with same-dtype pages the
    gathered buffer reproduces the dense StaticKVCache bit-for-bit,
    which keeps paged speculative serving bit-identical to the dense
    pool on the fallback path. The tuned table's (kernel, split_k)
    ladder picks the path and the gather-side split factor."""
    h, T, d = q.shape[1:]
    psz = k_pages.shape[1]
    q = _constrain_decode(q, "q")
    k_pages = _constrain_decode(k_pages, "pages")
    v_pages = _constrain_decode(v_pages, "pages")
    cfg = _tuned("paged_flash_verify",
                 (d, psz, str(k_pages.dtype), int(T)))
    if cfg is None:
        cfg = _paged_verify_heuristic()
    use_kernel = interpret or (
        _on_tpu() and _paged_kernel_fits(psz, h * d, T)
        and _flash_usable() and bool(cfg.get("kernel", True)))
    if use_kernel:
        return _constrain_decode(
            paged_flash_verify(q, k_pages, v_pages, k_scale, v_scale,
                               table, length, bias, scale, interpret),
            "out")
    kd = paged_gather_kv(k_pages, k_scale, table, h, q.dtype)
    vd = paged_gather_kv(v_pages, v_scale, table, h, q.dtype)
    split = int(cfg.get("split_k", 0)) or None
    return _constrain_decode(
        verify_attention(q, kd, vd, length, bias, scale,
                         split_k=split), "out")


def sdpa(q, k, v, mask=None, is_causal=False, scale=None,
         dropout_p=0.0, dropout_key=None, segment_ids=None):
    """Dispatch: pallas flash fwd+bwd on TPU whenever the mask reduces to
    a key-position bias (incl. every padded batch); XLA reference
    otherwise. Short sequences (< 512) stay on the XLA path — its fused
    attention beats the blockwise kernel there and the S x S buffer is
    tiny; flash pays off where it matters, long context (measured:
    ERNIE seq 128 is ~2% faster on the reference path). segment_ids
    ([B, S] int, packed monotone rows from core/lod.pack_padded) engage
    the segment-masked packed kernel; off-TPU or when any gate fails,
    the reference composition applies the same segment mask densely."""
    if q.ndim == 4:
        bias = _flash_plan(q.shape[2], k.shape[2], q.shape[-1], mask,
                           q.shape[0], q.shape[1], dropout_p,
                           dropout_key, dtype=str(q.dtype))
        if bias is not _NO_FLASH:
            seed = _seed_from_key(dropout_key) if dropout_p else None
            return flash_attention(q, k, v, bias, is_causal, scale,
                                   dropout_p=dropout_p,
                                   dropout_seed=seed,
                                   segment_ids=segment_ids)
    return sdpa_reference(q, k, v, _with_segment_mask(mask, segment_ids),
                          is_causal, scale, dropout_p, dropout_key)
