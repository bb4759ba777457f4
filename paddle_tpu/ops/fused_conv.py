"""Fused 1x1-conv + BatchNorm Pallas block kernels (NCHW-native).

Reference parity: operators/fused/conv_fusion_op.cu and
fused/fused_bn_activation_op.cc — the reference ships conv+BN+act as
first-class fused ops. TPU-native design, driven by the r05 device
profile of the ResNet-50 step (BENCH_DETAILS resnet50.roofline): the
convolutions themselves are already ~MXU-bound under XLA, but ~60% of
device time is BN data movement — the normalize pass (read x, write
xn), the stats pass (read z), and the backward's extra passes. This
kernel removes whole passes instead of speeding any of them up:

  fwd:  z = act(x * scale + shift) @ W, with per-channel sum/sumsq of z
        accumulated in the SAME kernel (grid-sequential revisiting of a
        [Co, 1] accumulator block). The normalized activation never
        exists in HBM; the stats read of z never happens.
  bwd:  ONE pass reads (x, z, dz) and writes dx while accumulating dW,
        dscale, dshift in VMEM — XLA needs separate passes for the dW
        matmul, the dx chain, and the two reductions.

The kernel also back-propagates the stats cotangents (ds, dss): batch
statistics feed the NEXT layer's scale/shift in BN training, so dz_eff
= dz + ds + 2*z*dss keeps the whole bn-chain differentiable.

MEASURED OUTCOME (r05, TPU v5e, B=128 ResNet bottleneck shapes, fwd+bwd
with stats consumed — tools via _scratch/fc_bench, recorded in
BENCH_DETAILS resnet50.roofline.fused_kernel_ab): this kernel LOSES to
the XLA dot_general chain at every shape —

    Ci 256  Co  64 HW 3136:  fused 1.93 ms   xla 0.54 ms  (0.28x)
    Ci  64  Co 256 HW 3136:  fused 1.42 ms   xla 0.31 ms  (0.22x)
    Ci 512  Co 128 HW  784:  fused 1.06 ms   xla 0.28 ms  (0.26x)
    Ci 128  Co 512 HW  784:  fused 0.70 ms   xla 0.13 ms  (0.18x)
    Ci 1024 Co 256 HW  196:  fused 0.64 ms   xla 0.13 ms  (0.20x)
    Ci 2048 Co 512 HW   49:  fused 1.06 ms   xla 0.93 ms  (0.88x)

because XLA already performs the operand/epilogue fusions this kernel
hand-builds when the contraction is a dot_general (the premise that the
stats pass costs a separate HBM read holds only for convolution HLOs),
and its batched-matmul tiling beats this kernel's one-batch-per-program
grid. The in-model conv-HLO story is different again — see the
PT_CONV1X1_DOT note in ops/kernels.py conv2d — and ResNet-50 keeps the
XLA path. The kernel stays: it is the committed, measured answer the
r04 verdict asked for ("a committed kernel + measurement proving it"),
it is numerically exact (tests/test_fused_conv.py), and its
stats-epilogue/accumulator patterns are the template for future fused
blocks where the producer is NOT a dot (e.g. gather+reduce chains).

Layout: NCHW with HW flattened to the lane axis — full-HW blocks, so
no transposes anywhere (a relayout would eat the savings). Mosaic pads
lanes to 128 physically, but jnp reductions inside the kernel operate
on the LOGICAL block shape, so the stats and dW contractions never see
padded lanes — no masking needed. Stride-1 1x1 convs only (the
bottleneck's conv1/conv3); 3x3, strided, and projection convs stay on
XLA.
"""
from __future__ import annotations

import functools

import numpy as np

from .attention import _z


@functools.lru_cache(maxsize=None)
def _fwd_call(B, Ci, Co, HW, relu, has_norm, dtype_str, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_str)

    def kernel(x_ref, sc_ref, sh_ref, w_ref, z_ref, s_ref, ss_ref):
        b = pl.program_id(0)
        x = x_ref[...]
        if has_norm:
            pre = x.astype(jnp.float32) * sc_ref[...] + sh_ref[...]
            if relu:
                pre = jnp.maximum(pre, jnp.float32(0.0))
            xn = pre.astype(dtype)
        else:
            xn = jnp.maximum(x, jnp.zeros((), x.dtype)) if relu else x
        z = jax.lax.dot_general(
            w_ref[...], xn, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Co, HW]
        z_ref[...] = z.astype(z_ref.dtype)
        # no lane masking needed: reductions here see the LOGICAL block
        # shape (z.shape[1] == HW at trace level) — Mosaic's physical
        # lane padding to 128 is invisible to jnp ops, so stats over
        # axis 1 already exclude it (an iota < HW mask was all-true
        # dead code, ADVICE r05)
        s_part = z.sum(axis=1, keepdims=True)          # [Co, 1]
        ss_part = (z * z).sum(axis=1, keepdims=True)
        first = b == 0
        # accumulator blocks are revisited every grid step (TPU grids
        # run sequentially); the where() discards the uninitialized
        # first read instead of branching
        s_ref[...] = jnp.where(first, s_part, s_ref[...] + s_part)
        ss_ref[...] = jnp.where(first, ss_part, ss_ref[...] + ss_part)

    in_specs = [
        pl.BlockSpec((None, Ci, HW), lambda b: (b, _z(), _z())),
        pl.BlockSpec((Ci, 1), lambda b: (_z(), _z())),
        pl.BlockSpec((Ci, 1), lambda b: (_z(), _z())),
        pl.BlockSpec((Co, Ci), lambda b: (_z(), _z())),
    ]
    out_specs = [
        pl.BlockSpec((None, Co, HW), lambda b: (b, _z(), _z())),
        pl.BlockSpec((Co, 1), lambda b: (_z(), _z())),
        pl.BlockSpec((Co, 1), lambda b: (_z(), _z())),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, Co, HW), dtype),
        jax.ShapeDtypeStruct((Co, 1), jnp.float32),
        jax.ShapeDtypeStruct((Co, 1), jnp.float32),
    ]
    return pl.pallas_call(kernel, grid=(B,), in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          interpret=interpret, name="fused_conv_fwd")


@functools.lru_cache(maxsize=None)
def _bwd_call(B, Ci, Co, HW, relu, has_norm, dtype_str, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_str)

    def kernel(x_ref, sc_ref, sh_ref, w_ref, z_ref, dz_ref, ds_ref,
               dss_ref, dx_ref, dw_ref, dsc_ref, dsh_ref):
        b = pl.program_id(0)
        x = x_ref[...]
        dz = dz_ref[...].astype(jnp.float32)
        z = z_ref[...].astype(jnp.float32)
        # logical-shape ops never see Mosaic's lane padding (see the
        # fwd kernel note), so dz_eff needs no lane mask before the dW
        # contraction either
        dz_eff = dz + ds_ref[...] + 2.0 * z * dss_ref[...]
        if has_norm:
            pre = x.astype(jnp.float32) * sc_ref[...] + sh_ref[...]
            mask = pre > 0 if relu else None
            xn_f = jnp.maximum(pre, 0.0) if relu else pre
            xn = xn_f.astype(dtype)
        else:
            mask = x > jnp.zeros((), x.dtype) if relu else None
            xn = jnp.maximum(x, jnp.zeros((), x.dtype)) if relu else x
        dzb = dz_eff.astype(dtype)
        dxn = jax.lax.dot_general(
            w_ref[...], dzb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Ci, HW]
        dpre = jnp.where(mask, dxn, 0.0) if relu else dxn
        if has_norm:
            dx_ref[...] = (dpre * sc_ref[...]).astype(dx_ref.dtype)
        else:
            dx_ref[...] = dpre.astype(dx_ref.dtype)
        dw_part = jax.lax.dot_general(
            dzb, xn, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Co, Ci]
        first = b == 0
        dw_ref[...] = jnp.where(first, dw_part, dw_ref[...] + dw_part)
        if has_norm:
            dsc_part = (dpre * x.astype(jnp.float32)).sum(
                axis=1, keepdims=True)                 # [Ci, 1]
            dsh_part = dpre.sum(axis=1, keepdims=True)
            dsc_ref[...] = jnp.where(first, dsc_part,
                                     dsc_ref[...] + dsc_part)
            dsh_ref[...] = jnp.where(first, dsh_part,
                                     dsh_ref[...] + dsh_part)
        else:
            dsc_ref[...] = jnp.zeros_like(dsc_ref)
            dsh_ref[...] = jnp.zeros_like(dsh_ref)

    in_specs = [
        pl.BlockSpec((None, Ci, HW), lambda b: (b, _z(), _z())),
        pl.BlockSpec((Ci, 1), lambda b: (_z(), _z())),
        pl.BlockSpec((Ci, 1), lambda b: (_z(), _z())),
        pl.BlockSpec((Co, Ci), lambda b: (_z(), _z())),
        pl.BlockSpec((None, Co, HW), lambda b: (b, _z(), _z())),
        pl.BlockSpec((None, Co, HW), lambda b: (b, _z(), _z())),
        pl.BlockSpec((Co, 1), lambda b: (_z(), _z())),
        pl.BlockSpec((Co, 1), lambda b: (_z(), _z())),
    ]
    out_specs = [
        pl.BlockSpec((None, Ci, HW), lambda b: (b, _z(), _z())),
        pl.BlockSpec((Co, Ci), lambda b: (_z(), _z())),
        pl.BlockSpec((Ci, 1), lambda b: (_z(), _z())),
        pl.BlockSpec((Ci, 1), lambda b: (_z(), _z())),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, Ci, HW), dtype),
        jax.ShapeDtypeStruct((Co, Ci), jnp.float32),
        jax.ShapeDtypeStruct((Ci, 1), jnp.float32),
        jax.ShapeDtypeStruct((Ci, 1), jnp.float32),
    ]
    return pl.pallas_call(kernel, grid=(B,), in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          interpret=interpret, name="fused_conv_bwd")


@functools.lru_cache(maxsize=None)
def _diff_fn(relu, has_norm, interpret):
    import jax

    @jax.custom_vjp
    def f(x, scale, shift, w):
        z, s, ss = _run_fwd(x, scale, shift, w)
        return z, s, ss

    def fwd(x, scale, shift, w):
        z, s, ss = _run_fwd(x, scale, shift, w)
        return (z, s, ss), (x, scale, shift, w, z)

    def bwd(res, cts):
        import jax.numpy as jnp

        x, scale, shift, w, z = res
        dz, ds, dss = cts
        B, Ci, HW = x.shape
        Co = w.shape[0]
        call = _bwd_call(B, Ci, Co, HW, relu, has_norm, str(x.dtype),
                         interpret)
        dz = jnp.zeros_like(z) if dz is None else dz
        ds2 = (jnp.zeros((Co, 1), jnp.float32) if ds is None
               else ds.reshape(Co, 1).astype(jnp.float32))
        dss2 = (jnp.zeros((Co, 1), jnp.float32) if dss is None
                else dss.reshape(Co, 1).astype(jnp.float32))
        dx, dw, dsc, dsh = call(x, _col(scale, Ci), _col(shift, Ci), w,
                                z, dz.astype(z.dtype), ds2, dss2)
        return (dx, dsc.reshape(Ci).astype(scale.dtype),
                dsh.reshape(Ci).astype(shift.dtype), dw.astype(w.dtype))

    def _run_fwd(x, scale, shift, w):
        B, Ci, HW = x.shape
        Co = w.shape[0]
        call = _fwd_call(B, Ci, Co, HW, relu, has_norm, str(x.dtype),
                         interpret)
        z, s, ss = call(x, _col(scale, Ci), _col(shift, Ci), w)
        return z, s.reshape(Co), ss.reshape(Co)

    f.defvjp(fwd, bwd)
    return f


def _col(v, n):
    import jax.numpy as jnp

    return v.reshape(n, 1).astype(jnp.float32)


def fused_scale_act_mm_stats(x, scale, shift, w, relu=True,
                             interpret=False):
    """z = act(x * scale[:, None] + shift[:, None]) @ w with channel
    stats of z, all in one pass over x.

    x: [B, Ci, HW] (NCHW with HW flattened); scale/shift: [Ci] f32 (the
    producing BN's folded batch-stat scale/shift — pass None for the
    identity); w: [Co, Ci]. Returns (z [B, Co, HW], sum_z [Co] f32,
    sumsq_z [Co] f32). Differentiable in x, scale, shift, w — INCLUDING
    through the stats outputs (BN-chain training).
    """
    import jax.numpy as jnp

    B, Ci, HW = x.shape
    has_norm = scale is not None
    if not has_norm:
        scale = jnp.ones((Ci,), jnp.float32)
        shift = jnp.zeros((Ci,), jnp.float32)
    f = _diff_fn(bool(relu), has_norm, bool(interpret))
    return f(x, scale, shift, w)


def bn_scale_shift(gamma, beta, s, ss, n, epsilon=1e-5):
    """Fold batch stats (channel sum, sumsq over n elements) + affine
    params into the per-channel (scale, shift) the next fused op
    normalizes with. Plain jax — differentiates through to (gamma,
    beta) AND back into the stats (hence the producing activation)."""
    import jax.numpy as jnp

    mean = s / n
    var = jnp.maximum(ss / n - mean * mean, 0.0)
    inv = 1.0 / jnp.sqrt(var + epsilon)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean * scale
    return scale, shift, mean, var
