"""The Mamba-2 mixer as a Layer: two projections around
`ops.ssm.mamba2_mix` (causal convolution, selective scan, gated group
norm). Parameter names follow the published `nemotron_h` / `mamba2`
modules: `in_proj`, `conv1d`, `dt_bias`, `A_log`, `D`, `norm`, `out_proj`.
"""
from __future__ import annotations

import math

import numpy as np

from .. import initializer as I
from .common import Linear
from .layers import Layer, keep_float32


class Mamba2Mixer(Layer):
    """u [b, s, hidden] -> [b, s, hidden].

    d_inner = num_heads x head_dim (not a multiple of hidden);
    in_proj gives [z | xBC | dt] of widths d_inner | d_inner + 2 g n | h.
    `A_log`, `D` and `dt_bias` are kept float32 under a trainer's
    `compute_dtype`. `time_step_*` only draw `dt_bias`: dt log-uniform in
    [min, max], floored, through the inverse of softplus; A_log = log U(1, 16),
    the Mamba-2 default."""

    def __init__(self, hidden_size, num_heads, head_dim, n_groups,
                 state_size, conv_kernel=4, chunk_size=128, eps=1e-5,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4):
        super().__init__()
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.n_groups, self.state_size = int(n_groups), int(state_size)
        self.chunk_size, self.eps = int(chunk_size), float(eps)
        d_inner = self.num_heads * self.head_dim
        conv_dim = d_inner + 2 * self.n_groups * self.state_size
        self.in_proj = Linear(hidden_size, d_inner + conv_dim
                              + self.num_heads, bias_attr=False)
        self.conv1d = _DepthwiseConv(conv_dim, int(conv_kernel))
        import jax

        from ...core import random as _random

        u = np.asarray(jax.random.uniform(
            _random.next_key(), (2, self.num_heads), "float32"), np.float64)
        dt = np.exp(math.log(time_step_min) + u[0] * (
            math.log(time_step_max) - math.log(time_step_min)))
        dt = np.maximum(dt, time_step_floor)
        self.dt_bias = keep_float32(self.create_parameter(
            [self.num_heads], default_initializer=I.Assign(
                (dt + np.log(-np.expm1(-dt))).astype(np.float32))))
        self.A_log = keep_float32(self.create_parameter(
            [self.num_heads], default_initializer=I.Assign(np.log(
                1.0 + 15.0 * u[1]).astype(np.float32))))
        self.D = keep_float32(self.create_parameter(
            [self.num_heads], default_initializer=I.Constant(1.0)))
        self.norm = _GroupNormWeight(d_inner)
        self.out_proj = Linear(d_inner, hidden_size, bias_attr=False)

    def forward(self, u):
        import functools

        from ...ops import ssm
        from ...tensor.ops import _op

        mix = functools.partial(
            ssm.mamba2_mix, num_heads=self.num_heads,
            head_dim=self.head_dim, n_groups=self.n_groups,
            state_size=self.state_size, chunk=self.chunk_size, eps=self.eps)
        y = _op("mamba2_mix", mix, self.in_proj(u), self.conv1d.weight,
                self.conv1d.bias, self.dt_bias, self.A_log, self.D,
                self.norm.weight)
        return self.out_proj(y)


class _DepthwiseConv(Layer):
    """`conv1d.weight` [channels, k] and `conv1d.bias` [channels]."""

    def __init__(self, channels, k):
        super().__init__()
        bound = 1.0 / math.sqrt(k)
        self.weight = self.create_parameter(
            [channels, k], default_initializer=I.Uniform(-bound, bound))
        self.bias = self.create_parameter([channels], is_bias=True)


class _GroupNormWeight(Layer):
    """`norm.weight` [d_inner] of the gated group RMS norm."""

    def __init__(self, width):
        super().__init__()
        self.weight = self.create_parameter(
            [width], default_initializer=I.Constant(1.0))
