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


def _held(layer, shape, init, dtype):
    """A parameter held in `dtype` from the start (no float32 copy)."""
    return layer.create_parameter(list(shape), dtype=dtype,
                                  default_initializer=init)


class Mamba1Mixer(Layer):
    """The Mamba-1 mixer (`ops.ssm.selective_scan`): a decay per channel
    and state. Inference only: `prefill` and `step` take and return raw
    arrays and record no gradient.

        [xs; z] = in_proj a         xs = silu(conv_causal(xs) + b)
        [d; B; C] = x_proj xs       dt = softplus(dt_proj d + b_dt)
        H_t = exp(dt_t A) H_(t-1) + (dt_t xs_t) (x) B_t,  A = -exp(A_log)
        s_t = H_t C_t + D xs_t      out = out_proj (s_t silu(z_t))

    Matrices are held in `dtype`; `A_log`, `D` and `dt_proj.bias` in
    float32. The state a position leaves is its last `d_conv - 1`
    pre-convolution rows and H (float32, held [n, d_inner]: `ops/ssm.py`)."""

    def __init__(self, hidden_size, d_inner, d_state=16, d_conv=4,
                 dt_rank=None, dtype="float32", time_step_min=0.001,
                 time_step_max=0.1, time_step_floor=1e-4):
        super().__init__()
        import jax

        from ...core import random as _random

        self.d_inner, self.d_state = int(d_inner), int(d_state)
        self.d_conv = int(d_conv)
        self.dt_rank = int(dt_rank or math.ceil(hidden_size / 16))
        di, n, r = self.d_inner, self.d_state, self.dt_rank
        self.in_proj = _held(self, (hidden_size, 2 * di),
                             I.XavierUniform(), dtype)
        bound = 1.0 / math.sqrt(self.d_conv)
        self.conv_weight = _held(self, (di, self.d_conv),
                                 I.Uniform(-bound, bound), dtype)
        self.conv_bias = _held(self, (di,), I.Constant(0.0), dtype)
        self.x_proj = _held(self, (di, r + 2 * n), I.XavierUniform(), dtype)
        self.dt_proj = _held(self, (r, di), I.XavierUniform(), dtype)
        u = np.asarray(jax.random.uniform(
            _random.next_key(), (di,), "float32"), np.float64)
        dt = np.maximum(np.exp(math.log(time_step_min) + u * (
            math.log(time_step_max) - math.log(time_step_min))),
            time_step_floor)
        self.dt_bias = keep_float32(_held(self, (di,), I.Assign(
            (dt + np.log(-np.expm1(-dt))).astype(np.float32)), "float32"))
        self.A_log = keep_float32(_held(self, (di, n), I.Assign(np.log(
            np.tile(np.arange(1, n + 1, dtype=np.float32), (di, 1)))),
            "float32"))
        self.D = keep_float32(_held(self, (di,), I.Constant(1.0),
                                    "float32"))
        self.out_proj = _held(self, (di, hidden_size), I.XavierUniform(),
                              dtype)

    def _dt_b_c(self, xc):
        import jax
        import jax.numpy as jnp

        r, n = self.dt_rank, self.d_state
        dbc = xc @ self.x_proj._data
        dt = jax.nn.softplus(
            (dbc[..., :r] @ self.dt_proj._data).astype(jnp.float32)
            + self.dt_bias._data)
        return dt, dbc[..., r:r + n], dbc[..., r + n:]

    def prefill(self, a, length):
        """a [b, s, hidden], length [b] -> (out [b, s, hidden], s
        [b, s, d_inner] before the gate, (tail [b, d_conv - 1, d_inner],
        H [b, n, d_inner]) as position length - 1 leaves them)."""
        import jax
        import jax.numpy as jnp

        from ...ops import ssm

        f32 = jnp.float32
        di, k = self.d_inner, self.d_conv
        xz = a @ self.in_proj._data
        xs, z = xz[..., :di], xz[..., di:]
        at = jnp.asarray(length, jnp.int32)[:, None] - (k - 1) \
            + jnp.arange(k - 1, dtype=jnp.int32)[None]
        tail = jnp.take_along_axis(xs, jnp.maximum(at, 0)[..., None], 1)
        tail = jnp.where((at >= 0)[..., None], tail, 0)
        xc = jax.nn.silu(ssm.causal_conv1d(
            xs, self.conv_weight._data, self.conv_bias._data).astype(
            f32)).astype(a.dtype)
        dt, b_mat, c_mat = self._dt_b_c(xc)
        y, h = ssm.selective_scan(
            xc, dt, -jnp.exp(self.A_log._data), b_mat, c_mat,
            self.D._data, None, length)
        out = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(
            a.dtype) @ self.out_proj._data
        return out, y, (tail, h)

    def step(self, a, tail, h):
        """One position a row: a [S, hidden], tail [S, d_conv - 1,
        d_inner], h [S, n, d_inner] -> (out, s, (tail, h))."""
        import jax
        import jax.numpy as jnp

        from ...ops import ssm

        f32 = jnp.float32
        di = self.d_inner
        xz = a @ self.in_proj._data
        xs, z = xz[..., :di], xz[..., di:]
        win = jnp.concatenate([tail, xs[:, None].astype(tail.dtype)], 1)
        w = self.conv_weight._data.astype(f32)
        acc = (win.astype(f32) * w.T[None]).sum(1) \
            + self.conv_bias._data.astype(f32)
        xc = jax.nn.silu(acc).astype(a.dtype)
        dt, b_vec, c_vec = self._dt_b_c(xc)
        y, h = ssm.selective_step(
            xc, dt, -jnp.exp(self.A_log._data), b_vec, c_vec,
            self.D._data, h)
        out = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(
            a.dtype) @ self.out_proj._data
        return out, y, (win[:, 1:], h)
