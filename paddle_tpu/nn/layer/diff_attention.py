"""Differential attention and the gated memory unit of the SambaY
decoders (arXiv:2507.06607) as Layers. Inference only: the methods take
and return raw arrays and record no gradient; the mathematics is
`ops/diff_attention.py`."""
from __future__ import annotations

from .. import initializer as I
from .layers import Layer, keep_float32
from .ssm import _held


class DifferentialAttention(Layer):
    """`num_heads` query heads in pairs over `num_kv_heads` key-value
    heads of `head_dim`. `cross=True` holds a query projection only: keys
    and values are another layer's. Projections carry biases; the four
    lambda vectors (N(0, 0.1)) and nothing else are float32."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 layer_idx, cross=False, dtype="float32"):
        super().__init__()
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim, self.layer_idx = int(head_dim), int(layer_idx)
        self.cross = bool(cross)
        qd, kvd = self.num_heads * self.head_dim, self.kv_width
        wide = qd if cross else qd + 2 * kvd
        self.in_weight = _held(self, (hidden_size, wide),
                               I.XavierUniform(), dtype)
        self.in_bias = _held(self, (wide,), I.Constant(0.0), dtype)
        self.out_weight = _held(self, (qd, hidden_size),
                                I.XavierUniform(), dtype)
        self.out_bias = _held(self, (hidden_size,), I.Constant(0.0), dtype)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, keep_float32(_held(
                self, (self.head_dim,), I.Normal(0.0, 0.1), "float32")))
        self.subln_weight = _held(self, (2 * self.head_dim,),
                                  I.Constant(1.0), dtype)

    @property
    def kv_width(self):
        return self.num_kv_heads * self.head_dim

    def project(self, a):
        """a [..., hidden] -> q [..., Hq, d] (and k, v rows [..., Hkv d]
        unless `cross`)."""
        y = a @ self.in_weight._data + self.in_bias._data
        qd = self.num_heads * self.head_dim
        q = y[..., :qd].reshape(y.shape[:-1] + (self.num_heads,
                                                self.head_dim))
        if self.cross:
            return q
        return q, y[..., qd:qd + self.kv_width], y[..., qd + self.kv_width:]

    def finish(self, att):
        """att [..., groups, G, 2 d] -> [..., hidden]: the pairs combined
        and projected out."""
        from ...ops import diff_attention as DA

        lam = DA.lam(self.lambda_q1._data, self.lambda_k1._data,
                     self.lambda_q2._data, self.lambda_k2._data,
                     self.layer_idx)
        return DA.combine(att, lam, self.subln_weight._data,
                          self.layer_idx) \
            @ self.out_weight._data + self.out_bias._data


class GatedMemoryUnit(Layer):
    """out_proj (m * silu(in_proj a)): `m` is another layer's state-space
    output at the same position. No bias, no state."""

    def __init__(self, hidden_size, d_inner, dtype="float32"):
        super().__init__()
        self.in_proj = _held(self, (hidden_size, d_inner),
                             I.XavierUniform(), dtype)
        self.out_proj = _held(self, (d_inner, hidden_size),
                              I.XavierUniform(), dtype)

    def mix(self, a, m):
        import jax
        import jax.numpy as jnp

        gate = jax.nn.silu((a @ self.in_proj._data).astype(jnp.float32))
        return (m.astype(jnp.float32) * gate).astype(a.dtype) \
            @ self.out_proj._data
