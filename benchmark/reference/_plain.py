"""Plain float32 building blocks the references share: straightforward
jax.numpy, no kernel, no cache, no batching trick. Matmul precision is set
to "highest" by the callers, because on a TPU a float32 matmul otherwise
runs in bfloat16 passes."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def linear(p, name, x):
    y = x @ p[name + ".weight"]
    b = p.get(name + ".bias")
    return y if b is None else y + b


def layer_norm(p, name, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p[name + ".weight"] \
        + p[name + ".bias"]


def attention(p, name, x_q, x_kv, n_heads, bias):
    """Multi-head attention, [.., Sq, D] x [.., Sk, D] -> [.., Sq, D].
    `bias` is added to the [.., H, Sq, Sk] scores (0 or -inf-like)."""
    d = x_q.shape[-1]
    dh = d // n_heads

    def heads(x):
        return jnp.swapaxes(x.reshape(x.shape[:-1] + (n_heads, dh)), -2, -3)

    q = heads(linear(p, name + ".q_proj", x_q))
    k = heads(linear(p, name + ".k_proj", x_kv))
    v = heads(linear(p, name + ".v_proj", x_kv))
    s = q @ jnp.swapaxes(k, -1, -2) / jnp.sqrt(jnp.float32(dh))
    if bias is not None:
        s = s + bias
    ctx = jax.nn.softmax(s, -1) @ v
    ctx = jnp.swapaxes(ctx, -2, -3)
    return linear(p, name + ".out_proj",
                  ctx.reshape(ctx.shape[:-2] + (d,)))


def as_f32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
