"""Plain reference of the ERNIE 2.0 Base sequence classifier's forward
pass (evaluation mode: no dropout), float32, matmul precision "highest".

Post-LN BERT encoder: embeddings (word + position + token type 0) ->
LayerNorm -> 12 x [self-attention, add & norm, gelu feed-forward, add &
norm] -> tanh pooler on the first token -> linear classifier. Departure
from the source noted in configs/ernie_base.json: no task-type embedding.
Takes the trainer's own parameter dict (flat, by name)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference._plain import as_f32, attention, layer_norm, linear


def logits(params, ids, cfg):
    eps = float(cfg["layer_norm_eps"])
    n_heads = int(cfg["num_attention_heads"])
    with jax.default_matmul_precision("highest"):
        p = as_f32(params)
        e = "ernie.embeddings."
        s = ids.shape[1]
        x = (p[e + "word_embeddings.weight"][ids]
             + p[e + "position_embeddings.weight"][jnp.arange(s)][None]
             + p[e + "token_type_embeddings.weight"][0][None, None])
        x = layer_norm(p, e + "layer_norm", x, eps)
        for i in range(int(cfg["num_hidden_layers"])):
            n = f"ernie.encoder.layers.{i}"
            x = layer_norm(p, n + ".norm1",
                           x + attention(p, n + ".self_attn", x, x,
                                         n_heads, None), eps)
            h = jax.nn.gelu(linear(p, n + ".linear1", x), approximate=False)
            x = layer_norm(p, n + ".norm2",
                           x + linear(p, n + ".linear2", h), eps)
        pooled = jnp.tanh(linear(p, "ernie.pooler", x[:, 0]))
        return linear(p, "classifier", pooled)
