"""Plain reference of the decoder the pool serves, float32, matmul
precision "highest": one teacher-forced pass over prompt + generated
tokens of one request.

Post-LN decoder layer as BART's: causal self-attention, add & norm,
cross-attention to `memory`, add & norm, gelu feed-forward, add & norm; an
untied linear projection to the vocabulary. No position embedding, no
embedding layer-norm (the departures in configs/bart_large_dec.json).

Position convention (ServingEngine's docstring): the pool holds the prompt
at [0, bucket), masks the pad hole for ever, and writes generated tokens
from `bucket` on. With no position embedding a masked hole changes
nothing, so the reference runs the sequence without it: prompt tokens then
generated tokens under a causal mask, keys past `n_valid` masked. The
logits at sequence position t predict the token at t + 1."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference._plain import as_f32, attention, layer_norm, linear

_NEG = -1e30


def sequence_logits(p, tokens, n_valid, memory, cfg):
    """[L] tokens (padded past n_valid), [M, D] memory -> [L, V]."""
    eps = 1e-5
    n_heads = int(cfg["decoder_attention_heads"])
    length = tokens.shape[0]
    pos = jnp.arange(length)
    keep = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
    bias = jnp.where(keep, jnp.float32(0.0), jnp.float32(_NEG))[None]
    x = p["embed.weight"][tokens]
    for i in range(int(cfg["decoder_layers"])):
        n = f"decoder.layers.{i}"
        x = layer_norm(p, n + ".norm1",
                       x + attention(p, n + ".self_attn", x, x, n_heads,
                                     bias), eps)
        x = layer_norm(p, n + ".norm2",
                       x + attention(p, n + ".cross_attn", x, memory,
                                     n_heads, None), eps)
        h = jax.nn.gelu(linear(p, n + ".linear1", x), approximate=False)
        x = layer_norm(p, n + ".norm3",
                       x + linear(p, n + ".linear2", h), eps)
    return linear(p, "project", x)


def token_margins(params, tokens, n_valid, memory, cfg):
    """For a batch of requests ([B, L] tokens = prompt + generated, padded;
    [B] valid lengths; [B, M, D] memories): at every position t, how far the
    reference logit of the token that FOLLOWS (tokens[t + 1]) lies under the
    position's largest, in standard deviations of that position's logits,
    and whether it is the argmax. One request at a time (lax.map), so only
    one [L, V] block of logits is alive."""
    with jax.default_matmul_precision("highest"):
        p = as_f32(params)

        def one(args):
            toks, n, mem = args
            lg = sequence_logits(p, toks, n, mem.astype(jnp.float32), cfg)
            nxt = jnp.roll(toks, -1)
            chosen = jnp.take_along_axis(lg, nxt[:, None], 1)[:, 0]
            top = lg.max(-1)
            return ((top - chosen) / lg.std(-1), lg.argmax(-1) == nxt)

        return jax.lax.map(one, (tokens, n_valid, memory))
