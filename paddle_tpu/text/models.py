"""Flagship transformer models (ERNIE/BERT-family encoders).

Reference parity: the reference framework itself ships no ERNIE model code
(it lives in PaddleNLP), but ERNIE-base is the reference's headline NLP
benchmark workload (BASELINE.md config 3) and the fused attention kernels
(operators/fused/multihead_matmul_op.cc, math/bert_encoder_functor.cu) exist
to serve it. Here the model is a first-class citizen built on paddle_tpu.nn,
bf16-friendly, with parameter names matching parallel.sharding.COMMON_TP_RULES
so tp/sp sharding is declarative.
"""
from __future__ import annotations

from .. import nn


class ErnieConfig:
    def __init__(self, vocab_size=18000, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072, max_position=513,
                 type_vocab_size=2, hidden_dropout=0.1, attn_dropout=0.1,
                 num_classes=2, moe_experts=0, moe_capacity_factor=1.25):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout = hidden_dropout
        self.attn_dropout = attn_dropout
        self.num_classes = num_classes
        # moe_experts > 0 replaces every encoder FFN with a top-1
        # routed MoELayer (nn/layer/moe.py) whose expert axis shards
        # over the mesh's `ep` axis
        self.moe_experts = moe_experts
        self.moe_capacity_factor = moe_capacity_factor

    @classmethod
    def base(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
                 intermediate_size=128, max_position=128)
        d.update(kw)
        return cls(**d)


class ErnieEmbeddings(nn.Layer):
    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.position_embeddings = nn.Embedding(cfg.max_position,
                                                cfg.hidden_size)
        self.token_type_embeddings = nn.Embedding(cfg.type_vocab_size,
                                                  cfg.hidden_size)
        self.layer_norm = nn.LayerNorm(cfg.hidden_size)
        self.dropout = nn.Dropout(cfg.hidden_dropout)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        from ..tensor import ops as T

        seq_len = input_ids.shape[1]
        if position_ids is None:
            position_ids = T.arange(0, seq_len, dtype="int64")
            position_ids = T.unsqueeze(position_ids, 0)
        if token_type_ids is None:
            token_type_ids = T.zeros_like(input_ids)
        emb = (self.word_embeddings(input_ids)
               + self.position_embeddings(position_ids)
               + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(emb))


class ErnieModel(nn.Layer):
    """BERT/ERNIE encoder. attention_mask: (B, S) 1/0 valid-token mask.

    Packed varlen feeds (LoD-native fine-tuning): pass the outputs of
    core/lod.pack_padded instead of a padded batch — `input_ids` =
    packed.data, `position_ids` = packed.positions, `attn_segment_ids`
    = packed.segment_ids, and `cls_flat_index` = packed.cls_flat_index()
    to pool each SEQUENCE's first token (several sequences share a
    row, so `seq_out[:, 0]` would miss all but the first). No dense
    attention_mask is needed: pads form their own segment, and the
    attention dispatcher routes segment ids to the segment-masked
    packed flash kernel on TPU."""

    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = ErnieEmbeddings(cfg)
        enc_layer = nn.TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout, activation="gelu",
            attn_dropout=cfg.attn_dropout,
            moe_experts=getattr(cfg, "moe_experts", 0),
            moe_capacity_factor=getattr(cfg, "moe_capacity_factor", 1.25))
        self.encoder = nn.TransformerEncoder(enc_layer, cfg.num_layers)
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.pooler_act = nn.Tanh()

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None, attn_segment_ids=None,
                cls_flat_index=None):
        from ..tensor import ops as T

        if attention_mask is not None:
            # (B, S) -> additive (B, 1, 1, S) broadcast over heads/queries
            m = T.unsqueeze(attention_mask, [1, 2])
            mask = (1.0 - m.astype("float32")) * -1e4
        else:
            mask = None
        x = self.embeddings(input_ids, token_type_ids, position_ids)
        seq_out = self.encoder(x, mask, segment_ids=attn_segment_ids)
        if cls_flat_index is not None:
            b, s, hdim = seq_out.shape
            flat = seq_out.reshape([b * s, hdim])
            cls_tok = T.index_select(flat, cls_flat_index, axis=0)
        else:
            cls_tok = seq_out[:, 0]
        pooled = self.pooler_act(self.pooler(cls_tok))
        return seq_out, pooled


class ErnieForSequenceClassification(nn.Layer):
    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.ernie = ErnieModel(cfg)
        self.dropout = nn.Dropout(cfg.hidden_dropout)
        self.classifier = nn.Linear(cfg.hidden_size, cfg.num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                position_ids=None, attn_segment_ids=None,
                cls_flat_index=None):
        _, pooled = self.ernie(input_ids, token_type_ids,
                               position_ids=position_ids,
                               attention_mask=attention_mask,
                               attn_segment_ids=attn_segment_ids,
                               cls_flat_index=cls_flat_index)
        return self.classifier(self.dropout(pooled))


class ErnieForPretraining(nn.Layer):
    """MLM head (tied to word embeddings) + NSP head."""

    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.ernie = ErnieModel(cfg)
        self.mlm_transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.mlm_norm = nn.LayerNorm(cfg.hidden_size)
        self.nsp = nn.Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        from .. import nn as _nn
        from ..tensor import ops as T

        seq_out, pooled = self.ernie(input_ids, token_type_ids,
                                     attention_mask=attention_mask)
        h = self.mlm_norm(_nn.functional.gelu(self.mlm_transform(seq_out)))
        # tied decoder: logits = h @ word_emb.T
        w = self.ernie.embeddings.word_embeddings.weight
        mlm_logits = T.matmul(h, w, transpose_y=True)
        nsp_logits = self.nsp(pooled)
        return mlm_logits, nsp_logits


def ernie_base(**kw):
    return ErnieModel(ErnieConfig.base(**kw))


def ernie_tiny(**kw):
    return ErnieModel(ErnieConfig.tiny(**kw))


# --------------------------------------------------------------------------
# NemotronH: a hybrid of Mamba-2 mixers, sparse experts and grouped-query
# attention (`model_type` "nemotron_h"). One mixer a layer under a pre-norm
# residual; the kind of each layer is a character of
# `hybrid_override_pattern`: M Mamba-2, E experts, * attention.
# --------------------------------------------------------------------------

class NemotronHConfig:
    """Keys as the source's `config.json` names them. `experts_held` =
    (first, count) is the one key of this repo's own: the routed experts
    this rank holds (default: all `n_routed_experts`)."""

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 hybrid_override_pattern="MEMEM*EME",
                 num_attention_heads=32, num_key_value_heads=2,
                 head_dim=128, mamba_num_heads=64, mamba_head_dim=64,
                 n_groups=8, ssm_state_size=128, conv_kernel=4,
                 chunk_size=128, time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4, n_routed_experts=128,
                 num_experts_per_tok=6, moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 mlp_hidden_act="relu2", layer_norm_epsilon=1e-5,
                 experts_held=None, **unused):
        bad = set(hybrid_override_pattern) - set("ME*")
        if bad:
            raise ValueError(f"hybrid_override_pattern holds {sorted(bad)}: "
                             f"only M, E and * layers are built")
        if mlp_hidden_act != "relu2" or not norm_topk_prob:
            raise ValueError(
                f"mlp_hidden_act {mlp_hidden_act!r}, norm_topk_prob "
                f"{norm_topk_prob!r}: the experts are built with relu(h)^2 "
                f"and normalised top-k weights only")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.hybrid_override_pattern = hybrid_override_pattern
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.n_groups = n_groups
        self.ssm_state_size = ssm_state_size
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.time_step_min = time_step_min
        self.time_step_max = time_step_max
        self.time_step_floor = time_step_floor
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.mlp_hidden_act = mlp_hidden_act
        self.layer_norm_epsilon = layer_norm_epsilon
        self.experts_held = tuple(experts_held) if experts_held else (
            0, n_routed_experts)

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=96, hidden_size=32,
                 hybrid_override_pattern="ME*E", num_attention_heads=4,
                 num_key_value_heads=2, head_dim=8, mamba_num_heads=4,
                 mamba_head_dim=8, n_groups=2, ssm_state_size=8,
                 chunk_size=8, n_routed_experts=8, num_experts_per_tok=3,
                 moe_intermediate_size=16,
                 moe_shared_expert_intermediate_size=24)
        d.update(kw)
        return cls(**d)


class NemotronHBlock(nn.Layer):
    """x + mixer(RMSNorm(x)); `kind` is the layer's pattern character."""

    SCOPES = {"M": "mamba", "E": "moe", "*": "attn"}

    def __init__(self, cfg: NemotronHConfig, kind):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon)
        if kind == "M":
            self.mixer = nn.Mamba2Mixer(
                cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
                cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel,
                cfg.chunk_size, cfg.layer_norm_epsilon, cfg.time_step_min,
                cfg.time_step_max, cfg.time_step_floor)
        elif kind == "E":
            self.mixer = nn.SparseMoELayer(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                shared_d_ff=cfg.moe_shared_expert_intermediate_size,
                routed_scaling=cfg.routed_scaling_factor,
                experts_held=cfg.experts_held)
        else:
            self.mixer = nn.GroupedQueryAttention(
                cfg.hidden_size, cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim)

    def forward(self, x):
        import jax

        # the scope names the layer's kind on every device operation
        with jax.named_scope(self.SCOPES[self.kind]):
            return x + self.mixer(self.norm(x))


class NemotronHForCausalLM(nn.Layer):
    """ids [b, s] -> logits [b, s, vocab]. Embedding, the pattern's
    blocks, a final RMSNorm and an untied head; no positional term
    anywhere (the state-space layers carry order). Each block goes through
    `run_block`, so `SpmdTrainer(remat=True)` recomputes block by block."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            NemotronHBlock(cfg, kind)
            for kind in cfg.hybrid_override_pattern])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids):
        from ..nn.layer.layers import run_block

        x = self.embeddings(input_ids)
        for block in self.layers:
            x = run_block(block, x)
        return self.lm_head(self.norm_f(x))


# --------------------------------------------------------------------------
# Phi4Flash (`model_type` "phi4flash"; the SambaY decoder-hybrid-decoder of
# arXiv:2507.06607). A self-decoder, blocks 0 .. L/2 + 1: Mamba-1 mixers at
# the even blocks up to L/2, sliding-window differential attention at the
# odd ones, full differential attention at block L/2 + 1; and a
# cross-decoder after it: gated memory units at the even blocks (they read
# block L/2's state-space output, "the memory") and cross attention at the
# odd ones (they read block L/2 + 1's keys and values). No positional term
# anywhere. Inference only: nothing here records a gradient.
# --------------------------------------------------------------------------

class Phi4FlashConfig:
    """Keys as the source's `config.json` names them; `head_dim`, `d_state`,
    `d_conv`, `expand`, `dt_rank` and `dtype` are not in it (the Mamba-1
    conventions and hidden / heads)."""

    def __init__(self, vocab_size=200064, hidden_size=2560,
                 intermediate_size=10240, num_hidden_layers=32,
                 num_attention_heads=40, num_key_value_heads=20,
                 sliding_window=512, mb_per_layer=2, layer_norm_eps=1e-5,
                 hidden_act="silu", tie_word_embeddings=True,
                 mlp_bias=False, lm_head_bias=False, head_dim=None,
                 d_state=16, d_conv=4, expand=2, dt_rank=None,
                 dtype="bfloat16", **unused):
        if hidden_act != "silu" or not tie_word_embeddings or mlp_bias \
                or lm_head_bias or mb_per_layer != 2:
            raise ValueError(
                "Phi4Flash is built with silu, tied embeddings, no bias in "
                "the feed-forward or the head and a Mamba layer every "
                "second block only")
        if num_hidden_layers % 4 or num_key_value_heads % 2 or \
                num_attention_heads % num_key_value_heads:
            raise ValueError(
                f"{num_hidden_layers} layers, {num_attention_heads} query "
                f"over {num_key_value_heads} key-value heads: the layout "
                f"needs layers in fours and heads in pairs")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.sliding_window, self.mb_per_layer = sliding_window, mb_per_layer
        self.layer_norm_eps = layer_norm_eps
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.d_state, self.d_conv = d_state, d_conv
        self.d_inner = expand * hidden_size
        self.dt_rank = dt_rank or -(-hidden_size // 16)
        self.dtype = dtype
        #: the Mamba block whose state-space output the memory units read
        self.memory_layer = num_hidden_layers // 2

    def kind(self, i):
        """Block i's mixer: mamba | swa | full | gmu | xattn."""
        half = self.num_hidden_layers // 2
        if i % 2 == 0:
            return "mamba" if i <= half else "gmu"
        return "swa" if i < half else "full" if i == half + 1 else "xattn"

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                 num_hidden_layers=8, num_attention_heads=4,
                 num_key_value_heads=2, sliding_window=8, d_state=4,
                 dt_rank=4, dtype="float32")
        d.update(kw)
        return cls(**d)


class Phi4FlashBlock(nn.Layer):
    """h = x + Mixer(LN(x)); y = h + W2 (u * silu(g)), [u; g] = W1 LN'(h)."""

    def __init__(self, cfg: Phi4FlashConfig, idx):
        super().__init__()
        from ..nn.layer.ssm import _held
        from ..nn import initializer as I

        self.kind, self.idx = cfg.kind(idx), idx
        d, dt = cfg.hidden_size, cfg.dtype
        self.input_layernorm = nn.LayerNorm(d, cfg.layer_norm_eps)
        self.post_attention_layernorm = nn.LayerNorm(d, cfg.layer_norm_eps)
        if self.kind == "mamba":
            self.mixer = nn.Mamba1Mixer(d, cfg.d_inner, cfg.d_state,
                                        cfg.d_conv, cfg.dt_rank, dt)
        elif self.kind == "gmu":
            self.mixer = nn.GatedMemoryUnit(d, cfg.d_inner, dt)
        else:
            self.mixer = nn.DifferentialAttention(
                d, cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim, idx, cross=self.kind == "xattn", dtype=dt)
        self.fc1 = _held(self, (d, 2 * cfg.intermediate_size),
                         I.XavierUniform(), dt)
        self.fc2 = _held(self, (cfg.intermediate_size, d),
                         I.XavierUniform(), dt)

    @staticmethod
    def _ln(ln, x):
        import jax
        import jax.numpy as jnp

        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + jnp.float32(ln._epsilon))
        return (y * ln.weight._data.astype(jnp.float32)
                + ln.bias._data.astype(jnp.float32)).astype(x.dtype)

    def pre(self, x):
        return self._ln(self.input_layernorm, x)

    def feed_forward(self, h):
        import jax
        import jax.numpy as jnp

        ug = self._ln(self.post_attention_layernorm, h) @ self.fc1._data
        w = ug.shape[-1] // 2
        act = (ug[..., :w].astype(jnp.float32)
               * jax.nn.silu(ug[..., w:].astype(jnp.float32))).astype(
            h.dtype)
        return h + act @ self.fc2._data


class Phi4FlashForCausalLM(nn.Layer):
    """ids -> logits, three ways over raw arrays, all inference:

    - `forward(ids)`: every block over every position.
    - `prefill(ids [b, s], length [b])`: a join. Blocks up to the full
      attention layer run over the prompt and leave their state: a
      convolution tail and a scan state a Mamba block, the last `window`
      key and value rows a window block (row r holds the newest position
      congruent to r), the full layer's key and value rows. The blocks
      after it, the memory and the full layer's own output are computed
      for position length - 1 alone: none of them leaves state.
    - `decode(tok [S], index [S], state, table)`: one position a slot,
      each kind of state (`state["recurrent" | "ring" | "paged"]`)
      updated in place.

    The logits are float32."""

    SCOPES = ("mamba", "swa", "full", "xattn", "gmu")

    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        from ..nn import initializer as I
        from ..nn.layer.ssm import _held

        self.cfg = cfg
        self.embed_tokens = _held(
            self, (cfg.vocab_size, cfg.hidden_size), I.Normal(0.0, 0.02),
            cfg.dtype)
        self.layers = nn.LayerList([
            Phi4FlashBlock(cfg, i) for i in range(cfg.num_hidden_layers)])
        self.final_layernorm = nn.LayerNorm(cfg.hidden_size,
                                            cfg.layer_norm_eps)
        for _, p in self.named_parameters():
            # LayerNorm's parameters are made float32: hold them as the
            # rest is held
            if str(p._data.dtype) != cfg.dtype and \
                    not p.optimize_attr.get("keep_float32"):
                p._data = p._data.astype(cfg.dtype)

    def cache_kinds(self):
        """What each block keeps of a sequence, for the pool's layout."""
        keeps = {"mamba": "recurrent", "swa": "ring", "full": "paged"}
        return [keeps.get(b.kind) for b in self.layers]

    # ---- shared pieces ----
    def _logits(self, y):
        import jax.numpy as jnp

        h = Phi4FlashBlock._ln(self.final_layernorm, y)
        return jnp.dot(h, self.embed_tokens._data.T,
                       preferred_element_type=jnp.float32)

    def forward(self, ids=None, op=None, args=()):
        """`forward(ids)`, or `forward(op="prefill" | "decode", args=...)`:
        how a functionalized copy (`FunctionalModule.apply` calls
        `forward`) reaches the other two."""
        import jax
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        from ..ops import diff_attention as DA

        if op is not None:
            return getattr(self, op)(*args)
        cfg = self.cfg
        ids = getattr(ids, "_data", ids)
        b, s = ids.shape
        length = jnp.full((b,), s, jnp.int32)
        x = self.embed_tokens._data[ids]
        memory = kv = None
        for blk in self.layers:
            a = blk.pre(x)
            with jax.named_scope(blk.kind):
                if blk.kind == "mamba":
                    out, y, _ = blk.mixer.prefill(a, length)
                    if blk.idx == cfg.memory_layer:
                        memory = y
                elif blk.kind == "gmu":
                    out = blk.mixer.mix(a, memory)
                elif blk.kind == "xattn":
                    out = blk.mixer.finish(DA.causal(
                        blk.mixer.project(a), kv[0], kv[1],
                        cfg.num_key_value_heads))
                else:
                    q, k, v = blk.mixer.project(a)
                    if blk.kind == "full":
                        kv = (k, v)
                    out = blk.mixer.finish(DA.causal(
                        q, k, v, cfg.num_key_value_heads,
                        cfg.sliding_window if blk.kind == "swa" else None))
            x = blk.feed_forward(x + out)
        return Tensor._wrap(self._logits(x))

    def prefill(self, ids, length):
        """-> (logits [b, vocab] of position length - 1, {"recurrent":
        [(tail, H)], "ring": [(k, v)] of [b, window, kv width] rows,
        "paged": [(k, v)] of the full layer, [b, Hkv, s, d]}, the
        program's counters: None)."""
        import jax
        import jax.numpy as jnp

        from ..ops import diff_attention as DA

        cfg = self.cfg
        ids = getattr(ids, "_data", ids)
        length = jnp.asarray(length, jnp.int32)
        last = (length - 1)[:, None, None]
        w = cfg.sliding_window
        x = self.embed_tokens._data[ids]
        recurrent, ring = [], []
        memory = kv = None
        # ring row r holds the newest position congruent to r mod window
        r = jnp.arange(w, dtype=jnp.int32)[None]
        newest = (length - 1)[:, None] - (length[:, None] - 1 - r) % w
        newest = jnp.clip(newest, 0, ids.shape[1] - 1)[..., None]

        def take_last(t):
            return jnp.take_along_axis(t, last, 1)[:, 0]

        for blk in self.layers:
            a = blk.pre(x)
            with jax.named_scope(blk.kind):
                if blk.kind == "mamba":
                    out, y, state = blk.mixer.prefill(a, length)
                    recurrent.append(state)
                    if blk.idx == cfg.memory_layer:
                        memory = take_last(y)
                elif blk.kind == "swa":
                    q, k, v = blk.mixer.project(a)
                    ring.append((jnp.take_along_axis(k, newest, 1),
                                 jnp.take_along_axis(v, newest, 1)))
                    out = blk.mixer.finish(DA.causal(
                        q, k, v, cfg.num_key_value_heads, w))
                elif blk.kind == "full":
                    # from here on: position length - 1 alone
                    _, k, v = blk.mixer.project(a)
                    kv = (k, v)
                    x, a = take_last(x), take_last(a)
                    q = blk.mixer.project(a)[0]
                    out = blk.mixer.finish(DA.dense(
                        q, k, v, cfg.num_key_value_heads, length))
                elif blk.kind == "gmu":
                    out = blk.mixer.mix(a, memory)
                else:
                    out = blk.mixer.finish(DA.dense(
                        blk.mixer.project(a), kv[0], kv[1],
                        cfg.num_key_value_heads, length))
            x = blk.feed_forward(x + out)

        def heads(rows):   # [b, s, Hkv d] -> [b, Hkv, s, d]
            return jnp.swapaxes(rows.reshape(
                rows.shape[:2] + (cfg.num_key_value_heads, cfg.head_dim)),
                1, 2)

        return self._logits(x), {
            "recurrent": recurrent, "ring": ring,
            "paged": [(heads(kv[0]), heads(kv[1]))]}, None

    @staticmethod
    def _ring_step(ring, rows, at, k, v):
        """This position's key and value rows written at row `at` of each
        slot's ring: (what the slot keeps, what this position reads).
        They are the same: a position reads its own row."""
        rk, rv = ring
        new = (rk.at[rows, at].set(k.astype(rk.dtype)),
               rv.at[rows, at].set(v.astype(rv.dtype)))
        return new, new

    def decode(self, tok, index, state, table, active=None):
        """tok, index [S]: the token each slot feeds and the position it
        stands at. state: "recurrent" [(tail, H)], "ring" [(k, v)] [S,
        window, kv width], "paged" [{"k", "v"}] [N + 1, psz, kv width]
        with `table` [S, max_pages]. -> (logits [S, vocab], the same
        kinds as the step leaves them, None)."""
        import jax
        import jax.numpy as jnp

        from ..ops import diff_attention as DA
        from ..serving import paging as PG

        cfg = self.cfg
        hkv, w = cfg.num_key_value_heads, cfg.sliding_window
        index = jnp.asarray(index, jnp.int32)
        x = self.embed_tokens._data[tok]
        rows = jnp.arange(tok.shape[0])
        new_rec, new_ring = [], []
        rec, rng = iter(state["recurrent"]), iter(state["ring"])
        pages = (state["paged"][0]["k"], state["paged"][0]["v"])
        memory = None
        for blk in self.layers:
            a = blk.pre(x)
            with jax.named_scope(blk.kind):
                if blk.kind == "mamba":
                    out, y, state = blk.mixer.step(a, *next(rec))
                    new_rec.append(state)
                    if blk.idx == cfg.memory_layer:
                        memory = y
                elif blk.kind == "swa":
                    q, k, v = blk.mixer.project(a)
                    kept, (rk, rv) = self._ring_step(
                        next(rng), rows, index % w, k, v)
                    new_ring.append(kept)
                    out = blk.mixer.finish(DA.dense(
                        q, rk, rv, hkv, jnp.minimum(index + 1, w)))
                elif blk.kind == "full":
                    q, k, v = blk.mixer.project(a)
                    d = cfg.head_dim
                    pages = tuple(
                        PG.write_token(pg, None, table, index,
                                       t.reshape(-1, hkv, d))[0]
                        for pg, t in zip(pages, (k, v)))
                    read = DA.paged_reader(pages[0], pages[1], table,
                                           hkv)
                    out = blk.mixer.finish(read(q, index + 1))
                elif blk.kind == "gmu":
                    out = blk.mixer.mix(a, memory)
                else:
                    out = blk.mixer.finish(read(blk.mixer.project(a),
                                                index + 1))
            x = blk.feed_forward(x + out)
        return self._logits(x), {
            "recurrent": new_rec, "ring": new_ring,
            "paged": [{"k": pages[0], "v": pages[1], "ks": None,
                       "vs": None}]}, None


# --------------------------------------------------------------------------
# DeepSeek-V3 (`model_type` "deepseek_v3"; arXiv:2412.19437). Pre-norm
# blocks, x = x + attn(RMSNorm(x)); x = x + ffn(RMSNorm(x)): multi-head
# latent attention in every block (`nn.LatentAttention`: rotary positions
# with YaRN on a slice of each head), a dense gated feed-forward in the
# first `first_k_dense_replace` blocks and group-limited sigmoid-routed
# gated experts with a shared expert in the rest (`nn.SparseMoELayer`). A
# token leaves ONE latent row a block. Inference only; the multi-token
# prediction module is not built (the report's section 2.2 discards it at
# inference).
# --------------------------------------------------------------------------

class DeepseekV3Config:
    """Keys as the source's `config.json` names them. Of this repo's own:
    `experts_held` = (first, count), the routed experts this rank holds
    (default: all `n_routed_experts`); `latent_row_pad`, zero values that
    close a cached latent row (to a lane multiple); `dtype`."""

    def __init__(self, vocab_size=129280, hidden_size=7168,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 num_hidden_layers=61, num_attention_heads=128,
                 n_shared_experts=1, n_routed_experts=256,
                 routed_scaling_factor=2.5, kv_lora_rank=512,
                 q_lora_rank=1536, qk_rope_head_dim=64, v_head_dim=128,
                 qk_nope_head_dim=128, n_group=8, topk_group=4,
                 num_experts_per_tok=8, first_k_dense_replace=3,
                 norm_topk_prob=True, scoring_func="sigmoid",
                 topk_method="noaux_tc", moe_layer_freq=1,
                 hidden_act="silu", rms_norm_eps=1e-6, rope_theta=10000,
                 rope_scaling=None, attention_bias=False,
                 tie_word_embeddings=False, num_nextn_predict_layers=0,
                 experts_held=None, latent_row_pad=0, dtype="bfloat16",
                 **unused):
        if hidden_act != "silu" or scoring_func != "sigmoid" or \
                not norm_topk_prob or topk_method != "noaux_tc" or \
                moe_layer_freq != 1 or attention_bias or \
                tie_word_embeddings or n_shared_experts < 1:
            raise ValueError(
                "DeepseekV3 is built with silu-gated experts, sigmoid "
                "scores normalised over the chosen experts, the "
                "bias-steered group-limited choice (noaux_tc), an expert "
                "layer in every block after the leading dense ones, a "
                "shared expert, no attention bias and an untied head")
        if num_nextn_predict_layers:
            raise ValueError(
                f"num_nextn_predict_layers={num_nextn_predict_layers}: "
                f"the multi-token prediction module is not built")
        rope = dict(rope_scaling or {})
        if rope and (rope.get("type", rope.get("rope_type")) != "yarn"
                     or rope.get("mscale") != rope.get("mscale_all_dim")):
            raise ValueError(
                f"rope_scaling {rope_scaling!r}: YaRN with mscale == "
                f"mscale_all_dim (the cos / sin factor 1) is what is "
                f"built")
        if not 0 <= first_k_dense_replace <= num_hidden_layers:
            raise ValueError("first_k_dense_replace past the depth")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.n_shared_experts = n_shared_experts
        self.n_routed_experts = n_routed_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.kv_lora_rank, self.q_lora_rank = kv_lora_rank, q_lora_rank
        self.qk_rope_head_dim = qk_rope_head_dim
        self.qk_nope_head_dim, self.v_head_dim = qk_nope_head_dim, v_head_dim
        self.n_group, self.topk_group = n_group, topk_group
        self.num_experts_per_tok = num_experts_per_tok
        self.first_k_dense_replace = first_k_dense_replace
        self.rms_norm_eps, self.rope_theta = rms_norm_eps, rope_theta
        self.rope_scaling = rope
        self.experts_held = tuple(experts_held) if experts_held else (
            0, n_routed_experts)
        self.latent_row_pad, self.dtype = int(latent_row_pad), dtype

    @property
    def latent_row_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim \
            + self.latent_row_pad

    @classmethod
    def tiny(cls, **kw):
        """One dense and two expert layers; 8 groups of 2 experts of which
        4 are chosen; 4 of the 16 experts held; positions past `original`
        within a short sequence."""
        d = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                 moe_intermediate_size=16, num_hidden_layers=3,
                 num_attention_heads=4, n_routed_experts=16,
                 kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8,
                 v_head_dim=8, qk_nope_head_dim=8, n_group=8, topk_group=4,
                 num_experts_per_tok=4, first_k_dense_replace=1,
                 rope_scaling={"type": "yarn", "factor": 40,
                               "original_max_position_embeddings": 8,
                               "beta_fast": 32, "beta_slow": 1,
                               "mscale": 1, "mscale_all_dim": 1},
                 experts_held=(4, 4), dtype="float32")
        d.update(kw)
        return cls(**d)


class DeepseekV3Block(nn.Layer):
    """x + attn(RMSNorm(x)), then x + ffn(RMSNorm(x)): `dense` blocks
    hold a gated feed-forward, the others an expert layer."""

    def __init__(self, cfg: DeepseekV3Config, idx):
        super().__init__()
        from ..nn import initializer as I
        from ..nn.layer.ssm import _held

        self.idx, self.dense = idx, idx < cfg.first_k_dense_replace
        d, dt, rope = cfg.hidden_size, cfg.dtype, cfg.rope_scaling
        self.input_layernorm = nn.RMSNorm(d, cfg.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(d, cfg.rms_norm_eps)
        self.self_attn = nn.LatentAttention(
            d, cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.rms_norm_eps, rope=dict(
                theta=cfg.rope_theta, factor=rope.get("factor", 1.0),
                original=rope.get("original_max_position_embeddings",
                                  4096),
                beta_fast=rope.get("beta_fast", 32),
                beta_slow=rope.get("beta_slow", 1),
                mscale_all_dim=rope.get("mscale_all_dim", 0.0)),
            row_pad=cfg.latent_row_pad, dtype=dt)
        if self.dense:
            f = cfg.intermediate_size
            self.gate_proj = _held(self, (d, f), I.XavierUniform(), dt)
            self.up_proj = _held(self, (d, f), I.XavierUniform(), dt)
            self.down_proj = _held(self, (f, d), I.XavierUniform(), dt)
        else:
            self.mlp = nn.SparseMoELayer(
                d, cfg.moe_intermediate_size, cfg.n_routed_experts,
                cfg.num_experts_per_tok,
                shared_d_ff=cfg.n_shared_experts
                * cfg.moe_intermediate_size,
                routed_scaling=cfg.routed_scaling_factor,
                experts_held=cfg.experts_held, activation="swiglu",
                n_group=cfg.n_group, topk_group=cfg.topk_group, dtype=dt)

    @staticmethod
    def norm(ln, x):
        from ..nn.layer.mla import rms

        return rms(x, ln.weight._data, ln._epsilon)

    def feed_forward(self, h, valid=None):
        """h [..., hidden] -> (h + ffn(RMSNorm(h)), the expert layer's
        counts [4] int32 or None). `valid` [...] bool: the tokens an
        expert layer routes."""
        import jax
        import jax.numpy as jnp

        from ..ops import moe

        a = self.norm(self.post_attention_layernorm, h)
        if self.dense:
            with jax.named_scope("dense_ffn"):
                f32 = jnp.float32
                act = moe.swiglu(
                    jnp.dot(a, self.gate_proj._data,
                            preferred_element_type=f32),
                    jnp.dot(a, self.up_proj._data,
                            preferred_element_type=f32)).astype(h.dtype)
                return h + act @ self.down_proj._data, None
        with jax.named_scope("moe"):
            y, counts = self.mlp.mix(a, valid)
            return h + y, counts


class DeepseekV3ForCausalLM(nn.Layer):
    """ids -> logits (float32), three ways over raw arrays, all inference:

    - `forward(ids)`: every block over every position, attention
      unabsorbed.
    - `prefill(ids [b, s], length [b])`: a join, attention unabsorbed ->
      (logits [b, vocab] of position length - 1, {"latent": a block's
      rows [b, s, row width]}, the expert layers' counts).
    - `decode(tok [S], index [S], state, table, active)`: one position a
      slot, attention ABSORBED over the slot's written rows read through
      its page table -> (logits [S, vocab], {"latent": pages}, counts).

    The counts ([4] int32, summed over the expert layers: token-slots
    routed, those on held experts, the fullest held expert's, held slots
    the experts' loops did not reach: 0) leave a program with its tokens."""

    SCOPES = ("mla", "dense_ffn", "moe", "router")
    #: what a program's counts are, in order (`snapshot()["experts"]`)
    COUNTS = ("token_slots", "held_slots", "load_max", "dropped_slots")

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        from ..nn import initializer as I
        from ..nn.layer.ssm import _held

        self.cfg = cfg
        self.embed_tokens = _held(
            self, (cfg.vocab_size, cfg.hidden_size), I.Normal(0.0, 0.02),
            cfg.dtype)
        self.layers = nn.LayerList([
            DeepseekV3Block(cfg, i) for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.lm_head = _held(self, (cfg.hidden_size, cfg.vocab_size),
                             I.XavierUniform(), cfg.dtype)
        for _, p in self.named_parameters():
            # the norms' weights are made float32: hold them as the rest
            # is held (the router stays float32)
            if str(p._data.dtype) != cfg.dtype and \
                    not p.optimize_attr.get("keep_float32"):
                p._data = p._data.astype(cfg.dtype)

    def cache_kinds(self):
        """What each block keeps of a sequence, for the pool's layout."""
        return ["latent"] * len(self.layers)

    def _logits(self, y):
        import jax.numpy as jnp

        return jnp.dot(DeepseekV3Block.norm(self.norm, y),
                       self.lm_head._data,
                       preferred_element_type=jnp.float32)

    @staticmethod
    def _sum(counts):
        import jax.numpy as jnp

        counts = [c for c in counts if c is not None]
        return sum(counts[1:], counts[0]) if counts else \
            jnp.zeros((4,), jnp.int32)

    def _blocks(self, x, positions, valid):
        """Every block over every position: (x, rows a block, counts)."""
        import jax

        rows, counts = [], []
        for blk in self.layers:
            with jax.named_scope("mla"):
                q, row = blk.self_attn.project(
                    blk.norm(blk.input_layernorm, x), positions)
                x = x + blk.self_attn.causal(q, row)
            rows.append(row)
            x, c = blk.feed_forward(x, valid)
            counts.append(c)
        return x, rows, self._sum(counts)

    def forward(self, ids=None, op=None, args=()):
        """`forward(ids)`, or `forward(op="prefill" | "decode", args=...)`:
        how a functionalized copy (`FunctionalModule.apply` calls
        `forward`) reaches the other two."""
        import jax.numpy as jnp

        from ..core.tensor import Tensor

        if op is not None:
            return getattr(self, op)(*args)
        ids = getattr(ids, "_data", ids)
        positions = jnp.broadcast_to(
            jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
        x, _, _ = self._blocks(self.embed_tokens._data[ids], positions,
                               None)
        return Tensor._wrap(self._logits(x))

    def prefill(self, ids, length):
        import jax.numpy as jnp

        ids = getattr(ids, "_data", ids)
        length = jnp.asarray(length, jnp.int32)
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
        x, rows, counts = self._blocks(
            self.embed_tokens._data[ids],
            jnp.broadcast_to(pos, ids.shape), pos[None] < length[:, None])
        last = jnp.take_along_axis(x, (length - 1)[:, None, None], 1)[:, 0]
        return self._logits(last), {"latent": rows}, counts

    def decode(self, tok, index, state, table, active=None):
        """tok, index [S]: the token each slot feeds and the position it
        stands at; state["latent"]: a block's pages [N + 1, psz, row
        width] with `table` [S, max_pages]; `active` [S] bool: the slots
        whose tokens the expert layers route (None: all)."""
        import jax
        import jax.numpy as jnp

        from ..serving import paging as PG

        index = jnp.asarray(index, jnp.int32)
        x = self.embed_tokens._data[tok]
        pages, counts = [], []
        for blk, pg in zip(self.layers, state["latent"]):
            with jax.named_scope("mla"):
                q, row = blk.self_attn.project(
                    blk.norm(blk.input_layernorm, x), index)
                pg = PG.write_token(pg, None, table, index,
                                    row[:, None])[0]
                x = x + blk.self_attn.absorbed_paged(q, pg, table,
                                                     index + 1)
            pages.append(pg)
            x, c = blk.feed_forward(x, active)
            counts.append(c)
        return self._logits(x), {"latent": pages}, self._sum(counts)
