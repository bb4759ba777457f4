"""Mixture-of-Experts FFN with top-1 (switch) routing and
capacity-bounded dispatch/combine over the `ep` mesh axis.

Reference role: the reference framework predates MoE support (its
distributed stack is PS/collective-only); this is a beyond-parity
capability required by the `ep` axis the SPMD engine advertises.
TPU-native design: dispatch/combine are dense one-hot einsums over a
STATIC [tokens, experts, capacity] tensor (Mesh-TensorFlow / Switch
Transformer formulation) — no dynamic shapes, no scatter; expert
weights are stacked [E, ...] so `parallel.sharding` rules
(`experts.weight_in/out` -> ("ep", ...)) shard the expert axis and XLA
inserts the all-to-alls implied by the einsum contractions.
"""
from __future__ import annotations

from .. import functional as F
from .layers import Layer


class _Experts(Layer):
    """Parameter container whose PATH gives the `experts.weight_in/out`
    names the sharding rules key on (parallel/sharding.py:59). `gated`
    adds `weight_gate` beside `weight_in`; `dtype` holds the matrices in
    that dtype from the start (no float32 copy), each expert drawn as a
    [d_model, d_ff] matrix of its own."""

    def __init__(self, num_experts, d_model, d_ff, gated=False, dtype=None):
        super().__init__()
        import paddle_tpu.nn.initializer as I

        own = dtype is not None
        w_in = I.XavierUniform(d_model, d_ff) if own else I.XavierUniform()
        w_out = I.XavierUniform(d_ff, d_model) if own else I.XavierUniform()
        self.weight_in = self.create_parameter(
            [num_experts, d_model, d_ff], dtype=dtype,
            default_initializer=w_in)
        if gated:
            self.weight_gate = self.create_parameter(
                [num_experts, d_model, d_ff], dtype=dtype,
                default_initializer=w_in)
        self.weight_out = self.create_parameter(
            [num_experts, d_ff, d_model], dtype=dtype,
            default_initializer=w_out)


class MoELayer(Layer):
    """Top-1 routed FFN: y[t] = gate[t] * W_out[e(t)] @ act(W_in[e(t)] x[t]).

    Tokens beyond an expert's capacity (capacity_factor * tokens /
    num_experts) are dropped (contribute zero — the residual connection
    around the layer carries them), matching Switch Transformer
    semantics. The router's load-balancing auxiliary loss is stored on
    `self.aux_loss` each forward; trainers add `moe_aux_weight *
    sum(aux losses)` to the objective.
    """

    def __init__(self, d_model, d_ff, num_experts=2, capacity_factor=1.25,
                 activation="gelu", name=None):
        super().__init__()
        import paddle_tpu.nn.initializer as I

        self.num_experts = int(num_experts)
        self.capacity_factor = float(capacity_factor)
        self.act = activation
        self.router = self.create_parameter(
            [d_model, self.num_experts],
            default_initializer=I.XavierUniform())
        # stacked expert weights: leading E axis is the `ep` shard axis
        # (parallel/sharding.py rules match the experts.* path)
        self.experts = _Experts(self.num_experts, d_model, d_ff)
        # the load-balance aux loss rides a (non-persistable) BUFFER:
        # FunctionalModule threads buffer mutations through apply()'s
        # RETURN value, which survives jit and jax.checkpoint — a side
        # list would leak tracers out of the remat trace. SpmdTrainer
        # picks every `aux_loss_val` buffer out of new_buffers and adds
        # moe_aux_weight * sum to the objective.
        import numpy as np

        from ...core.tensor import Tensor

        self.register_buffer("aux_loss_val",
                             Tensor(np.zeros((), np.float32)),
                             persistable=False)
        self._last_aux = None

    @property
    def aux_loss(self):
        """Eager: the tape Tensor from the last forward (differentiable
        for `total = loss + w * moe.aux_loss` training loops). In a
        functional/jit context read the `aux_loss_val` entry of
        apply()'s new_buffers instead."""
        if self._last_aux is not None:
            return self._last_aux
        return self._buffers["aux_loss_val"]

    def forward(self, x):
        """x: [B, S, d_model] -> [B, S, d_model]."""
        from ...tensor import ops as T

        B, S, D = x.shape
        E = self.num_experts
        tokens = B * S
        cap = max(1, int(self.capacity_factor * tokens / E))
        xf = T.reshape(x, [tokens, D])

        logits = T.einsum("td,de->te", xf, self.router)
        probs = F.softmax(logits, axis=-1)                    # [T, E]
        expert_idx = T.argmax(probs, axis=-1)                 # [T]
        onehot = F.one_hot(expert_idx, E)                     # [T, E]
        gate = T.sum(probs * onehot, axis=-1)                 # [T]

        # position of each token within its expert's queue, in token
        # order; tokens past capacity get mask 0
        pos = T.cumsum(onehot, axis=0) * onehot               # [T, E]
        pos = T.sum(pos, axis=-1) - 1.0                       # [T]
        keep = (pos < float(cap)).astype("float32")
        pos_oh = F.one_hot(T.clip(pos, 0.0, float(cap - 1)).astype(
            "int64"), cap)                                    # [T, C]
        # dispatch[t, e, c] = 1 iff token t sits in slot c of expert e
        dispatch = T.einsum("te,tc->tec",
                            onehot * T.unsqueeze(keep, -1), pos_oh)
        combine = dispatch * T.unsqueeze(
            T.unsqueeze(gate, -1), -1)                        # [T, E, C]

        expert_in = T.einsum("tec,td->ecd", dispatch, xf)     # [E, C, D]
        h = T.einsum("ecd,edf->ecf", expert_in,
                     self.experts.weight_in)
        h = F.gelu(h) if self.act == "gelu" else F.relu(h)
        expert_out = T.einsum("ecf,efd->ecd", h,
                              self.experts.weight_out)        # [E, C, D]
        out = T.einsum("tec,ecd->td", combine, expert_out)

        # Switch load-balance aux loss: E * sum_e f_e * P_e, where f_e =
        # fraction of tokens routed to e, P_e = mean router prob of e
        f_e = T.mean(onehot, axis=0)
        p_e = T.mean(probs, axis=0)
        aux = T.sum(f_e * p_e) * float(E)
        self._buffers["aux_loss_val"]._data = aux._data  # jit channel
        try:
            from jax._src import core as _jc

            self._last_aux = aux if _jc.trace_state_clean() else None
        except Exception:
            self._last_aux = None

        return T.reshape(out, [B, S, D])


class _Router(Layer):
    """`gate.weight` [experts, d_model], kept float32 under a trainer's
    `compute_dtype`, and `gate.e_score_correction_bias` [experts]: a
    buffer added to the scores for the CHOICE of experts only."""

    def __init__(self, num_experts, d_model):
        super().__init__()
        import numpy as np

        import paddle_tpu.nn.initializer as I

        from ...core.tensor import Tensor
        from .layers import keep_float32

        self.weight = keep_float32(self.create_parameter(
            [num_experts, d_model], default_initializer=I.XavierUniform()))
        self.register_buffer("e_score_correction_bias",
                             Tensor(np.zeros((num_experts,), np.float32)))


class _SharedExpert(Layer):
    def __init__(self, d_model, d_ff, gated=False, dtype=None):
        super().__init__()
        from .common import Linear

        self.up_proj = Linear(d_model, d_ff, bias_attr=False)
        if gated:
            self.gate_proj = Linear(d_model, d_ff, bias_attr=False)
        self.down_proj = Linear(d_ff, d_model, bias_attr=False)
        if dtype is not None:
            for p in self.parameters():
                p._data = p._data.astype(dtype)

    def mix(self, x, act):
        """x [..., d_model] raw -> the expert's output, raw."""
        import jax.numpy as jnp

        f32 = jnp.float32
        pre = [jnp.dot(x, p.weight._data, preferred_element_type=f32)
               for p in ((self.gate_proj, self.up_proj)
                         if hasattr(self, "gate_proj")
                         else (self.up_proj,))]
        return act(*pre).astype(x.dtype) @ self.down_proj.weight._data


class SparseMoELayer(Layer):
    """Top-k sigmoid-routed experts plus a shared expert, holding a
    share of the routed experts and dropping no token.

        s = sigmoid(W_r u)                    over all `num_experts`, float32
        chosen = top_k(s + correction_bias)   the bias steers the choice only
        w = scaling * s[chosen] / sum(s[chosen])
        y = sum_{e chosen and held} w_e W_out[e] relu(W_in[e] u)^2
            + W_down relu(W_up u)^2           the shared expert, every token

    `activation="swiglu"` makes every expert, the shared one too,
    W_out (silu(W_gate u) * (W_in u)); `n_group` / `topk_group` limit the
    choice to the best groups of experts (`ops.moe.route_top_k`); `dtype`
    holds the matrices in that dtype from the start. `mix(x)` is the
    same mathematics over raw arrays, for a serving program: it returns
    the counters beside the result instead of leaving them in buffers.

    `experts_held = (first, count)` says which routed experts live here
    (default: all). The layer routes over all of them and leaves the
    terms of experts it does not hold out of `y`: another rank adds
    those. Every token-slot that falls on a held expert is computed,
    whatever the imbalance (`ops.moe.routed_experts`).

    Counters of the last forward ride non-persistable buffers, as
    `MoELayer.aux_loss_val` does: `routed_slots_val` (token-slots on held
    experts), `load_max_val`, `load_mean_val` (of a held expert),
    `dropped_slots_val` (held slots less the rows the experts' loops
    counted as they gathered them: 0) and `expert_load_val` [num_experts]
    (token-slots on EVERY expert, held or not: what a job that balances
    the correction bias steers by)."""

    COUNTERS = ("routed_slots_val", "load_max_val", "load_mean_val",
                "dropped_slots_val")

    def __init__(self, d_model, d_ff, num_experts, top_k, shared_d_ff=0,
                 routed_scaling=1.0, experts_held=None, activation="relu2",
                 n_group=1, topk_group=1, dtype=None):
        super().__init__()
        import numpy as np

        from ...core.tensor import Tensor
        from ...ops import moe

        if activation not in moe.ACTIVATIONS:
            raise ValueError(f"activation {activation!r}: one of "
                             f"{sorted(moe.ACTIVATIONS)}")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        if self.num_experts % self.n_group or \
                not 1 <= self.topk_group <= self.n_group or \
                self.topk_group * (self.num_experts // self.n_group) \
                < self.top_k:
            raise ValueError(
                f"{topk_group} of {n_group} groups over {num_experts} "
                f"experts cannot hold a token's {top_k}")
        self.activation = activation
        gated = moe.is_gated(activation)
        first, count = experts_held or (0, self.num_experts)
        if not 0 <= first <= first + count <= self.num_experts or count < 1:
            raise ValueError(f"experts_held {experts_held!r} is not a "
                             f"range of the {num_experts} experts")
        self.experts_held = (int(first), int(count))
        self.routed_scaling = float(routed_scaling)
        self.gate = _Router(self.num_experts, d_model)
        self.experts = _Experts(int(count), d_model, d_ff, gated, dtype)
        self.shared_experts = (
            _SharedExpert(d_model, shared_d_ff, gated, dtype)
            if shared_d_ff else None)
        for name in self.COUNTERS:
            self.register_buffer(name, Tensor(np.zeros((), np.float32)),
                                 persistable=False)
        self.register_buffer(
            "expert_load_val",
            Tensor(np.zeros((self.num_experts,), np.float32)),
            persistable=False)

    def _routed(self, xr, gate_w, bias, w_in, w_out, w_gate=None,
                valid=None):
        """The routed experts over raw arrays: xr [..., d_model] ->
        (their part of y, stats [4] float32 in `COUNTERS`' order, loads
        [num_experts] float32). `valid` [...] bool leaves the other
        tokens out of the routing, the experts' loops and the counts."""
        import jax
        import jax.numpy as jnp

        from ...ops import moe

        first, count = self.experts_held
        f32 = jnp.float32
        tokens = xr.reshape(-1, xr.shape[-1])
        if valid is not None:
            valid = valid.reshape(-1)
        with jax.named_scope("router"):
            scores = jax.nn.sigmoid(jnp.dot(
                tokens.astype(f32), gate_w.astype(f32).T,
                precision="highest"))
            idx, weights = moe.route_top_k(
                scores, bias.astype(f32), self.top_k, self.routed_scaling,
                self.n_group, self.topk_group)
            order, starts, counts = moe.plan_held(idx, first, count, valid)
        y, visited = moe.routed_experts(
            tokens, weights, w_in, w_out, order, starts, counts,
            w_gate=w_gate, activation=self.activation)
        held = counts.sum()
        stats = jnp.stack([
            held, counts.max(), held / count,
            held - visited.sum()]).astype(f32)
        chosen = idx[..., None] == jnp.arange(self.num_experts,
                                              dtype=jnp.int32)
        if valid is not None:
            chosen = chosen & valid[:, None, None]
        return y.reshape(xr.shape), stats, chosen.sum((0, 1)).astype(f32)

    def mix(self, x, valid=None):
        """x [..., d_model] raw -> (y raw, counts [4] int32: token-slots
        routed (`valid` tokens x top_k), those on held experts, the
        fullest held expert's, and held slots less the rows the experts'
        loops counted: 0)."""
        import jax.numpy as jnp

        from ...ops import moe

        e = self.experts
        y, stats, loads = self._routed(
            x, self.gate.weight._data,
            self.gate.e_score_correction_bias._data, e.weight_in._data,
            e.weight_out._data,
            e.weight_gate._data if hasattr(e, "weight_gate") else None,
            valid)
        if self.shared_experts is not None:
            y = y + self.shared_experts.mix(
                x, moe.ACTIVATIONS[self.activation][0])
        counts = jnp.stack([loads.sum(), stats[0], stats[1],
                            stats[3]]).astype(jnp.int32)
        return y, counts

    def forward(self, x):
        """x: [B, S, d_model] -> [B, S, d_model]."""
        from ...tensor.ops import _op

        e = self.experts
        gate = (e.weight_gate,) if hasattr(e, "weight_gate") else ()
        y, stats, loads = _op("sparse_moe", self._routed, x,
                              self.gate.weight,
                              self.gate.e_score_correction_bias,
                              e.weight_in, e.weight_out, *gate,
                              n_outputs=3)
        for i, name in enumerate(self.COUNTERS):
            self._buffers[name]._data = stats._data[i]
        self._buffers["expert_load_val"]._data = loads._data
        if self.shared_experts is not None:
            sh = self.shared_experts
            h = sh.up_proj(x)
            if gate:
                h = _op("swiglu", _swiglu, sh.gate_proj(x), h)
            else:
                h = _op("relu2", _relu2, h)
            y = y + sh.down_proj(h)
        return y


def _relu2(t):
    import jax.numpy as jnp

    from ...ops import moe

    return moe.relu2(t.astype(jnp.float32)).astype(t.dtype)


def _swiglu(g, u):
    import jax.numpy as jnp

    from ...ops import moe

    return moe.swiglu(g.astype(jnp.float32),
                      u.astype(jnp.float32)).astype(u.dtype)
