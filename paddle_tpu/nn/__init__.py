"""paddle.nn parity surface (python/paddle/nn/__init__.py in the reference).
"""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .layer.layers import Layer, ParamAttr, Parameter  # noqa: F401
from .layer.common import (  # noqa: F401
    AdaptiveAvgPool2D, AdaptiveMaxPool2D, AlphaDropout, AvgPool1D, AvgPool2D,
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, BCELoss,
    BCEWithLogitsLoss, Bilinear, Conv1D, Conv2D, Conv2DTranspose,
    CosineSimilarity, CrossEntropyLoss, Dropout, Dropout2D, ELU, Embedding,
    Flatten, GELU, GroupNorm, Hardshrink, Hardsigmoid, Hardswish, Hardtanh,
    Identity, InstanceNorm2D, KLDivLoss, L1Loss, LayerDict, LayerList,
    LayerNorm, LeakyReLU, Linear, LocalResponseNorm, LogSigmoid, LogSoftmax,
    MarginRankingLoss, Maxout, MaxPool1D, MaxPool2D, Mish, MSELoss, NLLLoss,
    Pad2D, ParameterList, PixelShuffle, PReLU, ReLU, ReLU6, RMSNorm, SELU,
    Sequential, Sigmoid, Silu, SmoothL1Loss, Softmax, Softplus, Softshrink,
    Softsign, Swish, SyncBatchNorm, Tanh, Tanhshrink, Unfold, Upsample,
    UpsamplingBilinear2D, UpsamplingNearest2D)
from .layer.mla import LatentAttention  # noqa: F401
from .layer.moe import MoELayer, SparseMoELayer  # noqa: F401
from .layer.ssm import Mamba1Mixer, Mamba2Mixer  # noqa: F401
from .layer.diff_attention import (  # noqa: F401
    DifferentialAttention, GatedMemoryUnit)
from .layer.transformer import (  # noqa: F401
    GroupedQueryAttention, MultiHeadAttention, Transformer, TransformerDecoder,
    TransformerDecoderLayer, TransformerEncoder, TransformerEncoderLayer)
from .layer.rnn import (  # noqa: F401
    GRU, GRUCell, LSTM, LSTMCell, RNN, RNNCellBase, SimpleRNN, SimpleRNNCell)


_CLIP_GLOBAL_JIT = None


def _clip_global_jit():
    """One jitted computation for the whole grad list: fp32-accumulated
    global norm + every rescale in a single dispatch (vs the historical
    N+1 eager reductions and N scale-multiplies). The clip norm rides in
    as a traced scalar so every ClipGradByGlobalNorm instance shares the
    same compile-cache entry per grad-list signature."""
    global _CLIP_GLOBAL_JIT
    if _CLIP_GLOBAL_JIT is None:
        import jax
        import jax.numpy as jnp

        def clip(grads, clip_norm):
            gnorm = jnp.sqrt(sum((g.astype(jnp.float32) ** 2).sum()
                                 for g in grads))
            scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm,
                                                             1e-12))
            return tuple((g * scale).astype(g.dtype) for g in grads)

        _CLIP_GLOBAL_JIT = jax.jit(clip)
    return _CLIP_GLOBAL_JIT


class ClipGradByGlobalNorm:
    """nn.ClipGradByGlobalNorm parity (fluid/clip.py GradientClipByGlobalNorm).

    This legacy per-param path remains behind the fused optimizer step
    (the sparse fallback, user code calling the clip directly); the fused
    step folds the same math into its single dispatch instead."""

    def __init__(self, clip_norm=1.0):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        from ..core.tensor import Tensor

        grads = [g for _, g in params_grads if g is not None]
        if not grads:
            return params_grads
        scaled = iter(_clip_global_jit()(
            tuple(g._data for g in grads), self.clip_norm))
        return [(p, g if g is None else Tensor._wrap(next(scaled)))
                for p, g in params_grads]


class ClipGradByNorm:
    def __init__(self, clip_norm=1.0):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        from ..ops import kernels as K

        out = []
        for p, g in params_grads:
            if g is None:
                out.append((p, g))
            else:
                out.append((p, Tensor._wrap(K.clip_by_norm(g._data,
                                                           self.clip_norm))))
        return out


class ClipGradByValue:
    def __init__(self, max, min=None):
        self.max = float(max)
        self.min = float(min) if min is not None else -self.max

    def __call__(self, params_grads):
        import jax.numpy as jnp

        from ..core.tensor import Tensor

        out = []
        for p, g in params_grads:
            if g is None:
                out.append((p, g))
            else:
                out.append((p, Tensor._wrap(jnp.clip(g._data, self.min,
                                                     self.max))))
        return out


def utils_spectral_norm(*a, **k):
    raise NotImplementedError
