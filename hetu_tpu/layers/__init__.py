from hetu_tpu.layers.base import Identity, Lambda, Sequential
from hetu_tpu.layers.cache import CacheSpec, GroupedCacheSpec
from hetu_tpu.layers.linear import Embedding, Linear, MLPTower
from hetu_tpu.layers.conv import AvgPool2d, Conv2d, Flatten, MaxPool2d
from hetu_tpu.layers.norm import (
    BatchNorm2d,
    Dropout,
    GroupNorm,
    InstanceNorm2d,
    LayerNorm,
    RMSNorm,
)
from hetu_tpu.layers.attention import (
    GroupedQueryAttention,
    MultiHeadAttention,
    PagedDecode,
    decode_attention,
    dot_product_attention,
    ragged_cache_update,
    rotate_halves,
)
from hetu_tpu.layers.transformer import SwiGLU, TransformerBlock, TransformerMLP
from hetu_tpu.layers.kda import KimiDeltaAttention, causal_depthwise_conv
from hetu_tpu.layers.mla import (MultiHeadLatentAttention, YarnRope,
                                 rotate_pairs)
from hetu_tpu.layers.moe import (
    BalanceGate,
    ExpertMLP,
    HashGate,
    HeldExpertsMoE,
    KTop1Gate,
    MoELayer,
    SAMGate,
    SigmoidRouter,
    SoftmaxRouter,
    TopKGate,
    moe_transformer_mlp,
)
