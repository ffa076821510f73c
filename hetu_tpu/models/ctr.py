"""CTR model family: Wide&Deep, DeepFM, DCN (+ cross network).

TPU-native re-designs of the reference CTR examples
(examples/ctr/models/{wdl_criteo.py,wdl_adult.py,deepfm_criteo.py,
dcn_criteo.py}): criteo layout of 13 dense + 26 categorical fields embedded
into a shared id space, a deep MLP tower, and the model-specific parts —
W&D's wide concat, DeepFM's factorization-machine second-order term, DCN's
cross layers.

The embedding is pluggable: ``embedding="device"`` keeps the table on-chip
(pure XLA gather); ``embedding="host"`` uses the HET engine
(hetu_tpu/embed — host table + cache + server-side optimizer), matching the
reference's Hybrid mode where embeddings always route through the PS
(executor.py:276-283) while dense params train on-chip.
"""

from __future__ import annotations

import jax.numpy as jnp

from hetu_tpu.core.module import Module
from hetu_tpu.core.rng import next_key
from hetu_tpu.embed import (HBMCachedEmbedding, HostEmbedding,
                            StagedHostEmbedding)
from hetu_tpu.init import normal
from hetu_tpu.layers import Embedding, Linear, MLPTower
from hetu_tpu.ops import binary_cross_entropy_with_logits, relu, sigmoid

__all__ = ["CTRConfig", "WideDeep", "DeepFM", "DCN", "DeepCrossing",
           "make_embedding"]


class CTRConfig:
    """Criteo-shaped feature layout (reference examples/ctr/load_data.py)."""

    def __init__(self, dense_dim: int = 13, sparse_fields: int = 26,
                 vocab: int = 26000, embed_dim: int = 16,
                 mlp_hidden: int = 256, embedding: str = "device",
                 host_optimizer: str = "sgd", host_lr: float = 0.01,
                 cache_capacity: int = 0, cache_policy: str = "lru",
                 pull_bound: int = 0, push_bound: int = 0,
                 host_bridge: str = "auto", host_async_push: bool = False,
                 servers=None, reconnect_attempts: int = 0,
                 restore_path: str | None = None, storage: str = "f32",
                 host_cache_capacity: int | None = None,
                 promote_touches: int = 2, demote_idle: int = 0):
        self.dense_dim = dense_dim
        self.sparse_fields = sparse_fields
        self.vocab = vocab
        self.embed_dim = embed_dim
        self.mlp_hidden = mlp_hidden
        self.embedding = embedding
        self.host_optimizer = host_optimizer
        self.host_lr = host_lr
        self.cache_capacity = cache_capacity
        self.cache_policy = cache_policy
        self.pull_bound = pull_bound
        self.push_bound = push_bound
        # PS storage form ("f32" | "int8" — the quantized PS tier) for the
        # host-engine embedding modes; tier policy knobs apply to
        # embedding="tiered" (cache_capacity = the HBM row budget there,
        # host_cache_capacity = the host HET-cache width, default 4x)
        if storage not in ("f32", "int8"):
            raise ValueError(f"unknown storage {storage!r}: 'f32' or 'int8'")
        if storage != "f32" and embedding in ("device", "remote"):
            raise ValueError(
                'storage="int8" is the host-PS storage knob: it needs a '
                'host-engine embedding ("host" | "hbm" | "tiered")')
        self.storage = storage
        self.host_cache_capacity = host_cache_capacity
        self.promote_touches = promote_touches
        self.demote_idle = demote_idle
        # "callback" = io_callback bridge inside jit; "staged" = pull/push
        # outside jit (works on backends without host callbacks); "auto"
        # probes the backend (embed.bridge.host_callbacks_supported).
        self.host_bridge = host_bridge
        # ASP-style pushes off the step's critical path (reference PS
        # default bsp=-1, executor.py:203); staged bridge only
        self.host_async_push = host_async_push
        self.servers = list(servers) if servers else []  # embedding="remote"
        # PS fault tolerance (embedding="remote", uncached): reconnect
        # with bounded backoff + checkpoint restore on server restart
        # (embed.net.RemoteEmbeddingTable)
        if restore_path is not None and reconnect_attempts <= 0:
            raise ValueError(
                "restore_path only takes effect during a reconnect — set "
                "reconnect_attempts > 0 or the checkpoint would silently "
                "never be restored after a PS restart")
        if reconnect_attempts > 0 and embedding != "remote":
            raise ValueError(
                'reconnect_attempts is the network-PS fault-tolerance '
                'knob: it needs embedding="remote"')
        self.reconnect_attempts = reconnect_attempts
        self.restore_path = restore_path


def make_embedding(cfg: CTRConfig, dim: int | None = None, seed: int = 0):
    dim = dim if dim is not None else cfg.embed_dim
    if cfg.embedding == "remote":
        # key-partitioned across network PS servers (reference multi-server
        # deployment; servers spawned by heturun or embed.net standalone)
        from hetu_tpu.embed.net import RemoteHostEmbedding
        if not cfg.servers:
            raise ValueError('embedding="remote" needs CTRConfig.servers')
        return RemoteHostEmbedding(
            cfg.vocab, dim, servers=cfg.servers,
            optimizer=cfg.host_optimizer, lr=cfg.host_lr, seed=seed,
            cache_capacity=cfg.cache_capacity, policy=cfg.cache_policy,
            pull_bound=cfg.pull_bound, push_bound=cfg.push_bound,
            reconnect_attempts=cfg.reconnect_attempts,
            restore_path=cfg.restore_path)
    if cfg.embedding == "tiered":
        # the full production hierarchy: HBM hot rows over the host HET
        # cache over the (optionally int8-quantized) PS table, with
        # touch-frequency promotion/demotion (embed.tier)
        from hetu_tpu.embed import TieredEmbedding, TierPolicy
        if cfg.cache_capacity <= 0:
            raise ValueError('embedding="tiered" needs cache_capacity > 0 '
                             "(the HBM-resident row budget)")
        return TieredEmbedding(
            cfg.vocab, dim, hbm_capacity=cfg.cache_capacity,
            host_capacity=cfg.host_cache_capacity,
            policy=TierPolicy(promote_touches=cfg.promote_touches,
                              demote_idle=cfg.demote_idle),
            hbm_pull_bound=cfg.pull_bound, host_pull_bound=cfg.pull_bound,
            storage=cfg.storage, cache_policy=cfg.cache_policy,
            push_bound=cfg.push_bound, optimizer=cfg.host_optimizer,
            lr=cfg.host_lr, seed=seed)
    if cfg.embedding == "hbm":
        # host store + hot rows staged into device HBM (the north-star
        # layout; warm steps transfer only refreshed rows).  The device
        # cache is LRU; cache_policy/push_bound apply to the host paths
        # only.
        if cfg.cache_capacity <= 0:
            raise ValueError('embedding="hbm" needs cache_capacity > 0 '
                             "(the HBM-resident row budget)")
        return HBMCachedEmbedding(
            cfg.vocab, dim, optimizer=cfg.host_optimizer, lr=cfg.host_lr,
            seed=seed, hbm_capacity=cfg.cache_capacity,
            hbm_pull_bound=cfg.pull_bound, storage=cfg.storage)
    if cfg.embedding == "host":
        bridge = cfg.host_bridge
        if bridge == "auto":
            from hetu_tpu.embed.bridge import host_callbacks_supported
            bridge = "callback" if host_callbacks_supported() else "staged"
        cls = StagedHostEmbedding if bridge == "staged" else HostEmbedding
        kw = dict(optimizer=cfg.host_optimizer, lr=cfg.host_lr, seed=seed,
                  cache_capacity=cfg.cache_capacity,
                  policy=cfg.cache_policy, pull_bound=cfg.pull_bound,
                  push_bound=cfg.push_bound, storage=cfg.storage)
        if cls is StagedHostEmbedding:
            kw["async_push"] = cfg.host_async_push
        elif cfg.host_async_push:
            # the callback bridge pushes inside the jitted step; silently
            # ignoring the ASP request would change staleness semantics
            # per backend
            raise ValueError(
                "host_async_push requires the staged bridge "
                '(host_bridge="staged"); the callback bridge resolved here '
                "pushes inside the step")
        return cls(cfg.vocab, dim, **kw)
    return Embedding(cfg.vocab, dim)


class _DeepTower(MLPTower):
    """relu MLP tower (the shared DNN of all three models) — the constant-
    hidden special case of layers.MLPTower, last layer unactivated."""

    def __init__(self, in_dim: int, hidden: int, out_dim: int, depth: int = 3):
        super().__init__([in_dim] + [hidden] * (depth - 1) + [out_dim],
                         final_relu=False)


class WideDeep(Module):
    """Wide&Deep (reference wdl_criteo.py:8): deep tower on dense features,
    concat with flattened embeddings, linear head."""

    def __init__(self, cfg: CTRConfig):
        self.cfg = cfg
        self.embed = make_embedding(cfg)
        self.deep = _DeepTower(cfg.dense_dim, cfg.mlp_hidden, cfg.mlp_hidden)
        self.head = Linear(
            cfg.mlp_hidden + cfg.sparse_fields * cfg.embed_dim, 1)

    def logits(self, dense, sparse):
        emb = self.embed(sparse).reshape(dense.shape[0], -1)
        deep = self.deep(dense)
        return self.head(jnp.concatenate([emb, deep], axis=1))[:, 0]

    def loss(self, dense, sparse, label):
        logits = self.logits(dense, sparse)
        loss = binary_cross_entropy_with_logits(logits, label).mean()
        return loss, {"pred": sigmoid(logits)}


class DeepFM(Module):
    """DeepFM (reference deepfm_criteo.py): first-order embedding +
    FM second-order interaction + deep tower over flattened embeddings."""

    def __init__(self, cfg: CTRConfig):
        self.cfg = cfg
        self.embed = make_embedding(cfg)                 # second-order (k-dim)
        self.embed1 = make_embedding(cfg, dim=1, seed=1)  # first-order
        self.deep = _DeepTower(
            cfg.sparse_fields * cfg.embed_dim, cfg.mlp_hidden, 1)
        self.bias = jnp.zeros((1,), jnp.float32)

    def logits(self, dense, sparse):
        v = self.embed(sparse)                       # (b, fields, k)
        first = self.embed1(sparse)[..., 0].sum(1)   # (b,)
        # FM: 0.5 * ((sum_f v)^2 - sum_f v^2), summed over k
        s = v.sum(axis=1)
        second = 0.5 * ((s * s).sum(-1) - (v * v).sum(axis=(1, 2)))
        deep = self.deep(v.reshape(v.shape[0], -1))[:, 0]
        return first + second + deep + self.bias[0]

    def loss(self, dense, sparse, label):
        logits = self.logits(dense, sparse)
        loss = binary_cross_entropy_with_logits(logits, label).mean()
        return loss, {"pred": sigmoid(logits)}


class _ResidualUnit(Module):
    """DeepCrossing residual unit (reference dc_criteo.py residual_layer):
    relu(x + W2 relu(W1 x + b1) + b2)."""

    def __init__(self, dim: int, hidden: int):
        self.fc1 = Linear(dim, hidden, initializer=normal(stddev=0.1))
        self.fc2 = Linear(hidden, dim, initializer=normal(stddev=0.1))

    def __call__(self, x):
        return relu(x + self.fc2(relu(self.fc1(x))))


class DeepCrossing(Module):
    """DeepCrossing (reference examples/ctr/models/dc_criteo.py): stacked
    residual units over [embeddings ++ dense], linear scoring head."""

    def __init__(self, cfg: CTRConfig, num_residual: int = 3,
                 residual_hidden: int | None = None):
        self.cfg = cfg
        self.embed = make_embedding(cfg)
        in_dim = cfg.sparse_fields * cfg.embed_dim + cfg.dense_dim
        hidden = residual_hidden if residual_hidden is not None else cfg.mlp_hidden
        self.residuals = [_ResidualUnit(in_dim, hidden)
                          for _ in range(num_residual)]
        self.head = Linear(in_dim, 1, initializer=normal(stddev=0.1))

    def logits(self, dense, sparse):
        emb = self.embed(sparse).reshape(dense.shape[0], -1)
        x = jnp.concatenate([emb, dense], axis=1)
        for unit in self.residuals:
            x = unit(x)
        return self.head(x)[:, 0]

    def loss(self, dense, sparse, label):
        logits = self.logits(dense, sparse)
        loss = binary_cross_entropy_with_logits(logits, label).mean()
        return loss, {"pred": sigmoid(logits)}


class CrossLayer(Module):
    """One DCN cross layer (reference dcn_criteo.py:8 cross_layer):
    y = x0 * (x1 @ w) + b + x1."""

    def __init__(self, dim: int):
        init = normal(stddev=0.01)
        self.w = init(next_key(), (dim, 1), jnp.float32)
        self.b = init(next_key(), (dim,), jnp.float32)

    def __call__(self, x0, x1):
        x1w = x1 @ self.w              # (b, 1)
        return x0 * x1w + self.b + x1


class DCN(Module):
    """Deep&Cross (reference dcn_criteo.py:28): cross network + deep tower
    over [embeddings ++ dense], concatenated into the head."""

    def __init__(self, cfg: CTRConfig, num_cross: int = 3):
        self.cfg = cfg
        self.embed = make_embedding(cfg)
        in_dim = cfg.sparse_fields * cfg.embed_dim + cfg.dense_dim
        self.cross = [CrossLayer(in_dim) for _ in range(num_cross)]
        self.deep = _DeepTower(in_dim, cfg.mlp_hidden, cfg.mlp_hidden)
        self.head = Linear(in_dim + cfg.mlp_hidden, 1)

    def logits(self, dense, sparse):
        emb = self.embed(sparse).reshape(dense.shape[0], -1)
        x0 = jnp.concatenate([emb, dense], axis=1)
        x1 = x0
        for layer in self.cross:
            x1 = layer(x0, x1)
        deep = self.deep(x0)
        return self.head(jnp.concatenate([x1, deep], axis=1))[:, 0]

    def loss(self, dense, sparse, label):
        logits = self.logits(dense, sparse)
        loss = binary_cross_entropy_with_logits(logits, label).mean()
        return loss, {"pred": sigmoid(logits)}
