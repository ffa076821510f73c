"""AFMoE (Trinity) behind ``serve.ServingEngine``: the entry ``/infer``
calls.  The reference names its leaves by the dotted paths of the program's,
so the tree is filled leaf for leaf.

As for DeepSeek-V2 (``adapters.deepseek_v2``), the serving runner's counts
read this configuration as a dense decoder with one head count and one cache
layout, so what the family's readers (``benchmark.readers.afmoe``) need of
the run they find in ``SEEN``: the configuration, the engine's shapes, one
timestamped record of every device program the engine has collected
(``ServingEngine.on_program``), and, for the memory the traffic holds, a
timestamped reading of the pages held in each group of layers, taken when a
decode step is collected."""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from benchmark.adapters import deepseek_v2, kimi_linear
from benchmark.reference import afmoe as ref

SEEN = None    # of the last System built in this process


@dataclasses.dataclass
class Seen(deepseek_v2.Seen):
    """``programs`` as DeepSeek-V2's; ``held``: (time, {group: pages held})
    at every decode step collected; ``pool``: the cache by group as the
    engine built it (``stats()["cache"]``)."""
    held: list = dataclasses.field(default_factory=list)
    pool: dict = dataclasses.field(default_factory=dict)


def program_config(cfg: dict, **kw):
    from hetu_tpu.models import AfmoeConfig
    return AfmoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(ref.layer_types(cfg)),
        num_dense_layers=cfg["num_dense_layers"],
        intermediate_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"], rope_theta=cfg["rope_theta"],
        num_experts=ref.dims(cfg)["experts"],
        held_experts=tuple(ref.held_experts(cfg)),
        top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["num_shared_experts"],
        route_scale=cfg["route_scale"], mup_enabled=cfg["mup_enabled"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"],
        dtype=jnp.dtype(cfg["dtype"]), **kw)


def build_model(cfg: dict, seed: int, **kw):
    """The program's model with every leaf from the reference's seeded
    weights (made by the program that the serving runner makes its own copy
    with, so the compile cache holds it once; filling moves no data)."""
    from hetu_tpu.models import Afmoe
    pcfg = program_config(cfg, **kw)
    skeleton = jax.eval_shape(lambda: Afmoe(pcfg))
    weights = jax.jit(lambda k: ref.init_weights(cfg, k))(
        ref.C.seed_key(seed))
    return kimi_linear.fill(skeleton, weights)


class System:
    """The engine with its weights, started: ``submit`` is the timed entry."""

    def __init__(self, cfg: dict, engine: dict, seed: int):
        from hetu_tpu.serve import ServingEngine

        global SEEN
        self.cfg = cfg
        SEEN = self.seen = Seen(cfg, dict(engine))
        kw = dict(engine)
        kw["prompt_buckets"] = tuple(kw["prompt_buckets"])
        # the engine's own seed is a constant of its compiled sampler (as
        # in adapters.deepseek_v2): the weights and prompts come from --seed
        self.engine = ServingEngine(build_model(cfg, seed), seed=0, **kw)
        self.seen.pool = self.engine.pool.cache_stats()
        self.engine.on_program = self._ran

    def _ran(self, kind: str, info: dict):
        now = time.perf_counter()
        self.seen.programs.append((now, kind, info))
        if kind == "decode":    # under the engine's lock: the pool is still
            self.seen.held.append((now, {
                name: g.num_pages - 1 - g.free_pages
                for name, g in self.engine.pool.by_group().items()}))

    def free(self):
        """The model and both groups' arrays deleted, so that the reference
        starts on an empty chip."""
        eng, self.engine = self.engine, None
        eng.stop()
        for x in jax.tree_util.tree_leaves((eng.model, eng.pool.arrays)):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()
