"""DeepSeek-V2 behind ``serve.ServingEngine``: the entry ``/infer`` calls.
The reference names its leaves by the dotted paths of the program's, so the
tree is filled leaf for leaf.

The serving runner's ``facts`` carry neither the prompt lengths nor the
contexts of what the window served, and its counts read this configuration
as a dense decoder.  So what the family's readers
(``benchmark.readers.deepseek_v2``) need of the run they find in ``SEEN``:
the configuration the system was built from, the engine's shapes, and one
timestamped record of every device program whose results the engine has
collected (``ServingEngine.on_program``: a prefill with its prompt length,
a decode step with its rows and the cached tokens they attended over, each
with the routing counts that the program's expert layers counted)."""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from benchmark.adapters import kimi_linear
from benchmark.reference import deepseek_v2 as ref


@dataclasses.dataclass
class Seen:
    """What a ``System`` ran, as its family's readers need it."""
    cfg: dict                  # the configuration's file
    engine: dict               # the cell's engine shapes
    programs: list = dataclasses.field(default_factory=list)
    # (time collected, "prefill" | "decode", what on_program was told)


SEEN = None    # of the last System built in this process


def program_config(cfg: dict, **kw):
    from hetu_tpu.models import DeepseekV2Config
    rs = cfg["rope_scaling"]
    return DeepseekV2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], num_experts=cfg["n_routed_experts"],
        held_experts=tuple(ref.held_experts(cfg)),
        top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rope_factor=rs["factor"],
        rope_original_max_position=rs["original_max_position_embeddings"],
        rope_beta_fast=rs["beta_fast"], rope_beta_slow=rs["beta_slow"],
        rope_mscale=rs["mscale"], rope_mscale_all_dim=rs["mscale_all_dim"],
        initializer_range=cfg["initializer_range"],
        dtype=jnp.dtype(cfg["dtype"]), **kw)


def build_model(cfg: dict, seed: int, **kw):
    """The program's model with every leaf from the reference's seeded
    weights.  The weights are made by the program that the serving runner
    makes its own copy with (``served_gaps``: the same function of the same
    configuration), so the compile cache holds that program once; filling
    the tree moves no data."""
    from hetu_tpu.models import DeepseekV2
    pcfg = program_config(cfg, **kw)
    skeleton = jax.eval_shape(lambda: DeepseekV2(pcfg))
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
        # the engine's own seed is a constant of its compiled sampler, so
        # one from --seed would compile the decode program anew in every
        # run; the weights and the prompts come from --seed
        self.engine = ServingEngine(build_model(cfg, seed), seed=0, **kw)
        self.engine.on_program = self._ran

    def _ran(self, kind: str, info: dict):
        self.seen.programs.append((time.perf_counter(), kind, info))

    def free(self):
        """The model and the pool deleted, so that the reference starts on
        an empty chip."""
        eng, self.engine = self.engine, None
        eng.stop()
        for x in jax.tree_util.tree_leaves((eng.model, eng.pool.arrays)):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()
