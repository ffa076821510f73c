"""A GPT-2 decoder behind ``serve.ServingEngine``: the entry ``/infer`` calls."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import adapters
from benchmark.reference import gpt2 as ref

TOP = {"wte.weight": "wte", "wpe.weight": "wpe", "ln_f.scale": "lnf_g",
       "ln_f.bias": "lnf_b"}


def _program_config(cfg: dict):
    from hetu_tpu.models import GPTConfig
    if cfg["n_inner"] != 4 * cfg["n_embd"]:
        raise ValueError("models/gpt.py fixes the feed-forward width at "
                         "four times the hidden size")
    return GPTConfig(vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
                     num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                     max_seq_len=cfg["n_positions"],
                     initializer_range=cfg["initializer_range"],
                     tie_embeddings=True, dtype=jnp.dtype(cfg["dtype"]))


class System:
    """The engine with its weights, started: ``submit`` is the timed entry."""

    def __init__(self, cfg: dict, engine: dict, seed: int):
        from hetu_tpu.models import GPT
        from hetu_tpu.serve import ServingEngine

        self.cfg = cfg
        pcfg = _program_config(cfg)
        skeleton = jax.eval_shape(lambda: GPT(pcfg))
        self._make = jax.jit(lambda key: adapters.fill(
            skeleton, ref.init_weights(cfg, key), TOP))
        kw = dict(engine)
        kw["prompt_buckets"] = tuple(kw["prompt_buckets"])
        # the engine's own seed is a constant of its compiled sampler, so
        # one from --seed would compile the decode program anew in every
        # run; the weights and the prompts come from --seed
        self.engine = ServingEngine(self._make(ref.C.seed_key(seed)),
                                    seed=0, **kw)

    def free(self):
        eng, self.engine = self.engine, None
        eng.stop()
        for x in jax.tree_util.tree_leaves((eng.model, eng.pool.k,
                                            eng.pool.v)):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()
