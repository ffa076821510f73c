"""BERT pre-training through ``exec.Trainer``: the path a user configures."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import adapters
from benchmark.reference import bert as ref

TOP = {
    "bert.embeddings.word.weight": "word",
    "bert.embeddings.position.weight": "pos",
    "bert.embeddings.token_type.weight": "type",
    "bert.embeddings.ln.scale": "emb_ln_g",
    "bert.embeddings.ln.bias": "emb_ln_b",
    "bert.pooler.w": "pool_w", "bert.pooler.b": "pool_b",
    "heads.transform.w": "tr_w", "heads.transform.b": "tr_b",
    "heads.transform_ln.scale": "tr_ln_g",
    "heads.transform_ln.bias": "tr_ln_b",
    "heads.decoder_bias": "dec_b", "heads.nsp.w": "nsp_w",
    "heads.nsp.b": "nsp_b",
}


def _program_config(cfg: dict):
    from hetu_tpu.models import BertConfig
    if cfg["intermediate_size"] % cfg["hidden_size"]:
        raise ValueError("models/bert.py takes the feed-forward width as a "
                         "whole multiple of the hidden size")
    return BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_ratio=cfg["intermediate_size"] // cfg["hidden_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        dropout_rate=cfg["hidden_dropout_prob"],
        initializer_range=cfg["initializer_range"],
        dtype=jnp.dtype(cfg["dtype"]))


def _loss_fn(model, b, key):
    loss, _ = model.loss(b["input_ids"], b["token_type"], None,
                         b["mlm_labels"], b["nsp_labels"], key=key,
                         training=True)
    return loss, {}


class System:
    """The compiled step with its state: built once, checked on its first
    steps, then handed to the window."""

    def __init__(self, cfg: dict, opt: dict, seed: int):
        from hetu_tpu.exec import Trainer
        from hetu_tpu.models import BertForPreTraining
        from hetu_tpu.ops.pallas import flash_attn_fn
        from hetu_tpu.optim import AdamWOptimizer

        self.cfg, self.opt = cfg, opt
        pcfg = _program_config(cfg)
        attn = (flash_attn_fn(native_layout=True)
                if opt.get("attention", "flash") == "flash" else None)
        skeleton = jax.eval_shape(
            lambda: BertForPreTraining(pcfg, attn_fn=attn))
        self._make = jax.jit(lambda key: adapters.fill(
            skeleton, ref.init_weights(cfg, key), TOP))
        model = self._make(ref.C.seed_key(seed))
        self.trainer = Trainer(
            model, AdamWOptimizer(opt["learning_rate"], beta1=opt["beta1"],
                                  beta2=opt["beta2"], eps=opt["eps"],
                                  weight_decay=opt["weight_decay"]),
            _loss_fn)
        self._norms = jax.jit(lambda tree: _norms(tree))
        self._diff = jax.jit(lambda a, b: _norms(jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b)))

    def step(self, batch: dict, key):
        """``Trainer.step`` on a host batch: what the window calls."""
        return self.trainer.step(
            {k: jnp.asarray(v) for k, v in batch.items()}, key=key)

    def first_gradient_norms(self) -> dict:
        """After exactly one step Adam's first moment is (1 - beta1) times
        the gradient that the optimizer got: its norms by leaf."""
        m = self._norms(self.trainer.state.opt_state["m"])
        return {n: v / (1.0 - self.opt["beta1"]) for n, v in m.items()}

    def change_norms(self, seed: int) -> dict:
        """Norms by leaf of (parameters now - parameters from the seed)."""
        start = self._make(ref.C.seed_key(seed))
        return self._diff(self.trainer.state.model, start)

    def state_leaves(self):
        return jax.tree_util.tree_leaves(self.trainer.state)

    def free(self):
        for x in self.state_leaves():
            if isinstance(x, jax.Array):
                x.delete()
        self.trainer = None


def _norms(tree) -> dict:
    """Norms by leaf under the reference's names and in its layout: one a
    layer, and one a layer and part for the fused query-key-value leaves."""
    out = {}
    for name, v in adapters.gather(tree, TOP).items():
        if name in ref.FUSED:
            sq = lambda a: jnp.sum(jnp.square(a.astype(jnp.float32)).reshape(
                -1, 3, a.shape[-1] // 3), axis=(0, 2))
        else:
            sq = lambda a: jnp.sum(jnp.square(a.astype(jnp.float32)))
        out[name] = (jnp.sqrt(jnp.stack([sq(a) for a in v]))
                     if isinstance(v, list) else jnp.sqrt(sq(v)))
    return out
