"""Kimi-Linear pre-training through ``exec.Trainer``: the path a user
configures.  The reference names its leaves by the dotted paths of the
program's, so the tree is filled leaf for leaf.

The training runner keeps a step's loss and nothing else, and its ``facts``
carry nothing of an adapter's.  So the routing counts that a step's metrics
carry go through the program's counters here (``hetu_tpu.obs.
record_routing``): three steps late, when the runner has long waited for
that step, so that reading them waits for nothing.  And what the family's
readers (``benchmark.readers.kimi_linear``) need of the run, they find in
``SEEN``: the configuration the system was built from, the shape of its
batches, and each recorded step's counts with the time it was
dispatched."""

from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp

from benchmark import adapters
from benchmark.adapters import bert
from benchmark.reference import kimi_linear as ref
from hetu_tpu.models import KimiLinear


@dataclasses.dataclass
class Seen:
    """What a ``System`` ran, as its family's readers need it."""
    cfg: dict                  # the configuration's file
    batch: tuple = None        # (rows, tokens a row) of the last step
    routing: list = dataclasses.field(default_factory=list)
    # (time the step was dispatched, its routing counts as host numbers),
    # every recorded step, in order


SEEN = None    # of the last System built in this process
LATE = 3       # the runner waits for step n - 2 before it dispatches n + 1


def _program_config(cfg: dict):
    from hetu_tpu.models import KimiLinearConfig
    la = cfg["linear_attn_config"]
    return KimiLinearConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        kda_layers=tuple(la["kda_layers"]),
        full_attn_layers=tuple(la["full_attn_layers"]),
        first_k_dense=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        kda_num_heads=la["num_heads"], kda_head_dim=la["head_dim"],
        conv_size=la["short_conv_kernel_size"],
        kda_gate_rank=cfg["kda_gate_rank"],
        num_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        num_experts=cfg["num_experts_published"],
        held_experts=tuple(cfg["held_experts"]),
        top_k=cfg["num_experts_per_token"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["num_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        conv_initializer_range=cfg["conv_initializer_range"],
        remat="full", dtype=jnp.dtype(cfg["dtype"]))


def _loss_fn(model, b, key):
    return model.loss(b["input_ids"], b["labels"])


def fill(skeleton, weights: dict):
    """The program's model tree with every leaf taken from ``weights``."""
    flat, treedef = jax.tree_util.tree_flatten(skeleton)
    paths = adapters.leaf_paths(skeleton)
    if set(paths) != set(weights):
        raise ValueError(f"leaves differ: {sorted(set(paths) ^ set(weights))}")
    leaves = []
    for path, like in zip(paths, flat):
        leaf = weights[path]
        if leaf.shape != like.shape or leaf.dtype != like.dtype:
            raise ValueError(f"{path}: the program holds {like.dtype}"
                             f"{like.shape}, the reference made "
                             f"{leaf.dtype}{leaf.shape}")
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


class System(bert.System):
    """The compiled step with its state: built once, checked on its first
    steps, then handed to the window.  What it does with the trainer once
    built (the step, the norms read from Adam's first moment, the change
    against the seed's weights, freeing) is ``adapters.bert.System``'s."""

    def __init__(self, cfg: dict, opt: dict, seed: int):
        from hetu_tpu.exec import Trainer
        from hetu_tpu.optim import AdamWOptimizer

        global SEEN
        self.cfg, self.opt = cfg, opt
        SEEN = self.seen = Seen(cfg)
        pcfg = _program_config(cfg)
        skeleton = jax.eval_shape(lambda: KimiLinear(pcfg))
        self._make = jax.jit(lambda key: fill(
            skeleton, ref.init_weights(cfg, key)))
        kinds = {n: kind for n, (_, kind) in ref.shapes(cfg).items()}
        self.trained = [n for n in kinds if kinds[n] != "state"]
        model = self._make(ref.C.seed_key(seed))
        self.trainer = Trainer(
            model, AdamWOptimizer(opt["learning_rate"], beta1=opt["beta1"],
                                  beta2=opt["beta2"], eps=opt["eps"],
                                  weight_decay=opt["weight_decay"],
                                  decay_min_ndim=opt["decay_min_ndim"]),
            _loss_fn)
        self._norms = jax.jit(self._leaf_norms)
        self._diff = jax.jit(lambda a, b: self._leaf_norms(
            jax.tree_util.tree_map(
                lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                a, b)))
        self._unrecorded = collections.deque()

    def step(self, batch: dict, key):
        """``Trainer.step`` on a host batch: what the window calls."""
        while len(self._unrecorded) >= LATE:
            self._record(*self._unrecorded.popleft())
        self.seen.batch = tuple(batch["input_ids"].shape)
        at = time.perf_counter()
        metrics = super().step(batch, key)
        self._unrecorded.append((at, metrics))
        return metrics

    def _record(self, at, metrics):
        from hetu_tpu.obs import record_routing
        counts = record_routing(metrics)
        if counts:
            self.seen.routing.append((at, counts))

    def free(self):
        while self._unrecorded:
            self._record(*self._unrecorded.popleft())
        super().free()

    def _leaf_norms(self, tree) -> dict:
        leaves = dict(zip(adapters.leaf_paths(tree),
                          jax.tree_util.tree_leaves(tree)))
        return ref._leaf_norms({n: leaves[n] for n in self.trained})
