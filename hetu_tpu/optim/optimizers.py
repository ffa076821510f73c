"""Optimizers.

TPU-native equivalents of the reference optimizer family
(reference: python/hetu/optimizer.py — SGDUpdateOp:203, MomentumUpdateOp:289,
AdaGradUpdateOp:335, AdamUpdateOp:462, AdamWUpdateOp:629, LambUpdateOp:686,
plus sparse variants e.g. AdamSparseUpdateOp:553; CUDA kernels
src/ops/Optimizers.cu, OptimizersSparse.cu).

Design: each optimizer is a pure pytree transform —
``init(params) -> state`` and ``update(grads, state, params) ->
(new_params, new_state)`` — so the whole update jits into the train step and
shards with the params (ZeRO partitioning is just a sharding rule on the
state pytree, hetu_tpu/parallel/zero.py).  Learning rates may be floats or
schedules (step -> lr callables, hetu_tpu/optim/schedulers.py).

Sparse semantics: ``IndexedSlices`` gradients (embedding rows) are applied
row-wise, matching the reference's *lazy* sparse updates (only touched rows'
moments advance — optimizer.py:553 AdamSparse).  Dense pytrees and pytrees
containing IndexedSlices leaves both work.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Union

import jax
import jax.numpy as jnp

from hetu_tpu.ops.sparse import IndexedSlices

__all__ = [
    "Optimizer", "SGDOptimizer", "MomentumOptimizer", "AdaGradOptimizer",
    "AdamOptimizer", "AdamWOptimizer", "LambOptimizer",
    "global_norm", "clip_by_global_norm", "clip_by_value",
]

ScheduleOrFloat = Union[float, Callable[[Any], Any]]


def _lr_at(lr: ScheduleOrFloat, step):
    return lr(step) if callable(lr) else lr


def _is_leaf(x):
    return isinstance(x, IndexedSlices)


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees, is_leaf=_is_leaf)


def _grad_sq_sum(g):
    if isinstance(g, IndexedSlices):
        return jnp.sum(jnp.square(g.values.astype(jnp.float32)))
    return jnp.sum(jnp.square(g.astype(jnp.float32)))


def global_norm(grads):
    """L2 norm over the whole gradient pytree (IndexedSlices counted by
    their values; None leaves skipped)."""
    leaves = [g for g in jax.tree_util.tree_leaves(grads, is_leaf=_is_leaf)
              if g is not None]
    return jnp.sqrt(sum(_grad_sq_sum(g) for g in leaves))


def _scale_grad(g, s):
    if isinstance(g, IndexedSlices):
        return dataclasses.replace(g, values=g.values * s.astype(g.values.dtype))
    return g * s.astype(g.dtype)


def clip_by_global_norm(grads, max_norm: float):
    """Scale the whole gradient tree so its global L2 norm is <= max_norm
    (the standard BERT/GPT pretraining clip; reference models clip via
    optimizer kernels' l2 machinery)."""
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree_util.tree_map(
        lambda g: None if g is None else _scale_grad(g, scale), grads,
        is_leaf=lambda x: _is_leaf(x) or x is None)


def clip_by_value(grads, min_value: float, max_value: float):
    """Per-element value clip (reference gpu_ops/ParamClip.py semantics
    applied to gradients)."""
    def clip(g):
        if g is None:
            return None
        if isinstance(g, IndexedSlices):
            return dataclasses.replace(
                g, values=jnp.clip(g.values, min_value, max_value))
        return jnp.clip(g, min_value, max_value)

    return jax.tree_util.tree_map(
        clip, grads, is_leaf=lambda x: _is_leaf(x) or x is None)


def _zeros_slot(p):
    # Slots live in fp32 regardless of param dtype (bf16 moments destroy Adam
    # numerics, and dtype-stable state pytrees are required for scan/donation).
    if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating):
        return jnp.zeros(jnp.shape(p), jnp.float32)
    return jnp.zeros_like(p)


@dataclasses.dataclass
class Optimizer:
    """Base class.  Subclasses implement ``_dense`` and ``_sparse`` row updates."""

    learning_rate: ScheduleOrFloat = 0.01
    l2reg: float = 0.0
    # gradient clipping, applied over the whole grad tree before the update:
    # clip_norm > 0 = global-L2-norm clip; clip_value > 0 = |g| value clip
    clip_norm: float = 0.0
    clip_value: float = 0.0

    def init(self, params) -> dict:
        return {
            "step": jnp.zeros((), jnp.int32),
            **{k: jax.tree_util.tree_map(_zeros_slot, params) for k in self.slot_names()},
        }

    def slot_names(self) -> tuple:
        return ()

    # -- single-leaf updates --------------------------------------------------
    def _dense(self, g, p, slots: dict, lr, step):
        raise NotImplementedError

    def _sparse(self, s: IndexedSlices, p, slots: dict, lr, step):
        """Default sparse path: apply the dense rule on gathered rows only
        (lazy semantics — untouched rows' params and moments don't advance,
        reference optimizer.py:553 AdamSparseUpdateOp)."""
        s = s.dedup()
        idx = s.indices
        valid = (idx >= 0)[:, None]
        old_rows = {k: v[idx] for k, v in slots.items()}
        p_rows = p[idx]
        g_rows = s.values
        if self.l2reg > 0.0:
            g_rows = g_rows + self.l2reg * p_rows
        new_rows, new_slot_rows = self._dense(g_rows, p_rows, dict(old_rows), lr, step)
        upd = jnp.where(valid, (new_rows - p_rows).astype(p.dtype), 0)
        p = p.at[idx].add(upd, mode="drop")
        for k in slots:
            slot_upd = jnp.where(
                valid, (new_slot_rows[k] - old_rows[k]).astype(slots[k].dtype), 0
            )
            slots[k] = slots[k].at[idx].add(slot_upd, mode="drop")
        return p, slots

    # -- pytree update --------------------------------------------------------
    def update(self, grads, state, params, mask=None):
        """Apply one update.  ``mask`` (optional) is a params-congruent pytree
        of bools — False marks non-trainable leaves (BatchNorm statistics
        etc., see core.module.trainable_mask) which are passed through
        untouched (no weight decay, no moment update)."""
        step = state["step"] + 1
        lr = _lr_at(self.learning_rate, step)
        slot_names = self.slot_names()
        if self.clip_norm > 0.0:
            grads = clip_by_global_norm(grads, self.clip_norm)
        if self.clip_value > 0.0:
            grads = clip_by_value(grads, -self.clip_value, self.clip_value)

        # None grads mark frozen params; keep them as leaves so the treedefs
        # of grads and params stay congruent.
        is_leaf = lambda x: _is_leaf(x) or x is None  # noqa: E731
        leaves_g, treedef = jax.tree_util.tree_flatten(grads, is_leaf=is_leaf)
        leaves_p = treedef.flatten_up_to(params)
        leaves_slots = {k: treedef.flatten_up_to(state[k]) for k in slot_names}
        leaves_m = (
            treedef.flatten_up_to(mask) if mask is not None else [True] * len(leaves_g)
        )

        new_p, new_slots = [], {k: [] for k in slot_names}
        for i, (g, p) in enumerate(zip(leaves_g, leaves_p)):
            slots = {k: leaves_slots[k][i] for k in slot_names}
            if g is None or not bool(leaves_m[i]):
                np_, ns = p, slots
            elif isinstance(g, IndexedSlices):
                np_, ns = self._sparse(g, p, dict(slots), lr, step)
            else:
                if self.l2reg > 0.0:
                    g = g + self.l2reg * p
                np_, ns = self._dense(g, p, dict(slots), lr, step)
                np_ = np_.astype(p.dtype)
                ns = {k: v.astype(slots[k].dtype) for k, v in ns.items()}
            new_p.append(np_)
            for k in slot_names:
                new_slots[k].append(ns[k])

        new_params = jax.tree_util.tree_unflatten(treedef, new_p)
        new_state = {"step": step}
        for k in slot_names:
            new_state[k] = jax.tree_util.tree_unflatten(treedef, new_slots[k])
        return new_params, new_state

    # Facade matching the reference Optimizer.minimize (optimizer.py:66): the
    # graph-building role is subsumed by jax.grad; exec.Trainer wires it up.


@dataclasses.dataclass
class SGDOptimizer(Optimizer):
    """Plain SGD (optimizer.py:203 SGDUpdateOp; src/ops/Optimizers.cu sgd_update)."""

    def _dense(self, g, p, slots, lr, step):
        return p.astype(jnp.float32) - lr * g.astype(jnp.float32), slots


@dataclasses.dataclass
class MomentumOptimizer(Optimizer):
    """(Nesterov) momentum (optimizer.py:289 MomentumUpdateOp)."""

    momentum: float = 0.9
    nesterov: bool = False

    def slot_names(self):
        return ("velocity",)

    def _dense(self, g, p, slots, lr, step):
        g32 = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        v = self.momentum * slots["velocity"] - lr * g32
        if self.nesterov:
            p32 = p32 + self.momentum * v - lr * g32
        else:
            p32 = p32 + v
        slots["velocity"] = v
        return p32, slots


@dataclasses.dataclass
class AdaGradOptimizer(Optimizer):
    """AdaGrad (optimizer.py:335 AdaGradUpdateOp)."""

    initial_accumulator_value: float = 0.0
    eps: float = 1e-7

    def slot_names(self):
        return ("accum",)

    def init(self, params):
        state = super().init(params)
        if self.initial_accumulator_value:
            state["accum"] = jax.tree_util.tree_map(
                lambda a: a + self.initial_accumulator_value, state["accum"]
            )
        return state

    def _dense(self, g, p, slots, lr, step):
        g32 = g.astype(jnp.float32)
        acc = slots["accum"] + jnp.square(g32)
        p = p.astype(jnp.float32) - lr * g32 / (jnp.sqrt(acc) + self.eps)
        slots["accum"] = acc
        return p, slots


@dataclasses.dataclass
class AdamOptimizer(Optimizer):
    """Adam (optimizer.py:462 AdamUpdateOp), with optional AMSGrad."""

    learning_rate: ScheduleOrFloat = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-7
    amsgrad: bool = False

    def slot_names(self):
        return ("m", "v") + (("vhat",) if self.amsgrad else ())

    def _dense(self, g, p, slots, lr, step):
        g32 = g.astype(jnp.float32)
        m = self.beta1 * slots["m"] + (1 - self.beta1) * g32
        v = self.beta2 * slots["v"] + (1 - self.beta2) * jnp.square(g32)
        stepf = step.astype(jnp.float32)
        mhat = m / (1 - self.beta1**stepf)
        vhat = v / (1 - self.beta2**stepf)
        if self.amsgrad:
            vmax = jnp.maximum(slots["vhat"], vhat)
            slots["vhat"] = vmax
            denom = jnp.sqrt(vmax) + self.eps
        else:
            denom = jnp.sqrt(vhat) + self.eps
        p = (p.astype(jnp.float32) - lr * mhat / denom).astype(p.dtype)
        slots["m"], slots["v"] = m, v
        return p, slots


@dataclasses.dataclass
class AdamWOptimizer(AdamOptimizer):
    """AdamW — decoupled weight decay (optimizer.py:629 AdamWUpdateOp).

    ``decay_min_ndim`` spares the leaves of fewer dimensions from the decay:
    2 decays matrices (and stacked or convolution weights) and leaves norm
    gains, biases and other vectors alone, the usual LM recipe; the default
    0 decays every leaf."""

    weight_decay: float = 0.01
    decay_min_ndim: int = 0

    def _dense(self, g, p, slots, lr, step):
        new_p, slots = super()._dense(g, p, slots, lr, step)
        if jnp.ndim(p) < self.decay_min_ndim:
            return new_p, slots
        return new_p - lr * self.weight_decay * p, slots


@dataclasses.dataclass
class LambOptimizer(AdamOptimizer):
    """LAMB — layerwise trust-ratio AdamW (optimizer.py:686 LambUpdateOp)."""

    weight_decay: float = 0.01

    def _dense(self, g, p, slots, lr, step):
        g32 = g.astype(jnp.float32)
        m = self.beta1 * slots["m"] + (1 - self.beta1) * g32
        v = self.beta2 * slots["v"] + (1 - self.beta2) * jnp.square(g32)
        stepf = step.astype(jnp.float32)
        mhat = m / (1 - self.beta1**stepf)
        vhat = v / (1 - self.beta2**stepf)
        update = mhat / (jnp.sqrt(vhat) + self.eps) + self.weight_decay * p.astype(jnp.float32)
        wnorm = jnp.linalg.norm(p.astype(jnp.float32))
        unorm = jnp.linalg.norm(update)
        trust = jnp.where(
            (wnorm > 0) & (unorm > 0), wnorm / unorm, jnp.ones_like(wnorm)
        )
        p = (p.astype(jnp.float32) - lr * trust * update).astype(p.dtype)
        slots["m"], slots["v"] = m, v
        return p, slots
