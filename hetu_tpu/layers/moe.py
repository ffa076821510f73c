"""Mixture-of-Experts with expert parallelism — TPU-native GShard dispatch.

Reference machinery being rebuilt (reference: python/hetu/):
- gates: ``TopKGate`` (layers/TopGate.py:56, topkgating:14 with capacity,
  cumsum locations, balance aux loss), ``HashGate`` (layers/HashGate.py:20),
  ``KTop1Gate`` (layers/KTop1Gate.py), ``SAMGate``/``BalanceGate``;
- dispatch: ``layout_transform_op`` packs tokens into per-expert capacity
  buckets (gpu_ops/LayoutTransform.py:12, CUDA H_A2A_LayoutTransform), then
  ``alltoall_op`` / hierarchical ``halltoall_op`` exchanges buckets across
  devices (layers/moe_layer.py:45-120, mpi_nccl_communication.cu:152/245);
- experts: per-device FFN list, looped in Python (moe_layer.py:79-82).

TPU-native design: dispatch/combine are one-hot einsums (GShard) — the
layout transform becomes an MXU matmul instead of a scatter kernel; experts
are ONE stacked FFN vmapped over the local expert dim (no Python loop);
the exchange is ``lax.all_to_all`` over the ``ep`` mesh axis inside a
``shard_map`` that is manual over ``ep`` only, so dp/tp shardings stay
GSPMD-auto.  Hierarchical A2A falls out of factored mesh axes (the ICI/DCN
hierarchy XLA already knows) rather than a hand-coded gather/a2a/scatter.

Capacity, shapes, and expert counts are static — XLA requirement and also
how the reference sizes its buckets (capacity math in TopGate.py:19).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from hetu_tpu.core.module import Module
from hetu_tpu.core.rng import next_key
from hetu_tpu.init import normal, zeros
from hetu_tpu.ops import gelu

__all__ = [
    "TopKGate", "HashGate", "KTop1Gate", "SAMGate", "BalanceGate",
    "ExpertMLP", "MoELayer", "moe_transformer_mlp", "routing_stats",
    "SigmoidRouter", "SoftmaxRouter", "HeldExpertsMoE",
]


def _one_hot(x, n, dtype=jnp.float32):
    return jax.nn.one_hot(x, n, dtype=dtype)


def _slot_positions(mask, capacity: int, fill=None):
    """Capacity bucketing position math shared by all gates (reference
    TopGate.py:34-44 cumsum locations): first-come-first-served positions
    per expert, tokens past ``capacity`` dropped.  ``mask``: [T,E] one-hot
    choices; ``fill``: [1,E] running per-expert occupancy from earlier
    choice ranks.  Returns (slot [T] int32, in_cap [T,E], new_fill)."""
    fill = jnp.zeros((1, mask.shape[1]), jnp.float32) if fill is None else fill
    pos = jnp.cumsum(mask, axis=0) - mask + fill
    new_fill = fill + jnp.sum(mask, axis=0, keepdims=True)
    in_cap = (pos < capacity).astype(jnp.float32) * mask
    slot = jnp.sum(pos * in_cap, axis=-1).astype(jnp.int32)
    return slot, in_cap, new_fill


def _densify(plans, T: int, E: int, C: int):
    """Dense [T,E,C] (dispatch, combine) from an index plan — the einsum
    path and the test oracle; every gate's __call__ goes through here so
    index_plan is the single source of routing truth."""
    dispatch = jnp.zeros((T, E, C), jnp.float32)
    combine = jnp.zeros((T, E, C), jnp.float32)
    for e_idx, slot, keep, g in plans:
        oh = (_one_hot(e_idx, E)[:, :, None]
              * _one_hot(slot, C)[:, None, :]
              * keep.astype(jnp.float32)[:, None, None])
        dispatch = dispatch + oh
        combine = combine + g[:, None, None] * oh
    return dispatch, combine


def routing_stats(plans, E: int):
    """Routing observability from an index plan (any gate's
    ``index_plan`` output): the two numbers that tell you whether a MoE
    run is silently degrading (reference gate accounting,
    moe_layer.py:45).

    - ``overflow_frac``: fraction of (token, choice) assignments dropped
      by capacity buckets.  High values mean tokens are falling out of
      the model — raise capacity_factor or fix the balance loss.
    - ``load_entropy``: entropy of the post-capacity per-expert load,
      normalized to [0, 1] (1 = perfectly balanced, 0 = every kept token
      on one expert — router collapse).
    """
    import math

    total = 0.0
    kept = 0.0
    load = jnp.zeros((E,), jnp.float32)
    for e_idx, _slot, keep, _g in plans:
        kf = keep.astype(jnp.float32)
        kept = kept + jnp.sum(kf)
        total = total + e_idx.shape[0]
        load = load + jnp.sum(_one_hot(e_idx, E) * kf[:, None], axis=0)
    p = load / jnp.maximum(jnp.sum(load), 1e-9)
    ent = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-30)),
                             0.0))
    return {
        "overflow_frac": 1.0 - kept / total,
        "load_entropy": ent / math.log(E) if E > 1 else jnp.float32(1.0),
    }


class TopKGate(Module):
    """Top-k router with capacity buckets and load-balance auxiliary loss
    (reference TopGate.py:14 ``topkgating``: softmax → top-k one-hot masks →
    cumsum positions → capacity drop → per-slot combine weights).

    Returns ``(dispatch [T,E,C] one-hot, combine [T,E,C], aux_loss)``.
    """

    def __init__(self, dim: int, num_experts: int, k: int = 2, *,
                 capacity_factor: float = 1.25,
                 eval_capacity_factor: Optional[float] = None,
                 dtype=jnp.float32):
        self.w = normal(stddev=0.02)(next_key(), (dim, num_experts), dtype)
        self.w_axes = ("embed", None)
        self.b = zeros(None, (num_experts,), dtype)
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor or capacity_factor

    def capacity(self, n_tokens: int, training: bool = True) -> int:
        cf = self.capacity_factor if training else self.eval_capacity_factor
        import math
        return max(self.k, self.k * math.ceil(n_tokens / self.num_experts * cf))

    def __call__(self, x, *, training: bool = True):
        """Dense [T,E,C] dispatch/combine built FROM the index plan — one
        source of routing truth (index_plan); this densification exists for
        gates/consumers on the einsum path and as the test oracle."""
        plans, C, aux = self.index_plan(x, training=training)
        dispatch, combine = _densify(plans, x.shape[0], self.num_experts, C)
        return dispatch, combine, aux

    def index_plan(self, x, *, training: bool = True):
        """Index-level routing plan for the scatter/gather dispatch path
        (MoELayer): per choice rank, (expert_idx [T], slot [T], keep [T],
        gate [T]).  Same position math (_slot_positions) and balance loss
        as __call__ — the dense [T,E,C] one-hot tensors are never built;
        at bench shape their einsums burn T*E*C*d MACs to do a gather's
        job."""
        T, E = x.shape[0], self.num_experts
        C = self.capacity(T, training)
        logits = (x @ self.w.astype(x.dtype) + self.b.astype(x.dtype))
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        plans = []
        aux = 0.0
        remaining = gates
        fill = None
        for _ in range(self.k):
            idx = jnp.argmax(remaining, axis=-1)
            mask = _one_hot(idx, E)
            remaining = remaining * (1.0 - mask)
            slot, in_cap, fill = _slot_positions(mask, C, fill)
            keep = jnp.sum(in_cap, axis=-1) > 0.0
            gate_i = jnp.sum(gates * mask, axis=-1)
            plans.append((idx, slot, keep, gate_i))
            me = jnp.mean(gates, axis=0)
            ce = jnp.mean(mask, axis=0)
            aux = aux + jnp.sum(me * ce) * E
        if self.k > 1:
            denom = sum(g * k.astype(jnp.float32) for _, _, k, g in plans)
            denom = jnp.maximum(denom, 1e-9)
            plans = [(i, s_, k, g / denom) for i, s_, k, g in plans]
        return plans, C, aux


class HashGate(Module):
    """Content-independent routing by precomputed/ hashed expert index
    (reference HashGate.py:6 hashgating — 'Currently Random Hash').  The
    assignment is ``token_id % num_experts`` by default; pass explicit
    indices for learned-hash variants."""

    def __init__(self, dim: int, num_experts: int, *,
                 capacity_factor: float = 1.0):
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.k = 1

    def capacity(self, n_tokens: int, training: bool = True) -> int:
        import math
        return max(1, math.ceil(n_tokens / self.num_experts * self.capacity_factor))

    def __call__(self, x, indices=None, *, training: bool = True):
        plans, C, aux = self.index_plan(x, indices, training=training)
        dispatch, combine = _densify(plans, x.shape[0], self.num_experts, C)
        return dispatch, combine, aux

    def index_plan(self, x, indices=None, *, training: bool = True):
        T, E = x.shape[0], self.num_experts
        C = self.capacity(T, training)
        if indices is None:
            indices = jnp.arange(T, dtype=jnp.int32) % E
        mask = _one_hot(indices, E)
        slot, in_cap, _ = _slot_positions(mask, C)
        keep = jnp.sum(in_cap, axis=-1) > 0.0
        gate = jnp.ones((T,), jnp.float32)  # hash combine weight is 1
        return [(indices, slot, keep, gate)], C, jnp.float32(0.0)


class KTop1Gate(Module):
    """k independent top-1 routers over disjoint expert prototypes
    (reference layers/KTop1Gate.py:14 ``ktop1gating``): the E experts are
    split into k prototype groups of E/k; each group gets its own softmax
    over the corresponding logit slice and routes top-1 within the group, so
    every token is dispatched to exactly k experts — one per prototype.
    Balance loss is summed per prototype (KTop1Gate.py:32-35).

    Prototype expert sets are disjoint, so capacity slots never interact
    across choices (the reference's commented-out ``acc_base`` carries no
    fill either).  Returns ``(dispatch [T,E,C], combine [T,E,C], aux)``.
    """

    def __init__(self, dim: int, num_experts: int, k: int = 2, *,
                 capacity_factor: float = 1.0,
                 eval_capacity_factor: Optional[float] = None,
                 dtype=jnp.float32):
        if num_experts % k:
            raise ValueError(f"{num_experts} experts not divisible by k={k}")
        self.w = normal(stddev=0.02)(next_key(), (dim, num_experts), dtype)
        self.w_axes = ("embed", None)
        self.b = zeros(None, (num_experts,), dtype)
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor or capacity_factor

    def capacity(self, n_tokens: int, training: bool = True) -> int:
        import math
        cf = self.capacity_factor if training else self.eval_capacity_factor
        return max(1, self.k * math.ceil(n_tokens / self.num_experts * cf))

    def __call__(self, x, *, training: bool = True):
        plans, C, aux = self.index_plan(x, training=training)
        dispatch, combine = _densify(plans, x.shape[0], self.num_experts, C)
        return dispatch, combine, aux

    def index_plan(self, x, *, training: bool = True):
        T, E, k = x.shape[0], self.num_experts, self.k
        Ep = E // k                                   # experts per prototype
        C = self.capacity(T, training)
        logits = x @ self.w.astype(x.dtype) + self.b.astype(x.dtype)
        # [T, k, Ep]: per-prototype softmax (KTop1Gate.py:19-21 split+softmax)
        pgates = jax.nn.softmax(
            logits.astype(jnp.float32).reshape(T, k, Ep), axis=-1)
        idx = jnp.argmax(pgates, axis=-1)             # [T, k] local top-1
        pmask = _one_hot(idx, Ep)                     # [T, k, Ep]
        gate_val = jnp.sum(pgates * pmask, axis=-1)   # [T, k]

        # per-prototype balance loss vs its own softmax (Ep experts)
        me = jnp.mean(pgates, axis=0)                 # [k, Ep]
        ce = jnp.mean(pmask, axis=0)                  # [k, Ep]
        aux = jnp.sum(jnp.sum(me * ce, axis=-1) * Ep)

        # slot assignment per prototype (expert columns are disjoint, so
        # fills never interact; one choice per row each)
        plans = []
        for i in range(k):
            mask_i = jnp.zeros((T, k, Ep), jnp.float32).at[:, i].set(
                pmask[:, i]).reshape(T, E)
            slot, in_cap, _ = _slot_positions(mask_i, C)
            keep = jnp.sum(in_cap, axis=-1) > 0.0
            e_idx = i * Ep + idx[:, i]
            plans.append((e_idx, slot, keep, gate_val[:, i]))
        return plans, C, aux


class SAMGate(Module):
    """Switch-and-mix locality-aware gate (reference layers/SAMGate.py:21
    ``samgating``): softmax over all E experts, sum gates within each of G
    contiguous expert groups (one group per node; SamGroupSum.cu), route the
    token to its top-1 *group*, then take the top-k experts inside that
    group (GroupTopKIdx.cu).  All k choices land on one node, so the
    all-to-all stays intra-node.

    Aux = summed balance loss per choice (SAMGate.py:40,56) plus
    ``alignment_weight`` × the alignment loss (SamMax.cu: for each token,
    sum of relu(gate_j − gate_thresh) over experts *outside* the chosen
    group, thresh = the k-th chosen expert's gate — penalises out-of-group
    experts that outscore the selection).
    """

    def __init__(self, dim: int, num_experts: int, k: int = 2, *,
                 num_groups: int, capacity_factor: float = 1.0,
                 eval_capacity_factor: Optional[float] = None,
                 alignment_weight: float = 1.0, dtype=jnp.float32):
        if num_experts % num_groups:
            raise ValueError(f"{num_experts} experts not divisible into "
                             f"{num_groups} groups")
        if k > num_experts // num_groups:
            raise ValueError("k exceeds experts per group")
        self.w = normal(stddev=0.02)(next_key(), (dim, num_experts), dtype)
        self.w_axes = ("embed", None)
        self.b = zeros(None, (num_experts,), dtype)
        self.num_experts = num_experts
        self.k = k
        self.num_groups = num_groups
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor or capacity_factor
        self.alignment_weight = alignment_weight

    def capacity(self, n_tokens: int, training: bool = True) -> int:
        import math
        cf = self.capacity_factor if training else self.eval_capacity_factor
        return max(1, self.k * math.ceil(n_tokens / self.num_experts * cf))

    def __call__(self, x, *, training: bool = True):
        plans, C, aux = self.index_plan(x, training=training)
        dispatch, combine = _densify(plans, x.shape[0], self.num_experts, C)
        return dispatch, combine, aux

    def index_plan(self, x, *, training: bool = True):
        T, E, G = x.shape[0], self.num_experts, self.num_groups
        Eg = E // G                                    # experts per group
        C = self.capacity(T, training)
        logits = x @ self.w.astype(x.dtype) + self.b.astype(x.dtype)
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T,E]

        group_sum = jnp.sum(gates.reshape(T, G, Eg), axis=-1)        # [T,G]
        top1_group = jnp.argmax(group_sum, axis=-1)                  # [T]
        in_group = _one_hot(top1_group, G)[:, :, None] * jnp.ones((1, 1, Eg))
        in_group = in_group.reshape(T, E)              # [T,E] group member
        masked_gates = jnp.where(in_group > 0, gates, -jnp.inf)

        plans = []
        aux = 0.0
        remaining = masked_gates
        fill = None                                    # shared acc_base fill
        last_gate = None
        for _ in range(self.k):
            idx = jnp.argmax(remaining, axis=-1)
            mask = _one_hot(idx, E)
            remaining = jnp.where(mask > 0, -jnp.inf, remaining)
            slot, in_cap, fill = _slot_positions(mask, C, fill)
            keep = jnp.sum(in_cap, axis=-1) > 0.0
            gate_i = jnp.sum(gates * mask, axis=-1)
            last_gate = gate_i
            plans.append((idx, slot, keep, gate_i))
            me = jnp.mean(gates, axis=0)
            ce = jnp.mean(mask, axis=0)
            aux = aux + jnp.sum(me * ce) * E
        # alignment: out-of-chosen-group gates above the k-th chosen gate,
        # averaged over tokens so its scale is batch-invariant like the
        # balance term (means over T) and alignment_weight transfers
        # across batch/sequence sizes
        overflow = jnp.maximum(gates - last_gate[:, None], 0.0)
        alignment = jnp.sum(overflow * (1.0 - in_group)) / T
        return plans, C, aux + self.alignment_weight * alignment


class BalanceGate(Module):
    """BASE-layer balanced assignment (reference layers/BalanceGate.py:25
    ``BalanceAssignmentGate`` + BalanceAssignment.cu auction solver): tokens
    are scored against fixed orthogonal expert centroids and assigned so
    every expert receives exactly T/E tokens; output is weighted by
    sigmoid(score) (BASE, Lewis et al. '21).

    TPU redesign: the reference solves the assignment with a sequential
    auction algorithm — a data-dependent loop that is hostile to XLA.  Here
    the balanced transport plan comes from ``sinkhorn_iters`` rounds of
    Sinkhorn row/column normalisation (the S-BASE formulation) followed by
    capacity-bucketed argmax with C = ceil(T/E), which is a fixed unrollable
    compute graph of matmul-shaped reductions.  Aux loss is 0 — balance is
    enforced structurally, exactly as in the reference.
    """

    _state_fields = ("centroids",)

    def __init__(self, dim: int, num_experts: int, *,
                 sinkhorn_iters: int = 8, temperature: float = 1.0,
                 dtype=jnp.float32):
        key = next_key()
        # orthogonal, non-trainable centroids (BalanceGate.py:6
        # generate_orthogonal, gain 0.1)
        w = jax.random.normal(key, (num_experts, dim), jnp.float32)
        q, r = jnp.linalg.qr(w.T if num_experts < dim else w)
        q = q * jnp.sign(jnp.diag(r))
        self.centroids = (q.T if num_experts < dim else q).astype(dtype) * 0.1
        self.num_experts = num_experts
        self.k = 1
        self.sinkhorn_iters = sinkhorn_iters
        self.temperature = temperature

    def capacity(self, n_tokens: int, training: bool = True) -> int:
        import math
        return max(1, math.ceil(n_tokens / self.num_experts))

    def __call__(self, x, *, training: bool = True):
        plans, C, aux = self.index_plan(x, training=training)
        dispatch, combine = _densify(plans, x.shape[0], self.num_experts, C)
        return dispatch, combine, aux

    def index_plan(self, x, *, training: bool = True):
        T, E = x.shape[0], self.num_experts
        C = self.capacity(T, training)
        scores = (x @ self.centroids.astype(x.dtype).T).astype(jnp.float32)

        # Sinkhorn to a doubly-balanced plan (rows sum 1, cols sum T/E)
        logp = scores / self.temperature
        f = jnp.zeros((T, 1), jnp.float32)
        g = jnp.zeros((1, E), jnp.float32)
        for _ in range(self.sinkhorn_iters):
            f = -jax.nn.logsumexp(logp + g, axis=1, keepdims=True)
            g = (jnp.log(T / E)
                 - jax.nn.logsumexp(logp + f, axis=0, keepdims=True))
        plan = logp + f + g                            # balanced log-plan
        idx = jnp.argmax(plan, axis=-1)                # [T]
        mask = _one_hot(idx, E)
        slot, in_cap, _ = _slot_positions(mask, C)
        keep = jnp.sum(in_cap, axis=-1) > 0.0
        weight = jax.nn.sigmoid(jnp.sum(scores * mask, axis=-1))  # BASE
        return [(idx, slot, keep, weight)], C, jnp.float32(0.0)


class ExpertMLP(Module):
    """Stacked expert FFNs: leaves ``[n_experts, ...]`` on the ``experts``
    logical axis (→ ``ep`` mesh axis), applied with vmap — the TPU form of
    the reference's per-device expert list (moe_layer.py:7 Expert)."""

    def __init__(self, num_experts: int, dim: int, hidden: int, *,
                 activation: Callable = gelu, dtype=jnp.float32):
        init = normal(stddev=0.02)
        self.w1 = init(next_key(), (num_experts, dim, hidden), dtype)
        self.w1_axes = ("experts", "embed", "mlp")
        self.b1 = zeros(None, (num_experts, hidden), dtype)
        self.b1_axes = ("experts", "mlp")
        self.w2 = init(next_key(), (num_experts, hidden, dim), dtype)
        self.w2_axes = ("experts", "mlp", "embed")
        self.b2 = zeros(None, (num_experts, dim), dtype)
        self.b2_axes = ("experts", "embed")
        self.activation = activation
        self.num_experts = num_experts

    def __call__(self, x):
        """x: [E_local, tokens, dim] → same shape."""
        def one(w1, b1, w2, b2, t):
            h = self.activation(t @ w1.astype(t.dtype) + b1.astype(t.dtype))
            return h @ w2.astype(t.dtype) + b2.astype(t.dtype)
        return jax.vmap(one)(self.w1, self.b1, self.w2, self.b2, x)


class MoELayer(Module):
    """Gate → dispatch einsum → AllToAll over ``ep`` → experts → reverse
    AllToAll → combine einsum (reference moe_layer.py:45 MoELayer.__call__).

    ``mesh=None`` (or ep=1) degenerates to single-group MoE with no
    exchange — the oracle path tests compare against.

    Call: ``y, aux = moe(x)`` with x ``[..., dim]``; aux is the gate's
    balance loss (add to the objective scaled by ``aux_weight``).
    """

    def __init__(self, gate: Module, experts: ExpertMLP, *,
                 mesh: Optional[Mesh] = None,
                 axis: "str | Sequence[str]" = "ep"):
        self.gate = gate
        self.experts = experts
        self.mesh = mesh
        # a tuple axis, e.g. ("ep", "tp") or (dcn, ici), factors the expert
        # exchange hierarchically — the reference's HAllToAll
        # (mpi_nccl_communication.cu:152 intra-gather → inter-a2a → scatter);
        # XLA lowers the inner axis onto ICI and the outer onto DCN.
        self.axis = (axis,) if isinstance(axis, str) else tuple(axis)

    def _route_in(self, gate, t, training):
        """(ex_in [E,C,d], plan_ctx, aux).  Index path (scatter) when the
        gate provides index_plan — one O(T*d) scatter instead of a
        [T,E,C]x[T,d] einsum burning T*E*C*d MACs; else the one-hot
        einsum (reference moe_layer.py dispatch)."""
        E = self.experts.num_experts
        if hasattr(gate, "index_plan"):
            plans, C, aux = gate.index_plan(t, training=training)
            flat = jnp.zeros((E * C, t.shape[1]), t.dtype)
            for e_idx, slot, keep, _g in plans:
                tgt = jnp.where(keep, e_idx * C + slot, E * C)
                flat = flat.at[tgt].add(t, mode="drop")
            return flat.reshape(E, C, t.shape[1]), ("idx", plans, C), aux
        dispatch, combine, aux = gate(t, training=training)
        ex_in = jnp.einsum("tec,td->ecd", dispatch.astype(t.dtype), t)
        return ex_in, ("oh", combine), aux

    def _route_out(self, ctx, ex_out, t_dtype):
        """Combine expert outputs back to tokens per the routing context."""
        if ctx[0] == "idx":
            _, plans, C = ctx
            flat = ex_out.reshape(-1, ex_out.shape[-1])
            y = 0.0
            for e_idx, slot, keep, g in plans:
                src = jnp.clip(e_idx * C + slot, 0, flat.shape[0] - 1)
                w = (g * keep.astype(jnp.float32)).astype(t_dtype)
                y = y + flat[src] * w[:, None]
            return y
        _, combine = ctx
        return jnp.einsum("tec,ecd->td", combine.astype(t_dtype), ex_out)

    def _stats_of(self, ctx, E):
        """routing_stats from the routing context (index path only: the
        one-hot einsum path has no plan to account; all shipped gates
        provide index_plan)."""
        if ctx[0] != "idx":
            raise ValueError(
                "with_stats needs a gate with index_plan (scatter path)")
        return routing_stats(ctx[1], E)

    def __call__(self, x, *, training: bool = True,
                 with_stats: bool = False):
        """``with_stats=True`` returns ``(y, (aux, stats))`` where stats is
        ``routing_stats`` of this call's plan (overflow_frac,
        load_entropy) — pmean'd over ep so every rank logs the global
        picture."""
        shape = x.shape
        d = shape[-1]
        mesh = self.mesh
        ep = 1
        if mesh is not None:
            for a in self.axis:
                ep *= mesh.shape[a]
        E = self.experts.num_experts          # global expert count
        if E % max(ep, 1):
            raise ValueError(f"{E} experts not divisible over ep={ep}")

        if ep <= 1:
            t = x.reshape(-1, d)
            ex_in, ctx, aux = self._route_in(self.gate, t, training)
            ex_out = self.experts(ex_in)
            y = self._route_out(ctx, ex_out, t.dtype)
            if with_stats:
                return y.reshape(shape), (aux, self._stats_of(ctx, E))
            return y.reshape(shape), aux

        E_local = E // ep

        def _pvary_params(tree):
            # Mark replicated param leaves device-varying explicitly, in
            # their storage dtype (fp32).  Without this, shard_map inserts
            # the replicated->varying conversion lazily at first use — which
            # is AFTER the bf16 compute cast, producing a bf16 copy-reduction
            # all-reduce that XLA:CPU's AllReducePromotion pass cannot clone
            # (crash: "Invalid binary instruction opcode copy").  Varying
            # them up front keeps that collective in fp32 on every backend.
            def pv(p):
                if not isinstance(p, jax.Array):
                    return p
                missing = tuple(a for a in self.axis
                                if a not in jax.typeof(p).vma)
                if not missing:
                    return p
                pcast = getattr(lax, "pcast", None)
                if pcast is not None:
                    return pcast(p, missing, to="varying")
                return lax.pvary(p, missing)
            return jax.tree_util.tree_map(pv, tree)

        def inner(gate, experts, xl):
            gate = _pvary_params(gate)
            experts = _pvary_params(experts)
            # xl: the ep-local token shard [..., d]
            t = xl.reshape(-1, d)
            ex_in, ctx, aux = self._route_in(gate, t, training)
            # [E, C, d] -> exchange capacity buckets so each rank holds its
            # E_local experts' buckets from every rank: [E_local, ep*C, d]
            ex_in = lax.all_to_all(ex_in, self.axis, split_axis=0,
                                   concat_axis=1, tiled=True)
            ex_out = experts(ex_in)
            # reverse exchange: [E, C, d] back on every source rank
            ex_out = lax.all_to_all(ex_out, self.axis, split_axis=1,
                                    concat_axis=0, tiled=True)
            y = self._route_out(ctx, ex_out, t.dtype)
            aux = lax.pmean(aux, self.axis)
            if with_stats:
                stats = {k: lax.pmean(v, self.axis)
                         for k, v in self._stats_of(ctx, E).items()}
                return y.reshape(xl.shape), (aux, stats)
            return y.reshape(xl.shape), aux

        out_aux_spec = (P(), {"overflow_frac": P(), "load_entropy": P()}) \
            if with_stats else P()
        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(), P(self.axis), P(self.axis)),
            out_specs=(P(self.axis), out_aux_spec),
            axis_names=frozenset(self.axis),
        )(self.gate, self.experts, x)


def moe_transformer_mlp(dim: int, hidden: int, num_experts: int, *, k: int = 2,
                        capacity_factor: float = 1.25,
                        mesh: Optional[Mesh] = None,
                        dtype=jnp.float32) -> MoELayer:
    """The standard MoE-transformer FFN replacement (reference
    examples/moe model_dim 2048, experts-per-device × world config)."""
    gate = TopKGate(dim, num_experts, k, capacity_factor=capacity_factor,
                    dtype=dtype)
    experts = ExpertMLP(num_experts, dim, hidden, dtype=dtype)
    return MoELayer(gate, experts, mesh=mesh)


# --------------------------------------------------------------------------
# A router of the published width over the experts held here (no capacity)
# --------------------------------------------------------------------------
#
# The gates above pack tokens into capacity buckets and drop what does not
# fit.  The layer below does neither.  It is told which experts it holds
# (one chip's share under expert parallelism), scores every token against
# ALL the experts, and computes, for the tokens whose choice fell on a held
# expert, that expert's part of the result: tokens sorted by expert and one
# grouped matmul over the sorted rows.  What the experts held elsewhere
# would add comes over the exchange (R2), which one chip runs without.

class SigmoidRouter(Module):
    """``s = sigmoid(W_r x)`` over ``num_experts``; the ``top_k`` largest of
    ``s + bias`` are chosen and weighted ``scale * s / sum(chosen s)``.
    ``bias`` moves the choice only and is no trained
    leaf (the loss-free balancing term; zero unless set from outside)."""

    _state_fields = ("bias",)

    def __init__(self, dim: int, num_experts: int, top_k: int, *,
                 scale: float = 1.0, init_std: float = 0.02,
                 dtype=jnp.float32):
        self.w = normal(stddev=init_std)(next_key(), (dim, num_experts),
                                         dtype)
        self.bias = zeros(None, (num_experts,), jnp.float32)
        self.top_k, self.scale = top_k, scale

    def __call__(self, x):
        """x: [tokens, dim] -> (chosen [tokens, top_k] int32, weights
        [tokens, top_k] float32)."""
        s = jax.nn.sigmoid(jnp.dot(x, self.w.astype(x.dtype),
                                   preferred_element_type=jnp.float32))
        _, chosen = lax.top_k(s + self.bias, self.top_k)
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        return chosen, self.scale * picked / jnp.sum(picked, axis=-1,
                                                     keepdims=True)


class SoftmaxRouter(Module):
    """``s = softmax(W_r x)`` over ``num_experts`` in float32; the ``top_k``
    largest are chosen and weighted ``scale * s`` as they stand, not
    renormalised over the chosen (DeepSeek-V2: ``scoring_func`` softmax,
    ``topk_method`` greedy, ``norm_topk_prob`` false)."""

    def __init__(self, dim: int, num_experts: int, top_k: int, *,
                 scale: float = 1.0, init_std: float = 0.02,
                 dtype=jnp.float32):
        self.w = normal(stddev=init_std)(next_key(), (dim, num_experts),
                                         dtype)
        self.top_k, self.scale = top_k, scale

    def __call__(self, x):
        """x: [tokens, dim] -> (chosen [tokens, top_k] int32, weights
        [tokens, top_k] float32)."""
        s = jax.nn.softmax(jnp.dot(x, self.w.astype(x.dtype),
                                   preferred_element_type=jnp.float32),
                           axis=-1)
        picked, chosen = lax.top_k(s, self.top_k)
        return chosen, self.scale * picked


ROUTERS = {"sigmoid": SigmoidRouter, "softmax": SoftmaxRouter}


def _gmm_tiles(m: int, k: int, n: int) -> tuple:
    """(tm, tk, tn) for the grouped matmul: the largest listed tile that
    divides each dimension, else the dimension itself."""
    def pick(dim, sizes):
        return next((t for t in sizes if dim % t == 0), dim)
    return (pick(m, (256, 128, 64, 32, 16, 8)),
            pick(k, (1024, 768, 512, 384, 256, 128)),
            pick(n, (1024, 768, 512, 384, 256, 128)))


def _serving_tiles(m: int, k: int, n: int, budget: int = 3 << 19) -> tuple:
    """(tm, tk, tn) for the grouped matmul where the weights are read once
    and the rows are few: the largest tile of an expert's matrix, in
    multiples of 128 that divide it or the whole dimension, whose bfloat16
    bytes fit ``budget`` (1.5 MB), so that a grid step moves megabytes and
    not the 256 KB that :func:`_gmm_tiles` gives a width like 1408, which
    only 128 divides: a decode step's product is then a few hundred grid
    steps instead of 1,600, each some 0.35 us of fixed cost."""
    def parts(dim):
        return [t for t in range(dim, 0, -128)
                if dim % t == 0 and t % 128 == 0] or [dim]
    fits = [(tk, tn) for tk in parts(k) for tn in parts(n)
            if tk * tn * 2 <= budget] or [(parts(k)[-1], parts(n)[-1])]
    tk, tn = max(fits, key=lambda p: (p[0] * p[1], p[1]))
    return _gmm_tiles(m, k, n)[0], tk, tn


def _grouped_matmul(rows, w, group_sizes, valid, interpret, tiles=_gmm_tiles):
    """``rows[group g] @ w[g]`` for rows sorted by group.  Rows past the
    last group are never visited by the kernel, in either direction of the
    derivative, so they are zeroed on the way in and on the way out."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    rows = jnp.where(valid, rows, 0)
    out = gmm(rows, w.astype(rows.dtype), group_sizes, rows.dtype,
              tiles(rows.shape[0], w.shape[1], w.shape[2]),
              interpret=interpret)
    return jnp.where(valid, out, 0)


def _take_rows(rows, index):
    """``rows[index]`` where an index outside the rows reads zeros."""
    n = rows.shape[0]
    inside = (index >= 0) & (index < n)
    return jnp.where(inside[:, None], rows[jnp.clip(index, 0, n - 1)], 0)


@jax.custom_vjp
def _dispatch(x, order, inverse, first):
    """Rows of x [T, d] in assignment order, for the sorted pairs ``first``
    to ``first + len(order)`` of the T K: ``x[order // K]``.  The transpose
    is a gather too (unsort, then sum a token's K copies), where autodiff
    would scatter."""
    return x[order // (inverse.shape[0] // x.shape[0])]


def _dispatch_fwd(x, order, inverse, first):
    return _dispatch(x, order, inverse, first), (inverse, first, x.shape[0])


def _dispatch_bwd(res, d_rows):
    inverse, first, t = res
    back = _take_rows(d_rows, inverse - first).reshape(
        t, -1, d_rows.shape[-1])
    return back.sum(axis=1), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, weight, order, inverse, first):
    """``y[t] = sum_k weight[t, k] * rows[inverse[t K + k] - first]``: the
    sorted rows back in token order, weighted and summed a token.  A pair
    whose row is not among these reads zeros."""
    t, k = weight.shape
    back = _take_rows(rows, inverse - first).reshape(t, k, rows.shape[-1])
    return jnp.sum(back * weight[..., None].astype(rows.dtype), axis=1)


def _combine_fwd(rows, weight, order, inverse, first):
    return _combine(rows, weight, order, inverse, first), (
        rows, weight, order, inverse, first)


def _combine_bwd(res, dy):
    rows, weight, order, inverse, first = res
    t, k = weight.shape
    d_rows = dy[order // k] * weight.reshape(-1)[order][:, None].astype(
        dy.dtype)
    back = _take_rows(rows, inverse - first).reshape(t, k, rows.shape[-1])
    d_weight = jnp.sum(back.astype(jnp.float32)
                       * dy[:, None, :].astype(jnp.float32), axis=-1)
    return d_rows, d_weight.astype(weight.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


class HeldExpertsMoE(Module):
    """A mixture-of-experts feed-forward that is told its share.

    ``held`` names the experts (of the router's ``num_experts``) whose
    weights live here; ``y = sum over chosen and held e of w_e SwiGLU_e(x)
    + SwiGLU_shared(x)``.  No token is dropped and no expert has a
    capacity: the (token, choice) pairs are sorted by expert, held experts
    first, and the held ones' rows go through one grouped matmul a
    projection (``megablox.gmm``, which walks only the rows that exist).
    The sorted pairs are walked ``tokens`` rows at a time (``top_k`` passes
    could hold every pair there is), and a pass that no held pair is left
    for is skipped: uniform routing over as many experts as are held needs
    one pass, the worst routing all of them, and neither drops a pair.
    Rows past the held pairs are zero and cost no matmul.

    ``__call__`` returns ``(y, stats)``: ``stats`` holds ``held``, the
    pairs that fell on held experts, ``assignments``, all pairs,
    ``experts_hit``, the held experts that got a row at all, and
    ``load_max_over_mean``, the busiest held expert's rows over the mean.

    ``router`` names the scoring: ``"sigmoid"`` (:class:`SigmoidRouter`) or
    ``"softmax"`` (:class:`SoftmaxRouter`).  :meth:`infer` is the same
    layer for serving, where nothing is differentiated: every sorted pair
    in one pass and one grouped product a projection, so that an expert's
    weights are read once a call and none is copied.

    Why two walks: where a few of the router's experts are held (training
    one chip's share), nearly every sorted pair is another chip's, and a
    single pass pays its full length in every operation that is not the
    grouped product: the Kimi cell trains 9.5% slower through
    :meth:`infer` (PERF.md, PR 30).  Where every expert is held (serving)
    no pass can be skipped, and the walk's concatenated gate and up
    weights are a copy of every expert's weights a call.
    """

    def __init__(self, dim: int, hidden: int, num_experts: int, held, *,
                 top_k: int, scale: float = 1.0, shared_hidden: int = 0,
                 init_std: float = 0.02, dtype=jnp.float32, interpret=None,
                 router: str = "sigmoid"):
        from hetu_tpu.layers.transformer import SwiGLU
        held = tuple(int(e) for e in held)
        if len(set(held)) != len(held) or not all(
                0 <= e < num_experts for e in held):
            raise ValueError(f"held experts {held} of {num_experts}")
        init = normal(stddev=init_std)
        n = len(held)
        self.router = ROUTERS[router](dim, num_experts, top_k, scale=scale,
                                      init_std=init_std, dtype=dtype)
        self.experts = _HeldExperts(n, dim, hidden, init, dtype)
        self.shared = (SwiGLU(dim, shared_hidden, dtype=dtype,
                              init_std=init_std) if shared_hidden else None)
        self.held, self.num_experts = held, num_experts
        self.interpret = interpret

    def _interpret(self):
        from hetu_tpu.core.runtime import pallas_interpret
        return (pallas_interpret() if self.interpret is None
                else self.interpret)

    def _route(self, x):
        """The (token, choice) pairs of x [tokens, dim] sorted by expert,
        held experts first: the choices' weights (nought for an expert held
        elsewhere), the sort and its inverse, the rows each held expert
        got, and which sorted rows are a held expert's."""
        t, n = x.shape[0], len(self.held)
        with jax.named_scope("moe.route"):
            chosen, weight = self.router(x)
            k = chosen.shape[1]
            slot_of = jnp.full((self.num_experts,), n, jnp.int32).at[
                jnp.asarray(self.held)].set(jnp.arange(n, dtype=jnp.int32))
            slot = slot_of[chosen].reshape(-1)          # n = held elsewhere
            order = jnp.argsort(slot, stable=True).astype(jnp.int32)
            inverse = jnp.argsort(order).astype(jnp.int32)
            sizes = jnp.sum(slot[:, None] == jnp.arange(n)[None, :],
                            axis=0, dtype=jnp.int32)
            valid = (slot[order] < n)[:, None]
            weight = jnp.where(slot.reshape(t, k) < n, weight, 0.0)
        return weight, order, inverse, sizes, valid

    def _stats(self, sizes, pairs: int, held) -> dict:
        n = len(self.held)
        return {"held": held, "assignments": jnp.int32(pairs),
                "experts_hit": jnp.sum(sizes > 0, dtype=jnp.int32),
                "load_max_over_mean": jnp.max(sizes).astype(jnp.float32)
                * n / jnp.maximum(held, 1).astype(jnp.float32)}

    def infer(self, x):
        """``__call__`` for serving, x [..., dim] -> ``(y, stats)``: all
        ``tokens x top_k`` sorted rows through the gate's, the up's and the
        down's grouped product once each."""
        lead, d = x.shape[:-1], x.shape[-1]
        flat = x.reshape(-1, d)
        weight, order, inverse, sizes, valid = self._route(flat)
        t, k = weight.shape
        e, interpret = self.experts, self._interpret()
        with jax.named_scope("moe.experts"):
            rows = flat[order // k]
            product = functools.partial(
                _grouped_matmul, group_sizes=sizes, valid=valid,
                interpret=interpret, tiles=_serving_tiles)
            act = jax.nn.silu(product(rows, e.w_gate)) * product(rows,
                                                                 e.w_up)
            out = product(act, e.w_down)
            y = jnp.sum(out[inverse].reshape(t, k, d)
                        * weight[..., None].astype(out.dtype), axis=1)
        if self.shared is not None:
            with jax.named_scope("moe.shared"):
                y = y + self.shared(flat)
        return y.reshape(lead + (d,)), self._stats(sizes, t * k,
                                                   jnp.sum(sizes))

    def routed(self, x):
        """The held experts' part of the result for x [tokens, dim], and
        the routing's counts."""
        interpret = self._interpret()
        t = x.shape[0]
        weight, order, inverse, sizes, valid = self._route(x)
        k = weight.shape[1]

        ends = jnp.cumsum(sizes)
        held = ends[-1]

        @jax.checkpoint
        def one_pass(y, first):
            """Adds the held experts' part for the sorted pairs ``first`` to
            ``first + t``, if any of them is held."""
            def experts():
                e = self.experts
                part = lax.dynamic_slice_in_dim(order, first, t)
                ok = lax.dynamic_slice_in_dim(valid, first, t)
                here = (jnp.clip(ends, first, first + t)
                        - jnp.clip(ends - sizes, first, first + t))
                rows = _dispatch(x, part, inverse, first)
                both = _grouped_matmul(
                    rows, jnp.concatenate([e.w_gate, e.w_up], axis=-1),
                    here, ok, interpret)
                f = e.w_gate.shape[-1]
                act = jax.nn.silu(both[:, :f]) * both[:, f:]
                out = _grouped_matmul(act, e.w_down, here, ok, interpret)
                return y + _combine(out, weight, part, inverse, first)

            return lax.cond(first < held, experts, lambda: y), None

        with jax.named_scope("moe.experts"):
            y, _ = lax.scan(one_pass, jnp.zeros_like(x),
                            jnp.arange(k, dtype=jnp.int32) * t)
        return y, self._stats(sizes, t * k, held)

    def __call__(self, x):
        lead = x.shape[:-1]
        flat = x.reshape(-1, x.shape[-1])
        y, stats = self.routed(flat)
        if self.shared is not None:
            with jax.named_scope("moe.shared"):
                y = y + self.shared(flat)
        return y.reshape(lead + (x.shape[-1],)), stats


class _HeldExperts(Module):
    """The held experts' SwiGLU weights, stacked ``[held, ...]``."""

    def __init__(self, n: int, dim: int, hidden: int, init, dtype):
        self.w_gate = init(next_key(), (n, dim, hidden), dtype)
        self.w_gate_axes = ("experts", "embed", "mlp")
        self.w_up = init(next_key(), (n, dim, hidden), dtype)
        self.w_up_axes = ("experts", "embed", "mlp")
        self.w_down = init(next_key(), (n, hidden, dim), dtype)
        self.w_down_axes = ("experts", "mlp", "embed")
