"""GNN subsystem: GCN layers, models, and 1.5D-partitioned distributed
aggregation.

Reference: gpu_ops/DistGCN_15d.py (1.5D partitioned GCN spmm with staged
broadcasts over row/column process groups, CAGNET-style), examples/gnn
(GCN/GraphSAGE training over GraphMix sampling servers), tests/test_DistGCN.

TPU-native: the 1.5D scheme maps onto a ('gr', 'gc') mesh — device (i, j)
holds adjacency block A[i, j] and feature shard X[j]; the local matmul is a
dense MXU op and the partial-sum reduction is one ``psum`` over the column
axis (the reference's hand-staged broadcast loop becomes a single XLA
collective).  Sparse graphs aggregate via ``segment_sum`` over an edge list
instead of cuSPARSE csrmm.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from hetu_tpu.core.module import Module
from hetu_tpu.core.rng import next_key
from hetu_tpu.init import xavier_uniform, zeros

__all__ = ["normalize_adjacency", "spmm_edges", "GraphConv", "GCN",
           "dist_spmm_15d", "DistGCN15D", "GraphIndex", "sample_subgraph"]


def normalize_adjacency(edge_index, num_nodes: int, *, add_self_loops=True):
    """Symmetric GCN normalization D^-1/2 (A+I) D^-1/2 as (edges, weights).

    edge_index: [2, E] (src, dst) int array.
    """
    src, dst = np.asarray(edge_index)
    if add_self_loops:
        loops = np.arange(num_nodes)
        src = np.concatenate([src, loops])
        dst = np.concatenate([dst, loops])
    deg = np.bincount(dst, minlength=num_nodes).astype(np.float64)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    w = dinv[src] * dinv[dst]
    return (jnp.asarray(np.stack([src, dst]), jnp.int32),
            jnp.asarray(w, jnp.float32))


def spmm_edges(edge_index, edge_weight, x, num_nodes: int):
    """A @ x via gather + segment_sum (the sparse aggregation path; the
    reference uses CuSparseCsrmm, src/ops/CuSparse.cu)."""
    src, dst = edge_index
    msgs = jnp.take(x, src, axis=0) * edge_weight[:, None].astype(x.dtype)
    return jax.ops.segment_sum(msgs, dst, num_segments=num_nodes)


def dense_adjacency(edge_index, edge_weight, num_nodes: int):
    a = jnp.zeros((num_nodes, num_nodes), edge_weight.dtype)
    return a.at[edge_index[1], edge_index[0]].add(edge_weight)


class GraphConv(Module):
    """GCN layer: act(Â H W + b) (Kipf & Welling; examples/gnn gnn_model)."""

    def __init__(self, in_features: int, out_features: int, bias: bool = True,
                 initializer=None, dtype=jnp.float32):
        init = initializer or xavier_uniform()
        self.w = init(next_key(), (in_features, out_features), dtype)
        self.w_axes = (None, "embed")
        self.b = zeros(None, (out_features,), dtype) if bias else None
        self.b_axes = ("embed",)
        self.in_features = in_features
        self.out_features = out_features

    def __call__(self, x, edge_index, edge_weight, *, num_nodes=None):
        n = num_nodes or x.shape[0]
        h = x @ self.w.astype(x.dtype)          # transform first: E F << N^2
        h = spmm_edges(edge_index, edge_weight, h, n)
        if self.b is not None:
            h = h + self.b.astype(h.dtype)
        return h


class GCN(Module):
    """Multi-layer GCN classifier (examples/gnn/gnn_model/GCN.py shape)."""

    def __init__(self, in_features: int, hidden: int, num_classes: int,
                 num_layers: int = 2, dropout_rate: float = 0.5,
                 dtype=jnp.float32):
        dims = [in_features] + [hidden] * (num_layers - 1) + [num_classes]
        self.convs = [GraphConv(dims[i], dims[i + 1], dtype=dtype)
                      for i in range(num_layers)]
        self.dropout_rate = dropout_rate

    def __call__(self, x, edge_index, edge_weight, *, key=None,
                 training: bool = False):
        for i, conv in enumerate(self.convs):
            x = conv(x, edge_index, edge_weight)
            if i < len(self.convs) - 1:
                x = jax.nn.relu(x)
                if training and key is not None and self.dropout_rate > 0:
                    from hetu_tpu.ops.nn import dropout
                    key, sub = jax.random.split(key)
                    x = dropout(x, self.dropout_rate, sub, training=True)
        return x


# -- 1.5D distributed aggregation ---------------------------------------------


def dist_spmm_15d(a_dense, x, mesh, *, row_axis: str = "gr",
                  col_axis: str = "gc"):
    """1.5D partitioned Z = A @ X over a (row x col) device grid
    (DistGCN_15d.py broad_func, CAGNET 1.5D algorithm).

    Device (i, j) holds A block [N/r, N/c] and X shard [N/c, F] (replicated
    along rows); each computes its partial product and one psum over the
    column axis yields the row-sharded Z — the reference's staged
    broadcast/compute loop collapses into a single XLA collective that
    rides ICI.
    """
    from jax import shard_map

    def body(a_blk, x_blk):
        partial_z = a_blk @ x_blk
        return jax.lax.psum(partial_z, col_axis)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(row_axis, col_axis), P(col_axis, None)),
        out_specs=P(row_axis, None),
    )(a_dense, x)


class DistGCN15D(Module):
    """GCN whose aggregation runs 1.5D-partitioned over a device grid.

    Dense-block variant (adjacency materialized as [N, N] blocks): right for
    the mid-size graphs the reference's DistGCN examples target, where the
    per-device block is MXU-sized.
    """

    def __init__(self, in_features: int, hidden: int, num_classes: int,
                 mesh, num_layers: int = 2, row_axis: str = "gr",
                 col_axis: str = "gc", dtype=jnp.float32):
        dims = [in_features] + [hidden] * (num_layers - 1) + [num_classes]
        init = xavier_uniform()
        self.ws = [init(next_key(), (dims[i], dims[i + 1]), dtype)
                   for i in range(num_layers)]
        self.ws_axes = [(None, None)] * num_layers
        self.bs = [zeros(None, (dims[i + 1],), dtype)
                   for i in range(num_layers)]
        self.bs_axes = [(None,)] * num_layers
        self.mesh = mesh
        self.row_axis = row_axis
        self.col_axis = col_axis

    def __call__(self, a_dense, x):
        n_layers = len(self.ws)
        for i, (w, b) in enumerate(zip(self.ws, self.bs)):
            x = x @ w.astype(x.dtype)
            x = dist_spmm_15d(a_dense, x, self.mesh,
                              row_axis=self.row_axis, col_axis=self.col_axis)
            x = x + b.astype(x.dtype)  # post-aggregation, matching GraphConv
            if i < n_layers - 1:
                x = jax.nn.relu(x)
        return x


# -- host-side neighbor sampling (GraphMix-server capability, light) ----------


class GraphIndex:
    """CSR-style in-neighbor index built ONCE per graph and reused across
    minibatch sampling calls (the per-call work then touches only the
    sampled neighborhood, not the whole edge list)."""

    def __init__(self, edge_index):
        self.src, self.dst = (np.asarray(a) for a in edge_index)
        if self.src.size:
            self.order = np.argsort(self.dst, kind="stable")
            sorted_dst = self.dst[self.order]
            self.starts = np.searchsorted(
                sorted_dst, np.arange(int(sorted_dst.max()) + 2))
        else:
            self.order = np.zeros((0,), np.int64)
            self.starts = np.zeros((1,), np.int64)

    def in_neighbors(self, v: int) -> np.ndarray:
        if v + 1 >= len(self.starts):
            return self.src[:0]
        lo, hi = self.starts[v], self.starts[v + 1]
        return self.src[self.order[lo:hi]]


def sample_subgraph(edge_index, seed_nodes, num_hops: int = 2,
                    fanout: int = 10,
                    rng: Optional[np.random.Generator] = None,
                    index: Optional[GraphIndex] = None):
    """Uniform neighbor sampling producing an induced subgraph + relabeled
    edges (the role GraphMix sampling servers play for examples/gnn;
    dataloader.py:253 GNNDataLoaderOp feeds such blocks).

    Pass a prebuilt ``GraphIndex`` when sampling repeatedly from the same
    graph — building it is the only O(E log E) step.
    Returns (node_ids [M], sub_edge_index [2, E'], seed positions).
    """
    rng = rng or np.random.default_rng()
    index = index or GraphIndex(edge_index)
    src, dst = index.src, index.dst
    seeds = np.unique(np.asarray(seed_nodes))
    if src.size == 0:
        node_ids = np.sort(seeds).astype(np.int64)
        seed_pos = np.searchsorted(node_ids, np.asarray(seed_nodes))
        return node_ids, np.zeros((2, 0), np.int32), seed_pos.astype(np.int32)
    frontier = seeds
    nodes = set(frontier.tolist())
    for _ in range(num_hops):
        nxt = []
        for v in frontier:
            neigh = index.in_neighbors(v)
            if len(neigh) > fanout:
                neigh = rng.choice(neigh, fanout, replace=False)
            if len(neigh):
                nxt.append(neigh)
        if not nxt:
            break
        frontier = np.unique(np.concatenate(nxt))
        frontier = frontier[~np.isin(frontier, list(nodes))]
        nodes.update(frontier.tolist())
    node_ids = np.sort(np.fromiter(nodes, dtype=np.int64))
    # relabel via binary search over the (small) sampled node set — no
    # O(max_node_id) table allocation
    sub_src_parts, sub_dst_parts = [], []
    for v in node_ids:
        neigh = index.in_neighbors(int(v))
        keep = np.isin(neigh, node_ids, assume_unique=False)
        kept = neigh[keep]
        sub_src_parts.append(np.searchsorted(node_ids, kept))
        sub_dst_parts.append(
            np.full(len(kept), np.searchsorted(node_ids, v), np.int64))
    if sub_src_parts:
        sub_edges = np.stack([np.concatenate(sub_src_parts),
                              np.concatenate(sub_dst_parts)])
    else:
        sub_edges = np.zeros((2, 0), np.int64)
    seed_pos = np.searchsorted(node_ids, np.asarray(seed_nodes))
    return node_ids, sub_edges.astype(np.int32), seed_pos.astype(np.int32)
