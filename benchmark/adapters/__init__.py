"""The system under test, built from the benchmark's own weights.

One module a model family (the configuration's ``family`` key): it builds
the program's model, fills it with the leaves that the family's reference
made from the seed, and wraps it in the entry point that users call
(``exec.Trainer``, ``serve.ServingEngine``).  Nothing else of the program is
touched by the benchmark."""

from __future__ import annotations

import re

import jax

_BLOCK = re.compile(r"^(?P<pre>.*\.)?blocks\.(?P<i>\d+)\.(?P<rest>.+)$")
BLOCK_LEAVES = {
    "attn.wqkv": "wqkv", "attn.bqkv": "bqkv", "attn.wo": "wo",
    "attn.bo": "bo", "ln1.scale": "ln1_g", "ln1.bias": "ln1_b",
    "mlp.w_in": "w_in", "mlp.b_in": "b_in", "mlp.w_out": "w_out",
    "mlp.b_out": "b_out", "ln2.scale": "ln2_g", "ln2.bias": "ln2_b",
}


def leaf_paths(tree) -> list:
    """Dotted path of every leaf, in flatten order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [".".join(str(getattr(k, "name", getattr(k, "idx", getattr(
        k, "key", k)))) for k in path) for path, _ in flat]


def ref_name(path: str, top: dict) -> tuple:
    """(reference leaf, layer or None) of a program leaf's path."""
    m = _BLOCK.match(path)
    if m:
        return BLOCK_LEAVES[m.group("rest")], int(m.group("i"))
    return top[path], None


def fill(skeleton, weights: dict, top: dict):
    """The program's model tree with every leaf taken from ``weights``."""
    flat, treedef = jax.tree_util.tree_flatten(skeleton)
    leaves = []
    for path, like in zip(leaf_paths(skeleton), flat):
        name, layer = ref_name(path, top)
        leaf = weights[name] if layer is None else weights[name][layer]
        if leaf.shape != like.shape or leaf.dtype != like.dtype:
            raise ValueError(f"{path}: the program holds {like.dtype}"
                             f"{like.shape}, the reference made "
                             f"{leaf.dtype}{leaf.shape}")
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def gather(tree, top: dict) -> dict:
    """The reverse of :func:`fill`: reference name -> leaf, the blocks'
    leaves as lists by layer (not stacked: no copy is made)."""
    out = {}
    for path, leaf in zip(leaf_paths(tree), jax.tree_util.tree_leaves(tree)):
        name, layer = ref_name(path, top)
        if layer is None:
            out[name] = leaf
        else:
            out.setdefault(name, {})[layer] = leaf
    return {n: ([v[i] for i in range(len(v))] if isinstance(v, dict) else v)
            for n, v in out.items()}
