"""Export hetu_tpu models/functions to ONNX.

Counterpart of the reference's ``hetu2onnx`` (python/hetu/onnx/hetu2onnx.py +
per-op handlers in onnx/onnx_opset/).  Where the reference walks its
define-then-run Op DAG, here the model is traced to a **jaxpr** (the graph XLA
itself consumes) and each jax primitive is lowered to ONNX nodes.  Sub-jaxprs
(pjit, custom_jvp/vjp, remat) are inlined; equations whose inputs are all
known constants are folded eagerly so shape/iota machinery never reaches the
ONNX graph.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.core.module import Module, named_parameters
from hetu_tpu.interop import onnx_pb as pb

__all__ = ["export_fn", "export_module", "save_model"]


class _Exporter:
    def __init__(self, const_names: dict | None = None):
        self.nodes: list[pb.NodeProto] = []
        self.initializers: dict[str, np.ndarray] = {}
        self.names: dict[int, str] = {}   # id(jaxpr var) -> onnx name
        self.consts: dict[int, np.ndarray] = {}  # id(var) -> known value
        self.const_names = const_names or {}  # id(array) -> preferred name
        self.counter = itertools.count()

    # -- naming / plumbing -----------------------------------------------------

    def fresh(self, hint: str = "t") -> str:
        return f"{hint}_{next(self.counter)}"

    def emit(self, op: str, inputs: list[str], n_out: int = 1,
             hint: str | None = None, **attrs) -> list[str]:
        outs = [self.fresh(hint or op.lower()) for _ in range(n_out)]
        attributes = tuple(pb.AttributeProto.make(k, v)
                           for k, v in attrs.items() if v is not None)
        self.nodes.append(pb.NodeProto(
            op_type=op, inputs=tuple(inputs), outputs=tuple(outs),
            name=self.fresh(f"n_{op}"), attributes=attributes))
        return outs

    def const(self, arr, hint: str = "c") -> str:
        """Register a constant as an initializer, return its name."""
        arr = np.asarray(arr)
        name = self.fresh(hint)
        self.initializers[name] = arr
        return name

    def var_name(self, v) -> str:
        from jax.extend.core import Literal
        if isinstance(v, Literal):
            return self.const(np.asarray(v.val), "lit")
        return self.names[id(v)]

    def var_const(self, v):
        """Concrete value of a jaxpr atom if known, else None."""
        from jax.extend.core import Literal
        if isinstance(v, Literal):
            return np.asarray(v.val)
        return self.consts.get(id(v))

    # -- jaxpr walk ------------------------------------------------------------

    def run(self, jaxpr, consts, input_names: list[str]) -> list[str]:
        for v, c in zip(jaxpr.constvars, consts):
            arr = np.asarray(c)
            self.consts[id(v)] = arr
            preferred = self.const_names.get(id(c))
            if preferred is not None and preferred not in self.initializers:
                self.initializers[preferred] = arr
                self.names[id(v)] = preferred
            else:
                self.names[id(v)] = self.const(arr, "w")
        for v, name in zip(jaxpr.invars, input_names):
            self.names[id(v)] = name
        for eqn in jaxpr.eqns:
            self.eqn(eqn)
        return [self.var_name(v) for v in jaxpr.outvars]

    def _inline(self, eqn, inner):
        # inner may be a ClosedJaxpr (pjit/custom_jvp) or an open core.Jaxpr
        # (remat2 stores params['jaxpr'] unclosed)
        if hasattr(inner, "jaxpr"):
            jaxpr, consts = inner.jaxpr, inner.consts
        else:
            jaxpr, consts = inner, ()
        in_names = [self.var_name(v) for v in eqn.invars]
        sub_outs = self.run_sub(jaxpr, consts, in_names)
        for v, name in zip(eqn.outvars, sub_outs):
            self.names[id(v)] = name

    def run_sub(self, jaxpr, consts, input_names):
        saved_names = dict(self.names)
        outs = self.run(jaxpr, consts, input_names)
        # keep emitted nodes; restore outer scope names not overwritten
        self.names.update(saved_names)
        return outs

    def eqn(self, eqn) -> None:
        prim = eqn.primitive.name

        # inline wrappers
        if prim in ("pjit", "jit", "closed_call", "core_call", "remat",
                    "remat2", "checkpoint", "custom_vjp_call_jaxpr",
                    "xla_call"):
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            self._inline(eqn, inner)
            return
        if prim in ("custom_jvp_call", "custom_vjp_call"):
            inner = eqn.params.get("call_jaxpr")
            self._inline(eqn, inner)
            return

        # constant folding: every input known -> evaluate eagerly
        in_consts = [self.var_const(v) for v in eqn.invars]
        if all(c is not None for c in in_consts):
            outs = eqn.primitive.bind(
                *[jnp.asarray(c) for c in in_consts], **eqn.params)
            if not eqn.primitive.multiple_results:
                outs = [outs]
            for v, o in zip(eqn.outvars, outs):
                o = np.asarray(o)
                self.consts[id(v)] = o
                self.names[id(v)] = self.const(o, "fold")
            return

        handler = _HANDLERS.get(prim)
        if handler is None:
            raise NotImplementedError(
                f"ONNX export: unsupported primitive '{prim}'")
        ins = [self.var_name(v) for v in eqn.invars]
        outs = handler(self, eqn, ins)
        if isinstance(outs, str):
            outs = [outs]
        for v, name in zip(eqn.outvars, outs):
            self.names[id(v)] = name


# --- primitive handlers -------------------------------------------------------

_HANDLERS: dict[str, Callable] = {}


def handler(*prims):
    def deco(fn):
        for p in prims:
            _HANDLERS[p] = fn
        return fn
    return deco


_UNARY = {
    "neg": "Neg", "exp": "Exp", "log": "Log", "tanh": "Tanh",
    "logistic": "Sigmoid", "sqrt": "Sqrt", "abs": "Abs", "sign": "Sign",
    "floor": "Floor", "ceil": "Ceil", "round": "Round", "sin": "Sin",
    "cos": "Cos", "erf": "Erf", "not": "Not",
}
for _prim, _op in _UNARY.items():
    def _make(_op):
        def h(ex, eqn, ins):
            return ex.emit(_op, ins)
        return h
    _HANDLERS[_prim] = _make(_op)

_BINARY = {
    "add": "Add", "sub": "Sub", "mul": "Mul", "div": "Div",
    "max": "Max", "min": "Min", "pow": "Pow", "and": "And", "or": "Or",
    "xor": "Xor",
}
for _prim, _op in _BINARY.items():
    def _make2(_op):
        def h(ex, eqn, ins):
            return ex.emit(_op, ins)
        return h
    _HANDLERS[_prim] = _make2(_op)


_CMP = {"eq": ("Equal", False), "ne": ("Equal", True),
        "lt": ("Less", False), "le": ("LessOrEqual", False),
        "gt": ("Greater", False), "ge": ("GreaterOrEqual", False)}
for _prim, (_op, _negate) in _CMP.items():
    def _makec(_op, _negate):
        def h(ex, eqn, ins):
            out = ex.emit(_op, ins)
            if _negate:
                out = ex.emit("Not", out)
            return out
        return h
    _HANDLERS[_prim] = _makec(_op, _negate)


@handler("rsqrt")
def _rsqrt(ex, eqn, ins):
    s = ex.emit("Sqrt", ins)
    return ex.emit("Reciprocal", s)


@handler("rem")
def _rem(ex, eqn, ins):
    # lax.rem takes the dividend's sign => ONNX Mod with fmod=1
    return ex.emit("Mod", ins, fmod=1)


@handler("is_finite")
def _is_finite(ex, eqn, ins):
    inf = ex.emit("IsInf", ins)
    nan = ex.emit("IsNaN", ins)
    bad = ex.emit("Or", [inf[0], nan[0]])
    return ex.emit("Not", bad)


@handler("integer_pow")
def _integer_pow(ex, eqn, ins):
    y = eqn.params["y"]
    dt = np.dtype(eqn.invars[0].aval.dtype)
    p = ex.const(np.asarray(y, dt if dt.kind == "f" else np.int64), "pow")
    return ex.emit("Pow", [ins[0], p])


@handler("stop_gradient")
def _stopgrad(ex, eqn, ins):
    return ex.emit("Identity", ins)


@handler("copy")
def _copy(ex, eqn, ins):
    return ex.emit("Identity", ins)


@handler("convert_element_type")
def _cast(ex, eqn, ins):
    to = pb.DTYPE_TO_ONNX[np.dtype(eqn.params["new_dtype"])]
    return ex.emit("Cast", ins, to=int(to))


@handler("select_n")
def _select(ex, eqn, ins):
    if len(ins) != 3:
        raise NotImplementedError("select_n with >2 cases")
    # select_n(pred, on_false, on_true); ONNX Where(cond, X, Y) -> X if cond
    return ex.emit("Where", [ins[0], ins[2], ins[1]])


@handler("reshape")
def _reshape(ex, eqn, ins):
    shape = ex.const(np.asarray(eqn.params["new_sizes"], np.int64), "shape")
    return ex.emit("Reshape", [ins[0], shape])


@handler("squeeze")
def _squeeze(ex, eqn, ins):
    shape = ex.const(np.asarray(eqn.outvars[0].aval.shape, np.int64), "shape")
    return ex.emit("Reshape", [ins[0], shape])


@handler("expand_dims")
def _expand_dims(ex, eqn, ins):
    shape = ex.const(np.asarray(eqn.outvars[0].aval.shape, np.int64), "shape")
    return ex.emit("Reshape", [ins[0], shape])


@handler("transpose")
def _transpose(ex, eqn, ins):
    return ex.emit("Transpose", ins, perm=list(eqn.params["permutation"]))


@handler("broadcast_in_dim")
def _broadcast(ex, eqn, ins):
    out_shape = eqn.params["shape"]
    bdims = eqn.params["broadcast_dimensions"]
    mid = [1] * len(out_shape)
    for src_axis, dst_axis in enumerate(bdims):
        mid[dst_axis] = eqn.invars[0].aval.shape[src_axis]
    x = ins[0]
    if tuple(mid) != tuple(eqn.invars[0].aval.shape):
        shape = ex.const(np.asarray(mid, np.int64), "shape")
        x = ex.emit("Reshape", [x, shape])[0]
    if tuple(mid) != tuple(out_shape):
        target = ex.const(np.asarray(out_shape, np.int64), "shape")
        x = ex.emit("Expand", [x, target])[0]
    else:
        x = ex.emit("Identity", [x])[0]
    return [x]


@handler("concatenate")
def _concat(ex, eqn, ins):
    return ex.emit("Concat", ins, axis=int(eqn.params["dimension"]))


@handler("slice")
def _slice(ex, eqn, ins):
    starts = ex.const(np.asarray(eqn.params["start_indices"], np.int64), "st")
    ends = ex.const(np.asarray(eqn.params["limit_indices"], np.int64), "en")
    axes = ex.const(np.arange(len(eqn.params["start_indices"]), dtype=np.int64), "ax")
    strides = eqn.params["strides"] or [1] * len(eqn.params["start_indices"])
    steps = ex.const(np.asarray(strides, np.int64), "sp")
    return ex.emit("Slice", [ins[0], starts, ends, axes, steps])


@handler("rev")
def _rev(ex, eqn, ins):
    dims = eqn.params["dimensions"]
    shape = eqn.invars[0].aval.shape
    starts = ex.const(np.asarray([shape[d] - 1 for d in dims], np.int64), "st")
    ends = ex.const(np.asarray([-(shape[d] + 1) for d in dims], np.int64), "en")
    axes = ex.const(np.asarray(list(dims), np.int64), "ax")
    steps = ex.const(np.asarray([-1] * len(dims), np.int64), "sp")
    return ex.emit("Slice", [ins[0], starts, ends, axes, steps])


@handler("pad")
def _pad(ex, eqn, ins):
    cfg = eqn.params["padding_config"]
    if any(i != 0 for _, _, i in cfg):
        raise NotImplementedError("interior padding not supported in ONNX export")
    if any(l < 0 or h < 0 for l, h, _ in cfg):
        raise NotImplementedError("negative padding not supported in ONNX export")
    pads = [l for l, _, _ in cfg] + [h for _, h, _ in cfg]
    pads_c = ex.const(np.asarray(pads, np.int64), "pads")
    return ex.emit("Pad", [ins[0], pads_c, ins[1]], mode="constant")


@handler("iota")
def _iota(ex, eqn, ins):
    # no dynamic inputs -> materialize
    arr = np.asarray(jax.lax.iota(eqn.params["dtype"], eqn.params["shape"][eqn.params["dimension"]]))
    shape = eqn.params["shape"]
    dim = eqn.params["dimension"]
    view = [1] * len(shape)
    view[dim] = shape[dim]
    arr = np.broadcast_to(arr.reshape(view), shape)
    return [ex.const(arr, "iota")]


def _reduce(op_type, axes_as_input):
    def h(ex, eqn, ins):
        axes = [int(a) for a in eqn.params["axes"]]
        if axes_as_input:
            ax = ex.const(np.asarray(axes, np.int64), "axes")
            return ex.emit(op_type, [ins[0], ax], keepdims=0)
        return ex.emit(op_type, ins, axes=axes, keepdims=0)
    return h


@handler("split")
def _split(ex, eqn, ins):
    sizes = list(eqn.params["sizes"])
    axis = eqn.params["axis"]
    split_in = ex.const(np.asarray(sizes, np.int64), "sizes")
    return ex.emit("Split", [ins[0], split_in], n_out=len(sizes),
                   hint="split", axis=axis)


_SCAN_UNROLL_LIMIT = 256


@handler("scan")
def _scan(ex, eqn, ins):
    """lax.scan exported by unrolling (static length): per step, Gather the
    xs slice, inline the body jaxpr, chain the carry, and Concat the
    stacked ys.  Covers the RNN/LSTM/GRU recurrences and scan-over-layers
    stacks; bounded by _SCAN_UNROLL_LIMIT to keep graphs sane."""
    p = eqn.params
    body = p["jaxpr"]  # ClosedJaxpr: (consts, carry, x_t) -> (carry, y_t)
    n_const, n_carry = p["num_consts"], p["num_carry"]
    length, reverse = p["length"], p["reverse"]
    if length > _SCAN_UNROLL_LIMIT:
        raise NotImplementedError(
            f"ONNX export: scan of length {length} exceeds the unroll limit "
            f"({_SCAN_UNROLL_LIMIT})")
    if length == 0:
        raise NotImplementedError(
            "ONNX export: zero-length scan has no representable ys")
    const_names = ins[:n_const]
    carry = list(ins[n_const:n_const + n_carry])
    xs = ins[n_const + n_carry:]
    n_y = len(eqn.outvars) - n_carry
    ys_steps: list[list[str]] = [[] for _ in range(n_y)]
    steps = range(length - 1, -1, -1) if reverse else range(length)
    axes0 = ex.const(np.asarray([0], np.int64), "ax0")
    for t in steps:
        idx = ex.const(np.asarray(t, np.int64), "t")
        # scalar-index Gather on axis 0 drops the time axis, matching the
        # body's per-step slice
        x_slices = [ex.emit("Gather", [xn, idx], hint="xslice", axis=0)[0]
                    for xn in xs]
        outs = ex.run_sub(body.jaxpr, body.consts,
                          const_names + carry + x_slices)
        carry = list(outs[:n_carry])
        for i, yn in enumerate(outs[n_carry:]):
            ys_steps[i].append(
                ex.emit("Unsqueeze", [yn, axes0], hint="ystep")[0])
    ys = []
    for names in ys_steps:
        if reverse:
            names = list(reversed(names))  # ys align with xs order
        ys.append(names[0] if length == 1
                  else ex.emit("Concat", names, hint="ys", axis=0)[0])
    return carry + ys


_HANDLERS["reduce_sum"] = _reduce("ReduceSum", True)     # opset 13: axes input
_HANDLERS["reduce_max"] = _reduce("ReduceMax", False)
_HANDLERS["reduce_min"] = _reduce("ReduceMin", False)
_HANDLERS["reduce_prod"] = _reduce("ReduceProd", False)


@handler("reduce_and")
def _reduce_and(ex, eqn, ins):
    cast = ex.emit("Cast", ins, to=int(pb.INT32))
    ax = [int(a) for a in eqn.params["axes"]]
    red = ex.emit("ReduceMin", cast, axes=ax, keepdims=0)
    return ex.emit("Cast", red, to=int(pb.BOOL))


@handler("reduce_or")
def _reduce_or(ex, eqn, ins):
    cast = ex.emit("Cast", ins, to=int(pb.INT32))
    ax = [int(a) for a in eqn.params["axes"]]
    red = ex.emit("ReduceMax", cast, axes=ax, keepdims=0)
    return ex.emit("Cast", red, to=int(pb.BOOL))


@handler("argmax")
def _argmax(ex, eqn, ins):
    out = ex.emit("ArgMax", ins, axis=int(eqn.params["axes"][0]), keepdims=0)
    to = pb.DTYPE_TO_ONNX[np.dtype(eqn.params["index_dtype"])]
    return ex.emit("Cast", out, to=int(to))


@handler("argmin")
def _argmin(ex, eqn, ins):
    out = ex.emit("ArgMin", ins, axis=int(eqn.params["axes"][0]), keepdims=0)
    to = pb.DTYPE_TO_ONNX[np.dtype(eqn.params["index_dtype"])]
    return ex.emit("Cast", out, to=int(to))


@handler("cumsum")
def _cumsum(ex, eqn, ins):
    ax = ex.const(np.asarray(eqn.params["axis"], np.int64), "axis")
    reverse = 1 if eqn.params.get("reverse") else 0
    return ex.emit("CumSum", [ins[0], ax], reverse=reverse)


@handler("dot_general")
def _dot_general(ex, eqn, ins):
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    lr, rr = len(lhs.shape), len(rhs.shape)
    # standard matmul pattern: batch dims leading and aligned on BOTH sides,
    # exactly one free dim each, contracting lhs last with rhs second-to-last
    # — anything else (e.g. rank-3 rhs with no batch dims) must go through
    # Einsum, since ONNX MatMul would broadcast the extra dims differently.
    std = (list(lb) == list(range(len(lb)))
           and list(rb) == list(range(len(rb)))
           and lr - len(lb) == 2 and rr - len(rb) == 2
           and list(lc) == [lr - 1]
           and list(rc) == [rr - 2])
    if std:
        return ex.emit("MatMul", ins)
    # general: einsum
    letters = "abcdefghijklmnopqrstuvwxyz"
    it = iter(letters)
    l_sub = [None] * lr
    r_sub = [None] * rr
    for i, j in zip(lb, rb):
        c = next(it)
        l_sub[i] = r_sub[j] = c
    for i, j in zip(lc, rc):
        c = next(it)
        l_sub[i] = r_sub[j] = c
    for i in range(lr):
        if l_sub[i] is None:
            l_sub[i] = next(it)
    for j in range(rr):
        if r_sub[j] is None:
            r_sub[j] = next(it)
    out_sub = ([l_sub[i] for i in lb]
               + [l_sub[i] for i in range(lr) if i not in lb and i not in lc]
               + [r_sub[j] for j in range(rr) if j not in rb and j not in rc])
    eq = f"{''.join(l_sub)},{''.join(r_sub)}->{''.join(out_sub)}"
    return ex.emit("Einsum", ins, equation=eq)


def _space_to_nchw(ex, x, rank):
    """NHWC->NCHW transpose node (2d: rank 4)."""
    perm = [0, rank - 1] + list(range(1, rank - 1))
    return ex.emit("Transpose", [x], perm=perm)[0]


def _nchw_to_space(ex, x, rank):
    perm = [0] + list(range(2, rank)) + [1]
    return ex.emit("Transpose", [x], perm=perm)[0]


@handler("conv_general_dilated")
def _conv(ex, eqn, ins):
    dn = eqn.params["dimension_numbers"]
    lhs_spec, rhs_spec, out_spec = dn
    if any(d != 1 for d in eqn.params.get("lhs_dilation", ())):
        raise NotImplementedError(
            "ONNX export: input-dilated (transposed) convolution")
    if eqn.params.get("batch_group_count", 1) != 1:
        raise NotImplementedError("ONNX export: batch_group_count > 1")
    rank = len(eqn.invars[0].aval.shape)
    nd = rank - 2
    # we emit for the layouts hetu_tpu.ops.nn uses: NHWC x HWIO -> NHWC
    # and the already-NCHW case passes through.
    x, w = ins
    if lhs_spec[1] != 1:  # feature dim not at position 1 => NHWC-style
        x = _space_to_nchw(ex, x, rank)
    # kernel: ONNX wants OIHW == (out_c, in_c, *spatial)
    # jax rhs_spec = (out_feature_dim_pos, in_feature_dim_pos, *spatial_pos)
    o_dim, i_dim = rhs_spec[0], rhs_spec[1]
    spatial_dims = [d for d in range(rank) if d not in (o_dim, i_dim)]
    perm = [o_dim, i_dim] + spatial_dims
    if perm != list(range(rank)):
        w = ex.emit("Transpose", [w], perm=perm)[0]
    pads = eqn.params["padding"]
    onnx_pads = [p[0] for p in pads] + [p[1] for p in pads]
    groups = int(eqn.params.get("feature_group_count", 1))
    out = ex.emit("Conv", [x, w],
                  strides=[int(s) for s in eqn.params["window_strides"]],
                  dilations=[int(d) for d in eqn.params["rhs_dilation"]],
                  pads=onnx_pads, group=groups)[0]
    if out_spec[1] != 1:
        out = _nchw_to_space(ex, out, rank)
    else:
        out = ex.emit("Identity", [out])[0]
    return [out]


@handler("reduce_window_max")
def _maxpool(ex, eqn, ins):
    return _pool(ex, eqn, ins, "MaxPool")


@handler("reduce_window_sum")
def _sumpool(ex, eqn, ins):
    # AveragePool(count_include_pad=1) * window_size == sum pool: padded
    # positions contribute 0 to the sum and the divisor is the full window.
    out = _pool(ex, eqn, ins, "AveragePool", count_include_pad=1)
    dims = eqn.params["window_dimensions"]
    k = float(np.prod(dims))
    dt = np.dtype(eqn.outvars[0].aval.dtype)
    c = ex.const(np.asarray(k, dt), "k")
    return ex.emit("Mul", [out[0], c])


def _pool(ex, eqn, ins, op_type, **extra):
    dims = eqn.params["window_dimensions"]
    strides = eqn.params["window_strides"]
    padding = eqn.params["padding"]
    rank = len(dims)
    # NHWC windows: (1, h, w, 1)
    if dims[0] != 1 or dims[-1] != 1:
        raise NotImplementedError("pooling over batch/channel dims")
    x = _space_to_nchw(ex, ins[0], rank)
    spatial = list(range(1, rank - 1))
    kernel = [int(dims[d]) for d in spatial]
    strd = [int(strides[d]) for d in spatial]
    pads = [int(padding[d][0]) for d in spatial] + [int(padding[d][1]) for d in spatial]
    out = ex.emit(op_type, [x], kernel_shape=kernel, strides=strd, pads=pads,
                  **extra)[0]
    return [_nchw_to_space(ex, out, rank)]


@handler("gather")
def _gather(ex, eqn, ins):
    # support the jnp.take(axis=k)/embedding-lookup pattern produced by
    # ops/embed.py: offset_dims cover all but one dim, one collapsed slice dim
    dn = eqn.params["dimension_numbers"]
    operand = eqn.invars[0].aval
    idx = eqn.invars[1].aval
    slice_sizes = eqn.params["slice_sizes"]
    if (len(dn.start_index_map) == 1 and len(dn.collapsed_slice_dims) == 1
            and dn.start_index_map == dn.collapsed_slice_dims):
        axis = dn.start_index_map[0]
        full = all(slice_sizes[d] == operand.shape[d]
                   for d in range(len(operand.shape)) if d != axis)
        if full and idx.shape and idx.shape[-1] == 1:
            sq_shape = ex.const(np.asarray(idx.shape[:-1], np.int64), "shape")
            flat_idx = ex.emit("Reshape", [ins[1], sq_shape])[0]
            return ex.emit("Gather", [ins[0], flat_idx], axis=int(axis))
    # general fallback for statically-known indices: replay the gather on a
    # flat-position iota to obtain the output->operand element map, then a
    # single flat Gather reproduces it for any operand values.
    idx_val = ex.var_const(eqn.invars[1])
    if idx_val is not None:
        positions = np.arange(int(np.prod(operand.shape)),
                              dtype=np.int64).reshape(operand.shape)
        pos_map = np.asarray(eqn.primitive.bind(
            jnp.asarray(positions), jnp.asarray(idx_val), **eqn.params))
        flat = ex.emit("Reshape", [ins[0], ex.const(np.asarray([-1], np.int64), "flat")])[0]
        return ex.emit("Gather", [flat, ex.const(pos_map, "posmap")], axis=0)
    raise NotImplementedError(
        "gather with dynamic indices outside the take/embedding pattern "
        "is not supported in ONNX export")


@handler("dynamic_slice")
def _dynamic_slice(ex, eqn, ins):
    # jax clamps each start into [0, dim-size].  Emit per-axis:
    # idx = clamp(start) + arange(size); Gather(axis) — dynamic-index Gather
    # is valid ONNX, indices stay in-bounds, and the importer handles it
    # jittably (jnp.take).  Axes taken in full are skipped.
    sizes = eqn.params["slice_sizes"]
    shape = eqn.invars[0].aval.shape
    x = ins[0]
    for axis, (size, dim, start_in) in enumerate(zip(sizes, shape, ins[1:])):
        if size == dim:
            continue
        s = ex.emit("Cast", [start_in], to=int(pb.INT64))[0]
        lo = ex.const(np.asarray(0, np.int64), "lo")
        hi = ex.const(np.asarray(dim - size, np.int64), "hi")
        s = ex.emit("Max", [s, lo])[0]
        s = ex.emit("Min", [s, hi])[0]
        idx = ex.emit("Add", [s, ex.const(np.arange(size, dtype=np.int64), "ar")])[0]
        x = ex.emit("Gather", [x, idx], axis=axis)[0]
    return [ex.emit("Identity", [x])[0]]


@handler("clamp")
def _clamp(ex, eqn, ins):
    # lax.clamp(min, x, max)
    return ex.emit("Clip", [ins[1], ins[0], ins[2]])


@handler("square")
def _square(ex, eqn, ins):
    return ex.emit("Mul", [ins[0], ins[0]])


@handler("exp2")
def _exp2(ex, eqn, ins):
    dt = np.dtype(eqn.invars[0].aval.dtype)
    two = ex.const(np.asarray(2.0, dt), "two")
    return ex.emit("Pow", [two, ins[0]])


@handler("sort")
def _sort(ex, eqn, ins):
    if len(ins) != 1:
        raise NotImplementedError("multi-operand sort")
    dim = int(eqn.params["dimension"])
    shape = eqn.invars[0].aval.shape
    k = ex.const(np.asarray([shape[dim]], np.int64), "k")
    vals, _idx = ex.emit("TopK", [ins[0], k], n_out=2, axis=dim, largest=0)
    return [vals]


# --- public API ---------------------------------------------------------------


def export_fn(fn: Callable, *example_args, name: str = "hetu_tpu",
              const_names: dict | None = None) -> pb.ModelProto:
    """Trace ``fn(*example_args)`` and convert the jaxpr to an ONNX model.

    All traced-constant arrays (closure captures) become initializers;
    positional args become graph inputs.  ``const_names`` optionally maps
    ``id(array)`` of a closure constant to the initializer name to use
    (export_module passes parameter paths this way).
    """
    flat_args, in_tree = jax.tree_util.tree_flatten(example_args)

    def flat_fn(*flat):
        args = jax.tree_util.tree_unflatten(in_tree, flat)
        out = fn(*args)
        return jax.tree_util.tree_leaves(out)

    closed = jax.make_jaxpr(flat_fn)(*flat_args)
    ex = _Exporter(const_names)
    input_names = [f"input_{i}" for i in range(len(flat_args))]
    out_names = ex.run(closed.jaxpr, closed.consts, input_names)

    inputs = tuple(
        pb.ValueInfoProto(name=n,
                          elem_type=pb.DTYPE_TO_ONNX[np.dtype(a.dtype)],
                          shape=tuple(int(d) for d in np.shape(a)))
        for n, a in zip(input_names, flat_args))
    out_avals = [v.aval for v in closed.jaxpr.outvars]
    outputs = tuple(
        pb.ValueInfoProto(name=n,
                          elem_type=pb.DTYPE_TO_ONNX[np.dtype(a.dtype)],
                          shape=tuple(int(d) for d in a.shape))
        for n, a in zip(out_names, out_avals))
    inits = tuple(pb.tensor_from_numpy(k, v) for k, v in ex.initializers.items())
    graph = pb.GraphProto(name=name, nodes=tuple(ex.nodes),
                          initializers=inits, inputs=inputs, outputs=outputs)
    return pb.ModelProto(graph=graph)


def export_module(model: Module, *example_inputs, name: str | None = None,
                  apply: Callable | None = None) -> pb.ModelProto:
    """Export a ``Module``: parameters become initializers named by their
    qualified parameter path, the example inputs become graph inputs.
    ``apply(model, *inputs)`` defaults to ``model(*inputs)``."""
    apply = apply or (lambda m, *xs: m(*xs))
    fn = lambda *xs: apply(model, *xs)  # model enters via closure -> constvars
    const_names = {id(leaf): pname for pname, leaf in named_parameters(model)}
    return export_fn(fn, *example_inputs, name=name or type(model).__name__,
                     const_names=const_names)


def save_model(proto: pb.ModelProto, path: str) -> None:
    with open(path, "wb") as f:
        f.write(proto.encode())
