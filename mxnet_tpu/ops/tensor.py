"""Tensor operators (parity: reference ``src/operator/tensor/*`` — 57 files of
mshadow/CUDA kernels rebuilt as traceable JAX compute rules).

Gradients are NOT hand-written per-op as in the reference
(``elemwise_binary_op.h`` etc.): every rule here is jax-differentiable, so the
executor's vjp pass derives backward for free.  Ops with MXNet-specific
gradient semantics (loss layers, BlockGrad) live in ``nn.py`` with
``jax.custom_vjp``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from .registry import ParamSpec as P
from .registry import register

# ----------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------


def _unary(name, fn, aliases=()):
    @register(name, aliases=aliases, arg_names=["data"])
    def _op(attrs, x, _fn=fn):
        return _fn(x)

    return _op


def _binary(name, fn, aliases=()):
    @register(name, aliases=aliases, arg_names=["lhs", "rhs"])
    def _op(attrs, l, r, _fn=fn):
        return _fn(l, r)

    return _op


def _binary_scalar(name, fn, aliases=()):
    @register(
        name,
        aliases=aliases,
        arg_names=["data"],
        params={"scalar": P("float", 0.0, required=True)},
    )
    def _op(attrs, x, _fn=fn):
        return _fn(x, jnp.asarray(attrs["scalar"], dtype=x.dtype))

    return _op


def _to_dtype(x, dtype):
    return x.astype(dtype) if dtype else x


# ----------------------------------------------------------------------
# unary math (reference src/operator/tensor/elemwise_unary_op.cc)
# ----------------------------------------------------------------------

_unary("abs", jnp.abs)
_unary("sign", jnp.sign)
_unary("rint", jnp.rint)
_unary("round", jnp.round)
_unary("ceil", jnp.ceil)
_unary("floor", jnp.floor)
_unary("fix", jnp.trunc, aliases=["trunc"])
_unary("sqrt", jnp.sqrt)
_unary("rsqrt", lambda x: 1.0 / jnp.sqrt(x))
_unary("square", jnp.square)
_unary("exp", jnp.exp)
_unary("log", jnp.log)
_unary("log10", jnp.log10)
_unary("log2", jnp.log2)
_unary("log1p", jnp.log1p)
_unary("expm1", jnp.expm1)
_unary("sin", jnp.sin)
_unary("cos", jnp.cos)
_unary("tan", jnp.tan)
_unary("arcsin", jnp.arcsin)
_unary("arccos", jnp.arccos)
_unary("arctan", jnp.arctan)
_unary("sinh", jnp.sinh)
_unary("cosh", jnp.cosh)
_unary("tanh", jnp.tanh)
_unary("arcsinh", jnp.arcsinh)
_unary("arccosh", jnp.arccosh)
_unary("arctanh", jnp.arctanh)
_unary("sigmoid", jax.nn.sigmoid)
_unary("relu", jax.nn.relu)
_unary("softsign", jax.nn.soft_sign)
_unary("reciprocal", lambda x: 1.0 / x)
_unary("negative", jnp.negative, aliases=["_neg"])
_unary("degrees", jnp.degrees)
_unary("radians", jnp.radians)
_unary("gamma", lambda x: jnp.exp(jax.scipy.special.gammaln(x)))
_unary("gammaln", jax.scipy.special.gammaln)
_unary("_copy", lambda x: x, aliases=["identity"])
_unary("zeros_like", jnp.zeros_like)
_unary("ones_like", jnp.ones_like)
_unary("logical_not", lambda x: (x == 0).astype(x.dtype))

# ----------------------------------------------------------------------
# binary elemwise + scalar (reference elemwise_binary_{op,scalar_op}.cc)
# ----------------------------------------------------------------------

_binary("elemwise_add", jnp.add, aliases=["_plus", "_add"])
_binary("elemwise_sub", jnp.subtract, aliases=["_minus", "_sub"])
_binary("elemwise_mul", jnp.multiply, aliases=["_mul"])
_binary("elemwise_div", jnp.divide, aliases=["_div"])
_binary("_power", jnp.power, aliases=["pow"])
_binary("_maximum", jnp.maximum)
_binary("_minimum", jnp.minimum)
_binary("_hypot", jnp.hypot)
_binary("_mod", jnp.mod)


def _cmp(fn):
    return lambda l, r: fn(l, r).astype(l.dtype if hasattr(l, "dtype") else "float32")


_binary("_equal", _cmp(jnp.equal))
_binary("_not_equal", _cmp(jnp.not_equal))
_binary("_greater", _cmp(jnp.greater))
_binary("_greater_equal", _cmp(jnp.greater_equal))
_binary("_lesser", _cmp(jnp.less))
_binary("_lesser_equal", _cmp(jnp.less_equal))

_binary_scalar("_plus_scalar", jnp.add)
_binary_scalar("_minus_scalar", jnp.subtract)
_binary_scalar("_rminus_scalar", lambda x, s: s - x)
_binary_scalar("_mul_scalar", jnp.multiply)
_binary_scalar("_div_scalar", jnp.divide)
_binary_scalar("_rdiv_scalar", lambda x, s: s / x)
_binary_scalar("_power_scalar", jnp.power)
_binary_scalar("_rpower_scalar", lambda x, s: jnp.power(s, x))
_binary_scalar("_maximum_scalar", jnp.maximum)
_binary_scalar("_minimum_scalar", jnp.minimum)
_binary_scalar("_mod_scalar", jnp.mod)
_binary_scalar("_rmod_scalar", lambda x, s: jnp.mod(s, x))
_binary_scalar("_hypot_scalar", jnp.hypot)
_binary_scalar("_equal_scalar", _cmp(jnp.equal))
_binary_scalar("_not_equal_scalar", _cmp(jnp.not_equal))
_binary_scalar("_greater_scalar", _cmp(jnp.greater))
_binary_scalar("_greater_equal_scalar", _cmp(jnp.greater_equal))
_binary_scalar("_lesser_scalar", _cmp(jnp.less))
_binary_scalar("_lesser_equal_scalar", _cmp(jnp.less_equal))

# ----------------------------------------------------------------------
# broadcast binary (reference broadcast_reduce_op / elemwise_binary_broadcast)
# ----------------------------------------------------------------------

for _n, _f in [
    ("broadcast_add", jnp.add),
    ("broadcast_plus", jnp.add),
    ("broadcast_sub", jnp.subtract),
    ("broadcast_minus", jnp.subtract),
    ("broadcast_mul", jnp.multiply),
    ("broadcast_div", jnp.divide),
    ("broadcast_mod", jnp.mod),
    ("broadcast_power", jnp.power),
    ("broadcast_maximum", jnp.maximum),
    ("broadcast_minimum", jnp.minimum),
    ("broadcast_hypot", jnp.hypot),
    ("broadcast_equal", _cmp(jnp.equal)),
    ("broadcast_not_equal", _cmp(jnp.not_equal)),
    ("broadcast_greater", _cmp(jnp.greater)),
    ("broadcast_greater_equal", _cmp(jnp.greater_equal)),
    ("broadcast_lesser", _cmp(jnp.less)),
    ("broadcast_lesser_equal", _cmp(jnp.less_equal)),
]:
    _binary(_n, _f)


@register("broadcast_to", params={"shape": P("shape", None, required=True)})
def _broadcast_to(attrs, x):
    # MXNet semantics: 0 in target shape means "keep this dim"
    tgt = tuple(s if s != 0 else x.shape[i] for i, s in enumerate(attrs["shape"]))
    return jnp.broadcast_to(x, tgt)


@register(
    "broadcast_axis",
    aliases=["broadcast_axes"],
    params={"axis": P("shape", ()), "size": P("shape", ())},
)
def _broadcast_axis(attrs, x):
    tgt = list(x.shape)
    for ax, sz in zip(attrs["axis"] or (), attrs["size"] or ()):
        tgt[ax] = sz
    return jnp.broadcast_to(x, tuple(tgt))


# ----------------------------------------------------------------------
# reductions (reference broadcast_reduce_op_value.cc)
# ----------------------------------------------------------------------


def _norm_axis(axis):
    if axis is None or axis == ():
        return None
    if isinstance(axis, int):
        return (axis,)
    return tuple(axis)


def _reduce(name, fn, aliases=(), exclude_support=True):
    @register(
        name,
        aliases=aliases,
        params={
            "axis": P("shape", None),
            "keepdims": P("bool", False),
            "exclude": P("bool", False),
        },
    )
    def _op(attrs, x, _fn=fn):
        axis = _norm_axis(attrs["axis"])
        if attrs.get("exclude") and axis is not None:
            axis = tuple(i for i in range(x.ndim) if i not in set(a % x.ndim for a in axis))
        return _fn(x, axis=axis, keepdims=attrs["keepdims"])

    return _op


_reduce("sum", jnp.sum, aliases=["sum_axis"])
_reduce("mean", jnp.mean)
_reduce("prod", jnp.prod)
_reduce("max", jnp.max, aliases=["max_axis"])
_reduce("min", jnp.min, aliases=["min_axis"])
_reduce("nansum", jnp.nansum)
_reduce("nanprod", jnp.nanprod)


@register("norm")
def _norm(attrs, x):
    return jnp.sqrt(jnp.sum(jnp.square(x))).reshape((1,))


@register(
    "argmax",
    params={"axis": P("int", None), "keepdims": P("bool", False)},
)
def _argmax(attrs, x):
    ax = attrs["axis"]
    out = jnp.argmax(x, axis=ax)
    if attrs["keepdims"] and ax is not None:
        out = jnp.expand_dims(out, ax)
    return out.astype(x.dtype)


@register(
    "argmin",
    params={"axis": P("int", None), "keepdims": P("bool", False)},
)
def _argmin(attrs, x):
    ax = attrs["axis"]
    out = jnp.argmin(x, axis=ax)
    if attrs["keepdims"] and ax is not None:
        out = jnp.expand_dims(out, ax)
    return out.astype(x.dtype)


@register("argmax_channel")
def _argmax_channel(attrs, x):
    return jnp.argmax(x, axis=1).astype(x.dtype)


# ----------------------------------------------------------------------
# dot / batch_dot (MXU-targeted: these lower straight to XLA dot_general)
# ----------------------------------------------------------------------


@register(
    "dot",
    arg_names=["lhs", "rhs"],
    params={"transpose_a": P("bool", False), "transpose_b": P("bool", False)},
)
def _dot(attrs, a, b):
    if a.ndim == 1 and b.ndim == 1:
        return jnp.dot(a, b).reshape((1,))
    if attrs["transpose_a"]:
        a = jnp.swapaxes(a, -1, -2) if a.ndim > 1 else a
    if attrs["transpose_b"]:
        b = jnp.swapaxes(b, -1, -2) if b.ndim > 1 else b
    # preferred_element_type keeps fp32 accumulation for bf16 inputs on the MXU
    acc = jnp.float32 if a.dtype in (jnp.bfloat16, jnp.float16) else None
    out = jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())), preferred_element_type=acc
    )
    return out.astype(a.dtype)


@register(
    "batch_dot",
    arg_names=["lhs", "rhs"],
    params={"transpose_a": P("bool", False), "transpose_b": P("bool", False)},
)
def _batch_dot(attrs, a, b):
    if attrs["transpose_a"]:
        a = jnp.swapaxes(a, -1, -2)
    if attrs["transpose_b"]:
        b = jnp.swapaxes(b, -1, -2)
    acc = jnp.float32 if a.dtype in (jnp.bfloat16, jnp.float16) else None
    out = jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))), preferred_element_type=acc
    )
    return out.astype(a.dtype)


# ----------------------------------------------------------------------
# shape manipulation (reference matrix_op.cc)
# ----------------------------------------------------------------------


def _infer_reshape(shape, target):
    """MXNet Reshape special codes: 0 copy, -1 infer, -2 copy-rest,
    -3 merge-two, -4 split (reference matrix_op-inl.h ReshapeParam)."""
    src = list(shape)
    out = []
    i = 0  # index into src
    t = list(target)
    j = 0
    while j < len(t):
        d = t[j]
        if d == 0:
            out.append(src[i])
            i += 1
        elif d == -1:
            out.append(-1)
            i += 1
        elif d == -2:
            out.extend(src[i:])
            i = len(src)
        elif d == -3:
            out.append(src[i] * src[i + 1])
            i += 2
        elif d == -4:
            d1, d2 = t[j + 1], t[j + 2]
            if d1 == -1:
                d1 = src[i] // d2
            if d2 == -1:
                d2 = src[i] // d1
            out.extend([d1, d2])
            i += 1
            j += 2
        else:
            out.append(d)
            i += 1
        j += 1
    # resolve a single -1
    if -1 in out:
        known = 1
        for d in out:
            if d != -1:
                known *= d
        total = 1
        for d in shape:
            total *= d
        out[out.index(-1)] = total // max(known, 1)
    return tuple(out)


@register(
    "Reshape",
    aliases=["reshape"],
    params={
        "shape": P("shape", None),
        "target_shape": P("shape", None),
        "keep_highest": P("bool", False),
        "reverse": P("bool", False),
    },
)
def _reshape(attrs, x):
    tgt = attrs["shape"] or attrs["target_shape"]
    return jnp.reshape(x, _infer_reshape(x.shape, tgt))


@register("Flatten", aliases=["flatten"])
def _flatten(attrs, x):
    return jnp.reshape(x, (x.shape[0], -1))


@register("transpose", params={"axes": P("shape", None)})
def _transpose(attrs, x):
    axes = attrs["axes"]
    return jnp.transpose(x, axes if axes else None)


@register("expand_dims", params={"axis": P("int", 0, required=True)})
def _expand_dims(attrs, x):
    return jnp.expand_dims(x, attrs["axis"])


@register(
    "SwapAxis",
    aliases=["swapaxes"],
    params={"dim1": P("int", 0), "dim2": P("int", 0)},
)
def _swapaxes(attrs, x):
    return jnp.swapaxes(x, attrs["dim1"], attrs["dim2"])


@register(
    "slice",
    aliases=["crop_like_slice"],
    params={"begin": P("shape", None, required=True), "end": P("shape", None, required=True)},
)
def _slice(attrs, x):
    idx = tuple(
        slice(b, e) for b, e in zip(attrs["begin"], attrs["end"])
    )
    return x[idx]


def _norm_slice_bounds(attrs, shape):
    """Normalize (begin, end) against ``shape`` with negative-index support
    (matching the sibling ``slice`` op) and validate the extents."""
    begin = tuple(attrs["begin"])
    end = tuple(attrs["end"])
    if len(begin) != len(end) or len(begin) > len(shape):
        raise ValueError("slice assign: begin %r / end %r invalid for shape %r"
                         % (begin, end, shape))
    nb, ne = [], []
    for b, e, d in zip(begin, end, shape):
        b = b + d if b < 0 else b
        e = e + d if e < 0 else e
        if not (0 <= b <= e <= d):
            raise ValueError(
                "slice assign: normalized [%d:%d) out of bounds for dim %d"
                % (b, e, d))
        nb.append(b)
        ne.append(e)
    return tuple(nb), tuple(ne)


@register(
    "_slice_assign",
    aliases=["_crop_assign"],
    arg_names=["lhs", "rhs"],
    params={"begin": P("shape", None, required=True),
            "end": P("shape", None, required=True)},
)
def _slice_assign(attrs, lhs, rhs):
    """Functional slice assignment (reference matrix_op.cc ``_crop_assign``,
    alias ``_slice_assign``): a copy of ``lhs`` with ``lhs[begin:end] = rhs``.
    On XLA this is a static ``dynamic_update_slice`` — no in-place aliasing
    needed."""
    begin, end = _norm_slice_bounds(attrs, lhs.shape)
    want = tuple(e - b for b, e in zip(begin, end)) + lhs.shape[len(begin):]
    if tuple(rhs.shape) != want:
        raise ValueError("slice assign: rhs shape %r != slice extents %r"
                         % (tuple(rhs.shape), want))
    return jax.lax.dynamic_update_slice(
        lhs, rhs.astype(lhs.dtype),
        begin + (0,) * (lhs.ndim - len(begin)))


@register(
    "_slice_assign_scalar",
    aliases=["_crop_assign_scalar"],
    params={"begin": P("shape", None, required=True),
            "end": P("shape", None, required=True),
            "scalar": P("float", 0.0)},
)
def _slice_assign_scalar(attrs, lhs):
    """Scalar fill of a slice (reference ``_crop_assign_scalar``)."""
    begin, end = _norm_slice_bounds(attrs, lhs.shape)
    fill = jnp.full([e - b for b, e in zip(begin, end)]
                    + list(lhs.shape[len(begin):]),
                    attrs["scalar"], dtype=lhs.dtype)
    return jax.lax.dynamic_update_slice(
        lhs, fill, begin + (0,) * (lhs.ndim - len(begin)))


@register(
    "slice_axis",
    params={
        "axis": P("int", 0, required=True),
        "begin": P("int", 0, required=True),
        "end": P("int", None),
    },
)
def _slice_axis(attrs, x):
    ax = attrs["axis"] % x.ndim
    idx = [slice(None)] * x.ndim
    idx[ax] = slice(attrs["begin"], attrs["end"])
    return x[tuple(idx)]


@register(
    "clip",
    params={"a_min": P("float", 0.0, required=True), "a_max": P("float", 0.0, required=True)},
)
def _clip(attrs, x):
    return jnp.clip(x, attrs["a_min"], attrs["a_max"])


@register("repeat", params={"repeats": P("int", 1, required=True), "axis": P("int", None)})
def _repeat(attrs, x):
    return jnp.repeat(x, attrs["repeats"], axis=attrs["axis"])


@register("tile", params={"reps": P("shape", None, required=True)})
def _tile(attrs, x):
    return jnp.tile(x, attrs["reps"])


@register("reverse", aliases=["flip"], params={"axis": P("shape", None, required=True)})
def _reverse(attrs, x):
    return jnp.flip(x, axis=attrs["axis"])


@register("where", arg_names=["condition", "x", "y"])
def _where(attrs, cond, x, y):
    if cond.ndim == 1 and x.ndim > 1:  # row-wise selection form
        shape = (-1,) + (1,) * (x.ndim - 1)
        cond = cond.reshape(shape)
    return jnp.where(cond != 0, x, y)


@register("Cast", aliases=["cast"], params={"dtype": P("str", "float32")})
def _cast(attrs, x):
    from ..base import mx_dtype

    return x.astype(mx_dtype(attrs["dtype"]))


@register(
    "Concat",
    aliases=["concat"],
    variable_args=True,
    params={"dim": P("int", 1)},
)
def _concat(attrs, *xs):
    return jnp.concatenate(xs, axis=attrs["dim"])


@register("add_n", aliases=["ElementWiseSum", "_sum"], variable_args=True)
def _add_n(attrs, *xs):
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return out


@register("stack", variable_args=True, params={"axis": P("int", 0)})
def _stack(attrs, *xs):
    return jnp.stack(xs, axis=attrs["axis"])


def _slice_channel_nout(attrs):
    return attrs["num_outputs"]


@register(
    "SliceChannel",
    aliases=["split"],
    num_outputs=_slice_channel_nout,
    params={
        "num_outputs": P("int", 1, required=True),
        "axis": P("int", 1),
        "squeeze_axis": P("bool", False),
    },
)
def _slice_channel(attrs, x):
    parts = jnp.split(x, attrs["num_outputs"], axis=attrs["axis"])
    if attrs["squeeze_axis"]:
        parts = [jnp.squeeze(p, axis=attrs["axis"]) for p in parts]
    return tuple(parts)


# ----------------------------------------------------------------------
# indexing (reference indexing_op.cc)
# ----------------------------------------------------------------------


@register(
    "take",
    arg_names=["a", "indices"],
    params={"axis": P("int", 0), "mode": P("str", "clip", enum=["clip", "wrap", "raise"])},
)
def _take(attrs, a, idx):
    mode = attrs["mode"]
    idx = idx.astype(jnp.int32)
    ax = attrs["axis"]
    n = a.shape[ax]
    if mode == "clip":
        idx = jnp.clip(idx, 0, n - 1)
    elif mode == "wrap":
        idx = jnp.mod(idx, n)
    return jnp.take(a, idx, axis=ax)


@register("batch_take", arg_names=["a", "indices"])
def _batch_take(attrs, a, idx):
    idx = jnp.clip(idx.astype(jnp.int32), 0, a.shape[1] - 1)
    return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]


@register(
    "one_hot",
    arg_names=["indices"],
    params={
        "depth": P("int", 0, required=True),
        "on_value": P("float", 1.0),
        "off_value": P("float", 0.0),
        "dtype": P("str", "float32"),
    },
)
def _one_hot(attrs, idx):
    from ..base import mx_dtype

    d = attrs["depth"]
    oh = jax.nn.one_hot(idx.astype(jnp.int32), d)
    out = oh * (attrs["on_value"] - attrs["off_value"]) + attrs["off_value"]
    return out.astype(mx_dtype(attrs["dtype"]))


@register(
    "pick",
    arg_names=["data", "index"],
    params={"axis": P("int", -1), "keepdims": P("bool", False)},
)
def _pick(attrs, x, idx):
    ax = attrs["axis"] % x.ndim
    idx = jnp.clip(idx.astype(jnp.int32), 0, x.shape[ax] - 1)
    picked = jnp.take_along_axis(x, jnp.expand_dims(idx, ax), axis=ax)
    if not attrs["keepdims"]:
        picked = jnp.squeeze(picked, axis=ax)
    return picked


@register(
    "Embedding",
    arg_names=["data", "weight"],
    params={
        "input_dim": P("int", 0, required=True),
        "output_dim": P("int", 0, required=True),
        "dtype": P("str", "float32"),
    },
)
def _embedding(attrs, data, weight):
    idx = jnp.clip(data.astype(jnp.int32), 0, attrs["input_dim"] - 1)
    return jnp.take(weight, idx, axis=0)


# ----------------------------------------------------------------------
# ordering (reference ordering_op.cc)
# ----------------------------------------------------------------------


@register(
    "sort",
    params={"axis": P("int", -1), "is_ascend": P("bool", True)},
)
def _sort(attrs, x):
    out = jnp.sort(x, axis=attrs["axis"])
    if not attrs["is_ascend"]:
        out = jnp.flip(out, axis=attrs["axis"])
    return out


@register(
    "argsort",
    params={"axis": P("int", -1), "is_ascend": P("bool", True)},
)
def _argsort(attrs, x):
    out = jnp.argsort(x, axis=attrs["axis"])
    if not attrs["is_ascend"]:
        out = jnp.flip(out, axis=attrs["axis"])
    return out.astype(x.dtype)


def _topk_nout(attrs):
    return 2 if attrs.get("ret_typ") == "both" else 1


@register(
    "topk",
    num_outputs=_topk_nout,
    params={
        "axis": P("int", -1),
        "k": P("int", 1),
        "ret_typ": P("str", "indices", enum=["value", "indices", "mask", "both"]),
        "is_ascend": P("bool", False),
    },
)
def _topk(attrs, x):
    ax = attrs["axis"] % x.ndim
    k = attrs["k"]
    xs = jnp.moveaxis(x, ax, -1)
    top_vals, top_idx = jax.lax.top_k(xs if not attrs["is_ascend"] else -xs, k)
    if attrs["is_ascend"]:
        top_vals = -top_vals
    rt = attrs["ret_typ"]
    if rt == "mask":
        # one-hot over the reduced axis, summed across the k picks
        oh = jax.nn.one_hot(top_idx, x.shape[ax], dtype=x.dtype).sum(-2)
        return jnp.moveaxis(oh, -1, ax)
    top_vals = jnp.moveaxis(top_vals, -1, ax)
    top_idx = jnp.moveaxis(top_idx, -1, ax)
    if rt == "value":
        return top_vals
    if rt == "indices":
        return top_idx.astype(x.dtype)
    return (top_vals, top_idx.astype(x.dtype))


# ----------------------------------------------------------------------
# init ops (reference init_op.cc) — nullary creators
# ----------------------------------------------------------------------


@register(
    "_zeros",
    arg_names=[],
    params={"shape": P("shape", None), "dtype": P("str", "float32"), "ctx": P("str", None)},
)
def _zeros_op(attrs, ):
    from ..base import mx_dtype

    return jnp.zeros(attrs["shape"] or (1,), dtype=mx_dtype(attrs["dtype"]))


@register(
    "_ones",
    arg_names=[],
    params={"shape": P("shape", None), "dtype": P("str", "float32"), "ctx": P("str", None)},
)
def _ones_op(attrs, ):
    from ..base import mx_dtype

    return jnp.ones(attrs["shape"] or (1,), dtype=mx_dtype(attrs["dtype"]))


@register(
    "_full",
    arg_names=[],
    params={
        "shape": P("shape", None),
        "dtype": P("str", "float32"),
        "value": P("float", 0.0),
        "ctx": P("str", None),
    },
)
def _full_op(attrs, ):
    from ..base import mx_dtype

    return jnp.full(attrs["shape"] or (1,), attrs["value"], dtype=mx_dtype(attrs["dtype"]))


@register(
    "_arange",
    arg_names=[],
    params={
        "start": P("float", 0.0),
        "stop": P("float", None),
        "step": P("float", 1.0),
        "repeat": P("int", 1),
        "dtype": P("str", "float32"),
        "ctx": P("str", None),
    },
)
def _arange_op(attrs, ):
    from ..base import mx_dtype

    start, stop = attrs["start"], attrs["stop"]
    if stop is None:
        start, stop = 0.0, start
    out = _np.arange(start, stop, attrs["step"])
    if attrs["repeat"] > 1:
        out = _np.repeat(out, attrs["repeat"])
    return jnp.asarray(out, dtype=mx_dtype(attrs["dtype"]))


# ----------------------------------------------------------------------
# random sampling (reference sample_op.cc) — counter-based via jax PRNG
# ----------------------------------------------------------------------


def _sample(name, aliases, extra, draw):
    params = {
        "shape": P("shape", None),
        "dtype": P("str", "float32"),
        "ctx": P("str", None),
    }
    params.update(extra)

    @register(name, aliases=aliases, arg_names=[], params=params, needs_rng=True)
    def _op(attrs, rng=None, _draw=draw):
        from ..base import mx_dtype

        shape = attrs["shape"] or (1,)
        return _draw(rng, attrs, shape).astype(mx_dtype(attrs["dtype"]))

    return _op


_sample(
    "_random_uniform",
    ["uniform", "random_uniform"],
    {"low": P("float", 0.0), "high": P("float", 1.0)},
    lambda k, a, s: jax.random.uniform(k, s, minval=a["low"], maxval=a["high"]),
)
_sample(
    "_random_normal",
    ["normal", "random_normal"],
    {"loc": P("float", 0.0), "scale": P("float", 1.0)},
    lambda k, a, s: a["loc"] + a["scale"] * jax.random.normal(k, s),
)
_sample(
    "_random_gamma",
    ["random_gamma"],
    {"alpha": P("float", 1.0), "beta": P("float", 1.0)},
    lambda k, a, s: jax.random.gamma(k, a["alpha"], s) * a["beta"],
)
_sample(
    "_random_exponential",
    ["random_exponential"],
    {"lam": P("float", 1.0)},
    lambda k, a, s: jax.random.exponential(k, s) / a["lam"],
)
_sample(
    "_random_poisson",
    ["random_poisson"],
    {"lam": P("float", 1.0)},
    lambda k, a, s: jax.random.poisson(k, a["lam"], s).astype(jnp.float32),
)
_sample(
    "_random_negative_binomial",
    ["random_negative_binomial"],
    {"k": P("float", 1.0), "p": P("float", 0.5)},
    lambda k, a, s: jax.random.poisson(
        k, jax.random.gamma(jax.random.fold_in(k, 1), a["k"], s) * (1 - a["p"]) / a["p"]
    ).astype(jnp.float32),
)
# generalized (Polya / gamma-Poisson) negative binomial, mean mu and
# dispersion alpha (reference sample_op.cc GeneralizedNegativeBinomialSampler):
# lambda ~ Gamma(shape=1/alpha, scale=mu*alpha); x ~ Poisson(lambda).
# alpha == 0 degenerates to plain Poisson(mu), as in the reference sampler.
def _gen_nb_draw(k, a, s):
    if a["alpha"] <= 0.0:
        return jax.random.poisson(k, a["mu"], s).astype(jnp.float32)
    lam = jax.random.gamma(jax.random.fold_in(k, 1), 1.0 / a["alpha"], s) \
        * a["mu"] * a["alpha"]
    return jax.random.poisson(k, lam).astype(jnp.float32)


_sample(
    "_random_generalized_negative_binomial",
    ["random_generalized_negative_binomial"],
    {"mu": P("float", 1.0), "alpha": P("float", 1.0)},
    _gen_nb_draw,
)


def _multisample(name, aliases, arg_names, draw):
    """Per-row sampling with tensor distribution params (parity: the
    reference's ``multisample_op`` family, ``src/operator/tensor/
    multisample_op.cc``): inputs are 1-D parameter arrays; output is
    ``param_shape + shape`` with row i drawn from distribution(params[i])."""
    params = {"shape": P("shape", None), "dtype": P("str", "float32")}

    @register(name, aliases=aliases, arg_names=list(arg_names), params=params,
              needs_rng=True)
    def _op(attrs, *ps, rng=None, _draw=draw):
        from ..base import mx_dtype

        shape = attrs["shape"] or ()
        if isinstance(shape, int):
            shape = (shape,)
        full = tuple(ps[0].shape) + tuple(shape)
        # broadcast each 1-D param against the sample dims
        expand = (...,) + (None,) * len(shape)
        bps = [p[expand] if shape else p for p in ps]
        return _draw(rng, full, *bps).astype(mx_dtype(attrs["dtype"]))

    return _op


_multisample(
    "_sample_uniform", ["sample_uniform"], ["low", "high"],
    lambda k, s, lo, hi: lo + (hi - lo) * jax.random.uniform(k, s),
)
_multisample(
    "_sample_normal", ["sample_normal"], ["mu", "sigma"],
    lambda k, s, mu, sig: mu + sig * jax.random.normal(k, s),
)
_multisample(
    "_sample_gamma", ["sample_gamma"], ["alpha", "beta"],
    lambda k, s, a, b: jax.random.gamma(k, jnp.broadcast_to(a, s)) * b,
)
_multisample(
    "_sample_exponential", ["sample_exponential"], ["lam"],
    lambda k, s, lam: jax.random.exponential(k, s) / lam,
)
_multisample(
    "_sample_poisson", ["sample_poisson"], ["lam"],
    lambda k, s, lam: jax.random.poisson(k, jnp.broadcast_to(lam, s)).astype(
        jnp.float32),
)
_multisample(
    "_sample_negbinomial",
    ["sample_negbinomial", "sample_negative_binomial"], ["k", "p"],
    lambda key, s, kk, p: jax.random.poisson(
        key,
        jax.random.gamma(jax.random.fold_in(key, 1), jnp.broadcast_to(kk, s))
        * (1 - p) / p,
    ).astype(jnp.float32),
)
def _gen_nb_multidraw(key, s, mu, al):
    # alpha entries of 0 degenerate to Poisson(mu); guard the gamma shape
    # against the division so those lanes stay finite
    safe = jnp.maximum(al, 1e-6)
    lam = jax.random.gamma(jax.random.fold_in(key, 1),
                           jnp.broadcast_to(1.0 / safe, s)) * mu * safe
    lam = jnp.where(jnp.broadcast_to(al, s) > 0.0, lam,
                    jnp.broadcast_to(mu, s))
    return jax.random.poisson(key, lam).astype(jnp.float32)


_multisample(
    "_sample_gennegbinomial",
    ["sample_gennegbinomial", "sample_generalized_negative_binomial"],
    ["mu", "alpha"],
    _gen_nb_multidraw,
)


# ----------------------------------------------------------------------
# softmax family (reference softmax_output.cc lives in nn.py; these are the
# pure ones from src/operator/nn/softmax*)
# ----------------------------------------------------------------------


@register("softmax", params={"axis": P("int", -1), "temperature": P("float", None)})
def _softmax(attrs, x):
    t = attrs["temperature"]
    if t:
        x = x / t
    return jax.nn.softmax(x, axis=attrs["axis"])


@register("log_softmax", params={"axis": P("int", -1), "temperature": P("float", None)})
def _log_softmax(attrs, x):
    t = attrs["temperature"]
    if t:
        x = x / t
    return jax.nn.log_softmax(x, axis=attrs["axis"])


@register("softmax_cross_entropy", arg_names=["data", "label"])
def _softmax_cross_entropy(attrs, data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    onehot = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=data.dtype)
    return -jnp.sum(onehot * logp).reshape((1,))


# ----------------------------------------------------------------------
# fused optimizer update ops (reference src/operator/optimizer_op.cc).
# Functional form: return the updated tensors instead of mutating in place;
# the python Optimizer assigns them back (NDArray rebinds its buffer).
# ----------------------------------------------------------------------


def _prep_grad(grad, attrs):
    g = grad * attrs["rescale_grad"]
    cg = attrs.get("clip_gradient")
    if cg is not None and cg > 0:
        g = jnp.clip(g, -cg, cg)
    return g


# operand=True: lr, wd (and adam's t) change from step to step, and the
# rules below only do arithmetic on them — eager calls pass them as scalar
# operands of one compiled program, the fused trainers trace them.
# clip_gradient stays an attribute (_prep_grad branches on it), as do the
# constants of one optimizer object (rescale_grad, momentum, betas, ...).
_OPT_COMMON = {
    "lr": P("float", 0.01, required=True, operand=True),
    "wd": P("float", 0.0, operand=True),
    "rescale_grad": P("float", 1.0),
    "clip_gradient": P("float", -1.0),
}


@register("sgd_update", arg_names=["weight", "grad"], params=dict(_OPT_COMMON))
def _sgd_update(attrs, w, g):
    g = _prep_grad(g, attrs)
    return w - attrs["lr"] * (g + attrs["wd"] * w)


@register(
    "sgd_mom_update",
    arg_names=["weight", "grad", "mom"],
    num_outputs=2,
    params=dict(_OPT_COMMON, momentum=P("float", 0.0)),
)
def _sgd_mom_update(attrs, w, g, mom):
    g = _prep_grad(g, attrs)
    new_mom = attrs["momentum"] * mom - attrs["lr"] * (g + attrs["wd"] * w)
    return w + new_mom, new_mom


@register(
    "adam_update",
    arg_names=["weight", "grad", "mean", "var"],
    num_outputs=3,
    params=dict(
        _OPT_COMMON,
        beta1=P("float", 0.9),
        beta2=P("float", 0.999),
        epsilon=P("float", 1e-8),
        t=P("int", 1, operand=True),
    ),
)
def _adam_update(attrs, w, g, mean, var):
    g = _prep_grad(g, attrs) + attrs["wd"] * w
    b1, b2 = attrs["beta1"], attrs["beta2"]
    new_mean = b1 * mean + (1 - b1) * g
    new_var = b2 * var + (1 - b2) * jnp.square(g)
    # t is a declared operand: eager calls pass the step count as a
    # scalar argument, ShardedTrainer and dist_tpu the on-device counter,
    # so long runs don't recompile per step.  The bias correction is
    # computed explicitly in f32 so that all of them get BITWISE-identical
    # updates — the dist_tpu-vs-dist_sync exact-parity contract depends on
    # it.  (A t compiled in as a constant can differ in the last place:
    # XLA folds beta**3 to b*b*b where the run-time power is pow's.)
    t = jnp.asarray(attrs["t"], jnp.float32)
    b1f, b2f = jnp.float32(b1), jnp.float32(b2)
    lr = attrs["lr"] * jnp.sqrt(1 - b2f**t) / (1 - b1f**t)
    new_w = w - lr * new_mean / (jnp.sqrt(new_var) + attrs["epsilon"])
    return new_w, new_mean, new_var


@register(
    "rmsprop_update",
    arg_names=["weight", "grad", "n"],
    num_outputs=2,
    params=dict(_OPT_COMMON, gamma1=P("float", 0.95), epsilon=P("float", 1e-8)),
)
def _rmsprop_update(attrs, w, g, n):
    g = _prep_grad(g, attrs) + attrs["wd"] * w
    g1 = attrs["gamma1"]
    new_n = g1 * n + (1 - g1) * jnp.square(g)
    new_w = w - attrs["lr"] * g / jnp.sqrt(new_n + attrs["epsilon"])
    return new_w, new_n


@register(
    "rmspropalex_update",
    arg_names=["weight", "grad", "n", "g", "delta"],
    num_outputs=4,
    params=dict(
        _OPT_COMMON,
        gamma1=P("float", 0.95),
        gamma2=P("float", 0.9),
        epsilon=P("float", 1e-8),
    ),
)
def _rmspropalex_update(attrs, w, grad, n, g, delta):
    grad = _prep_grad(grad, attrs) + attrs["wd"] * w
    g1, g2 = attrs["gamma1"], attrs["gamma2"]
    new_n = g1 * n + (1 - g1) * jnp.square(grad)
    new_g = g1 * g + (1 - g1) * grad
    new_delta = g2 * delta - attrs["lr"] * grad / jnp.sqrt(
        new_n - jnp.square(new_g) + attrs["epsilon"]
    )
    return w + new_delta, new_n, new_g, new_delta


@register(
    "smooth_l1",
    arg_names=["data"],
    params={"scalar": P("float", 1.0)},
)
def _smooth_l1(attrs, x):
    """Huber-style smooth L1 (reference ``src/operator/tensor/
    elemwise_unary_op.cc:smooth_l1``): 0.5*(sigma*x)^2 for |x| < 1/sigma^2,
    |x| - 0.5/sigma^2 otherwise.  Used by SSD/RCNN bbox regression."""
    sigma2 = attrs["scalar"] ** 2
    ax = jnp.abs(x)
    return jnp.where(ax < 1.0 / sigma2, 0.5 * sigma2 * jnp.square(x),
                     ax - 0.5 / sigma2)
