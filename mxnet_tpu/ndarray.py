"""NDArray — the imperative array type (parity: reference
``include/mxnet/ndarray.h`` + ``python/mxnet/ndarray.py``).

The reference NDArray pairs a ``Storage::Handle`` with an ``Engine::VarHandle``
so reads/writes order through the dependency engine.  Here the backing store is
a ``jax.Array``: XLA's async dispatch IS the engine (every op returns
immediately with a future-backed buffer; ``wait_to_read`` blocks on the ready
event, replacing ``WaitToRead``'s engine var wait).  Mutation (``a[:] = x``,
``+=``, optimizer updates) rebinds the underlying buffer — the functional
equivalent of the reference's in-place engine writes, with XLA buffer donation
recovering the memory.

Every registered op materializes as a function in this module at import time,
mirroring how the reference generates ``mx.nd.*`` from the C op registry
(``python/mxnet/ndarray.py:_init_ndarray_module``).
"""

from __future__ import annotations

import builtins
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as _np

from . import random as _random
from .base import MXNetError, mx_dtype, numeric_types
from .context import Context, current_context
from .ops.registry import OP_REGISTRY, _ALIAS, get_op

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "concatenate", "load", "save", "imresize", "onehot_encode",
           "waitall", "multiply", "subtract", "divide", "true_divide",
           "moveaxis", "imdecode"]


class NDArray:
    """Multi-dimensional array with async semantics on a device context."""

    __slots__ = ("_data", "_ctx", "_writable", "_tape_entry")

    def __init__(self, data, ctx=None, writable=True):
        if isinstance(data, NDArray):
            data = data._data
        self._data = data
        self._ctx = ctx if ctx is not None else current_context()
        self._writable = writable
        self._tape_entry = None  # autograd tape hook (contrib.autograd)

    # -- basic properties ---------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def size(self):
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        return self._ctx

    @property
    def handle(self):  # API-compat shim (reference exposes a C handle)
        return self

    def __len__(self):
        return self.shape[0]

    def __repr__(self):
        return "<NDArray %s @%s>" % ("x".join(str(d) for d in self.shape), self._ctx)

    # -- synchronization (parity: WaitToRead / WaitForAll) ------------
    def wait_to_read(self):
        jax.block_until_ready(self._data)

    def asnumpy(self):
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(-1)[0]

    # -- conversion / movement ----------------------------------------
    def astype(self, dtype):
        return NDArray(self._data.astype(mx_dtype(dtype)), self._ctx)

    def copy(self):
        return NDArray(self._data + 0, self._ctx)

    def copyto(self, other):
        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise ValueError(
                    "copyto shape mismatch: %s vs %s" % (self.shape, other.shape))
            other._set_data(jax.device_put(self._data, other._ctx.jax_device))
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device), other)
        raise TypeError("copyto does not support type " + str(type(other)))

    def as_in_context(self, context):
        if context == self._ctx:
            return self
        return self.copyto(context)

    # -- mutation ------------------------------------------------------
    def _set_data(self, new_data):
        if not self._writable:
            raise MXNetError("trying to write to a read-only NDArray")
        self._data = new_data

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        elif isinstance(value, numeric_types):
            value = jnp.asarray(value, dtype=self.dtype)
        else:
            value = jnp.asarray(value, dtype=self.dtype)
        # NB: builtins.slice — the generated mx.nd.slice op shadows the name
        # in this module's namespace
        if key == builtins.slice(None) or key is Ellipsis:
            self._set_data(jnp.broadcast_to(value, self.shape).astype(self.dtype))
        else:
            self._set_data(self._data.at[key].set(value))

    def __getitem__(self, key):
        return NDArray(self._data[key], self._ctx)

    def slice(self, start, stop):
        return NDArray(self._data[start:stop], self._ctx)

    # -- shape ops -----------------------------------------------------
    def reshape(self, shape):
        return NDArray(jnp.reshape(self._data, shape), self._ctx)

    @property
    def T(self):
        return NDArray(self._data.T, self._ctx)

    # -- arithmetic (broadcasting, like reference broadcast_* sugar) ---
    def _binary(self, other, fn, op_name=None, scalar_op=None, swap=False):
        # when the autograd tape is active, route through the op registry so
        # the op is recorded (parity: reference sugar maps to broadcast_* /
        # _*_scalar ops which MXImperativeInvoke tapes)
        from .contrib import autograd as _ag

        if _ag.is_training() and (op_name or scalar_op):
            if isinstance(other, (int, float)) and scalar_op:
                # _r*_scalar ops encode the operand order themselves
                return invoke(scalar_op, [self], {"scalar": float(other)})
            if op_name:
                o = other if isinstance(other, NDArray) else \
                    NDArray(jnp.asarray(other, dtype=self.dtype), self._ctx)
                pair = [o, self] if swap else [self, o]
                return invoke(op_name, pair)
        if isinstance(other, NDArray):
            a, b = self._data, other._data
        else:
            a, b = self._data, jnp.asarray(other, dtype=self.dtype)
        if swap:
            a, b = b, a
        return NDArray(fn(a, b), self._ctx)

    def __add__(self, other):
        return self._binary(other, jnp.add, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, jnp.subtract, "broadcast_sub",
                            "_minus_scalar")

    def __rsub__(self, other):
        return self._binary(other, jnp.subtract, "broadcast_sub",
                            "_rminus_scalar", swap=True)

    def __mul__(self, other):
        return self._binary(other, jnp.multiply, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __div__(self, other):
        return self._binary(other, jnp.divide, "broadcast_div", "_div_scalar")

    __truediv__ = __div__

    def __rdiv__(self, other):
        return self._binary(other, jnp.divide, "broadcast_div", "_rdiv_scalar",
                            swap=True)

    __rtruediv__ = __rdiv__

    def __pow__(self, other):
        return self._binary(other, jnp.power, "broadcast_power",
                            "_power_scalar")

    def __mod__(self, other):
        return self._binary(other, jnp.mod, "broadcast_mod", "_mod_scalar")

    def __neg__(self):
        from .contrib import autograd as _ag

        if _ag.is_training():
            return invoke("negative", [self])
        return NDArray(-self._data, self._ctx)

    def __iadd__(self, other):
        o = other._data if isinstance(other, NDArray) else other
        self._set_data(self._data + o)
        return self

    def __isub__(self, other):
        o = other._data if isinstance(other, NDArray) else other
        self._set_data(self._data - o)
        return self

    def __imul__(self, other):
        o = other._data if isinstance(other, NDArray) else other
        self._set_data(self._data * o)
        return self

    def __idiv__(self, other):
        o = other._data if isinstance(other, NDArray) else other
        self._set_data(self._data / o)
        return self

    __itruediv__ = __idiv__

    def __eq__(self, other):
        if isinstance(other, (NDArray,) + numeric_types):
            return self._binary(other, lambda a, b: (a == b).astype(a.dtype))
        return NotImplemented

    def __ne__(self, other):
        if isinstance(other, (NDArray,) + numeric_types):
            return self._binary(other, lambda a, b: (a != b).astype(a.dtype))
        return NotImplemented

    def __gt__(self, other):
        return self._binary(other, lambda a, b: (a > b).astype(a.dtype))

    def __ge__(self, other):
        return self._binary(other, lambda a, b: (a >= b).astype(a.dtype))

    def __lt__(self, other):
        return self._binary(other, lambda a, b: (a < b).astype(a.dtype))

    def __le__(self, other):
        return self._binary(other, lambda a, b: (a <= b).astype(a.dtype))

    def __hash__(self):
        return id(self)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")


# ----------------------------------------------------------------------
# creation API
# ----------------------------------------------------------------------


def _ctx_or_current(ctx):
    return ctx if ctx is not None else current_context()


def array(source_array, ctx=None, dtype=None):
    """Create an NDArray from any array-like (parity: ``mx.nd.array``)."""
    ctx = _ctx_or_current(ctx)
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    if dtype is None:
        # reference semantics: numpy arrays keep their dtype, anything else
        # (lists, scalars) defaults to float32
        if isinstance(source_array, _np.ndarray):
            dtype = source_array.dtype
            if dtype == _np.float64:
                dtype = _np.float32
            elif dtype == _np.int64:
                dtype = _np.int32
        else:
            dtype = _np.float32
    arr = _np.asarray(source_array, dtype=mx_dtype(dtype))
    return NDArray(jax.device_put(jnp.asarray(arr), ctx.jax_device), ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype=None):
    ctx = _ctx_or_current(ctx)
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(
        jax.device_put(jnp.zeros(shape, dtype=mx_dtype(dtype)), ctx.jax_device), ctx
    )


def ones(shape, ctx=None, dtype=None):
    ctx = _ctx_or_current(ctx)
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(
        jax.device_put(jnp.ones(shape, dtype=mx_dtype(dtype)), ctx.jax_device), ctx
    )


def full(shape, val, ctx=None, dtype=None):
    ctx = _ctx_or_current(ctx)
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(
        jax.device_put(jnp.full(shape, val, dtype=mx_dtype(dtype)), ctx.jax_device), ctx
    )


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    ctx = _ctx_or_current(ctx)
    if stop is None:
        start, stop = 0, start
    out = _np.arange(start, stop, step)
    if repeat > 1:
        out = _np.repeat(out, repeat)
    return NDArray(
        jax.device_put(jnp.asarray(out.astype(mx_dtype(dtype))), ctx.jax_device), ctx
    )


def concatenate(arrays, axis=0, always_copy=True):
    return NDArray(
        jnp.concatenate([a._data for a in arrays], axis=axis), arrays[0]._ctx
    )


def onehot_encode(indices, out):
    """(parity: ``mx.nd.onehot_encode``)"""
    depth = out.shape[1]
    out._set_data(jax.nn.one_hot(indices._data.astype(jnp.int32), depth,
                                 dtype=out.dtype))
    return out


def imresize(src, w, h, *args, **kwargs):
    data = jax.image.resize(src._data, (h, w) + src.shape[2:], method="bilinear")
    return NDArray(data, src._ctx)


def waitall():
    """Block until all async work completes (parity: ``mx.nd.waitall``)."""
    (jax.device_put(0.0) + 0).block_until_ready()


# ----------------------------------------------------------------------
# serialization (parity: NDArray::Save/Load, reference ndarray.h:355-370).
# Format: numpy .npz with a manifest — not the dmlc binary format, but the
# same save/load API and name-map semantics.
# ----------------------------------------------------------------------


def _save_npz(fname, arrays, fmt):
    """Single writer of the on-disk container (shared by :func:`save` and
    the engine-deferred checkpoint write): atomic via temp-file + rename so
    a crash mid-write can never leave a truncated file at the final path."""
    import os
    import tempfile

    d = os.path.dirname(os.path.abspath(fname)) or "."
    fd, tmp = tempfile.mkstemp(prefix=".mxtpu_save_", dir=d)
    try:
        with os.fdopen(fd, "wb") as f:  # file object keeps exact name (no .npz)
            _np.savez(f, __mx_format__=fmt, **arrays)
        os.replace(tmp, fname)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def save(fname, data):
    """Save a list or str->NDArray dict (parity: ``mx.nd.save``)."""
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        arrays = {k: v.asnumpy() for k, v in data.items()}
        fmt = "dict"
    else:
        arrays = {"arr_%d" % i: v.asnumpy() for i, v in enumerate(data)}
        fmt = "list"
    _save_npz(fname, arrays, fmt)


def load(fname):
    """Load NDArrays saved by :func:`save`."""
    with _np.load(fname, allow_pickle=False) as f:
        fmt = str(f["__mx_format__"]) if "__mx_format__" in f else "dict"
        keys = [k for k in f.files if k != "__mx_format__"]
        if fmt == "list":
            keys = sorted(keys, key=lambda k: int(k.split("_")[1]))
            return [array(f[k]) for k in keys]
        return {k: array(f[k]) for k in keys}


def load_frombuffer(buf):
    """Load NDArrays from serialized bytes (parity: ``mx.nd.load_frombuffer``
    / ``MXNDArrayLoadFromBuffer`` — the predict API's param path)."""
    import io as _io

    return load(_io.BytesIO(buf))


# ----------------------------------------------------------------------
# op namespace generation (parity: _init_ndarray_module)
# ----------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jitted_apply(op_name, attrs_key, n_args, n_aux, is_train, with_rng):
    """One compiled program per (op, static attributes, input signature).
    Unbounded, yet bounded in practice: the attributes an op declares
    ``operand=True`` (an optimizer's lr, wd, step count) are arguments of
    the program, not part of ``attrs_key``, so the cache holds the distinct
    static configurations a process uses and does not grow with its steps."""
    op = get_op(op_name)
    attrs = dict(attrs_key)
    n_tensors = n_args + n_aux + bool(with_rng)

    def run(*inputs):
        args = inputs[:n_args]
        auxs = inputs[n_args : n_args + n_aux]
        rng = inputs[n_args + n_aux] if with_rng else None
        call_attrs = op.with_operands(
            attrs, **dict(zip(op.operand_params, inputs[n_tensors:])))
        outputs, new_aux = op.apply(call_attrs, args, auxs,
                                    is_train=is_train, rng=rng)
        return tuple(outputs) + tuple(new_aux)

    return jax.jit(run)


def invoke(op_name, args, kwargs=None, out=None, is_train=False):
    """Imperative op invoke (parity: ``MXImperativeInvoke``,
    reference ``src/c_api/c_api_ndarray.cc:322``): look up the op, jit-cache by
    (op, static attrs), run on the arrays' device, wrap outputs."""
    op = get_op(op_name)
    kwargs = dict(kwargs or {})
    kwargs.pop("name", None)
    ctx = kwargs.pop("ctx", None)
    if isinstance(ctx, str):  # attrs-style ctx string from graph load
        ctx = None
    if op.variable_args and "num_args" not in kwargs:
        kwargs["num_args"] = len(args)
    attrs = op.parse_attrs(kwargs)
    n_declared = len(op.input_names(attrs))
    arg_list = list(args)
    # split aux trailing args (eager BatchNorm passes moving stats positionally)
    n_aux = len(op.aux_names)
    if n_aux and len(arg_list) == n_declared + n_aux:
        aux_list = arg_list[n_declared:]
        arg_list = arg_list[:n_declared]
    else:
        aux_list = []
        n_aux = 0
    for a in arg_list + aux_list:
        if isinstance(a, NDArray):
            ctx = ctx or a._ctx
    ctx = _ctx_or_current(ctx)

    def as_jax(a):
        return a._data if isinstance(a, NDArray) else jnp.asarray(a)

    tensors = [as_jax(a) for a in arg_list] + [as_jax(a) for a in aux_list]
    if op.needs_rng:
        tensors.append(_random.next_key())
    # declared operands ride behind the tensors as Python scalars, which jit
    # traces as weak-typed 0-d float32 / int32: the program is keyed on the
    # other attributes only, and promotes dtypes as a Python attribute would
    static = attrs
    if op.operand_params:
        static = {k: v for k, v in attrs.items()
                  if k not in op.operand_params}
        tensors.extend(attrs[k] for k in op.operand_params)
    fn = _jitted_apply(
        op_name, op.attrs_key(static), len(arg_list), n_aux, is_train,
        op.needs_rng
    )
    if op.mesh_aware:
        # eager calls run dense on the inputs' device: sharding constraints
        # belong to mesh-scoped traced graphs (ShardedTrainer), and a cached
        # eager trace must never bake in an ambient mesh
        from .parallel import default_mesh

        with default_mesh(None):
            results = fn(*tensors)
    else:
        results = fn(*tensors)
    n_out = op.n_outputs(attrs)
    outputs = [NDArray(r, ctx) for r in results[:n_out]]
    # autograd tape hook (contrib.autograd train_section)
    from .contrib import autograd as _ag

    if _ag.is_training():
        _ag._record(op, attrs, arg_list + aux_list, outputs, len(arg_list))
    # write back updated aux state (engine-write equivalent)
    for aux_nd, new in zip(aux_list, results[n_out : n_out + n_aux]):
        if isinstance(aux_nd, NDArray):
            aux_nd._set_data(new)
    if out is not None:
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o, r in zip(outs, outputs):
            o._set_data(r._data)
        return out
    if n_out == 1:
        return outputs[0]
    return outputs


def _make_nd_fn(op_name):
    op = get_op(op_name)

    def fn(*args, **kwargs):
        out = kwargs.pop("out", None)
        # tensor inputs may also be passed by keyword (name=...)
        pos = list(args)
        names = op.arg_names if not op.variable_args else []
        for nm in names:
            if nm in kwargs:
                pos.append(kwargs.pop(nm))
        return invoke(op_name, pos, kwargs, out=out)

    fn.__name__ = op_name
    from .ops.opdocs import op_doc

    fn.__doc__ = "%s\n\n%s" % (
        "Imperative op %r (TPU-native)." % op_name,
        op_doc(op, aliases=[a for a, t in _ALIAS.items() if t == op.name]))
    return fn


def _init_module():
    mod = sys.modules[__name__]
    for name in list(OP_REGISTRY) + list(_ALIAS):
        if not hasattr(mod, name):
            setattr(mod, name, _make_nd_fn(name))
        public = name[1:] if name.startswith("_") else name
        if public and not hasattr(mod, public):
            setattr(mod, public, _make_nd_fn(name))


# populated by mxnet_tpu/__init__ after all op modules import


def multiply(lhs, rhs):
    """Elementwise product (parity: ``ndarray.py:multiply``)."""
    return lhs * rhs


def subtract(lhs, rhs):
    """Elementwise difference (parity: ``ndarray.py:subtract``)."""
    return lhs - rhs


def divide(lhs, rhs):
    """Elementwise quotient (parity: ``ndarray.py:divide``)."""
    return lhs / rhs


true_divide = divide


def moveaxis(tensor, source, destination):
    """Move an axis to a new position (parity: ``ndarray.py:moveaxis``;
    numpy axis semantics — out-of-range axes raise)."""
    nd_ = tensor.ndim

    def _norm(ax, name):
        if not -nd_ <= ax < nd_:
            raise ValueError("%s axis %d out of range for %d-d array"
                             % (name, ax, nd_))
        return ax + nd_ if ax < 0 else ax

    src = _norm(source, "source")
    dst = _norm(destination, "destination")
    axes = list(range(nd_))
    axes.insert(dst, axes.pop(src))
    return NDArray(jnp.transpose(tensor._data, axes), tensor.context)


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0,
             channels=3, mean=None):
    """Decode an encoded image to NDArray (parity: ``ndarray.py:imdecode``).
    Unsupported reference options raise rather than being silently
    ignored; plain decodes delegate to the image package."""
    if out is not None or index != 0 or tuple(clip_rect) != (0, 0, 0, 0) \
            or channels != 3 or mean is not None:
        raise MXNetError(
            "imdecode: only plain 3-channel decodes are supported here; "
            "use mx.image.imdecode + ndarray ops for crop/mean handling")
    from . import image as _image

    return array(_image.imdecode_bytes(str_img))
