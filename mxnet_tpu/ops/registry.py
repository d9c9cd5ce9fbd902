"""Operator registry — the NNVM ``Op`` registry rebuilt for XLA.

In the reference, ops live in two C++ registries (``OperatorProperty`` and NNVM
``FCompute``; reference ``include/mxnet/op_attr_types.h:57-62``,
``src/nnvm/legacy_op_util.cc``) and kernels are mshadow/CUDA.  Here there is a
single registry and every op's compute function is a *traceable JAX function*:
the imperative path jits it per (attrs, shapes) and the symbolic executor traces
whole graphs of them into one XLA computation.  That one design change replaces
the dependency engine + mshadow + cuDNN stack: XLA does the scheduling, fusion
and memory planning that the reference does by hand.

An op declares:

* ``arg_names``   — positional tensor inputs (e.g. ``['data','weight','bias']``);
  missing inputs auto-materialize as variables at Symbol compose time, exactly
  like the reference's parameter inputs.
* ``aux_names``   — auxiliary states mutated by training forward (BatchNorm
  moving stats).  The compute fn returns their new values after the outputs.
* ``params``      — attribute spec (name -> ParamSpec), the ``dmlc::Parameter``
  equivalent: typed, defaulted, string-parseable (for JSON graph loading).
  An attribute declared ``operand=True`` may reach ``fn`` as a traced scalar:
  the imperative path hands it to the compiled program as an argument instead
  of compiling it in, and a fused caller sets it with ``Op.with_operands``.
* ``fn(attrs, *tensors, is_train=..., rng=...)`` — the compute rule on jax
  arrays.  ``rng`` is a jax PRNG key for stochastic ops (Dropout, samplers).
"""

from __future__ import annotations

import ast
from typing import Callable, Dict, List, Optional, Sequence

import numpy as _np

from ..base import MXNetError

__all__ = ["Op", "ParamSpec", "register", "get_op", "list_ops", "OP_REGISTRY"]

OP_REGISTRY: Dict[str, "Op"] = {}
_ALIAS: Dict[str, str] = {}


def _parse_bool(s):
    if isinstance(s, bool):
        return s
    if isinstance(s, (int, float)):
        return bool(s)
    s = s.strip().lower()
    if s in ("true", "1"):
        return True
    if s in ("false", "0"):
        return False
    raise ValueError("cannot parse bool from %r" % s)


def _parse_shape(s):
    if s is None:
        return None
    if isinstance(s, (tuple, list)):
        return tuple(int(x) for x in s)
    if isinstance(s, (int, _np.integer)):
        return (int(s),)
    s = s.strip()
    if s in ("None", ""):
        return None
    val = ast.literal_eval(s)
    if isinstance(val, (int, float)):
        return (int(val),)
    return tuple(int(x) for x in val)


class ParamSpec:
    """One attribute of an op (the ``DMLC_DECLARE_FIELD`` equivalent).

    ``operand=True`` says the compute rule only does arithmetic on the
    value (no Python branch, shape or dtype depends on it), so it may be
    a traced scalar: set it on a number that changes from call to call
    (a learning rate, a step count), never on one the rule branches on."""

    __slots__ = ("name", "type", "default", "required", "enum", "operand")

    def __init__(self, type="str", default=None, required=False, enum=None,
                 operand=False):
        self.type = type
        self.default = default
        self.required = required
        self.enum = enum
        self.operand = operand

    def parse(self, value):
        if value is None:
            return None
        t = self.type
        if t == "int":
            return int(value)
        if t == "float":
            return float(value)
        if t == "bool":
            return _parse_bool(value)
        if t == "shape":
            return _parse_shape(value)
        if t == "str":
            v = str(value)
            if self.enum is not None and v not in self.enum:
                raise MXNetError("invalid value %r; expected one of %s" % (v, self.enum))
            return v
        if t == "any":
            return value
        raise MXNetError("unknown param type %r" % (t,))


class Op:
    """A registered operator."""

    def __init__(
        self,
        name: str,
        fn: Callable,
        arg_names: Sequence[str] = ("data",),
        aux_names: Sequence[str] = (),
        num_outputs=1,
        params: Optional[Dict[str, ParamSpec]] = None,
        needs_mode: bool = False,
        needs_rng: bool = False,
        variable_args: bool = False,
        output_names: Optional[Sequence[str]] = None,
        input_names_fn: Optional[Callable] = None,
        collect_extra: bool = False,
        mesh_aware: bool = False,
    ):
        self.name = name
        self.fn = fn
        self.arg_names = list(arg_names)
        self.aux_names = list(aux_names)
        self.num_outputs = num_outputs  # int or callable(attrs) -> int
        self.params = params or {}
        # the attributes that may arrive as traced scalars, in declaration
        # order: THE list every caller reads (``ndarray.invoke`` passes them
        # as operands, the fused trainers overwrite them per step)
        self.operand_params = tuple(
            k for k, spec in self.params.items() if spec.operand)
        self.needs_mode = needs_mode
        self.needs_rng = needs_rng
        # variable_args: op takes N homogeneous inputs (Concat, add_n, ...)
        # controlled by attr 'num_args'
        self.variable_args = variable_args
        self.output_names = list(output_names) if output_names else None
        self.input_names_fn = input_names_fn
        self.collect_extra = collect_extra
        # mesh_aware: the compute rule consults the ambient default mesh at
        # trace time, so jit caches must key on the mesh identity too
        self.mesh_aware = mesh_aware

    # -- attrs ---------------------------------------------------------
    def parse_attrs(self, kwargs: Dict) -> Dict:
        """Validate/parse keyword attributes into a canonical attrs dict."""
        attrs = {}
        for k, v in kwargs.items():
            if k in self.params:
                attrs[k] = self.params[k].parse(v)
            elif k == "num_args" and self.variable_args:
                attrs["num_args"] = int(v)
            elif self.collect_extra:
                attrs.setdefault("_kwargs", {})[k] = v
            else:
                raise MXNetError(
                    "%s got unknown attribute %r (known: %s)"
                    % (self.name, k, sorted(self.params))
                )
        for k, spec in self.params.items():
            if k not in attrs:
                if spec.required:
                    raise MXNetError("%s missing required attribute %r" % (self.name, k))
                attrs[k] = spec.default
        return attrs

    def attrs_key(self, attrs: Dict):
        """Hashable canonical form of attrs (jit-cache key component)."""
        return tuple(sorted((k, _hashable(v)) for k, v in attrs.items()))

    def is_operand(self, name: str) -> bool:
        return name in self.operand_params

    def with_operands(self, attrs: Dict, **values) -> Dict:
        """``attrs`` with the operands among ``values`` set, to Python
        numbers or traced scalars alike.  A name the op does not have is
        skipped (a trainer offers its step count to every update op); one
        it has but did not declare ``operand=True`` is refused, because
        the compute rule may branch on it."""
        out = dict(attrs)
        for k, v in values.items():
            if k in self.operand_params:
                out[k] = v
            elif k in self.params:
                raise MXNetError(
                    "%s: attribute %r is not declared operand=True and "
                    "cannot be set per call (operands: %s)"
                    % (self.name, k, list(self.operand_params)))
        return out

    def n_outputs(self, attrs) -> int:
        if callable(self.num_outputs):
            return self.num_outputs(attrs)
        return self.num_outputs

    def input_names(self, attrs) -> List[str]:
        if self.variable_args:
            n = int(attrs.get("num_args") or 0)
            return ["arg%d" % i for i in range(n)]
        if self.input_names_fn is not None:
            return list(self.input_names_fn(attrs))
        return self.arg_names

    # -- compute -------------------------------------------------------
    def apply(self, attrs, args, auxs=(), is_train=False, rng=None):
        """Run the compute rule.  Returns (outputs_list, new_aux_list)."""
        kw = {}
        if self.needs_mode:
            kw["is_train"] = is_train
        if self.needs_rng:
            kw["rng"] = rng
        out = self.fn(attrs, *args, *auxs, **kw)
        n_out = self.n_outputs(attrs)
        if not isinstance(out, tuple):
            out = (out,)
        outputs = list(out[:n_out])
        new_aux = list(out[n_out:])
        if len(outputs) != n_out or len(new_aux) != len(self.aux_names):
            raise MXNetError(
                "%s returned %d arrays; expected %d outputs + %d aux"
                % (self.name, len(out), n_out, len(self.aux_names))
            )
        return outputs, new_aux

    def __repr__(self):
        return "Op(%s)" % self.name


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


def register(name, aliases=(), **kwargs):
    """Decorator: register ``fn`` as op ``name`` (+ aliases)."""

    def deco(fn):
        op = Op(name, fn, **kwargs)
        OP_REGISTRY[name] = op
        for a in aliases:
            _ALIAS[a] = name
        return fn

    return deco


def register_op(op: Op, aliases=()):
    OP_REGISTRY[op.name] = op
    for a in aliases:
        _ALIAS[a] = op.name
    return op


def get_op(name: str) -> Op:
    if name in OP_REGISTRY:
        return OP_REGISTRY[name]
    if name in _ALIAS:
        return OP_REGISTRY[_ALIAS[name]]
    raise MXNetError("operator %r is not registered" % name)


def list_ops() -> List[str]:
    return sorted(set(OP_REGISTRY) | set(_ALIAS))
