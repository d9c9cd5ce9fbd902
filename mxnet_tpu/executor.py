"""Executor — symbolic graph execution (parity: reference
``src/executor/graph_executor.cc`` + ``python/mxnet/executor.py``).

Where the reference builds a full fwd+bwd NNVM graph, plans memory, and pushes
cached engine ops per node (``GraphExecutor::RunOps``), this executor *traces*
the whole Symbol into ONE jitted XLA computation:

* ``forward``      → single compiled HLO module (XLA = PlanMemory + engine)
* ``backward``     → fused forward+vjp compiled step.  In training mode the
  forward is *deferred*: ``forward(is_train=True)`` records inputs, and
  ``backward()`` runs one fused (outputs, grads, new_aux) computation — the
  XLA-native version of the reference's bulk-executed segments
  (``MXNET_EXEC_BULK_EXEC_TRAIN``), with zero re-computation and full fusion.
* gradient graph   → ``jax.vjp`` replaces ``nnvm::pass::Gradient``;
  ``grad_req='add'`` accumulation is applied functionally on the stored grads.

Auxiliary states (BatchNorm moving stats) are extra functional outputs written
back after the step — the reference mutates them through engine writes.
"""

from __future__ import annotations

import functools
import os as _os
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as _np

from . import ndarray as nd
from . import random as _random
from .base import MXNetError, mx_dtype
from .context import Context
from .ndarray import NDArray
from .symbol import Symbol, _infer

__all__ = ["Executor"]


def _eval_node(node, args, auxs, rng, is_train):
    """Evaluate one graph node — the single dispatch rule shared by the
    eager walker and the placed segment jits, so their numerics can never
    diverge (the MXTPU_PLACED_EAGER parity contract)."""
    node_rng = (jax.random.fold_in(rng, node._id)
                if node.op.needs_rng else None)
    return node.op.apply(node.attrs, args, auxs,
                         is_train=is_train, rng=node_rng)


def _graph_fn(symbol: Symbol, node_device=None):
    """Build the pure function evaluating the symbol graph.

    Returns ``run(arg_values, aux_values, rng, is_train) -> (outputs, new_aux)``
    where arg/aux values are name->jax array dicts.

    ``node_device`` (node_id -> jax.Device) enables ``group2ctx`` model
    parallelism (parity: ``nnvm::pass::PlaceDevice`` + ``_CrossDeviceCopy``
    insertion, reference ``graph_executor.cc:318``,
    ``src/operator/cross_device_copy.cc``): heterogeneous placement can't
    live inside ONE XLA computation, so the graph is partitioned into
    contiguous single-device *segments*, each jitted into its own XLA
    computation — the reference's cached-segment bulk execution
    (``CreateCachedSegOpr``, ``MXNET_EXEC_BULK_EXEC_TRAIN``) adapted to
    placement.  Cross-device copies (``jax.device_put``) happen eagerly at
    segment boundaries only, and the whole composition stays differentiable
    (``jax.vjp`` through jitted segments transposes the copies back).
    Set ``MXTPU_PLACED_EAGER=1`` to fall back to the per-op eager walker
    for debugging (the NaiveEngine analog).
    """
    nodes = symbol._topo()
    out_entries = list(symbol._outputs)
    node_device = node_device or {}
    if node_device and not _os.environ.get("MXTPU_PLACED_EAGER"):
        return _placed_graph_fn(nodes, out_entries, node_device)

    # __remat__ segmentation composes with the default single-device path
    # only: under heterogeneous placement (node_device — including the
    # MXTPU_PLACED_EAGER walker) remat regions would silently skip the
    # per-node device_put contract, so placement wins and tags are ignored
    if node_device:
        plan = [("var", n) if n.is_variable else ("node", n) for n in nodes]
    else:
        plan = _remat_plan(nodes, out_entries)

    def _eval_plain(node, env, new_aux, rng, is_train):
        ins = [env[s._id][i] for s, i in node.inputs]
        dev = node_device.get(node._id)
        if dev is not None:
            ins = [jax.device_put(v, dev) for v in ins]
        n_args = len(node.op.input_names(node.attrs))
        outs, aux_updates = _eval_node(
            node, ins[:n_args], ins[n_args:], rng, is_train)
        env[node._id] = outs
        for (aux_node, _), new_val in zip(node.inputs[n_args:], aux_updates):
            new_aux[aux_node.name] = new_val

    def run(arg_values, aux_values, rng, is_train):
        env = {}
        new_aux = {}
        for item in plan:
            if item[0] == "var":
                node = item[1]
                src = aux_values if node.is_aux else arg_values
                if node.name not in src:
                    raise MXNetError("unbound variable %r" % node.name)
                env[node._id] = [src[node.name]]
            elif item[0] == "node":
                _eval_plain(item[1], env, new_aux, rng, is_train)
            else:  # remat segment
                _, seg_nodes, ext, live = item
                ext_vals = [env[sid][i] for sid, i in ext]
                seg_ids = {n._id for n in seg_nodes}
                ext_index = {e: k for k, e in enumerate(ext)}

                def seg_fn(ext_vals, rng, _seg_nodes=seg_nodes,
                           _seg_ids=seg_ids, _ext_index=ext_index,
                           _live=live):
                    lenv = {}
                    laux = {}

                    def get(s, i):
                        if s._id in _seg_ids:
                            return lenv[s._id][i]
                        return ext_vals[_ext_index[(s._id, i)]]

                    for node in _seg_nodes:
                        ins = [get(s, i) for s, i in node.inputs]
                        n_args = len(node.op.input_names(node.attrs))
                        outs, aux_updates = _eval_node(
                            node, ins[:n_args], ins[n_args:], rng, is_train)
                        lenv[node._id] = outs
                        for (an, _), nv in zip(node.inputs[n_args:],
                                               aux_updates):
                            laux[an.name] = nv
                    # return ONLY values consumed outside (anything
                    # returned becomes a saved residual — returning every
                    # intermediate would defeat the remat)
                    return [lenv[sid][i] for sid, i in _live], laux

                outs_live, laux = jax.checkpoint(
                    seg_fn, policy=_remat_policy())(ext_vals, rng)
                for (sid, i), v in zip(live, outs_live):
                    env.setdefault(sid, {})[i] = v
                new_aux.update(laux)
        outputs = [env[n._id][i] for n, i in out_entries]
        # pass untouched aux through so the pytree structure is stable
        for name in aux_values:
            new_aux.setdefault(name, aux_values[name])
        return outputs, new_aux

    return run


def _remat_policy():
    """Optional jax.checkpoint policy for __remat__ segments, by name
    (``MXTPU_REMAT_POLICY=dots_saveable`` etc.); default: save only
    segment inputs + live outputs."""
    name = _os.environ.get("MXTPU_REMAT_POLICY")
    return getattr(jax.checkpoint_policies, name) if name else None


def _remat_plan(nodes, out_entries):
    """Partition the topo order into an execution plan honoring the
    ``__remat__`` node attr (the reference's graph-executor *mirror*
    option, ``graph_executor.cc:225-233`` ``nnvm::pass::Gradient`` mirror
    fun — recompute-in-backward at marked boundaries; here each maximal
    contiguous run of op nodes sharing a ``__remat__`` tag becomes one
    ``jax.checkpoint`` region whose intermediates are rematerialized in
    the backward pass).

    Returns a list of items:
      ("var", node)                       — variable read
      ("node", node)                      — plain op eval
      ("seg", nodes, ext, live)           — remat segment; ``ext`` is the
        ordered list of external (node_id, out_idx) inputs, ``live`` the
        (node_id, out_idx) values consumed outside the segment.
    Variables never join segments (their values are explicit segment
    inputs, so jax.checkpoint differentiates through them); an untagged
    op between two same-tag ops splits the run (correct, just smaller
    regions).
    """
    # variables depend on nothing: hoist them to the front of the plan so
    # interleaved parameter reads cannot split a block's contiguous run
    # into per-op fragments
    runs = [("var", n) for n in nodes if n.is_variable]
    for node in nodes:
        if node.is_variable:
            continue
        tag = node.extra_attrs.get("__remat__")
        if not tag:
            runs.append(("node", node))
            continue
        if runs and runs[-1][0] == "seg" and runs[-1][1] == tag:
            runs[-1][2].append(node)
        else:
            runs.append(("seg", tag, [node]))

    out_set = {(n._id, i) for n, i in out_entries}
    consumers = {}
    for node in nodes:
        if node.is_variable:
            continue
        for s, i in node.inputs:
            consumers.setdefault((s._id, i), []).append(node._id)

    plan = []
    for item in runs:
        if item[0] != "seg":
            plan.append(item)
            continue
        _, _, seg_nodes = item
        seg_ids = {n._id for n in seg_nodes}
        ext, seen = [], set()
        for node in seg_nodes:
            for s, i in node.inputs:
                key = (s._id, i)
                if s._id not in seg_ids and key not in seen:
                    seen.add(key)
                    ext.append(key)
        live = []
        for node in seg_nodes:
            for i in range(node.num_outputs()):
                key = (node._id, i)
                outside = [c for c in consumers.get(key, ())
                           if c not in seg_ids]
                if outside or key in out_set:
                    live.append(key)
        plan.append(("seg", seg_nodes, ext, live))
    return plan


def _already_on(v, dev):
    """True iff ``v`` is a concrete single-device array on ``dev`` —
    cheap guard that skips the eager device_put dispatch (~25-50us each;
    a placed graph touches hundreds of params per step)."""
    try:
        return isinstance(v, jax.Array) and not v.is_deleted() \
            and v.committed and v.devices() == {dev}
    except Exception:  # tracers during vjp: fall through to device_put
        return False


def _put(v, dev):
    return v if _already_on(v, dev) else jax.device_put(v, dev)


def _placed_graph_fn(nodes, out_entries, node_device):
    """Segment-jitted runner for device-placed (group2ctx) graphs."""
    # ---- partition the topo order into contiguous same-device segments
    segments = []  # list of dicts: device, nodes
    for node in nodes:
        if node.is_variable:
            continue
        dev = node_device[node._id]
        if segments and segments[-1]["device"] is dev:
            segments[-1]["nodes"].append(node)
        else:
            segments.append({"device": dev, "nodes": [node]})

    # ---- per-segment interface: external input entries + exported entries
    produced_by = {}  # node_id -> segment index
    for si, seg in enumerate(segments):
        for node in seg["nodes"]:
            produced_by[node._id] = si
    needed = set((n._id, i) for n, i in out_entries)
    for seg in segments:
        for node in seg["nodes"]:
            for src, i in node.inputs:
                if src.is_variable or produced_by.get(src._id) != \
                        produced_by[node._id]:
                    needed.add((src._id, i))
    for si, seg in enumerate(segments):
        ext, exports, aux_names = [], [], []
        seen_ext, seen_exp = set(), set()
        for node in seg["nodes"]:
            n_args = len(node.op.input_names(node.attrs))
            for src, i in node.inputs[:n_args]:
                entry = (src._id, i)
                if (src.is_variable or produced_by.get(src._id) != si) \
                        and entry not in seen_ext:
                    seen_ext.add(entry)
                    ext.append(entry)
            for src, _ in node.inputs[n_args:]:
                if src.name not in aux_names:
                    aux_names.append(src.name)
            for oi in range(node.op.n_outputs(node.attrs)):
                entry = (node._id, oi)
                if entry in needed and entry not in seen_exp:
                    seen_exp.add(entry)
                    exports.append(entry)
        seg["ext"], seg["exports"], seg["aux_names"] = ext, exports, aux_names

        seg_nodes = seg["nodes"]

        def seg_fn(ext_vals, aux_vals, rng, is_train,
                   _ext=tuple(ext), _exports=tuple(exports),
                   _nodes=tuple(seg_nodes)):
            env = dict(zip(_ext, ext_vals))
            aux_env = dict(aux_vals)
            updates = {}
            for node in _nodes:
                n_args = len(node.op.input_names(node.attrs))
                args = [env[(s._id, i)] for s, i in node.inputs[:n_args]]
                auxs = [aux_env[s.name] for s, _ in node.inputs[n_args:]]
                outs, aux_updates = _eval_node(node, args, auxs, rng,
                                               is_train)
                for oi, o in enumerate(outs):
                    env[(node._id, oi)] = o
                for (aux_node, _), new_val in zip(node.inputs[n_args:],
                                                  aux_updates):
                    aux_env[aux_node.name] = new_val
                    updates[aux_node.name] = new_val
            return [env[e] for e in _exports], updates

        seg["jit"] = {
            mode: jax.jit(functools.partial(seg_fn, is_train=mode))
            for mode in (False, True)
        }

    def run(arg_values, aux_values, rng, is_train):
        env = {}
        for node in nodes:
            if node.is_variable:
                src = aux_values if node.is_aux else arg_values
                if node.name not in src:
                    raise MXNetError("unbound variable %r" % node.name)
                env[(node._id, 0)] = src[node.name]
        aux_env = dict(aux_values)
        new_aux = {}
        for seg in segments:
            dev = seg["device"]
            ext_vals = [_put(env[e], dev) for e in seg["ext"]]
            aux_in = {n: _put(aux_env[n], dev) for n in seg["aux_names"]}
            outs, updates = seg["jit"][bool(is_train)](ext_vals, aux_in, rng)
            for e, o in zip(seg["exports"], outs):
                env[e] = o
            for name, val in updates.items():
                aux_env[name] = val
                new_aux[name] = val
        outputs = [env[(n._id, i)] for n, i in out_entries]
        for name in aux_values:
            new_aux.setdefault(name, aux_values[name])
        return outputs, new_aux

    return run


class Executor:
    """Bound computation graph over concrete arrays on one context/mesh."""

    def __init__(self, symbol, ctx, arg_dict, grad_dict, grad_req, aux_dict,
                 group2ctx=None, shared_exec=None):
        from .context import current_context

        self._symbol = symbol
        self._ctx = ctx if ctx is not None else current_context()
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self.arg_dict: Dict[str, NDArray] = arg_dict
        self.grad_dict: Dict[str, Optional[NDArray]] = grad_dict
        self.aux_dict: Dict[str, NDArray] = aux_dict
        if isinstance(grad_req, str):
            grad_req = {k: grad_req for k in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(self._arg_names, grad_req))
        self._grad_req = {
            k: (grad_req.get(k, "null") if grad_dict.get(k) is not None else "null")
            for k in self._arg_names
        }
        # group2ctx model parallelism: when groups land on other devices,
        # switch to the placed (eager, per-op dispatch) walker.  Ungrouped
        # nodes run on the main ctx (the reference's PlaceDevice default),
        # so mixed-device inputs always get an explicit copy.
        self._placed = False
        node_device = {}
        if group2ctx:
            main_dev = self._ctx.jax_device
            var_device = {}
            for node in symbol._topo():
                if node.is_variable:
                    continue
                grp = node.extra_attrs.get("ctx_group")
                dev = (group2ctx[grp].jax_device
                       if grp and grp in group2ctx else main_dev)
                node_device[node._id] = dev
                if dev != main_dev:
                    self._placed = True
                for src, _ in node.inputs:
                    if src.is_variable:
                        var_device.setdefault(src.name, dev)
            if self._placed:
                self._var_device = var_device
        self._run = _graph_fn(symbol, node_device if self._placed else None)
        # stochastic graphs (Dropout, samplers) need a fresh PRNG key per
        # call; deterministic graphs reuse one cached key — a per-call
        # eager fold_in is a whole extra device execution that would
        # dominate small-batch inference.  Mode-gated
        # stochastic ops (Dropout: needs_mode) are deterministic at eval,
        # so inference only pays for always-stochastic ops (samplers).
        rng_ops = [node.op for node in symbol._topo()
                   if not node.is_variable and node.op.needs_rng]
        self._needs_rng_train = bool(rng_ops)
        self._needs_rng_eval = any(not op.needs_mode for op in rng_ops)
        self._fixed_rng = None
        self._jit_fwd = {}     # is_train -> jitted forward
        self._jit_step = None  # fused fwd+bwd
        self._outputs: Optional[List[NDArray]] = None
        self._pending_train = False
        self._monitor_callback = None
        self.group2ctx = group2ctx
        self.shared_exec = shared_exec
        self.mesh = None  # set by Module for multi-device GSPMD execution

    def replicate_params(self, skip_names=()):
        """Re-place every non-data array replicated over ``self.mesh`` so the
        jitted step sees consistent placements (params replicated, data
        batch-sharded) — the GSPMD layout for data parallelism."""
        if self.mesh is None:
            return
        from .parallel.mesh import replicate

        for d in (self.arg_dict, self.grad_dict, self.aux_dict):
            for k, v in d.items():
                if v is None or k in skip_names:
                    continue
                v._data = replicate(self.mesh, v._data)

    # ------------------------------------------------------------------
    # binding constructors
    # ------------------------------------------------------------------
    @staticmethod
    def _bind(symbol, ctx, args, args_grad=None, grad_req="write", aux_states=None,
              group2ctx=None, shared_exec=None):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_dict = _to_dict("args", args, arg_names)
        if args_grad is None:
            grad_dict = {}
        else:
            grad_dict = _to_dict("args_grad", args_grad, arg_names, allow_missing=True)
        aux_dict = _to_dict("aux_states", aux_states or [], aux_names, allow_missing=True)
        return Executor(symbol, ctx, arg_dict, grad_dict, grad_req, aux_dict,
                        group2ctx=group2ctx, shared_exec=shared_exec)

    @staticmethod
    def _simple_bind(symbol, ctx, grad_req="write", type_dict=None, group2ctx=None,
                     shared_exec=None, shapes=None):
        shapes = shapes or {}
        type_dict = type_dict or {}
        (arg_shapes, out_shapes, aux_shapes,
         arg_types, aux_types) = _infer(symbol, shapes, type_dict)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if any(s is None for s in arg_shapes):
            missing = [n for n, s in zip(arg_names, arg_shapes) if s is None]
            raise MXNetError("simple_bind could not infer shapes for %s" % missing)
        # allocate at the INFERRED dtypes (type_dict already won inside
        # _infer; __dtype__ variable hints — e.g. int8 quantized weights —
        # must not be clobbered back to float32 here)
        arg_dict = {
            n: nd.zeros(s, ctx, dtype=t or type_dict.get(n, "float32"))
            for n, s, t in zip(arg_names, arg_shapes, arg_types)
        }
        aux_dict = {
            n: nd.zeros(s, ctx, dtype=t or type_dict.get(n, "float32"))
            for n, s, t in zip(aux_names, aux_shapes, aux_types)
        }
        if isinstance(grad_req, str):
            req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            req = dict(zip(arg_names, grad_req))
        else:
            req = dict(grad_req)
        grad_dict = {
            n: nd.zeros(s, ctx, dtype=type_dict.get(n, "float32"))
            for n, s in zip(arg_names, arg_shapes)
            if req.get(n, "null") != "null"
        }
        return Executor(symbol, ctx, arg_dict, grad_dict, req, aux_dict,
                        group2ctx=group2ctx, shared_exec=shared_exec)

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def _gather(self):
        if self._placed:
            # keep each array on its consumer group's device, writing the
            # placement back so re-initialized params pay one copy, not one
            # per step (the reference pins params on their PlaceDevice
            # device at bind)
            for d in (self.arg_dict, self.aux_dict):
                for name, arr in d.items():
                    dev = self._var_device.get(name)
                    if dev is not None and arr is not None \
                            and not _already_on(arr._data, dev):
                        placed = jax.device_put(arr._data, dev)
                        if placed is not arr._data:
                            arr._set_data(placed)
        args = {k: v._data for k, v in self.arg_dict.items()}
        auxs = {k: v._data for k, v in self.aux_dict.items()}
        return args, auxs

    def _forward_fn(self, is_train):
        if is_train not in self._jit_fwd:
            run = self._run

            def f(args, auxs, rng):
                return run(args, auxs, rng, is_train)

            # placed (group2ctx) graphs span devices: _run is already the
            # segment-jitted composition, so no outer jit
            self._jit_fwd[is_train] = f if self._placed else jax.jit(f)
        return self._jit_fwd[is_train]

    def _call_rng(self, is_train):
        """Per-call PRNG key: advancing for graphs stochastic in this mode,
        cached constant otherwise (no per-call device traffic)."""
        if self._needs_rng_train if is_train else self._needs_rng_eval:
            return _random.next_key()
        if self._fixed_rng is None:
            self._fixed_rng = _random.next_key()
        return self._fixed_rng

    def _place(self, data):
        """Commit data onto this executor's device (H2D copy if needed) —
        the PJRT transfer that replaces the engine's copy workers."""
        if self.mesh is not None:
            from .parallel.mesh import replicate

            return replicate(self.mesh, data)
        return jax.device_put(data, self._ctx.jax_device)

    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown forward argument %r" % k)
            if isinstance(v, NDArray):
                self.arg_dict[k]._set_data(
                    self._place(v._data.astype(self.arg_dict[k].dtype)))
            else:
                self.arg_dict[k][:] = v
        if is_train:
            # defer: backward() runs the fused step; reading .outputs before
            # backward() materializes a forward-only pass (see module docstring)
            self._pending_train = True
            self._outputs = None
            return None
        self._pending_train = False
        args, auxs = self._gather()
        outs, new_aux = self._forward_fn(False)(args, auxs, self._call_rng(False))
        self._write_aux(new_aux)
        self._outputs = [NDArray(o, self._ctx) for o in outs]
        return self._outputs

    def _materialize_forward(self):
        """Compute deferred train-mode forward without backward."""
        args, auxs = self._gather()
        outs, new_aux = self._forward_fn(True)(args, auxs, self._call_rng(True))
        self._write_aux(new_aux)
        self._outputs = [NDArray(o, self._ctx) for o in outs]
        self._pending_train = False

    @property
    def outputs(self):
        if self._outputs is None and self._pending_train:
            # lazily evaluated on first access; backward() will recompute the
            # fused step only if it runs before this materialization
            self._materialize_forward()
        if self._outputs is None:
            return []
        return self._outputs

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def _step_fn(self):
        if self._jit_step is None:
            run = self._run
            diff = sorted(
                k for k, r in self._grad_req.items()
                if r != "null" and not _np.issubdtype(self.arg_dict[k].dtype, _np.integer)
            )

            def step(args, auxs, rng, out_grads):
                fixed = {k: v for k, v in args.items() if k not in diff}
                dargs = {k: args[k] for k in diff}

                def f(d):
                    all_args = dict(fixed)
                    all_args.update(d)
                    outs, new_aux = run(all_args, auxs, rng, True)
                    return outs, new_aux

                (outs, new_aux), vjp_fn = jax.vjp(f, dargs)
                zero_aux = {k: jnp.zeros_like(v) for k, v in new_aux.items()}
                cot = [
                    g if g is not None else jnp.ones_like(o)
                    for o, g in zip(outs, out_grads)
                ]
                grads = vjp_fn((cot, zero_aux))[0]
                return outs, new_aux, grads

            self._jit_step = step if self._placed else jax.jit(step)
        return self._jit_step

    def backward(self, out_grads=None):
        if out_grads is None:
            out_grads = [None] * len(self._symbol._outputs)
        elif isinstance(out_grads, NDArray):
            out_grads = [out_grads]
        out_grads = [g._data if isinstance(g, NDArray) else g for g in out_grads]
        # jit needs a fixed pytree: substitute ones for None inside step via
        # eval-shape-known outputs — pass ones arrays here instead
        args, auxs = self._gather()
        if any(g is None for g in out_grads):
            shapes = self._out_shapes(args, auxs)
            out_grads = [
                g if g is not None else jnp.ones(s, dtype=d)
                for g, (s, d) in zip(out_grads, shapes)
            ]
        outs, new_aux, grads = self._step_fn()(args, auxs, self._call_rng(True), out_grads)
        self._outputs = [NDArray(o, self._ctx) for o in outs]
        self._pending_train = False
        self._write_aux(new_aux)
        for k, g in grads.items():
            tgt = self.grad_dict.get(k)
            if tgt is None:
                continue
            if self._grad_req[k] == "add":
                tgt._set_data(tgt._data + g)
            else:
                tgt._set_data(g)

    def _out_shapes(self, args, auxs):
        # instance memo (NOT lru_cache on the method — that would pin every
        # Executor and its device buffers alive for the process lifetime)
        memo = getattr(self, "_out_shapes_memo", None)
        if memo is not None:
            return memo
        run = self._run

        def f(a, x):
            outs, _ = run(a, x, jax.random.PRNGKey(0), True)
            return outs

        shapes = jax.eval_shape(f, args, auxs)
        self._out_shapes_memo = [(tuple(s.shape), s.dtype) for s in shapes]
        return self._out_shapes_memo

    def _write_aux(self, new_aux):
        for k, v in new_aux.items():
            if k in self.aux_dict:
                self.aux_dict[k]._set_data(v)

    # ------------------------------------------------------------------
    # conveniences (reference executor.py API)
    # ------------------------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[k] for k in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(k) for k in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[k] for k in self._aux_names]

    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        def _copy(tgt_dict, k, v, what):
            tgt = tgt_dict[k]
            if tuple(v.shape) != tgt.shape:
                raise MXNetError(
                    "%s %r has shape %s; executor expects %s"
                    % (what, k, tuple(v.shape), tgt.shape))
            tgt._set_data(self._place(v._data.astype(tgt.dtype)))

        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                _copy(self.arg_dict, k, v, "arg_param")
            elif not allow_extra_params:
                raise MXNetError("Found name %r not in executor arguments" % k)
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                _copy(self.aux_dict, k, v, "aux_param")
            elif not allow_extra_params:
                raise MXNetError("Found name %r not in executor aux states" % k)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor with new input shapes (XLA recompiles; the
        executable cache plays the reference's memory-sharing role)."""
        shapes = dict(kwargs)
        arg_shapes, _, aux_shapes, _, _ = _infer(self._symbol, shapes, {})
        arg_names = self._symbol.list_arguments()
        new_args = {}
        for n, s in zip(arg_names, arg_shapes):
            cur = self.arg_dict[n]
            if s == cur.shape:
                new_args[n] = cur
            else:
                new_args[n] = nd.zeros(s, self._ctx, dtype=cur.dtype)
        new_grads = {
            k: (nd.zeros(new_args[k].shape, self._ctx, dtype=v.dtype) if v is not None else None)
            for k, v in self.grad_dict.items()
        }
        new_aux = {}
        for n, s in zip(self._aux_names, aux_shapes):
            cur = self.aux_dict[n]
            new_aux[n] = cur if s == cur.shape else nd.zeros(s, self._ctx, dtype=cur.dtype)
        return Executor(self._symbol, self._ctx, new_args, new_grads, self._grad_req,
                        new_aux, group2ctx=self.group2ctx)

    def set_monitor_callback(self, callback):
        """Install ``callback(name, NDArray)`` invoked per interior output
        by :meth:`run_monitor_capture` (parity: the reference's executor
        monitor callback, ``graph_executor.cc:131 ExecuteMonCallback``)."""
        self._monitor_callback = callback

    def run_monitor_capture(self, is_train=True):
        """Re-run the graph interpreted (un-jitted) over the current inputs
        and feed every interior output to the installed monitor callback.
        The jitted step can't call back per-op; this is the observability
        path ``mx.mon.Monitor`` drives (reference: bulk-exec disabled under
        monitoring for per-op granularity)."""
        if self._monitor_callback is None:
            return
        sym = self._symbol
        args = {k: v._data for k, v in self.arg_dict.items()}
        auxs = {k: v._data for k, v in self.aux_dict.items()}
        env = {}
        rng = self._call_rng(is_train)
        for node in sym._topo():
            if node.is_variable:
                src = auxs if node.is_aux else args
                env[node._id] = [src.get(node.name)]
                continue
            ins = [env[s._id][i] for s, i in node.inputs]
            n_args = len(node.op.input_names(node.attrs))
            outs, _ = _eval_node(node, ins[:n_args], ins[n_args:], rng,
                                 is_train)
            env[node._id] = outs
            for i, o in enumerate(outs):
                self._monitor_callback(node.output_name(i),
                                       NDArray(o, self._ctx))

    def debug_str(self):
        lines = ["Symbol outputs: %s" % self._symbol.list_outputs()]
        for node in self._symbol._topo():
            if node.is_variable:
                lines.append("Variable:%s" % node.name)
            else:
                lines.append("Op:%s, Name=%s" % (node.op.name, node.name))
        return "\n".join(lines)


def _to_dict(what, values, names, allow_missing=False):
    if isinstance(values, dict):
        out = {}
        for n in names:
            if n in values:
                out[n] = values[n]
            elif not allow_missing:
                raise MXNetError("%s is missing entry for %r" % (what, n))
        return out
    values = list(values)
    if not allow_missing and len(values) != len(names):
        raise MXNetError(
            "%s length %d does not match number of names %d (%s)"
            % (what, len(values), len(names), names)
        )
    return {n: v for n, v in zip(names, values) if v is not None}
