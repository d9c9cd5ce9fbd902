"""GSPMD sharded training — the TPU-native capability layer that subsumes the
reference's distributed machinery (``DataParallelExecutorGroup`` +
kvstore reduce + ``PlaceDevice`` model parallelism; reference
``python/mxnet/module/executor_group.py:77``, ``src/kvstore/comm.h:211``,
``src/executor/graph_executor.cc:318``) and extends it to the parallelism
modes the reference lacks (tensor/sequence/expert — SURVEY.md §2.4).

One fused jitted step = forward + backward + optimizer update, with every
array carrying a ``NamedSharding`` over a ``jax.sharding.Mesh``.  XLA inserts
the collectives (psum over the ``data`` axis for gradients — the kvstore
all-reduce; all-gather/reduce-scatter along ``model`` for sharded weights)
and schedules them to overlap with compute on ICI — the role the reference's
per-layer ``priority=-index`` push/pull scheduling plays by hand
(``model.py:94-110``).
"""

from __future__ import annotations

import contextlib as _contextlib
import functools
import time as _time

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as _np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import compile_cache as _compile_cache
from ..base import MXNetError
from ..observability import attribution as _attr
from ..observability import efficiency as _eff
from ..observability import memory as _mem
from ..observability import metrics as _metrics
from ..ops.fused.parity import case_rng, register_parity

__all__ = ["ShardedTrainer", "auto_tp_specs", "zero_extend_spec"]

# -- compile accounting: every jit cache miss (step / grad / fwd / each
# (n, unroll) pipeline trace) is one entry here.  Steady state records
# NOTHING — a counter that moves outside warmup IS the recompile bug the
# cache keys exist to prevent (changed pipeline depth, epoch-tail flush,
# resharded input), and the histogram says what each miss cost.
_M_COMPILES = _metrics.counter(
    "trainer_compiles_total",
    "Jit-cache misses (traces compiled), by cache key; steady-state "
    "training must not move this", ["cache"])
_M_COMPILE_T = _metrics.histogram(
    "trainer_compile_seconds",
    "Wall time of each first-call trace+compile, by cache key", ["cache"])
_M_STREAM_STALLS = _metrics.counter(
    "stream_stalls_total",
    "Stream-source stall timeouts surfaced to fit_stream; each is one "
    "bounded-retry episode, never a silent hang (watchdog rule "
    "stream_stall fires on a sustained run of them)")
_M_STREAM_SKIPPED = _metrics.counter(
    "stream_skipped_total",
    "Chunks abandoned by fit_stream's skip-and-count degraded mode "
    "after a typed corrupt-stream error")


def auto_tp_specs(symbol, arg_shapes, mesh, data_axis="data", model_axis="model"):
    """Heuristic tensor-parallel sharding specs for a symbol's parameters.

    Megatron-style: FullyConnected / Convolution output channels shard along
    ``model_axis`` when divisible by its size; everything else replicates.
    (The reference has no TP at all — this is capability-gap item §2.4.)
    """
    if model_axis not in mesh.axis_names:
        return {}
    msize = mesh.shape[model_axis]
    specs = {}
    for name, shape in arg_shapes.items():
        if name.endswith("_weight") and len(shape) >= 2 and shape[0] % msize == 0:
            specs[name] = P(model_axis, *([None] * (len(shape) - 1)))
        elif name.endswith("_bias") and len(shape) == 1 and shape[0] % msize == 0:
            specs[name] = P(model_axis)
    return specs


def zero_extend_spec(spec, shape, mesh, data_axis="data"):
    """Extend a parameter's PartitionSpec with the ``data`` axis on the first
    unsharded, divisible dimension — the ZeRO sharding rule.

    The reference shards optimizer state across parameter-server processes by
    key (``src/kvstore/kvstore_dist_server.h:136-205`` applies the optimizer on
    each server's shard); on a TPU mesh the same idea is a sharding
    annotation: optimizer state (and, for ZeRO-3/FSDP, the weights) live
    sliced along ``data`` and XLA inserts the reduce-scatter/all-gather.
    Returns ``spec`` unchanged when no dimension divides the axis size.
    """
    if data_axis not in mesh.axis_names:
        return spec
    dsize = mesh.shape[data_axis]
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used = [ax for e in entries if e is not None
            for ax in (e if isinstance(e, tuple) else (e,))]
    if data_axis in used:  # caller already shards this param over data
        return spec
    for i, (e, s) in enumerate(zip(entries, shape)):
        if e is None and s > 0 and s % dsize == 0:
            entries[i] = data_axis
            while entries and entries[-1] is None:
                entries.pop()
            return P(*entries)
    return spec


_STEP_COUNT = "__num_update__"  # reserved key in the optimizer-state tree


def resolve_update_op(optimizer, optimizer_params, momentum, learning_rate,
                      wd, rescale_grad, clip_gradient):
    """Resolve an optimizer name to ``(update_op, attrs, n_states, needs_t)``
    over the registered fused-update ops (reference
    ``src/operator/optimizer_op.cc``) — shared by ShardedTrainer and
    PipelinedTrainer so there is ONE spelling of the optimizer contract."""
    from ..ops.registry import get_op

    opt_name = (optimizer or "sgd").lower()
    opt_kwargs = dict(optimizer_params or {})
    if opt_name == "sgd":
        # momentum may arrive via the historical kwarg or (MXNet-parity)
        # optimizer_params; both at once must agree
        if ("momentum" in opt_kwargs and momentum
                and opt_kwargs["momentum"] != momentum):
            raise MXNetError(
                "momentum given twice (momentum=%r, optimizer_params"
                "['momentum']=%r)" % (momentum, opt_kwargs["momentum"]))
        eff_mom = opt_kwargs.pop("momentum", momentum)
        op_name = "sgd_mom_update" if eff_mom else "sgd_update"
        if eff_mom:
            opt_kwargs["momentum"] = eff_mom
    else:
        if momentum:
            raise MXNetError(
                "momentum= is an SGD knob; pass optimizer_params for %r"
                % opt_name)
        op_name = (opt_name if opt_name.endswith("_update")
                   else opt_name + "_update")
    try:
        update_op = get_op(op_name)
    except Exception:
        raise MXNetError(
            "no fused update op %r for optimizer %r" % (op_name, opt_name))
    static = {"lr": learning_rate, "wd": wd, "rescale_grad": rescale_grad,
              "clip_gradient": (clip_gradient if clip_gradient is not None
                                else -1.0)}
    static.update(opt_kwargs)
    attrs = update_op.parse_attrs(static)
    n_states = update_op.n_outputs(attrs) - 1
    return update_op, attrs, n_states, update_op.is_operand("t")


def sgd_mom_tree_stock(attrs, params, grads, moms, ok=None):
    """The reference spelling of the whole-tree momentum step, kept for
    the parity harness and the old bench (the trainer does not reach
    it): one ``sgd_mom_update`` per parameter, then (when ``ok`` is
    given) the ``skip_nonfinite`` guard as separate keep-old passes over
    each subtree — the per-parameter dispatch shape the reference
    updater (``model.py _update_params``) and the trainer's generic loop
    both spell.  Returns ``(new_params, new_moms)`` dicts over the same
    keys."""
    from ..ops.registry import get_op

    update = get_op("sgd_mom_update").fn
    new_p, new_m = {}, {}
    for n in params:
        new_p[n], new_m[n] = update(attrs, params[n], grads[n], moms[n])
    if ok is not None:
        keep = jax.tree_util.tree_map
        new_p = keep(lambda a, b: jnp.where(ok, a, b), new_p,
                     dict(params))
        new_m = keep(lambda a, b: jnp.where(ok, a, b), new_m,
                     dict(moms))
    return new_p, new_m


def fused_sgd_mom_tree(attrs, params, grads, moms, ok=None):
    """The whole-tree momentum step the trainer's bare-momentum SGD
    runs: rescale + clip + weight decay + momentum + the
    ``skip_nonfinite`` select, all folded into ONE pass per leaf inside
    the jitted step — no per-parameter op dispatches and no post-update
    guard round trips over the tree.  Plain jax on every backend (not a
    Pallas kernel); the parity harness holds it to
    :func:`sgd_mom_tree_stock`'s bytes."""
    lr, wd = attrs["lr"], attrs["wd"]
    mu, rescale = attrs["momentum"], attrs["rescale_grad"]
    clip = attrs.get("clip_gradient")

    def leaf(w, g, m):
        g = g * rescale
        if clip is not None and clip > 0:
            g = jnp.clip(g, -clip, clip)
        new_m = mu * m - lr * (g + wd * w)
        new_w = w + new_m
        if ok is not None:
            new_w = jnp.where(ok, new_w, w)
            new_m = jnp.where(ok, new_m, m)
        return new_w, new_m

    out = {n: leaf(params[n], grads[n], moms[n]) for n in params}
    return ({n: wm[0] for n, wm in out.items()},
            {n: wm[1] for n, wm in out.items()})


def _sgd_mom_tree_case(case):
    guard, clip = case
    rng = case_rng(case)
    shapes = {"w1": (64,), "w2": (7, 9), "w3": (128, 3), "b": (5,)}

    def tree():
        return {n: jnp.asarray(rng.standard_normal(s), jnp.float32)
                for n, s in shapes.items()}

    params, grads, moms = tree(), tree(), tree()
    attrs = {"lr": 0.05, "wd": 1e-4, "momentum": 0.9,
             "rescale_grad": 1.0, "clip_gradient": clip}
    ok = None if guard is None else jnp.asarray(guard)
    return (functools.partial(sgd_mom_tree_stock, attrs),
            functools.partial(fused_sgd_mom_tree, attrs),
            (params, grads, moms, ok))


register_parity(
    "sgd_mom_tree", _sgd_mom_tree_case, parity="bitwise", pallas=False,
    grid=(
        (None, -1.0),    # no guard
        (True, -1.0),    # guard passes: update applies
        (False, 0.5),    # guard trips: every leaf keeps old state
        (True, 0.25),    # guard + clip
    ))


def resolve_lr_fn(lr_scheduler, learning_rate):
    """Resolve a scheduler to a traced ``num_update -> lr`` callable (or
    None), validating at construction time rather than first trace.

    Matching the reference optimizer contract (``optimizer.py`` sets
    ``lr_scheduler.base_lr = optimizer.learning_rate``), the scheduler
    object is retargeted **in place** to this trainer's ``learning_rate``.
    Consequence: one scheduler instance cannot be shared between trainers
    with different learning rates — the last-constructed trainer wins.
    Pass separate scheduler instances (or a plain ``callable(num_update)``,
    which is never mutated) when rates differ."""
    if lr_scheduler is None:
        return None
    from ..lr_scheduler import LRScheduler

    if isinstance(lr_scheduler, LRScheduler):
        lr_scheduler.base_lr = learning_rate
        # fail at construction, not first trace: the subclass must provide
        # the jnp form next to its host __call__
        if type(lr_scheduler).traced is LRScheduler.traced:
            raise MXNetError(
                "%s has no traced() form for in-step evaluation"
                % type(lr_scheduler).__name__)
        return lr_scheduler.traced
    if callable(lr_scheduler):
        return lr_scheduler  # jnp map of the traced counter
    raise MXNetError("lr_scheduler must be an LRScheduler or a "
                     "callable(num_update) -> lr")




class ShardedTrainer:
    """A whole-model sharded training step over a device mesh.

    Parameters
    ----------
    symbol : Symbol
        Loss-headed symbol (e.g. ``SoftmaxOutput`` net).
    mesh : jax.sharding.Mesh
        Logical device mesh; conventional axes: ``data`` (DP), ``model`` (TP),
        ``seq`` (SP), ``expert`` (EP), ``pipe`` (PP).
    data_shapes : dict name -> global shape for data inputs.
    data_specs : dict name -> PartitionSpec for data inputs (default: batch
        axis over ``data``, and — when a ``seq`` axis exists in the mesh —
        axis 1 over ``seq`` for rank>=2 integer/sequence inputs).
    param_specs : dict name -> PartitionSpec (default: auto_tp_specs).

    Output-shape contract under ``grad_accum=k``: batched outputs (rank>=1
    per microbatch) merge back row-major to the full-batch shape; rank-0
    scalar heads are AVERAGED across the k microbatches so shapes (not
    dtypes — integer scalars promote to float) are invariant to k.  The
    average equals the full-batch value for mean-normalized losses over
    the equal row-major split; a sum-normalized scalar head reads k times
    smaller — fold the factor into ``grad_scale``/``rescale_grad`` or
    normalize per-row if the logged magnitude matters.
    """

    @_compile_cache.scope("trainer.build")
    def __init__(self, symbol, mesh: Mesh, data_shapes: Dict[str, tuple],
                 label_shapes: Optional[Dict[str, tuple]] = None,
                 data_specs: Optional[Dict[str, P]] = None,
                 param_specs: Optional[Dict[str, P]] = None,
                 type_dict: Optional[Dict[str, str]] = None,
                 learning_rate=0.01, momentum=0.0, wd=0.0,
                 rescale_grad=1.0, clip_gradient=None,
                 data_axis="data", dtype="float32",
                 remat=False, remat_policy=None, zero_stage=0,
                 optimizer="sgd", optimizer_params=None, lr_scheduler=None,
                 grad_accum=1, multi_precision=False, skip_nonfinite=False,
                 pipeline_steps=1):
        from ..executor import _graph_fn
        from ..symbol import _infer

        from . import default_mesh

        self.symbol = symbol
        self.mesh = mesh
        self.data_axis = data_axis
        label_shapes = label_shapes or {}
        type_dict = dict(type_dict or {})
        # gradient accumulation: the declared shapes stay the GLOBAL batch;
        # the graph traces at the microbatch (dim0 / grad_accum), the step
        # lax.scans the microbatches and sums gradients before ONE optimizer
        # update — effective batch beyond HBM with identical update math.
        # place_batch splits row-major: microbatch i = rows [i*mb, (i+1)*mb).
        self.grad_accum = int(grad_accum)
        if self.grad_accum < 1:
            raise MXNetError("grad_accum must be >= 1")
        # multi-step fusion: pipeline_steps=K runs K optimizer steps inside
        # ONE jitted lax.scan over a stacked superbatch, so the host→device
        # dispatch (docs/PERF.md "Batch-32 inference") is paid once per K
        # steps.  Semantics are the per-step
        # path's exactly: per-step RNG keys, LR schedule, skip_nonfinite
        # verdicts, and grad_accum all evaluate per scanned step.
        self.pipeline_steps = int(pipeline_steps)
        if self.pipeline_steps < 1:
            raise MXNetError("pipeline_steps must be >= 1")
        if self.grad_accum > 1:
            def _micro(name, shp):
                if not shp or shp[0] % self.grad_accum:
                    raise MXNetError(
                        "input %r dim0 %r not divisible by grad_accum=%d"
                        % (name, shp, self.grad_accum))
                return (shp[0] // self.grad_accum,) + tuple(shp[1:])

            data_shapes = {n: _micro(n, s) for n, s in data_shapes.items()}
            label_shapes = {n: _micro(n, s)
                            for n, s in label_shapes.items()}
        shapes = dict(data_shapes)
        shapes.update(label_shapes)
        # mesh-aware ops (ring attention) consult the ambient mesh while the
        # graph traces; scope it so multiple trainers don't clobber each other
        with default_mesh(mesh):
            arg_shapes, out_shapes, aux_shapes, arg_dtypes, aux_dtypes = _infer(
                symbol, shapes, type_dict)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        self._input_names = set(shapes)
        self.param_names = [n for n in arg_names if n not in self._input_names]
        self.arg_shapes = dict(zip(arg_names, arg_shapes))
        self.aux_shapes = dict(zip(aux_names, aux_shapes))
        self.arg_dtypes = dict(zip(arg_names, arg_dtypes))
        self.aux_dtypes = dict(zip(aux_names, aux_dtypes))
        if any(self.arg_shapes[n] is None for n in arg_names):
            missing = [n for n in arg_names if self.arg_shapes[n] is None]
            raise MXNetError("cannot infer shapes for %s" % missing)

        # -- shardings ---------------------------------------------------
        pspecs = auto_tp_specs(
            symbol, {n: self.arg_shapes[n] for n in self.param_names}, mesh,
            data_axis)
        pspecs.update(param_specs or {})
        self.param_specs = {n: pspecs.get(n, P()) for n in self.param_names}
        # ZeRO: stage>=1 shards optimizer state (and constrains gradients)
        # along the data axis; stage>=3 shards the weights themselves (FSDP).
        # Stages compose with TP specs — zero_extend_spec only claims a
        # dimension the TP spec left unsharded.
        if zero_stage not in (0, 1, 2, 3):
            raise MXNetError("zero_stage must be 0, 1, 2, or 3")
        self.zero_stage = zero_stage
        self.opt_specs = dict(self.param_specs)
        if zero_stage >= 1:
            for n in self.param_names:
                self.opt_specs[n] = zero_extend_spec(
                    self.param_specs[n], self.arg_shapes[n], mesh, data_axis)
            if zero_stage >= 3:
                self.param_specs = dict(self.opt_specs)
        dspecs = {}
        for n in self._input_names:
            shp = self.arg_shapes[n]
            spec = [None] * len(shp)
            if len(shp) >= 1 and data_axis in mesh.axis_names \
                    and shp[0] % mesh.shape[data_axis] == 0:
                spec[0] = data_axis
            if len(shp) >= 2 and "seq" in mesh.axis_names \
                    and shp[1] % mesh.shape["seq"] == 0:
                spec[1] = "seq"
            dspecs[n] = P(*spec)
        dspecs.update(data_specs or {})
        self.data_specs = dspecs

        self._run = _graph_fn(symbol)
        # rematerialization: trade FLOPs for HBM in backward (the reference's
        # memonger / MXNET_BACKWARD_DO_MIRROR, graph_executor.cc:87-89 —
        # here it's jax.checkpoint over the traced graph).  remat_policy is
        # a jax.checkpoint_policies name, e.g. 'dots_saveable' keeps matmul
        # outputs (MXU work) and recomputes the cheap elementwise chains.
        self._remat = bool(remat) or remat_policy is not None
        self._remat_policy = (getattr(jax.checkpoint_policies, remat_policy)
                              if remat_policy is not None else None)
        # -- optimizer: any registered fused-update op (the single source of
        # update math shared with the imperative Optimizer classes).  The
        # bias-correction step count and LR schedules both ride an on-device
        # counter so long runs never recompile (Optimizer sets
        # sched.base_lr, reference optimizer.py:60-61).
        (self._update_op, self._opt_attrs, self._n_states,
         self._needs_t) = resolve_update_op(
            optimizer, optimizer_params, momentum, learning_rate, wd,
            rescale_grad, clip_gradient)
        self._lr_fn = resolve_lr_fn(lr_scheduler, learning_rate)
        self._needs_count = self._needs_t or self._lr_fn is not None
        # -- multi-precision: weights live in a low-precision dtype (HBM
        # bandwidth + memory), the optimizer updates an fp32 MASTER copy
        # stored as the leading optimizer-state slot (so ZeRO shards it —
        # the bf16 + sharded-fp32-master recipe).  The reference's
        # fp16 + multi_precision SGD concept, TPU-idiomatic in bf16.
        if multi_precision:
            self._mp_dtype = ("bfloat16" if multi_precision is True
                              else str(multi_precision))
        else:
            self._mp_dtype = None
        self._diff_set = {
            n for n in self.param_names
            if not _np.issubdtype(_np.dtype(self.arg_dtypes.get(n, "float32")),
                                  _np.integer)
        }
        self._use_momentum = (self._n_states > 0
                              or self._mp_dtype is not None)
        # -- non-finite guard: when enabled the step checks loss + every
        # gradient for NaN/Inf IN-GRAPH and, on a bad batch, keeps the old
        # (params, moms, aux) via jnp.where — the step's inputs are donated,
        # so a host-side revert is impossible by construction.  The step
        # then reports the verdict as one extra trailing scalar output
        # (1.0 ok / 0.0 skipped) that ``fit`` consumes for its
        # skip-count/abort policy.  Opt-in: the trace changes shape.
        self._skip_nonfinite = bool(skip_nonfinite)
        self._step_raw = None  # untraced step body, shared with pipeline_fn
        self._jit_step = None
        self._jit_fwd = None
        self._jit_grad = None  # gradient-only step for kvstore-backed fit
        self._jit_pipe = {}  # n-step pipelines keyed by (n, unroll) —
        # partial epoch-tail flushes get their own cached trace

    def _param_dtype(self, name):
        """On-device storage dtype for a parameter (the working copy)."""
        if self._mp_dtype is not None and name in self._diff_set:
            return self._mp_dtype
        return self.arg_dtypes.get(name, "float32")

    def _state_layout(self, name):
        """(slots, state_dtype, bare) of ``moms[name]``: slot count
        (+1 leading fp32 master under multi_precision), element dtype, and
        whether a single slot stores bare (legacy sgd-momentum layout)."""
        mp = self._mp_dtype is not None and name in self._diff_set
        slots = self._n_states + (1 if mp else 0)
        dtype = "float32" if mp else self.arg_dtypes.get(name, "float32")
        return slots, dtype, (slots == 1 and not mp)

    # ------------------------------------------------------------------
    def _sharding(self, spec):
        return NamedSharding(self.mesh, spec)

    @_compile_cache.scope("trainer.build")
    def init(self, initializer=None, seed=0):
        """Create (params, moms, aux) host-side then place sharded on mesh."""
        from ..initializer import Uniform, InitDesc

        initializer = initializer or Uniform(0.07)
        # initializers draw from the global numpy stream (reference
        # initializer.py does the same); seed it for reproducibility but
        # restore the caller's stream position afterwards
        saved_state = _np.random.get_state()
        _np.random.seed(seed)
        try:
            params, moms, aux = {}, {}, {}
            for n in self.param_names:
                shp = self.arg_shapes[n]
                arr = _np.zeros(shp, dtype=self.arg_dtypes.get(n, "float32"))
                initializer(InitDesc(n), _HostArray(arr))
                params[n] = jax.device_put(
                    arr.astype(self._param_dtype(n)),
                    self._sharding(self.param_specs[n]))
                slots, sdtype, bare = self._state_layout(n)
                if slots:
                    oshard = self._sharding(self.opt_specs[n])
                    mp_here = self._mp_dtype is not None and n in self._diff_set
                    states = []
                    if mp_here:  # leading slot = the fp32 master copy
                        states.append(jax.device_put(
                            arr.astype(_np.float32), oshard))
                    while len(states) < slots:
                        states.append(jax.device_put(
                            _np.zeros(shp, dtype=sdtype), oshard))
                    moms[n] = states[0] if bare else tuple(states)
            for n, shp in self.aux_shapes.items():
                init_val = (_np.ones if n.endswith("_var") or "moving_var" in n
                            else _np.zeros)
                aux[n] = jax.device_put(
                    init_val(shp, dtype=self.aux_dtypes.get(n, "float32")),
                    self._sharding(P()))
        finally:
            _np.random.set_state(saved_state)
        if self._needs_count:
            moms[_STEP_COUNT] = jax.device_put(
                _np.zeros((), _np.int32), self._sharding(P()))
        return params, moms, aux

    def opt_state_struct(self):
        """ShapeDtypeStructs matching ``init()``'s optimizer-state tree
        (tuples for multi-state optimizers, the on-device step counter for
        bias-corrected ones) — the restore target for sharded checkpoints."""
        if not self._use_momentum and not self._needs_count:
            return {}
        out = {}
        if self._use_momentum:
            for n in self.param_names:
                slots, sdtype, bare = self._state_layout(n)
                if not slots:
                    continue
                s = jax.ShapeDtypeStruct(
                    tuple(self.arg_shapes[n]), sdtype,
                    sharding=self._sharding(self.opt_specs[n]))
                out[n] = s if bare else (s,) * slots
        if self._needs_count:
            out[_STEP_COUNT] = jax.ShapeDtypeStruct(
                (), _np.int32, sharding=self._sharding(P()))
        return out

    def place_batch(self, arrays: Dict[str, _np.ndarray], train=True):
        """Shard a host batch onto the mesh along the declared input specs.
        With ``grad_accum=k`` a TRAINING batch splits row-major into
        ``[k, dim0/k, ...]`` on the host (free) so the scanned microbatch
        axis is unsharded and each device keeps its own rows.
        ``train=False`` places the batch unsplit for ``forward_fn`` —
        inference has no accumulation semantics, so any batch size goes."""
        out = {}
        for n, v in arrays.items():
            v = _np.asarray(v)
            if train and self.grad_accum > 1:
                k = self.grad_accum
                if v.shape[0] % k:
                    raise MXNetError(
                        "batch %r dim0 %d not divisible by grad_accum=%d"
                        % (n, v.shape[0], k))
                v = v.reshape((k, v.shape[0] // k) + v.shape[1:])
            out[n] = jax.device_put(
                v, self._sharding(self._batch_spec(n) if train
                                  else self.data_specs[n]))
        return out

    # ------------------------------------------------------------------
    def _build_step(self):
        """The raw (untraced) fused step body — the ONE spelling of the
        train-step math, traced standalone by ``step_fn`` and under
        ``lax.scan`` by ``pipeline_fn`` so the two paths cannot drift."""
        if self._step_raw is not None:
            return self._step_raw
        run = self._run
        use_mom = self._use_momentum
        update_op = self._update_op
        opt_attrs = self._opt_attrs
        needs_count = self._needs_count
        lr_fn = self._lr_fn
        diff = [n for n in self.param_names if n in self._diff_set]
        layouts = {n: self._state_layout(n) for n in self.param_names}
        mp_set = (set(diff) if self._mp_dtype is not None else set())
        mp_dtype = self._mp_dtype
        # fused-tier whole-tree optimizer step: only the plain momentum
        # shape qualifies (bare momentum slot per param, no fp32-master
        # mixed precision, no traced step count) — everything else stays
        # on the generic per-op loop below
        use_tree = (use_mom and update_op.name == "sgd_mom_update"
                    and not mp_set and not needs_count
                    and all(layouts[n][2] for n in diff))

        graph = run
        if self._remat:
            graph = jax.checkpoint(
                run, policy=self._remat_policy, static_argnums=(3,))

        accum = self.grad_accum

        def step(params, moms, aux, batch, rng):
            def micro_grads(dparams, aux_c, mb, key):
                def loss_fn(p):
                    args = dict(mb)
                    args.update(params)
                    args.update(p)
                    outs, new_aux = graph(args, aux_c, key, True)
                    total = sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
                    return total, (outs, new_aux)

                return jax.value_and_grad(loss_fn, has_aux=True)(dparams)

            def constrain(g):
                # force the gradient reduction to land sharded
                # (reduce-scatter rather than all-reduce) so the optimizer
                # math runs on 1/dp of each tensor — the ZeRO saving
                if not zero:
                    return g
                return {n: jax.lax.with_sharding_constraint(
                    g[n], zero_shard[n]) for n in g}

            dparams = {n: params[n] for n in diff}
            if accum == 1:
                (loss_total, (outs, new_aux)), grads = micro_grads(
                    dparams, aux, batch, rng)
                grads = constrain(grads)
            else:
                def body(carry, xs):
                    gacc, aux_c, lsum = carry
                    mb, i = xs
                    (lv, (outs_i, aux_n)), g = micro_grads(
                        dparams, aux_c, mb, jax.random.fold_in(rng, i))
                    gacc = constrain({
                        n: gacc[n] + g[n].astype(jnp.float32) for n in g})
                    return (gacc, aux_n, lsum + lv), outs_i

                gacc0 = constrain({
                    n: jnp.zeros(dparams[n].shape, jnp.float32)
                    for n in diff})
                (gacc, new_aux, loss_total), outs_stack = jax.lax.scan(
                    body, (gacc0, aux, jnp.float32(0)),
                    (batch, jnp.arange(accum)))
                # multi-precision updates consume fp32 grads directly;
                # otherwise return to the parameter dtype
                grads = {n: (gacc[n] if n in mp_set
                             else gacc[n].astype(dparams[n].dtype))
                         for n in diff}
                # merge the stacked microbatch axis back into the batch axis
                # (row-major — the inverse of place_batch's split); scalar
                # heads (rank-0 per microbatch) average across microbatches
                # so output shapes are invariant to grad_accum — exact for
                # mean-normalized losses over the equal row-major split
                outs = [o.reshape((o.shape[0] * o.shape[1],) + o.shape[2:])
                        if o.ndim >= 2 else o.mean(0) for o in outs_stack]
            if guard:
                ok = jnp.isfinite(loss_total)
                for n in diff:
                    ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(grads[n])))
            new_params, new_moms = dict(params), dict(moms)
            attrs = opt_attrs
            if needs_count:
                t_new = moms[_STEP_COUNT] + 1
                new_moms[_STEP_COUNT] = t_new
                traced = {"t": t_new}
                if lr_fn is not None:
                    traced["lr"] = lr_fn(t_new)
                attrs = update_op.with_operands(opt_attrs, **traced)
            if use_tree:
                tree_p, tree_m = fused_sgd_mom_tree(
                    attrs, {n: params[n] for n in diff}, grads,
                    {n: moms[n] for n in diff}, ok if guard else None)
                new_params.update(tree_p)
                new_moms.update(tree_m)
                if guard:
                    # params/moms guard is folded into the tree step;
                    # aux still keeps its old state on a bad batch
                    new_aux = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(ok, a, b), new_aux, aux)
                    outs = list(outs) + [ok.astype(jnp.float32)]
                return outs, new_params, new_moms, new_aux
            for n in diff:
                slots, _, bare = layouts[n]
                st = moms.get(n, ()) if use_mom else ()
                if bare:
                    st = (st,)
                if n in mp_set:
                    # update the fp32 master (leading state slot); the
                    # working weight is its low-precision cast
                    master, op_st = st[0], st[1:]
                    upd, _ = update_op.apply(
                        attrs,
                        [master, grads[n].astype(jnp.float32), *op_st])
                    new_params[n] = upd[0].astype(mp_dtype)
                    new_moms[n] = tuple(upd)
                else:
                    upd, _ = update_op.apply(
                        attrs, [params[n], grads[n], *st])
                    new_params[n] = upd[0]
                    if bare:
                        new_moms[n] = upd[1]
                    elif slots:
                        new_moms[n] = tuple(upd[1:])
            if guard:
                # bad batch: keep EVERY piece of old state (weights, momenta,
                # the schedule counter, aux) — the skipped step never happened
                keep = jax.tree_util.tree_map
                new_params = keep(lambda a, b: jnp.where(ok, a, b),
                                  new_params, params)
                new_moms = keep(lambda a, b: jnp.where(ok, a, b),
                                new_moms, moms)
                new_aux = keep(lambda a, b: jnp.where(ok, a, b),
                               new_aux, aux)
                outs = list(outs) + [ok.astype(jnp.float32)]
            return outs, new_params, new_moms, new_aux

        guard = self._skip_nonfinite
        zero = self.zero_stage >= 1
        zero_shard = {n: self._sharding(self.opt_specs[n])
                      for n in self.param_names}
        self._step_raw = step
        return step

    def _step_shardings(self):
        """NamedSharding trees ``(pshard, mshard, ashard, dshard)`` for the
        fused step's arguments — one spelling shared by ``step_fn`` and
        ``pipeline_fn`` so their placement contracts cannot diverge."""
        zero_shard = {n: self._sharding(self.opt_specs[n])
                      for n in self.param_names}
        pshard = {n: self._sharding(self.param_specs[n])
                  for n in self.param_names}
        mshard = {}
        if self._use_momentum:
            for n in self.param_names:
                slots, _, bare = self._state_layout(n)
                if not slots:
                    continue
                mshard[n] = (zero_shard[n] if bare
                             else (zero_shard[n],) * slots)
        if self._needs_count:
            mshard[_STEP_COUNT] = self._sharding(P())
        ashard = {n: self._sharding(P()) for n in self.aux_shapes}
        dshard = {n: self._sharding(self._batch_spec(n))
                  for n in self._input_names}
        return pshard, mshard, ashard, dshard

    def _compile_counted(self, cache, jitted, raw=None, steps=1):
        """Wrap a jitted callable so its FIRST call (the trace+compile)
        lands in the compile-accounting families under ``cache``; every
        later call passes straight through.  Pairs with the jit caches:
        one wrapper per cache entry, so steady-state fit records zero
        compiles and a moving counter means the cache keys missed.

        With ``raw`` (the underlying ``jax.jit`` object), the first
        call also records the compiled program's HLO cost analysis —
        FLOPs / bytes / memory footprint under
        ``trainer_compile_flops{cache}`` etc. and, via ``steps`` (how
        many optimizer steps one dispatch advances — ``pipeline_fn(n)``
        scans ``n``; 0 = not a train step), the per-step model-FLOPs
        figure MFU is derived from (``observability.efficiency``).  The
        lowering happens BEFORE the dispatch runs, while donated
        argument buffers are still live; its cost (one extra AOT
        compile per cache under the default
        ``MXNET_TPU_COST_ANALYSIS=compiled`` tier) is deliberately
        inside the ``trainer_compile_seconds`` window so the goodput
        ledger books it as recompile badput.

        That window is the start-up scope ``trainer.first_call``
        (``program`` = ``cache``; :mod:`mxnet_tpu.compile_cache`), whose
        clock the histogram takes; the cost analysis is the scope
        ``trainer.cost_analysis`` inside it, and the table of what both
        held is logged when the call returns."""
        done = []
        mesh = self.mesh

        def call(*args, **kwargs):
            if done:
                return jitted(*args, **kwargs)
            with _compile_cache.scope("trainer.first_call", cache) as first:
                if raw is not None:
                    from . import default_mesh

                    def _lower():
                        with default_mesh(mesh):
                            return raw.lower(*args, **kwargs)

                    _eff.record_compile(cache, _lower, steps=steps)
                out = jitted(*args, **kwargs)
            done.append(True)
            _M_COMPILES.labels(cache).inc()
            _M_COMPILE_T.labels(cache).observe(first.seconds)
            _compile_cache.log_table("first call of %r" % cache, [first])
            return out

        return call

    def step_fn(self):
        """The fused train step: (params, moms, aux, batch, rng) ->
        (outputs, new_params, new_moms, new_aux)."""
        if self._jit_step is not None:
            return self._jit_step
        step = self._build_step()
        pshard, mshard, ashard, dshard = self._step_shardings()
        self._jit_step_raw = jax.jit(
            step,
            in_shardings=(pshard, mshard, ashard, dshard, None),
            out_shardings=(None, pshard, mshard, ashard),
            donate_argnums=(0, 1),
        )
        self._jit_step = self._compile_counted(
            "step", self._with_mesh(self._jit_step_raw),
            raw=self._jit_step_raw)
        return self._jit_step

    # ------------------------------------------------------------------
    def _superbatch_spec(self, name):
        """Input spec for the stacked pipeline axis: ``[K, ...]`` with the
        leading (scanned) step axis unsharded on top of ``_batch_spec``."""
        return P(None, *self._batch_spec(name))

    def place_superbatch(self, batches):
        """Stack K host batches into one ``[K, ...]`` superbatch sharded on
        the mesh — ``pipeline_fn``'s input.  Each element of ``batches`` is
        a ``name -> host array`` dict; under ``grad_accum`` each batch is
        first split row-major exactly as ``place_batch`` would (so the
        scanned layout is ``[K, grad_accum, mb, ...]``)."""
        if not batches:
            raise MXNetError("place_superbatch needs at least one batch")
        out = {}
        ga = self.grad_accum
        for n in batches[0]:
            vs = []
            for b in batches:
                v = _np.asarray(b[n])
                if ga > 1:
                    if v.shape[0] % ga:
                        raise MXNetError(
                            "batch %r dim0 %d not divisible by grad_accum=%d"
                            % (n, v.shape[0], ga))
                    v = v.reshape((ga, v.shape[0] // ga) + v.shape[1:])
                vs.append(v)
            out[n] = jax.device_put(
                _np.stack(vs), self._sharding(self._superbatch_spec(n)))
        return out

    def pipeline_fn(self, n=None, unroll=None):
        """``n`` fused steps in ONE dispatch: ``(params, moms, aux,
        superbatch, base_key, step0) -> (stacked_outs, params, moms, aux)``.

        ``lax.scan`` over the superbatch's leading axis runs the SAME raw
        step body ``step_fn`` traces; scanned step ``i`` draws
        ``fold_in(base_key, step0 + i)`` — ``fold_in`` of a traced counter
        is bitwise the eager per-step stream, so pipelined parameter
        evolution is the per-step path's exactly.  Outputs come back
        stacked ``[n, ...]`` (the trailing skip_nonfinite verdict, when
        enabled, as an ``[n]`` vector) and are fetched once per flush —
        the host is crossed once per ``n`` steps.  Jitted per
        ``(n, unroll)`` and cached, so epoch-tail partial flushes reuse
        their own trace.

        ``unroll`` defaults to full (the scan emits ``n`` copies of the
        step): pipeline depths are small, and the rolled while-loop
        measured ~5x slower per step on XLA:CPU (the loop carries the
        whole parameter tree through per-iteration buffer shuffles that
        straight-line code avoids).  Pass ``unroll=1`` to trade that for
        an ``n``-independent compile time at large depths — or when
        bitwise-exact parity with the per-step path matters for
        multi-state optimizers: full unroll lets XLA fuse across
        iterations, which moved adam by ~1e-8 in testing (sgd/momentum/
        multi-precision stayed exact either way)."""
        if n is None:
            n = self.pipeline_steps
        n = int(n)
        if n < 1:
            raise MXNetError("pipeline_fn needs n >= 1")
        unroll = n if unroll is None else int(unroll)
        cached = self._jit_pipe.get((n, unroll))
        if cached is not None:
            return cached
        step = self._build_step()

        def pipe(params, moms, aux, superbatch, base_key, step0):
            def body(carry, xs):
                p, m, a = carry
                batch, i = xs
                key = jax.random.fold_in(base_key, step0 + i)
                outs, p, m, a = step(p, m, a, batch, key)
                return (p, m, a), outs

            (p, m, a), outs_stack = jax.lax.scan(
                body, (params, moms, aux),
                (superbatch, jnp.arange(n, dtype=jnp.int32)),
                unroll=unroll)
            return outs_stack, p, m, a

        pshard, mshard, ashard, _ = self._step_shardings()
        sshard = {nm: self._sharding(self._superbatch_spec(nm))
                  for nm in self._input_names}
        fn = jax.jit(
            pipe,
            in_shardings=(pshard, mshard, ashard, sshard, None, None),
            out_shardings=(None, pshard, mshard, ashard),
            donate_argnums=(0, 1),
        )
        wrapped = self._compile_counted(
            "pipe:%d:%d" % (n, unroll), self._with_mesh(fn), raw=fn,
            steps=n)
        self._jit_pipe[(n, unroll)] = wrapped
        return wrapped

    def _batch_spec(self, name):
        """Input spec as the step receives it (microbatch axis prepended
        under grad_accum — matching place_batch's host-side split)."""
        spec = self.data_specs[name]
        return P(None, *spec) if self.grad_accum > 1 else spec

    def lowered_step(self, params, moms, aux, batch, rng):
        """AOT-lower the fused step for inspection (cost/memory analysis via
        ``.compile().memory_analysis()`` — the memonger accounting)."""
        from . import default_mesh

        self.step_fn()
        with default_mesh(self.mesh):
            return self._jit_step_raw.lower(params, moms, aux, batch, rng)

    def grad_fn(self):
        """Jitted gradient-only step for parameter-server training:
        ``(params, aux, batch, rng) -> (outputs, grads, new_aux)``.

        Where ``step_fn`` fuses forward + backward + optimizer update,
        this stops at the gradients: the optimizer runs wherever the
        authoritative weights live — for ``kvstore='dist_async'`` that is
        the (replicated) parameter server, which applies the update the
        moment the pushed gradient arrives (``set_optimizer`` contract).
        Inputs are NOT donated: the caller re-feeds the same ``params``
        until the next pull replaces them."""
        if self._jit_grad is not None:
            return self._jit_grad
        run = self._run
        graph = run
        if self._remat:
            graph = jax.checkpoint(
                run, policy=self._remat_policy, static_argnums=(3,))
        diff = [n for n in self.param_names if n in self._diff_set]

        def gstep(params, aux, batch, rng):
            def loss_fn(p):
                args = dict(batch)
                args.update(params)
                args.update(p)
                outs, new_aux = graph(args, aux, rng, True)
                total = sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
                return total, (outs, new_aux)

            dparams = {n: params[n] for n in diff}
            (_, (outs, new_aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(dparams)
            return outs, grads, new_aux

        pshard, _, ashard, dshard = self._step_shardings()
        gjit = jax.jit(gstep, in_shardings=(pshard, ashard, dshard, None))
        self._jit_grad = self._compile_counted(
            "grad", self._with_mesh(gjit), raw=gjit)
        return self._jit_grad

    def forward_fn(self):
        """Jitted inference forward: (params, aux, batch) -> outputs."""
        if self._jit_fwd is not None:
            return self._jit_fwd
        run = self._run

        def fwd(params, aux, batch, rng):
            # inference takes the batch UNSPLIT regardless of grad_accum —
            # accumulation only exists to fit the backward pass in HBM
            args = dict(batch)
            args.update(params)
            outs, _ = run(args, aux, rng, False)
            return outs

        pshard = {n: self._sharding(self.param_specs[n]) for n in self.param_names}
        ashard = {n: self._sharding(P()) for n in self.aux_shapes}
        dshard = {n: self._sharding(self.data_specs[n])
                  for n in self._input_names}
        fjit = jax.jit(fwd, in_shardings=(pshard, ashard, dshard, None))
        # steps=0: the eval forward is not a training step — its cost
        # rows are recorded, the model-FLOPs/step gauge is left alone
        self._jit_fwd = self._compile_counted(
            "fwd", self._with_mesh(fjit), raw=fjit, steps=0)
        return self._jit_fwd

    # ------------------------------------------------------------------
    def fit(self, train_data, eval_data=None, num_epoch=1, seed=0,
            eval_metric="accuracy", initializer=None, state=None,
            begin_epoch=0, checkpoint_dir=None, checkpoint_every=None,
            resume=None, max_bad_steps=5, log_every=50, logger=None,
            batch_end_callback=None, metric_every=1, kvstore=None,
            roster=None):
        """Mesh-native training loop — ``Module.fit``'s role
        (reference ``module/base_module.py:368``) for a ``ShardedTrainer``:
        epochs over a ``DataIter``, metric updates, throughput logging
        (``Speedometer``, reference ``callback.py:89``), optional eval pass
        and sharded checkpoints.

        Pipelined execution
        -------------------
        With ``pipeline_steps=K > 1`` each dispatch runs a K-step
        ``pipeline_fn`` flush over a superbatch that a background
        ``PrefetchFeeder`` (engine IO lane) staged while the previous
        flush computed — dispatch and host-feed latency hide behind
        device work, and parameter evolution stays bitwise the per-step
        path's (same per-step RNG keys, LR schedule, skip policy).
        Chunk sizes are planned so flush boundaries land exactly on
        ``checkpoint_every`` multiples — checkpoints and their resume
        metas are identical to the per-step path's, including resume
        from a checkpoint that falls mid-superbatch.  ``metric_every=F``
        fetches step outputs for the metric only every F-th flush (the
        non-blocking-metrics knob: the skipped flushes never sync on a
        readback); the epoch metric then samples 1/F of the flushes.
        The trailing short flush of an epoch reuses a cached smaller
        trace, so tails cost one extra compile, not wrong math.

        Fault tolerance
        ---------------
        ``checkpoint_every=N`` saves every N global steps (numbered by
        global step) in addition to epoch ends; without it, epoch-end
        saves keep the historical ``epoch + 1`` numbering.  Every save
        made by this loop also writes a ``fit-meta-<step>.json`` sidecar
        recording the loop position (global step, epoch, batch offset,
        RNG anchor).

        ``resume="auto"`` restarts from the newest restorable checkpoint
        in ``checkpoint_dir``: the newest one is validated by actually
        restoring it, and on failure (torn write, corrupt shard) the loop
        falls back to the previous step, then the one before, starting
        fresh only when none restore.  A resumed run re-enters the
        interrupted epoch at the saved batch offset with the SAME
        per-step RNG stream, so an interrupted+resumed run reproduces the
        uninterrupted run's parameters at every later checkpoint
        boundary.  (Resume replaces ``state``/``begin_epoch``;
        ``num_epoch`` stays the TOTAL epoch target, so a run killed at
        epoch 3 of 10 resumes and finishes the remaining 7.)

        When the trainer was built with ``skip_nonfinite=True``, each
        step's non-finite verdict feeds a skip policy: a bad batch leaves
        the state untouched and is excluded from the metric;
        ``max_bad_steps`` CONSECUTIVE bad batches abort with
        ``MXNetError`` (a diverged run re-reading the same poison forever
        is worse than a crash).

        ``state`` resumes from an existing ``(params, moms, aux)`` (e.g. a
        ``checkpoint.restore_sharded`` result); pass ``begin_epoch`` so
        checkpoint steps and history keys continue from the right epoch.
        NOTE: the step donates its inputs, so ``state``'s arrays are
        CONSUMED by the first step — a caller branching several runs from
        one restore must re-restore (or copy) per run.
        Returns ``((params, moms, aux), history)`` where ``history[epoch]``
        maps ``"train"``/``"eval"`` to the metric's ``get()`` result.

        ``kvstore=`` switches to parameter-server-backed training: each
        step computes gradients locally (``grad_fn``), pushes them to
        the kvstore (whose server-side optimizer — ``set_optimizer``,
        called by the caller beforehand — applies the update), and pulls
        the fresh weights back.  A replicated ``dist_async`` store rides
        out single-server failures transparently inside push/pull
        (heartbeat failover + same-seq retry), so a mid-epoch primary
        kill neither aborts the loop nor trips any resume machinery.

        ``roster=`` (kvstore path only) makes the worker set elastic:
        an :class:`~mxnet_tpu.elastic.WorkerRoster` assigns each global
        batch index to exactly one member rank, re-consulted EVERY
        batch — a ``roster.join``/``drain`` between two steps
        re-balances the remaining batches across the new member set
        with no epoch restart.  The loop records its position in the
        roster (``mark_progress``) after every batch, so a rank that
        joins mid-epoch fast-forwards its iterator to the group's
        ``resume_point()`` instead of re-running covered batches — the
        mid-epoch handoff that keeps ``resume="auto"``-style
        exactly-once batch coverage across topology changes.  The
        roster is this process's view of membership (in-process ranks
        share one instance; cross-process deployments drive each
        process's roster from the same control plane).

        A terminal failure escaping the loop (``ShardFailedError`` after
        a whole-group loss, poison surfacing at a sync point, divergence
        abort) triggers the flight recorder on its way out — with
        ``MXNET_TPU_FLIGHT_DIR`` set, a postmortem bundle (span tail,
        metrics snapshot, chaos rules, membership epochs, exception
        chain) lands there before the exception reaches the caller.
        """
        try:
            return self._fit_impl(
                train_data, eval_data=eval_data, num_epoch=num_epoch,
                seed=seed, eval_metric=eval_metric,
                initializer=initializer, state=state,
                begin_epoch=begin_epoch, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, resume=resume,
                max_bad_steps=max_bad_steps, log_every=log_every,
                logger=logger, batch_end_callback=batch_end_callback,
                metric_every=metric_every, kvstore=kvstore,
                roster=roster)
        except Exception as exc:
            from ..observability import flight_recorder as _flight

            _flight.record_failure("trainer.fit", exc)
            raise

    def _fit_impl(self, train_data, eval_data=None, num_epoch=1, seed=0,
                  eval_metric="accuracy", initializer=None, state=None,
                  begin_epoch=0, checkpoint_dir=None,
                  checkpoint_every=None, resume=None, max_bad_steps=5,
                  log_every=50, logger=None, batch_end_callback=None,
                  metric_every=1, kvstore=None, roster=None):
        import logging
        import time as _time

        import jax as _jax

        from .. import metric as _metric_mod
        from .. import observability as _obs
        from . import checkpoint as _ckpt
        from . import prefetch as _prefetch

        if kvstore is not None:
            return self._fit_kvstore(
                kvstore, train_data, eval_data=eval_data,
                num_epoch=num_epoch, seed=seed, eval_metric=eval_metric,
                initializer=initializer, state=state,
                begin_epoch=begin_epoch, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, resume=resume,
                log_every=log_every, logger=logger,
                batch_end_callback=batch_end_callback, roster=roster)

        if roster is not None:
            raise MXNetError(
                "roster= is the elastic-worker knob of the kvstore path; "
                "pass kvstore= as well (the local fused-update path has "
                "no cross-worker batch assignment to re-balance)")

        log = logger or logging.getLogger(__name__)
        metric = (eval_metric if isinstance(eval_metric, _metric_mod.EvalMetric)
                  else _metric_mod.create(eval_metric))

        # -- resume="auto": newest RESTORABLE checkpoint wins ------------
        resume_meta = None
        if resume not in (None, False, "auto"):
            raise MXNetError("resume must be None or 'auto', got %r"
                             % (resume,))
        if resume == "auto" and checkpoint_dir is not None:
            from .. import durable as _durable
            from ..base import CheckpointCorruptError as _CkptCorrupt

            _state_in = state  # restored on every fallback hop
            for ckpt_step in reversed(_ckpt.all_steps(checkpoint_dir)):
                try:
                    verified = _ckpt.verify_checkpoint(checkpoint_dir,
                                                       ckpt_step)
                    state = _ckpt.restore_sharded(checkpoint_dir, ckpt_step,
                                                  trainer=self)
                    resume_meta = _ckpt.load_fit_meta(checkpoint_dir,
                                                      ckpt_step)
                except _CkptCorrupt as exc:
                    state = _state_in
                    _durable.quarantine(
                        "checkpoint", exc, step=int(ckpt_step),
                        directory=str(checkpoint_dir),
                        file=getattr(exc, "file", None))
                    log.warning(
                        "resume: checkpoint step %d failed integrity "
                        "verification (%s); falling back to the previous "
                        "checkpoint", ckpt_step, exc)
                    continue
                except Exception as exc:  # noqa: BLE001 — fall back a step
                    log.warning(
                        "resume: checkpoint step %d failed validation "
                        "(%r); falling back to the previous checkpoint",
                        ckpt_step, exc)
                    continue
                if resume_meta is None and verified:
                    # manifest-era checkpoint with its sidecar missing:
                    # the save was killed between the shard write and the
                    # meta write — its loop position is unknowable, so
                    # fall back to the previous intact step
                    state = _state_in
                    log.warning(
                        "resume: checkpoint step %d has a manifest but no "
                        "fit-meta sidecar (save killed mid-write); falling "
                        "back to the previous checkpoint", ckpt_step)
                    continue
                if resume_meta is None:
                    # pre-sidecar checkpoint: its step number is an epoch
                    # boundary (the historical epoch+1 numbering) and the
                    # historical RNG anchoring applies
                    resume_meta = {"global_step": 0, "epoch": ckpt_step,
                                   "batch_in_epoch": 0, "seed": seed,
                                   "base_epoch": ckpt_step}
                log.info("resume: restored checkpoint step %d (epoch %d, "
                         "batch %d, global step %d)", ckpt_step,
                         resume_meta["epoch"],
                         resume_meta.get("batch_in_epoch", 0),
                         resume_meta.get("global_step", 0))
                break
            else:
                log.info("resume: no restorable checkpoint under %r — "
                         "starting fresh", checkpoint_dir)

        params, moms, aux = (state if state is not None
                             else self.init(initializer=initializer,
                                            seed=seed))
        # memory-ledger seams: the state trees are the pool baseline the
        # reconcile gate checks against jax.live_arrays() at sample points
        _mem.tag_tree("params", id(self), (params, aux))
        _mem.tag_tree("optimizer", id(self), moms)
        K = self.pipeline_steps
        step = self.step_fn() if K == 1 else None
        fwd = self.forward_fn()

        from ..io import batch_arrays as _io_batch_arrays

        def batch_arrays(batch, it):
            # the shared iterator hook, restricted to this graph's inputs
            return _io_batch_arrays(batch, it, self._input_names)

        from ..callback import Speedometer
        from ..model import BatchEndParam

        callbacks = (list(batch_end_callback)
                     if isinstance(batch_end_callback, (list, tuple))
                     else [batch_end_callback] if batch_end_callback
                     else [])
        speedo = None  # built from the first batch's row count

        history = {}
        if checkpoint_every is not None:
            checkpoint_every = int(checkpoint_every)
            if checkpoint_every < 1:
                raise MXNetError("checkpoint_every must be >= 1")
            if checkpoint_dir is None:
                raise MXNetError(
                    "checkpoint_every needs a checkpoint_dir to save into")
        if resume_meta is not None:
            start_epoch = int(resume_meta["epoch"])
            global_step = int(resume_meta.get("global_step", 0))
            skip_batches = int(resume_meta.get("batch_in_epoch", 0))
            rng_seed = int(resume_meta.get("seed", seed))
            rng_anchor = int(resume_meta.get("base_epoch", 0))
        else:
            start_epoch = begin_epoch
            global_step = 0
            skip_batches = 0
            rng_seed = seed
            # fold begin_epoch in so a manually-resumed run (state= +
            # begin_epoch=) continues a fresh key stream instead of
            # replaying the original run's dropout masks
            rng_anchor = begin_epoch
        end_epoch = begin_epoch + num_epoch
        # per-step keys are fold_in(anchor, global_step): because BOTH the
        # anchor and the step index persist across resume (via the meta
        # sidecar), a resumed run draws exactly the keys the uninterrupted
        # run would have
        base_key = _jax.random.fold_in(_jax.random.PRNGKey(rng_seed),
                                       rng_anchor)

        # stream-capable iterators (state()/load_state(): StreamDataIter)
        # carry their serialized cursor in the meta sidecar, so a
        # mid-epoch resume restores the EXACT read position (file,
        # offset, shuffle epoch) instead of replaying the epoch head;
        # epoch starts go through seek_epoch(epoch) so the shuffle
        # schedule is a pure function of the loop epoch on fresh and
        # resumed runs alike
        streamable = (hasattr(train_data, "state")
                      and hasattr(train_data, "load_state"))
        stream_state = [None]
        stream_loaded = False
        if streamable and resume_meta is not None:
            st = resume_meta.get("stream")
            if st is not None and int(st.get("epoch", -1)) == start_epoch:
                train_data.load_state(st)
                skip_batches = 0
                stream_loaded = True

        def fit_meta(epoch, batch_in_epoch):
            meta = {"global_step": global_step, "epoch": epoch,
                    "batch_in_epoch": batch_in_epoch, "seed": rng_seed,
                    "base_epoch": rng_anchor}
            if stream_state[0] is not None:
                meta["stream"] = stream_state[0]
            return meta

        # observability: handles resolved ONCE here; the loop pays one
        # method call per event (MXNET_TPU_METRICS=0 short-circuits it)
        _m_step = _obs.histogram(
            "trainer_step_seconds",
            "Optimizer-step wall time seen by the fit loop; pipelined "
            "flushes are amortized over their K fused steps")
        _m_steps = _obs.counter("trainer_steps_total",
                                "Optimizer steps applied by fit")
        _m_tokens = _obs.gauge(
            "trainer_tokens_per_sec",
            "Training throughput (batch rows per second) of the most "
            "recent step or flush")

        # goodput ledger: every wall second from here to the return is
        # accounted productive vs badput{cause} (observability.efficiency);
        # the snapshot must precede the first compile so warmup books as
        # recompile badput
        led = _eff.ledger()
        t_fit = _time.monotonic()

        guard = self._skip_nonfinite
        bad_streak = 0
        skipped_total = 0
        last_saved = None
        flushes = 0
        metric_every = int(metric_every)
        if metric_every < 1:
            raise MXNetError("metric_every must be >= 1")

        def after_step(epoch, arrays, data_names, ok, outs_host,
                       can_ckpt=True, att=None):
            """Per-step host bookkeeping shared by the per-step and
            pipelined paths: skip policy, metric, speedometer, callbacks,
            periodic checkpoint.  ``outs_host=None`` = this step's flush
            skipped its metric fetch (``metric_every``); ``can_ckpt`` is
            False for mid-flush steps — the in-hand (params, moms, aux)
            are END-of-flush state, valid to save only at the flush's
            last step (chunk planning puts every checkpoint boundary
            there)."""
            nonlocal bad_streak, skipped_total, speedo, last_saved
            if ok:
                bad_streak = 0
                if outs_host is not None:
                    labels = [v for n, v in arrays.items()
                              if n not in data_names]
                    metric.update([_np.asarray(v) for v in labels],
                                  outs_host)
            else:
                bad_streak += 1
                skipped_total += 1
                log.warning(
                    "non-finite loss/grad at global step %d — step "
                    "skipped, state unchanged (%d consecutive, %d "
                    "total)", global_step - 1, bad_streak,
                    skipped_total)
                if bad_streak >= max_bad_steps:
                    raise MXNetError(
                        "aborting fit: %d consecutive non-finite "
                        "steps (last at global step %d) — the run "
                        "has diverged or the input data is bad"
                        % (bad_streak, global_step - 1))
            if speedo is None and log_every:
                # windowed samples/s (metric=None so the epoch metric
                # is not reset mid-epoch by the logger)
                speedo = Speedometer(
                    next(iter(arrays.values())).shape[0],
                    frequent=log_every)
            bep = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                eval_metric=metric, locals=None)
            if speedo is not None:
                speedo(bep._replace(eval_metric=None))
            for cb in callbacks:
                cb(bep)
            if (can_ckpt and checkpoint_every
                    and global_step % checkpoint_every == 0):
                # timed as its own phase: the in-step save is badput in
                # the goodput ledger's books, not productive step time
                with (att.phase("checkpoint") if att is not None
                      else _contextlib.nullcontext()):
                    _ckpt.save_sharded(checkpoint_dir, global_step,
                                       params, moms, aux)
                    _ckpt.save_fit_meta(checkpoint_dir, global_step,
                                        fit_meta(epoch, nbatch))
                last_saved = global_step
                _attr.sample_memory()

        for epoch in range(start_epoch, end_epoch):
            metric.reset()
            if stream_loaded and epoch == start_epoch:
                pass  # cursor already at the bitwise mid-epoch position
            elif streamable and hasattr(train_data, "seek_epoch"):
                train_data.seek_epoch(epoch)
            else:
                train_data.reset()
            nbatch = 0
            if K == 1:
                it = iter(train_data)
                while True:
                    # attribution brackets the WHOLE step — including the
                    # iterator pull — so the phase sums plus the residual
                    # reconcile against trainer_step_seconds exactly
                    att = _attr.attributor()
                    t_step = _time.monotonic()
                    try:
                        with att.phase("data_wait"):
                            batch = next(it)
                    except StopIteration:
                        break
                    if skip_batches:
                        # resumed mid-epoch: replay the iterator up to the
                        # checkpointed batch offset without stepping (the
                        # attributor is dropped unclosed: records nothing)
                        skip_batches -= 1
                        nbatch += 1
                        continue
                    if streamable:
                        # the batch just pulled left the cursor exactly
                        # at its end — the watermark the next periodic
                        # checkpoint's meta will carry
                        stream_state[0] = train_data.state()
                    arrays, data_names = batch_arrays(batch, train_data)
                    with _obs.span("trainer.step", step=global_step):
                        with att.phase("placement"):
                            placed = self.place_batch(arrays)
                        with att.phase("compute"):
                            outs, params, moms, aux = step(
                                params, moms, aux, placed,
                                _jax.random.fold_in(base_key, global_step))
                            ok = True
                            if guard:
                                # trailing scalar = the step's in-graph
                                # verdict; the asnumpy read syncs, which
                                # the skip policy needs anyway
                                ok = bool(_np.asarray(outs[-1]))
                                outs = outs[:-1]
                    global_step += 1
                    nbatch += 1
                    flushes += 1
                    with att.phase("flush"):
                        outs_host = ([_np.asarray(o) for o in outs]
                                     if flushes % metric_every == 0
                                     else None)
                    after_step(epoch, arrays, data_names, ok, outs_host,
                               att=att)
                    dt = _time.monotonic() - t_step
                    led.step(dt, att.close(dt))
                    _m_step.observe(dt)
                    _m_steps.inc()
                    _eff.record_step_rate(1, dt)
                    if dt > 0:
                        _m_tokens.set(
                            next(iter(arrays.values())).shape[0] / dt)
            else:
                # -- pipelined path: K fused steps per dispatch over a
                # feeder-staged superbatch -------------------------------
                while skip_batches:
                    # resumed mid-epoch: replay BEFORE the feeder starts
                    # prefetching, so chunk 0 begins at the right batch
                    try:
                        next(train_data)
                    except StopIteration:
                        break
                    skip_batches -= 1
                    nbatch += 1
                # plan chunk sizes at push time so every flush END lands
                # on a checkpoint boundary (never crosses one mid-flush):
                # the feeder calls plan_size once per fetch, in push order
                planned = [global_step]

                def plan_size():
                    k = K
                    if checkpoint_every:
                        k = min(k, checkpoint_every
                                - planned[0] % checkpoint_every)
                    planned[0] += k
                    return k

                def extract(b):
                    # runs on the IO worker right after the iterator
                    # pull, so a stream-capable iterator's cursor is
                    # exactly at this batch's end: the snapshot rides
                    # with the batch and the checkpoint at a flush end
                    # gets the watermark of the last CONSUMED batch,
                    # immune to the feeder's read-ahead
                    arrays, data_names = batch_arrays(b, train_data)
                    return (arrays, data_names,
                            train_data.state() if streamable else None)

                with _obs.span("trainer.prefetch_start"):
                    # fetch ops pushed by the constructor inherit this
                    # span as their cross-thread parent
                    feeder = _prefetch.PrefetchFeeder(
                        iter(train_data), extract=extract,
                        place=lambda host: self.place_superbatch(
                            [h[0] for h in host]),
                        sizes=plan_size, depth=2, name="fit.prefetch")
                try:
                    while True:
                        # per-FLUSH attribution (feeder-side placement is
                        # accounted by prefetch_place_seconds_total — here
                        # data_wait is the stall waiting on the feeder)
                        att = _attr.attributor()
                        t_flush = _time.monotonic()
                        with _obs.span("trainer.flush", flush=flushes):
                            with att.phase("data_wait"):
                                chunk = feeder.next_chunk()
                            if chunk is None:
                                break
                            n = chunk.count
                            with att.phase("compute"):
                                outs_stack, params, moms, aux = \
                                    self.pipeline_fn(n)(
                                        params, moms, aux, chunk.placed,
                                        base_key, _np.int32(global_step))
                        flushes += 1
                        verdicts = None
                        with att.phase("flush"):
                            if guard:
                                # one [n] readback per flush drives the
                                # skip policy for all n steps
                                verdicts = _np.asarray(outs_stack[-1])
                                outs_stack = outs_stack[:-1]
                            outs_host = None
                            if flushes % metric_every == 0:
                                outs_host = [_np.asarray(o)
                                             for o in outs_stack]
                        for j in range(n):
                            arrays, data_names = chunk.host[j][:2]
                            if streamable:
                                stream_state[0] = chunk.host[j][2]
                            ok = (True if verdicts is None
                                  else bool(verdicts[j]))
                            global_step += 1
                            nbatch += 1
                            after_step(
                                epoch, arrays, data_names, ok,
                                None if outs_host is None
                                else [o[j] for o in outs_host],
                                can_ckpt=(j == n - 1), att=att)
                        dt = _time.monotonic() - t_flush
                        led.step(dt, att.close(dt))
                        _m_steps.inc(n)
                        for _ in range(n):  # amortized per-step latency
                            _m_step.observe(dt / n)
                        _eff.record_step_rate(n, dt)
                        if dt > 0:
                            rows = next(iter(
                                chunk.host[0][0].values())).shape[0]
                            _m_tokens.set(rows * n / dt)
                        # flush end = a stable live set (no mid-dispatch
                        # churn): the meaningful HBM watermark point
                        _attr.sample_memory()
                finally:
                    feeder.close()
            history.setdefault(epoch, {})["train"] = metric.get()
            log.info("epoch %d train: %s", epoch, history[epoch]["train"])

            if eval_data is not None:
                metric.reset()
                eval_data.reset()
                for batch in eval_data:
                    arrays, data_names = batch_arrays(batch, eval_data)
                    placed = self.place_batch(arrays, train=False)
                    outs = fwd(params, aux, placed,
                               _jax.random.PRNGKey(0))
                    labels = [v for n, v in arrays.items()
                              if n not in data_names]
                    metric.update([_np.asarray(v) for v in labels],
                                  [_np.asarray(o) for o in outs])
                history[epoch]["eval"] = metric.get()
                log.info("epoch %d eval: %s", epoch, history[epoch]["eval"])

            if checkpoint_dir is not None:
                t_ck = _time.monotonic()
                if checkpoint_every:
                    # global-step numbering throughout (the historical
                    # epoch+1 numbering would collide with step numbers)
                    if last_saved != global_step:
                        _ckpt.save_sharded(checkpoint_dir, global_step,
                                           params, moms, aux)
                        last_saved = global_step
                    # (re)write the meta to point at the NEXT epoch's first
                    # batch — a periodic save at the epoch's last batch
                    # would otherwise resume into a fully-skipped epoch
                    _ckpt.save_fit_meta(checkpoint_dir, global_step,
                                        fit_meta(epoch + 1, 0))
                else:
                    _ckpt.save_sharded(checkpoint_dir, epoch + 1, params,
                                       moms, aux)
                    _ckpt.save_fit_meta(checkpoint_dir, epoch + 1,
                                        fit_meta(epoch + 1, 0))
                _attr.sample_memory()
                # out-of-step badput: the epoch-end save happens outside
                # any step window
                led.bad("checkpoint", _time.monotonic() - t_ck)
        led.close(_time.monotonic() - t_fit)
        return (params, moms, aux), history

    def _fit_kvstore(self, kv, train_data, eval_data=None, num_epoch=1,
                     seed=0, eval_metric="accuracy", initializer=None,
                     state=None, begin_epoch=0, checkpoint_dir=None,
                     checkpoint_every=None, resume=None, log_every=50,
                     logger=None, batch_end_callback=None, roster=None):
        """Parameter-server-backed loop behind ``fit(kvstore=)``: local
        gradients (``grad_fn``) pushed to the kvstore, whose server-side
        optimizer owns weights and state; fresh weights pulled back each
        step.  Requires the caller to have called ``kv.set_optimizer``.

        With ``roster=`` the batch loop becomes elastic: each global
        batch index runs on the rank ``roster.owns`` says, membership
        re-read per batch so a join/drain re-balances mid-epoch, and
        ``mark_progress``/``resume_point`` give a joining rank the
        iterator fast-forward (see :meth:`fit`)."""
        import logging

        import jax as _jax

        from .. import metric as _metric_mod
        from ..callback import Speedometer
        from ..io import batch_arrays as _io_batch_arrays
        from ..model import BatchEndParam
        from ..ndarray import NDArray

        if self.pipeline_steps != 1 or self.grad_accum != 1:
            raise MXNetError(
                "kvstore-backed fit pushes one gradient per step: "
                "pipeline_steps and grad_accum must both be 1 (the server "
                "applies updates per arriving push)")
        if self._skip_nonfinite:
            raise MXNetError(
                "skip_nonfinite guards the fused LOCAL update; with "
                "kvstore= the optimizer runs server-side where the verdict "
                "cannot gate it — not supported")
        if checkpoint_dir is not None or checkpoint_every or resume:
            raise MXNetError(
                "kvstore-backed fit: weights and optimizer state live on "
                "the parameter server (replicated shards are the "
                "durability story) — checkpoint_dir/checkpoint_every/"
                "resume are not supported here")

        log = logger or logging.getLogger(__name__)
        metric = (eval_metric
                  if isinstance(eval_metric, _metric_mod.EvalMetric)
                  else _metric_mod.create(eval_metric))
        params, moms, aux = (state if state is not None
                             else self.init(initializer=initializer,
                                            seed=seed))
        diff = [n for n in self.param_names if n in self._diff_set]
        # seed the server: rank-0-wins first-writer semantics, so every
        # worker calling this converges on one initial state
        kv.init(diff, [NDArray(jnp.asarray(params[n])) for n in diff])
        # pull buffers reused across steps (pull writes them in place)
        bufs = [NDArray(jnp.asarray(params[n])) for n in diff]
        kv.pull(diff, out=bufs)
        pshard = {n: self._sharding(self.param_specs[n]) for n in diff}
        for n, b in zip(diff, bufs):
            params[n] = jax.device_put(
                jnp.asarray(b._data).astype(self._param_dtype(n)),
                pshard[n])
        _mem.tag_tree("params", id(self), (params, aux))
        _mem.tag_tree("optimizer", id(self), moms)
        gradf = self.grad_fn()
        fwd = self.forward_fn()

        def batch_arrays(batch, it):
            return _io_batch_arrays(batch, it, self._input_names)

        callbacks = (list(batch_end_callback)
                     if isinstance(batch_end_callback, (list, tuple))
                     else [batch_end_callback] if batch_end_callback
                     else [])
        speedo = None
        history = {}
        global_step = 0
        base_key = _jax.random.fold_in(_jax.random.PRNGKey(seed),
                                       begin_epoch)
        end_epoch = begin_epoch + num_epoch
        # same step-latency families the local paths feed — one dashboard
        # regardless of where the optimizer runs; the kv phase (absent
        # from the local paths) is where this loop earns its breakdown
        _m_step = _metrics.histogram(
            "trainer_step_seconds",
            "Optimizer-step wall time seen by the fit loop; pipelined "
            "flushes are amortized over their K fused steps")
        _m_steps = _metrics.counter("trainer_steps_total",
                                    "Optimizer steps applied by fit")
        _m_tokens = _metrics.gauge(
            "trainer_tokens_per_sec",
            "Training throughput (batch rows per second) of the most "
            "recent step or flush")
        # goodput ledger: RPC retry/backoff and failover seconds booked by
        # the kvstore client surface here as badput counter deltas
        led = _eff.ledger()
        t_fit = _time.monotonic()
        my_rank = getattr(kv, "rank", 0)
        for epoch in range(begin_epoch, end_epoch):
            metric.reset()
            train_data.reset()
            nbatch = 0
            bidx = -1
            for batch in train_data:
                bidx += 1
                if roster is not None:
                    if (epoch, bidx) < roster.resume_point():
                        # the group already covered this batch before we
                        # joined — fast-forward, never re-apply it
                        continue
                    if not roster.owns(my_rank, bidx):
                        roster.mark_progress(epoch, bidx + 1)
                        continue
                att = _attr.attributor()
                t_step = _time.monotonic()
                arrays, data_names = batch_arrays(batch, train_data)
                with att.phase("placement"):
                    placed = self.place_batch(arrays)
                with att.phase("compute"):
                    outs, grads, aux = gradf(
                        params, aux, placed,
                        _jax.random.fold_in(base_key, global_step))
                with att.phase("kv"):
                    # the push may ride out a shard failover internally
                    # (promote + same-seq retry); only whole-group loss
                    # escapes, as ShardFailedError.  push_pull fuses the
                    # step's two flushes into one RPC per shard on
                    # dist_async (falling back to push();pull() on every
                    # other mode or with coalescing off)
                    if hasattr(kv, "push_pull"):
                        kv.push_pull(diff,
                                     [NDArray(grads[n]) for n in diff],
                                     out=bufs)
                    else:
                        kv.push(diff, [NDArray(grads[n]) for n in diff])
                        kv.pull(diff, out=bufs)
                with att.phase("placement"):
                    # accumulates onto the batch placement above: both
                    # are host->device transfers on the step's critical
                    # path
                    for n, b in zip(diff, bufs):
                        params[n] = jax.device_put(
                            jnp.asarray(b._data).astype(
                                self._param_dtype(n)),
                            pshard[n])
                global_step += 1
                nbatch += 1
                if roster is not None:
                    roster.mark_progress(epoch, bidx + 1)
                with att.phase("flush"):
                    labels = [v for n, v in arrays.items()
                              if n not in data_names]
                    metric.update([_np.asarray(v) for v in labels],
                                  [_np.asarray(o) for o in outs])
                dt = _time.monotonic() - t_step
                led.step(dt, att.close(dt))
                _m_step.observe(dt)
                _m_steps.inc()
                _eff.record_step_rate(1, dt)
                if dt > 0:
                    _m_tokens.set(
                        next(iter(arrays.values())).shape[0] / dt)
                if speedo is None and log_every:
                    speedo = Speedometer(
                        next(iter(arrays.values())).shape[0],
                        frequent=log_every)
                bep = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                    eval_metric=metric, locals=None)
                if speedo is not None:
                    speedo(bep._replace(eval_metric=None))
                for cb in callbacks:
                    cb(bep)
            history.setdefault(epoch, {})["train"] = metric.get()
            log.info("epoch %d train: %s", epoch, history[epoch]["train"])
            if eval_data is not None:
                metric.reset()
                eval_data.reset()
                for batch in eval_data:
                    arrays, data_names = batch_arrays(batch, eval_data)
                    placed = self.place_batch(arrays, train=False)
                    outs = fwd(params, aux, placed, _jax.random.PRNGKey(0))
                    labels = [v for n, v in arrays.items()
                              if n not in data_names]
                    metric.update([_np.asarray(v) for v in labels],
                                  [_np.asarray(o) for o in outs])
                history[epoch]["eval"] = metric.get()
                log.info("epoch %d eval: %s", epoch,
                         history[epoch]["eval"])
        led.close(_time.monotonic() - t_fit)
        return (params, moms, aux), history

    def fit_stream(self, train_data, seed=0, max_steps=None,
                   checkpoint_dir=None, checkpoint_every=100,
                   checkpoint_every_s=None, resume=None,
                   initializer=None, state=None, max_bad_steps=5,
                   retries=None, backoff_s=None, stall_timeout=None,
                   skip_on_error=False, log_every=0, logger=None,
                   batch_end_callback=None):
        """Online learning: consume an UNBOUNDED iterator (e.g. a
        ``loop=True`` :class:`~mxnet_tpu.stream.StreamDataIter`),
        checkpointing every ``checkpoint_every`` steps and/or every
        ``checkpoint_every_s`` seconds — the producer side of the
        continuous-training loop (``deployd`` is the consumer).

        There are no epochs: the loop runs until ``max_steps``
        optimizer steps land (``None`` = forever), pulling
        feeder-staged chunks whose decode runs on the engine IO lane.
        Every checkpoint's meta sidecar carries the stream iterator's
        serialized cursor, so ``resume="auto"`` continues **bitwise**
        from the last saved step: same records, same shuffle order,
        same per-step RNG keys.

        Failure contract (never a silent hang):

        - a stalled source surfaces as a typed
          :class:`~mxnet_tpu.base.StreamStallError` after
          ``stall_timeout`` seconds (default
          ``MXNET_TPU_PREFETCH_STALL_S``), is retried with exponential
          backoff up to ``retries`` times (default
          ``MXNET_TPU_STREAM_RETRIES``, backoff base
          ``MXNET_TPU_STREAM_BACKOFF_S``), each stall counted in
          ``stream_stalls_total`` — the watchdog's ``stream_stall``
          rule fires on a sustained run of them — and the final miss
          re-raises;
        - a truncated/garbled source surfaces as
          ``CorruptMessageError``; with ``skip_on_error=True`` the bad
          chunk is counted (``stream_skipped_total``) and skipped
          (feeder reset, stream keeps moving), bounded by
          ``max_bad_steps`` consecutive losses;
        - a cleanly-ending finite iterator just ends the loop.

        Returns ``((params, moms, aux), info)`` where ``info`` has
        ``steps``/``global_step``/``stalls``/``skipped``/
        ``last_checkpoint``.  A terminal escape is flight-recorded
        (``trainer.fit_stream``)."""
        try:
            return self._fit_stream_impl(
                train_data, seed=seed, max_steps=max_steps,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                checkpoint_every_s=checkpoint_every_s, resume=resume,
                initializer=initializer, state=state,
                max_bad_steps=max_bad_steps, retries=retries,
                backoff_s=backoff_s, stall_timeout=stall_timeout,
                skip_on_error=skip_on_error, log_every=log_every,
                logger=logger, batch_end_callback=batch_end_callback)
        except Exception as exc:
            from ..observability import flight_recorder as _flight

            _flight.record_failure("trainer.fit_stream", exc)
            raise

    def _fit_stream_impl(self, train_data, seed=0, max_steps=None,
                         checkpoint_dir=None, checkpoint_every=100,
                         checkpoint_every_s=None, resume=None,
                         initializer=None, state=None, max_bad_steps=5,
                         retries=None, backoff_s=None, stall_timeout=None,
                         skip_on_error=False, log_every=0, logger=None,
                         batch_end_callback=None):
        import logging
        import os as _os

        import jax as _jax

        from .. import observability as _obs
        from ..base import CorruptMessageError, StreamStallError
        from ..io import batch_arrays as _io_batch_arrays
        from ..model import BatchEndParam
        from . import checkpoint as _ckpt
        from . import prefetch as _prefetch

        log = logger or logging.getLogger(__name__)
        if checkpoint_every is not None:
            checkpoint_every = int(checkpoint_every)
            if checkpoint_every < 1:
                raise MXNetError("checkpoint_every must be >= 1")
        if checkpoint_dir is None:
            # no directory = no checkpointing (checkpoint_every keeps
            # its default so callers opting IN only pass the dir)
            checkpoint_every = None
            checkpoint_every_s = None
        if retries is None:
            try:
                retries = int(_os.environ.get(
                    "MXNET_TPU_STREAM_RETRIES", "5") or 5)
            except ValueError:
                retries = 5
        if backoff_s is None:
            try:
                backoff_s = float(_os.environ.get(
                    "MXNET_TPU_STREAM_BACKOFF_S", "0.05") or 0.05)
            except ValueError:
                backoff_s = 0.05

        # -- resume="auto": the fit ladder, stream cursor included -------
        resume_meta = None
        if resume not in (None, False, "auto"):
            raise MXNetError("resume must be None or 'auto', got %r"
                             % (resume,))
        if resume == "auto" and checkpoint_dir is not None:
            from .. import durable as _durable
            from ..base import CheckpointCorruptError as _CkptCorrupt

            _state_in = state  # restored on every fallback hop
            for ckpt_step in reversed(_ckpt.all_steps(checkpoint_dir)):
                try:
                    verified = _ckpt.verify_checkpoint(checkpoint_dir,
                                                       ckpt_step)
                    state = _ckpt.restore_sharded(checkpoint_dir,
                                                  ckpt_step, trainer=self)
                    resume_meta = _ckpt.load_fit_meta(checkpoint_dir,
                                                      ckpt_step)
                except _CkptCorrupt as exc:
                    state = _state_in
                    _durable.quarantine(
                        "checkpoint", exc, step=int(ckpt_step),
                        directory=str(checkpoint_dir),
                        file=getattr(exc, "file", None))
                    log.warning(
                        "resume: checkpoint step %d failed integrity "
                        "verification (%s); falling back to the previous "
                        "checkpoint", ckpt_step, exc)
                    continue
                except Exception as exc:  # noqa: BLE001 — fall back a step
                    log.warning(
                        "resume: checkpoint step %d failed validation "
                        "(%r); falling back to the previous checkpoint",
                        ckpt_step, exc)
                    continue
                if resume_meta is None and verified:
                    # manifest-era step with no sidecar: the save was
                    # killed between shard and meta writes — fall back
                    state = _state_in
                    log.warning(
                        "resume: checkpoint step %d has a manifest but no "
                        "fit-meta sidecar (save killed mid-write); falling "
                        "back to the previous checkpoint", ckpt_step)
                    continue
                log.info("resume: restored checkpoint step %d", ckpt_step)
                break
            else:
                log.info("resume: no restorable checkpoint under %r — "
                         "starting fresh", checkpoint_dir)

        params, moms, aux = (state if state is not None
                             else self.init(initializer=initializer,
                                            seed=seed))
        _mem.tag_tree("params", id(self), (params, aux))
        _mem.tag_tree("optimizer", id(self), moms)
        if resume_meta is not None:
            global_step = int(resume_meta.get("global_step", 0))
            rng_seed = int(resume_meta.get("seed", seed))
            rng_anchor = int(resume_meta.get("base_epoch", 0))
        else:
            global_step = 0
            rng_seed = seed
            rng_anchor = 0
        base_key = _jax.random.fold_in(_jax.random.PRNGKey(rng_seed),
                                       rng_anchor)
        streamable = (hasattr(train_data, "state")
                      and hasattr(train_data, "load_state"))
        if (streamable and resume_meta is not None
                and resume_meta.get("stream") is not None):
            train_data.load_state(resume_meta["stream"])
        stream_state = [train_data.state() if streamable else None]

        def fit_meta():
            meta = {"global_step": global_step,
                    "epoch": (stream_state[0] or {}).get("epoch", 0),
                    "batch_in_epoch": 0, "seed": rng_seed,
                    "base_epoch": rng_anchor, "mode": "stream"}
            if stream_state[0] is not None:
                meta["stream"] = stream_state[0]
            return meta

        K = self.pipeline_steps
        stop_at = None if max_steps is None else global_step + int(max_steps)
        planned = [global_step]

        def plan_size():
            # every flush END lands on a checkpoint boundary and never
            # overshoots the stop step (extra read-ahead is harmless:
            # the watermark advances only with consumed batches)
            k = K
            if checkpoint_every:
                k = min(k, checkpoint_every - planned[0] % checkpoint_every)
            if stop_at is not None:
                k = max(min(k, stop_at - planned[0]), 1)
            planned[0] += k
            return k

        def extract(b):
            arrays, names = _io_batch_arrays(b, train_data,
                                             self._input_names)
            return (arrays, names,
                    train_data.state() if streamable else None)

        callbacks = (list(batch_end_callback)
                     if isinstance(batch_end_callback, (list, tuple))
                     else [batch_end_callback] if batch_end_callback
                     else [])
        _m_step = _obs.histogram(
            "trainer_step_seconds",
            "Optimizer-step wall time seen by the fit loop; pipelined "
            "flushes are amortized over their K fused steps")
        _m_steps = _obs.counter("trainer_steps_total",
                                "Optimizer steps applied by fit")
        led = _eff.ledger()
        t_fit = _time.monotonic()
        guard = self._skip_nonfinite
        steps_done = stalls = skipped = 0
        bad_streak = corrupt_streak = 0
        last_saved = None
        last_save_t = _time.monotonic()

        with _obs.span("trainer.stream_prefetch_start"):
            feeder = _prefetch.PrefetchFeeder(
                iter(train_data), extract=extract,
                place=lambda host: self.place_superbatch(
                    [h[0] for h in host]),
                sizes=plan_size, depth=2, name="fit_stream.prefetch")
        try:
            while stop_at is None or global_step < stop_at:
                att = _attr.attributor()
                t_flush = _time.monotonic()
                attempt = 0
                while True:
                    try:
                        with att.phase("data_wait"):
                            chunk = feeder.next_chunk(
                                timeout=stall_timeout)
                        corrupt_streak = 0
                        break
                    except StreamStallError:
                        stalls += 1
                        _M_STREAM_STALLS.inc()
                        attempt += 1
                        if attempt > retries:
                            raise StreamStallError(
                                "stream source stalled: %d consecutive "
                                "next_chunk timeouts at global step %d "
                                "(retries=%d exhausted)"
                                % (attempt, global_step, retries))
                        delay = min(backoff_s * (2 ** (attempt - 1)), 5.0)
                        log.warning(
                            "stream stall at global step %d (attempt "
                            "%d/%d) — backing off %.3fs", global_step,
                            attempt, retries, delay)
                        led.bad("data_wait", delay)
                        _time.sleep(delay)
                    except CorruptMessageError:
                        if not skip_on_error:
                            raise
                        skipped += 1
                        corrupt_streak += 1
                        _M_STREAM_SKIPPED.inc()
                        if corrupt_streak > max_bad_steps:
                            raise
                        log.warning(
                            "corrupt stream chunk at global step %d — "
                            "skipped and counted (%d consecutive)",
                            global_step, corrupt_streak)
                        feeder.reset()
                if chunk is None:
                    break  # finite source ended cleanly
                n = chunk.count
                with _obs.span("trainer.stream_flush", step=global_step):
                    with att.phase("compute"):
                        outs_stack, params, moms, aux = \
                            self.pipeline_fn(n)(
                                params, moms, aux, chunk.placed,
                                base_key, _np.int32(global_step))
                verdicts = None
                with att.phase("flush"):
                    if guard:
                        verdicts = _np.asarray(outs_stack[-1])
                        outs_stack = outs_stack[:-1]
                for j in range(n):
                    if streamable:
                        stream_state[0] = chunk.host[j][2]
                    ok = True if verdicts is None else bool(verdicts[j])
                    global_step += 1
                    steps_done += 1
                    if ok:
                        bad_streak = 0
                    else:
                        bad_streak += 1
                        if bad_streak >= max_bad_steps:
                            raise MXNetError(
                                "aborting fit_stream: %d consecutive "
                                "non-finite steps (last at global step "
                                "%d)" % (bad_streak, global_step - 1))
                    for cb in callbacks:
                        cb(BatchEndParam(epoch=0, nbatch=global_step,
                                         eval_metric=None, locals=None))
                    due_n = (checkpoint_every
                             and global_step % checkpoint_every == 0)
                    due_t = (checkpoint_every_s is not None
                             and _time.monotonic() - last_save_t
                             >= checkpoint_every_s)
                    if (j == n - 1 and checkpoint_dir is not None
                            and (due_n or due_t)):
                        with att.phase("checkpoint"):
                            _ckpt.save_sharded(checkpoint_dir,
                                               global_step, params,
                                               moms, aux)
                            _ckpt.save_fit_meta(checkpoint_dir,
                                                global_step, fit_meta())
                        last_saved = global_step
                        last_save_t = _time.monotonic()
                        _attr.sample_memory()
                dt = _time.monotonic() - t_flush
                led.step(dt, att.close(dt))
                _m_steps.inc(n)
                for _ in range(n):
                    _m_step.observe(dt / n)
                _eff.record_step_rate(n, dt)
                if log_every and steps_done % max(int(log_every), 1) == 0:
                    log.info("fit_stream: %d steps (global %d), "
                             "%d stalls, %d skipped", steps_done,
                             global_step, stalls, skipped)
        finally:
            feeder.close()
        if checkpoint_dir is not None and last_saved != global_step:
            # the exit checkpoint: deployd's next scan sees the final
            # state even when the loop stopped off the periodic boundary
            _ckpt.save_sharded(checkpoint_dir, global_step, params,
                               moms, aux)
            _ckpt.save_fit_meta(checkpoint_dir, global_step, fit_meta())
            last_saved = global_step
        led.close(_time.monotonic() - t_fit)
        return (params, moms, aux), {
            "steps": steps_done, "global_step": global_step,
            "stalls": stalls, "skipped": skipped,
            "last_checkpoint": last_saved}

    def _with_mesh(self, jitted):
        """Call `jitted` with this trainer's mesh ambient, so mesh-aware ops
        trace against the right mesh no matter which trainer traced last."""
        from . import default_mesh

        def call(*args, **kwargs):
            with default_mesh(self.mesh):
                return jitted(*args, **kwargs)

        return call


class _HostArray:
    """Minimal NDArray stand-in so initializer patterns run on numpy buffers."""

    def __init__(self, arr):
        self._arr = arr

    @property
    def shape(self):
        return self._arr.shape

    def __setitem__(self, key, value):
        self._arr[key] = value

    def asnumpy(self):
        return self._arr
