"""Pipeline parallelism — GPipe-style microbatched stage pipeline over a
``pipe`` mesh axis.

Capability-gap item (SURVEY.md §2.4 "NOT present": true pipeline
parallelism; the reference only gets op-level dataflow overlap from its
async engine).  TPU-first design: the canonical shard_map + ``ppermute``
rotation schedule — each device owns one stage's weights (stacked pytree,
leading stage axis sharded over ``pipe``), activations rotate along the ICI
ring each tick, and the whole schedule is one jitted computation.
Differentiating through it gives the reverse (backward) pipeline
automatically: the transpose of ``ppermute`` is the reverse rotation, so
grads flow stage-to-stage without hand-written scheduling.
"""

from __future__ import annotations

import functools
import inspect as _inspect

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["pipeline_apply", "pipeline_train_1f1b", "stack_stage_params",
           "PipelinedTrainer"]


def stack_stage_params(stage_params_list):
    """Stack per-stage pytrees into one pytree with a leading stage axis
    (to be sharded over ``pipe``)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *stage_params_list)


def pipeline_apply(stage_fn, stacked_params, x, *, mesh: Mesh,
                   n_microbatch: int, axis: str = "pipe"):
    """Run ``x`` through S pipelined stages of ``stage_fn``.

    stage_fn(params_i, x_mb) -> y_mb, applied S times in sequence, where
    ``stacked_params`` has leading axis S == mesh.shape[axis].  ``x`` is the
    global batch (B, ...); it is split into ``n_microbatch`` microbatches
    which flow through the stage ring GPipe-style: total ticks =
    n_microbatch + S - 1, with activations rotated one hop per tick.

    Returns the full output batch (B, ...), replicated across ``axis``
    (shard it downstream as needed).  All stages must preserve the
    microbatch shape (homogeneous-block pipelines — transformer stacks).
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_microbatch == 0, "batch must divide into microbatches"
    mb = B // n_microbatch

    def per_device(params, xs):
        # params: (1, ...) this device's stage slice; xs: full batch
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        stage_idx = lax.axis_index(axis)
        xs = xs.reshape(n_microbatch, mb, *xs.shape[1:])
        n_ticks = n_microbatch + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            cur_in, acc = carry
            # stage 0 ingests microbatch t (garbage after the last one —
            # masked out of the output accumulation below)
            feed = xs[jnp.minimum(t, n_microbatch - 1)]
            cur_in = jnp.where(stage_idx == 0, feed, cur_in)
            y = _stage_call(stage_fn, params, cur_in, stage_idx)
            # last stage banks its finished microbatch t-(S-1)
            done = (stage_idx == S - 1) & (t >= S - 1)
            slot = jnp.clip(t - (S - 1), 0, n_microbatch - 1)
            acc = lax.cond(
                done, lambda a: a.at[slot].set(y), lambda a: a, acc)
            nxt = lax.ppermute(y, axis, perm)
            return (nxt, acc), None

        init = (jnp.zeros((mb,) + xs.shape[2:], x.dtype),
                jnp.zeros((n_microbatch, mb) + xs.shape[2:], x.dtype))
        (_, acc), _ = lax.scan(tick, init, jnp.arange(n_ticks))
        # broadcast the last stage's accumulated outputs to every device
        acc = lax.psum(jnp.where(stage_idx == S - 1, acc, 0.0), axis)
        return acc.reshape(B, *x.shape[1:])

    pspec = _stage_pspec(stacked_params, axis)
    in_specs = (pspec, P())
    # other mesh axes (e.g. data) stay unmapped: this helper owns only pipe
    return shard_map(
        per_device, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False)(stacked_params, x)


def _takes_stage_idx(stage_fn):
    """True iff stage_fn's third POSITIONAL, NO-DEFAULT parameter exists —
    the opt-in signature ``stage_fn(params, x, stage_idx)``.  Parameters
    with defaults / keyword-only / *args do NOT opt in (a traced int
    landing in e.g. ``train=True`` would silently change behavior)."""
    try:
        sig = _inspect.signature(stage_fn)
    except (TypeError, ValueError):
        return False
    positional = [p for p in sig.parameters.values()
                  if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    return len(positional) >= 3 and positional[2].default is _inspect.Parameter.empty


def _stage_call(stage_fn, params, x, stage_idx):
    """Invoke stage_fn, passing stage_idx iff its signature opts in —
    heterogeneous pipelines condition behavior on the stage index (the
    SPMD-compatible form of non-homogeneous stages: one program, uniform
    param container, per-stage routing inside)."""
    if _takes_stage_idx(stage_fn):
        return stage_fn(params, x, stage_idx)
    return stage_fn(params, x)


def _stage_pspec(stacked_params, axis):
    """PartitionSpec tree sharding the leading stage axis over ``axis``."""
    return jax.tree_util.tree_map(
        lambda _: P(axis), stacked_params,
        is_leaf=lambda l: isinstance(l, jnp.ndarray))


def pipeline_train_1f1b(stage_fn, loss_fn, stacked_params, x, target, *,
                        mesh: Mesh, n_microbatch: int, axis: str = "pipe",
                        batch_axis=None, param_axes=None, reduce_axes=()):
    """One training step with the **1F1B schedule** (PipeDream-flush):
    returns ``(mean_loss, grads)`` where grads matches ``stacked_params``.

    Differences vs differentiating :func:`pipeline_apply` (GPipe):

    * **Bounded activation memory.**  Stage ``s`` holds at most
      ``2*(S-s)-1`` live microbatch inputs (≤ 2S), independent of the
      microbatch count M — GPipe's scan residuals grow with M.  Backward
      recomputes the stage forward from the saved INPUT (the standard TPU
      remat tradeoff: ~1 extra stage-forward per microbatch).
    * **Explicit schedule.**  Tick ``t``: stage ``s`` forwards microbatch
      ``t - s`` and backwards microbatch ``t - (2S-1-s)`` (each when in
      range), so steady state interleaves one-forward-one-backward.
      Total ticks = M + 2S - 1.
    * **Heterogeneous stages** via an optional third ``stage_idx`` arg to
      ``stage_fn`` (embedding/head behavior per stage); activations must
      keep one shape (ring rotation), parameters one stacked container —
      the SPMD form of non-homogeneity.

    ``loss_fn(y_mb, target_mb) -> scalar`` is applied at the last stage;
    its mean over microbatches is returned.

    **Composed meshes** (dp x tp x pp in ONE mesh): pass ``batch_axis``
    to shard ``x``/``target`` along a data axis (loss and grads are
    ``pmean``-reduced over it — the kvstore all-reduce as an XLA
    collective); ``param_axes`` to override the per-leaf PartitionSpecs
    of ``stacked_params`` (leading dim must stay the pipe axis; other
    dims may shard Megatron-style over a model axis); and
    ``reduce_axes`` naming the model axes whose contraction the stage
    shards.  Contract: with ``reduce_axes``, ``stage_fn`` returns
    PARTIAL sums (no internal psum) and the pipeline reduces the stage
    output on both passes — this keeps the manual per-stage vjp exact
    (replicated cotangents seed each partial directly; ``dx`` is
    psum-reduced because the replicated input feeds every shard).
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    dp = mesh.shape[batch_axis] if batch_axis is not None else 1
    assert B % (n_microbatch * dp) == 0, \
        "batch must divide into data shards x microbatches"
    M = n_microbatch
    mb = B // dp // M  # microbatch size of the LOCAL data shard
    n_ticks = M + 2 * S - 1
    window = 2 * S  # ring slots for saved inputs; live span < window

    def per_device(params, xs, tgt):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        s_idx = lax.axis_index(axis)
        xs = xs.reshape(M, mb, *xs.shape[1:])
        tgt = tgt.reshape(M, mb, *tgt.shape[1:])
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [((i + 1) % S, i) for i in range(S)]
        last = s_idx == S - 1

        def tick(carry, t):
            act_in, grad_in, saved, gacc, loss_acc = carry

            # ---------- forward lane: microbatch t - s ----------
            m_f = t - s_idx
            fwd_valid = (m_f >= 0) & (m_f < M)
            m_f = jnp.clip(m_f, 0, M - 1)
            x_in = jnp.where(s_idx == 0, xs[m_f], act_in)
            y = _stage_call(stage_fn, params, x_in, s_idx)
            if reduce_axes:
                # model-parallel stages emit PARTIAL sums; the pipeline
                # owns the reduction (keeping stage_fn free of psum makes
                # the manual vjp below exact: replicated cotangents seed
                # each partial directly, no transpose inflation)
                y = lax.psum(y, reduce_axes)
            slot_f = m_f % window
            saved = saved.at[slot_f].set(
                jnp.where(fwd_valid, x_in, saved[slot_f]))

            # ---------- backward lane: microbatch t - (2S-1-s) --------
            m_b = t - (2 * S - 1 - s_idx)
            bwd_valid = (m_b >= 0) & (m_b < M)
            m_b = jnp.clip(m_b, 0, M - 1)
            x_saved = saved[m_b % window]
            # recompute the stage forward from the saved input; the last
            # stage seeds the chain with the loss gradient of its output
            y_re, vjp = jax.vjp(
                lambda p, xi: _stage_call(stage_fn, p, xi, s_idx),
                params, x_saved)
            if reduce_axes:
                y_re = lax.psum(y_re, reduce_axes)
            mb_loss, g_seed = jax.value_and_grad(
                lambda yy: loss_fn(yy, tgt[m_b]))(y_re)
            g_eff = jnp.where(last, g_seed, grad_in)
            dparams, dx = vjp(g_eff)
            if reduce_axes:
                # x is replicated across the model axis and consumed by
                # every shard, so its cotangent is the sum of per-shard
                # contributions
                dx = lax.psum(dx, reduce_axes)
            # where (not multiply): warm-up/cool-down recomputes run on
            # garbage inputs whose grads may be NaN, and 0*NaN = NaN
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(bwd_valid, g,
                                           jnp.zeros_like(g)), gacc, dparams)
            loss_acc = loss_acc + jnp.where(bwd_valid & last, mb_loss, 0.0)

            # ---------- ring rotations ----------
            act_out = lax.ppermute(y, axis, fwd_perm)
            grad_out = lax.ppermute(dx, axis, bwd_perm)
            return (act_out, grad_out, saved, gacc, loss_acc), None

        zeros_mb = jnp.zeros((mb,) + xs.shape[2:], x.dtype)
        init = (zeros_mb, zeros_mb,
                jnp.zeros((window, mb) + xs.shape[2:], x.dtype),
                jax.tree_util.tree_map(jnp.zeros_like, params),
                jnp.zeros((), jnp.float32))
        (_, _, _, gacc, loss_acc), _ = lax.scan(
            tick, init, jnp.arange(n_ticks))
        loss = lax.psum(loss_acc, axis) / M
        # grads of mean-over-microbatches loss: accumulated per-mb grads / M
        gacc = jax.tree_util.tree_map(lambda g: g / M, gacc)
        if batch_axis is not None:
            # data-parallel reduction: global loss is the mean over data
            # shards, so its param grads are the pmean of shard grads
            loss = lax.pmean(loss, batch_axis)
            gacc = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, batch_axis), gacc)
        # re-add the stage axis so out_specs' pipe axis rebuilds the stack
        return loss, jax.tree_util.tree_map(lambda g: g[None], gacc)

    pspec = param_axes if param_axes is not None \
        else _stage_pspec(stacked_params, axis)
    dspec = P(batch_axis) if batch_axis is not None else P()
    return shard_map(
        per_device, mesh=mesh, in_specs=(pspec, dspec, dspec),
        out_specs=(P(), pspec), check_vma=False)(
            stacked_params, x, target)


class PipelinedTrainer:
    """Fused train step for a pipelined homogeneous-stage model: S stages
    sharded over the ``pipe`` axis, GPipe or 1F1B schedule, updated by any
    registered fused-optimizer op (the same contract as ``ShardedTrainer``:
    ``optimizer=``/``optimizer_params=``/``lr_scheduler=``).

    Stateless configurations (plain SGD, no schedule) keep the historical
    step signature ``step(params, x, target) -> (loss, new_params)``.
    Stateful ones (momentum/adam/…, or a schedule) use
    ``step(params, states, x, target) -> (loss, new_params, new_states)``
    with ``states = init_states(params)``; ``has_state`` says which."""

    def __init__(self, stage_fn, loss_fn, mesh, n_microbatch, axis="pipe",
                 learning_rate=0.1, schedule="gpipe", optimizer="sgd",
                 optimizer_params=None, momentum=0.0, wd=0.0,
                 rescale_grad=1.0, clip_gradient=None, lr_scheduler=None,
                 batch_axis=None, param_axes=None, reduce_axes=()):
        from .trainer import resolve_lr_fn, resolve_update_op

        self.stage_fn = stage_fn
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.n_microbatch = n_microbatch
        self.axis = axis
        if schedule not in ("gpipe", "1f1b"):
            raise MXNetError("schedule must be 'gpipe' or '1f1b', got %r"
                             % (schedule,))
        if schedule == "gpipe" and (batch_axis or param_axes
                                    or tuple(reduce_axes)):
            # pipeline_apply has no partial-sum/param-sharding contract;
            # silently dropping these would train on wrong gradients
            raise MXNetError(
                "batch_axis/param_axes/reduce_axes require schedule='1f1b'")
        self.schedule = schedule
        self.batch_axis = batch_axis
        self.param_axes = param_axes
        self.reduce_axes = tuple(reduce_axes)
        (self._update_op, self._opt_attrs, self._n_states,
         self._needs_t) = resolve_update_op(
            optimizer, optimizer_params, momentum, learning_rate, wd,
            rescale_grad, clip_gradient)
        self._lr_fn = resolve_lr_fn(lr_scheduler, learning_rate)
        self._needs_count = self._needs_t or self._lr_fn is not None
        self.has_state = self._n_states > 0 or self._needs_count
        self._jit = None

    def init_states(self, stacked_params):
        """Optimizer state for placed params: one zeros-tree per state slot,
        explicitly placed on each param's own sharding (stage-stacked from
        :meth:`place_params`; ``zeros_like`` sharding inheritance is not
        guaranteed across JAX versions), plus the on-device step counter
        when the optimizer/schedule consumes it."""
        stage_shard = NamedSharding(self.mesh, P(self.axis))

        def zeros_placed(a):
            return jax.device_put(
                jnp.zeros(a.shape, a.dtype),
                getattr(a, "sharding", None) or stage_shard)

        st = {}
        if self._n_states:
            st["slots"] = tuple(
                jax.tree_util.tree_map(zeros_placed, stacked_params)
                for _ in range(self._n_states))
        if self._needs_count:
            st["num_update"] = jnp.zeros((), jnp.int32)
        return st

    def _grads(self, params, x, target):
        if self.schedule == "1f1b":
            return pipeline_train_1f1b(
                self.stage_fn, self.loss_fn, params, x, target,
                mesh=self.mesh, n_microbatch=self.n_microbatch,
                axis=self.axis, batch_axis=self.batch_axis,
                param_axes=self.param_axes, reduce_axes=self.reduce_axes)

        def loss(p):
            y = pipeline_apply(self.stage_fn, p, x, mesh=self.mesh,
                               n_microbatch=self.n_microbatch,
                               axis=self.axis)
            return self.loss_fn(y, target)

        return jax.value_and_grad(loss)(params)

    def _apply_updates(self, params, grads, slot_trees, attrs):
        """Flat sweep of the fused-update op over every param leaf."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = treedef.flatten_up_to(grads)
        slot_leaves = [treedef.flatten_up_to(s) for s in slot_trees]
        new_w, new_slots = [], [[] for _ in slot_trees]
        for i, (w, g) in enumerate(zip(leaves, g_leaves)):
            upd, _ = self._update_op.apply(
                attrs, [w, g, *(s[i] for s in slot_leaves)])
            new_w.append(upd[0])
            for k in range(len(slot_trees)):
                new_slots[k].append(upd[1 + k])
        unflatten = jax.tree_util.tree_unflatten
        return (unflatten(treedef, new_w),
                tuple(unflatten(treedef, s) for s in new_slots))

    def step_fn(self):
        if self._jit is not None:
            return self._jit

        if not self.has_state:
            def step(stacked_params, x, target):
                l, grads = self._grads(stacked_params, x, target)
                new_params, _ = self._apply_updates(
                    stacked_params, grads, (), self._opt_attrs)
                return l, new_params

            self._jit = jax.jit(step, donate_argnums=(0,))
            return self._jit

        def step(stacked_params, states, x, target):
            l, grads = self._grads(stacked_params, x, target)
            attrs = self._opt_attrs
            new_states = dict(states)
            if self._needs_count:
                t_new = states["num_update"] + 1
                new_states["num_update"] = t_new
                traced = {"t": t_new}
                if self._lr_fn is not None:
                    traced["lr"] = self._lr_fn(t_new)
                attrs = self._update_op.with_operands(attrs, **traced)
            new_params, slots = self._apply_updates(
                stacked_params, grads, states.get("slots", ()), attrs)
            if slots:
                new_states["slots"] = slots
            return l, new_params, new_states

        self._jit = jax.jit(step, donate_argnums=(0, 1))
        return self._jit

    def place_params(self, stage_params_list):
        stacked = stack_stage_params(stage_params_list)
        shard = NamedSharding(self.mesh, P(self.axis))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, shard), stacked)
