"""``dist_tpu`` — the TPU-native kvstore mode (SURVEY §5's named comm
surface; reference mode dispatch: ``src/kvstore/kvstore.cc:17-44``).

``dist_sync`` reproduces the reference's worker/server split: gradients
gather to the host, the updater runs as host-side imperative ops, results
scatter back.  On TPU that split costs a host round-trip per key per step.
``dist_tpu`` keeps ``dist_sync``'s synchronous exact-arithmetic semantics
but expresses push as what the hardware actually wants: ONE jitted XLA
program per key that (a) sums the per-worker gradients across the global
process mesh (ICI/DCN collective — the summation is an axis-0 sum over the
worker-stacked gradient, the same order ``dist_sync``'s host reduce uses,
so integer-valued flows agree bitwise) and (b) applies the optimizer via
the registered fused ``*_update`` op in the same program — weights and
optimizer state never leave the device between steps.  This is the
kvstore-API spelling of ``ShardedTrainer``'s fused step: same update ops,
same one-registry contract (``Optimizer.fused_spec`` mirrors exactly the
kwargs each ``Optimizer.update`` passes, and a parity test pins the two
paths bitwise).

Mode semantics vs the other dist stores:

* requires ``set_optimizer`` with a fused-op-backed optimizer for
  update-on-push; a plain ``push`` without one accumulates (the
  ``dist_sync`` default-updater behavior) — still fused, still on-device.
* ``set_updater`` is rejected: an arbitrary host callback would reintroduce
  the host round-trip this mode exists to remove (use ``dist_sync``).
"""

from __future__ import annotations

import numpy as _np

from ..base import MXNetError

__all__ = ["FusedTPUStore"]


class FusedTPUStore:
    """Per-key fused reduce+update programs over the global process mesh."""

    def __init__(self):
        import jax

        self._nproc = jax.process_count()
        self._mesh = None
        self._weights = {}   # key -> jnp array (global replicated when dist)
        self._states = {}    # key -> tuple of jnp arrays
        self._spec = None    # (update_op, static_attrs, n_states)
        self._jits = {}      # (kind, shape, dtype) -> compiled step

    # -- plumbing ------------------------------------------------------

    def _ensure_mesh(self):
        """1-D mesh with exactly ONE device per process (hosts with
        several local chips still contribute one mesh slot — the stacked
        gradient's axis is process-sized, and the fused program runs on
        the representative device; dist_sync's reduce is likewise
        per-process)."""
        import jax
        from jax.sharding import Mesh

        if self._mesh is None:
            per_proc = {}
            for d in jax.devices():
                per_proc.setdefault(d.process_index, d)
            devs = [per_proc[p] for p in sorted(per_proc)]
            self._mesh = Mesh(_np.array(devs), ("host",))
            self._local_dev = per_proc[jax.process_index()]
        return self._mesh

    def _to_global(self, arr, stacked=False):
        """Local value -> global array on the process mesh.  The per-push
        gradient (``stacked=True``) stays on-device: its row is this
        process's addressable shard of the worker-stacked global array —
        no host round trip.  Weights/state replicate (init/restore-time
        only, so the host hop there is fine)."""
        import jax
        import jax.numpy as jnp
        from jax.experimental import multihost_utils
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._nproc == 1:
            a = jnp.asarray(arr)
            return a[None] if stacked else a
        mesh = self._ensure_mesh()
        if stacked:
            row = jax.device_put(jnp.asarray(arr)[None], self._local_dev)
            return jax.make_array_from_single_device_arrays(
                (self._nproc,) + tuple(row.shape[1:]),
                NamedSharding(mesh, P("host")), [row])
        return multihost_utils.host_local_array_to_global_array(
            _np.asarray(arr), mesh, P())

    def _local(self, garr):
        """Local (full, replicated) view of a stored array."""
        import jax.numpy as jnp

        if self._nproc == 1:
            return garr
        return jnp.asarray(garr.addressable_shards[0].data)

    def _replicated_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self._ensure_mesh(), P())

    def _step(self, kind, shape, dtype):
        """Build/cache the fused program for one key signature.  ``kind``
        is 'accum' or the update op; the program takes
        (weight, stacked_grads, lr, wd, t, *state) and returns
        (new_weight, *new_state) — reduce and update in one compile."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        key = (kind, tuple(shape), str(dtype))
        if key in self._jits:
            return self._jits[key]
        spec = self._spec
        nproc = self._nproc

        def fn(w, gstack, lr, wd, t, *state):
            # worker-stacked sum: the same axis-0 summation order the
            # dist_sync host reduce uses (exact for integer-valued flows)
            g = jnp.sum(gstack, axis=0)
            if kind == "accum":
                return (w + g,)
            update_op, static_attrs, _ = spec
            attrs = update_op.with_operands(static_attrs, lr=lr, wd=wd, t=t)
            outs, _ = update_op.apply(attrs, [w, g, *state])
            return tuple(outs)

        if nproc == 1:
            comp = jax.jit(fn)
        else:
            mesh = self._ensure_mesh()
            from jax.sharding import NamedSharding

            rep = NamedSharding(mesh, P())
            n_state = 0 if kind == "accum" else spec[2]
            in_sh = (rep, NamedSharding(mesh, P("host")), rep, rep, rep) \
                + (rep,) * n_state
            out_sh = (rep,) * (1 + n_state)
            comp = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        self._jits[key] = comp
        return comp

    # -- store API -----------------------------------------------------

    def set_optimizer(self, optimizer):
        self._spec = optimizer.fused_spec()  # raises if not fused-capable
        self._jits = {k: v for k, v in self._jits.items()
                      if k[0] == "accum"}
        self._states = {}

    def init(self, key, value_jnp):
        self._weights[key] = self._to_global(value_jnp)
        self._states.pop(key, None)

    def __contains__(self, key):
        return key in self._weights

    def push(self, key, grad_jnp, lr=0.0, wd=0.0, t=0):
        if key not in self._weights:
            raise MXNetError("key %s has not been initialized" % key)
        w = self._weights[key]
        gstack = self._to_global(grad_jnp, stacked=True)
        if self._spec is None:
            kind, state = "accum", ()
        else:
            kind = self._spec[0].name
            state = self._states.get(key)
            if state is None:
                z = _np.zeros(w.shape, w.dtype)
                state = tuple(self._to_global(z)
                              for _ in range(self._spec[2]))
        step = self._step(kind, w.shape, w.dtype)
        outs = step(w, gstack,
                    _np.float32(lr), _np.float32(wd), _np.int32(t), *state)
        self._weights[key] = outs[0]
        if self._spec is not None:
            self._states[key] = tuple(outs[1:])

    def pull(self, key):
        if key not in self._weights:
            raise MXNetError("key %s has not been initialized" % key)
        return self._local(self._weights[key])

    # -- optimizer-state persistence ----------------------------------

    def get_states(self):
        import pickle

        return pickle.dumps({
            k: tuple(_np.asarray(self._local(s)) for s in st)
            for k, st in self._states.items()})

    def set_states(self, blob):
        import pickle

        self._states = {
            k: tuple(self._to_global(s) for s in st)
            for k, st in pickle.loads(blob).items()}
