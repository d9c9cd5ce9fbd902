"""Device context (parity: reference ``python/mxnet/context.py``).

``mx.tpu(i)`` is the native device here; ``mx.gpu(i)`` is accepted as an alias
so reference example scripts run with ``--gpus`` unchanged.  A Context maps to a
concrete ``jax.Device``; a context stack (``with mx.tpu(0):``) supplies the
default, exactly like the reference's ``Context._default_ctx``.
"""

from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_tpus"]


class Context:
    """Device context.

    Parameters mirror reference ``context.py:Context`` (device_type, device_id).
    ``devtype2id``/``devid2type`` keep the reference's numeric codes and add
    ``tpu`` (code 6, unused by the reference).
    """

    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 6}
    devid2type = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 6: "tpu"}

    _state = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devtype2id[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devid2type[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        stack = _ctx_stack()
        stack.append(self)
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        _ctx_stack().pop()

    # ------------------------------------------------------------------
    # JAX mapping
    # ------------------------------------------------------------------
    @property
    def jax_device(self):
        """The concrete jax.Device this context denotes.

        ``cpu`` maps to the JAX cpu backend.  ``tpu`` (and its alias
        ``gpu``) maps onto the TPU backend and nothing else: with no
        TPU backend it raises, so a program that asked for the chip
        never runs on the host unnoticed.
        """
        import jax

        # multi-process SPMD: a context always denotes one of THIS process's
        # devices (the reference's ctx is likewise process-local; global
        # placement is the mesh/sharding layer's job)
        local = jax.process_count() > 1
        if self.device_type in ("cpu", "cpu_pinned"):
            try:
                devs = (jax.local_devices(backend="cpu") if local
                        else jax.devices("cpu"))
            except RuntimeError:
                devs = jax.local_devices() if local else jax.devices()
            return devs[min(self.device_id, len(devs) - 1)]
        devs = jax.local_devices() if local else jax.devices()
        if devs[0].platform != "tpu":
            raise MXNetError(
                "context %s needs a TPU backend, JAX found platform %r; "
                "say mx.cpu() to run on the host" % (self, devs[0].platform))
        if self.device_id >= len(devs):
            raise ValueError(
                "context %s out of range: only %d device(s) visible" % (self, len(devs))
            )
        return devs[self.device_id]


def _ctx_stack():
    st = getattr(Context._state, "stack", None)
    if st is None:
        st = [Context("cpu", 0)]
        Context._state.stack = st
    return st


def cpu(device_id=0):
    """Return a CPU context (parity: ``context.py:cpu``)."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Alias for :func:`tpu` so ``--gpus`` scripts run unchanged."""
    return Context("tpu", device_id)


def tpu(device_id=0):
    """Return a TPU context — the native accelerator context of this framework."""
    return Context("tpu", device_id)


def current_context():
    """Return the current context (parity: ``context.py:current_context``)."""
    return _ctx_stack()[-1]


def num_tpus():
    """Number of visible accelerator devices."""
    import jax

    return len(jax.devices())


def devices_from_arg(tpus_arg):
    """Map a ``--tpus`` CLI string (e.g. ``"0,1,2"``) to a context list —
    the TPU twin of the reference examples' ``--gpus`` mapping
    (``example/image-classification/common/fit.py``).  Empty/None takes
    tpu(0) where JAX found a TPU and cpu() where it did not, and prints
    which, so a run that landed on the host says so."""
    import jax

    if tpus_arg:
        return [tpu(int(i)) for i in tpus_arg.split(",")]
    dev = jax.devices()[0]
    ctx = tpu(0) if dev.platform == "tpu" else cpu()
    print("devices_from_arg: no --tpus given, using %s (%s %s, %d visible)"
          % (ctx, dev.platform, dev.device_kind, len(jax.devices())))
    return [ctx]
