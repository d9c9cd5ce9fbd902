"""Optimizers (parity: reference ``python/mxnet/optimizer.py``: SGD, NAG,
SGLD, ccSGD, Adam, AdaGrad, RMSProp, AdaDelta, Ftrl, DCASGD, Test).

Update math runs through the fused update ops in ``ops/tensor.py``
(reference ``src/operator/optimizer_op.cc``) or inline jnp expressions —
either way it jit-compiles and fuses with nothing else to schedule.  The
``Updater`` closure and ``get_updater`` keep KVStore's server-side-optimizer
contract (``kvstore.set_optimizer`` pickles an Optimizer, reference
``kvstore.py:226``).
"""

from __future__ import annotations

import math
import pickle

import numpy

from .ndarray import NDArray, zeros
from . import ndarray as nd


def _zeros_like(weight):
    """State tensor matching the weight's dtype AND device placement/sharding
    (mesh-replicated weights get mesh-replicated optimizer state)."""
    import jax.numpy as jnp

    return NDArray(jnp.zeros_like(weight._data), weight.context)

__all__ = [
    "Optimizer", "SGD", "NAG", "SGLD", "ccSGD", "Adam", "AdaGrad", "RMSProp",
    "AdaDelta", "Ftrl", "DCASGD", "Test", "Updater", "get_updater", "create",
    "register",
]


class Optimizer(object):
    """Base optimizer (parity: ``optimizer.py:Optimizer``)."""

    opt_registry = {}

    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        import threading

        self._count_lock = threading.Lock()
        self.clip_gradient = clip_gradient
        if param_idx2name is None:
            param_idx2name = {}
        self.idx2name = param_idx2name.copy()
        self.sym = sym
        if sym is not None:
            attrs = sym.attr_dict()
            for name in sym.list_arguments():
                if name in attrs:
                    if "__lr_mult__" in attrs[name]:
                        self.lr_mult[name] = float(attrs[name]["__lr_mult__"])
                    if "__wd_mult__" in attrs[name]:
                        self.wd_mult[name] = float(attrs[name]["__wd_mult__"])

    def create_state(self, index, weight):
        raise NotImplementedError()

    def update(self, index, weight, grad, state):
        raise NotImplementedError()

    def set_lr_scale(self, args_lrscale):  # deprecated in reference too
        raise DeprecationWarning

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        if self.sym is not None:
            attr = self.sym.attr_dict()
            for name in self.sym.list_arguments():
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym is not None:
            attr = self.sym.attr_dict()
            for name in self.sym.list_arguments():
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def __getstate__(self):
        # the count lock is not picklable; set_optimizer pickles optimizers
        # to the (possibly remote) updater side
        state = self.__dict__.copy()
        state.pop("_count_lock", None)
        return state

    def __setstate__(self, state):
        import threading

        self.__dict__.update(state)
        self._count_lock = threading.Lock()

    def _update_count(self, index):
        # engine-backed kvstores may run per-key updates on concurrent
        # worker threads; the read-modify-writes must be atomic or the
        # lr_scheduler sees a stale step count
        with self._count_lock:
            if index not in self._index_update_count:
                self._index_update_count[index] = self.begin_num_update
            self._index_update_count[index] += 1
            self.num_update = max(self._index_update_count[index],
                                  self.num_update)

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        if index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    def _fused_spec_for(self, op_name, **static):
        """Build a ``dist_tpu`` fused-step spec from a registered update
        op: ``(op, attrs, n_states)``.  ``attrs`` is fully parsed, the
        op's declared operands (lr, wd, adam's t) at their defaults: the
        fused program sets them to traced values at every push
        (``Op.with_operands``) — so the update arithmetic is THE
        registered op's, the same one :meth:`update` calls (one registry,
        zero drift)."""
        from .ops.registry import get_op

        op = get_op(op_name)
        full = dict(static, rescale_grad=self.rescale_grad,
                    clip_gradient=self.clip_gradient or -1.0)
        full.update((k, op.params[k].default) for k in op.operand_params)
        attrs = op.parse_attrs(full)
        return op, attrs, op.n_outputs(attrs) - 1

    def fused_spec(self):
        """The fused reduce+update spec for the ``dist_tpu`` kvstore.
        Optimizers whose update math has no registered fused op cannot run
        on-device-fused; use ``dist_sync`` (host-side updater) for those."""
        from .base import MXNetError

        raise MXNetError(
            "%s has no fused update op: dist_tpu fuses the optimizer into "
            "the on-device sync step and needs one (sgd/adam/rmsprop). "
            "Use kvstore 'dist_sync' for host-side updaters."
            % type(self).__name__)


register = Optimizer.register


def _prep(grad_np, rescale, clip):
    g = grad_np * rescale
    if clip is not None and clip > 0:
        import jax.numpy as jnp

        g = jnp.clip(g, -clip, clip)
    return g


@register
class SGD(Optimizer):
    """SGD with momentum (parity: ``optimizer.py:SGD``), lowered to the fused
    ``sgd_update``/``sgd_mom_update`` ops."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _zeros_like(weight)

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        kwargs = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                      clip_gradient=self.clip_gradient or -1.0)
        if state is not None:
            nd.sgd_mom_update(weight, grad, state, out=[weight, state],
                              momentum=self.momentum, **kwargs)
        else:
            nd.sgd_update(weight, grad, out=weight, **kwargs)

    def fused_spec(self):
        if self.momentum:
            return self._fused_spec_for("sgd_mom_update",
                                        momentum=self.momentum)
        return self._fused_spec_for("sgd_update")


@register
class NAG(SGD):
    """Nesterov accelerated SGD (parity: ``optimizer.py:NAG``)."""

    def fused_spec(self):  # NAG's lookahead is not sgd_mom_update's math
        return Optimizer.fused_spec(self)

    def update(self, index, weight, grad, state):
        import jax.numpy as jnp

        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient)
        if state is not None:
            mom = state._data * self.momentum
            gfull = g + wd * weight._data
            mom = mom + gfull
            g2 = gfull + self.momentum * mom
            state._set_data(mom)
            weight._set_data(weight._data - lr * g2)
        else:
            weight._set_data(weight._data - lr * (g + wd * weight._data))


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (parity: ``optimizer.py:SGLD``)."""

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        import jax

        from . import random as _random

        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient)
        noise = jax.random.normal(_random.next_key(), weight.shape,
                                  dtype=weight._data.dtype) * math.sqrt(lr)
        weight._set_data(weight._data - lr / 2 * (g + wd * weight._data) + noise)


@register
class ccSGD(SGD):
    """Same as SGD (the reference's ccSGD is a C++-side SGD clone)."""


@register
class Adam(Optimizer):
    """Adam (parity: ``optimizer.py:Adam``), fused ``adam_update`` op."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        t = self._index_update_count[index]
        mean, var = state
        nd.adam_update(weight, grad, mean, var, out=[weight, mean, var],
                       lr=lr, wd=wd, beta1=self.beta1, beta2=self.beta2,
                       epsilon=self.epsilon, t=t,
                       rescale_grad=self.rescale_grad,
                       clip_gradient=self.clip_gradient or -1.0)

    def fused_spec(self):
        return self._fused_spec_for("adam_update", beta1=self.beta1,
                                    beta2=self.beta2, epsilon=self.epsilon)


@register
class AdaGrad(Optimizer):
    """AdaGrad (parity: ``optimizer.py:AdaGrad``)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return _zeros_like(weight)

    def update(self, index, weight, grad, state):
        import jax.numpy as jnp

        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient)
        hist = state._data + jnp.square(g)
        state._set_data(hist)
        weight._set_data(
            weight._data
            - lr * (g / jnp.sqrt(hist + self.float_stable_eps) + wd * weight._data)
        )


@register
class RMSProp(Optimizer):
    """RMSProp (parity: ``optimizer.py:RMSProp``; centered=True matches the
    reference's Alex Graves variant via ``rmspropalex_update``)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (_zeros_like(weight), _zeros_like(weight),
                    _zeros_like(weight))
        return (_zeros_like(weight),)

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        kwargs = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                      clip_gradient=self.clip_gradient or -1.0,
                      gamma1=self.gamma1, epsilon=self.epsilon)
        if not self.centered:
            (n,) = state
            nd.rmsprop_update(weight, grad, n, out=[weight, n], **kwargs)
        else:
            n, g, delta = state
            nd.rmspropalex_update(weight, grad, n, g, delta,
                                  out=[weight, n, g, delta],
                                  gamma2=self.gamma2, **kwargs)

    def fused_spec(self):
        if self.centered:
            return self._fused_spec_for(
                "rmspropalex_update", gamma1=self.gamma1,
                gamma2=self.gamma2, epsilon=self.epsilon)
        return self._fused_spec_for("rmsprop_update", gamma1=self.gamma1,
                                    epsilon=self.epsilon)


@register
class AdaDelta(Optimizer):
    """AdaDelta (parity: ``optimizer.py:AdaDelta``)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))

    def update(self, index, weight, grad, state):
        import jax.numpy as jnp

        wd = self._get_wd(index)
        self._update_count(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient)
        acc_g, acc_delta = state
        new_acc_g = self.rho * acc_g._data + (1.0 - self.rho) * jnp.square(g)
        delta = (
            jnp.sqrt(acc_delta._data + self.epsilon)
            / jnp.sqrt(new_acc_g + self.epsilon)
            * g
        )
        new_acc_delta = self.rho * acc_delta._data + (1.0 - self.rho) * jnp.square(delta)
        acc_g._set_data(new_acc_g)
        acc_delta._set_data(new_acc_delta)
        weight._set_data(weight._data - delta - wd * weight._data)


@register
class Ftrl(Optimizer):
    """FTRL (parity: ``optimizer.py:Ftrl``)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))

    def update(self, index, weight, grad, state):
        import jax.numpy as jnp

        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient)
        z, n = state
        sigma = (jnp.sqrt(n._data + jnp.square(g)) - jnp.sqrt(n._data)) / lr
        new_z = z._data + g - sigma * weight._data
        new_n = n._data + jnp.square(g)
        z._set_data(new_z)
        n._set_data(new_n)
        new_w = jnp.where(
            jnp.abs(new_z) <= self.lamda1,
            jnp.zeros_like(new_z),
            (jnp.sign(new_z) * self.lamda1 - new_z)
            / ((self.beta + jnp.sqrt(new_n)) / lr + wd),
        )
        weight._set_data(new_w)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (parity: ``optimizer.py:DCASGD``)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (_zeros_like(weight), weight.copy())

    def update(self, index, weight, grad, state):
        import jax.numpy as jnp

        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient)
        mon, previous_weight = state
        delta = -lr * (
            g
            + wd * weight._data
            + self.lamda * g * g * (weight._data - previous_weight._data)
        )
        if mon is not None:
            m = self.momentum * mon._data + delta
            mon._set_data(m)
            delta = m
        previous_weight._set_data(weight._data)
        weight._set_data(weight._data + delta)


@register
class Test(Optimizer):
    """Test optimizer: ``w += rescale_grad * grad`` (parity:
    ``optimizer.py:706`` — used by the kvstore exact-arithmetic tests)."""

    def create_state(self, index, weight):
        return _zeros_like(weight)

    def update(self, index, weight, grad, state):
        weight._set_data(weight._data + grad._data * self.rescale_grad)
        state._set_data(weight._data)


def create(name, rescale_grad=1.0, **kwargs):
    """Create optimizer by name (parity: ``optimizer.py:create``)."""
    if isinstance(name, Optimizer):
        return name
    return Optimizer.create_optimizer(name, rescale_grad=rescale_grad, **kwargs)


class Updater(object):
    """Weight updater closure for kvstore (parity: ``optimizer.py:get_updater``)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state(index, weight)
        self.optimizer.update(index, weight, grad, self.states[index])

    def set_states(self, states):
        self.states = pickle.loads(states)

    def get_states(self):
        return pickle.dumps(self.states)


def get_updater(optimizer):
    return Updater(optimizer)
