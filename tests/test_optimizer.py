"""Optimizers vs python reference updaters (parity model: reference
``tests/python/unittest/test_optimizer.py``)."""

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal


def _run(opt, w0, g, steps=3):
    """Apply `opt` for `steps` steps on a copy of w0 with constant grad g."""
    w = mx.nd.array(w0.copy())
    state = opt.create_state(0, w)
    for _ in range(steps):
        opt.update(0, w, mx.nd.array(g), state)
    return w.asnumpy()


def _prep(g, rescale, clip):
    g = g * rescale
    if clip is not None:
        g = np.clip(g, -clip, clip)
    return g


def test_sgd_matches_numpy():
    w0 = np.random.uniform(-1, 1, (5, 4)).astype(np.float32)
    g = np.random.uniform(-1, 1, (5, 4)).astype(np.float32)
    for momentum in (0.0, 0.9):
        for wd in (0.0, 0.05):
            for clip in (None, 0.1):
                opt = mx.optimizer.SGD(learning_rate=0.1, momentum=momentum,
                                       wd=wd, rescale_grad=0.5,
                                       clip_gradient=clip)
                got = _run(opt, w0, g)
                w = w0.copy()
                mom = np.zeros_like(w)
                for _ in range(3):
                    gg = _prep(g, 0.5, clip)
                    mom = momentum * mom - 0.1 * (gg + wd * w)
                    w = w + mom
                assert_almost_equal(got, w, rtol=1e-5, atol=1e-6)


def test_adam_matches_numpy():
    w0 = np.random.uniform(-1, 1, (4, 3)).astype(np.float32)
    g = np.random.uniform(-1, 1, (4, 3)).astype(np.float32)
    b1, b2, eps = 0.9, 0.999, 1e-8
    opt = mx.optimizer.Adam(learning_rate=0.01, beta1=b1, beta2=b2,
                            epsilon=eps, wd=0.02)
    got = _run(opt, w0, g)
    w = w0.copy()
    mean = np.zeros_like(w)
    var = np.zeros_like(w)
    for t in range(1, 4):
        gg = g + 0.02 * w
        mean = b1 * mean + (1 - b1) * gg
        var = b2 * var + (1 - b2) * gg * gg
        lr = 0.01 * np.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        w = w - lr * mean / (np.sqrt(var) + eps)
    assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)


def test_rmsprop_matches_numpy():
    w0 = np.random.uniform(-1, 1, (4, 3)).astype(np.float32)
    g = np.random.uniform(-1, 1, (4, 3)).astype(np.float32)
    opt = mx.optimizer.RMSProp(learning_rate=0.01, gamma1=0.95)
    got = _run(opt, w0, g)
    w = w0.copy()
    n = np.zeros_like(w)
    for _ in range(3):
        n = 0.95 * n + 0.05 * g * g
        w = w - 0.01 * g / np.sqrt(n + 1e-8)
    assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)


def test_adagrad_matches_numpy():
    w0 = np.random.uniform(-1, 1, (4,)).astype(np.float32)
    g = np.random.uniform(-1, 1, (4,)).astype(np.float32)
    opt = mx.optimizer.AdaGrad(learning_rate=0.1, eps=1e-7)
    got = _run(opt, w0, g)
    w = w0.copy()
    h = np.zeros_like(w)
    for _ in range(3):
        h = h + g * g
        w = w - 0.1 * g / np.sqrt(h + 1e-7)
    assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)


def test_nag_differs_from_sgd():
    w0 = np.random.uniform(-1, 1, (4,)).astype(np.float32)
    g = np.random.uniform(-1, 1, (4,)).astype(np.float32)
    sgd = _run(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9), w0, g)
    nag = _run(mx.optimizer.NAG(learning_rate=0.1, momentum=0.9), w0, g)
    assert not np.allclose(sgd, nag)


def test_create_by_name_and_registry():
    for name in ("sgd", "adam", "rmsprop", "adagrad", "adadelta", "ftrl",
                 "nag", "sgld", "dcasgd", "test", "ccsgd"):
        opt = mx.optimizer.create(name)
        assert isinstance(opt, mx.optimizer.Optimizer)


def test_lr_wd_mult():
    opt = mx.optimizer.SGD(learning_rate=1.0,
                           param_idx2name={0: "w_weight", 1: "b_bias"}, wd=0.1)
    opt.set_lr_mult({"w_weight": 0.5})
    opt.set_wd_mult({})
    assert opt._get_lr(0) == 0.5
    assert opt._get_lr(1) == 1.0
    # bias gets wd_mult 0 by the _weight/_gamma convention
    assert opt._get_wd(1) == 0.0
    assert abs(opt._get_wd(0) - 0.1) < 1e-12


def test_lr_scheduler_factor():
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    opt = mx.optimizer.SGD(learning_rate=1.0, lr_scheduler=sched)
    w = mx.nd.zeros((2,))
    g = mx.nd.ones((2,))
    lrs = []
    for _ in range(6):
        opt.update(0, w, g, None)
        lrs.append(opt._get_lr(0))
    assert lrs[0] == 1.0
    assert lrs[-1] < lrs[0]


def test_multifactor_scheduler():
    sched = mx.lr_scheduler.MultiFactorScheduler(step=[3, 6], factor=0.1)
    sched.base_lr = 1.0
    assert abs(sched(1) - 1.0) < 1e-9
    assert abs(sched(4) - 0.1) < 1e-9
    assert abs(sched(7) - 0.01) < 1e-9


def test_updater_and_serialization():
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
    upd = mx.optimizer.get_updater(opt)
    w = mx.nd.array(np.ones((3,), np.float32))
    g = mx.nd.array(np.full((3,), 0.5, np.float32))
    upd(0, g, w)
    states = upd.get_states()
    upd2 = mx.optimizer.get_updater(mx.optimizer.SGD(learning_rate=0.1,
                                                     momentum=0.9))
    upd2.set_states(states)
    assert 0 in upd2.states


# ----------------------------------------------------------------------
# The five fused update ops declare which hyperparameters are operands
# (``ParamSpec(operand=True)``): eager calls compile one program a static
# configuration and shape, whatever lr / wd / t do from step to step.
# ----------------------------------------------------------------------

import jax
import pytest

from mxnet_tpu import ndarray as _nd_mod
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops.registry import get_op

# op -> (number of state tensors, the optimizer's own constants)
_UPDATE_OPS = {
    "sgd_update": (0, {}),
    "sgd_mom_update": (1, {"momentum": 0.9}),
    "adam_update": (2, {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}),
    "rmsprop_update": (1, {"gamma1": 0.95, "epsilon": 1e-8}),
    "rmspropalex_update": (3, {"gamma1": 0.95, "gamma2": 0.9,
                               "epsilon": 1e-8}),
}
_update_ops = pytest.mark.parametrize("op_name", sorted(_UPDATE_OPS))


def _tensors(op_name, shape, rs):
    """weight, grad and states the op's arithmetic is defined on (the
    second moments positive, and above the first's square)."""
    n_states = _UPDATE_OPS[op_name][0]
    w = rs.randn(*shape).astype(np.float32)
    g = (2 * rs.randn(*shape)).astype(np.float32)
    states = [(0.1 * np.abs(rs.randn(*shape))).astype(np.float32)
              for _ in range(n_states)]
    if op_name == "rmspropalex_update":
        states[0] += 1.0
    return [w, g] + states


def _step_kwargs(op_name, step, **static):
    kw = dict(_UPDATE_OPS[op_name][1], lr=0.1 * 0.93 ** step,
              wd=1e-3 * step, **static)
    if op_name == "adam_update":
        kw["t"] = step
    return kw


@_update_ops
def test_eager_update_compiles_one_program(op_name, monkeypatch):
    """Twenty eager steps with lr, wd and t changing at every step are ONE
    ``_jitted_apply`` program, compiled once a parameter shape (at PR 43
    every step of every parameter was a new ``jax.jit`` and a new compile)."""
    programs = set()
    cached = _nd_mod._jitted_apply

    def spy(*key):
        fn = cached(*key)
        programs.add(fn)
        return fn

    monkeypatch.setattr(_nd_mod, "_jitted_apply", spy)
    rs = np.random.RandomState(0)
    shapes = [(6, 5), (7,)]
    arrays = [[mx.nd.array(a) for a in _tensors(op_name, s, rs)]
              for s in shapes]
    for step in range(1, 21):
        for arrs in arrays:
            # a rescale_grad no other test uses: the program is this test's
            out = getattr(mx.nd, op_name)(
                *arrs, **_step_kwargs(op_name, step, rescale_grad=0.4375))
            outs = out if isinstance(out, list) else [out]
            arrs[0], arrs[2:] = outs[0], outs[1:]
    assert len(programs) == 1, len(programs)
    (fn,) = programs
    assert fn._cache_size() == len(shapes), fn._cache_size()
    assert all(np.isfinite(a.asnumpy()).all() for arrs in arrays for a in arrs)


@_update_ops
def test_eager_update_is_the_registered_arithmetic(op_name):
    """A step through ``nd.<op>`` (lr, wd, t as operands) is bitwise the
    registered function jitted with Python attributes — PR 43's program —
    with and without clip_gradient, wd zero and non-zero."""
    op = get_op(op_name)
    rs = np.random.RandomState(1)
    for clip in (-1.0, 0.5):
        for wd in (0.0, 1e-3):
            for step in (1, 2, 5, 40):
                kw = _step_kwargs(op_name, step, rescale_grad=1.0 / 3,
                                  clip_gradient=clip)
                kw["wd"] = wd
                arrays = _tensors(op_name, (9, 8), rs)
                attrs = op.parse_attrs(kw)
                want = jax.jit(lambda *t: op.fn(attrs, *t))(*arrays)
                got = getattr(mx.nd, op_name)(
                    *[mx.nd.array(a) for a in arrays], **kw)
                got = got if isinstance(got, list) else [got]
                want = want if isinstance(want, tuple) else (want,)
                assert len(got) == len(want)
                for a, b in zip(got, want):
                    assert a.asnumpy().tobytes() == np.asarray(b).tobytes(), (
                        op_name, clip, wd, step)


def test_adam_step_three_reads_the_runtime_power():
    """The one step where the operand program is not bitwise PR 43's:
    with ``t=3`` baked in, XLA folded ``beta**3`` at compile time to
    ``b*b*b``; as an operand it is the runtime ``pow`` every fused trainer
    always ran, and ``1 - beta2**3`` magnifies their last-place difference
    to 108 float32 spacings of the step size (5e-6 of it, and the nearer
    of the two to the exact value)."""
    op = get_op("adam_update")
    rs = np.random.RandomState(2)
    arrays = _tensors("adam_update", (9, 8), rs)
    kw = _step_kwargs("adam_update", 3)
    attrs = op.parse_attrs(kw)
    want = jax.jit(lambda *t: op.fn(attrs, *t))(*arrays)
    got = mx.nd.adam_update(*[mx.nd.array(a) for a in arrays], **kw)
    step_want = np.asarray(want[0]) - arrays[0]
    step_got = got[0].asnumpy() - arrays[0]
    np.testing.assert_allclose(step_got, step_want, rtol=2e-5, atol=1e-7)
    for a, b in zip(got[1:], want[1:]):  # the moments do not read t
        assert a.asnumpy().tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("make", [
    lambda s: mx.optimizer.SGD(learning_rate=0.1, lr_scheduler=s),
    lambda s: mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                               lr_scheduler=s),
    lambda s: mx.optimizer.Adam(learning_rate=0.01, lr_scheduler=s),
    lambda s: mx.optimizer.RMSProp(learning_rate=0.01, lr_scheduler=s),
    lambda s: mx.optimizer.RMSProp(learning_rate=0.01, centered=True,
                                   lr_scheduler=s),
], ids=["sgd", "sgd_mom", "adam", "rmsprop", "rmspropalex"])
def test_program_cache_does_not_grow_with_steps(make):
    """Fifty updater steps under a decaying learning rate leave
    ``_jitted_apply`` holding what it held after the second."""
    sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.97)
    upd = mx.optimizer.get_updater(make(sched))
    rs = np.random.RandomState(3)
    weights = [mx.nd.array(rs.randn(*s).astype(np.float32))
               for s in ((4, 3), (5,))]
    first = [w.asnumpy() for w in weights]
    sizes = []
    for _ in range(50):
        for i, w in enumerate(weights):
            upd(i, mx.nd.array(rs.randn(*w.shape).astype(np.float32)), w)
        sizes.append(_nd_mod._jitted_apply.cache_info().currsize)
    assert sizes[-1] == sizes[1], sizes
    assert all(np.abs(w.asnumpy() - f).max() > 0
               for w, f in zip(weights, first))


@_update_ops
def test_declared_operands(op_name):
    """The op's registration is the one place that says which attributes
    may be traced: lr and wd, and adam's t.  ``with_operands`` sets those,
    skips a name the op does not have, and refuses any other attribute."""
    op = get_op(op_name)
    want = ("lr", "wd") + (("t",) if op_name == "adam_update" else ())
    assert op.operand_params == want
    attrs = op.parse_attrs(dict(_UPDATE_OPS[op_name][1], lr=0.5))
    set_ = op.with_operands(attrs, lr=0.25, wd=0.125, t=7)
    changed = {k for k in set_ if set_[k] != attrs[k]}
    assert changed == set(want) and set(set_) == set(attrs)
    assert attrs["lr"] == 0.5  # the caller's dict is not written
    for name in sorted(set(op.params) - set(want)):
        with pytest.raises(MXNetError, match="operand"):
            op.with_operands(attrs, **{name: 1.0})


@_update_ops
def test_fused_callers_trace_only_declared_operands(op_name, monkeypatch):
    """What ShardedTrainer, PipelinedTrainer and the dist_tpu store hand
    the op as traced values lies inside its declared operands, and the
    spec ``Optimizer.fused_spec`` builds overwrites nothing else."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from mxnet_tpu.ops.registry import Op
    from mxnet_tpu.parallel import pipeline as pp
    from mxnet_tpu.parallel.dist_tpu import FusedTPUStore
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    op = get_op(op_name)
    traced = []
    apply_ = Op.apply

    def spy(self, attrs, *a, **kw):
        if self is op:
            traced.append({k for k, v in attrs.items()
                           if isinstance(v, jax.core.Tracer)})
        return apply_(self, attrs, *a, **kw)

    monkeypatch.setattr(Op, "apply", spy)
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    name = op_name[:-len("_update")]
    opt_kw = dict(optimizer="sgd" if name.startswith("sgd") else name,
                  momentum=0.9 if name == "sgd_mom" else 0.0,
                  lr_scheduler=sched, learning_rate=0.05)

    fc = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=1,
                               no_bias=True, name="fc")
    tr = ShardedTrainer(mx.sym.MakeLoss(fc, name="loss"),
                        Mesh(np.array(jax.devices()[:1]), ("data",)),
                        data_shapes={"data": (4, 6)}, **opt_kw)
    params, moms, aux = tr.init(seed=0)
    batch = tr.place_batch({"data": np.ones((4, 6), np.float32)})
    tr.step_fn()
    tr.lowered_step(params, moms, aux, batch, jax.random.PRNGKey(0))
    n_sharded = len(traced)

    mesh = Mesh(np.array(jax.devices()[:2]), ("pipe",))
    stages = [{"w": jnp.eye(4, dtype=jnp.float32)} for _ in range(2)]
    pt = pp.PipelinedTrainer(lambda p, x: jnp.tanh(x @ p["w"]),
                             lambda y, t: jnp.mean((y - t) ** 2), mesh,
                             n_microbatch=2, schedule="1f1b", **opt_kw)
    placed = pt.place_params(stages)
    x = jnp.ones((4, 4), jnp.float32)
    pt.step_fn().lower(placed, pt.init_states(placed), x, x)
    n_pipe = len(traced) - n_sharded

    eager = {"sgd": mx.optimizer.SGD(),
             "sgd_mom": mx.optimizer.SGD(momentum=0.9),
             "adam": mx.optimizer.Adam(),
             "rmsprop": mx.optimizer.RMSProp(),
             "rmspropalex": mx.optimizer.RMSProp(centered=True)}[name]
    store = FusedTPUStore()
    store.set_optimizer(eager)
    spec_op, spec_attrs, n_states = store._spec
    assert spec_op is op and n_states == _UPDATE_OPS[op_name][0]
    store.init(0, jnp.ones((3,), jnp.float32))
    store.push(0, jnp.ones((3,), jnp.float32), lr=0.1, wd=0.01, t=1)
    n_store = len(traced) - n_sharded - n_pipe

    assert n_sharded and n_pipe and n_store, (n_sharded, n_pipe, n_store)
    operands = set(op.operand_params)
    assert all(t <= operands for t in traced), traced
    # the scheduled lr and (adam) the step count did arrive traced, and
    # the store traced every operand
    assert traced[0] == operands - {"wd"}
    assert traced[-1] == operands
