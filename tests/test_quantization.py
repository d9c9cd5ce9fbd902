"""Model-level PTQ passes (mxnet_tpu.contrib.quantization): BN fold
exactness, int8 graph rewrite vs fake-quant parity, NHWC quantized conv,
and the __dtype__ variable-hint plumbing the rewrite relies on."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.contrib import quantization as Q


def _fwd(sym, args, auxs, x, ctx=None):
    exe = sym.simple_bind(ctx or mx.cpu(), grad_req="null",
                          data=tuple(x.shape))
    for k, v in args.items():
        if k in exe.arg_dict:
            exe.arg_dict[k][:] = v
    for k, v in auxs.items():
        if k in exe.aux_dict:
            exe.aux_dict[k][:] = v
    exe.arg_dict["data"][:] = x
    return exe.forward(is_train=False)[0].asnumpy()


def _conv_bn_net(layout=None, no_bias=True):
    kw = {"layout": layout} if layout else {}
    net = mx.sym.Convolution(mx.sym.Variable("data"), kernel=(3, 3),
                             num_filter=8, pad=(1, 1), no_bias=no_bias,
                             name="conv0", **kw)
    net = mx.sym.BatchNorm(net, name="bn0", fix_gamma=False,
                           **({"axis": 3} if layout == "NHWC" else {}))
    net = mx.sym.Activation(net, act_type="relu", name="relu0")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=5,
                                name="fc1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _params(rng, layout=None, no_bias=True):
    wshape = (8, 3, 3, 4) if layout == "NHWC" else (8, 4, 3, 3)
    args = {"conv0_weight": mx.nd.array(rng.randn(*wshape) * 0.2),
            "bn0_gamma": mx.nd.array(rng.rand(8) + 0.5),
            "bn0_beta": mx.nd.array(rng.randn(8) * 0.1),
            "fc1_weight": mx.nd.array(rng.randn(5, 8 * 36) * 0.1),
            "fc1_bias": mx.nd.array(rng.randn(5) * 0.1)}
    if not no_bias:
        args["conv0_bias"] = mx.nd.array(rng.randn(8) * 0.1)
    auxs = {"bn0_moving_mean": mx.nd.array(rng.randn(8) * 0.1),
            "bn0_moving_var": mx.nd.array(rng.rand(8) + 0.5)}
    return args, auxs


def _data(rng, layout=None):
    return (rng.randn(4, 6, 6, 4) if layout == "NHWC"
            else rng.randn(4, 4, 6, 6)).astype(np.float32)


@pytest.mark.parametrize("no_bias", [True, False])
def test_fold_bn_exact(no_bias):
    """Folded conv+bias must equal conv->BN(inference stats) to float
    rounding; gamma/beta/moving stats disappear from the params."""
    rng = np.random.RandomState(0)
    net = _conv_bn_net(no_bias=no_bias)
    args, auxs = _params(rng, no_bias=no_bias)
    x = _data(rng)
    y0 = _fwd(net, args, auxs, x)
    fsym, fargs, fauxs = Q.fold_bn(net, args, auxs)
    y1 = _fwd(fsym, fargs, fauxs, x)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)
    assert "bn0_gamma" not in fargs and "bn0_moving_mean" not in fauxs
    assert "conv0_bias" in fargs
    assert "bn0" not in fsym.tojson()


def test_fold_bn_skips_shared_conv_output():
    """A conv whose output feeds the BN AND something else must not fold
    (the scale would corrupt the second consumer)."""
    data = mx.sym.Variable("data")
    conv = mx.sym.Convolution(data, kernel=(1, 1), num_filter=4,
                              no_bias=True, name="convs")
    bn = mx.sym.BatchNorm(conv, name="bns")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Flatten(bn + conv), num_hidden=3, name="fcs"),
        name="softmax")
    rng = np.random.RandomState(1)
    args = {"convs_weight": mx.nd.array(rng.randn(4, 2, 1, 1)),
            "bns_gamma": mx.nd.array(rng.rand(4) + 0.5),
            "bns_beta": mx.nd.array(rng.randn(4)),
            "fcs_weight": mx.nd.array(rng.randn(3, 4 * 9) * 0.1),
            "fcs_bias": mx.nd.array(rng.randn(3))}
    auxs = {"bns_moving_mean": mx.nd.array(rng.randn(4) * 0.1),
            "bns_moving_var": mx.nd.array(rng.rand(4) + 0.5)}
    fsym, fargs, fauxs = Q.fold_bn(net, args, auxs)
    assert "BatchNorm" in fsym.tojson()  # kept, not corrupted
    x = rng.randn(2, 2, 3, 3).astype(np.float32)
    np.testing.assert_allclose(_fwd(fsym, fargs, fauxs, x),
                               _fwd(net, args, auxs, x),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("layout", [None, "NHWC"])
def test_quantize_model_end_to_end(layout):
    """Full pipeline on both conv layouts: int8 predictions track fp32
    closely on in-range data (symmetric calib on the same batch)."""
    rng = np.random.RandomState(2)
    net = _conv_bn_net(layout=layout)
    args, auxs = _params(rng, layout=layout)
    x = _data(rng, layout=layout)
    y0 = _fwd(net, args, auxs, x)
    qsym, qargs, qauxs = Q.quantize_model(net, args, auxs,
                                          [{"data": x}], mx.cpu())
    y1 = _fwd(qsym, qargs, qauxs, x)
    assert qargs["conv0_weight"].asnumpy().dtype == np.int8
    assert qargs["fc1_weight"].asnumpy().dtype == np.int8
    # int8 quantization noise on softmax probabilities
    np.testing.assert_allclose(y1, y0, atol=0.02)
    assert (y1.argmax(axis=1) == y0.argmax(axis=1)).mean() == 1.0


def test_quantize_excluded_nodes_stay_float():
    rng = np.random.RandomState(3)
    net = _conv_bn_net()
    args, auxs = _params(rng)
    x = _data(rng)
    qsym, qargs, qauxs = Q.quantize_model(
        net, args, auxs, [{"data": x}], mx.cpu(),
        excluded_sym_names=["conv0"])
    assert qargs["conv0_weight"].asnumpy().dtype == np.float32
    assert qargs["fc1_weight"].asnumpy().dtype == np.int8
    j = qsym.tojson()
    assert "_contrib_quantized_conv" not in j
    assert "_contrib_quantized_fully_connected" in j


def test_dtype_hint_drives_simple_bind_allocation():
    """__dtype__ Variable hints must survive into simple_bind's array
    allocation (int8 params bind as int8 without a type_dict)."""
    v = mx.sym.Variable("w", shape=(4, 4), dtype="int8")
    out = mx.sym.Cast(v, dtype="float32")
    exe = out.simple_bind(mx.cpu(), grad_req="null")
    assert exe.arg_dict["w"].asnumpy().dtype == np.int8


def test_quantize_tied_weight_with_excluded_consumer_raises():
    """A weight shared between a quantized node and an excluded one
    would be silently rewritten to int8 codes under the float consumer —
    must refuse loudly."""
    from mxnet_tpu.base import MXNetError

    rng = np.random.RandomState(5)
    d = mx.sym.Variable("data")
    w = mx.sym.Variable("shared_w")
    f1 = mx.sym.FullyConnected(d, weight=w, num_hidden=6, no_bias=True,
                               name="fc1")
    f2 = mx.sym.FullyConnected(d, weight=w, num_hidden=6, no_bias=True,
                               name="fc2")
    net = mx.sym.SoftmaxOutput(f1 + f2, name="softmax")
    args = {"shared_w": mx.nd.array(rng.randn(6, 4))}
    with pytest.raises(MXNetError, match="shared"):
        Q.quantize_symbol(net, args, {"fc1": 1.0},
                          excluded_sym_names=["fc2"])
    # both quantized: legal; the tied weight quantizes once with one range
    qsym, qargs = Q.quantize_symbol(net, args, {"fc1": 1.0, "fc2": 1.0})
    assert qargs["shared_w"].asnumpy().dtype == np.int8
    assert np.asarray(qargs["fc1_weight_max"].asnumpy()) \
        == np.asarray(qargs["fc2_weight_max"].asnumpy())


def test_quantize_shared_input_single_quantize_node():
    """Two convs reading the same tensor (the ResNet downsample-block
    shape) share ONE _contrib_quantize node — not one per consumer."""
    rng = np.random.RandomState(6)
    d = mx.sym.Variable("data")
    c1 = mx.sym.Convolution(d, kernel=(1, 1), num_filter=4, no_bias=True,
                            name="ca")
    c2 = mx.sym.Convolution(d, kernel=(3, 3), num_filter=4, pad=(1, 1),
                            no_bias=True, name="cb")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Flatten(c1 + c2), num_hidden=3, name="fcq"),
        name="softmax")
    args = {"ca_weight": mx.nd.array(rng.randn(4, 2, 1, 1)),
            "cb_weight": mx.nd.array(rng.randn(4, 2, 3, 3) * 0.2),
            "fcq_weight": mx.nd.array(rng.randn(3, 4 * 25) * 0.1),
            "fcq_bias": mx.nd.array(rng.randn(3))}
    x = rng.randn(2, 2, 5, 5).astype(np.float32)
    qsym, qargs, qauxs = Q.quantize_model(net, args, {}, [{"data": x}],
                                          mx.cpu())
    j = qsym.tojson()
    # ca+cb share one quantize of `data`; the FC has its own
    assert j.count('"_contrib_quantize"') == 2
    y = _fwd(qsym, qargs, qauxs, x)
    y0 = _fwd(net, args, {}, x)
    assert (y.argmax(axis=1) == y0.argmax(axis=1)).all()


def test_quantize_bf16_outputs():
    """out_dtype='bfloat16' (the chip-winning configuration —
    docs/PERF.md int8-at-model-level): rescaled outputs and biases carry
    bf16, predictions stay within bf16+int8 noise of fp32."""
    rng = np.random.RandomState(7)
    net = _conv_bn_net()
    args, auxs = _params(rng)
    x = _data(rng)
    y0 = _fwd(net, args, auxs, x)
    qsym, qargs, qauxs = Q.quantize_model(net, args, auxs, [{"data": x}],
                                          mx.cpu(), out_dtype="bfloat16")
    y1 = _fwd(qsym, qargs, qauxs, x).astype(np.float32)
    np.testing.assert_allclose(y1, y0, atol=0.03)
    assert (y1.argmax(axis=1) == y0.argmax(axis=1)).mean() == 1.0
    assert str(qargs["conv0_bias"].asnumpy().dtype) == "bfloat16"


# ---------------------------------------------------------------------
# QAT: fake-quant op semantics + insert/finetune/export pipeline
# ---------------------------------------------------------------------


def test_fake_quant_op_ste_and_ema():
    """Clipped STE: gradient 1 inside [-amax, amax], 0 outside; EMA
    observer seeds from the first batch then tracks with momentum; an
    empty observer passes eval-mode data through unchanged."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.registry import get_op

    op = get_op("_contrib_fake_quant")
    attrs = {"ema_momentum": 0.9, "num_bits": 8}
    amax = jnp.array([1.0], jnp.float32)

    def f(xx):
        return op.apply(attrs, [xx], [amax], is_train=False)[0][0].sum()

    g = jax.grad(f)(jnp.array([0.5, -2.0, 3.0, 0.01], jnp.float32))
    np.testing.assert_allclose(np.asarray(g), [1.0, 0.0, 0.0, 1.0])

    # forward snaps to the 127-level grid
    y = op.apply(attrs, [jnp.array([0.5004, 2.0])], [amax],
                 is_train=False)[0][0]
    np.testing.assert_allclose(
        np.asarray(y), [np.round(0.5004 * 127) / 127, 1.0], rtol=1e-6)

    # observer: first batch seeds, then EMA
    _, aux = op.apply(attrs, [jnp.array([2.0, -4.0])],
                      [jnp.array([0.0])], is_train=True)
    assert float(aux[0][0]) == 4.0
    _, aux = op.apply(attrs, [jnp.array([2.0, -4.0])],
                      [jnp.array([8.0])], is_train=True)
    np.testing.assert_allclose(float(aux[0][0]), 0.9 * 8 + 0.1 * 4)

    # empty observer (amax=0) in eval: identity
    y, aux = op.apply(attrs, [jnp.array([0.123, -7.0])],
                      [jnp.array([0.0])], is_train=False)
    np.testing.assert_allclose(np.asarray(y[0]), [0.123, -7.0])


def _blobs(rng, n=400, d=16, k=4):
    centers = rng.randn(k, d) * 3.0
    labels = rng.randint(0, k, n)
    data = (centers[labels] + rng.randn(n, d)).astype(np.float32)
    return data, labels.astype(np.float32)


def _mlp(k=4):
    # a fresh name scope: the anonymous Activation is ``activation0``
    # whatever this process built before (the auto-name counter is
    # process-wide, and the QAT observers are named after their nodes)
    with mx.name.NameManager():
        net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                    num_hidden=32, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        return mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(net, num_hidden=k, name="fc2"),
            name="softmax")


def test_qat_pipeline_mlp():
    """Train fp32 -> insert fake-quant -> finetune (observers fill via
    the aux-update path) -> export: the int8 graph's outputs match the
    QAT graph's eval-mode forward (same grids by construction) and
    accuracy holds."""
    rng = np.random.RandomState(0)
    data, labels = _blobs(rng)
    it = mx.io.NDArrayIter(data, labels, batch_size=40, shuffle=True)
    net = _mlp()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=8, optimizer="sgd",
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
            initializer=mx.initializer.Xavier())
    args, _ = mod.get_params()

    qat = Q.quantize_aware_symbol(net)
    # one observer per distinct data tensor, dynamic fq per weight
    assert sorted(qat.list_auxiliary_states()) == [
        "activation0_fq_amax", "data_fq_amax"]
    m2 = mx.mod.Module(qat, context=mx.cpu())
    it.reset()
    m2.fit(it, num_epoch=4, optimizer="sgd",
           optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
           arg_params=args, aux_params={}, allow_missing=True,
           initializer=mx.initializer.Xavier())
    qargs, qauxs = m2.get_params()
    assert all(float(v.asnumpy().max()) > 0 for v in qauxs.values())
    acc_qat = m2.score(mx.io.NDArrayIter(data, labels, batch_size=40),
                       "acc")[0][1]
    assert acc_qat > 0.95, acc_qat

    qsym, qa, qx = Q.quantize_model_qat(qat, qargs, qauxs)
    ops = [n["op"] for n in __import__("json").loads(qsym.tojson())["nodes"]]
    assert "_contrib_quantized_fully_connected" in ops
    assert "_contrib_fake_quant" not in ops
    m3 = mx.mod.Module(qsym, context=mx.cpu())
    m3.bind(data_shapes=[("data", (40, 16))],
            label_shapes=[("softmax_label", (40,))], for_training=False)
    m3.set_params(qa, qx)
    acc_int8 = m3.score(mx.io.NDArrayIter(data, labels, batch_size=40),
                        "acc")[0][1]
    assert acc_int8 > 0.95, acc_int8

    # eval-mode QAT forward == int8 graph forward (shared grids)
    m2p = mx.mod.Module(qat, context=mx.cpu())
    m2p.bind(data_shapes=[("data", (40, 16))],
             label_shapes=[("softmax_label", (40,))], for_training=False)
    m2p.set_params(qargs, qauxs)
    b = mx.io.NDArrayIter(data[:40], labels[:40], batch_size=40)
    o_sim = m2p.predict(b).asnumpy()
    b.reset()
    o_int8 = m3.predict(b).asnumpy()
    np.testing.assert_allclose(o_sim, o_int8, rtol=1e-5, atol=1e-6)


def test_qat_conv_after_fold():
    """The documented convnet flow: fold_bn first, then QAT-finetune the
    folded graph (convs carry the folded bias), then export — the conv
    becomes a quantized conv and the graph still runs."""
    rng = np.random.RandomState(3)
    net = _conv_bn_net()
    args, auxs = _params(rng)
    fsym, fargs, fauxs = Q.fold_bn(net, args, auxs)
    qat = Q.quantize_aware_symbol(fsym)
    x = _data(rng)
    labels = rng.randint(0, 5, 4).astype(np.float32)
    m = mx.mod.Module(qat, context=mx.cpu())
    it = mx.io.NDArrayIter(x, labels, batch_size=4)
    m.fit(it, num_epoch=2, optimizer="sgd",
          optimizer_params={"learning_rate": 0.01},
          arg_params=dict(fargs), aux_params={}, allow_missing=True,
          initializer=mx.initializer.Xavier())
    qargs, qauxs = m.get_params()
    qsym, qa, qx = Q.quantize_model_qat(qat, qargs, qauxs)
    ops = [n["op"] for n in __import__("json").loads(qsym.tojson())["nodes"]]
    assert "_contrib_quantized_conv" in ops
    out = _fwd(qsym, {k: v.asnumpy() for k, v in qa.items()},
               {k: v.asnumpy() for k, v in qx.items()}, x)
    assert out.shape == (4, 5)
    assert np.isfinite(out).all()


def test_qat_shared_input_one_observer():
    """Two FCs reading the same tensor share ONE observer node (the
    shared-``_contrib_quantize`` rule's training twin)."""
    import json as _json

    d = mx.sym.Variable("data")
    a = mx.sym.FullyConnected(d, num_hidden=4, name="fca")
    b = mx.sym.FullyConnected(d, num_hidden=4, name="fcb")
    qat = Q.quantize_aware_symbol(mx.sym.Group([a, b]))
    nodes = _json.loads(qat.tojson())["nodes"]
    fq_obs = [n for n in nodes if n["op"] == "_contrib_fake_quant"]
    assert len(fq_obs) == 1, [n["name"] for n in fq_obs]


def test_qat_export_empty_observer_raises():
    """Exporting before any training batch must fail loudly, naming the
    empty observer."""
    net = _mlp()
    qat = Q.quantize_aware_symbol(net)
    rng = np.random.RandomState(0)
    args = {"fc1_weight": mx.nd.array(rng.randn(32, 16) * 0.1),
            "fc1_bias": mx.nd.zeros((32,)),
            "fc2_weight": mx.nd.array(rng.randn(4, 32) * 0.1),
            "fc2_bias": mx.nd.zeros((4,))}
    auxs = {k: mx.nd.zeros((1,)) for k in qat.list_auxiliary_states()}
    with pytest.raises(mx.base.MXNetError, match="empty"):
        Q.quantize_model_qat(qat, args, auxs)


def test_qat_dual_role_tensor_gets_both_fq_types():
    """A tensor consumed as one node's DATA and another's WEIGHT needs
    both fake-quant flavors: an EMA observer on the data edge and a
    dynamic fq on the weight edge — the cache must key on role, not just
    on the source tensor."""
    import json as _json

    d = mx.sym.Variable("data")
    w = mx.sym.Variable("shared")
    fca = mx.sym.FullyConnected(d, weight=w, num_hidden=16, no_bias=True,
                                name="fca")
    fcb = mx.sym.FullyConnected(w, num_hidden=4, no_bias=True, name="fcb")
    qat = Q.quantize_aware_symbol(mx.sym.Group([fca, fcb]))
    nodes = _json.loads(qat.tojson())["nodes"]
    by_name = {n["name"]: n for n in nodes}
    names = [n["name"] for n in nodes]

    def _input_op(consumer, idx):
        return nodes[by_name[consumer]["inputs"][idx][0]]["op"]

    # fcb reads `shared` as data -> EMA observer (with amax aux);
    # fca reads `shared` as weight -> dynamic fq; both must exist
    assert _input_op("fcb", 0) == "_contrib_fake_quant"
    assert _input_op("fca", 1) == "_contrib_fake_quant_dynamic"
    assert "shared_fq" in names and "shared_fqw" in names
    assert "shared_fq_amax" in qat.list_auxiliary_states()


def test_qat_export_num_bits_mismatch_raises():
    """quantize_symbol deploys a hard int8/127 grid; a graph finetuned at
    another width must refuse to export rather than silently change the
    quantization the training simulated."""
    net = _mlp()
    qat = Q.quantize_aware_symbol(net, num_bits=4)
    rng = np.random.RandomState(0)
    args = {"fc1_weight": mx.nd.array(rng.randn(32, 16) * 0.1),
            "fc1_bias": mx.nd.zeros((32,)),
            "fc2_weight": mx.nd.array(rng.randn(4, 32) * 0.1),
            "fc2_bias": mx.nd.zeros((4,))}
    auxs = {k: mx.nd.array([1.0]) for k in qat.list_auxiliary_states()}
    with pytest.raises(mx.base.MXNetError, match="num_bits=4"):
        Q.quantize_model_qat(qat, args, auxs)


def test_qat_export_missing_observer_warns(caplog):
    """Excluding a node at insertion but not at export leaves it with no
    observer: the export must warn (the node silently stays float)
    instead of skipping it without a trace."""
    import json as _json
    import logging

    net = _mlp()
    qat = Q.quantize_aware_symbol(net, excluded_sym_names=("fc2",))
    rng = np.random.RandomState(0)
    args = {"fc1_weight": mx.nd.array(rng.randn(32, 16) * 0.1),
            "fc1_bias": mx.nd.zeros((32,)),
            "fc2_weight": mx.nd.array(rng.randn(4, 32) * 0.1),
            "fc2_bias": mx.nd.zeros((4,))}
    auxs = {k: mx.nd.array([1.0]) for k in qat.list_auxiliary_states()}
    with caplog.at_level(logging.WARNING):
        qsym, _qa, _qx = Q.quantize_model_qat(qat, args, auxs)
    assert any("fc2" in r.message and "observer" in r.message
               for r in caplog.records), caplog.records
    ops = {n["name"]: n["op"] for n in _json.loads(qsym.tojson())["nodes"]}
    assert ops["fc2"] == "FullyConnected"  # stayed float
    assert ops["fc1"].startswith("_contrib_quantized")
