"""Model zoo smoke tests: shape inference + a forward pass on small inputs
(the reference exercises its symbols via tests/python/train and
benchmark_score.py; here shape-level checks keep CI fast)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models


@pytest.mark.parametrize("net,shape", [
    ("mlp", (2, 1, 28, 28)),
    ("lenet", (2, 1, 28, 28)),
])
def test_small_models_forward(net, shape):
    sym = models.get_symbol(net, num_classes=10)
    exe = sym.simple_bind(ctx=mx.cpu(), data=shape, softmax_label=(shape[0],))
    exe.arg_dict["data"][:] = np.random.uniform(size=shape).astype(np.float32)
    out = exe.forward(is_train=False)[0]
    assert out.shape == (shape[0], 10)
    np.testing.assert_allclose(out.asnumpy().sum(axis=1), 1.0, rtol=1e-4)


@pytest.mark.parametrize("net", ["resnet-18", "resnet-50", "resnext"])
def test_resnet_shapes(net):
    sym = models.get_symbol(net, num_classes=1000)
    arg_shapes, out_shapes, aux_shapes = sym.infer_shape(
        data=(2, 3, 224, 224), softmax_label=(2,))
    assert out_shapes[0] == (2, 1000)


@pytest.mark.parametrize("net", ["alexnet", "vgg", "googlenet",
                                 "inception-bn", "inception-v3",
                                 "inception-resnet-v2"])
def test_big_convnets_infer(net):
    shape = ((2, 3, 299, 299) if net in ("inception-v3",
                                         "inception-resnet-v2")
             else (2, 3, 224, 224))
    sym = models.get_symbol(net, num_classes=1000)
    arg_shapes, out_shapes, aux_shapes = sym.infer_shape(
        data=shape, softmax_label=(2,))
    assert out_shapes[0] == (2, 1000)


def test_resnet_cifar_forward():
    sym = models.get_symbol("resnet", num_classes=10, num_layers=8,
                            image_shape=(3, 28, 28))
    exe = sym.simple_bind(ctx=mx.cpu(), data=(2, 3, 28, 28),
                          softmax_label=(2,))
    exe.arg_dict["data"][:] = np.random.uniform(size=(2, 3, 28, 28)).astype(np.float32)
    # init BN gammas to 1 so the forward is non-degenerate
    for k, v in exe.arg_dict.items():
        if k.endswith("_gamma"):
            v[:] = 1.0
    out = exe.forward(is_train=False)[0]
    assert out.shape == (2, 10)


def test_resnet_bf16():
    sym = models.get_symbol("resnet", num_classes=10, num_layers=8,
                            image_shape=(3, 28, 28), dtype="bfloat16")
    exe = sym.simple_bind(ctx=mx.cpu(), data=(2, 3, 28, 28),
                          softmax_label=(2,))
    out = exe.forward(is_train=False)[0]
    assert out.shape == (2, 10)
    assert str(out.dtype) == "float32"  # loss head cast back


def test_lstm_lm_forward():
    from mxnet_tpu.models import lstm
    s = lstm.get_symbol(num_classes=50, seq_len=7, num_embed=16,
                        num_hidden=16, num_layers=2)
    exe = s.simple_bind(ctx=mx.cpu(), data=(4, 7), softmax_label=(4, 7),
                        type_dict={"data": "int32"})
    exe.arg_dict["data"][:] = np.random.randint(0, 50, size=(4, 7))
    out = exe.forward(is_train=False)[0]
    assert out.shape == (4 * 7, 50)


def test_resnet_s2d_stem_exact_equivalence():
    """stem='s2d' (space-to-depth conv0) is numerically EXACT vs the
    standard 7x7/s2 stem once conv0_weight is mapped with
    convert_stem_to_s2d — whole-network forward parity."""
    import numpy as np

    from mxnet_tpu.models import resnet

    shape = (2, 3, 64, 64)
    std = resnet.get_symbol(num_classes=5, num_layers=18,
                            image_shape=(3, 64, 64), layout="NHWC")
    s2d = resnet.get_symbol(num_classes=5, num_layers=18,
                            image_shape=(3, 64, 64), layout="NHWC",
                            stem="s2d")
    ex1 = std.simple_bind(mx.cpu(), data=shape, grad_req="null")
    np.random.seed(0)
    for name, arr in ex1.arg_dict.items():
        if name != "data":
            arr[:] = np.random.randn(*arr.shape).astype(np.float32) * 0.1
    args2 = resnet.convert_stem_to_s2d(
        {k: v for k, v in ex1.arg_dict.items() if k != "data"})
    ex2 = s2d.simple_bind(mx.cpu(), data=shape, grad_req="null")
    for name, arr in ex2.arg_dict.items():
        if name != "data":
            arr[:] = args2[name].asnumpy()
    x = np.random.randn(*shape).astype(np.float32)
    ex1.arg_dict["data"][:] = x
    ex2.arg_dict["data"][:] = x
    o1 = ex1.forward(is_train=False)[0].asnumpy()
    o2 = ex2.forward(is_train=False)[0].asnumpy()
    np.testing.assert_allclose(o2, o1, rtol=1e-4, atol=1e-5)


def test_resnet_s2d_stem_backward_parity():
    """Gradients w.r.t. the input match between stems (the transform is a
    linear reparameterization of conv0, so d/d(data) is identical)."""
    import numpy as np

    from mxnet_tpu.models import resnet

    shape = (2, 3, 64, 64)
    kw = dict(num_classes=3, num_layers=18, image_shape=(3, 64, 64),
              layout="NHWC")
    std = resnet.get_symbol(**kw)
    s2d = resnet.get_symbol(stem="s2d", **kw)
    ex1 = std.simple_bind(mx.cpu(), data=shape,
                          softmax_label=(2,), grad_req="write")
    np.random.seed(3)
    for name, arr in ex1.arg_dict.items():
        if name not in ("data", "softmax_label"):
            arr[:] = np.random.randn(*arr.shape).astype(np.float32) * 0.1
    args2 = resnet.convert_stem_to_s2d(
        {k: v for k, v in ex1.arg_dict.items()
         if k not in ("data", "softmax_label")})
    ex2 = s2d.simple_bind(mx.cpu(), data=shape,
                          softmax_label=(2,), grad_req="write")
    for name, arr in ex2.arg_dict.items():
        if name not in ("data", "softmax_label"):
            arr[:] = args2[name].asnumpy()
    x = np.random.randn(*shape).astype(np.float32)
    y = np.array([0.0, 2.0], np.float32)
    for ex in (ex1, ex2):
        ex.arg_dict["data"][:] = x
        ex.arg_dict["softmax_label"][:] = y
        ex.forward(is_train=True)
        ex.backward()
    # deeper-layer weight grads are stem-independent
    for k in ("fc1_weight", "stage1_unit1_conv1_weight"):
        np.testing.assert_allclose(ex2.grad_dict[k].asnumpy(),
                                   ex1.grad_dict[k].asnumpy(),
                                   rtol=1e-3, atol=1e-5)
    # conv0 grads agree on the embedded 7x7 support; the zero-padded
    # kernel slots are EXTRA trainable parameters in the s2d layout (they
    # legitimately receive their own gradients)
    g1 = {"conv0_weight": mx.nd.array(ex1.grad_dict["conv0_weight"].asnumpy())}
    g1m = resnet.convert_stem_to_s2d(g1)["conv0_weight"].asnumpy()
    ones = {"conv0_weight": mx.nd.array(
        np.ones_like(ex1.grad_dict["conv0_weight"].asnumpy()))}
    support = resnet.convert_stem_to_s2d(ones)["conv0_weight"] \
        .asnumpy().astype(bool)
    g2 = ex2.grad_dict["conv0_weight"].asnumpy()
    np.testing.assert_allclose(g2[support], g1m[support], rtol=1e-3,
                               atol=1e-5)


def test_benchmark_score_device_loop_smoke():
    """--device-loop scoring (all batches in one jitted fori_loop; the
    dispatch-free methodology of docs/PERF.md) runs end to end and
    produces a positive throughput on a tiny net."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable,
         os.path.join(repo, "examples", "image_classification",
                      "benchmark_score.py"),
         "--network", "alexnet", "--batch-size", "2", "--num-batches", "3",
         "--device-loop"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout + r.stderr
    line = [l for l in r.stderr.splitlines() + r.stdout.splitlines()
            if "images/sec" in l]
    assert line, (r.stdout, r.stderr)
    assert float(line[0].rsplit(" ", 1)[1]) > 0


def test_transformer_fused_ce_head_matches_softmax_grads():
    """transformer.get_symbol(head='fused_ce') trains through
    ShardedTrainer with IDENTICAL parameter updates to the softmax head
    (same math, chunked; the softmax head's unused pred_bias aside) —
    the long-context configuration that never materializes [T, vocab]
    logits."""
    import jax
    from jax.sharding import Mesh

    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    batch, seq, vocab = 2, 32, 29
    rng = np.random.RandomState(0)
    data = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    label = rng.randint(0, vocab, (batch, seq)).astype(np.float32)
    results = {}
    for head in ("softmax", "fused_ce"):
        sym = transformer.get_symbol(
            num_classes=vocab, seq_len=seq, num_embed=16, num_heads=2,
            num_layers=2, head=head, ce_chunk=16)
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "seq"))
        tr = ShardedTrainer(
            sym, mesh, data_shapes={"data": (batch, seq)},
            label_shapes={"softmax_label": (batch, seq)},
            type_dict={"data": "int32"}, learning_rate=0.2, momentum=0.9,
            rescale_grad=1.0 / (batch * seq))
        params, moms, aux = tr.init(seed=0)
        if head == "softmax":
            # zero the bias the fused head lacks so updates can align
            params["pred_bias"] = params["pred_bias"] * 0.0
        arrays = tr.place_batch({"data": data, "softmax_label": label})
        step = tr.step_fn()
        # ONE step: after it the softmax head's pred_bias becomes nonzero
        # and the heads legitimately diverge from step 2 on
        outs, params, moms, aux = step(params, moms, aux, arrays,
                                       jax.random.PRNGKey(0))
        results[head] = {k: np.asarray(jax.device_get(v))
                         for k, v in params.items() if k != "pred_bias"}
    for k in results["fused_ce"]:
        np.testing.assert_allclose(
            results["softmax"][k], results["fused_ce"][k],
            rtol=1e-3, atol=1e-4,
            err_msg="param %r diverges between heads" % k)


def test_transformer_moe_ffn_trains():
    """ffn='moe': MoELayer FFNs + grouped aux load-balancing loss.  One
    ShardedTrainer step must run, move the expert weights, and emit a
    finite aux loss; on an expert-axis-less mesh the indexed dispatch
    path executes (the single-chip MoE bench configuration)."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    B, S, d = 2, 16, 32
    sym = transformer.get_symbol(num_classes=50, seq_len=S, num_embed=d,
                                 num_heads=2, num_layers=2, ffn="moe",
                                 num_experts=4, moe_top_k=2)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tr = ShardedTrainer(sym, mesh, data_shapes={"data": (B, S)},
                        label_shapes={"softmax_label": (B, S)},
                        type_dict={"data": "int32"}, learning_rate=0.1,
                        rescale_grad=1.0 / (B * S))
    params, moms, aux = tr.init(seed=0)
    rs = np.random.RandomState(0)
    batch = tr.place_batch({
        "data": rs.randint(0, 50, (B, S)).astype(np.int32),
        "softmax_label": rs.randint(0, 50, (B, S)).astype(np.float32)})
    w1_before = np.asarray(params["l0_moe_w1_weight"]).copy()
    step = tr.step_fn()
    outs, params, moms, aux = step(params, moms, aux, batch,
                                   jax.random.PRNGKey(0))
    # outputs: softmax probs + the MakeLoss'd aux loss (finite scalar-ish)
    assert np.all(np.isfinite(np.asarray(outs[-1])))
    assert not np.allclose(np.asarray(params["l0_moe_w1_weight"]),
                           w1_before)
