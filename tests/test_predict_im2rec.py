"""Predict API + im2rec tool tests (reference tiers:
``tests/python/predict/mxnet_predict_example.py`` and the im2rec tool flow
feeding ``ImageRecordIter``)."""

import os
import subprocess
import sys

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import predict


def _train_tiny(tmp_path):
    rng = np.random.RandomState(0)
    data = rng.randn(64, 6).astype(np.float32)
    labels = (data.sum(axis=1) > 0).astype(np.float32)
    it = mx.io.NDArrayIter(data, labels, batch_size=16)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(net, num_hidden=2, name="fc2"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier())
    prefix = str(tmp_path / "tiny")
    mod.save_checkpoint(prefix, 3)
    return prefix, data, mod


def test_predictor_matches_module(tmp_path):
    prefix, data, mod = _train_tiny(tmp_path)
    pred = predict.load(prefix, 3, ctx=mx.cpu(),
                        input_shapes={"data": (16, 6)})
    pred.forward(data=data[:16])
    out = pred.get_output(0)
    assert out.shape == (16, 2)

    mod2 = mx.mod.Module(*[mx.model.load_checkpoint(prefix, 3)[0]],
                         context=mx.cpu())
    mod2.bind(data_shapes=[("data", (16, 6))], for_training=False)
    mod2.set_params(*mx.model.load_checkpoint(prefix, 3)[1:])
    mod2.forward(mx.io.DataBatch([mx.nd.array(data[:16])]), is_train=False)
    want = mod2.get_outputs()[0].asnumpy()
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_predictor_reshape(tmp_path):
    prefix, data, _ = _train_tiny(tmp_path)
    pred = predict.load(prefix, 3, ctx=mx.cpu(),
                        input_shapes={"data": (16, 6)})
    # feeding a different batch size auto-reshapes (MXPredReshape path)
    pred.forward(data=data[:4])
    assert pred.get_output(0).shape == (4, 2)
    pred.forward(data=data[:16])
    assert pred.get_output(0).shape == (16, 2)


def test_im2rec_roundtrip(tmp_path):
    # write a tiny class-per-dir image tree, pack it, read it back
    rng = np.random.RandomState(0)
    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(4):
            arr = (rng.rand(10, 12, 3) * 255).astype(np.uint8)
            np.save(root / cls / ("%s%d.npy" % (cls, i)), arr)
    prefix = str(tmp_path / "ds")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "im2rec.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, tool, prefix, str(root), "--list",
                    "--recursive"], check=True, env=env, timeout=120)
    assert os.path.exists(prefix + ".lst")
    subprocess.run([sys.executable, tool, prefix + ".lst", str(root),
                    "--encoding", ".npy"], check=True, env=env,
                   timeout=120)
    assert os.path.exists(prefix + ".rec") and os.path.exists(prefix + ".idx")

    from mxnet_tpu import recordio

    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    labels = set()
    for k in rec.keys:
        header, img = recordio.unpack_img(rec.read_idx(k))
        assert img.shape == (10, 12, 3)
        labels.add(float(header.label))
    rec.close()
    assert labels == {0.0, 1.0}
    assert len(rec.keys) == 8

    # and the packed set feeds ImageRecordIter
    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               path_imgidx=prefix + ".idx",
                               data_shape=(3, 10, 12), batch_size=4)
    batch = next(iter(it))
    assert batch.data[0].shape == (4, 3, 10, 12)


def test_export_model_roundtrip(tmp_path):
    # amalgamation-analog: StableHLO artifact serves without the Module stack
    from mxnet_tpu import deploy

    prefix, data, mod = _train_tiny(tmp_path)
    path = deploy.export_model(prefix, 3, input_shapes={"data": (8, 6)})
    assert path.endswith("-export.mxtpu") and os.path.exists(path)
    model = deploy.load_exported(path)
    out = model(data=data[:8])
    assert out[0].shape == (8, 2)

    pred = predict.load(prefix, 3, ctx=mx.cpu(),
                        input_shapes={"data": (8, 6)})
    pred.forward(data=data[:8])
    np.testing.assert_allclose(out[0], pred.get_output(0),
                               rtol=1e-5, atol=1e-6)

    # unbaked variant: params travel beside the graph
    path2 = deploy.export_model(prefix, 3, input_shapes={"data": (8, 6)},
                                bake_params=False)
    model2 = deploy.load_exported(path2)
    np.testing.assert_allclose(model2(data=data[:8])[0], out[0],
                               rtol=1e-5, atol=1e-6)
