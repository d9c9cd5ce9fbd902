"""Inference throughput benchmark on synthetic data (parity: reference
``example/image-classification/benchmark_score.py``)."""

import argparse
import logging
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))  # repo root

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import models

logging.basicConfig(level=logging.INFO)


def _build_symbol(network, image_shape, num_layers, dtype):
    """One network-setup path shared by both scoring modes (host-loop and
    --device-loop must benchmark the identical configuration)."""
    kwargs = {}
    if num_layers:
        kwargs["num_layers"] = num_layers
    if network == "inception-v3":
        image_shape = (3, 299, 299)
    sym = models.get_symbol(network, num_classes=1000,
                            image_shape=image_shape, dtype=dtype, **kwargs)
    return sym, image_shape


def score(network, dev, batch_size, num_batches, image_shape=(3, 224, 224),
          num_layers=None, dtype="float32"):
    sym, image_shape = _build_symbol(network, image_shape, num_layers, dtype)
    data_shape = [("data", (batch_size,) + image_shape)]
    mod = mx.mod.Module(symbol=sym, context=dev)
    mod.bind(for_training=False, inputs_need_grad=False, data_shapes=data_shape)
    mod.init_params(initializer=mx.initializer.Xavier(magnitude=2.0))
    # device-resident synthetic batch: H2D once, not per iteration
    batch = mx.io.DataBatch(
        [mx.nd.array(np.random.uniform(-1, 1, (batch_size,) + image_shape),
                     ctx=dev)], [])
    def sync():
        # wait on the device without timing the (slow) full-logits host
        # transfer
        mod.get_outputs()[0]._data.block_until_ready()

    # warmup (compile)
    for _ in range(2):
        mod.forward(batch, is_train=False)
    sync()
    tic = time.time()
    for _ in range(num_batches):
        mod.forward(batch, is_train=False)
    sync()
    return num_batches * batch_size / (time.time() - tic)


def score_device_loop(network, dev, batch_size, num_batches,
                      image_shape=(3, 224, 224), num_layers=None,
                      dtype="float32"):
    """Pure-device inference throughput: ``num_batches`` forwards inside
    ONE jitted ``lax.fori_loop``, so per-batch host dispatch never enters
    the measurement.  This is the apples-to-apples number against the
    reference's local-PCIe GPUs (`benchmark_score.py`): per-call
    dispatch latency dominates any sub-2ms step in the host-loop
    ``score``.  Each iteration's input depends on the previous output (a
    1e-30-scaled logit perturbation), so XLA can neither hoist the
    forward out of the loop nor collapse iterations."""
    import jax
    import jax.numpy as jnp

    sym, image_shape = _build_symbol(network, image_shape, num_layers, dtype)
    ex = sym.simple_bind(dev, grad_req="null",
                         data=(batch_size,) + image_shape)
    for name, arr in ex.arg_dict.items():
        if name != "data" and not name.endswith("_label"):
            mx.initializer.Xavier(magnitude=2.0)(name, arr)
    params = {k: v._data for k, v in ex.arg_dict.items() if k != "data"}
    aux = {k: v._data for k, v in ex.aux_dict.items()}
    run = ex._run  # the executor's already-built graph function
    data = jnp.asarray(np.random.uniform(
        -1, 1, (batch_size,) + image_shape).astype(np.float32))
    key = jax.random.PRNGKey(0)

    @jax.jit
    def loop(params, aux, data):
        def body(i, carry):
            acc, d = carry
            args = dict(params)
            args["data"] = d.astype(data.dtype)
            outs, _ = run(args, aux, key, False)
            m = outs[0].astype(jnp.float32).ravel()[0]
            return (acc + m, d + m * 1e-30)
        acc, d = jax.lax.fori_loop(0, num_batches, body, (0.0, data))
        return acc

    np.asarray(loop(params, aux, data))  # compile + warm
    tic = time.time()
    np.asarray(loop(params, aux, data))  # D2H scalar fetch = true sync
    return num_batches * batch_size / (time.time() - tic)


def score_pipeline(network, dev, batch_size, num_batches,
                   image_shape=(3, 224, 224), num_layers=None,
                   dtype="float32"):
    """Serving-shaped device-loop throughput: ``num_batches`` DISTINCT
    batches stacked ``[N, B, ...]`` and scanned in ONE dispatch via
    ``Predictor.forward_pipeline`` — the trainer's ``pipeline_steps``
    applied to inference.  Unlike ``score_device_loop`` (whose synthetic
    chained input isolates pure device compute), this path measures what a
    batch-window serving deployment gets: real per-batch inputs, one H2D
    of the stacked window, one dispatch, stacked logits back."""
    from mxnet_tpu import predict as _predict

    sym, image_shape = _build_symbol(network, image_shape, num_layers, dtype)
    ex = sym.simple_bind(dev, grad_req="null",
                         data=(batch_size,) + image_shape)
    for name, arr in ex.arg_dict.items():
        if name != "data" and not name.endswith("_label"):
            mx.initializer.Xavier(magnitude=2.0)(name, arr)
    pred = _predict.Predictor(
        sym.tojson(),
        {"arg:" + k: v for k, v in ex.arg_dict.items() if k != "data"}
        | {"aux:" + k: v for k, v in ex.aux_dict.items()},
        ctx=dev, input_shapes={"data": (batch_size,) + image_shape})
    stacked = {"data": np.random.uniform(
        -1, 1, (num_batches, batch_size) + image_shape).astype(np.float32)}
    pred.forward_pipeline(stacked)  # compile + warm
    tic = time.time()
    outs = pred.forward_pipeline(stacked)
    np.asarray(outs[0]).ravel()[0]  # already host-side; keep the sync idiom
    return num_batches * batch_size / (time.time() - tic)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--network", type=str, default="all")
    parser.add_argument("--batch-size", type=int, default=0)
    parser.add_argument("--num-batches", type=int, default=10)
    parser.add_argument("--dtype", type=str, default="float32")
    parser.add_argument("--device-loop", action="store_true",
                        help="run all batches inside one jitted fori_loop "
                             "(excludes per-batch dispatch latency; "
                             "the apples-to-apples number vs local-PCIe "
                             "GPUs for sub-2ms steps)")
    parser.add_argument("--pipeline", action="store_true",
                        help="serving-shaped device loop: N distinct "
                             "batches stacked and scanned in one dispatch "
                             "(Predictor.forward_pipeline)")
    args = parser.parse_args()

    import jax
    dev = mx.tpu(0) if jax.default_backend() == "tpu" else mx.cpu()
    networks = (["alexnet", "vgg", "inception-bn", "inception-v3",
                 "resnet-50", "resnet-152"]
                if args.network == "all" else [args.network])
    batch_sizes = [args.batch_size] if args.batch_size else [1, 32, 64, 128]
    if args.device_loop and args.pipeline:
        parser.error("--device-loop and --pipeline are exclusive modes")
    fn = (score_pipeline if args.pipeline
          else score_device_loop if args.device_loop else score)
    for net in networks:
        logging.info("network: %s", net)
        for b in batch_sizes:
            speed = fn(net, dev, b, args.num_batches, dtype=args.dtype)
            logging.info("batch size %3d, dtype %s, images/sec: %f",
                         b, args.dtype, speed)
