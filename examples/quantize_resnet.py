"""Model-level int8 PTQ on ResNet (VERDICT r4 #2 — the chip-measured
int8 MODEL row; the op-level 71 Tops/s claim tested against real layer
shapes, rescale overhead, and memory traffic).

Two modes:

* gate (default): train a cifar-style ResNet-8 fp32 on synthetic
  blob-images, PTQ it with ``mxnet_tpu.contrib.quantization``
  (BN fold -> symmetric calibration -> int8 graph rewrite), and verify
  the int8 top-1 accuracy stays within a point of fp32.
* ``--benchmark``: ResNet-50 at ImageNet shape on the current device —
  int8 vs bf16 vs fp32 inference throughput (synthetic weights;
  throughput does not depend on weight values), one JSON line per
  dtype.  Run on the chip for ``tools/bench_table.py``'s int8 row.

    python examples/quantize_resnet.py            # accuracy gate
    python examples/quantize_resnet.py --benchmark --tpus 1
"""

import argparse
import json
import logging
import os
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))


def _want_tpu(argv):
    return any(a == "--tpus" and argv[i + 1] != "0"
               for i, a in enumerate(argv[:-1])) or \
        any(a.startswith("--tpus=") and a.split("=", 1)[1] != "0"
            for a in argv)


if __name__ == "__main__" and not _want_tpu(sys.argv[1:]):
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.contrib import quantization as Q  # noqa: E402
from mxnet_tpu.models import resnet  # noqa: E402


def make_data(rng, n, classes=4, hw=24):
    """Blob 'images': class = which quadrant carries the bright blob +
    a channel signature; learnable by a small convnet, not by a linear
    model on raw pixels (blob position jitters)."""
    x = rng.randn(n, 3, hw, hw).astype(np.float32) * 0.3
    y = rng.randint(0, classes, n)
    for i in range(n):
        q = y[i]
        r0 = (q // 2) * (hw // 2) + rng.randint(0, hw // 4)
        c0 = (q % 2) * (hw // 2) + rng.randint(0, hw // 4)
        ch = q % 3
        x[i, ch, r0:r0 + hw // 4, c0:c0 + hw // 4] += 2.0
    return x, y.astype(np.float32)


def _accuracy(sym, args, auxs, x, y, ctx, batch=64):
    exe = sym.simple_bind(ctx, grad_req="null",
                          data=(batch,) + x.shape[1:])
    for k, v in args.items():
        if k in exe.arg_dict:
            exe.arg_dict[k][:] = v.asnumpy()
    for k, v in auxs.items():
        if k in exe.aux_dict:
            exe.aux_dict[k][:] = v.asnumpy()
    hits = 0
    for s in range(0, len(x) - batch + 1, batch):
        exe.arg_dict["data"][:] = x[s:s + batch]
        out = exe.forward(is_train=False)[0].asnumpy()
        hits += (out.argmax(axis=1) == y[s:s + batch]).sum()
    return hits / float(len(x) // batch * batch)


def run(epochs=6, n_train=1024, seed=0, log=True):
    rng = np.random.RandomState(seed)
    xs, ys = make_data(rng, n_train)
    xv, yv = make_data(rng, max(n_train // 2, 256))
    ctx = mx.cpu()

    sym = resnet.get_symbol(num_classes=4, num_layers=8,
                            image_shape=(3, 24, 24))
    mod = mx.mod.Module(sym, context=ctx)
    it = mx.io.NDArrayIter(xs, ys, batch_size=64, shuffle=True, seed=1)
    mod.fit(it, num_epoch=epochs, optimizer="adam",
            optimizer_params={"learning_rate": 2e-3},
            initializer=mx.initializer.Xavier())
    args, auxs = mod.get_params()

    fp32_acc = _accuracy(sym, args, auxs, xv, yv, ctx)

    calib = [{"data": xs[s:s + 64]}
             for s in range(0, min(256, n_train), 64)]
    qsym, qargs, qauxs = Q.quantize_model(sym, args, auxs, calib, ctx)
    int8_acc = _accuracy(qsym, qargs, qauxs, xv, yv, ctx)
    if log:
        logging.info("fp32 acc=%.3f int8 acc=%.3f", fp32_acc, int8_acc)
    return {"fp32_acc": fp32_acc, "int8_acc": int8_acc}


def _throughput(sym, args, auxs, ctx, batch, image, batches=20):
    import jax
    import jax.numpy as jnp

    exe = sym.simple_bind(ctx, grad_req="null",
                          data=(batch, 3, image, image))
    # assign HOST numpy: an NDArray source re-binds the destination to
    # the source's device (uncommitted-follow semantics), silently
    # moving the whole graph to host CPU (measured: 8.8 img/s)
    for k, v in args.items():
        if k in exe.arg_dict:
            exe.arg_dict[k][:] = v.asnumpy()
    for k, v in auxs.items():
        if k in exe.aux_dict:
            exe.aux_dict[k][:] = v.asnumpy()
    exe.arg_dict["data"][:] = np.random.uniform(
        -1, 1, (batch, 3, image, image)).astype(np.float32)

    def sync(o):
        return np.asarray(jnp.ravel(o[0]._data)[0])

    sync(exe.forward(is_train=False))
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(batches):
            out = exe.forward(is_train=False)
        sync(out)
        best = max(best, batch * batches / (time.perf_counter() - t0))
    return best


def benchmark(batch=128, image=224, log=True):
    """ResNet-50 inference throughput: int8 PTQ graph vs bf16 vs fp32 on
    the current device.  NHWC (the TPU layout the fp rows also use)."""
    import jax

    ctx = mx.tpu(0) if jax.default_backend() == "tpu" else mx.cpu()
    rng = np.random.RandomState(0)

    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, image, image), layout="NHWC",
                            dtype="float32")
    # synthetic trained-looking params: shapes from inference, small
    # random values (throughput is value-independent)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(batch, 3, image, image))
    names = sym.list_arguments()
    args = {n: mx.nd.array(rng.randn(*s).astype(np.float32) * 0.05)
            for n, s in zip(names, arg_shapes) if n != "data"}
    auxs = {}
    for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
        auxs[n] = mx.nd.array(
            np.abs(rng.rand(*s)).astype(np.float32) + 0.5
            if n.endswith("var") else
            rng.randn(*s).astype(np.float32) * 0.1)

    # calibration at a small batch: per-tensor max-|x| doesn't need the
    # full bench batch, and the internals executor compiles much faster
    calib = [{"data": rng.uniform(-1, 1, (16, 3, image, image))
              .astype(np.float32)}]
    # out_dtype=bfloat16: the rescaled conv outputs (and the next
    # layer's quantize reads) move half the bytes — the model is
    # HBM-bound, so this is where int8 wins or loses (docs/PERF.md)
    qsym, qargs, qauxs = Q.quantize_model(sym, args, auxs, calib, ctx,
                                          out_dtype="bfloat16")

    rows = {}
    for tag, (s, a, au) in {
        "fp32": (sym, args, auxs),
        "int8": (qsym, qargs, qauxs),
    }.items():
        rows[tag] = _throughput(s, a, au, ctx, batch, image)
        if log:
            print(json.dumps({"metric": "resnet50_infer_%s" % tag,
                              "value": round(rows[tag], 1),
                              "unit": "img/s", "batch": batch}),
                  flush=True)
    # bf16 via the model's dtype knob (bench_table's fp rows use this)
    bsym = resnet.get_symbol(num_classes=1000, num_layers=50,
                             image_shape=(3, image, image), layout="NHWC",
                             dtype="bfloat16")
    rows["bf16"] = _throughput(bsym, args, auxs, ctx, batch, image)
    if log:
        print(json.dumps({"metric": "resnet50_infer_bf16",
                          "value": round(rows["bf16"], 1),
                          "unit": "img/s", "batch": batch}), flush=True)
    return rows


def main():
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--benchmark", action="store_true")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--tpus", default="0")
    args = ap.parse_args()
    if args.benchmark:
        benchmark(batch=args.batch)
        return
    stats = run(epochs=args.epochs)
    print("quantize_resnet: fp32=%.3f int8=%.3f"
          % (stats["fp32_acc"], stats["int8_acc"]))


if __name__ == "__main__":
    main()
