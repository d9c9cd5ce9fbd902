"""Custom operators in Python (parity: reference ``example/numpy-ops/
custom_softmax.py`` — a CustomOp/CustomOpProp pair implementing softmax
with numpy, registered and used inside a Symbol graph).

    python examples/numpy_ops.py [--tpus 0]

NB: python callbacks lower to PJRT host send/recv.
"""

import argparse
import os
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

import mxnet_tpu as mx


class NumpySoftmax(mx.CustomOp):
    """Softmax + cross-entropy grad computed in numpy on the host
    (the async-safe callback path; reference custom-inl.h:43)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        e = np.exp(x - x.max(axis=1, keepdims=True))
        self.assign(out_data[0], req[0], mx.nd.array(
            e / e.sum(axis=1, keepdims=True)))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        label = in_data[1].asnumpy().astype(int)
        prob = out_data[0].asnumpy().copy()
        prob[np.arange(prob.shape[0]), label] -= 1.0
        self.assign(in_grad[0], req[0], mx.nd.array(prob / prob.shape[0]))


@mx.operator.register("numpy_softmax")
class NumpySoftmaxProp(mx.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["data", "label"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return [in_shape[0], (in_shape[0][0],)], [in_shape[0]], []

    def create_operator(self, ctx, shapes, dtypes):
        return NumpySoftmax()


def main():
    parser = argparse.ArgumentParser(description="CustomOp demo")
    parser.add_argument("--num-epochs", type=int, default=8)
    parser.add_argument("--tpus", type=str, default=None)
    args = parser.parse_args()

    # initializer + NDArrayIter shuffle draw from the global stream: pin it
    # so the accuracy gate is deterministic
    np.random.seed(1)
    rng = np.random.RandomState(0)
    centers = rng.randn(4, 8) * 3.0
    labels = rng.randint(0, 4, 400)
    data = (centers[labels] + rng.randn(400, 8)).astype(np.float32)
    it = mx.io.NDArrayIter(data, labels.astype(np.float32), batch_size=40,
                           shuffle=True)

    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="fc")
    net = mx.sym.Custom(net, mx.sym.Variable("softmax_label"),
                        op_type="numpy_softmax", name="softmax")
    mod = mx.mod.Module(net, context=mx.context.devices_from_arg(args.tpus))
    mod.fit(it, num_epoch=args.num_epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.3},
            initializer=mx.initializer.Xavier())
    acc = mod.score(mx.io.NDArrayIter(data, labels.astype(np.float32),
                                      batch_size=40), "acc")
    print("custom-op model accuracy: %s" % acc)
    assert acc[0][1] > 0.9, acc


if __name__ == "__main__":
    main()
