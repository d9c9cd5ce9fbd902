"""Cross-backend consistency sweep on the real chip — the reference's
GPU-consistency test tier (``tests/python/gpu/test_operator_gpu.py:242``:
run the same graph on every available implementation and cross-check
outputs AND gradients via ``check_consistency``), with cpu-vs-tpu as the
pair.  Run by ``tests/test_tpu_consistency.py`` in a subprocess WITHOUT
the conftest's CPU forcing (one process holds the chip: this one);
prints SKIP_NO_TPU and exits 0 where no chip is attached.

Tolerances: TPU fp32 matmuls/convs use reduced default precision
(~1e-2 relative vs the CPU backend), so MXU-path cases carry a looser
tol than VPU/elementwise cases.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import jax

if jax.default_backend() != "tpu":
    print("SKIP_NO_TPU (backend=%s)" % jax.default_backend())
    sys.exit(0)

import numpy as np

import mxnet_tpu as mx

np.random.seed(7)


def v(name="data"):
    return mx.sym.Variable(name)


MXU_TOL = 2e-2     # matmul/conv path: reduced-precision fp32 on the MXU
VPU_TOL = 1e-3     # elementwise/reduce path

CASES = [
    ("FullyConnected",
     mx.sym.FullyConnected(v(), num_hidden=32, name="fc"),
     {"data": (8, 64)}, MXU_TOL),
    ("Convolution",
     mx.sym.Convolution(v(), kernel=(3, 3), num_filter=16, pad=(1, 1),
                        name="c"),
     {"data": (2, 3, 16, 16)}, MXU_TOL),
    ("BatchNorm",
     mx.sym.BatchNorm(mx.sym.Convolution(v(), kernel=(3, 3), num_filter=8,
                                         name="c"), fix_gamma=False,
                      name="bn"),
     {"data": (2, 3, 12, 12)}, MXU_TOL),
    ("Pooling",
     mx.sym.Pooling(v(), kernel=(2, 2), stride=(2, 2), pool_type="max"),
     {"data": (2, 4, 12, 12)}, VPU_TOL),
    ("Activation+softmax",
     mx.sym.softmax(mx.sym.Activation(v(), act_type="tanh")),
     {"data": (4, 33)}, VPU_TOL),
    ("broadcast+reduce",
     mx.sym.sum(mx.sym.broadcast_mul(v(), mx.sym.Variable("b")), axis=1),
     {"data": (4, 5, 6), "b": (1, 5, 6)}, VPU_TOL),
    ("Embedding+take",
     mx.sym.Embedding(v(), input_dim=50, output_dim=16, name="emb"),
     {"data": (4, 7)}, VPU_TOL),
    ("LayerNorm",
     mx.sym.LayerNorm(v(), name="ln"),
     {"data": (4, 8, 32)}, VPU_TOL),
    ("MultiHeadAttention",
     mx.sym.MultiHeadAttention(v(), num_heads=2, causal=True, name="mha"),
     {"data": (2, 16, 32)}, MXU_TOL),
    ("transpose+slice",
     mx.sym.slice_axis(mx.sym.transpose(v(), axes=(0, 2, 1)), axis=2,
                       begin=1, end=5),
     {"data": (3, 6, 8)}, VPU_TOL),
    ("LeakyReLU+clip",
     mx.sym.clip(mx.sym.LeakyReLU(v(), act_type="leaky", slope=0.1),
                 a_min=-0.5, a_max=0.5),
     {"data": (4, 40)}, VPU_TOL),
    ("fused_lm_head",
     mx.sym._contrib_fused_lm_head(
         v(), mx.sym.Variable("w", shape=(40, 16)),
         mx.sym.Variable("softmax_label"), chunk=16, name="head"),
     {"data": (32, 16), "softmax_label": (32,)}, MXU_TOL),
    ("Deconvolution",
     mx.sym.Deconvolution(v(), kernel=(4, 4), num_filter=8, stride=(2, 2),
                          name="dc"),
     {"data": (2, 4, 8, 8)}, MXU_TOL),
    ("SequenceMask+Reverse",
     mx.sym.SequenceReverse(mx.sym.SequenceMask(
         v(), mx.sym.Variable("seqlen"), use_sequence_length=True,
         value=-1.0), mx.sym.Variable("seqlen"), use_sequence_length=True),
     {"data": (6, 3, 5), "seqlen": (3,)}, VPU_TOL),
    ("topk+sort",
     mx.sym.sort(mx.sym.topk(v(), k=3, axis=-1, ret_typ="value"), axis=-1),
     {"data": (5, 17)}, VPU_TOL),
    ("BilinearSampler",
     mx.sym.BilinearSampler(v(), mx.sym.GridGenerator(
         mx.sym.Variable("affine"), transform_type="affine",
         target_shape=(8, 8)), name="bs"),
     {"data": (2, 3, 8, 8), "affine": (2, 6)}, MXU_TOL),
    ("InstanceNorm+L2Norm",
     mx.sym.L2Normalization(mx.sym.InstanceNorm(v(), name="in_"),
                            mode="instance"),
     {"data": (3, 4, 6, 6)}, VPU_TOL),
    ("batch_dot+swapaxis",
     mx.sym.batch_dot(mx.sym.SwapAxis(v(), dim1=1, dim2=2),
                      mx.sym.Variable("rhs")),
     {"data": (4, 6, 5), "rhs": (4, 6, 7)}, MXU_TOL),
    # quantized compute tier: float in -> quantize -> int8 MXU op; int32
    # accumulation is exact on both backends so the tolerance is tight
    ("quantized_fc",
     mx.sym._contrib_quantized_fully_connected(
         *(lambda dq, wq: (dq[0], wq[0], dq[1], dq[2], wq[1], wq[2]))(
             mx.sym._contrib_quantize(
                 v(), mx.sym.Variable("dlo", shape=(1,)),
                 mx.sym.Variable("dhi", shape=(1,)), out_type="int8"),
             mx.sym._contrib_quantize(
                 mx.sym.Variable("w"), mx.sym.Variable("wlo", shape=(1,)),
                 mx.sym.Variable("whi", shape=(1,)), out_type="int8")),
         num_hidden=12),
     {"data": (8, 16), "w": (12, 16), "dlo": (1,), "dhi": (1,),
      "wlo": (1,), "whi": (1,)}, VPU_TOL, "null"),
]


# data inputs that must hold integer-valued floats: name -> (lo, hi)
INT_INPUTS = {"Embedding+take": {"data": (0, 50)},
              "fused_lm_head": {"softmax_label": (0, 40)},
              "SequenceMask+Reverse": {"seqlen": (1, 7)}}

# pinned non-integer inputs: near-identity affine keeps the sampling
# grid away from floor() cell boundaries, where the MXU's ~1e-2 fp32
# coordinate error would legitimately flip a cell on one backend only
# (a real discontinuity of the op, not an implementation divergence)
PINNED_INPUTS = {
    "BilinearSampler": {"affine": np.tile(
        np.array([0.91, 0.03, 0.013, 0.02, 0.87, -0.021], np.float32),
        (2, 1))},
    # valid (lo < hi) quantization ranges covering the uniform(-1,1) data
    "quantized_fc": {"dlo": np.array([-1.0], np.float32),
                     "dhi": np.array([1.0], np.float32),
                     "wlo": np.array([-1.0], np.float32),
                     "whi": np.array([1.0], np.float32)},
}


def trainer_step_case():
    """The fused ShardedTrainer step (momentum + traced Factor schedule +
    grad_accum) cross-checked cpu-vs-tpu: 3 updates on identical data
    must land the same parameters.  Extends the consistency tier from
    single graphs to the training stack itself.  Momentum-SGD, not Adam:
    Adam's variance normalization turns a near-zero gradient's backend
    sign flip into a full ±lr update divergence (a property of the
    optimizer under ~1e-2 fp32 backend skew, not an implementation
    difference), while SGD keeps parameter error proportional to
    gradient error; Adam's plumbing is pinned by exact-parity CPU tests
    (tests/test_trainer_optimizers.py)."""
    from jax.sharding import Mesh

    from mxnet_tpu.lr_scheduler import FactorScheduler
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    rs = np.random.RandomState(11)
    data = rs.randn(8, 16).astype(np.float32)
    labels = rs.randint(0, 4, (8,)).astype(np.float32)
    results = {}
    for dev in (jax.devices("cpu")[0], jax.devices()[0]):
        net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                    name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            net, num_hidden=4, name="fc2"), name="softmax")
        mesh = Mesh(np.array([dev]), ("data",))
        tr = ShardedTrainer(
            net, mesh, data_shapes={"data": (8, 16)},
            label_shapes={"softmax_label": (8,)},
            learning_rate=0.1, momentum=0.9,
            lr_scheduler=FactorScheduler(step=2, factor=0.5),
            rescale_grad=1.0 / 8, grad_accum=2)
        params, moms, aux = tr.init(seed=0)
        batch = tr.place_batch({"data": data, "softmax_label": labels})
        step = tr.step_fn()
        for i in range(3):
            _, params, moms, aux = step(params, moms, aux, batch,
                                        jax.random.PRNGKey(0))
        results[dev.platform] = {
            k: np.asarray(jax.device_get(v)) for k, v in params.items()}
    ref, got = results["cpu"], results["tpu"]
    for k in ref:
        err = np.abs(got[k] - ref[k])
        bound = MXU_TOL * np.abs(ref[k]) + 3e-3  # atol floor: bias values
        # start at zero, so tiny absolute skew is all relative error
        worst = float(np.max(err - bound))
        assert worst <= 0, "trainer param %r diverged (worst excess %.3e)" \
            % (k, worst)


def main():
    n_ok = 0
    for case in CASES:
        name, s, shapes, tol = case[:4]
        grad_req = case[4] if len(case) > 4 else "write"
        # pin only the integer-valued inputs; check_consistency shares
        # one draw of everything else across both contexts (and completes
        # a partial arg_params with random params)
        arg_params = {
            n: np.random.randint(lo, hi, shapes[n]).astype(np.float32)
            for n, (lo, hi) in INT_INPUTS.get(name, {}).items()}
        arg_params.update(PINNED_INPUTS.get(name, {}))
        mx.test_utils.check_consistency(
            s, [dict(ctx=mx.cpu(), **shapes), dict(ctx=mx.tpu(0), **shapes)],
            tol=tol, grad_req=grad_req, arg_params=arg_params or None)
        n_ok += 1
        print("ok %s" % name, flush=True)
    trainer_step_case()
    n_ok += 1
    print("ok trainer_step(momentum+schedule+accum)", flush=True)
    print("CONSISTENCY_OK %d" % n_ok)


if __name__ == "__main__":
    main()
