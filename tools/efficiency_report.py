"""``make efficiency``: run a short instrumented fit and print the
compute-efficiency books — per-cache HLO cost analysis (FLOPs, bytes,
arithmetic intensity, memory footprint), the model-FLOPs/MFU summary,
and the goodput ledger.

Drives the efficiency accounting plane end to end on whatever device
JAX finds (the ``make`` target asks for ``JAX_PLATFORMS=cpu``): a
pipelined ``ShardedTrainer.fit`` records compile cost analysis for
every jit cache (``trainer_compile_flops{cache}``), derives
``trainer_step_model_flops`` / ``model_flops_utilization`` from the
compiled program, and closes a goodput ledger over the fit wall.  Exits
non-zero if no compile FLOPs were accounted, no train-step model-FLOPs
figure was derived, or the goodput books fail the 5% reconciliation
gate (productive + every badput cause must match
``fit_wall_seconds_total`` — the same falsifiability contract tier-1
enforces).

Run:  python tools/efficiency_report.py
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_TPU_METRICS", "1")


def main():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import mxnet_tpu as mx
    from mxnet_tpu import observability as obs
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(net, num_hidden=8, name="fc2"),
        name="softmax")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tr = ShardedTrainer(net, mesh, data_shapes={"data": (8, 6)},
                        label_shapes={"softmax_label": (8,)},
                        momentum=0.9, rescale_grad=1.0 / 8,
                        pipeline_steps=2)
    rs = np.random.RandomState(0)
    # 10 optimizer steps: 5 full flushes of 2
    it = NDArrayIter(rs.randn(80, 6).astype(np.float32),
                     rs.randint(0, 8, (80,)).astype(np.float32),
                     batch_size=8)
    tr.fit(it, num_epoch=1, seed=0)

    print("HLO cost accounting (per jit cache):")
    print(obs.format_efficiency())
    print()
    print("goodput ledger:")
    print(obs.format_goodput())

    rows, _ = obs.efficiency_table()
    if not rows:
        print("FAIL: no compile cost analysis was accounted",
              file=sys.stderr)
        return 1
    if obs.model_flops_per_step() is None:
        print("FAIL: no train-step model-FLOPs figure was derived",
              file=sys.stderr)
        return 1

    ok, wall, accounted = obs.goodput_reconciles(tol=0.05)
    drift = abs(accounted - wall) / wall if wall else 1.0
    print("goodput books vs fit wall: %.2f%% drift" % (100 * drift))
    if not ok:
        print("FAIL: goodput books off by more than 5%% "
              "(wall=%.4fs accounted=%.4fs)" % (wall, accounted),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
