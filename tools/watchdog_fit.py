"""``make watchdog``: run a short instrumented fit, print the step-time
attribution table, and evaluate the default SLO watchdog rules.

Drives the performance-observability plane end to end on whatever
device JAX finds (the ``make`` target asks for ``JAX_PLATFORMS=cpu``):
a pipelined ``ShardedTrainer.fit`` fills the attribution
histograms (``trainer_step_phase_seconds``) and compile-accounting
counters, then the attribution books are checked against the wall-clock
step histogram — phases + the ``unattributed`` residual must reconcile
with ``trainer_step_seconds`` within 5% — and a default-rules
:class:`~mxnet_tpu.observability.Watchdog` runs two evaluation passes
over the live registry, printing whatever fires (a clean local run
fires nothing).  Exits non-zero if the books don't balance, no compile
was accounted, or no attribution was recorded.

Run:  python tools/watchdog_fit.py
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

    print("step-time attribution:")
    print(obs.format_attribution())

    # the falsifiability contract: phase sums + residual == wall sum
    phase = obs.REGISTRY.get("trainer_step_phase_seconds")
    wall = obs.REGISTRY.get("trainer_step_seconds")
    covered = sum(c.sum for c in phase._children.values())
    wall_sum = wall._default.sum
    drift = abs(covered - wall_sum) / wall_sum if wall_sum else 1.0
    print("attribution drift vs wall: %.2f%%" % (100 * drift))
    if drift > 0.05:
        print("FAIL: attribution books off by more than 5%",
              file=sys.stderr)
        return 1

    compiles = obs.REGISTRY.get("trainer_compiles_total")
    n_compiles = int(compiles.total()) if compiles else 0
    print("compiles accounted: %d" % n_compiles)
    if not n_compiles:
        print("FAIL: no jit compile was accounted", file=sys.stderr)
        return 1

    wd = obs.Watchdog(obs.default_rules())
    for _ in range(2):  # two passes so window/baseline rules get samples
        wd.evaluate()
    firing = wd.firing()
    print("watchdog: %d rule(s), %d firing" % (len(wd.rules), len(firing)))
    for alert in firing:
        print("  ALERT %s" % alert.as_dict())
    return 0


if __name__ == "__main__":
    sys.exit(main())
