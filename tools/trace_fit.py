"""``make trace``: run a short pipelined fit with tracing on and
validate the emitted chrome://tracing JSON.

Drives the full observability path end to end on whatever device JAX
finds (the ``make`` target asks for ``JAX_PLATFORMS=cpu``): a
5-step ``ShardedTrainer.fit`` (pipeline_steps=2, so the prefetch feeder
and engine IO lane are load-bearing) under ``profiler_set_state('run')``,
then ``dump_profile()`` and a JSON re-load of the merged trace.  Exits
non-zero if the trace fails to parse, has no span events, or lacks the
cross-thread engine children the span propagation exists to produce.

Run:  python tools/trace_fit.py [out_dir]      (default: ./trace_output)
Open the printed ``trace.json`` at https://ui.perfetto.dev.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import mxnet_tpu as mx
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    out_dir = sys.argv[1] if len(sys.argv) > 1 else "trace_output"

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
    # 5 optimizer steps: 2 flushes of 2 + the odd tail flush
    it = NDArrayIter(rs.randn(40, 6).astype(np.float32),
                     rs.randint(0, 8, (40,)).astype(np.float32),
                     batch_size=8)

    mx.profiler.profiler_set_config(filename=os.path.join(out_dir, "x"))
    mx.profiler.profiler_set_state("run")
    tr.fit(it, num_epoch=1, seed=0)
    path = mx.profiler.dump_profile()

    with open(path, encoding="utf-8") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    spans = [e for e in events if e.get("ph") == "X"]
    engine_children = [
        e for e in spans
        if e.get("cat") == "engine" and e.get("args", {}).get("parent")]
    print("trace: %d events (%d spans, %d cross-thread engine children) "
          "-> %s" % (len(events), len(spans), len(engine_children), path))
    if not spans:
        print("FAIL: no span events recorded", file=sys.stderr)
        return 1
    if not engine_children:
        print("FAIL: no engine spans parented across threads",
              file=sys.stderr)
        return 1
    print("metrics snapshot:\n" + "\n".join(
        line for line in mx.observability.dump_metrics().splitlines()
        if line.startswith(("trainer_steps_total", "prefetch_chunks_total",
                            "engine_push_total"))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
