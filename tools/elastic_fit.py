"""``make elastic``: a seeded 2→4→2 PS-shard resize mid-fit, driven by
the watchdog→autoscaler loop, with parity checked against a run that
never resized.

Drives the elastic-scale plane end to end, on whatever device JAX
finds (the ``make`` target asks for ``JAX_PLATFORMS=cpu``):

1. a reference ``ShardedTrainer.fit(kvstore=)`` run against a *fixed*
   2-shard server group records the final parameters;
2. the elastic run starts on 2 live shards with 2 spares parked (the
   ``tools/launch.py --elastic-spares`` layout, addresses in
   ``MXNET_TPU_ELASTIC_SPARE_ADDRS``), then mid-epoch a synthetic
   ``queue_saturation`` spike makes the real
   :class:`~mxnet_tpu.observability.Watchdog` fire and the
   :class:`~mxnet_tpu.observability.Autoscaler` grow 2→4 through
   ``kv.resize()`` — a live two-phase cutover under training pushes —
   and one epoch later sustained idleness drains 4→2 the same way;
3. final parameters must match the reference run within tolerance
   (seqno dedup means no push is lost or double-applied across either
   cutover), the autoscaler must have taken exactly one scale_up and
   one scale_down, and the flight recorder must hold a bundle naming
   the triggering rule.

Exits non-zero on any miss.  Run:  python tools/elastic_fit.py
"""

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_TPU_METRICS", "1")

B, D = 8, 6


def _mlp(mx):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _fit(mx, kv, callback=None):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    rs = np.random.RandomState(3)
    it = NDArrayIter({"data": rs.randn(32, D).astype(np.float32)},
                     {"softmax_label": rs.randint(0, 8, (32,)).astype(
                         np.float32)}, batch_size=B)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tr = ShardedTrainer(_mlp(mx), mesh, data_shapes={"data": (B, D)},
                        label_shapes={"softmax_label": (B,)},
                        rescale_grad=1.0 / B)
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1,
                                      rescale_grad=1.0 / B, wd=0.0))
    (params, _, _), _ = tr.fit(it, num_epoch=2, seed=5, log_every=0,
                               kvstore=kv, batch_end_callback=callback)
    return params


def _make_kv(mx, ka, addrs):
    os.environ["MXNET_TPU_ASYNC_PS_ADDRS"] = ",".join(addrs)
    ka.reset_membership()
    kv = mx.kv.create("dist_async")
    assert kv._async is not None
    return kv


def main():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import kvstore_async as ka
    from mxnet_tpu import observability as obs
    from mxnet_tpu.observability import Autoscaler, Watchdog
    from mxnet_tpu.observability.watchdog import Rule

    flight_dir = tempfile.mkdtemp(prefix="mxtpu_elastic_flight_")
    os.environ["MXNET_TPU_FLIGHT_DIR"] = flight_dir
    os.environ["MXNET_TPU_PS_SECRET"] = "elastic"

    # -- reference: fixed 2-shard topology, no resize ever --------------
    ref = [ka.AsyncServer(secret="elastic", server_id=i).start()
           for i in range(2)]
    try:
        kv_ref = _make_kv(mx, ka, [s.address for s in ref])
        p_ref = _fit(mx, kv_ref)
        kv_ref._async.shutdown()
    finally:
        for s in ref:
            s.stop()

    # -- elastic: 2 live shards + 2 parked spares (the --elastic-spares
    # layout); the watchdog->autoscaler loop does ALL the resizing ------
    servers = [ka.AsyncServer(secret="elastic", server_id=i).start()
               for i in range(4)]
    live = [s.address for s in servers[:2]]
    os.environ["MXNET_TPU_ELASTIC_SPARE_ADDRS"] = ",".join(
        s.address for s in servers[2:])
    try:
        kv = _make_kv(mx, ka, live)
        sat = obs.gauge("serving_queue_saturation",
                        "Scheduler queue fill fraction",
                        ["model"]).labels("elastic_fit")
        dog = Watchdog([Rule(
            "queue_saturation", "serving_queue_saturation", stat="max",
            op=">=", threshold=0.9, severity="critical",
            description="synthetic load spike for the elastic drill")])
        cutovers = []

        def up(action):
            spares = os.environ["MXNET_TPU_ELASTIC_SPARE_ADDRS"].split(",")
            r = kv.resize(live + spares)
            cutovers.append(r["cutover_ms"])
            return r

        def down(action):
            r = kv.resize(live)
            cutovers.append(r["cutover_ms"])
            return r

        scaler = Autoscaler(dog, scale_up=up, scale_down=down,
                            size=lambda: len(kv._async._specs),
                            sustain_s=0.0, cooldown_s=0.0, idle_s=0.05,
                            min_size=2, max_size=4)
        taken = []
        state = {"grew": False, "shrunk": False}

        def drill(bep):
            # epoch 0 batch 2: spike -> sustained alert -> grow 2->4,
            # with the remaining batches pushed at the new striping
            if not state["grew"] and bep.epoch == 0 and bep.nbatch == 2:
                sat.set(1.0)
                act = scaler.evaluate()
                if not (act and act.action == "scale_up" and act.ok):
                    raise AssertionError(
                        "spike did not scale up: %r"
                        % (act and act.as_dict()))
                state["grew"] = True
                taken.append(act)
            # epoch 1 batch 2: load gone -> sustained idle -> drain 4->2
            elif (state["grew"] and not state["shrunk"]
                    and bep.epoch == 1 and bep.nbatch == 2):
                sat.set(0.0)
                deadline = time.time() + 10
                while time.time() < deadline:
                    act = scaler.evaluate()
                    if act is not None:
                        if not (act.action == "scale_down" and act.ok):
                            raise AssertionError("idle drained wrong: %r"
                                                 % act.as_dict())
                        state["shrunk"] = True
                        taken.append(act)
                        return
                    time.sleep(0.02)
                raise AssertionError("idleness never drained 4->2")

        p_el = _fit(mx, kv, callback=drill)
        kv._async.shutdown()
    finally:
        for s in servers:
            s.stop()

    failures = []
    if not (state["grew"] and state["shrunk"]):
        failures.append("scale cycle incomplete: %r" % state)
    if len(cutovers) != 2:
        failures.append("expected 2 cutovers, saw %r" % cutovers)

    # parity: every update landed exactly once across both cutovers
    worst = 0.0
    for n in sorted(p_ref):
        a, b = np.asarray(p_ref[n]), np.asarray(p_el[n])
        worst = max(worst, float(np.max(np.abs(a - b))) if a.size else 0.0)
        try:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                       err_msg=n)
        except AssertionError as e:
            failures.append("parity miss on %s: %s" % (n, e))

    # the flight record must name the rule that triggered scale-up
    bundles = sorted(d for d in os.listdir(flight_dir)
                     if d.startswith("flight_autoscale_action"))
    rules = []
    for d in bundles:
        with open(os.path.join(flight_dir, d, "manifest.json")) as f:
            rules.append(json.load(f)["extra"].get("rule"))
    if "queue_saturation" not in rules:
        failures.append("no flight bundle names the triggering rule "
                        "(bundles=%r rules=%r)" % (bundles, rules))

    actions = obs.REGISTRY.get("cluster_autoscale_actions_total")
    print("elastic fit: 2->4->2 resize mid-fit")
    print("  cutovers: %s ms" % ", ".join("%.2f" % c for c in cutovers))
    print("  autoscaler actions: %s"
          % ", ".join("%s(%s)" % (a.action, a.rule) for a in taken))
    print("  autoscale_actions_total: %d"
          % int(actions.total() if actions else 0))
    print("  parity vs fixed topology: max |delta| = %.3g" % worst)
    print("  flight bundles: %d (rules: %s)" % (len(bundles), rules))
    if failures:
        for f in failures:
            print("FAIL: %s" % f, file=sys.stderr)
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
