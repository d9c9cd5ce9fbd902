"""``make serve`` / ``python tools/serve.py``: stand up the serving tier.

Loads one or more models — ``save_checkpoint`` artifacts or exported
``.mxtpu`` bundles — behind the continuous-batching scheduler and the
v1 HTTP front-end (``mxnet_tpu/serving/``):

    # one replica, one checkpoint model
    python tools/serve.py --model mlp=ckpt/model:3 \
        --input-shape mlp.data=16x6 --port 8080

    # a .mxtpu deployment artifact (buckets frozen at export)
    python tools/serve.py --model mlp=ckpt/model.mxtpu --port 8080

    # 2-replica group with failover routing
    python tools/serve.py --model mlp=ckpt/model:3 \
        --input-shape mlp.data=16x6 --replicas 2

    # a language model by its configuration file (seeded weights): the
    # file names its family, benchmark/models/<family>.py builds it
    python tools/serve.py \
        --lm dots=benchmark/configs/dots-vlm1-ep16.json:7 --port 8080

``--smoke`` (the ``make serve`` target) is self-contained: it builds a
tiny in-memory MLP, serves it on a 2-replica group, drives the HTTP
API end to end — predict, models listing, readiness — kills one
replica mid-run to prove the failover path, and exits non-zero on any
miss.  No checkpoint; a few seconds with ``JAX_PLATFORMS=cpu``, which
the ``make`` target asks for.  Without it the server runs on whatever
device JAX finds.
"""

import argparse
import json
import os
import sys
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_TPU_METRICS", "1")


def _parse_models(specs):
    """``name=prefix:epoch`` or ``name=path.mxtpu`` -> [(name, src)]."""
    models = []
    for spec in specs:
        name, _, src = spec.partition("=")
        if not name or not src:
            raise SystemExit("--model wants name=prefix:epoch or "
                             "name=path.mxtpu, got %r" % spec)
        models.append((name, src))
    return models


def _parse_shapes(specs):
    """``model.input=16x6`` -> {model: {input: (16, 6)}}."""
    shapes = {}
    for spec in specs:
        key, _, dims = spec.partition("=")
        model, _, inp = key.partition(".")
        if not model or not inp or not dims:
            raise SystemExit("--input-shape wants model.input=16x6, "
                             "got %r" % spec)
        shapes.setdefault(model, {})[inp] = tuple(
            int(d) for d in dims.lower().split("x"))
    return shapes


def _backend_factory(name, src, shapes):
    """A zero-arg factory so every replica gets its own executors."""
    from mxnet_tpu import serving

    if src.endswith(".mxtpu"):
        return lambda: serving.ExportedBackend(src)
    prefix, _, epoch = src.rpartition(":")
    if not prefix:
        raise SystemExit("--model %s: checkpoint source wants "
                         "prefix:epoch, got %r" % (name, src))
    if name not in shapes:
        raise SystemExit("--model %s: checkpoint serving needs "
                         "--input-shape %s.<input>=<dims>" % (name, name))
    return lambda: serving.PredictorBackend.from_checkpoint(
        prefix, int(epoch), dict(shapes[name]))


def lm_backend(name, src):
    """``path/config.json[:seed]`` -> an ``LMBackend`` over seeded
    weights.  The configuration is a benchmark configuration file: its
    ``family`` names the module under ``benchmark/models/`` that makes
    the weights and hands ``LMBackend`` the model's definition, its
    ``deployment.serve`` sizes the cache."""
    from benchmark.spec import Spec

    path, _, seed = src.partition(":")
    with open(path) as f:
        cfg = json.load(f)
    family = Spec(ROOT).model(cfg["family"])
    return family.build_backend(
        cfg, cfg["deployment"]["serve"],
        family.make_weights(cfg, int(seed or 0)), name, lambda base: base)


def serve(args):
    from mxnet_tpu import serving

    shapes = _parse_shapes(args.input_shape)
    models = _parse_models(args.model)
    buckets = ([int(b) for b in args.buckets.split(",")]
               if args.buckets else None)
    group = None
    if args.lm:         # generation lanes behind /v1/generate
        target = serving.GenerationScheduler()
        for name, src in _parse_models(args.lm):
            target.register(name, lm_backend(name, src))
            target.warmup(name)
        models, route = args.lm, "/v1/generate"
    elif not models:
        raise SystemExit("nothing to serve: pass --model, --lm (or "
                         "--smoke)")
    elif args.replicas > 1:
        group = serving.ReplicaGroup(replicas=args.replicas)
        for name, src in models:
            group.register(name, _backend_factory(name, src, shapes),
                           buckets=buckets, max_queue=args.max_queue)
            group.warmup(name)
        target, route = serving.ServingRouter(group), "/v1/predict"
    else:
        target, route = serving.Scheduler(), "/v1/predict"
        for name, src in models:
            target.register(name, _backend_factory(name, src, shapes)(),
                            buckets=buckets, max_queue=args.max_queue)
            target.warmup(name)
    fe = serving.start_frontend(target, port=args.port, addr=args.addr)
    print("serving %d model(s) on %s (%d replica(s))"
          % (len(models), fe.url, args.replicas))
    print("  POST %s%s   GET %s/v1/models" % (fe.url, route, fe.url))
    try:
        import time

        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("draining...")
        (group or target).close()
        fe.close()
    return 0


def _post_json(url, payload, timeout=10):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.load(resp)
    except urllib.error.HTTPError as err:
        return err.code, json.load(err)


def smoke():
    """End-to-end smoke: tiny MLP, 2 replicas, HTTP round-trips, one
    replica killed mid-run — the brownout demo in miniature."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu import predict, serving

    feat = 6
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = net.infer_shape(data=(1, feat))
    rs = np.random.RandomState(0)
    params = {"arg:%s" % n: nd.array(rs.randn(*s).astype(np.float32)
                                     * 0.1)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n != "data" and not n.endswith("label")}

    def factory():
        return predict.Predictor(net.tojson(), dict(params),
                                 input_shapes={"data": (1, feat)})

    group = serving.ReplicaGroup(replicas=2, group="smoke")
    group.register("mlp", factory, buckets=[1, 2, 4])
    group.warmup("mlp")
    router = serving.ServingRouter(group)
    with serving.start_frontend(router) as fe:
        print("smoke front-end at %s" % fe.url)
        with urllib.request.urlopen(fe.url + "/v1/models",
                                    timeout=10) as resp:
            listing = json.load(resp)
        assert listing["models"][0]["name"] == "mlp", listing
        with urllib.request.urlopen(fe.url + "/readyz",
                                    timeout=10) as resp:
            assert json.load(resp)["status"] == "ready"
        status, out = _post_json(fe.url + "/v1/predict", {
            "model": "mlp", "inputs": {"data": [0.1] * feat}})
        assert status == 200 and len(out["outputs"][0]) == 8, out
        status, err = _post_json(fe.url + "/v1/predict", {
            "model": "nope", "inputs": {"data": [0.1] * feat}})
        assert status == 404 and err["type"] == "UnknownModelError", err
        # brownout: kill replica 0, the survivor keeps answering
        group.kill(0)
        status, out = _post_json(fe.url + "/v1/predict", {
            "model": "mlp", "inputs": {"data": [0.2] * feat}})
        assert status == 200, out
        assert group.membership()["epoch"] == 1
        # every request left a structured access-log event behind
        from mxnet_tpu import observability as obs

        access = obs.events("serving.access")
        assert access, "no serving.access event in the ops log"
        ok = [e for e in access if e.fields.get("status") == 200
              and e.fields.get("model") == "mlp"]
        assert ok and ok[-1].fields.get("latency_ms") is not None, [
            e.as_dict() for e in access]
        print("predict, shed, failover, and access-log paths all answered")
    group.close()
    print("serve smoke OK")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", action="append", default=[],
                    metavar="NAME=PREFIX:EPOCH|NAME=PATH.mxtpu",
                    help="model to serve (repeatable)")
    ap.add_argument("--lm", action="append", default=[],
                    metavar="NAME=CONFIG.json[:SEED]",
                    help="language model to generate from, by its "
                         "configuration file (repeatable; seeded weights)")
    ap.add_argument("--input-shape", action="append", default=[],
                    metavar="MODEL.INPUT=16x6",
                    help="batched input shape for checkpoint models "
                         "(repeatable; batch dim = default bucket)")
    ap.add_argument("--port", type=int, default=None,
                    help="front-end port (default "
                         "MXNET_TPU_SERVING_PORT or a free port)")
    ap.add_argument("--addr", default="127.0.0.1")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated batch buckets (default "
                         "MXNET_TPU_SERVING_BUCKETS)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="per-model queue bound (default "
                         "MXNET_TPU_SERVING_MAX_QUEUE)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving replicas (failover router when > 1)")
    ap.add_argument("--smoke", action="store_true",
                    help="self-contained end-to-end smoke, then exit")
    args = ap.parse_args(argv)
    from mxnet_tpu import compile_cache

    compile_cache.enable()
    if args.smoke:
        return smoke()
    return serve(args)


if __name__ == "__main__":
    sys.exit(main())
