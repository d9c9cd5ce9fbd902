"""``make continuous``: the continuous-training loop end to end —
stream fit -> mid-fit kill -> bitwise resume -> checkpoint -> gate ->
hot-swap under live traffic -> seeded regression -> automatic rollback.

Drives all three tentpole pieces on whatever device JAX finds (the
``make`` target asks for ``JAX_PLATFORMS=cpu``) and asserts the
acceptance contract:

1. **Bitwise mid-epoch resume**: a ``StreamDataIter`` fit killed in the
   middle of epoch 1 resumes with ``resume="auto"`` and lands on
   final parameters bitwise-equal to the uninterrupted run — the
   stream cursor and shuffle RNG ride in the fit-meta sidecar.
2. **Attribution**: the streamed fit (background decode on the
   pipelined prefetch feeder) books a smaller ``data_wait`` share of
   wall time than the in-memory ``NDArrayIter`` baseline on the
   synchronous path — the stall the PR-6 books could only name is
   actually overlapped away.
3. **Gated deploy + rollback**: ``fit_stream`` drops a checkpoint,
   :class:`~mxnet_tpu.deployd.DeployDaemon` gates and hot-swaps it
   onto a 2-replica group while a client thread hammers the router —
   zero accepted requests dropped — then a seeded chaos burn
   (``serving.admit`` delay + 1 ms deadlines) fires the availability
   fast-burn rule inside probation: exactly ONE rollback, emitted as a
   ``deploy.rollback`` ops event plus a flight bundle naming the rule,
   after which serving answers from the previous model.

Exits non-zero on any miss.  Run:  python tools/continuous_fit.py
"""

import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_TPU_METRICS", "1")

B, D, C = 8, 6, 8


class _Kill(RuntimeError):
    pass


def _mlp(mx, hidden=16, depth=1):
    net = mx.sym.Variable("data")
    for i in range(depth):
        net = mx.sym.FullyConnected(net, num_hidden=hidden,
                                    name="fc%d" % (i + 1))
        net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=C, name="out")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _trainer(mx, batch, dim, hidden=16, depth=1, pipeline_steps=1):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from mxnet_tpu.parallel.trainer import ShardedTrainer

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    return ShardedTrainer(
        _mlp(mx, hidden, depth), mesh,
        data_shapes={"data": (batch, dim)},
        label_shapes={"softmax_label": (batch,)},
        optimizer="sgd",
        optimizer_params={"lr": 0.1, "rescale_grad": 1.0 / batch},
        pipeline_steps=pipeline_steps)


def _bitwise_resume(mx, failures):
    """Phase 1: kill the streamed fit mid-epoch-1, resume, compare
    bitwise against the uninterrupted run."""
    import numpy as np

    from mxnet_tpu import stream

    work = tempfile.mkdtemp(prefix="mxtpu_continuous_")
    rng = np.random.RandomState(0)
    files = []
    for i in range(2):
        f = os.path.join(work, "part-%d.rec" % i)
        stream.write_ndarray_records(
            f, rng.randn(40, D).astype(np.float32),
            (np.arange(40) % C).astype(np.float32))
        files.append(f)

    def make_it():
        return stream.StreamDataIter(files, (D,), B, seed=7)

    ck_ref = os.path.join(work, "ref")
    (p_ref, _, _), _ = _trainer(mx, B, D).fit(
        make_it(), num_epoch=2, seed=5, log_every=0,
        checkpoint_dir=ck_ref, checkpoint_every=4)

    ck = os.path.join(work, "killed")

    def killer(bep):
        if bep.epoch == 1 and bep.nbatch == 3:
            raise _Kill("mid-epoch kill")

    killed_at = None
    try:
        _trainer(mx, B, D).fit(
            make_it(), num_epoch=2, seed=5, log_every=0,
            checkpoint_dir=ck, checkpoint_every=4,
            batch_end_callback=killer)
    except _Kill:
        killed_at = "epoch 1, batch 3"
    if killed_at is None:
        failures.append("the mid-epoch kill never fired")
        return
    (p_res, _, _), _ = _trainer(mx, B, D).fit(
        make_it(), num_epoch=2, seed=5, log_every=0,
        checkpoint_dir=ck, checkpoint_every=4, resume="auto")
    exact = all(np.array_equal(np.asarray(p_ref[n]), np.asarray(p_res[n]))
                for n in p_ref)
    print("continuous fit: killed at %s, resumed from sidecar" % killed_at)
    print("  bitwise parity vs uninterrupted run: %s" % exact)
    if not exact:
        failures.append("mid-epoch resume is not bitwise")


def _data_wait(mx, failures):
    """Phase 2: data_wait share of wall — streamed fit on the pipelined
    prefetch feeder vs the in-memory NDArrayIter baseline."""
    import numpy as np

    from mxnet_tpu import observability as obs
    from mxnet_tpu import stream
    from mxnet_tpu.io import NDArrayIter

    batch, dim, hidden = 32, 256, 1024
    n = 48 * batch
    rng = np.random.RandomState(1)
    data = rng.randn(n, dim).astype(np.float32)
    labels = (np.arange(n) % C).astype(np.float32)
    rec = os.path.join(tempfile.mkdtemp(prefix="mxtpu_continuous_"),
                       "train.rec")
    stream.write_ndarray_records(rec, data, labels)

    def wait_pct(tr, it):
        fam = obs.REGISTRY.get("badput_seconds_total")
        before = fam.labels("data_wait").value if fam else 0.0
        t0 = time.monotonic()
        tr.fit(it, num_epoch=2, seed=5, log_every=0)
        wall = time.monotonic() - t0
        fam = obs.REGISTRY.get("badput_seconds_total")
        after = fam.labels("data_wait").value if fam else 0.0
        return 100.0 * (after - before) / wall

    base = wait_pct(
        _trainer(mx, batch, dim, hidden, depth=2),
        NDArrayIter({"data": data}, {"softmax_label": labels},
                    batch_size=batch))
    streamed = wait_pct(
        _trainer(mx, batch, dim, hidden, depth=2, pipeline_steps=4),
        stream.StreamDataIter([rec], (dim,), batch, seed=7))
    print("  data_wait: streamed %.2f%% vs in-memory baseline %.2f%%"
          % (streamed, base))
    if not streamed < base:
        failures.append(
            "streamed fit did not reduce data_wait (%.2f%% vs baseline "
            "%.2f%%)" % (streamed, base))


def _deploy_cycle(mx, flight_dir, failures):
    """Phase 3: fit_stream -> gate -> swap under traffic -> seeded
    regression -> exactly one rollback."""
    import numpy as np

    from mxnet_tpu import chaos, deployd, stream
    from mxnet_tpu import observability as obs
    from mxnet_tpu.parallel import checkpoint as ckpt
    from mxnet_tpu.serving.registry import Backend
    from mxnet_tpu.serving.replication import ReplicaGroup, ServingRouter

    class NpBackend(Backend):
        def __init__(self, params, tag):
            self.p = {k: np.asarray(v) for k, v in params.items()}
            self.tag = tag
            self.input_shapes = {"data": (D,)}

        def infer(self, batch):
            x = np.asarray(batch["data"], dtype=np.float64)
            h = np.maximum(x @ self.p["fc1_weight"].T
                           + self.p["fc1_bias"], 0)
            o = h @ self.p["out_weight"].T + self.p["out_bias"]
            e = np.exp(o - o.max(axis=-1, keepdims=True))
            return [e / e.sum(axis=-1, keepdims=True)], False

    work = tempfile.mkdtemp(prefix="mxtpu_continuous_")
    rng = np.random.RandomState(2)
    rec = os.path.join(work, "train.rec")
    stream.write_ndarray_records(
        rec, rng.randn(48, D).astype(np.float32),
        (np.arange(48) % C).astype(np.float32))
    ckdir = os.path.join(work, "ckpt")
    it = stream.StreamDataIter([rec], (D,), B, seed=7, loop=True)
    (p0, _, _), info = _trainer(mx, B, D).fit_stream(
        it, seed=5, max_steps=4, checkpoint_dir=ckdir, checkpoint_every=4)
    print("  fit_stream: %d step(s), checkpoints %r"
          % (info["steps"], ckpt.all_steps(ckdir)))

    tr_restore = _trainer(mx, B, D)

    def loader(d, step):
        params, _, _ = ckpt.restore_sharded(d, step, trainer=tr_restore)
        return NpBackend(params, "step%d" % step)

    group = ReplicaGroup(replicas=2, group="continuous")
    group.register("mlp", lambda: NpBackend(p0, "baseline"),
                   buckets=[1, 4])
    router = ServingRouter(group)
    golden = {"data": np.random.RandomState(3).randn(4, D).astype(
        np.float32)}
    dd = deployd.DeployDaemon(
        ckdir, group, "mlp", loader,
        eval_fn=lambda b: float(np.max(b.infer(dict(golden))[0])),
        eval_floor=0.0, golden_batch=golden, probation_s=60.0)

    # hammer the router from a client thread across the swap: accepted
    # requests must never be dropped (brownout, not blackout)
    stats = {"ok": 0, "err": []}
    stop = threading.Event()

    def client():
        x = golden["data"][0]
        while not stop.is_set():
            try:
                router.request("mlp", {"data": x}, timeout=10)
                stats["ok"] += 1
            except Exception as exc:  # noqa: BLE001
                stats["err"].append(repr(exc))

    t = threading.Thread(target=client, daemon=True)
    t.start()
    now = 1000.0
    time.sleep(0.05)
    t_swap = time.monotonic()
    dec = dd.poll_once(now=now)
    swap_ms = (time.monotonic() - t_swap) * 1000.0
    time.sleep(0.05)
    stop.set()
    t.join(timeout=10)
    if not (dec and dec["action"] == "promote"):
        failures.append("candidate did not promote: %r" % (dec,))
        return
    print("  promoted step %d onto 2 replicas in %.2f ms; served %d "
          "request(s) across the swap, %d dropped"
          % (dec["step"], swap_ms, stats["ok"], len(stats["err"])))
    if stats["err"]:
        failures.append("dropped accepted requests during swap: %r"
                        % stats["err"][:3])
    if stats["ok"] == 0:
        failures.append("client never got an answer during the swap")

    # seeded regression: delay at admission + 1ms deadline -> typed
    # deadline rejections -> availability fast burn inside probation
    with chaos.inject("serving.admit", "delay", prob=1.0, delay=0.01,
                      seed=11):
        for _ in range(64):
            try:
                router.request("mlp", {"data": golden["data"][0]},
                               deadline_ms=1, timeout=5)
            except Exception:  # noqa: BLE001
                pass
    dec = dd.poll_once(now=now + 5)
    if not (dec and dec["action"] == "rollback"):
        failures.append("seeded regression did not roll back: %r"
                        % (dec,))
        return
    rolled = obs.REGISTRY.get("deployd_rollbacks_total").total()
    again = dd.poll_once(now=now + 6)
    live = [s.registry.get("mlp").backend.tag for _, s in group.live()]
    out = router.request("mlp", {"data": golden["data"][0]}, timeout=10)
    events = obs.events(kind="deploy.rollback")
    bundles = [b for b in os.listdir(flight_dir)
               if b.startswith("flight_deployd.rollback")]
    rule = None
    if bundles:
        with open(os.path.join(flight_dir, bundles[-1],
                               "manifest.json")) as f:
            rule = json.load(f)["extra"].get("rule")
    print("  rollback: rule=%r rollbacks_total=%d live=%r "
          "flight bundles=%d" % (dec["rule"], int(rolled), live,
                                 len(bundles)))
    if int(rolled) != 1 or again is not None:
        failures.append("expected exactly one rollback (total=%r, "
                        "next poll=%r)" % (rolled, again))
    if len(events) != 1 or events[0].fields.get("rule") != dec["rule"]:
        failures.append("deploy.rollback ops event missing or wrong: %r"
                        % [e.fields for e in events])
    if len(bundles) != 1 or rule != dec["rule"]:
        failures.append("flight bundle must name the firing rule "
                        "(bundles=%r rule=%r)" % (bundles, rule))
    if set(live) != {"baseline"}:
        failures.append("serving is not back on the previous model: %r"
                        % live)
    if np.asarray(out[0]).shape[-1] != C:
        failures.append("post-rollback serving answered garbage")


def main():
    flight_dir = tempfile.mkdtemp(prefix="mxtpu_continuous_flight_")
    os.environ["MXNET_TPU_FLIGHT_DIR"] = flight_dir

    import mxnet_tpu as mx

    failures = []
    _bitwise_resume(mx, failures)
    _data_wait(mx, failures)
    _deploy_cycle(mx, flight_dir, failures)
    if failures:
        for f in failures:
            print("FAIL: %s" % f, file=sys.stderr)
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
