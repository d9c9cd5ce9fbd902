"""``make dr``: the disaster-recovery drill — kill the ENTIRE cluster
mid-fit, cold-restart at a different PS shard count from the latest
durable snapshot, and continue training bitwise-equal to a run that was
never interrupted.

The drill drives the PR-18 durability subsystem end to end, on whatever
device JAX finds (the ``make`` target asks for ``JAX_PLATFORMS=cpu``):

1. a reference ``ShardedTrainer.fit(kvstore=)`` run on a 2-shard PS
   trains 2 epochs uninterrupted and records the final parameters;
2. the DR run starts identically, and mid-epoch-0 its batch callback
   (a) proves the ``storage.write`` chaos site: a seeded ENOSPC aborts
   a snapshot attempt cleanly (native ``OSError``, no staging litter,
   nothing visible), (b) takes two committed snapshots of the live PS
   via ``kv.snapshot()`` — consistent seqno-barrier cuts whose frozen
   window must stay bounded — (c) flips one byte in the NEWEST
   snapshot's largest shard record (silent bit rot), then (d) kills the
   whole cluster: the fit dies and every server stops;
3. a COLD restart brings up 3 fresh shards (different topology), and
   ``snapshot.restore_latest`` must quarantine the corrupt newest
   snapshot — exactly one ``snapshot.quarantined`` event and one flight
   bundle naming the bad shard file — then restore the intact one,
   re-striping 2→3;
4. the fit resumes from the exact killed batch (roster fast-forward)
   and its final parameters must equal the reference run's
   **bitwise** — every update landed exactly once, on every shard
   layout.

Exits non-zero on any miss.  Run:  python tools/dr_drill.py
"""

import errno
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_TPU_METRICS", "1")
# tmpfs-friendly: the drill measures protocol correctness, not disk
os.environ.setdefault("MXNET_TPU_SNAPSHOT_FSYNC", "0")

B, D = 8, 6
KILL_AT_BATCH = 2          # batches of epoch 0 completed before the kill
FROZEN_BOUND_MS = 500.0    # the consistent cut must stay this cheap


def _mlp(mx):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _fit(mx, kv, roster=None, callback=None):
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
                               kvstore=kv, roster=roster,
                               batch_end_callback=callback)
    return params


def _servers(ka, n, base=0):
    return [ka.AsyncServer(secret="dr", server_id=base + i).start()
            for i in range(n)]


def _make_kv(mx, ka, addrs):
    os.environ["MXNET_TPU_ASYNC_PS_ADDRS"] = ",".join(addrs)
    ka.reset_membership()
    kv = mx.kv.create("dist_async")
    assert kv._async is not None
    return kv


def _flip_byte(path):
    with open(path, "r+b") as f:
        f.seek(-8, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0x5A]))


class _ClusterKilled(Exception):
    pass


def main():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import chaos
    from mxnet_tpu import elastic
    from mxnet_tpu import kvstore_async as ka
    from mxnet_tpu import observability as obs
    from mxnet_tpu import snapshot

    flight_dir = tempfile.mkdtemp(prefix="mxtpu_dr_flight_")
    snap_dir = tempfile.mkdtemp(prefix="mxtpu_dr_snaps_")
    os.environ["MXNET_TPU_FLIGHT_DIR"] = flight_dir
    os.environ["MXNET_TPU_PS_SECRET"] = "dr"

    failures = []

    # -- reference: 2 shards, never interrupted -------------------------
    ref = _servers(ka, 2)
    try:
        kv_ref = _make_kv(mx, ka, [s.address for s in ref])
        p_ref = _fit(mx, kv_ref)
        kv_ref._async.shutdown()
    finally:
        for s in ref:
            s.stop()

    # -- DR run: same fit, killed whole-cluster mid-epoch-0 -------------
    servers = _servers(ka, 2, base=10)
    frozen = []

    def drill(bep):
        if bep.epoch != 0 or bep.nbatch != KILL_AT_BATCH:
            return
        # (a) seeded ENOSPC mid-snapshot: clean abort, nothing visible
        with chaos.inject("storage.write", "drop", limit=1):
            try:
                kv.snapshot(snap_dir, step=1)
                raise AssertionError("seeded ENOSPC did not abort")
            except OSError as e:
                if e.errno != errno.ENOSPC:
                    raise
        if snapshot.list_snapshots(snap_dir) or any(
                n.endswith(".tmp") for n in os.listdir(snap_dir)):
            raise AssertionError("aborted save left something behind")
        # (b) two committed consistent cuts of the live PS
        for step in (1, 2):
            r = kv.snapshot(snap_dir, step=step)
            frozen.append(r["frozen_ms"])
        # (c) silent bit rot in the newest snapshot's largest shard
        shard_files = [
            (os.path.getsize(os.path.join(snap_dir, "snap-2", n)), n)
            for n in os.listdir(os.path.join(snap_dir, "snap-2"))
            if n.endswith(".bin")]
        victim = max(shard_files)[1]
        _flip_byte(os.path.join(snap_dir, "snap-2", victim))
        drill.victim = victim
        # (d) kill the entire cluster mid-fit
        for s in servers:
            s.stop()
        raise _ClusterKilled()

    try:
        kv = _make_kv(mx, ka, [s.address for s in servers])
        try:
            _fit(mx, kv, callback=drill)
            failures.append("the kill callback never fired")
        except _ClusterKilled:
            pass
    finally:
        for s in servers:
            s.stop()

    obs.clear_events()

    # -- cold restart: 3 fresh shards, restore from the snapshot ladder -
    servers2 = _servers(ka, 3, base=20)
    try:
        kv2 = _make_kv(mx, ka, [s.address for s in servers2])
        restored = snapshot.restore_latest(snap_dir, kv2._async,
                                           secret="dr")
        roster = elastic.WorkerRoster(ranks=[0])
        roster.mark_progress(0, KILL_AT_BATCH)   # resume at the kill point
        p_dr = _fit(mx, kv2, roster=roster)
        kv2._async.shutdown()
    finally:
        for s in servers2:
            s.stop()

    # -- the acceptance bars --------------------------------------------
    if restored["step"] != 1 or restored["saved_shards"] != 2 \
            or restored["restored_shards"] != 3:
        failures.append("restore took the wrong path: %r" % (restored,))

    worst = 0.0
    for n in sorted(p_ref):
        a, b = np.asarray(p_ref[n]), np.asarray(p_dr[n])
        if a.size:
            worst = max(worst, float(np.max(np.abs(
                a.astype(np.float64) - b.astype(np.float64)))))
        if not np.array_equal(a, b):
            failures.append("continuation not bitwise-equal on %s" % n)

    evs = obs.events(kind="snapshot.quarantined")
    if len(evs) != 1:
        failures.append("expected exactly 1 quarantine event, saw %d"
                        % len(evs))
    if not os.path.isdir(os.path.join(snap_dir, "snap-2.quarantined")):
        failures.append("corrupt snapshot was not quarantined on disk")
    bundles = [d for d in os.listdir(flight_dir)
               if d.startswith("flight_snapshot_quarantined")]
    named = []
    for d in bundles:
        with open(os.path.join(flight_dir, d, "manifest.json")) as f:
            named.append(json.load(f)["extra"].get("file"))
    if len(bundles) != 1 or named != [drill.victim]:
        failures.append("flight bundle must name the bad shard "
                        "(bundles=%r files=%r want=%r)"
                        % (bundles, named, drill.victim))

    if not frozen or any(f is None or f > FROZEN_BOUND_MS
                         for f in frozen):
        failures.append("frozen window unbounded: %r ms" % (frozen,))

    print("dr drill: whole-cluster kill mid-fit -> cold 2->3 restore")
    print("  snapshots: 1 aborted by seeded ENOSPC, 2 committed, "
          "1 bit-rotted")
    print("  frozen windows: %s ms"
          % ", ".join("%.2f" % f for f in frozen))
    print("  quarantined: snap-2 (bad shard: %s), restored: snap-%d "
          "onto %d shards" % (drill.victim, restored["step"],
                              restored["restored_shards"]))
    print("  continuation vs uninterrupted: max |delta| = %.3g "
          "(bitwise %s)" % (worst, "EQUAL" if worst == 0.0 else "MISS"))
    if failures:
        for f in failures:
            print("FAIL: %s" % f, file=sys.stderr)
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
