"""Replicated parameter server: hot-standby replication, heartbeat
failover, epoch fencing, and live rejoin — plus the wire/stop/heartbeat
hardening satellites.

Everything runs IN-PROCESS with thread-backed servers: the cross-process
launcher scripts are unusable under the forced-CPU tier-1 platform
(DIST_ATTEMPTS.jsonl), so the multi-server behaviors they covered —
bigarray striping, the init barrier, worker liveness — are re-pinned
here over real sockets between threads.  Chaos schedules are seeded, so
every failure scenario is deterministic.
"""

import socket
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import chaos
from mxnet_tpu import kvstore_async as ka
from mxnet_tpu.base import (MXNetError, ServerDeadError, ShardFailedError,
                            StaleEpochError, TruncatedMessageError)
from mxnet_tpu.kvstore_async import (AsyncClient, AsyncServer,
                                     ReplicatedClient, ServerGroup)


@pytest.fixture(autouse=True)
def _fast_and_isolated(monkeypatch):
    """Sub-second retry/liveness envelope + a clean membership directory
    for every test."""
    monkeypatch.setattr(AsyncClient, "_BACKOFF_CAP_S", 0.1)
    monkeypatch.setenv("MXNET_TPU_PS_CALL_TIMEOUT", "2")
    monkeypatch.setenv("MXNET_TPU_PS_DEADLINE", "3")
    monkeypatch.setenv("MXNET_TPU_PS_DEAD_AFTER", "2")
    monkeypatch.setenv("MXNET_TPU_KV_REPL_SYNC", "1")
    ka.reset_membership()
    yield
    ka.reset_membership()


def _sgd_pickle(lr=0.1):
    import pickle

    from mxnet_tpu import optimizer as opt

    return pickle.dumps(opt.SGD(learning_rate=lr, wd=0.0))


def _pair_group(secret="r"):
    """primary + snapshot-synced follower, one logical shard."""
    p = AsyncServer(secret=secret, server_id=0).start()
    f = AsyncServer(secret=secret, server_id=0).start()
    f.rejoin(p.address)
    return p, f


def _wait_until(pred, timeout=5.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() >= deadline:
            raise AssertionError("timed out waiting for %s" % what)
        time.sleep(0.02)


# ---------------------------------------------------------------------------
# wire hardening (satellite): EINTR + truncation
# ---------------------------------------------------------------------------

class _FlakyRecvSock:
    """recv() in tiny chunks, with injected EINTRs and an optional early
    close, so the partial-read paths are exercised deterministically."""

    def __init__(self, data, chunk=3, eintr_at=(1, 4)):
        self._data = data
        self._pos = 0
        self._chunk = chunk
        self._eintr_at = set(eintr_at)
        self._calls = 0

    def recv(self, n):
        self._calls += 1
        if self._calls in self._eintr_at:
            raise InterruptedError("EINTR")
        if self._pos >= len(self._data):
            return b""
        out = self._data[self._pos:self._pos + min(n, self._chunk)]
        self._pos += len(out)
        return out


def test_recv_exact_retries_short_reads_and_eintr():
    payload = bytes(range(32))
    sock = _FlakyRecvSock(payload)
    assert ka._recv_exact(sock, 32, "frame body") == payload
    assert sock._calls > 32 // 3  # it really arrived in pieces


def test_recv_exact_truncation_is_typed_and_retriable():
    sock = _FlakyRecvSock(b"only-9-by")  # dies mid-frame
    with pytest.raises(TruncatedMessageError) as ei:
        ka._recv_exact(sock, 64, "frame body")
    assert "9 of 64" in str(ei.value)
    # EOFError subclass: the client retry path catches it like any other
    # connection loss instead of handing garbage to the decoder
    assert isinstance(ei.value, EOFError)
    # a clean close BETWEEN frames stays a plain EOF (not truncation)
    with pytest.raises(EOFError) as ei2:
        ka._recv_exact(_FlakyRecvSock(b"", eintr_at=()), 8, "frame header")
    assert not isinstance(ei2.value, TruncatedMessageError)


class _FlakySendSock:
    def __init__(self, cap=5, eintr_at=(2,)):
        self.sent = b""
        self._cap = cap
        self._eintr_at = set(eintr_at)
        self._calls = 0

    def send(self, view):
        self._calls += 1
        if self._calls in self._eintr_at:
            raise InterruptedError("EINTR")
        taken = bytes(view[:self._cap])
        self.sent += taken
        return len(taken)


def test_sendall_resumes_after_partial_write_and_eintr():
    payload = bytes(range(64))
    sock = _FlakySendSock()
    ka._sendall(sock, payload)
    # every byte exactly once, in order — an EINTR retry must not resend
    # a prefix (that would desynchronize the length-framed stream)
    assert sock.sent == payload


# ---------------------------------------------------------------------------
# stop() idempotency (satellite)
# ---------------------------------------------------------------------------

def test_stop_is_idempotent_and_safe_without_start():
    srv = AsyncServer(secret="s")  # never started
    t0 = time.monotonic()
    srv.stop()  # regression: used to hang in socketserver.shutdown()
    srv.stop()
    assert time.monotonic() - t0 < 2.0
    started = AsyncServer(secret="s").start()
    cli = AsyncClient(started.address, rank=0, heartbeat=False, secret="s")
    cli.init([("w", np.zeros(2, np.float32))])
    started.stop()
    started.stop()  # second call: clean no-op
    cli.close()


# ---------------------------------------------------------------------------
# heartbeat loop (satellite): backoff + exit once dead
# ---------------------------------------------------------------------------

def test_heartbeat_backs_off_and_exits_once_dead(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PS_HEARTBEAT", "0.05")
    monkeypatch.setenv("MXNET_TPU_PS_DEAD_AFTER", "0.4")
    srv = AsyncServer(secret="s").start()
    died = []
    before = set(threading.enumerate())
    cli = AsyncClient(srv.address, rank=0, secret="s",
                      on_dead=died.append)
    hb = [t for t in threading.enumerate()
          if t.name == "mxtpu-ps-heartbeat" and t not in before]
    assert len(hb) == 1
    _wait_until(lambda: srv._heartbeat, what="first heartbeat")
    srv.stop()
    _wait_until(lambda: cli.dead, what="death verdict")
    assert died == [cli]
    # the loop EXITED: no thread keeps hammering the dead address
    _wait_until(lambda: not hb[0].is_alive(),
                what="heartbeat thread exit")
    cli.close()


# ---------------------------------------------------------------------------
# replication: stream, sync acks, failover, fencing, rejoin
# ---------------------------------------------------------------------------

def test_replication_mirrors_state_and_dedup_cache():
    p, f = _pair_group()
    try:
        cli = ReplicatedClient([p.address, f.address], rank=3,
                               heartbeat=False, secret="r")
        cli.set_optimizer(_sgd_pickle())
        cli.init([("w", np.zeros(4, np.float32))])
        cli.push([("w", np.ones(4, np.float32))])
        # sync mode: the push response implies the follower acked
        with p._lock, f._lock:
            np.testing.assert_array_equal(p._store["w"], f._store["w"])
            assert p._seqnos == f._seqnos == {"w": 2}  # init + push
            assert p._applied_seq == f._applied_seq == 3  # +set_optimizer
            # the at-most-once dedup cache rides the stream too, so a
            # request retried ACROSS a failover is still applied once
            assert f._last_seq[3] == p._last_seq[3]
        assert f.role == "follower"
        cli.close()
    finally:
        p.stop()
        f.stop()


@pytest.mark.chaos
def test_repl_drop_is_resent_and_deduped():
    p, f = _pair_group()
    try:
        cli = ReplicatedClient([p.address, f.address], rank=0,
                               heartbeat=False, secret="r")
        cli.set_optimizer(_sgd_pickle())
        cli.init([("w", np.zeros(4, np.float32))])
        with chaos.inject("kvstore.repl_drop", "drop", seed=0,
                          limit=1) as inj:
            cli.push([("w", np.ones(4, np.float32))])
        assert inj.fires == 1  # one stream frame genuinely lost
        with p._lock, f._lock:
            # resent + applied exactly once (log-seqno dedup)
            np.testing.assert_array_equal(p._store["w"], f._store["w"])
            assert p._applied_seq == f._applied_seq
        cli.close()
    finally:
        p.stop()
        f.stop()


@pytest.mark.chaos
def test_repl_delay_keeps_async_follower_eventually_consistent(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_KV_REPL_SYNC", "0")  # async stream
    p, f = _pair_group()
    try:
        cli = ReplicatedClient([p.address, f.address], rank=0,
                               heartbeat=False, secret="r")
        cli.set_optimizer(_sgd_pickle())
        cli.init([("w", np.zeros(4, np.float32))])
        with chaos.inject("kvstore.repl_delay", "delay", seed=0,
                          delay=0.1, limit=2):
            cli.push([("w", np.ones(4, np.float32))])
        # async mode: the push returned before the follower applied; the
        # stream catches it up
        _wait_until(lambda: f._applied_seq == p._applied_seq,
                    what="follower catch-up")
        with f._lock:
            np.testing.assert_array_equal(
                f._store["w"], np.full(4, -0.1, np.float32))
        cli.close()
    finally:
        p.stop()
        f.stop()


@pytest.mark.chaos
def test_failover_promotes_follower_and_retries_inflight_push():
    p, f = _pair_group()
    try:
        cli = ReplicatedClient([p.address, f.address], rank=0,
                               heartbeat=False, secret="r")
        cli.set_optimizer(_sgd_pickle())
        cli.init([("w", np.zeros(4, np.float32))])
        # the kill fires at dispatch entry of the NEXT push on the
        # primary: the update is applied nowhere, the client retries the
        # SAME seq through the promoted follower — applied exactly once
        with chaos.inject("kvstore.server_kill", "raise", seed=0,
                          match="s0:primary:push", limit=1) as inj:
            cli.push([("w", np.ones(4, np.float32))])
        assert inj.fires == 1
        assert cli.epoch == 1 and f.role == "primary"
        vals, seqs = cli.pull(["w"], seqnos=True)
        np.testing.assert_allclose(vals[0], np.full(4, -0.1, np.float32),
                                   rtol=1e-6)
        assert seqs == [2]  # init + exactly one applied push
    finally:
        p.stop()
        f.stop()


def test_zombie_primary_is_fenced_and_rejects_writes():
    p, f = _pair_group()
    try:
        # a partitioned-away client promotes the follower directly: the
        # old primary does not know it was deposed
        promoter = AsyncClient(f.address, rank=9, heartbeat=False,
                               secret="r")
        resp = promoter._call({"op": "promote", "epoch": p.epoch + 1})
        assert resp["epoch"] == 1 and f.role == "primary"
        promoter.close()
        # a stale worker writes to the zombie; the zombie's replication
        # stream is rejected by the higher-epoch ex-follower, which
        # FENCES it — from then on it rejects all client traffic
        stale = AsyncClient(p.address, rank=0, heartbeat=False, secret="r")
        stale.set_optimizer(_sgd_pickle())
        _wait_until(lambda: p.role == "fenced", what="zombie fencing")
        with pytest.raises(StaleEpochError) as ei:
            stale.init([("x", np.zeros(2, np.float32))])
        assert ei.value.epoch == 1 and ei.value.not_primary
        # a worker that stamps a stale epoch is rejected by the NEW
        # primary too (epoch fence, independent of role bookkeeping)
        late = AsyncClient(f.address, rank=1, heartbeat=False, secret="r")
        with pytest.raises(StaleEpochError):
            late._call({"op": "init", "epoch": 0,
                        "pairs": [("y", np.zeros(2, np.float32))]})
        stale.close()
        late.close()
    finally:
        p.stop()
        f.stop()


def test_rejoin_transfers_snapshot_and_rides_the_stream():
    p, f = _pair_group()
    restarted = None
    try:
        cli = ReplicatedClient([p.address, f.address], rank=0,
                               heartbeat=False, secret="r")
        cli.set_optimizer(_sgd_pickle())
        cli.init([("w", np.zeros(4, np.float32))])
        cli.push([("w", np.ones(4, np.float32))])
        p.kill()
        cli.push([("w", np.ones(4, np.float32))])  # forces the failover
        assert f.role == "primary" and cli.epoch == 1
        # 'restart' the dead server: a fresh process state-transfers the
        # snapshot (weights + seqnos + optimizer state) from the current
        # primary and re-enters as follower
        restarted = AsyncServer(secret="r").start()
        restarted.rejoin(f.address)
        assert restarted.role == "follower"
        with restarted._lock, f._lock:
            np.testing.assert_array_equal(restarted._store["w"],
                                          f._store["w"])
            assert restarted._seqnos == f._seqnos
            assert restarted._updater is not None  # optimizer came along
        # and it rides the live stream: the next push reaches it
        cli.push([("w", np.ones(4, np.float32))])
        with restarted._lock, f._lock:
            np.testing.assert_array_equal(restarted._store["w"],
                                          f._store["w"])
            assert restarted._applied_seq == f._applied_seq
        # the rejoined standby can serve a consistent seqno'd pull
        probe = AsyncClient(restarted.address, rank=5, heartbeat=False,
                            secret="r")
        got = probe._call({"op": "pull", "keys": ["w"], "seqnos": True})
        assert got["seqnos"] == [4]  # init + 3 pushes
        probe.close()
        cli.close()
    finally:
        p.stop()
        f.stop()
        if restarted is not None:
            restarted.stop()


def test_whole_group_loss_raises_shard_failed():
    p, f = _pair_group()
    grp = ServerGroup([[p.address, f.address]], rank=0, heartbeat=False,
                      secret="r")
    grp.init([("w", np.zeros(2, np.float32))])
    p.kill()
    f.kill()
    with pytest.raises(ShardFailedError) as ei:
        grp.stats()
    assert "no reachable standby" in str(ei.value)


# ---------------------------------------------------------------------------
# in-process replacements for the cross-process dist scripts
# ---------------------------------------------------------------------------

def test_striping_preserved_across_failover():
    """In-process stand-in for dist_async_multiserver.py, plus failover:
    big arrays stripe one chunk per LOGICAL shard, and a replica failover
    inside one shard group does not move any chunk."""
    p, f = _pair_group()
    lone = AsyncServer(secret="r", server_id=1).start()
    try:
        grp = ServerGroup([[p.address, f.address], lone.address], rank=0,
                          heartbeat=False, secret="r", bigarray_bound=64)
        grp.set_optimizer(_sgd_pickle(lr=0.05))
        big = np.arange(256, dtype=np.float32).reshape(16, 16)
        grp.init([("big", big), ("small", np.zeros(3, np.float32))])
        # chunk i lives on logical shard i and ONLY there
        with p._lock:
            assert ("stripe", "big", 0) in p._store
            assert ("stripe", "big", 1) not in p._store
        with lone._lock:
            assert ("stripe", "big", 1) in lone._store
        np.testing.assert_array_equal(grp.pull(["big"])[0], big)
        # kill shard 0's primary mid-workload: the group fails over
        # inside the replica group; striped routing is untouched
        p.kill()
        grp.push([("big", np.ones((16, 16), np.float32)),
                  ("small", np.ones(3, np.float32))])
        out = grp.pull(["big", "small"])
        np.testing.assert_allclose(out[0], big - 0.05, rtol=1e-6)
        np.testing.assert_allclose(out[1], np.full(3, -0.05, np.float32),
                                   rtol=1e-6)
        assert f.role == "primary"
        with f._lock:  # chunk 0 now served by the promoted follower
            assert ("stripe", "big", 0) in f._store
    finally:
        p.stop()
        f.stop()
        lone.stop()


def test_init_barrier_in_process(monkeypatch):
    """In-process stand-in for dist_async_init_barrier.py: a non-zero
    rank's init BLOCKS until rank 0's values are visible, and rank 0's
    values win on every shard (no torn striped tensors)."""
    monkeypatch.setenv("MXNET_TPU_PS_INIT_TIMEOUT", "10")
    s0 = AsyncServer(secret="r", server_id=0).start()
    s1 = AsyncServer(secret="r", server_id=1).start()
    try:
        addrs = [s0.address, s1.address]
        g0 = ServerGroup(addrs, rank=0, heartbeat=False, secret="r",
                         bigarray_bound=64)
        g1 = ServerGroup(addrs, rank=1, heartbeat=False, secret="r",
                         bigarray_bound=64)
        big0 = np.full((16, 16), 7.0, np.float32)
        done = []

        def rank1_init():
            # rank != 0: values are ignored by contract; shapes drive
            # stripe routing.  Must block until rank 0 initializes.
            g1.init([("big", np.full((16, 16), -1.0, np.float32)),
                     ("k", np.full(3, -1.0, np.float32))])
            done.append(time.monotonic())

        t = threading.Thread(target=rank1_init, daemon=True)
        t.start()
        time.sleep(0.3)
        assert not done  # still blocked: rank 0 hasn't initialized
        g0.init([("big", big0), ("k", np.full(3, 2.0, np.float32))])
        t.join(timeout=10)
        assert done
        # rank 1 sees rank 0's values, untorn, on sharded AND striped keys
        out = g1.pull(["big", "k"])
        np.testing.assert_array_equal(out[0], big0)
        np.testing.assert_array_equal(out[1], np.full(3, 2.0, np.float32))
    finally:
        s0.stop()
        s1.stop()


def test_multi_server_liveness_in_process(monkeypatch):
    """In-process stand-in for dist_async_liveness.py: a worker that
    stops heartbeating is declared dead on every server; live workers
    are not."""
    monkeypatch.setenv("MXNET_TPU_PS_HEARTBEAT", "0.05")
    monkeypatch.setenv("MXNET_TPU_PS_DEAD_AFTER", "0.5")
    s0 = AsyncServer(secret="r", server_id=0).start()
    s1 = AsyncServer(secret="r", server_id=1).start()
    try:
        addrs = [s0.address, s1.address]
        alive = ServerGroup(addrs, rank=0, secret="r")   # heartbeats on
        doomed = ServerGroup(addrs, rank=1, heartbeat=False, secret="r")
        alive.init([("w", np.zeros(2, np.float32))])
        doomed.stats()  # rank 1 makes contact once, then goes silent
        # the verdict holds on EVERY server, not just one (each server
        # keeps its own clock, so wait for the slower of the two)
        _wait_until(lambda: all(1 in per["dead"] for per in
                                alive.stats()["per_server"]),
                    timeout=10, what="dead-worker verdict on every server")
        stats = alive.stats()
        assert 1 in stats["dead"] and 0 not in stats["dead"]
    finally:
        s0.stop()
        s1.stop()


# ---------------------------------------------------------------------------
# acceptance: fit survives a seeded primary kill, exactly
# ---------------------------------------------------------------------------

import jax
from jax.sharding import Mesh

from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.parallel.trainer import ShardedTrainer

B, D = 8, 6


def _mlp():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(n=32, seed=3):
    rs = np.random.RandomState(seed)
    return (rs.randn(n, D).astype(np.float32),
            rs.randint(0, 8, (n,)).astype(np.float32))


def _trainer():
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    return ShardedTrainer(_mlp(), mesh, data_shapes={"data": (B, D)},
                          label_shapes={"softmax_label": (B,)},
                          rescale_grad=1.0 / B)


def _fit_once(kill):
    ka.reset_membership()
    X, Y = _data()
    kv = mx.kv.create("dist_async")
    assert kv._async is not None and len(kv._async_replicas) == 2
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1,
                                      rescale_grad=1.0 / B, wd=0.0))
    it = NDArrayIter({"data": X}, {"softmax_label": Y}, batch_size=B)
    inj = chaos.inject("kvstore.server_kill", "raise", seed=0,
                       match="s0:primary:push", limit=1) if kill else None
    try:
        (params, _, _), _ = _trainer().fit(it, num_epoch=2, seed=5,
                                           log_every=0, kvstore=kv)
    finally:
        if inj is not None:
            inj.remove()
    if kill:
        assert inj.fires == 1, "the seeded kill never fired"
    return params, kv


@pytest.mark.chaos
def test_fit_survives_primary_kill_exactly(monkeypatch):
    """Acceptance: with a 2-replica group, a seeded kvstore.server_kill
    of the primary mid-fit completes training with no ShardFailedError,
    and (sync replication) final params match the no-fault run EXACTLY;
    the killed server then rejoins and serves a seqno-consistent pull."""
    monkeypatch.setenv("MXNET_TPU_KV_REPLICAS", "2")
    p_ref, kv_ref = _fit_once(kill=False)
    p_kill, kv_kill = _fit_once(kill=True)
    killed = [s for s in kv_kill._async_replicas if s._killed]
    survivors = [s for s in kv_kill._async_replicas if not s._killed]
    assert len(killed) == 1 and survivors[0].role == "primary"
    for n in p_ref:
        np.testing.assert_array_equal(np.asarray(p_ref[n]),
                                      np.asarray(p_kill[n]), err_msg=n)
    # live rejoin: a fresh server snapshots from the surviving primary
    # and serves the same weights at the same per-key seqnos
    fresh = AsyncServer(secret=survivors[0].secret).start()
    try:
        fresh.rejoin(survivors[0].address)
        probe = AsyncClient(fresh.address, rank=11, heartbeat=False,
                            secret=survivors[0].secret)
        via_new = probe._call({"op": "pull", "keys": ["fc1_weight"],
                               "seqnos": True})
        probe.close()
        probe2 = AsyncClient(survivors[0].address, rank=12,
                             heartbeat=False, secret=survivors[0].secret)
        via_old = probe2._call({"op": "pull", "keys": ["fc1_weight"],
                                "seqnos": True})
        probe2.close()
        assert via_new["seqnos"] == via_old["seqnos"]
        np.testing.assert_array_equal(via_new["vals"][0],
                                      via_old["vals"][0])
    finally:
        fresh.stop()
        for s in survivors:
            s.stop()
        for s in kv_ref._async_replicas:
            s.stop()
