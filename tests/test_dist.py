"""Distributed kvstore tests through the real launcher (reference strategy:
``tests/nightly/test_all.sh:37`` runs ``../../tools/launch.py -n 4 python
dist_sync_kvstore.py`` — a simulated cluster of N local processes)."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every test here spawns a multi-process cluster whose barrier/bcast init
# runs cross-process collectives (jax multihost allgather).  The XLA CPU
# backend does not implement multiprocess computations, so under a forced
# CPU platform each worker fails after its full launch-retry budget —
# minutes of guaranteed failure per test.  Skip up front instead.
_PLAT = os.environ.get("JAX_PLATFORMS", "").strip().lower()
pytestmark = pytest.mark.skipif(
    _PLAT == "cpu",
    reason="cross-process collectives are not implemented on the XLA "
           "CPU backend (JAX_PLATFORMS=cpu)")


def _launch(n, script, timeout=240, extra_env=None, servers=0, replicas=0):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("MXNET_TPU_", "XLA_FLAGS"))}
    env.update(extra_env or {})
    argv = [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
            "-n", str(n)]
    if servers:
        argv += ["-s", str(servers)]
    if replicas:
        argv += ["-r", str(replicas)]
    argv += [sys.executable, script]
    return subprocess.run(argv, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=_REPO)


def _launch_and_expect(n, script, marker, attempts=4, extra_env=None,
                       servers=0, replicas=0):
    """Launch + assert all ranks print ``marker``.  Retries: on a loaded
    single-core box the 30 s gloo handshake occasionally times out; a
    genuine regression fails every attempt.  Attempts used are appended
    to ``DIST_ATTEMPTS.jsonl`` so a creeping flake (passes needing >1
    attempt) is machine-checkable, not buried in CI logs."""
    import json
    import time

    last = None
    for attempt in range(attempts):
        try:
            r = _launch(n, os.path.join(_REPO, "tests", "dist", script),
                        extra_env=extra_env, servers=servers,
                        replicas=replicas)
        except subprocess.TimeoutExpired as e:
            # a hang is the most common flake mode — record it and retry
            # like any other failed attempt instead of escaping the loop
            last = subprocess.CompletedProcess(
                e.cmd, returncode=-1,
                stdout="TIMEOUT after %ss\n%s" % (e.timeout, e.stdout or ""),
                stderr=str(e.stderr or ""))
            if attempt < attempts - 1:
                time.sleep(8 * (attempt + 1))
            continue
        ok = [l for l in r.stdout.splitlines() if marker in l]
        if r.returncode == 0 and len(ok) == n:
            with open(os.path.join(_REPO, "DIST_ATTEMPTS.jsonl"), "a") as f:
                f.write(json.dumps({"script": script, "n": n,
                                    "attempts": attempt + 1,
                                    "ok": True}) + "\n")
            if attempt > 0:
                print("WARNING: %s needed %d launch attempts (gloo "
                      "handshake contention?)" % (script, attempt + 1))
            return
        last = r
        if attempt < attempts - 1:
            time.sleep(8 * (attempt + 1))  # let the load spike drain
    with open(os.path.join(_REPO, "DIST_ATTEMPTS.jsonl"), "a") as f:
        f.write(json.dumps({"script": script, "n": n, "attempts": attempts,
                            "ok": False}) + "\n")
    raise AssertionError(last.stdout + "\n" + last.stderr)


@pytest.mark.parametrize("n", [2])
def test_dist_sync_kvstore_via_launcher(n):
    _launch_and_expect(n, "dist_sync_kvstore.py", "dist_sync kvstore OK")


def test_dist_module_fit_via_launcher():
    # the reference's dist_lenet.py role: real Module.fit training over
    # dist_sync — rank-0-wins broadcast init (ranks seed divergently),
    # bitwise-replicated weights after fit, convergence on held-out data
    _launch_and_expect(2, "dist_module_fit.py", "dist module fit OK")


def test_dist_sync_overlap_via_launcher():
    # the push(priority=) note measured: async comm-lane pushes return
    # immediately, so pull(k) waits only key k — time-to-first-key is ~1
    # stagger delay, not nkeys of them, against a straggler peer; raw
    # compute/comm overlap numbers recorded for docs/PERF.md
    _launch_and_expect(2, "dist_sync_overlap.py", "dist_sync overlap OK")


def test_dist_tpu_kvstore_via_launcher():
    # the TPU-native fused sync mode: accumulate semantics + bitwise
    # update-on-push parity with dist_sync (sgd-momentum AND adam),
    # weights/optimizer state never visiting a host-side updater
    _launch_and_expect(2, "dist_tpu_kvstore.py", "dist_tpu kvstore OK")


def test_dist_sharded_trainer_via_launcher():
    # cross-process GSPMD: one global mesh, grads psum over the process
    # boundary, params stay replicated, model converges
    _launch_and_expect(2, "dist_sharded_trainer.py",
                       "dist GSPMD training OK")


def test_dist_async_kvstore_via_launcher():
    # update-on-push, no barrier: worker step counts diverge yet training
    # converges; staleness asserted from the server's arrival counts
    _launch_and_expect(2, "dist_async_kvstore.py", "dist_async kvstore OK")


def test_dist_async_multiserver_via_launcher():
    # real `-s 2` server processes: keys shard by hash across both, the
    # big array stripes one chunk per server, training still converges
    _launch_and_expect(4, "dist_async_multiserver.py",
                       "dist_async multiserver OK", servers=2,
                       extra_env={"MXNET_TPU_PS_DEAD_AFTER": "60"})


def test_dist_async_replicated_failover_via_launcher():
    # `-s 2 -r 2`: each shard is a primary + hot-standby process pair;
    # rank 0 terminates shard 0's primary mid-training and both workers
    # must fail over to the promoted standby and converge
    _launch_and_expect(2, "dist_async_replicated.py",
                       "dist_async replicated OK", servers=2, replicas=2,
                       extra_env={"MXNET_TPU_PS_DEAD_AFTER": "3",
                                  "MXNET_TPU_PS_CALL_TIMEOUT": "3",
                                  "MXNET_TPU_PS_DEADLINE": "8"})


def test_dist_async_liveness_detects_dead_worker():
    # fault injection: rank 1 dies abruptly; rank 0 keeps training (no
    # barrier) and num_dead_node flips via the missing heartbeats
    _launch_and_expect(2, "dist_async_liveness.py",
                       "dist_async liveness OK",
                       extra_env={"MXNET_TPU_PS_DEAD_AFTER": "3"})


def test_dist_async_init_barrier_via_launcher():
    # atomic cross-server init: ranks race inits with different values +
    # rank 0 delayed; everyone must see rank 0's values, untorn, on both
    # sharded and striped keys
    _launch_and_expect(3, "dist_async_init_barrier.py",
                       "dist_async init barrier OK", servers=2)
