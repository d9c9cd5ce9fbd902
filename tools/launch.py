"""Distributed launch tool (parity: reference ``tools/launch.py`` — the
dmlc-core tracker that spawns scheduler/server/worker processes and wires
their env).

TPU-native topology has no separate server/scheduler roles: every worker
runs the same SPMD program under ``jax.distributed`` with process 0 hosting
the coordination service.  This launcher covers the reference's ``local``
("simulated cluster = N local processes", the tests/nightly strategy) and
ssh modes:

    python tools/launch.py -n 4 python my_training_script.py
    python tools/launch.py -n 4 --launcher ssh -H hostfile python script.py

Env handed to each process (the DMLC_PS_ROOT_URI / DMLC_ROLE analogs):
``MXNET_TPU_COORDINATOR``, ``MXNET_TPU_NUM_PROCS``, ``MXNET_TPU_PROC_ID``;
scripts pick them up via ``mxnet_tpu.parallel.init_process_group()``.
"""

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading


def _relay(pipe, sink, prefix=b""):
    """Forward one worker's private pipe to the launcher's output, one
    COMPLETE line per write() syscall.

    Without this, all ranks share the launcher's stdout fd and — under
    ``PYTHONUNBUFFERED=1`` — ``print()`` emits the text and the newline
    as two separate unbuffered write()s, so ranks that print at the same
    instant (e.g. right after a barrier) interleave mid-line and consumers
    counting marker lines miscount.  Each rank writing to its own pipe +
    readline() reassembling full lines + one write() per line (atomic for
    pipes up to PIPE_BUF) makes cross-rank interleaving impossible.

    ``prefix`` (``--tag-output``, the mpirun option of the same name)
    prepends a rank tag to every line so consumers can attribute output
    per rank — the prefix rides in the same atomic write."""
    with pipe:
        for line in iter(pipe.readline, b""):
            sink.write(prefix + line)
            sink.flush()


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch_servers(args, coordinator=None):
    """Start ``-s N`` parameter-server shard processes (the reference's
    ``DMLC_ROLE=server`` topology, ``kvstore_dist_server.h``), each
    optionally backed by ``-r R - 1`` hot-standby replicas.  Returns
    (server procs, env entries workers need to find them).
    ``coordinator`` stamps the cluster id (as the inert
    ``MXNET_TPU_CLUSTER_ID``) into each server's env so
    ``tools/kill_mxnet.py --coordinator`` covers servers too.

    Each server binds port 0 and reports its actual address through a
    file — the launcher never pre-allocates ports, so there is no
    probe-then-bind race with other jobs on the host.  Replica addresses
    reach the workers ``|``-joined inside the shard's slot of
    ``MXNET_TPU_ASYNC_PS_ADDRS``, so the worker-side ``ServerGroup``
    routes the shard through a failover-capable ``ReplicatedClient``.
    ``--elastic-spares K`` additionally parks K blank servers outside
    the live topology (addresses in ``MXNET_TPU_ELASTIC_SPARE_ADDRS``)
    as pre-warmed ``kv.resize()`` targets."""
    import secrets
    import tempfile
    import time

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    secret = secrets.token_hex(16)
    addr_dir = tempfile.mkdtemp(prefix="mxtpu_ps_")
    replicas = max(1, getattr(args, "num_replicas", 1))
    procs = []

    metrics_base = getattr(args, "metrics_port_base", 0) or 0

    def spawn(shard, tag, slot, primary_addr=None):
        addr_file = os.path.join(addr_dir, "server_%s.addr" % tag)
        env = dict(os.environ)
        # servers are host-side: never let one grab (or hang on) a chip
        env["JAX_PLATFORMS"] = "cpu"
        env["MXNET_TPU_SERVER_PORT"] = "0"
        env["MXNET_TPU_SERVER_ADDR_FILE"] = addr_file
        env["MXNET_TPU_SERVER_ID"] = str(shard)
        env["MXNET_TPU_NUM_SERVERS"] = str(args.num_servers)
        env["MXNET_TPU_PS_SECRET"] = secret
        if metrics_base:
            # deterministic federation scrape targets: server process at
            # slot k (replicas count as their own slots) serves /metrics
            # on base+k; workers continue after the server block
            env["MXNET_TPU_METRICS_PORT"] = str(metrics_base + slot)
        if primary_addr:
            env["MXNET_TPU_SERVER_PRIMARY"] = primary_addr
        # merged chrome-trace views need each process on its own named
        # track; an explicit operator choice still wins
        env.setdefault("MXNET_TPU_TRACE_TRACK", "server%d:%s" % (
            shard, "standby" if primary_addr else "primary"))
        if coordinator:
            # inert cluster-identity marker (NOT MXNET_TPU_COORDINATOR —
            # that one makes jax.distributed join the worker cluster, and
            # a server registering as a phantom task aborts every worker)
            env["MXNET_TPU_CLUSTER_ID"] = coordinator
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "mxnet_tpu._async_ps_main"], env=env)
        procs.append(proc)
        return proc, addr_file

    def collect(proc, addr_file, what, deadline):
        while True:
            if os.path.exists(addr_file):
                with open(addr_file) as f:
                    addr = f.read().strip()
                if addr:
                    return addr
            if proc.poll() is not None:
                raise RuntimeError("PS %s exited rc=%d before binding"
                                   % (what, proc.returncode))
            if time.time() > deadline:
                raise RuntimeError("PS %s did not report an address "
                                   "within 90s" % what)
            time.sleep(0.1)

    deadline = time.time() + 90
    try:
        # primaries first: followers need the primary address to rejoin
        primaries = [spawn(i, "%d" % i, i * replicas)
                     for i in range(args.num_servers)]
        shard_addrs = [[collect(p, f, "server %d" % i, deadline)]
                       for i, (p, f) in enumerate(primaries)]
        for i in range(args.num_servers):
            for j in range(1, replicas):
                p, f = spawn(i, "%d_%d" % (i, j), i * replicas + j,
                             primary_addr=shard_addrs[i][0])
                shard_addrs[i].append(
                    collect(p, f, "server %d replica %d" % (i, j), deadline))
        # elastic spares: blank shards parked beyond the live topology,
        # sharing the cluster secret so a later ``kv.resize()`` (or the
        # autoscaler's scale_up actuator) can adopt them without a cold
        # process launch — the expensive part of growing is already paid
        spares = max(0, getattr(args, "elastic_spares", 0) or 0)
        spare_addrs = []
        for k in range(spares):
            p, f = spawn(args.num_servers + k, "spare%d" % k,
                         args.num_servers * replicas + k)
            spare_addrs.append(
                collect(p, f, "elastic spare %d" % k, deadline))
    except Exception:
        # don't orphan the shards that DID start
        for p in procs:
            if p.poll() is None:
                p.kill()
        raise
    worker_env = {
        "MXNET_TPU_ASYNC_PS_ADDRS": ",".join("|".join(group)
                                             for group in shard_addrs),
        "MXNET_TPU_NUM_SERVERS": str(args.num_servers),
        "MXNET_TPU_PS_SECRET": secret,
    }
    if spare_addrs:
        worker_env["MXNET_TPU_ELASTIC_SPARE_ADDRS"] = ",".join(spare_addrs)
    return procs, worker_env


def launch_local(args, cmd):
    if args.platform != "cpu" and args.num_workers > 1:
        # a chip belongs to one process: N workers that all ask for the
        # host's accelerator would fight over the same chips (the second
        # fails or hangs), and this launcher does not confine a worker
        # to a chip of its own
        raise SystemExit(
            "launch.py: --platform %s with -n %d on one host: a chip "
            "belongs to one process, and local workers are not confined "
            "to a chip each.  Run ONE worker (one process drives every "
            "chip of its host through the mesh), or --launcher ssh with "
            "one worker per host." % (args.platform, args.num_workers))
    coordinator = "127.0.0.1:%d" % _free_port()
    server_procs, server_env = ([], {})
    if args.num_servers > 0:
        server_procs, server_env = launch_servers(args, coordinator)
    procs = []
    for i in range(args.num_workers):
        env = dict(os.environ)
        env["MXNET_TPU_COORDINATOR"] = coordinator
        env["MXNET_TPU_NUM_PROCS"] = str(args.num_workers)
        env["MXNET_TPU_PROC_ID"] = str(i)
        # each local worker gets its own CPU "chip" (the one-host simulated
        # cluster of tests/nightly)
        env["JAX_PLATFORMS"] = args.platform
        env.setdefault("MXNET_TPU_TRACE_TRACK", "worker%d" % i)
        env.update(server_env)
        metrics_base = getattr(args, "metrics_port_base", 0) or 0
        if metrics_base:
            # workers take the ports after the server block: base +
            # (num server procs incl. replicas) + worker rank
            server_slots = ((args.num_servers
                             * max(1, getattr(args, "num_replicas", 1))
                             + max(0, getattr(args, "elastic_spares", 0)))
                            if args.num_servers > 0 else 0)
            env["MXNET_TPU_METRICS_PORT"] = str(
                metrics_base + server_slots + i)
        procs.append(subprocess.Popen(cmd, env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE))
    relays = []
    for i, p in enumerate(procs):
        prefix = (("[worker-%d] " % i).encode()
                  if getattr(args, "tag_output", False) else b"")
        for pipe, sink in ((p.stdout, sys.stdout.buffer),
                           (p.stderr, sys.stderr.buffer)):
            t = threading.Thread(target=_relay, args=(pipe, sink, prefix),
                                 daemon=True)
            t.start()
            relays.append(t)
    code = 0
    try:
        for p in procs:
            p.wait()
            code = code or p.returncode
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        code = 1
    finally:
        # drain every relayed line (incl. SIGTERM shutdown tracebacks on
        # the interrupt path) before the launcher exits and pipes close
        for t in relays:
            t.join(timeout=30)
        for p in server_procs:  # servers live for the workers' lifetime
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in server_procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    return code


def _ssh_with_secret(host, remote_cmd, secret):
    """Run a remote command with MXNET_TPU_PS_SECRET delivered on STDIN —
    never on the command line, where any local user could read it from
    /proc/<pid>/cmdline and forge the set_optimizer HMAC."""
    wrapped = ("IFS= read -r MXNET_TPU_PS_SECRET; "
               "export MXNET_TPU_PS_SECRET; " + remote_cmd)
    proc = subprocess.Popen(["ssh", host, wrapped], stdin=subprocess.PIPE,
                            text=True)
    proc.stdin.write(secret + "\n")
    proc.stdin.close()
    return proc


def launch_ssh(args, cmd):
    import secrets

    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    assert len(hosts) >= args.num_workers, "hostfile too small"
    coordinator = "%s:%d" % (hosts[0], args.port or _free_port())
    procs = []
    server_env = ""
    secret = secrets.token_hex(16) if args.num_servers > 0 else ""
    if args.num_servers > 0:
        # remote servers bind operator-chosen ports (no addr-file channel
        # across hosts): shard i replica j on hosts[(i*R + j) % len],
        # port base + i*R + j; replica 0 is the shard's initial primary
        # and replicas j > 0 rejoin it as hot standbys
        replicas = max(1, args.num_replicas)
        shard_addrs = []
        for i in range(args.num_servers):
            group = []
            for j in range(replicas):
                slot = i * replicas + j
                host = hosts[slot % len(hosts)]
                port = args.server_port_base + slot
                env = ("JAX_PLATFORMS=cpu "
                       "MXNET_TPU_SERVER_PORT=%d MXNET_TPU_SERVER_ID=%d "
                       "MXNET_TPU_NUM_SERVERS=%d MXNET_TPU_PS_HOST=%s "
                       "MXNET_TPU_TRACE_TRACK=server%d:%s"
                       % (port, i, args.num_servers, host, i,
                          "standby" if j > 0 else "primary"))
                if args.metrics_port_base:
                    env += (" MXNET_TPU_METRICS_PORT=%d"
                            % (args.metrics_port_base + slot))
                if j > 0:
                    env += " MXNET_TPU_SERVER_PRIMARY=%s" % group[0]
                remote = "cd %s && %s %s -m mxnet_tpu._async_ps_main" % (
                    os.getcwd(), env, sys.executable)
                procs.append(_ssh_with_secret(host, remote, secret))
                group.append("%s:%d" % (host, port))
            shard_addrs.append(group)
        # quoted: '|' is a replica separator here, not a shell pipe
        server_env = ("MXNET_TPU_ASYNC_PS_ADDRS='%s' MXNET_TPU_NUM_SERVERS=%d "
                      % (",".join("|".join(g) for g in shard_addrs),
                         args.num_servers))
        spares = max(0, getattr(args, "elastic_spares", 0) or 0)
        spare_addrs = []
        for k in range(spares):
            # blank shards beyond the live topology — resize targets
            slot = args.num_servers * replicas + k
            host = hosts[slot % len(hosts)]
            port = args.server_port_base + slot
            env = ("JAX_PLATFORMS=cpu "
                   "MXNET_TPU_SERVER_PORT=%d MXNET_TPU_SERVER_ID=%d "
                   "MXNET_TPU_NUM_SERVERS=%d MXNET_TPU_PS_HOST=%s "
                   "MXNET_TPU_TRACE_TRACK=server%d:spare"
                   % (port, args.num_servers + k, args.num_servers, host,
                      args.num_servers + k))
            if args.metrics_port_base:
                env += (" MXNET_TPU_METRICS_PORT=%d"
                        % (args.metrics_port_base + slot))
            remote = "cd %s && %s %s -m mxnet_tpu._async_ps_main" % (
                os.getcwd(), env, sys.executable)
            procs.append(_ssh_with_secret(host, remote, secret))
            spare_addrs.append("%s:%d" % (host, port))
        if spare_addrs:
            server_env += ("MXNET_TPU_ELASTIC_SPARE_ADDRS=%s "
                           % ",".join(spare_addrs))
    server_slots = ((args.num_servers * max(1, args.num_replicas)
                     + max(0, getattr(args, "elastic_spares", 0)))
                    if args.num_servers > 0 else 0)
    workers = []
    for i in range(args.num_workers):
        env = ("MXNET_TPU_COORDINATOR=%s MXNET_TPU_NUM_PROCS=%d "
               "MXNET_TPU_PROC_ID=%d MXNET_TPU_TRACE_TRACK=worker%d %s"
               % (coordinator, args.num_workers, i, i, server_env))
        if args.metrics_port_base:
            env += ("MXNET_TPU_METRICS_PORT=%d "
                    % (args.metrics_port_base + server_slots + i))
        remote = "cd %s && %s %s" % (os.getcwd(), env, " ".join(cmd))
        if secret:
            workers.append(_ssh_with_secret(hosts[i], remote, secret))
        else:
            workers.append(subprocess.Popen(["ssh", hosts[i], remote]))
    code = 0
    for p in workers:
        p.wait()
        code = code or p.returncode
    for p in procs:  # reap server shells once the workers are done
        p.terminate()
    return code


def main():
    parser = argparse.ArgumentParser(
        description="launch a distributed job",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="parameter-server shard processes (dist_async "
                             "multi-server topology; 0 = rank-0 hosts one "
                             "server thread)")
    parser.add_argument("-r", "--num-replicas", type=int, default=1,
                        help="replicas per PS shard (1 = no replication; "
                             "R > 1 adds R-1 hot standbys per shard — "
                             "workers fail over to a promoted standby if "
                             "the shard's primary dies)")
    parser.add_argument("--elastic-spares", type=int, default=0,
                        help="extra blank PS processes beyond -s N, parked "
                             "with the cluster secret but outside the live "
                             "topology; their addresses reach workers as "
                             "MXNET_TPU_ELASTIC_SPARE_ADDRS so kv.resize() "
                             "/ the autoscaler can grow onto pre-warmed "
                             "shards (needs -s > 0)")
    parser.add_argument("--server-port-base", type=int, default=9700,
                        help="first PS port for --launcher ssh (server i "
                             "listens on base+i; local mode self-assigns)")
    parser.add_argument("--metrics-port-base", type=int, default=0,
                        help="export MXNET_TPU_METRICS_PORT=base+slot to "
                             "every launched process so each serves its "
                             "own /metrics endpoint on a deterministic "
                             "port: server process k (replicas count as "
                             "slots) gets base+k, worker rank i gets "
                             "base+<server procs>+i — the scrape targets "
                             "for observability.federation (0 = off)")
    parser.add_argument("--launcher", choices=["local", "ssh"],
                        default="local")
    parser.add_argument("-H", "--hostfile", type=str, default=None)
    parser.add_argument("--port", type=int, default=None)
    parser.add_argument("--platform", type=str, default="cpu",
                        help="JAX platform for local workers; anything "
                             "but cpu is refused with more than one "
                             "worker (a chip belongs to one process)")
    parser.add_argument("--tag-output", action="store_true",
                        help="prefix every relayed line with [worker-N] "
                             "(mpirun-style) for per-rank attribution")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    if args.launcher == "ssh":
        sys.exit(launch_ssh(args, args.command))
    sys.exit(launch_local(args, args.command))


if __name__ == "__main__":
    main()
