"""Communication bandwidth measurement (parity: reference
``tools/bandwidth/measure.py`` — times kvstore push/pull to estimate the
reduce bandwidth a training job will see).

TPU-native measurements:
 - host→device and device→host transfer bandwidth (the PJRT staging path
   the data pipeline rides)
 - on-mesh all-reduce / all-gather bandwidth over the visible device mesh
   (ICI on real slices; a virtual CPU mesh validates plumbing)
 - multi-process allreduce (the dist kvstore path) when launched under
   ``tools/launch.py``

    python tools/bandwidth.py --size-mb 64
    python tools/launch.py -n 2 python tools/bandwidth.py --dist

``--wire`` additionally runs an in-process 2-shard kvstore push/pull
loop under the PR-15 byte books and prints ``wire_report()`` next to
the transfer numbers, so one tool answers both "what can the hardware
do" and "what does the wire actually use".
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, n=10):
    fn()  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    # block
    import jax

    jax.block_until_ready(out) if out is not None else None
    return (time.perf_counter() - t0) / n


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--size-mb", type=float, default=64.0)
    parser.add_argument("--repeat", type=int, default=10)
    parser.add_argument("--dist", action="store_true",
                        help="measure cross-process allreduce (use with "
                             "tools/launch.py)")
    parser.add_argument("--wire", action="store_true",
                        help="also run an in-process 2-shard kvstore "
                             "loop and print the wire-bandwidth books "
                             "(observability.wire.wire_report)")
    args = parser.parse_args()

    import mxnet_tpu as mx  # noqa: F401  (bootstraps jax.distributed)
    import jax
    import jax.numpy as jnp

    n_elem = int(args.size_mb * (1 << 20) / 4)
    host = np.random.rand(n_elem).astype(np.float32)
    dev = jax.local_devices()[0]
    gb = args.size_mb / 1024.0

    # H2D / D2H (distinct arrays per rep — repeated fetches of one array
    # hit the runtime's host cache and report nonsense)
    t = _time(lambda: jax.device_put(host, dev).block_until_ready(),
              args.repeat)
    print("h2d: %8.2f ms   %6.2f GB/s" % (t * 1e3, gb / t))
    fresh = [jax.device_put(host, dev) + np.float32(i)
             for i in range(args.repeat + 1)]
    jax.block_until_ready(fresh)
    it = iter(fresh)
    t = _time(lambda: np.asarray(next(it)), args.repeat)
    print("d2h: %8.2f ms   %6.2f GB/s" % (t * 1e3, gb / t))

    # on-mesh collectives (needs >1 local device: virtual CPU mesh or slice)
    devs = jax.local_devices()
    if len(devs) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(devs), ("x",))
        sharded = jax.device_put(host, NamedSharding(mesh, P("x")))

        psum = jax.jit(
            jax.shard_map(lambda x: jax.lax.psum(x, "x"), mesh=mesh,
                          in_specs=P("x"), out_specs=P("x")))
        t = _time(lambda: psum(sharded).block_until_ready(), args.repeat)
        # ring all-reduce moves 2*(n-1)/n of the data per device
        algo = 2 * (len(devs) - 1) / len(devs) * gb
        print("all-reduce (%d dev): %8.2f ms   %6.2f GB/s algo-bw"
              % (len(devs), t * 1e3, algo / t))

        ag = jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))
        t = _time(lambda: ag(sharded).block_until_ready(), args.repeat)
        print("all-gather (%d dev): %8.2f ms   %6.2f GB/s"
              % (len(devs), t * 1e3, gb / t))

    # wire books: what the kvstore wire ACTUALLY uses, next to what the
    # hardware can do above
    if args.wire:
        import pickle

        from mxnet_tpu import kvstore_async as ka
        from mxnet_tpu import optimizer as mx_opt
        from mxnet_tpu.observability import wire as owire

        servers = [ka.AsyncServer(server_id=i, secret="bw").start()
                   for i in range(2)]
        group = ka.ServerGroup([s.address for s in servers], rank=0,
                               heartbeat=False, secret="bw")
        group._bound = 1 << 10  # stripe the big key across both shards
        big = np.random.rand(
            max(int(args.size_mb * (1 << 20) / 4 / 16), 1 << 10)
        ).astype(np.float32)
        group.init([("big", big), ("small", np.ones(8, np.float32))])
        group.set_optimizer(pickle.dumps(mx_opt.SGD(learning_rate=0.01)))
        t0 = time.perf_counter()
        for _ in range(args.repeat):
            group.push([("big", big), ("small", np.ones(8, np.float32))])
            group.pull(["big", "small"])
        dt = time.perf_counter() - t0
        group.shutdown()
        for s in servers:
            s.stop()
        rep = owire.wire_report()
        print()
        print("kvstore wire books (%d push+pull rounds, 2 shards):"
              % args.repeat)
        print(owire.format_wire_report())
        if dt > 0:
            print("measured wire rate: %6.2f MB/s over %.3fs"
                  % (rep["bytes_total"] / (1 << 20) / dt, dt))

    # cross-process (dist kvstore reduce path)
    if args.dist and jax.process_count() > 1:
        from mxnet_tpu.parallel.collectives import allreduce_hosts

        t = _time(lambda: jax.block_until_ready(allreduce_hosts(host)),
                  args.repeat)
        print("[rank %d] dist allreduce (%d proc): %8.2f ms   %6.2f GB/s"
              % (jax.process_index(), jax.process_count(), t * 1e3, gb / t))


if __name__ == "__main__":
    main()
