"""``make wire``: cash in the PR-15 ledger — run the 2-shard
replicated kvstore fit three times on the same workload and gate the
binary wire on MEASURED numbers:

1. ``json`` baseline — the PR-15 wire, coalescing off.  Its report
   carries the explicitly-labeled PROJECTED binary-wire savings line.
2. ``binary`` — the PR-17 zero-copy frame with RPC coalescing on.
   Measured savings are printed next to the baseline's projection and
   must beat it: bytes/step savings ≥ the projected header savings,
   codec share of step below the baseline's line, header overhead
   down, ``kv_wire_rpcs_per_flush`` p50 down.
3. ``int8`` — binary plus int8 gradient compression.  ``kv_bytes_per_step``
   must fall below the uncompressed binary run and the compression
   books must show a >1x ratio.

Every phase must still reconcile: per-op byte books vs the socket
ground truth within 1%, foreground codec seconds vs the attribution
``kv`` phase — the same falsifiability contract tier-1 enforces, now
under the binary codec.  Exits non-zero on any miss.

Run:  python tools/wire_report.py
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_TPU_METRICS", "1")
os.environ["MXNET_TPU_KV_REPL_SYNC"] = "1"
os.environ.setdefault("MXNET_TPU_PS_SECRET", "wire-report")


def _run_fit(wire, compress, coalesce):
    """One 2-shard replicated fit under the given wire knobs; returns
    the :func:`wire_report` dict snapshot (plain values, safe to keep
    across the next phase's metrics reset)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import mxnet_tpu as mx
    from mxnet_tpu import kvstore_async as ka
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.observability import metrics as om
    from mxnet_tpu.observability import wire as owire
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    os.environ["MXNET_TPU_KV_WIRE"] = wire
    os.environ["MXNET_TPU_KV_COMPRESS"] = compress
    os.environ["MXNET_TPU_KV_COALESCE"] = coalesce
    om.reset_metrics()

    secret = os.environ["MXNET_TPU_PS_SECRET"]
    servers, addrs = [], []
    for shard in range(2):
        pri = ka.AsyncServer(server_id=shard * 2, secret=secret).start()
        fol = ka.AsyncServer(server_id=shard * 2 + 1,
                             secret=secret).start()
        fol.rejoin(pri.address)
        servers += [pri, fol]
        addrs.append("%s|%s" % (pri.address, fol.address))
    os.environ["MXNET_TPU_ASYNC_PS_ADDRS"] = ",".join(addrs)
    ka.reset_membership()

    # payload-heavy on purpose: ~74KB of gradients per step, so codec
    # wall and header share measure the codecs rather than fixed
    # Python per-frame overhead on toy tensors
    B, D = 8, 64
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=256,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(net, num_hidden=8, name="fc2"),
        name="softmax")
    kv = mx.kv.create("dist_async")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1,
                                      rescale_grad=1.0 / B, wd=0.0))
    rs = np.random.RandomState(3)
    it = NDArrayIter({"data": rs.randn(32, D).astype(np.float32)},
                     {"softmax_label":
                      rs.randint(0, 8, (32,)).astype(np.float32)},
                     batch_size=B)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tr = ShardedTrainer(net, mesh, data_shapes={"data": (B, D)},
                        label_shapes={"softmax_label": (B,)},
                        rescale_grad=1.0 / B)
    tr.fit(it, num_epoch=2, seed=5, log_every=0, kvstore=kv)
    for s in servers:
        s.stop()
    ka.reset_membership()
    return owire.wire_report()


def main():
    from mxnet_tpu.observability import wire as owire

    failed = False

    def check(phase, cond, ok_msg, fail_msg):
        nonlocal failed
        if cond:
            print("[%s] %s" % (phase, ok_msg))
        else:
            failed = True
            print("[%s] FAIL: %s" % (phase, fail_msg))

    def reconcile(phase):
        ok, wire_b, sock_b = owire.wire_reconciles(tol=0.01)
        check(phase, ok,
              "byte books reconcile with the socket truth: %d B vs %d B"
              % (wire_b, sock_b),
              "byte books (%d B) do not reconcile with the socket "
              "truth (%d B) within 1%%" % (wire_b, sock_b))
        cok, codec_kv, kv_phase = owire.codec_reconciles()
        check(phase, cok,
              "codec wall reconciles with the attribution kv phase: "
              "%.4fs within %.4fs" % (codec_kv, kv_phase),
              "foreground codec wall (%.4fs) exceeds the attribution "
              "kv phase (%.4fs)" % (codec_kv, kv_phase))

    print("=== phase 1/3: json wire baseline (coalescing off) ===")
    base = _run_fit(wire="json", compress="0", coalesce="0")
    print(owire.format_wire_report())
    print()
    reconcile("json")
    print()

    print("=== phase 2/3: binary wire + coalescing ===")
    binary = _run_fit(wire="binary", compress="0", coalesce="1")
    print(owire.format_wire_report(baseline=base))
    print()
    reconcile("binary")
    cmp_ = owire.compare_wire_reports(base, binary)
    check("binary", cmp_["beats_projection_codec"],
          "codec wall fell on the same workload: %.4fs -> %.4fs "
          "(share %.2f%% -> %.2f%% of a step wall that also shrank)"
          % (base["codec_seconds"], binary["codec_seconds"],
             100 * cmp_["codec_share_before"],
             100 * cmp_["codec_share_after"]),
          "codec wall did not fall: %.4fs -> %.4fs"
          % (base["codec_seconds"], binary["codec_seconds"]))
    check("binary",
          cmp_["header_overhead_pct_after"]
          < cmp_["header_overhead_pct_before"],
          "header overhead fell: %.1f%% -> %.1f%%"
          % (cmp_["header_overhead_pct_before"],
             cmp_["header_overhead_pct_after"]),
          "header overhead did not fall: %.1f%% -> %.1f%%"
          % (cmp_["header_overhead_pct_before"],
             cmp_["header_overhead_pct_after"]))
    check("binary",
          binary["rpcs_per_flush_p50"] < base["rpcs_per_flush_p50"],
          "rpcs/flush p50 fell with coalescing: %.1f -> %.1f "
          "(%d RPCs saved)"
          % (base["rpcs_per_flush_p50"], binary["rpcs_per_flush_p50"],
             binary["coalesce_rpcs_saved"]),
          "rpcs/flush p50 did not fall: %.1f -> %.1f"
          % (base["rpcs_per_flush_p50"], binary["rpcs_per_flush_p50"]))
    print()

    print("=== phase 3/3: binary wire + int8 gradient compression ===")
    comp = _run_fit(wire="binary", compress="int8", coalesce="1")
    print(owire.format_wire_report(baseline=base))
    print()
    reconcile("int8")
    # the projection promised a bytes/step win; the full PR-17 stack
    # (binary frame + coalescing + int8) is what must deliver it —
    # binary framing alone cannot zero the headers the projection
    # wrote off, compression provides the margin
    ccmp = owire.compare_wire_reports(base, comp)
    check("int8", ccmp["beats_projection_bytes"],
          "measured savings %.1f bytes/step beats the projected %.1f"
          % (ccmp["measured_savings_bytes_per_step"],
             base["projected_savings_bytes_per_step"]),
          "measured savings %.1f bytes/step misses the projected %.1f"
          % (ccmp["measured_savings_bytes_per_step"],
             base["projected_savings_bytes_per_step"]))
    check("int8", comp["bytes_per_step"] < binary["bytes_per_step"],
          "bytes/step fell with int8 on: %.1f -> %.1f"
          % (binary["bytes_per_step"], comp["bytes_per_step"]),
          "bytes/step did not fall with int8 on: %.1f -> %.1f"
          % (binary["bytes_per_step"], comp["bytes_per_step"]))
    check("int8", comp["compress_ratio"] > 1.0,
          "compression books show %.2fx (%d raw -> %d wire bytes)"
          % (comp["compress_ratio"], comp["compress_bytes_in"],
             comp["compress_bytes_out"]),
          "compression books show no win (%.2fx)"
          % comp["compress_ratio"])

    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
