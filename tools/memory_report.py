"""``make memory``: cash in the PR-20 capacity ledger — the memory
analogue of ``tools/wire_report.py``.  Three phases, each gated:

1. **checkpointed fit** — a pipelined CPU fit with periodic sharded
   checkpoints.  The trainer's tagging seams book ``params`` /
   ``optimizer`` / ``prefetch``; the sample points at checkpoint
   boundaries refresh the ``jax.live_arrays()`` ground truth; the
   phase fails unless :func:`memory_reconciles` holds within 5% —
   booked pools explain what the allocator can see, and an empty
   ledger fails by contract.
2. **generation-lane serving run** — an ``LMBackend`` (weight tree
   booked into ``params``, block pools into ``kv_cache``) serves a
   few generations; the books must reconcile again and the KV-block
   economy gauges (occupancy/headroom, blocks-per-session) must have
   measured.
3. **synthetic headroom squeeze** — ``MXNET_TPU_MEMORY_BUDGET_BYTES``
   is pinned just above the live total so ``memory_headroom_ratio``
   drops under the ``oom_proximity`` threshold; two watchdog passes
   must fire the rule EXACTLY once and write EXACTLY one flight
   bundle whose manifest carries the pool ledger snapshot and the
   top-K largest live buffers.

Exits non-zero on any miss.

Run:  python tools/memory_report.py
"""

import gc
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_TPU_METRICS", "1")

_FAILED = False


def check(phase, cond, ok_msg, fail_msg):
    global _FAILED
    if cond:
        print("[%s] %s" % (phase, ok_msg))
    else:
        _FAILED = True
        print("[%s] FAIL: %s" % (phase, fail_msg))


def reconcile(phase):
    from mxnet_tpu.observability import memory as omem

    ok, booked, truth = omem.memory_reconciles(tol=0.05)
    check(phase, ok,
          "pool books reconcile with jax.live_arrays(): %d B booked "
          "vs %d B live" % (booked, truth),
          "pool books (%d B) do not reconcile with the live-array "
          "truth (%d B) within 5%%" % (booked, truth))


def phase_fit(ckpt_dir):
    """Checkpointed pipelined fit; leaves nothing tagged alive."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import mxnet_tpu as mx
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.observability import metrics as om
    from mxnet_tpu.observability import memory as omem
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    om.reset_metrics()
    B, D = 8, 64
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=256,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(net, num_hidden=8, name="fc2"),
        name="softmax")
    rs = np.random.RandomState(7)
    it = NDArrayIter({"data": rs.randn(64, D).astype(np.float32)},
                     {"softmax_label":
                      rs.randint(0, 8, (64,)).astype(np.float32)},
                     batch_size=B)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tr = ShardedTrainer(net, mesh, data_shapes={"data": (B, D)},
                        label_shapes={"softmax_label": (B,)},
                        rescale_grad=1.0 / B, momentum=0.9,
                        pipeline_steps=2)
    # hold the returned state across the sample: the booked params /
    # optimizer trees must still be LIVE when the ground truth is read,
    # or the reconcile gate (rightly) reports books without backing
    state, _history = tr.fit(it, num_epoch=2, seed=3, log_every=0,
                             checkpoint_dir=ckpt_dir, checkpoint_every=4)
    # orbax's save path keeps internal copies of the saved trees alive
    # until every reference to the returned state drops (observed on
    # CPU jax 0.4.37: ~2x the state tree outlives the fit, pinned to
    # the returned arrays).  Round-trip the final state through host so
    # the post-fit live set is exactly the state the pool books
    # describe; the booked byte counts are unchanged by re-placement.
    host = jax.tree_util.tree_map(np.asarray, state)
    del state
    gc.collect()
    state = jax.device_put(host)
    del host
    omem.sample()
    rep = omem.memory_report()
    print(omem.format_memory_report())
    print()
    reconcile("fit")
    check("fit", rep["pools"].get("params", {}).get("all", 0) > 0,
          "params pool booked %d B"
          % rep["pools"].get("params", {}).get("all", 0),
          "params pool is empty — the trainer seam did not tag")
    check("fit", rep["pools"].get("optimizer", {}).get("all", 0) > 0,
          "optimizer pool booked %d B"
          % rep["pools"].get("optimizer", {}).get("all", 0),
          "optimizer pool is empty — the trainer seam did not tag")
    check("fit", rep["pool_watermarks"].get("prefetch", 0) > 0,
          "prefetch pool watermark saw %d B staged"
          % rep["pool_watermarks"].get("prefetch", 0),
          "prefetch pool never booked a staged superbatch")
    check("fit", rep["allocs"].get("params", 0) > 0,
          "ledger alloc counters measured",
          "memory_pool_alloc_total{pool=params} never incremented")
    del state


def phase_serving():
    """Generation-lane serving run over a paged KV cache."""
    from mxnet_tpu import serving
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.observability import metrics as om
    from mxnet_tpu.observability import memory as omem

    om.reset_metrics()
    cfg = tfm.lm_config(num_classes=128, seq_len=64, num_embed=64,
                        num_heads=4, num_layers=2)
    # host-numpy weights, as a checkpoint gives them: the backend places
    # the tree on the device and books what it placed
    params = tfm.init_lm_params(cfg, seed=0)
    sched = serving.GenerationScheduler()
    be = serving.LMBackend(params, cfg, block_size=8, num_blocks=32)
    sched.register("lm", be, decode_buckets=[1, 2],
                   prefill_buckets=[8, 16])
    sched.warmup("lm")
    for seed in range(3):
        toks = sched.generate("lm", list(range(1 + seed, 9 + seed)),
                              max_new_tokens=8)
        assert toks, "generation produced no tokens"
    omem.sample()
    rep = omem.memory_report()
    print(omem.format_memory_report())
    print()
    reconcile("serving")
    check("serving", rep["pools"].get("params", {}).get("all", 0) > 0,
          "weight tree booked %d B into params"
          % rep["pools"].get("params", {}).get("all", 0),
          "params pool is empty — the LMBackend seam did not tag")
    check("serving",
          rep["pools"].get("kv_cache", {}).get("all", 0) > 0,
          "block pools booked %d B into kv_cache{device=all}"
          % rep["pools"].get("kv_cache", {}).get("all", 0),
          "kv_cache pool is empty — the PagedKVCache seam did not tag")
    reg = om.REGISTRY
    hist = reg.get("serving_kv_blocks_per_session")
    count = hist.labels("lm").count if hist is not None else 0
    check("serving", count > 0,
          "blocks-per-session histogram measured %d freed sequences"
          % count,
          "serving_kv_blocks_per_session never observed a free")
    frees = reg.get("serving_kv_cache_free_blocks_total")
    check("serving",
          frees is not None and frees.labels("lm").value > 0,
          "block alloc/free rate counters measured",
          "serving_kv_cache_free_blocks_total never incremented")
    sched.close()


def phase_squeeze(flight_dir):
    """Synthetic headroom squeeze: oom_proximity fires exactly once
    with exactly one flight bundle naming pools + top-K buffers."""
    import jax.numpy as jnp

    import mxnet_tpu.observability as obs
    from mxnet_tpu.observability import metrics as om
    from mxnet_tpu.observability import memory as omem

    om.reset_metrics()
    ballast = jnp.ones((64, 1024), jnp.float32)  # noqa: F841 held live
    omem.tag_tree("params", "squeeze-ballast", ballast)
    live = omem.sample()
    # pin the synthetic budget 2% above the live total: headroom
    # ~0.02 < the 0.05 oom_proximity threshold
    os.environ["MXNET_TPU_MEMORY_BUDGET_BYTES"] = str(int(live * 1.02))
    os.environ["MXNET_TPU_FLIGHT_DIR"] = flight_dir
    try:
        omem.sample()
        dog = obs.Watchdog(rules=obs.default_rules())
        dog.evaluate(now=1.0)
        dog.evaluate(now=2.0)   # still red: edge already recorded
        dog.stop()
    finally:
        del os.environ["MXNET_TPU_MEMORY_BUDGET_BYTES"]
        del os.environ["MXNET_TPU_FLIGHT_DIR"]
    fired = om.REGISTRY.get("cluster_alerts_fired_total")
    edges = fired.labels("oom_proximity").value if fired else 0
    check("squeeze", edges == 1,
          "oom_proximity fired exactly once across two passes",
          "oom_proximity rising edges = %s (want exactly 1)" % edges)
    bundles = [d for d in os.listdir(flight_dir)
               if d.startswith("flight_watchdog.oom_proximity")]
    check("squeeze", len(bundles) == 1,
          "exactly one flight bundle written: %s"
          % (bundles[0] if bundles else "-"),
          "expected exactly 1 oom_proximity bundle, found %d"
          % len(bundles))
    if len(bundles) == 1:
        with open(os.path.join(flight_dir, bundles[0],
                               "manifest.json")) as fh:
            manifest = json.load(fh)
        extra = manifest.get("extra", {})
        pools = str(extra.get("memory_pools", ""))
        bufs = str(extra.get("top_buffers", ""))
        check("squeeze", "params" in pools,
              "manifest carries the pool ledger snapshot",
              "manifest extra.memory_pools does not name the params "
              "pool: %r" % pools[:200])
        check("squeeze", "nbytes" in bufs and "shape" in bufs,
              "manifest names the top-K largest live buffers",
              "manifest extra.top_buffers is missing buffer rows: %r"
              % bufs[:200])


def main():
    print("=== phase 1/3: checkpointed fit ===")
    ckpt = tempfile.mkdtemp(prefix="memrep_ckpt_")
    try:
        phase_fit(ckpt)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    gc.collect()
    print()

    print("=== phase 2/3: generation-lane serving run ===")
    phase_serving()
    gc.collect()
    print()

    print("=== phase 3/3: synthetic headroom squeeze ===")
    flights = tempfile.mkdtemp(prefix="memrep_flight_")
    try:
        phase_squeeze(flights)
    finally:
        shutil.rmtree(flights, ignore_errors=True)

    from mxnet_tpu.observability import autoscaler as oscale
    check("squeeze", "kv_cache_pressure" in oscale.WATCHED_RULES,
          "kv_cache_pressure rides the autoscaler's WATCHED_RULES",
          "kv_cache_pressure is not in autoscaler.WATCHED_RULES")
    return 1 if _FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
