"""Memory & capacity observability (PR 20): the reconciled pool
ledger, the KV-block economy, and OOM-proximity alerting.

- **Ledger math**: tag/tag_tree/untag with replace semantics,
  per-pool watermarks, and alloc/free event counters.
- **Falsifiability**: ``memory_reconciles`` fails on an empty ledger
  AND on an overbooked one — ok only when the ``device='all'`` books
  and the ``jax.live_arrays()`` truth are both nonzero and agree
  within tolerance (the ``wire_reconciles`` contract).
- **KV-block economy**: occupancy/headroom/fragmentation gauges,
  alloc/free/exhaustion counters, the blocks-per-session histogram,
  and the pool bytes booked under ``kv_cache{device=all}`` (the
  pools are live device arrays).
- **Alerting**: a headroom squeeze fires ``oom_proximity`` exactly
  once per edge with exactly ONE flight bundle whose manifest names
  the pool ledger and the top-K largest live buffers;
  ``kv_cache_pressure`` warns and rides the autoscaler.
- **Constant-time off-switch**: with ``MXNET_TPU_METRICS=0`` every
  new seam records nothing (zero ``_record`` calls).
- **Surfaces**: federated ``cluster_memory_*`` rows and the
  ``/memory`` JSON endpoint.
"""

import http.client
import json
import os

import numpy as np
import pytest

import mxnet_tpu.observability as obs
from mxnet_tpu.observability import memory as omem
from mxnet_tpu.observability import metrics as om
from mxnet_tpu.ops.kv_cache import PagedKVCache


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    om.reset_metrics()
    yield
    om.reset_metrics()


class _Buf(object):
    """Stands in for a live jax array in the monkeypatched truth."""

    def __init__(self, nbytes, shape=None, dtype="float32"):
        self.nbytes = int(nbytes)
        self.shape = shape if shape is not None else (nbytes // 4,)
        self.dtype = dtype


def _fake_truth(monkeypatch, *sizes):
    """Pin ``jax.live_arrays()`` to a deterministic set of buffers —
    the process-global truth is otherwise polluted by every other test
    module's module-scope params."""
    import jax

    bufs = [_Buf(s) for s in sizes]
    monkeypatch.setattr(jax, "live_arrays", lambda: bufs)


def _pool_bytes(pool, device="all"):
    fam = om.REGISTRY.get("memory_pool_bytes")
    return fam.labels(pool, device).value if fam is not None else None


# ------------------------------------------------------------ ledger math

def test_tag_books_pools_watermarks_and_counters():
    omem.tag("params", "k1", 1000)
    omem.tag("kv_cache", "pool", 512, device="host")
    assert _pool_bytes("params") == 1000
    assert _pool_bytes("kv_cache", "host") == 512
    # replace semantics: re-tagging the same key updates the row and
    # the watermark keeps the high-water mark
    omem.tag("params", "k1", 400)
    assert _pool_bytes("params") == 400
    wm = om.REGISTRY.get("memory_pool_watermark_bytes")
    assert wm.labels("params").value == 1000
    allocs = om.REGISTRY.get("memory_pool_alloc_total")
    assert allocs.labels("params").value == 2
    omem.untag("params", "k1")
    assert _pool_bytes("params") == 0
    frees = om.REGISTRY.get("memory_pool_free_total")
    assert frees.labels("params").value == 1
    # untagging an unknown key is safe and counts nothing
    omem.untag("params", "never-tagged")
    assert frees.labels("params").value == 1


def test_other_pool_cannot_be_tagged():
    with pytest.raises(ValueError):
        omem.tag("other", "k", 1)
    with pytest.raises(ValueError):
        omem.tag("no-such-pool", "k", 1)


def test_tag_tree_books_jax_leaves_only():
    import jax

    dev = jax.device_put(np.ones((8,), np.float32))     # 32 B
    tree = {"w": dev, "host": np.ones((100,), np.float32), "n": 3}
    assert omem.tag_tree("params", "t", tree) == 32
    assert _pool_bytes("params") == 32


# --------------------------------------------------------- reconcile gate

def test_empty_ledger_fails_reconcile(monkeypatch):
    _fake_truth(monkeypatch, 1000)
    omem.sample()
    ok, booked, truth = omem.memory_reconciles()
    assert (ok, booked, truth) == (False, 0.0, 1000.0)


def test_reconcile_within_tolerance_and_overbook_fails(monkeypatch):
    omem.tag("params", "k", 1000)
    _fake_truth(monkeypatch, 980)
    omem.sample()
    ok, booked, truth = omem.memory_reconciles(tol=0.05)
    assert ok and booked == 1000 and truth == 980
    # books that claim far more than the allocator can see must fail
    _fake_truth(monkeypatch, 400)
    omem.sample()
    ok, booked, truth = omem.memory_reconciles(tol=0.05)
    assert not ok and booked == 1000 and truth == 400


def test_sample_derives_other_residual(monkeypatch):
    omem.tag("params", "k", 600)
    omem.tag("compile", "cache", 5000, device="xla")   # outside the gate
    _fake_truth(monkeypatch, 1000)
    omem.sample()
    assert _pool_bytes("other") == 400
    rep = omem.memory_report()
    assert rep["booked_bytes"] == 600
    assert rep["other_bytes"] == 400
    assert rep["live_bytes"] == 1000
    assert rep["reconciles"] is False        # 600 vs 1000 misses 5%
    assert rep["pools"]["compile"]["xla"] == 5000
    assert "params" in omem.format_memory_report()


def test_headroom_budget_ratio_floors_above_zero(monkeypatch):
    omem.tag("params", "k", 900)
    _fake_truth(monkeypatch, 900)
    monkeypatch.setenv("MXNET_TPU_MEMORY_BUDGET_BYTES", "1000")
    omem.sample()
    head = om.REGISTRY.get("memory_headroom_ratio").labels("all")
    assert abs(head.value - 0.1) < 1e-9
    # a fully-exhausted budget floors at 1e-6, never exactly 0: the
    # watchdog's skip_zero convention must not mistake true exhaustion
    # for a registry-reset placeholder
    _fake_truth(monkeypatch, 2000)
    omem.sample()
    assert 0 < head.value <= 1e-6


def test_reset_metrics_drops_ledger_bookings(monkeypatch):
    omem.tag("params", "k", 640)
    assert omem.ledger_entries()
    om.reset_metrics()
    assert omem.ledger_entries() == {}
    # nothing resurrects at the next sample
    _fake_truth(monkeypatch, 1000)
    omem.sample()
    assert _pool_bytes("params") == 0


def test_top_buffers_largest_first(monkeypatch):
    import jax

    bufs = [_Buf(64, shape=(16,)), _Buf(256, shape=(8, 8)),
            _Buf(128, shape=(32,))]
    monkeypatch.setattr(jax, "live_arrays", lambda: bufs)
    rows = omem.top_buffers(k=2)
    assert [r["nbytes"] for r in rows] == [256, 128]
    assert rows[0]["shape"] == [8, 8]
    monkeypatch.setenv("MXNET_TPU_MEMORY_TOPK", "1")
    assert len(omem.top_buffers()) == 1


# --------------------------------------------------------- kv-block economy

def test_kv_cache_books_pool_and_economy_gauges():
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         block_size=4, num_blocks=8, model="eco")
    pool_b = cache.k_pages.nbytes + cache.v_pages.nbytes
    # the pools are live device arrays: the row the reconcile gate sums
    assert _pool_bytes("kv_cache", "all") == pool_b
    assert _pool_bytes("kv_cache", "host") == 0
    assert cache.stats()["pool_bytes"] == pool_b
    cache.allocate("a", 12)                  # 3 of 8 blocks
    reg = om.REGISTRY
    assert reg.get("serving_kv_cache_headroom").labels("eco").value \
        == pytest.approx(5 / 8)
    assert reg.get("serving_kv_cache_alloc_blocks_total") \
        .labels("eco").value == 3
    # nothing written yet: 0 of the 12 reserved slots hold a token,
    # fragmentation is maximal until append() fills pages
    frag = reg.get("serving_kv_cache_fragmentation").labels("eco")
    assert frag.value == 1.0
    cache.free("a")
    assert reg.get("serving_kv_cache_free_blocks_total") \
        .labels("eco").value == 3
    hist = reg.get("serving_kv_blocks_per_session").labels("eco")
    assert hist.count == 1 and hist.sum == 3
    assert reg.get("serving_kv_cache_headroom").labels("eco").value == 1.0
    assert frag.value == 0.0                 # unused pool: no fragmentation


def test_kv_cache_collection_untags_the_pool():
    cache = PagedKVCache(num_layers=1, num_heads=1, head_dim=2,
                         block_size=2, num_blocks=4, model="tmp")
    assert _pool_bytes("kv_cache", "all") > 0
    del cache
    import gc

    gc.collect()
    assert _pool_bytes("kv_cache", "all") == 0


# ----------------------------------------------------------------- alerting

def test_oom_proximity_fires_once_with_one_bundle(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_MEMORY_BUDGET_BYTES", "1000")
    omem.tag("params", "k", 980)
    _fake_truth(monkeypatch, 980)
    omem.sample()                            # headroom 0.02 < 0.05
    rule = [r for r in obs.default_rules()
            if r.name == "oom_proximity"][0]
    assert rule.severity == "terminal"
    wd = obs.Watchdog([rule])
    (alert,) = wd.evaluate(now=0.0)
    assert alert.name == "oom_proximity"
    # still red: the alert stays active but the edge was already
    # recorded — no second fired-count, no second bundle
    assert [a.name for a in wd.evaluate(now=1.0)] == ["oom_proximity"]
    fired = om.REGISTRY.get("cluster_alerts_fired_total")
    assert fired.labels("oom_proximity").value == 1
    bundles = [d for d in os.listdir(str(tmp_path))
               if d.startswith("flight_watchdog.oom_proximity")]
    assert len(bundles) == 1
    with open(os.path.join(str(tmp_path), bundles[0],
                           "manifest.json")) as fh:
        extra = json.load(fh).get("extra", {})
    pools = json.loads(extra["memory_pools"])
    assert pools["params"]["all"] == 980
    bufs = json.loads(extra["top_buffers"])
    assert bufs and bufs[0]["nbytes"] == 980


def test_oom_rule_skips_the_reset_placeholder():
    # a zeroed registry (post-reset) must not look like an exhausted
    # device: the rule's skip_zero guard ignores exact-zero gauges
    om.REGISTRY.get("memory_headroom_ratio").labels("all").set(0.0)
    rule = [r for r in obs.default_rules()
            if r.name == "oom_proximity"][0]
    assert obs.Watchdog([rule]).evaluate(now=0.0) == []


def test_kv_pressure_warns_and_rides_the_autoscaler():
    from mxnet_tpu.observability import autoscaler as oscale

    om.REGISTRY.get("serving_kv_cache_occupancy").labels("m").set(0.95)
    rule = [r for r in obs.default_rules()
            if r.name == "kv_cache_pressure"][0]
    assert rule.severity == "warning"
    (alert,) = obs.Watchdog([rule]).evaluate(now=0.0)
    assert alert.name == "kv_cache_pressure"
    assert "kv_cache_pressure" in oscale.WATCHED_RULES


# -------------------------------------------------- constant-time off-switch

def test_metrics_disabled_records_nothing(monkeypatch):
    calls = []
    monkeypatch.setattr(om.Counter, "_record",
                        lambda self, *a, **k: calls.append("counter"))
    monkeypatch.setattr(om.Gauge, "_record",
                        lambda self, *a, **k: calls.append("gauge"))
    monkeypatch.setattr(om.Histogram, "_record",
                        lambda self, *a, **k: calls.append("histogram"))
    monkeypatch.setenv("MXNET_TPU_METRICS", "0")
    assert omem.tag_tree("params", "k", {"n": 1}) == 0
    omem.tag("params", "k", 100)
    omem.untag("params", "k")
    assert omem.sample() is None
    assert omem.ledger_entries() == {}
    cache = PagedKVCache(num_layers=1, num_heads=1, head_dim=2,
                         block_size=2, num_blocks=4, model="off")
    cache.allocate("a", 4)
    cache.free("a")
    assert calls == []


# ------------------------------------------------------------------ surfaces

def test_federation_derives_cluster_memory_rows():
    text = ('memory_pool_bytes{pool="params",device="all"} 600\n'
            'memory_pool_bytes{pool="params",device="host"} 40\n'
            'memory_pool_bytes{pool="kv_cache",device="host"} 256\n'
            'memory_headroom_ratio{device="all"} 0.25\n'
            'memory_headroom_ratio{device="dev0"} 0.5\n')
    peer = ('memory_pool_bytes{pool="params",device="all"} 100\n'
            'memory_headroom_ratio{device="all"} 0.75\n')
    out = obs.federate([
        {"shard": 0, "role": "primary", "epoch": 1, "text": text},
        {"shard": 1, "role": "primary", "epoch": 1, "text": peer},
    ])
    # device rows collapse per (member, pool); headroom takes the min
    assert ('cluster_memory_pool_bytes{member="0:primary:1",'
            'pool="params"} 640') in out
    assert ('cluster_memory_pool_bytes{member="0:primary:1",'
            'pool="kv_cache"} 256') in out
    assert ('cluster_memory_pool_bytes{member="1:primary:1",'
            'pool="params"} 100') in out
    assert "cluster_memory_headroom_min 0.25" in out


def test_memory_endpoint_serves_the_report(monkeypatch):
    omem.tag("params", "k", 640)
    _fake_truth(monkeypatch, 650)
    omem.sample()
    with obs.start_metrics_server(port=0) as srv:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=10)
        conn.request("GET", "/memory")
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith(
            "application/json")
        body = json.loads(resp.read().decode())
    assert body["pools"]["params"]["all"] == 640
    assert body["live_bytes"] == 650
    assert body["reconciles"] is True


def test_attribution_sample_memory_delegates_to_the_ledger(monkeypatch):
    # one reader: the attribution facade and the ledger agree because
    # they ARE the same probe (family names unchanged from pre-PR-20)
    _fake_truth(monkeypatch, 512)
    obs.sample_memory()
    live = om.REGISTRY.get("memory_live_buffer_bytes")
    assert live.labels("all").value == 512
    assert om.REGISTRY.get(
        "memory_live_buffer_watermark_bytes").value == 512
