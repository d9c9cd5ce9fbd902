"""Reconciled device-memory ledger: named pools vs. allocator truth.

PR 15 gave bandwidth a falsifiable ledger (``wire_reconciles``: per-op
byte books vs. socket truth); this module is the capacity analogue.
Every live device byte is booked into a named **pool** —

- ``params``     — the model parameter tree (plus non-momentum aux
  state) the trainer placed on device,
- ``optimizer``  — the momentum/optimizer-state tree,
- ``kv_cache``   — :class:`~mxnet_tpu.ops.kv_cache.PagedKVCache` block
  pools (host-resident numpy pages, booked under ``device="host"``),
- ``recurrent_state`` — the same cache's pool of per-sequence state
  slots (a model with recurrent layers: two versions a slot),
- ``prefetch``   — superbatches staged on device by
  :class:`~mxnet_tpu.parallel.prefetch.PrefetchFeeder`,
- ``compile``    — the XLA ``memory_analysis()`` footprint of the live
  compiled step (allocator-side, booked under ``device="xla"``),
- ``other``      — the derived residual: ground truth minus the sum of
  booked on-device pools (written by :func:`sample`, never tagged).

The seams call :func:`tag` / :func:`tag_tree` / :func:`untag` with a
stable key; bookings have replace semantics so a re-placed tree just
updates its row.  Pools render as ``memory_pool_bytes{pool,device}``
with per-pool watermarks and alloc/free event counters.

**Device labels are the reconciliation contract.**  Only bookings with
``device="all"`` claim bytes that ``jax.live_arrays()`` can see, and
only those enter the :func:`memory_reconciles` gate; ``host`` (numpy
pools) and ``xla`` (allocator-side compile footprint) rows render and
federate but are outside the live-array books.  The gate follows the
``wire_reconciles`` falsifiability contract: an empty ledger FAILS —
``(ok, booked, truth)`` with ``ok`` only when both sides are nonzero
and agree within tolerance.

:func:`sample` is the single ground-truth probe (``attribution.
sample_memory`` delegates here): it sums ``jax.live_arrays()`` into the
pre-existing ``memory_live_buffer_bytes{device='all'}`` /
``memory_live_buffer_watermark_bytes`` families, reads per-device
allocator ``memory_stats()`` (``bytes_in_use`` / ``peak_bytes_in_use``
→ ``memory_live_buffer_bytes{devN}`` / ``memory_peak_bytes{devN}``),
derives the ``other`` residual, and computes
``memory_headroom_ratio{device}`` — from the allocator's
``bytes_limit`` where the backend reports one, or from the synthetic
``MXNET_TPU_MEMORY_BUDGET_BYTES`` budget (CPU soak rigs, tests) under
``device="all"``.  That gauge drives the ``oom_proximity`` (terminal)
and ``kv_cache_pressure`` (warning) watchdog rules.

With ``MXNET_TPU_METRICS=0`` every entry point is a constant-time
guard: no booking, no live-array walk, no allocation.
"""

from __future__ import annotations

import json as _json
import os
import threading

from . import metrics as _metrics

__all__ = ["POOLS", "tag", "tag_tree", "untag", "ledger_entries",
           "sample", "top_buffers", "memory_report",
           "format_memory_report", "memory_reconciles",
           "headroom_budget_bytes", "oom_bundle_extras"]

#: The named pools; ``other`` is the derived residual and cannot be
#: tagged directly.
POOLS = ("params", "optimizer", "kv_cache", "recurrent_state",
         "prefetch", "compile", "other")

_M_POOL = _metrics.gauge(
    "memory_pool_bytes",
    "Live bytes booked into one named memory pool; device='all' rows "
    "are live jax arrays and reconcile against "
    "memory_live_buffer_bytes, 'host'/'xla' rows are outside the "
    "live-array books, pool='other' is the derived residual",
    ["pool", "device"])
_M_POOL_WM = _metrics.gauge(
    "memory_pool_watermark_bytes",
    "High-water mark of one pool's total booked bytes (all devices) "
    "since the last registry reset", ["pool"])
_M_ALLOC = _metrics.counter(
    "memory_pool_alloc_total",
    "Ledger bookings (tag/tag_tree calls) into one pool", ["pool"])
_M_FREE = _metrics.counter(
    "memory_pool_free_total",
    "Ledger releases (untag calls) out of one pool", ["pool"])
_M_HEADROOM = _metrics.gauge(
    "memory_headroom_ratio",
    "Fraction of the device memory budget still free (1 - used/limit); "
    "per-device from the allocator's bytes_limit, device='all' from "
    "the MXNET_TPU_MEMORY_BUDGET_BYTES synthetic budget", ["device"])

# ground truth families (owned here since Round 20; attribution's
# sample_memory delegates so the family names and golden expositions
# are unchanged)
_M_LIVE = _metrics.gauge(
    "memory_live_buffer_bytes",
    "Bytes held by live device buffers at the last sample point "
    "(device='all' sums jax.live_arrays(); per-device series come from "
    "the backend allocator's bytes_in_use when it reports one)",
    ["device"])
_M_PEAK = _metrics.gauge(
    "memory_peak_bytes",
    "Backend allocator peak bytes in use, per device (HBM watermark; "
    "absent on backends whose memory_stats() reports nothing)",
    ["device"])
_M_LIVE_WM = _metrics.gauge(
    "memory_live_buffer_watermark_bytes",
    "High-water mark of memory_live_buffer_bytes{device='all'} across "
    "sample points since the last registry reset")

#: pools the seams may tag (everything but the derived residual).
_TAGGABLE = tuple(p for p in POOLS if p != "other")

# pre-resolved per-pool handles — the seams record through these,
# never labels().  The 'all'-device truth/residual/headroom children
# are resolved lazily in sample() so a process that never samples
# renders no phantom zero series (the pre-PR-20 exposition shape).
_H_WM = {p: _M_POOL_WM.labels(p) for p in _TAGGABLE}
_H_ALLOC = {p: _M_ALLOC.labels(p) for p in _TAGGABLE}
_H_FREE = {p: _M_FREE.labels(p) for p in _TAGGABLE}

_lock = threading.Lock()
_entries = {}        # (pool, key) -> (nbytes, device)
_pool_devices = {}   # pool -> set of device labels ever booked
_H_POOL = {}         # (pool, device) -> gauge child cache


def headroom_budget_bytes():
    """The synthetic device-memory budget (bytes) from
    ``MXNET_TPU_MEMORY_BUDGET_BYTES``; 0 disables the device='all'
    headroom series (backends with a real ``bytes_limit`` still get
    per-device headroom)."""
    try:
        return int(os.environ.get("MXNET_TPU_MEMORY_BUDGET_BYTES", "0"))
    except ValueError:
        return 0


def _pool_child(pool, device):
    h = _H_POOL.get((pool, device))
    if h is None:
        h = _M_POOL.labels(pool, device)
        _H_POOL[(pool, device)] = h
    return h


def _sync_pool_locked(pool):
    """Re-render one pool's per-device gauge rows from the ledger
    (absolute set, so a registry reset cannot leave a stale delta)."""
    sums = {}
    for (p, _key), (nbytes, device) in _entries.items():
        if p == pool:
            sums[device] = sums.get(device, 0) + nbytes
    seen = _pool_devices.setdefault(pool, set())
    seen.update(sums)
    for device in seen:
        _pool_child(pool, device).set(float(sums.get(device, 0)))
    total = float(sum(sums.values()))
    wm = _H_WM[pool]
    if total > (wm.value or 0.0):
        wm.set(total)


def tag(pool, key, nbytes, device="all"):
    """Book ``nbytes`` into ``pool`` under a stable ``key`` (replace
    semantics — re-tagging the same key updates the row).  ``device``
    is the reconciliation class: ``"all"`` for live jax arrays (enters
    the :func:`memory_reconciles` gate), ``"host"``/``"xla"`` for
    bytes outside ``jax.live_arrays()``.  Constant-time no-op with
    metrics disabled."""
    if not _metrics.metrics_enabled():
        return
    if pool not in _TAGGABLE:
        raise ValueError("unknown memory pool %r (taggable: %s)"
                         % (pool, ", ".join(_TAGGABLE)))
    with _lock:
        _entries[(pool, key)] = (int(nbytes), str(device))
        _H_ALLOC[pool].inc()
        _sync_pool_locked(pool)


def tag_tree(pool, key, tree, device="all"):
    """Book the summed ``nbytes`` of every live ``jax.Array`` leaf in
    ``tree`` (host numpy leaves are excluded — they are not in the
    live-array truth).  Returns the booked byte count (0 with metrics
    disabled)."""
    if not _metrics.metrics_enabled():
        return 0
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            try:
                total += int(leaf.nbytes)
            except (AttributeError, TypeError):
                pass
    tag(pool, key, total, device=device)
    return total


def untag(pool, key):
    """Release a booking; safe to call for a key that was never tagged
    (retire paths).  Constant-time no-op with metrics disabled."""
    if not _metrics.metrics_enabled():
        return
    with _lock:
        if _entries.pop((pool, key), None) is not None:
            if pool in _H_FREE:
                _H_FREE[pool].inc()
            _sync_pool_locked(pool)


def ledger_entries():
    """Snapshot of the raw bookings: ``{(pool, key): (nbytes, device)}``."""
    with _lock:
        return dict(_entries)


def _reset_ledger():
    """Drop every booking (called by ``reset_metrics`` so the ledger
    starts over with the registry — a booking that survived a reset
    while its gauges were zeroed would resurrect at the next sample
    and poison the reconcile gate)."""
    with _lock:
        _entries.clear()


def sample():
    """The single ground-truth probe (see module doc): live-array and
    allocator gauges, the ``other`` residual, per-pool re-sync, and
    headroom.  Returns the live-array byte total (None when metrics are
    disabled or jax is unavailable)."""
    if not _metrics.metrics_enabled():
        return None
    import jax

    with _lock:
        booked_all = 0
        for (pool, _key), (nbytes, device) in _entries.items():
            if device == "all":
                booked_all += nbytes
        for pool in {p for (p, _k) in _entries}:
            _sync_pool_locked(pool)
    total = 0
    try:
        arrays = jax.live_arrays()
    except Exception:
        return None
    for a in arrays:
        try:
            total += int(a.nbytes)
        except (AttributeError, TypeError):
            pass
    _M_LIVE.labels("all").set(float(total))
    if total > (_M_LIVE_WM.value or 0.0):
        _M_LIVE_WM.set(float(total))
    _M_POOL.labels("other", "all").set(float(total - booked_all))
    budget = headroom_budget_bytes()
    if budget > 0:
        # floor 1e-6, never exactly 0: the watchdog's skip_zero
        # convention treats an exact-zero gauge as a registry-reset
        # placeholder, and a fully-exhausted device must still fire
        _M_HEADROOM.labels("all").set(
            max(1e-6, 1.0 - total / float(budget)))
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        in_use = stats.get("bytes_in_use")
        if in_use is not None:
            _M_LIVE.labels("dev%d" % d.id).set(float(in_use))
        if "peak_bytes_in_use" in stats:
            _M_PEAK.labels("dev%d" % d.id).set(
                float(stats["peak_bytes_in_use"]))
        limit = stats.get("bytes_limit") or stats.get(
            "bytes_reservable_limit")
        if limit and in_use is not None:
            _M_HEADROOM.labels("dev%d" % d.id).set(
                max(1e-6, 1.0 - float(in_use) / float(limit)))
    return total


def top_buffers(k=None):
    """The ``k`` largest live device buffers (default
    ``MXNET_TPU_MEMORY_TOPK``, 5) as ``{"nbytes", "shape", "dtype"}``
    rows, largest first — the flight-bundle payload that names what to
    evict when ``oom_proximity`` fires."""
    if k is None:
        try:
            k = int(os.environ.get("MXNET_TPU_MEMORY_TOPK", "5"))
        except ValueError:
            k = 5
    try:
        import jax
        arrays = jax.live_arrays()
    except Exception:
        return []
    rows = []
    for a in arrays:
        try:
            rows.append((int(a.nbytes), tuple(int(s) for s in a.shape),
                         str(a.dtype)))
        except (AttributeError, TypeError):
            pass
    rows.sort(key=lambda r: -r[0])
    return [{"nbytes": nb, "shape": list(shape), "dtype": dtype}
            for nb, shape, dtype in rows[:max(int(k), 0)]]


def _fam_children(reg, name):
    fam = reg.get(name)
    if fam is None:
        return {}
    with fam._lock:
        return dict(fam._children)


def memory_report(registry=None):
    """The ledger as a dict (registry reads only, like ``wire_report``):

    ``pools``
        ``{pool: {device: bytes}}`` from ``memory_pool_bytes``.
    ``pool_watermarks`` / ``allocs`` / ``frees``
        per-pool high-water marks and tag/untag event counts.
    ``live_bytes`` / ``live_watermark_bytes``
        the ground truth the ``device='all'`` pools reconcile against.
    ``booked_bytes`` / ``other_bytes``
        sum of ``device='all'`` pool rows (excluding ``other``) and the
        derived residual.
    ``headroom`` / ``headroom_min``
        per-device headroom ratios and their minimum (None when no
        device reported one).
    ``reconciles`` / ``reconcile_tolerance``
        the :func:`memory_reconciles` verdict at the default 5%.
    """
    reg = registry or _metrics.REGISTRY
    if not hasattr(reg, "get"):        # e.g. a FederatedCollector
        reg = _metrics.REGISTRY
    pools = {}
    for (pool, device), child in _fam_children(
            reg, "memory_pool_bytes").items():
        pools.setdefault(pool, {})[device] = child.value
    wm = {p: c.value for (p,), c in _fam_children(
        reg, "memory_pool_watermark_bytes").items()}
    allocs = {p: c.value for (p,), c in _fam_children(
        reg, "memory_pool_alloc_total").items()}
    frees = {p: c.value for (p,), c in _fam_children(
        reg, "memory_pool_free_total").items()}
    live = 0.0
    live_fam = reg.get("memory_live_buffer_bytes")
    if live_fam is not None:
        with live_fam._lock:
            child = live_fam._children.get(("all",))
        if child is not None:
            live = child.value
    wm_fam = reg.get("memory_live_buffer_watermark_bytes")
    live_wm = 0.0
    if wm_fam is not None and wm_fam._default is not None:
        live_wm = wm_fam._default.value
    headroom = {d: c.value for (d,), c in _fam_children(
        reg, "memory_headroom_ratio").items()}
    booked = sum(devs.get("all", 0.0) for pool, devs in pools.items()
                 if pool != "other")
    ok, booked_b, truth_b = memory_reconciles(registry=reg)
    return {
        "pools": pools,
        "pool_watermarks": wm,
        "allocs": allocs,
        "frees": frees,
        "live_bytes": live,
        "live_watermark_bytes": live_wm,
        "booked_bytes": booked,
        "other_bytes": pools.get("other", {}).get("all", 0.0),
        "headroom": headroom,
        "headroom_min": min(headroom.values()) if headroom else None,
        "reconciles": ok,
        "reconcile_tolerance": 0.05,
    }


def memory_reconciles(tol=0.05, registry=None):
    """The falsifiability gate: ``(ok, booked_bytes, truth_bytes)``.
    ``booked`` sums the ``device='all'`` pool rows (excluding the
    derived ``other``); ``truth`` is
    ``memory_live_buffer_bytes{device='all'}`` from the last
    :func:`sample`.  ``ok`` only when BOTH sides are nonzero and agree
    within ``tol`` — an empty ledger must not pass a gate, and neither
    must a ledger that overbooks what the allocator can see."""
    reg = registry or _metrics.REGISTRY
    if not hasattr(reg, "get"):
        reg = _metrics.REGISTRY
    booked = 0.0
    for (pool, device), child in _fam_children(
            reg, "memory_pool_bytes").items():
        if device == "all" and pool != "other":
            booked += child.value
    truth = 0.0
    fam = reg.get("memory_live_buffer_bytes")
    if fam is not None:
        with fam._lock:
            child = fam._children.get(("all",))
        if child is not None:
            truth = child.value
    ok = truth > 0 and booked > 0 and abs(truth - booked) <= tol * truth
    return ok, booked, truth


def format_memory_report(registry=None):
    """:func:`memory_report` as an aligned text table."""
    rep = memory_report(registry)
    lines = ["%-12s %-8s %14s %14s %8s %8s"
             % ("pool", "device", "bytes", "watermark_b", "allocs",
                "frees")]
    order = {p: i for i, p in enumerate(POOLS)}
    for pool in sorted(rep["pools"], key=lambda p: order.get(p, 99)):
        for device in sorted(rep["pools"][pool]):
            lines.append("%-12s %-8s %14d %14d %8d %8d"
                         % (pool, device, rep["pools"][pool][device],
                            rep["pool_watermarks"].get(pool, 0),
                            rep["allocs"].get(pool, 0),
                            rep["frees"].get(pool, 0)))
    lines.append("")
    lines.append("live truth      %14d  (watermark %d)"
                 % (rep["live_bytes"], rep["live_watermark_bytes"]))
    lines.append("booked (all)    %14d  (other residual %+d)"
                 % (rep["booked_bytes"], rep["other_bytes"]))
    for device in sorted(rep["headroom"]):
        lines.append("headroom %-6s %14.3f" % (device,
                                               rep["headroom"][device]))
    lines.append("reconciles      %14s  (tol %.0f%%)"
                 % (rep["reconciles"],
                    100 * rep["reconcile_tolerance"]))
    return "\n".join(lines)


def oom_bundle_extras():
    """Flight-bundle payload for the ``oom_proximity`` watchdog rule:
    the pool ledger snapshot and the top-K largest live buffers, JSON-
    encoded so the manifest carries them verbatim."""
    rep = memory_report()
    return {
        "memory_pools": _json.dumps(rep["pools"], sort_keys=True),
        "memory_other_bytes": rep["other_bytes"],
        "memory_live_bytes": rep["live_bytes"],
        "top_buffers": _json.dumps(top_buffers()),
    }
