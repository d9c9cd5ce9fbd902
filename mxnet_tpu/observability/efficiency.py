"""Compute-efficiency accounting: HLO FLOPs, MFU, and the goodput ledger.

PRs 4-6 built the wall-clock side of the observability plane (what
happened, where the time went); this module is the *what did the
hardware achieve* layer — the denominator that makes the ROADMAP's
"as fast as the hardware allows" claim verifiable.

Three accounts, one falsifiability bar:

- **HLO cost accounting** (:func:`record_compile`): every jit-cache
  compile in ``ShardedTrainer`` records the compiled program's FLOPs /
  bytes-accessed / memory footprint from XLA's own
  ``lowered.compile().cost_analysis()`` into
  ``trainer_compile_flops{cache}`` et al.  The per-step model-FLOPs
  figure (``trainer_step_model_flops``) therefore comes from the
  program XLA actually runs — not a ``6N + 12LTd`` formula — with a
  graceful fallback chain: compiled cost analysis → the cheaper
  pre-compile ``lowered.cost_analysis()`` → a
  ``trainer_compile_cost_unsupported_total{cache}`` marker when the
  backend supports neither.
- **MFU + roofline** (:func:`record_step_rate`):
  ``model_flops_utilization`` = achieved model FLOPs/s ÷ device peak
  (per-device-kind table, ``MXNET_TPU_DEVICE_PEAK_FLOPS`` override),
  plus ``trainer_compile_arithmetic_intensity{cache}`` (FLOPs per byte
  accessed — the roofline x-coordinate).  Federated into
  ``cluster_mfu{member}`` / ``cluster_mfu_min`` by ``federation.py``.
- **Goodput ledger** (:func:`ledger`): accounts every second of a
  ``fit()`` call as ``goodput_productive_seconds_total`` vs
  ``badput_seconds_total{cause=data_wait|recompile|kv_retry|failover|
  checkpoint|other}``.  Productive time is summed step wall minus the
  in-step badput (attribution phases + compile/kv-retry/failover
  counter deltas); whatever the named causes do not cover lands in
  ``cause="other"`` — so the books reconcile against
  ``fit_wall_seconds_total`` within 5% *by construction*, and a tier-1
  test asserts it (the same falsifiability contract as step-time
  attribution).  ``goodput_ratio`` is the derived gauge.

:func:`capture_profile` backs the ``/profile?ms=N`` endpoint
(``exporters.start_metrics_server``): an on-demand ``jax.profiler``
device trace, falling back to the span-ring tail
(``export_chrome_trace``) when the backend profiler is unavailable.
Either way the result is Perfetto-loadable and mergeable with other
processes' dumps via ``merge_chrome_traces``.

Every record path honors the ``MXNET_TPU_METRICS=0`` constant-time
guard, and the gauge families register lazily (first record, not
import) so a process that never measures efficiency never renders
zero-valued ``goodput_ratio`` / ``model_flops_utilization`` rows.
"""

from __future__ import annotations

import os
import threading
import time as _time

from ..base import MXNetError
from . import metrics as _metrics

__all__ = [
    "peak_flops", "record_compile", "record_variant_compile",
    "record_step_rate",
    "model_flops_per_step", "GoodputLedger", "ledger", "BADPUT_CAUSES",
    "efficiency_table", "format_efficiency", "goodput_table",
    "format_goodput", "goodput_reconciles", "capture_profile",
]

#: Every cause ``badput_seconds_total`` can carry.
BADPUT_CAUSES = ("data_wait", "recompile", "kv_retry", "failover",
                 "checkpoint", "other")

# attribution phases that are badput when they show up inside a step
# (compute/placement/kv/flush are the productive work itself)
_IN_STEP_BAD_PHASES = ("data_wait", "checkpoint")

# ----------------------------------------------------------------------
# Device peak FLOP/s

#: Peak dense (bf16) FLOP/s per chip, matched as a lowercase substring
#: of ``device.device_kind`` — first hit wins, so more specific entries
#: come first (public per-chip numbers from the vendor datasheets).
PEAK_FLOPS_TABLE = (
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6", 918e12),             # Trillium / v6e
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
    ("h100", 989e12),           # bf16 dense, SXM
    ("a100", 312e12),
)

_KIND_CACHE = {"v": None}


def peak_flops(device_kind=None):
    """Peak FLOP/s for one device, or None on the CPU.

    ``MXNET_TPU_DEVICE_PEAK_FLOPS`` (raw FLOP/s, e.g. ``197e12``)
    overrides; otherwise the :data:`PEAK_FLOPS_TABLE` row matching
    ``device_kind`` (default: the first visible device's kind).  The
    CPU has no row and no utilization: it gives None, and callers
    record no MFU there.  Any other kind the table does not know is an
    error, not a default — a utilization against a made-up peak reads
    like a measurement."""
    env = os.environ.get("MXNET_TPU_DEVICE_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if device_kind is None:
        device_kind = _KIND_CACHE["v"]
        if device_kind is None:
            import jax

            device_kind = jax.devices()[0].device_kind
            _KIND_CACHE["v"] = device_kind
    kind = str(device_kind).lower()
    if kind == "cpu":
        return None
    for sub, flops in PEAK_FLOPS_TABLE:
        if sub in kind:
            return flops
    raise MXNetError(
        "no peak FLOP/s known for device kind %r: add it to "
        "PEAK_FLOPS_TABLE with its source, or set "
        "MXNET_TPU_DEVICE_PEAK_FLOPS" % (device_kind,))


# ----------------------------------------------------------------------
# Lazily-registered families (see module doc for why not at import)

_LAZY = {}
_LAZY_LOCK = threading.Lock()


def _cost_fams():
    with _LAZY_LOCK:
        f = _LAZY.get("cost")
        if f is None:
            f = {
                "flops": _metrics.gauge(
                    "trainer_compile_flops",
                    "FLOPs of one execution of the compiled program, from "
                    "XLA cost analysis, per jit cache", ["cache"]),
                "bytes": _metrics.gauge(
                    "trainer_compile_bytes_accessed",
                    "Bytes the compiled program reads+writes per execution "
                    "(XLA cost analysis), per jit cache", ["cache"]),
                "mem": _metrics.gauge(
                    "trainer_compile_peak_memory_bytes",
                    "Compiled-program memory footprint: argument + output "
                    "+ XLA temp allocation bytes (memory_analysis), per "
                    "jit cache", ["cache"]),
                "ai": _metrics.gauge(
                    "trainer_compile_arithmetic_intensity",
                    "FLOPs per byte accessed of the compiled program (the "
                    "roofline x-coordinate), per jit cache", ["cache"]),
                "unsupported": _metrics.counter(
                    "trainer_compile_cost_unsupported_total",
                    "Compiles whose backend supports neither compiled nor "
                    "lowered cost analysis (MFU falls back to 0/absent)",
                    ["cache"]),
                "step_flops": _metrics.gauge(
                    "trainer_step_model_flops",
                    "Model FLOPs of ONE optimizer step, derived from the "
                    "latest train-step compile's cost analysis (flops / "
                    "steps-per-dispatch)"),
            }
            _LAZY["cost"] = f
        return f


def _mfu_fams():
    with _LAZY_LOCK:
        f = _LAZY.get("mfu")
        if f is None:
            f = {
                "rate": _metrics.gauge(
                    "model_flops_per_sec",
                    "Achieved model FLOP/s over the most recent step "
                    "(trainer_step_model_flops x steps / wall)"),
                "mfu": _metrics.gauge(
                    "model_flops_utilization",
                    "Model FLOPs utilization: achieved model FLOP/s over "
                    "the device peak (peak_flops(); "
                    "MXNET_TPU_DEVICE_PEAK_FLOPS override); never set "
                    "on the CPU, which has no peak"),
            }
            _LAZY["mfu"] = f
        return f


def _goodput_fams():
    with _LAZY_LOCK:
        f = _LAZY.get("goodput")
        if f is None:
            f = {
                "productive": _metrics.counter(
                    "goodput_productive_seconds_total",
                    "fit() wall seconds spent on productive training work "
                    "(step wall minus in-step badput)"),
                "bad": _metrics.counter(
                    "badput_seconds_total",
                    "fit() wall seconds lost to one badput cause; "
                    "productive + all causes reconcile with "
                    "fit_wall_seconds_total within 5% (tier-1-enforced)",
                    ["cause"]),
                "wall": _metrics.counter(
                    "fit_wall_seconds_total",
                    "Total fit() wall seconds the goodput ledger "
                    "accounted"),
                "ratio": _metrics.gauge(
                    "goodput_ratio",
                    "Productive fraction of the last closed fit() ledger "
                    "(goodput_productive / fit_wall)"),
            }
            _LAZY["goodput"] = f
        return f


# ----------------------------------------------------------------------
# HLO cost accounting


def _first_cost(obj):
    """Normalize a cost_analysis() result: newer jax returns a list of
    per-program dicts, older a plain dict."""
    if isinstance(obj, (list, tuple)):
        return obj[0] if obj else None
    return obj if isinstance(obj, dict) else None


def record_compile(cache, lower, steps=1):
    """Record HLO cost analysis for one jit-cache compile.

    ``lower`` is a zero-arg callable returning a ``jax.stages.Lowered``
    for the traced call (the trainer lowers the raw jit under its mesh
    with the first call's arguments).  ``steps`` is how many optimizer
    steps one dispatch advances (``pipeline_fn(n)`` scans ``n``); pass
    ``steps=0`` for programs that are not a training step (the eval
    forward) — cost families are still recorded, but
    ``trainer_step_model_flops`` is left alone.

    Fallback chain: ``lowered.compile().cost_analysis()`` (+
    ``memory_analysis()``) → ``lowered.cost_analysis()`` (no peak
    memory) → ``trainer_compile_cost_unsupported_total{cache}``.
    Never raises; constant-time guard when metrics are disabled.
    ``MXNET_TPU_COST_ANALYSIS=0`` skips entirely, ``=lowered`` skips
    the AOT compile (cheaper, no memory footprint).  The lowering and
    the AOT compile run under the start-up scope
    ``trainer.cost_analysis`` (``program`` = ``cache``;
    :mod:`mxnet_tpu.compile_cache`), which says what they cost.
    """
    if not _metrics.metrics_enabled():
        return
    mode = os.environ.get("MXNET_TPU_COST_ANALYSIS", "compiled").lower()
    if mode in ("0", "false", "off", "no"):
        return
    from .. import compile_cache as _compile_cache

    fams = _cost_fams()
    cost = mem = None
    with _compile_cache.scope("trainer.cost_analysis", cache):
        try:
            lowered = lower()
        except Exception:
            fams["unsupported"].labels(cache).inc()
            return
        if mode != "lowered":
            try:
                compiled = lowered.compile()
                cost = _first_cost(compiled.cost_analysis())
                try:
                    mem = compiled.memory_analysis()
                except Exception:
                    mem = None
            except Exception:
                cost = None
    if cost is None:
        try:
            cost = _first_cost(lowered.cost_analysis())
        except Exception:
            cost = None
    if not cost:
        fams["unsupported"].labels(cache).inc()
        return
    flops = float(cost.get("flops", 0.0) or 0.0)
    nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    fams["flops"].labels(cache).set(flops)
    fams["bytes"].labels(cache).set(nbytes)
    if nbytes > 0:
        fams["ai"].labels(cache).set(flops / nbytes)
    if mem is not None:
        try:
            footprint = float(
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0))
        except Exception:
            footprint = 0.0
        if footprint > 0:
            fams["mem"].labels(cache).set(footprint)
            # book the XLA footprint into the memory ledger (allocator-
            # side bytes, outside the live-array truth → device="xla")
            from . import memory as _memory
            _memory.tag("compile", cache, int(footprint), device="xla")
    if steps and flops > 0:
        fams["step_flops"].set(flops / float(steps))


def record_variant_compile(op_name, variant, fn, *args, **kwargs):
    """Record the compile cost of one body of a hot path (a kernel or
    its reference body) under the cache key ``variant:<op>:<variant>``.

    The per-body ``trainer_compile_flops{cache}`` row is how MFU
    attribution credits a kernel-level win to the body that earned it
    — the attention kernels gate on parity plus this row, never on a
    quoted CPU timing.  ``fn(*args, **kwargs)`` is
    jit-lowered for analysis only; nothing executes.  Never raises
    (:func:`record_compile`'s fallback chain applies).
    """
    import jax

    record_compile("variant:%s:%s" % (op_name, variant),
                   lambda: jax.jit(fn).lower(*args, **kwargs), steps=0)


def model_flops_per_step(registry=None):
    """The latest cost-analysis-derived model FLOPs per optimizer step,
    or None when no train-step compile has been accounted (backend
    unsupported, metrics off, or nothing compiled yet)."""
    reg = registry or _metrics.REGISTRY
    fam = reg.get("trainer_step_model_flops")
    if fam is None or fam._default is None:
        return None
    v = fam._default.value
    return v if v > 0 else None


def record_step_rate(steps, seconds, peak=None):
    """Update ``model_flops_per_sec`` / ``model_flops_utilization``
    from ``steps`` optimizer steps that took ``seconds`` of wall.
    No-op until a train-step compile has recorded its FLOPs (the MFU
    numerator comes from the compiled program, never a formula)."""
    if not _metrics.metrics_enabled():
        return
    if seconds <= 0.0:
        return
    mfps = model_flops_per_step()
    if not mfps:
        return
    fams = _mfu_fams()
    achieved = mfps * steps / seconds
    fams["rate"].set(achieved)
    pk = peak if peak else peak_flops()
    if pk:
        fams["mfu"].set(achieved / pk)


# ----------------------------------------------------------------------
# Goodput ledger

# counter families whose in-fit deltas become badput causes
_DELTA_SOURCES = (
    ("recompile", "trainer_compile_seconds", "hist"),
    ("kv_retry", "kv_retry_seconds_total", "counter"),
    ("failover", "kv_failover_seconds_total", "counter"),
)


class GoodputLedger(object):
    """Books one ``fit()``'s wall seconds into productive vs badput.

    Construction snapshots the compile/kv-retry/failover second
    counters; :meth:`step` feeds each step's wall + attribution phases;
    :meth:`bad` books out-of-step badput (the epoch-end checkpoint);
    :meth:`close` settles: counter deltas become in-step badput
    (compiles, RPC retries and failovers all happen inside step
    windows), productive = step wall minus in-step badput (clamped at
    0), and the unaccounted remainder of ``wall_s`` lands in
    ``cause="other"`` — eval passes, iterator resets, epoch plumbing.
    Books that overcount are falsifiable: the causes can only exceed
    wall if a timer double-books, and the 5% reconciliation test
    catches exactly that."""

    __slots__ = ("_reg", "_base", "_step_wall", "_in_step", "_out")

    def __init__(self, registry=None):
        self._reg = registry or _metrics.REGISTRY
        self._base = self._snapshot()
        self._step_wall = 0.0
        self._in_step = {}
        self._out = {}

    def _snapshot(self):
        snap = {}
        for cause, fam_name, kind in _DELTA_SOURCES:
            fam = self._reg.get(fam_name)
            total = 0.0
            if fam is not None:
                try:
                    if kind == "hist":
                        with fam._lock:
                            total = sum(c.sum
                                        for c in fam._children.values())
                        if fam._default is not None:
                            total += fam._default.sum
                    else:
                        total = fam.total()
                except Exception:
                    total = 0.0
            snap[cause] = total
        return snap

    def step(self, wall_s, phases=None):
        """Book one step/flush: its wall seconds plus the attribution
        phase dict ``StepAttribution.close`` returned (data-wait and
        in-step checkpoint seconds are badput)."""
        self._step_wall += wall_s
        if phases:
            for cause in _IN_STEP_BAD_PHASES:
                v = phases.get(cause)
                if v:
                    self._in_step[cause] = self._in_step.get(cause, 0.0) + v

    def bad(self, cause, seconds):
        """Book out-of-step badput (e.g. the epoch-end checkpoint)."""
        if seconds > 0.0:
            self._out[cause] = self._out.get(cause, 0.0) + seconds

    def close(self, wall_s):
        """Settle the books over ``wall_s`` fit wall seconds; records
        the goodput/badput counters + ``goodput_ratio`` and returns the
        settled dict (None when metrics got disabled mid-run)."""
        if not _metrics.metrics_enabled():
            return None
        now = self._snapshot()
        in_step = dict(self._in_step)
        for cause, _, _ in _DELTA_SOURCES:
            d = max(now[cause] - self._base[cause], 0.0)
            if d > 0.0:
                in_step[cause] = in_step.get(cause, 0.0) + d
        productive = max(self._step_wall - sum(in_step.values()), 0.0)
        causes = dict(in_step)
        for cause, v in self._out.items():
            causes[cause] = causes.get(cause, 0.0) + v
        other = wall_s - productive - sum(causes.values())
        if other > 0.0:
            causes["other"] = other
        fams = _goodput_fams()
        fams["productive"].inc(productive)
        fams["wall"].inc(wall_s)
        for cause, v in sorted(causes.items()):
            if v > 0.0:
                fams["bad"].labels(cause).inc(v)
        ratio = productive / wall_s if wall_s > 0 else 0.0
        fams["ratio"].set(ratio)
        return {"wall": wall_s, "productive": productive,
                "badput": causes, "goodput_ratio": ratio}


class _NullLedger(object):
    """Shared no-op ledger for the metrics-disabled path: no clock
    reads, no snapshots, no allocation."""

    __slots__ = ()

    def step(self, wall_s, phases=None):
        pass

    def bad(self, cause, seconds):
        pass

    def close(self, wall_s):
        return None


_NULL_LEDGER = _NullLedger()


def ledger(registry=None):
    """A fresh :class:`GoodputLedger` — or the shared no-op singleton
    when ``MXNET_TPU_METRICS=0`` (constant-time guard)."""
    if not _metrics.metrics_enabled():
        return _NULL_LEDGER
    return GoodputLedger(registry)


# ----------------------------------------------------------------------
# Tables / reconciliation


def efficiency_table(registry=None):
    """Per-cache HLO cost rows ``(cache, flops, bytes, intensity,
    footprint_bytes)`` sorted by FLOPs, plus trailing
    ``("model_flops/step", v)`` / ``("mfu", v)`` summary pairs (None
    when unmeasured)."""
    reg = registry or _metrics.REGISTRY

    def _children(name):
        fam = reg.get(name)
        if fam is None:
            return {}
        with fam._lock:
            return {k[0]: c.value for k, c in fam._children.items()}

    flops = _children("trainer_compile_flops")
    nbytes = _children("trainer_compile_bytes_accessed")
    ai = _children("trainer_compile_arithmetic_intensity")
    mem = _children("trainer_compile_peak_memory_bytes")
    rows = [(c, v, nbytes.get(c), ai.get(c), mem.get(c))
            for c, v in flops.items()]
    rows.sort(key=lambda r: -r[1])

    def _gauge(name):
        fam = reg.get(name)
        if fam is None or fam._default is None:
            return None
        v = fam._default.value
        return v if v > 0 else None

    summary = [("model_flops/step", _gauge("trainer_step_model_flops")),
               ("model_flops/s", _gauge("model_flops_per_sec")),
               ("mfu", _gauge("model_flops_utilization"))]
    return rows, summary


def format_efficiency(registry=None):
    """:func:`efficiency_table` rendered as an aligned text table."""
    rows, summary = efficiency_table(registry)
    lines = ["%-12s %14s %14s %10s %14s"
             % ("cache", "flops", "bytes", "flops/B", "mem_bytes")]
    for cache, fl, nb, ai, mem in rows:
        lines.append("%-12s %14.4g %14s %10s %14s"
                     % (cache, fl,
                        "-" if nb is None else "%.4g" % nb,
                        "-" if ai is None else "%.3f" % ai,
                        "-" if mem is None else "%.4g" % mem))
    if not rows:
        lines.append("(no compile cost recorded)")
    for name, v in summary:
        lines.append("%-18s %s" % (name + ":",
                                   "-" if v is None else "%.6g" % v))
    return "\n".join(lines)


def goodput_table(registry=None):
    """The goodput books as rows ``(cause, seconds, share-of-wall)``:
    ``productive`` first, then each badput cause by size, then a
    trailing ``("wall", wall, 1.0)`` row."""
    reg = registry or _metrics.REGISTRY

    def _total(name):
        fam = reg.get(name)
        return fam.total() if fam is not None else 0.0

    wall = _total("fit_wall_seconds_total")
    rows = [("productive", _total("goodput_productive_seconds_total"),
             None)]
    fam = reg.get("badput_seconds_total")
    if fam is not None:
        with fam._lock:
            bad = [(k[0], c.value) for k, c in fam._children.items()
                   if c.value > 0]
        bad.sort(key=lambda r: -r[1])
        rows.extend((c, v, None) for c, v in bad)
    rows = [(c, v, (v / wall if wall > 0 else None)) for c, v, _ in rows]
    rows.append(("wall", wall, 1.0 if wall > 0 else None))
    return rows


def format_goodput(registry=None):
    """:func:`goodput_table` rendered as an aligned text table."""
    lines = ["%-12s %12s %7s" % ("account", "seconds", "share")]
    for cause, v, share in goodput_table(registry):
        lines.append("%-12s %12.4f %7s"
                     % (cause, v,
                        "-" if share is None else "%5.1f%%" % (100 * share)))
    return "\n".join(lines)


def goodput_reconciles(tol=0.05, registry=None):
    """The falsifiability gate: ``(ok, wall, accounted)`` where
    ``accounted`` = productive + every badput cause and ``ok`` means it
    matches ``fit_wall_seconds_total`` within ``tol`` (False when no
    ledger closed)."""
    reg = registry or _metrics.REGISTRY

    def _total(name):
        fam = reg.get(name)
        return fam.total() if fam is not None else 0.0

    wall = _total("fit_wall_seconds_total")
    accounted = (_total("goodput_productive_seconds_total")
                 + _total("badput_seconds_total"))
    ok = wall > 0 and abs(accounted - wall) <= tol * wall
    return ok, wall, accounted


# ----------------------------------------------------------------------
# On-demand device profiling (the /profile endpoint's engine)

_PROFILE_LOCK = threading.Lock()

#: ``/profile?ms=N`` cap — a scrape must not hold the profiler hostage.
PROFILE_MS_CAP = 10000


def capture_profile(duration_ms=500):
    """Capture a ``duration_ms`` device trace and return
    ``(trace_dict, source)`` where ``source`` is ``"jax_profiler"`` or
    ``"span_ring"``.

    Primary: ``jax.profiler`` start/stop into a temp dir, returning the
    gunzipped chrome-trace JSON (device + host tracks, Perfetto-
    loadable).  Fallback — profiler unavailable, another capture in
    flight, or no trace produced: the span ring buffer tail via
    :func:`~.exporters.export_chrome_trace`.  Both shapes carry
    ``traceEvents`` so :func:`~.exporters.merge_chrome_traces` accepts
    them unchanged."""
    import glob
    import gzip
    import json
    import shutil
    import tempfile

    ms = max(1, min(int(duration_ms), PROFILE_MS_CAP))
    trace = None
    if _PROFILE_LOCK.acquire(blocking=False):
        tmpdir = tempfile.mkdtemp(prefix="mxtpu_profile_")
        try:
            import jax

            jax.profiler.start_trace(tmpdir)
            try:
                _time.sleep(ms / 1000.0)
            finally:
                jax.profiler.stop_trace()
            dumps = sorted(glob.glob(
                os.path.join(tmpdir, "**", "*.trace.json.gz"),
                recursive=True), key=os.path.getmtime)
            if dumps:
                with gzip.open(dumps[-1], "rt", encoding="utf-8") as f:
                    candidate = json.load(f)
                if candidate.get("traceEvents"):
                    trace = candidate
        except Exception:
            trace = None
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
            _PROFILE_LOCK.release()
    if trace is not None:
        return trace, "jax_profiler"
    from . import exporters as _exporters

    return _exporters.export_chrome_trace(), "span_ring"
