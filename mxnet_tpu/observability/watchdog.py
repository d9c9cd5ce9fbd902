"""Watchdog: declarative SLO rules evaluated over the metrics plane.

The observability plane (PRs 4-5) is passive — it records and renders,
and a human decides whether the run is healthy.  The watchdog closes
that loop: a set of declarative :class:`Rule`\\ s is evaluated against a
metrics source — the local registry, a :class:`~.federation.
FederatedCollector` (cluster-wide), or raw exposition text — and the
firing set is exposed three ways:

- as metrics: ``cluster_alert{alert,severity}`` is 1 while firing and
  ``cluster_alerts_fired_total{alert}`` counts rising edges (so "fired
  exactly once" is a testable statement);
- as JSON: the ``/alerts`` endpoint (``start_metrics_server(...,
  watchdog=)`` or :meth:`Watchdog.serve`) evaluates on GET and returns
  the firing list;
- as flight-recorder bundles: a rule with ``severity="terminal"``
  routes its rising edge through :func:`~.flight_recorder.
  record_failure` — one postmortem bundle per firing episode, with the
  span tail and metrics snapshot that existed at the transition.

Three rule kinds cover the SLO shapes the plane needs:

``threshold``
    the stat compared against ``threshold``, optionally sustained for
    ``for_s`` seconds before firing (gauge-style conditions: heartbeat
    age, replication lag, straggler skew).
``increase``
    the stat's increase over the trailing ``window_s`` compared against
    ``threshold`` — the burn-rate window for counters that should stay
    flat (``spans_dropped_total`` rising, scrape errors climbing).
``regression``
    the stat compared against ``factor ×`` its own rolling baseline
    (mean of the samples in the trailing ``window_s``, needing
    ``min_samples`` history) — step p99 regression against the run's
    recent self.

Stats are computed from parsed exposition text, so local and federated
sources evaluate identically: ``value``/``sum``/``max``/``min`` over
matching series, ``count``/``avg``/``p50``/``p90``/``p99`` over
histograms (bucket-resolution quantiles, matching
``metrics.Histogram.percentile``).  ``selector={"kind": "shard"}``
restricts matching to series carrying those label values.

With ``MXNET_TPU_METRICS=0``, :meth:`Watchdog.evaluate` returns without
scraping anything — the same constant-time-guard contract as the rest
of the plane.  ``MXNET_TPU_WATCHDOG=1`` makes ``_async_ps_main`` server
processes run a default-rule watchdog next to their ``/metrics``
endpoint; ``MXNET_TPU_WATCHDOG_INTERVAL`` paces the background loop.
"""

from __future__ import annotations

import json
import os
import threading
import time as _time

from .events import emit as _emit_event
from . import federation as _federation
from . import flight_recorder as _flight
from . import metrics as _metrics

__all__ = ["Rule", "Alert", "Watchdog", "default_rules"]

_SEVERITIES = ("info", "warning", "critical", "terminal")
_KINDS = ("threshold", "increase", "regression")
_OPS = {
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
}

_M_ALERT = _metrics.gauge(
    "cluster_alert", "1 while the named watchdog alert is firing",
    ["alert", "severity"])
_M_FIRED = _metrics.counter(
    "cluster_alerts_fired_total",
    "Watchdog alert rising edges (resolved-to-firing transitions)",
    ["alert"])
_M_EVALS = _metrics.counter(
    "watchdog_evaluations_total", "Watchdog rule-evaluation passes")


def _interval_s():
    try:
        return float(os.environ.get("MXNET_TPU_WATCHDOG_INTERVAL", "10"))
    except ValueError:
        return 10.0


def _env_float(name, default):
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


# -- stat extraction from parsed exposition --------------------------------

def _matching(fam, metric, selector, suffix=""):
    """Values of series named ``metric + suffix`` whose labels contain
    ``selector``; yields (label_dict, float_value)."""
    want = metric + suffix
    for name, labels, value in fam["series"]:
        if name != want:
            continue
        ld = _federation._label_dict(labels or "")
        if selector and any(ld.get(k) != str(v)
                            for k, v in selector.items()):
            continue
        try:
            yield ld, float(value)
        except ValueError:
            continue


def _histogram_quantile(fam, metric, selector, q):
    """Bucket-resolution quantile across every matching series (same
    semantics as ``metrics.Histogram.percentile``: the upper bound of
    the bucket holding the q-th observation)."""
    cum = {}
    for ld, v in _matching(fam, metric, selector, "_bucket"):
        le = ld.get("le", "")
        try:
            ub = float("inf") if le == "+Inf" else float(le)
        except ValueError:
            continue
        cum[ub] = cum.get(ub, 0.0) + v
    if not cum:
        return None
    bounds = sorted(cum)
    total = cum[bounds[-1]]           # +Inf (or widest) cumulative count
    if total <= 0:
        return None
    rank = q * total
    # cumulative counts were summed across series per bound, so they
    # remain cumulative in bound order
    for ub in bounds:
        if cum[ub] >= rank:
            return ub
    return bounds[-1]


def _stat_of(fams, metric, stat, selector):
    """Evaluate ``stat`` for ``metric`` from parsed exposition ``fams``;
    None when the metric (or the requested slice) is absent."""
    fam = fams.get(metric)
    if fam is None:
        return None
    if stat in ("p50", "p90", "p99"):
        return _histogram_quantile(fam, metric, selector,
                                   float(stat[1:]) / 100.0)
    if fam.get("type") == "histogram" or stat in ("count", "avg"):
        sums = [v for _, v in _matching(fam, metric, selector, "_sum")]
        counts = [v for _, v in _matching(fam, metric, selector, "_count")]
        if stat == "count":
            return sum(counts) if counts else None
        if stat == "avg":
            return (sum(sums) / sum(counts)
                    if counts and sum(counts) else None)
        return sum(sums) if sums else None      # "sum"/"value" on a histogram
    vals = [v for _, v in _matching(fam, metric, selector)]
    if not vals:
        return None
    if stat == "max":
        return max(vals)
    if stat == "min":
        return min(vals)
    return sum(vals)                             # "value" / "sum"


class Rule(object):
    """One declarative alert rule (see module doc for the kinds).

    Rules are stateful — burn-rate and regression windows live on the
    instance — so a rule object belongs to exactly one
    :class:`Watchdog`.
    """

    def __init__(self, name, metric, *, stat="value", selector=None,
                 op=">", threshold=0.0, kind="threshold", window_s=300.0,
                 for_s=0.0, factor=2.0, min_samples=3,
                 severity="warning", description="", direction="up",
                 skip_zero=False):
        if kind not in _KINDS:
            raise ValueError("rule kind must be one of %s, got %r"
                             % (_KINDS, kind))
        if severity not in _SEVERITIES:
            raise ValueError("severity must be one of %s, got %r"
                             % (_SEVERITIES, severity))
        if op not in _OPS:
            raise ValueError("op must be one of %s, got %r"
                             % (sorted(_OPS), op))
        if direction not in ("up", "down"):
            raise ValueError("direction must be 'up' or 'down', got %r"
                             % (direction,))
        self.name = name
        self.metric = metric
        self.stat = stat
        self.selector = dict(selector) if selector else None
        self.op = op
        self.threshold = float(threshold)
        self.kind = kind
        self.window_s = float(window_s)
        self.for_s = float(for_s)
        self.factor = float(factor)
        self.min_samples = int(min_samples)
        self.severity = severity
        self.description = description
        # direction="down": a regression fires when the value FALLS below
        # baseline/factor (throughput-style metrics — MFU, goodput —
        # where lower is worse); "up" keeps the latency-style raw >
        # factor*baseline.  skip_zero treats an exact-zero sample like an
        # absent metric: gauges that exist but have not measured yet
        # (a lazily-registered family zeroed by a registry reset) must
        # neither fire nor poison the baseline.
        self.direction = direction
        self.skip_zero = bool(skip_zero)
        # value_fn seam: when set (slo.BurnRateRule), the rule derives
        # its own raw quantity from the parsed exposition instead of
        # the stock _stat_of(metric, stat, selector) lookup
        self.value_fn = None
        # bundle_extra_fn seam: a terminal rule may attach extra
        # diagnosis payload to its rising-edge flight bundle (the
        # oom_proximity rule ships the pool ledger + top-K buffers)
        self.bundle_extra_fn = None
        # evaluation state
        self.firing = False
        self.value = None          # the quantity last compared
        self.baseline = None       # regression rules: the rolling mean
        self._samples = []         # [(t, raw_value)] within window_s
        self._true_since = None

    def _condition(self, raw, now):
        """Update windows, return (quantity, condition_bool)."""
        if self.kind == "threshold":
            return raw, _OPS[self.op](raw, self.threshold)
        self._samples = [(t, v) for t, v in self._samples
                         if now - t <= self.window_s]
        if self.kind == "increase":
            base = self._samples[0][1] if self._samples else raw
            self._samples.append((now, raw))
            delta = raw - base
            return delta, _OPS[self.op](delta, self.threshold)
        # regression: compare against the rolling mean of PRIOR samples
        prior = [v for _, v in self._samples]
        self._samples.append((now, raw))
        if len(prior) < self.min_samples:
            return raw, False
        self.baseline = sum(prior) / len(prior)
        if self.direction == "down":
            return raw, raw * self.factor < self.baseline
        return raw, raw > self.factor * self.baseline

    def update(self, raw, now):
        """Feed one evaluation; returns whether the rule is firing."""
        if raw is not None and self.skip_zero and float(raw) == 0.0:
            raw = None
        if raw is None:
            # metric absent: resolve and forget sustained-state (a
            # vanished series must not keep an alert pinned)
            self.value = None
            self._true_since = None
            self.firing = False
            return False
        self.value, cond = self._condition(float(raw), now)
        if not cond:
            self._true_since = None
            self.firing = False
            return False
        if self._true_since is None:
            self._true_since = now
        self.firing = (now - self._true_since) >= self.for_s
        return self.firing


class Alert(object):
    """One firing alert: the rule's identity plus the evaluation that
    tripped it."""

    __slots__ = ("name", "severity", "value", "threshold", "since",
                 "description")

    def __init__(self, rule, now):
        self.name = rule.name
        self.severity = rule.severity
        self.value = rule.value
        if rule.kind == "regression" and rule.baseline is not None:
            self.threshold = (rule.baseline / rule.factor
                              if getattr(rule, "direction", "up") == "down"
                              else rule.factor * rule.baseline)
        else:
            self.threshold = rule.threshold
        self.since = now
        self.description = rule.description

    def as_dict(self):
        return {"name": self.name, "severity": self.severity,
                "value": self.value, "threshold": self.threshold,
                "since": self.since, "description": self.description}


class Watchdog(object):
    """Evaluate rules against a metrics source (see module doc).

    ``source`` may be None (the process-global registry), any object
    with a ``render()`` method (a :class:`Registry` or a
    :class:`FederatedCollector`), exposition text, or a callable
    returning exposition text.
    """

    def __init__(self, rules=None, source=None):
        self.rules = list(rules) if rules is not None else default_rules()
        self.source = source
        self._active = {}              # rule name -> Alert
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = None

    def _scrape_text(self):
        src = self.source
        if src is None:
            return _metrics.REGISTRY.render()
        if callable(getattr(src, "render", None)):
            return src.render()
        if callable(src):
            return src()
        return str(src)

    def evaluate(self, now=None):
        """One evaluation pass; returns the list of active
        :class:`Alert`\\ s.  ``now`` (monotonic seconds) is injectable
        so tests can drive the burn-rate/sustain windows."""
        if not _metrics.metrics_enabled():
            return []
        if now is None:
            now = _time.monotonic()
        fams = _federation._parse(self._scrape_text())
        _M_EVALS.inc()
        with self._lock:
            for rule in self.rules:
                if rule.value_fn is not None:
                    raw = rule.value_fn(fams)
                else:
                    raw = _stat_of(fams, rule.metric, rule.stat,
                                   rule.selector)
                was = rule.firing
                firing = rule.update(raw, now)
                if firing and not was:
                    alert = Alert(rule, now)
                    self._active[rule.name] = alert
                    _M_ALERT.labels(rule.name, rule.severity).set(1)
                    _M_FIRED.labels(rule.name).inc()
                    _emit_event("alert", name=rule.name,
                                 severity=rule.severity, state="firing",
                                 value=rule.value)
                    if rule.severity == "terminal":
                        # one bundle per firing episode: the edge, not
                        # every evaluation while it stays red
                        extra = {}
                        if rule.bundle_extra_fn is not None:
                            try:
                                extra = dict(rule.bundle_extra_fn())
                            except Exception:
                                # diagnosis payload must never block
                                # the bundle itself
                                extra = {}
                        _flight.record_failure(
                            "watchdog.%s" % rule.name, None,
                            alert=alert.as_dict(), **extra)
                elif firing:
                    self._active[rule.name].value = rule.value
                elif was:
                    self._active.pop(rule.name, None)
                    _M_ALERT.labels(rule.name, rule.severity).set(0)
                    _emit_event("alert", name=rule.name,
                                 severity=rule.severity,
                                 state="resolved")
            return list(self._active.values())

    def firing(self):
        """The currently-active alerts (no evaluation pass)."""
        with self._lock:
            return list(self._active.values())

    def alerts_json(self, evaluate=False):
        """JSON-safe dict for the ``/alerts`` endpoint; ``evaluate=True``
        runs a pass first so a bare GET drives the engine."""
        if evaluate:
            self.evaluate()
        with self._lock:
            active = list(self._active.values())
        return {"alerts": [a.as_dict() for a in active],
                "rules": len(self.rules),
                "firing": len(active)}

    def render_alerts(self):
        """The ``/alerts`` body as a JSON string (evaluates first)."""
        return json.dumps(self.alerts_json(evaluate=True), sort_keys=True)

    # -- background loop ----------------------------------------------
    def start(self, interval_s=None):
        """Evaluate every ``interval_s`` (default
        ``MXNET_TPU_WATCHDOG_INTERVAL``) on a daemon thread."""
        interval = _interval_s() if interval_s is None else float(interval_s)

        def loop():
            while not self._stop.wait(interval):
                try:
                    self.evaluate()
                except Exception:
                    # the watchdog must never take down what it watches
                    pass

        with self._lock:
            if self._thread is not None:
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=loop, name="mxtpu-watchdog", daemon=True)
            self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        with self._lock:
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=5)

    def serve(self, port=None, addr="127.0.0.1", registry=None):
        """Serve ``/metrics`` + ``/alerts`` on one endpoint (a
        :class:`~.exporters.MetricsServer` with this watchdog wired)."""
        from . import exporters as _exporters

        return _exporters.start_metrics_server(
            port=port, addr=addr, registry=registry, watchdog=self)


def _wire_bytes_per_step(fams):
    """Raw quantity for ``wire_bytes_regression``: total kvstore wire
    bytes divided by trainer steps (both monotonic counters, so the
    ratio is a stable per-step quantity the rolling baseline can hold).
    None while nothing crossed the wire or no step completed — server
    processes and fresh registries must neither fire nor seed the
    baseline."""
    total = _stat_of(fams, "kv_wire_bytes_total", "value", None)
    steps = _stat_of(fams, "trainer_step_seconds", "count", None)
    if not total or not steps:
        return None
    return total / steps


def _wire_codec_share(fams):
    """Raw quantity for ``wire_codec_share``: encode+decode wall as a
    share of the measured step wall.  None before any step completes."""
    codec = _stat_of(fams, "kv_wire_codec_seconds", "sum", None)
    wall = _stat_of(fams, "trainer_step_seconds", "sum", None)
    if codec is None or not wall:
        return None
    return codec / wall


def _memory_bundle_extras():
    """Diagnosis payload for the ``oom_proximity`` flight bundle: the
    pool ledger snapshot and top-K largest live buffers."""
    from . import memory as _memory

    return _memory.oom_bundle_extras()


def default_rules():
    """The stock SLO rule set: trace-buffer pressure, heartbeat age,
    replication lag, step-p99 self-regression, (when evaluated over a
    federated source) straggler skew, MFU self-regression, the goodput
    floor, the serving tier's request-p99 SLO + queue-saturation
    rules, the wire-bandwidth pair (bytes/step rolling-baseline
    regression at terminal severity + codec-share threshold), the
    memory/capacity pair (``oom_proximity`` terminal on headroom,
    ``kv_cache_pressure`` warning on block-pool occupancy), and the
    error-budget burn-rate rules
    (:func:`~.slo.burn_rules`: fast-burn terminal, slow-burn warning,
    for each default SLO), and the durable-state quarantine rule (any
    ``snapshot_quarantined_total`` increase is corrupt training state
    on disk).  Thresholds come from the
    ``MXNET_TPU_WATCHDOG_*`` / ``MXNET_TPU_SLO_*`` env rows
    (docs/env_vars.md)."""
    from . import slo as _slo   # function-level: slo imports this module

    dead_after = _env_float("MXNET_TPU_PS_DEAD_AFTER", 30.0)
    rules = [
        Rule("spans_dropped", "spans_dropped_total", kind="increase",
             threshold=0.0, window_s=300.0, severity="warning",
             description="trace ring buffer is evicting unexported "
                         "spans (raise MXNET_TPU_METRICS_TRACE_BUFFER "
                         "or export more often)"),
        Rule("heartbeat_stale", "kv_heartbeat_age_seconds", stat="max",
             threshold=dead_after, severity="critical",
             description="a server has not answered heartbeats for "
                         "longer than MXNET_TPU_PS_DEAD_AFTER"),
        Rule("replication_lag", "kv_replication_lag", stat="max",
             threshold=_env_float("MXNET_TPU_WATCHDOG_REPL_LAG", 64.0),
             for_s=_env_float("MXNET_TPU_WATCHDOG_REPL_LAG_FOR_S", 0.0),
             severity="warning",
             description="a follower is falling behind the primary's "
                         "replication log"),
        Rule("step_p99_regression", "trainer_step_seconds", stat="p99",
             kind="regression",
             factor=_env_float("MXNET_TPU_WATCHDOG_STEP_P99_FACTOR", 2.0),
             window_s=600.0, severity="warning",
             description="step p99 regressed against its own rolling "
                         "baseline"),
        Rule("straggler", "cluster_straggler_skew", stat="max",
             threshold=_env_float("MXNET_TPU_WATCHDOG_STRAGGLER_SKEW",
                                  2.0),
             severity="critical",
             description="the slowest shard/worker's latency skew "
                         "exceeds the straggler threshold "
                         "(cluster_straggler_info names it)"),
        # efficiency rules (observability/efficiency.py): both gauges are
        # lazily measured, so skip_zero keeps a not-yet-measuring (or
        # registry-reset) process from firing on the zero placeholder
        Rule("mfu_regression", "model_flops_utilization",
             kind="regression", direction="down", skip_zero=True,
             factor=_env_float("MXNET_TPU_WATCHDOG_MFU_FACTOR", 1.5),
             window_s=600.0, severity="warning",
             description="model FLOPs utilization fell below its own "
                         "rolling baseline / MXNET_TPU_WATCHDOG_MFU_"
                         "FACTOR (hardware efficiency regressed)"),
        Rule("snapshot_quarantine", "snapshot_quarantined_total",
             kind="increase",
             threshold=_env_float(
                 "MXNET_TPU_WATCHDOG_QUARANTINE_MAX", 0.0),
             window_s=3600.0, severity="critical",
             description="durable state (a snapshot or checkpoint) "
                         "failed integrity verification and was "
                         "quarantined — the restore ladder is burning "
                         "through history; the snapshot_quarantined "
                         "flight bundle names the corrupt file"),
        Rule("goodput_floor", "goodput_ratio", op="<", skip_zero=True,
             threshold=_env_float("MXNET_TPU_WATCHDOG_GOODPUT_FLOOR",
                                  0.5),
             severity="warning",
             description="the last fit's goodput ratio fell below the "
                         "floor — badput_seconds_total{cause} says "
                         "where the wall time went"),
        # streaming data plane (parallel/trainer.py fit_stream): each
        # stall is one bounded-retry episode, so a sustained run of them
        # inside the window means the source is down, not hiccuping
        Rule("stream_stall", "stream_stalls_total", kind="increase",
             threshold=_env_float("MXNET_TPU_WATCHDOG_STREAM_STALLS",
                                  3.0),
             window_s=_env_float(
                 "MXNET_TPU_WATCHDOG_STREAM_STALLS_WINDOW_S", 300.0),
             severity="critical",
             description="the streaming source kept stalling past the "
                         "bounded-staleness limit — fit_stream is in "
                         "its retry/backoff loop, not making progress"),
        # serving-tier SLOs (serving/scheduler.py)
        Rule("request_p99_slo", "serving_request_seconds", stat="p99",
             threshold=_env_float("MXNET_TPU_WATCHDOG_REQUEST_P99", 1.0),
             severity="critical",
             description="serving request p99 (admission to response) "
                         "broke the MXNET_TPU_WATCHDOG_REQUEST_P99 SLO"),
        # generation lane (serving/generation.py): the token stream's
        # UX is inter-token latency, not request latency — one slow
        # decode step stalls EVERY live sequence at once
        Rule("inter_token_p99", "generation_inter_token_seconds",
             stat="p99",
             threshold=_env_float("MXNET_TPU_WATCHDOG_ITL_P99", 0.5),
             severity="critical",
             description="inter-token latency p99 across live "
                         "generations broke the MXNET_TPU_WATCHDOG_"
                         "ITL_P99 SLO — decode steps are stalling the "
                         "whole batch"),
        Rule("queue_saturation", "serving_queue_saturation", stat="max",
             threshold=_env_float("MXNET_TPU_WATCHDOG_QUEUE_SAT", 0.9),
             for_s=_env_float("MXNET_TPU_WATCHDOG_QUEUE_SAT_FOR_S", 0.0),
             severity="warning",
             description="a model lane's queue is nearly full "
                         "(depth/max_queue) — overload shedding is "
                         "imminent; add replicas or widen buckets"),
        # multi-tenant quotas (serving/tenancy.py): quota sheds are
        # *correct* behaviour for a saturating tenant, so the rule only
        # warns on a surge — a sudden pile of 429s usually means a
        # misconfigured budget or a runaway client, not capacity
        Rule("quota_shed_surge", "serving_rejected_total",
             kind="increase", selector={"reason": "quota"},
             threshold=_env_float("MXNET_TPU_WATCHDOG_QUOTA_SHEDS",
                                  100.0),
             window_s=_env_float(
                 "MXNET_TPU_WATCHDOG_QUOTA_SHEDS_WINDOW_S", 60.0),
             severity="warning",
             description="per-tenant quota sheds surged inside the "
                         "window — check serving_rejected_total"
                         "{reason=quota} by tenant for the runaway "
                         "client or a misconfigured budget"),
    ]
    # wire-bandwidth rules (observability/wire.py books): both derive a
    # ratio from two families, so they ride the value_fn seam instead of
    # the stock single-metric lookup
    wire_regress = Rule(
        "wire_bytes_regression", "kv_wire_bytes_total",
        kind="regression",
        factor=_env_float("MXNET_TPU_WATCHDOG_WIRE_FACTOR", 2.0),
        window_s=600.0, severity="terminal",
        description="kvstore wire bytes/step blew past the rolling "
                    "baseline by MXNET_TPU_WATCHDOG_WIRE_FACTOR — a "
                    "wire-format or striping change is resending bytes "
                    "(the flight bundle carries the evaluation)")
    wire_regress.value_fn = _wire_bytes_per_step
    codec_share = Rule(
        "wire_codec_share", "kv_wire_codec_seconds", op=">",
        threshold=_env_float("MXNET_TPU_WATCHDOG_WIRE_CODEC_SHARE", 0.25),
        severity="warning",
        description="frame encode/decode wall exceeds the allowed share "
                    "of step time — serialization is eating the step "
                    "budget (the binary-wire lane's trigger condition)")
    codec_share.value_fn = _wire_codec_share
    rules.extend([wire_regress, codec_share])
    # memory/capacity rules (observability/memory.py books).  Headroom
    # is clamped to a 1e-6 floor by memory.sample(), so skip_zero can
    # keep a registry-reset zero placeholder from false-firing while a
    # genuinely exhausted device (headroom ~0) still trips the rule.
    oom = Rule(
        "oom_proximity", "memory_headroom_ratio", stat="min", op="<",
        skip_zero=True,
        threshold=_env_float("MXNET_TPU_WATCHDOG_HEADROOM_MIN", 0.05),
        for_s=_env_float("MXNET_TPU_WATCHDOG_HEADROOM_FOR_S", 0.0),
        severity="terminal",
        description="a device's memory headroom fell below MXNET_TPU_"
                    "WATCHDOG_HEADROOM_MIN — the next allocation spike "
                    "is an OOM; the flight bundle carries the pool "
                    "ledger snapshot and the top-K largest live buffers")
    oom.bundle_extra_fn = _memory_bundle_extras
    rules.append(oom)
    rules.append(Rule(
        "kv_cache_pressure", "serving_kv_cache_occupancy", stat="max",
        op=">", skip_zero=True,
        threshold=_env_float("MXNET_TPU_WATCHDOG_KV_PRESSURE", 0.9),
        for_s=_env_float("MXNET_TPU_WATCHDOG_KV_PRESSURE_FOR_S", 0.0),
        severity="warning",
        description="a model's KV-cache block pool is nearly full — "
                    "CacheExhaustedError 429s are imminent; the rule "
                    "rides the autoscaler's WATCHED_RULES so sustained "
                    "pressure grows the replica group"))
    rules.extend(_slo.burn_rules())
    return rules
