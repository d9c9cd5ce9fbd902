"""Deterministic fault injection for the async runtime.

Production-scale training is defined by how the system behaves when
things fail, so failure must be a *testable* code path: this module is a
seeded, per-site fault registry that the runtime consults at the places
where real systems actually break —

========================  ==================================================
site                      planted at
========================  ==================================================
``engine.op``             dependency-engine op execution (``engine.push``)
``kvstore.send``          PS wire send (``kvstore_async._send_msg``)
``kvstore.recv``          PS wire receive (``kvstore_async._recv_msg``)
``kvstore.call``          worker RPC attempt (``AsyncClient._call``)
``kvstore.server_kill``   PS server dispatch entry (``AsyncServer.dispatch``)
                          — a fired rule KILLS that server abruptly (op
                          names are ``s<id>:<role>:<op>`` so ``match`` can
                          target e.g. ``s0:primary:push``)
``kvstore.repl_drop``     primary→follower replication send (one lost
                          stream frame; re-sent and deduped by log seqno)
``kvstore.repl_delay``    primary→follower replication send (stretches
                          the replication-lag window)
``checkpoint.write``      sharded + two-file checkpoint writes
``serving.admit``         serving request admission
                          (``serving.Scheduler.submit``; ``name`` is the
                          model, so ``match`` can shed one tenant)
``serving.dispatch``      serving batch dispatch, just before the device
                          call (``name`` is ``<model>:<bucket>``; retried
                          ``MXNET_TPU_SERVING_RETRIES`` times, then failed
                          requests fail over to a peer replica)
``kvstore.resize_drop``   elastic re-striping transfer/cutover steps
                          (``elastic.ResizePlan``; ``name`` is
                          ``prepare:<key>`` / ``commit:<shard>`` — a fired
                          rule aborts the plan cleanly at the old epoch,
                          no key orphaned)
``serving.scale``         serving-group scale action entry
                          (``ReplicaGroup.grow``/``shrink``; ``name`` is
                          ``grow:<group>`` / ``shrink:<group>`` — a fired
                          rule aborts the action before any membership
                          change)
``serving.decode``        generation decode-step dispatch, just before the
                          device call (``GenerationScheduler``; ``name`` is
                          ``<model>:<bucket>``; retried
                          ``MXNET_TPU_SERVING_RETRIES`` times — cache
                          writes happen only after a successful step, so a
                          retry can never corrupt another sequence's
                          blocks)
``serving.route``         replica selection in the KV-affinity router
                          (``serving.routing.KVAffinityRouter``; fires
                          once per candidate replica, ``name`` is
                          ``<model>:<replica index>`` — a fired rule
                          makes THAT replica unroutable for this
                          attempt, so ``drop``/``raise`` drill the
                          spill-to-peer and re-prefill fallback paths;
                          ``delay`` stretches the routing step)
``serving.kv_alloc``      paged KV-cache block allocation
                          (``PagedKVCache.allocate``; ``name`` is the
                          sequence id; ``raise``/``drop`` surface as the
                          typed 429 ``CacheExhaustedError`` path, ``delay``
                          stretches the admission window)
``storage.write``         durable-state file write (``durable.
                          atomic_write_bytes`` — snapshot shards,
                          manifests, fit-meta sidecars; ``name`` is the
                          destination path).  ``corrupt`` is a torn
                          write / bit flip in the payload about to hit
                          disk, ``drop`` is a full disk
                          (``OSError(ENOSPC)``), ``raise`` a failed
                          write, ``delay`` a slow fsync —
                          ``chaos.corrupt_file`` with this site is the
                          post-commit bit-rot counterpart
``data.read``             RecordIO record read (``MXRecordIO.read``;
                          ``name`` is the stream's uri).  ``corrupt``
                          garbles the record header so the magic check
                          trips; ``drop`` raises the typed
                          ``CorruptMessageError`` the production
                          skip-and-count handler catches; ``delay``
                          stretches the stream-stall window
========================  ==================================================

Four failure modes:

* ``raise`` — raise :class:`ChaosError` at the site (a crashed op / a
  failed write).
* ``drop`` — raise the site's *native* loss exception (connection reset
  on send, EOF on recv, socket timeout on call) so the production retry
  path — not a test-only path — handles it.  At ``engine.op`` /
  ``checkpoint.write`` a drop silently skips the work (a lost write).
* ``delay`` — sleep (bounded, sub-second by default) to surface
  ordering and timeout windows.
* ``corrupt`` — deterministically flip bytes in the payload passing
  through the site (wire frames, checkpoint files).

Every rule owns a ``random.Random(seed)``, so a failure schedule is a
pure function of (seed, visit sequence): a test that proves recovery
under 30% message drop proves the *same* schedule on every run.

Configuration is either programmatic::

    with chaos.inject("kvstore.send", "drop", prob=0.3, seed=7):
        ...   # every _send_msg flips a seeded coin

or environment-driven for soak runs (no code changes)::

    MXNET_TPU_CHAOS="kvstore.send:drop:0.3:seed=7,engine.op:raise:0.05"

The hot-path cost when idle is one dict lookup per site visit.
"""

from __future__ import annotations

import os
import random
import threading
import time

from .observability import metrics as _metrics

__all__ = ["ChaosError", "ChaosDrop", "inject", "clear", "visit",
           "corrupt_file", "rules", "SITES"]

_M_FIRED = _metrics.counter(
    "chaos_fired_total", "Chaos-injection rules fired, by site", ["site"])

SITES = frozenset({
    "engine.op", "kvstore.send", "kvstore.recv", "kvstore.call",
    "kvstore.server_kill", "kvstore.repl_drop", "kvstore.repl_delay",
    "kvstore.resize_drop", "checkpoint.write", "storage.write",
    "serving.admit", "serving.dispatch", "serving.scale",
    "serving.decode", "serving.kv_alloc", "serving.route", "data.read",
})


class ChaosError(RuntimeError):
    """Injected failure (mode=``raise``)."""


class ChaosDrop(ChaosError):
    """Injected loss at a site with no native loss exception — the
    instrumentation point treats it as 'the work silently never
    happened' (skip the engine op, skip the checkpoint write)."""


def _drop_exc(site):
    """The exception a real loss at this site would produce, so drops
    exercise the production recovery path rather than a bespoke one."""
    import socket

    if site == "kvstore.send":
        return ConnectionResetError("chaos: dropped on send")
    if site == "kvstore.recv":
        return EOFError("chaos: dropped on receive")
    if site == "kvstore.call":
        return socket.timeout("chaos: call timed out")
    if site == "kvstore.repl_drop":
        return ConnectionResetError("chaos: replication frame dropped")
    if site == "kvstore.resize_drop":
        return ConnectionResetError("chaos: resize transfer dropped")
    if site == "data.read":
        from . import base

        return base.CorruptMessageError("chaos: record dropped mid-read")
    if site == "storage.write":
        import errno

        return OSError(errno.ENOSPC, "chaos: no space left on device")
    return ChaosDrop("chaos: dropped at %s" % site)


class _Rule:
    """One injection rule; owns its seeded RNG so the failure schedule
    is deterministic per (seed, visit sequence)."""

    __slots__ = ("site", "mode", "prob", "seed", "delay", "match",
                 "limit", "fires", "visits", "_rng")

    def __init__(self, site, mode, prob=1.0, seed=0, delay=0.05,
                 match=None, limit=None):
        if site not in SITES:
            raise ValueError("unknown chaos site %r (have %s)"
                             % (site, sorted(SITES)))
        if mode not in ("drop", "delay", "raise", "corrupt"):
            raise ValueError("unknown chaos mode %r" % mode)
        self.site = site
        self.mode = mode
        self.prob = float(prob)
        self.seed = int(seed)
        self.delay = float(delay)
        self.match = match
        self.limit = None if limit is None else int(limit)
        self.fires = 0
        self.visits = 0
        self._rng = random.Random(self.seed)

    def should_fire(self, name):
        if self.match is not None and self.match not in (name or ""):
            return False
        if self.limit is not None and self.fires >= self.limit:
            return False
        self.visits += 1
        # always draw, even for prob=1: keeps the schedule a function of
        # the visit sequence alone, independent of the prob value
        if self._rng.random() >= self.prob:
            return False
        self.fires += 1
        return True

    def corrupt_bytes(self, payload):
        """Flip a few deterministic bytes; never changes the length (a
        truncation would be a different failure class — framing)."""
        buf = bytearray(payload)
        if not buf:
            return bytes(buf)
        for _ in range(min(8, len(buf))):
            pos = self._rng.randrange(len(buf))
            buf[pos] ^= 0x5A
        return bytes(buf)

    def describe(self):
        return {"site": self.site, "mode": self.mode, "prob": self.prob,
                "seed": self.seed, "visits": self.visits,
                "fires": self.fires}


_lock = threading.Lock()
_rules = []          # programmatic rules, in registration order
_env_rules = []      # rules parsed from MXNET_TPU_CHAOS
_env_cache = None    # the env string the cached _env_rules came from


def _parse_env(value):
    """``site:mode[:prob][:key=val]...`` comma-separated."""
    out = []
    for part in value.split(","):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        if len(fields) < 2:
            raise ValueError(
                "MXNET_TPU_CHAOS entry %r: need at least site:mode" % part)
        site, mode = fields[0], fields[1]
        kwargs = {}
        for extra in fields[2:]:
            if "=" in extra:
                k, v = extra.split("=", 1)
                if k not in ("seed", "delay", "match", "limit", "prob"):
                    raise ValueError(
                        "MXNET_TPU_CHAOS entry %r: unknown key %r"
                        % (part, k))
                kwargs[k] = v if k == "match" else float(v)
            else:
                kwargs["prob"] = float(extra)
        for k in ("seed", "limit"):
            if k in kwargs:
                kwargs[k] = int(kwargs[k])
        out.append(_Rule(site, mode, **kwargs))
    return out


def _active_rules(site):
    """Rules for one site, env rules refreshed lazily so tests and jobs
    can (re)configure without re-importing anything."""
    global _env_rules, _env_cache

    env = os.environ.get("MXNET_TPU_CHAOS")
    if env != _env_cache:
        with _lock:
            if env != _env_cache:
                _env_rules = _parse_env(env) if env else []
                _env_cache = env
    return [r for r in _rules + _env_rules if r.site == site]


class _Injection:
    """Handle returned by :func:`inject`; context manager removes the
    rule on exit.  ``.fires``/``.visits`` expose the realized schedule."""

    def __init__(self, rule):
        self._rule = rule

    @property
    def fires(self):
        return self._rule.fires

    @property
    def visits(self):
        return self._rule.visits

    def remove(self):
        with _lock:
            if self._rule in _rules:
                _rules.remove(self._rule)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.remove()
        return False


def inject(site, mode, prob=1.0, seed=0, delay=0.05, match=None,
           limit=None):
    """Register an injection rule; returns a removable handle that is
    also a context manager.

    ``prob``   per-visit fire probability (seeded coin).
    ``seed``   the rule's private RNG seed — the whole failure schedule.
    ``delay``  sleep seconds for ``delay`` mode (keep sub-second in tests).
    ``match``  only fire when the site's op name contains this substring.
    ``limit``  stop firing after this many injections.
    """
    rule = _Rule(site, mode, prob=prob, seed=seed, delay=delay,
                 match=match, limit=limit)
    with _lock:
        _rules.append(rule)
    return _Injection(rule)


def clear():
    """Remove every programmatic rule (env rules follow the env var)."""
    with _lock:
        del _rules[:]


def rules():
    """Snapshot of active rules (programmatic + env) for observability."""
    env_sites = _active_rules  # force env refresh via any site
    _ = env_sites("engine.op")
    with _lock:
        return [r.describe() for r in _rules + _env_rules]


def visit(site, payload=None, name=None):
    """Consult the registry at an instrumented site.

    May sleep (``delay``), raise (``raise`` → :class:`ChaosError`;
    ``drop`` → the site's native loss exception), or return a corrupted
    copy of ``payload`` (``corrupt``, only when ``payload`` is bytes-like
    — corrupt rules are inert at sites that pass no payload).
    Returns ``payload`` (possibly transformed) otherwise.
    """
    matched = _active_rules(site)
    if not matched:
        return payload
    with _lock:
        for rule in matched:
            if rule.mode == "corrupt" and payload is None:
                continue
            if not rule.should_fire(name):
                continue
            _M_FIRED.labels(site).inc()
            if rule.mode == "delay":
                time.sleep(rule.delay)
            elif rule.mode == "raise":
                raise ChaosError(
                    "chaos: injected failure at %s (op=%r, seed=%d, "
                    "fire #%d)" % (site, name, rule.seed, rule.fires))
            elif rule.mode == "drop":
                raise _drop_exc(site)
            else:  # corrupt
                payload = rule.corrupt_bytes(payload)
    return payload


def corrupt_file(site, path):
    """File-payload counterpart of ``visit``'s corrupt mode: when a
    corrupt rule on ``site`` fires, garble the largest file under
    ``path`` (a file or a directory tree) in place.  Returns the path
    corrupted, or None."""
    matched = [r for r in _active_rules(site) if r.mode == "corrupt"]
    if not matched:
        return None
    with _lock:
        rule = next((r for r in matched if r.should_fire(None)), None)
        if rule is None:
            return None
        _M_FIRED.labels(site).inc()
        target = path
        if os.path.isdir(path):
            best = None
            for root, _dirs, files in os.walk(path):
                for f in files:
                    p = os.path.join(root, f)
                    try:
                        size = os.path.getsize(p)
                    except OSError:
                        continue
                    if best is None or size > best[0]:
                        best = (size, p)
            if best is None:
                return None
            target = best[1]
        try:
            with open(target, "r+b") as f:
                data = f.read()
                f.seek(0)
                f.write(rule.corrupt_bytes(data))
        except OSError:
            return None
        return target
