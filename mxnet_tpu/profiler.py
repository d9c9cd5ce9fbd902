"""Profiler (parity: reference ``python/mxnet/profiler.py`` +
``src/engine/profiler.cc``) — now a façade over
:mod:`mxnet_tpu.observability`.

Three lanes under one API:
 - **device**: the jax/XLA profiler (xplane) — ``profiler_set_state('run')``
   starts a trace viewable in TensorBoard/Perfetto.  This is the TPU
   equivalent of the reference's GPU op timing.
 - **host engine**: the native engine profiler (``native/src/profiler.cc``)
   records per-op start/end/thread for host-side engine work — the direct
   equivalent of the reference's ``OprExecStat`` → ``DumpProfile`` path
   (``src/engine/profiler.h:20-141``, hook ``threaded_engine.h:294-308``).
 - **the program's spans**: ``scope()`` and every instrumented runtime
   seam record through :func:`observability.span`.

**The rule: a live profiler session records the program's spans.**
Between ``jax.profiler.start_trace`` and ``stop_trace`` — this module's
``profiler_set_state('run')``, a serving replica's ``/profile?ms=N``, a
benchmark's traced run — every ``observability.span`` records, with
nothing else to turn on, and goes to two places.  (1) Into the
profiler's own trace, as a ``TraceAnnotation`` named ``mx:<span>`` with
its span id, parent id and request token as metadata: there it lies in
the host plane **on the device's clock**, over the device's operations,
and a gap on the device reads off what the serving loop was doing in it
(``mx:decode.wait``, ``mx:decode.copy``, ``mx:generation.idle``…; the
span table is in ``docs/how_to/observability.md``).  (2) Into the
cross-thread ring buffer, stamped CLOCK_MONOTONIC µs like the native
engine's events, which ``dump_profile`` merges with the native dump
into one chrome://tracing JSON beside the xplane directory.  **These are
two timelines, not one**: the chrome JSON has the host's spans and
engine ops on the host's clock and no device operation; the xplane has
the device's operations and the ``mx:`` spans on the profiler's.  To lay
host work over device work, open the xplane.  With no session (and
``observability.enable_tracing()`` not called) a span is a constant-time
guard and records nothing.
"""

from __future__ import annotations

import logging
import os
import threading

from . import _native, observability as _obs

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "scope"]


class _ProfilerState(object):
    """Lock-guarded profiler session state.  The old module-global dict
    let two threads racing ``profiler_set_state('run')`` both observe
    ``running=False`` and double-start the xplane trace; here the
    check-and-flip happens under one lock."""

    def __init__(self):
        self.lock = threading.Lock()
        self.mode = "symbolic"
        self.dir = "profile_output"
        self.running = False


_STATE = _ProfilerState()


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """(parity: ``profiler.py:profiler_set_config``)"""
    with _STATE.lock:
        _STATE.mode = mode
        _STATE.dir = os.path.splitext(filename)[0]


def profiler_set_state(state="stop"):
    """'run' starts the xplane trace and the native engine recording,
    and with the session the program's spans record (the module
    docstring's rule; ``enable_tracing`` is set besides, for a build
    whose profiler starts no session); 'stop' ends all three (parity:
    ``profiler.py:profiler_set_state``).  Idempotent and thread-safe:
    concurrent or repeated 'run' calls start ONE session."""
    import jax

    lib = _native.lib()
    with _STATE.lock:
        if state == "run" and not _STATE.running:
            os.makedirs(_STATE.dir, exist_ok=True)
            jax.profiler.start_trace(_STATE.dir)
            if lib is not None:
                lib.mxtpu_profiler_clear()  # fresh session, no stale events
                lib.mxtpu_profiler_set_state(1)
            _obs.clear_spans()
            _obs.enable_tracing()
            _STATE.running = True
        elif state == "stop" and _STATE.running:
            jax.profiler.stop_trace()
            if lib is not None:
                lib.mxtpu_profiler_set_state(0)
            _obs.disable_tracing()
            _STATE.running = False
        else:
            logging.debug("profiler state change to %r ignored", state)


def dump_profile():
    """Stop + flush all traces.  The host-engine chrome trace lands at
    ``<dir>/engine_trace.json`` (parity: ``profiler.py:dump_profile`` /
    ``Profiler::DumpProfile``); the MERGED view — frontend/engine/
    prefetch/kvstore spans plus the native engine ops on one timeline —
    lands at ``<dir>/trace.json``.  Returns the merged path."""
    profiler_set_state("stop")
    with _STATE.lock:
        out_dir = _STATE.dir
    os.makedirs(out_dir, exist_ok=True)
    lib = _native.lib()
    if lib is not None:
        path = os.path.join(out_dir, "engine_trace.json")
        n = lib.mxtpu_profiler_dump(path.encode())
        logging.info("dumped %d engine events to %s", n, path)
    merged = os.path.join(out_dir, "trace.json")
    trace = _obs.export_chrome_trace(merged)
    logging.info("dumped merged trace (%d events) to %s",
                 len(trace["traceEvents"]), merged)
    return merged


class scope(object):
    """Context manager recording a named frontend span (the
    ``mx.profiler``-visible analog of engine op events).  Routed through
    the observability span API — nested scopes parent correctly, engine
    ops pushed inside inherit the scope across threads — and mirrored
    into the native event table for the legacy ``engine_trace.json``."""

    def __init__(self, name, cat="frontend"):
        self.name = name
        self.cat = cat
        self._span = _obs.span(name, cat=cat)

    def __enter__(self):
        import time

        self._t0 = int(time.monotonic() * 1e6)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import time

        self._span.__exit__(*exc)
        if _obs.tracing_enabled():
            return False  # the span IS the record; don't double-emit
        lib = _native.lib()
        if lib is not None and lib.mxtpu_profiler_state():
            # legacy path: native profiler driven directly, span
            # recording off — mirror into the native event table
            lib.mxtpu_profiler_add_event(
                self.name.encode(), self.cat.encode(), self._t0,
                int(time.monotonic() * 1e6),
                threading.get_ident() % 100000)
        return False
