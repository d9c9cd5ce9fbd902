"""Where JAX's persistent compilation cache lives, and where a start-up
goes.

A cold process recompiles every jitted step (ResNet-50 and a 12-layer
LM are minutes of compile on a chip); the persistent cache keeps the
executables across processes.  Its directory is part of the cache key,
so it must not move between runs: it is placed from outside through
``JAX_COMPILATION_CACHE_DIR`` or, failing that, at one fixed path
under the checkout.

A warm start still traces and lowers every program (the cache's key is
made from the lowered module) and reads the cache.  What a start-up is
made of is booked here, always on, by the program itself:

- :class:`scope` names a stretch of start-up (``backend.build``,
  ``warmup.prefill`` / ``warmup.decode`` a bucket, ``serve.cold``,
  ``trainer.build``, ``trainer.first_call`` a jit cache with
  ``trainer.cost_analysis`` inside it) and books its wall seconds to
  ``startup_seconds_total{scope}``; the package's own import is booked
  to the same family as ``package.import``.  A scope is entered only
  where the program starts something up, never in a steady step.
- :func:`enable` and the first scope install, once, listeners on
  ``jax.monitoring``: every stage JAX reports (``trace``, ``lower``,
  ``xla``: the compile *or* the cache's load, ``cache_load``: the part
  of ``xla`` that read the cache) is booked to the innermost scope open
  on the calling thread, ``none`` where the program opened none, in
  ``compile_stage_seconds_total{scope,stage}`` and, under the scope's
  ``program``, ``compile_program_seconds_total{scope,program,stage}``;
  a program compiled or loaded counts in
  ``compile_requests_total{scope}``, the persistent cache's answers in
  ``compile_cache_hits_total{scope}`` /
  ``compile_cache_misses_total{scope}`` (JAX counts a miss where it
  writes the entry).  A jit traced inside another's trace reports its
  seconds inside its caller's: only the outermost trace is booked.
- While :func:`~mxnet_tpu.observability.tracing.tracing_enabled`, a
  scope is also the span of its name and each stage a
  ``compile.<stage>`` span under it: the cause is the parent.
- :func:`log_table` writes what some scopes held as one table through
  this module's logger (``GenerationScheduler.warmup`` and a trainer's
  first call do, at INFO).
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time

from .observability import metrics as _metrics
from .observability import tracing as _tracing

__all__ = ["enable", "scope", "is_open", "book", "log_table", "table",
           "STAGES"]

_LOG = logging.getLogger(__name__)

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: The stages of a compile, in the order they run, by JAX's event.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_STAGE_OF = {
    _TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "xla",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
STAGES = tuple(_STAGE_OF.values())

_M_SECONDS = _metrics.counter(
    "startup_seconds_total",
    "Wall seconds of the program's start-up scopes (compile_cache.scope; "
    "a scope inside another is in both) and of the package's import",
    ["scope"])
_M_STAGE = _metrics.counter(
    "compile_stage_seconds_total",
    "Seconds of each compile stage JAX reported (trace, lower, xla: "
    "compile or cache load, cache_load: the cache's read inside xla), "
    "by the innermost start-up scope open on the calling thread; none "
    "where the program opened none", ["scope", "stage"])
_M_PROGRAM = _metrics.counter(
    "compile_program_seconds_total",
    "compile_stage_seconds_total by the scope's program (a prefill or "
    "decode bucket, a trainer's jit cache)", ["scope", "program", "stage"])
_M_REQUESTS = _metrics.counter(
    "compile_requests_total",
    "Programs handed to XLA, compiled or loaded from the persistent "
    "cache, by start-up scope", ["scope"])
_M_HITS = _metrics.counter(
    "compile_cache_hits_total",
    "Programs the persistent compilation cache held, by start-up scope",
    ["scope"])
_M_MISSES = _metrics.counter(
    "compile_cache_misses_total",
    "Programs compiled and written to the persistent compilation cache, "
    "by start-up scope", ["scope"])
#: The persistent cache's answers, by JAX's event.
_ANSWER_OF = {
    "/jax/compilation_cache/cache_hits": ("hit", _M_HITS),
    "/jax/compilation_cache/cache_misses": ("miss", _M_MISSES),
}

_tls = threading.local()
_install_lock = threading.Lock()
_installed = False


def enable():
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX reads the variable
    itself and nothing is set in code.  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored).  Call before the first
    compile: the entry points do (``chip_smoke.py``, ``bench.py``,
    ``tools/serve.py``, the examples' ``common/fit.py``).  Also
    installs the listeners that book every compile stage and cache
    answer to the start-up scope that caused it (the module docstring),
    as the first :class:`scope` would."""
    _install()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def book(name, seconds):
    """``seconds`` of start-up that no scope could time (the package's
    import, stamped by ``mxnet_tpu/__init__.py``)."""
    _M_SECONDS.labels(name).inc(seconds)


def _install():
    global _installed
    if _installed:
        return
    with _install_lock:
        if _installed:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_on_start)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True


def _top():
    """The innermost scope open on the calling thread; :data:`_NOBODY`
    where the program opened none."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else _NOBODY


def _on_start(event, _value, **_kw):
    """JAX marks a stage's start with a scalar: the depth of the traces
    open on this thread."""
    if event == _TRACE_EVENT:
        _tls.traces = getattr(_tls, "traces", 0) + 1


def _on_duration(event, seconds, **_kw):
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    if stage == "trace":
        depth = _tls.traces = max(getattr(_tls, "traces", 1) - 1, 0)
        if depth:
            return      # inside its caller's trace, which reports it too
    top = _top()
    top.stages[stage] += seconds
    _M_STAGE.labels(top.name, stage).inc(seconds)
    if top.program is not None:
        _M_PROGRAM.labels(top.name, top.program, stage).inc(seconds)
    if stage == "xla":
        top.requests += 1
        _M_REQUESTS.labels(top.name).inc()
    if _tracing.tracing_enabled():
        end = time.monotonic() * 1e6
        _tracing.record_span("compile." + stage, cat="startup",
                             start_us=end - seconds * 1e6, end_us=end,
                             program=top.program or "")


def _on_event(event, **_kw):
    answer = _ANSWER_OF.get(event)
    if answer is None:
        return
    what, family = answer
    top = _top()
    top.cache[what] += 1
    family.labels(top.name).inc()


class scope(object):
    """A named stretch of start-up, as a context manager or, around a
    whole function, a decorator (a new scope a call).

    While it is open it is the innermost scope of its thread: the
    compile stages and cache answers JAX reports there are booked to
    ``name`` (and ``program``) and kept on the object (``stages``,
    ``requests``, ``cache``: its hits and misses).  On exit its wall
    seconds (``seconds``, by ``time.monotonic``) go to
    ``startup_seconds_total{scope}`` and it joins the ``children`` of
    the scope it was opened in.  It is the
    :func:`~mxnet_tpu.observability.tracing.span` of its name too,
    which records only while tracing is enabled."""

    __slots__ = ("name", "program", "seconds", "stages", "requests",
                 "cache", "children", "_t0", "_span")

    def __init__(self, name, program=None):
        self.name, self.program = name, program
        self.seconds = 0.0
        self.stages = dict.fromkeys(STAGES, 0.0)
        self.requests = 0
        self.cache = {"hit": 0, "miss": 0}
        self.children = []

    def __enter__(self):
        _install()
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        attrs = {} if self.program is None else {"program": self.program}
        self._span = _tracing.span(self.name, cat="startup", **attrs)
        self._span.__enter__()
        stack.append(self)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.seconds = time.monotonic() - self._t0
        stack = _tls.stack
        stack.pop()
        self._span.__exit__(*exc)
        _M_SECONDS.labels(self.name).inc(self.seconds)
        if stack:
            stack[-1].children.append(self)
        return False

    def __call__(self, fn):
        name, program = self.name, self.program

        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with scope(name, program):
                return fn(*args, **kwargs)

        return scoped


#: Whom a compile is booked to where the program opened no scope (the
#: benchmark's weight maker, a reference): visible, and owned by nobody.
_NOBODY = scope("none")


def is_open():
    """Is a start-up scope open on the calling thread?"""
    return _top() is not _NOBODY


def table(scopes):
    """What ``scopes`` (and the scopes opened inside them) held, as the
    lines of a table: scope and program, seconds of each stage, the cache's
    answers, wall seconds."""
    rows = []

    def walk(sc, depth):
        answers = " ".join("%d %s" % (n, what)
                           for what, n in sc.cache.items() if n)
        rows.append(("  " * depth + " ".join(
            part for part in (sc.name, sc.program) if part),)
                    + tuple("%.3f" % sc.stages[s] for s in STAGES)
                    + (answers or "-", "%.3f" % sc.seconds))
        for child in sc.children:
            walk(child, depth + 1)

    for sc in scopes:
        walk(sc, 0)
    head = ("scope program",) + STAGES + ("cache", "wall_s")
    widths = [max(len(row[i]) for row in [head] + rows)
              for i in range(len(head))]
    return ["  ".join(cell.ljust(w) if i == 0 else cell.rjust(w)
                      for i, (cell, w) in enumerate(zip(row, widths)))
            for row in [head] + rows]


def log_table(what, scopes):
    """Log :func:`table` of ``scopes`` under the heading ``what``, at
    INFO through this module's logger."""
    if _LOG.isEnabledFor(logging.INFO):
        _LOG.info("%s: %.3f s\n%s", what,
                  sum(sc.seconds for sc in scopes),
                  "\n".join(table(scopes)))
