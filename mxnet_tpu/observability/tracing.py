"""Trace spans: nested, cross-thread, ring-buffered.

``span("name")`` times a region on whatever thread it runs on; spans
nest through a thread-local stack, and a parent context can be carried
ACROSS threads — ``engine.push`` captures the pusher's context with
:func:`capture_context` and re-attaches it on the worker thread with
:func:`attach_context`, so an engine op's span is a child of the
``trainer.flush`` (or ``prefetch``/RPC) span that scheduled it even
though they run on different threads.

A context can also be carried ACROSS processes: the kvstore client
serializes its context with :func:`capture_wire_context` into the RPC
frame header, and the server re-attaches it with
:func:`attach_wire_context`, so push/pull/replication handling shows
up as children of the worker's RPC span.  The wire token is
``"<pid>:<span_id>"``; a same-pid token (the in-process test layout)
parents locally, a cross-pid token is kept as a remote parent and
stitched at export time via ``args.parent_uid``.  Corrupt tokens are
silently ignored — tracing must never fail an RPC.

Finished spans land in a bounded ring buffer (capacity
``MXNET_TPU_METRICS_TRACE_BUFFER``, default 65536; oldest evicted
first — each eviction of an unexported span counts in
``spans_dropped_total``).  Timestamps are ``time.monotonic()``
microseconds — the same CLOCK_MONOTONIC the native engine profiler
stamps (``native/src/profiler.cc NowUs``), so Python spans and native
engine ops merge onto ONE aligned timeline in
``exporters.export_chrome_trace``.

Spans record while :func:`enable_tracing` holds **or a JAX profiler
session is live** (``jax.profiler.start_trace`` … ``stop_trace``:
``profiler_set_state('run')``, the front end's ``/profile?ms=N``, a
benchmark's traced run), and at no other time: there is no other
switch.  Under a live session a span is also entered as a
``jax.profiler.TraceAnnotation`` named ``"mx:" + name``, with its span
id, its parent's and (where the span has a ``request`` attribute) the
request's token as metadata, so the same span lies in the profiler's
own trace, in the host plane, **on the clock of the device's
operations**.  The ring's own timestamps stay CLOCK_MONOTONIC: the ring
and the profiler's trace are two records of the same spans, not one
timeline.  A span with explicit timestamps (:func:`record_span`) cannot
be back-dated in the profiler's trace; it leaves a mark there at the
moment it is recorded, with ``start_us`` / ``end_us`` as metadata.
With no session and tracing not enabled, ``span()`` is a no-op context
manager (constant-time guard) and nothing is recorded.
"""

from __future__ import annotations

import collections
import itertools
import os
import sys
import threading
import time

from . import metrics as _metrics

__all__ = ["span", "record_span", "capture_context", "attach_context",
           "capture_wire_context", "attach_wire_context",
           "enable_tracing", "disable_tracing", "tracing_enabled",
           "spans", "clear_spans", "Span"]

#: Ring-buffer evictions of spans that were never exported (satellite:
#: silent truncation makes merged traces misleading).
_M_DROPPED = _metrics.counter(
    "spans_dropped_total",
    "Trace spans evicted from the ring buffer before export")

#: Prefix of a span's name in the profiler's trace.
ANNOTATION_PREFIX = "mx:"

_enabled = False
_annotation = None   # jax.profiler.TraceAnnotation, bound at first use
_lock = threading.Lock()
_ids = itertools.count(1)
_buffer = None       # created lazily so the env cap is read at first use
_tls = threading.local()


class Span(object):
    """One finished span record."""

    __slots__ = ("name", "cat", "start_us", "end_us", "tid", "span_id",
                 "parent_id", "attrs")

    def __init__(self, name, cat, start_us, end_us, tid, span_id,
                 parent_id, attrs):
        self.name = name
        self.cat = cat
        self.start_us = start_us
        self.end_us = end_us
        self.tid = tid
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs


def _buf():
    global _buffer
    if _buffer is None:
        with _lock:
            if _buffer is None:
                cap = int(os.environ.get(
                    "MXNET_TPU_METRICS_TRACE_BUFFER", "65536"))
                _buffer = collections.deque(maxlen=max(cap, 1))
    return _buffer


def enable_tracing():
    """Start recording spans (cleared of nothing: the buffer keeps any
    prior session's spans until :func:`clear_spans`)."""
    global _enabled
    _buf()
    _enabled = True


def disable_tracing():
    """Take :func:`enable_tracing` back.  Spans still record while a
    profiler session is live."""
    global _enabled
    _enabled = False


def _bind_session():
    """Is a JAX profiler session live?  Stands in as ``_session_live``
    until JAX is found imported, then binds
    ``TraceAnnotation.is_enabled`` (under 0.1 us a call) in its place:
    this module imports nothing of JAX, and without JAX there is no
    session to be live."""
    global _annotation, _session_live
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return False
    _annotation = profiler.TraceAnnotation
    _session_live = _annotation.is_enabled
    return _session_live()


_session_live = _bind_session


def tracing_enabled():
    """Do spans record now: :func:`enable_tracing` was called, or a JAX
    profiler session is live.  The one predicate of every site."""
    return _enabled or _session_live()


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def capture_context():
    """The calling thread's current span id (0 = tracing on, no open
    span), or ``None`` when tracing is off.  Pass the result to
    :func:`attach_context` on another thread to parent spans across the
    hop — this pair is what ``engine.push`` threads through to worker
    threads."""
    if not tracing_enabled():
        return None
    st = getattr(_tls, "stack", None)
    return st[-1] if st else 0


class attach_context(object):
    """Context manager installing a captured parent context on THIS
    thread; spans opened inside become its children.  A ``None`` context
    (tracing was off at capture time) is a no-op."""

    __slots__ = ("_ctx", "_pushed")

    def __init__(self, ctx):
        self._ctx = ctx
        self._pushed = False

    def __enter__(self):
        if self._ctx is not None and self._ctx != 0:
            _stack().append(self._ctx)
            self._pushed = True
        return self

    def __exit__(self, *exc):
        if self._pushed:
            _stack().pop()
        return False


def capture_wire_context():
    """The calling thread's span context as a wire token
    (``"<pid>:<span_id>"``), or ``None`` when tracing is off or no span
    is open.  The token is a plain string so it rides in the kvstore
    JSON frame header as an OPTIONAL field — old peers that do not know
    it decode the frame unchanged."""
    if not tracing_enabled():
        return None
    st = getattr(_tls, "stack", None)
    if not st:
        return None
    top = st[-1]
    # under a foreign attach the top may already be a remote token;
    # forward it unchanged so the chain keeps its true origin
    if isinstance(top, str):
        return top
    return "%d:%d" % (os.getpid(), top)


class attach_wire_context(object):
    """Install a wire token received from a peer as the parent context
    on THIS thread.  A same-pid token becomes a true local parent
    (spans nest exactly as if in-thread); a cross-pid token is pushed
    as-is and recorded as the child span's remote parent, stitched at
    export time through ``args.parent_uid``.  ``None``, non-string, or
    corrupt tokens are silently ignored (constant-time no-op) — a bad
    trace header must never fail the RPC carrying it."""

    __slots__ = ("_tok", "_pushed")

    def __init__(self, token):
        self._tok = token
        self._pushed = False

    def __enter__(self):
        if not isinstance(self._tok, str) or not tracing_enabled():
            return self
        try:
            pid_s, span_s = self._tok.split(":", 1)
            pid, sid = int(pid_s), int(span_s)
        except ValueError:
            return self
        if pid <= 0 or sid <= 0:
            return self
        _stack().append(sid if pid == os.getpid() else self._tok)
        self._pushed = True
        return self

    def __exit__(self, *exc):
        if self._pushed:
            _stack().pop()
        return False


class span(object):
    """Record a named span over the ``with`` body.

    ``cat`` groups spans in the trace viewer (engine / prefetch /
    kvstore / frontend...); extra keyword attrs land in the chrome-trace
    ``args``.  Records while :func:`tracing_enabled` (and then, under a
    live profiler session, into the profiler's trace too: the module
    docstring); a no-op (constant-time guard) otherwise.
    """

    __slots__ = ("_name", "_cat", "_attrs", "_t0", "_id", "_parent",
                 "_live", "_note")

    def __init__(self, name, cat="frontend", **attrs):
        self._name = name
        self._cat = cat
        self._attrs = attrs
        self._live = False

    def set(self, **attrs):
        """Attach attrs to a span already open (facts learned mid-body,
        e.g. the batch a request landed in).  No-op when tracing is off."""
        if self._live:
            self._attrs.update(attrs)
        return self

    def __enter__(self):
        session = _session_live()
        if not (session or _enabled):
            return self
        self._live = True
        st = _stack()
        self._parent = st[-1] if st else 0
        self._id = next(_ids)
        st.append(self._id)
        if session:
            self._note = _annotate(self._name, self._id, self._parent,
                                   self._attrs)
            self._note.__enter__()
        else:
            self._note = None
        self._t0 = int(time.monotonic() * 1e6)
        return self

    def __exit__(self, *exc):
        if not self._live:
            return False
        self._live = False
        end = int(time.monotonic() * 1e6)
        if self._note is not None:
            self._note.__exit__(None, None, None)
        st = _stack()
        if st and st[-1] == self._id:
            st.pop()
        buf = _buf()
        if len(buf) == buf.maxlen:
            _M_DROPPED.inc()
        buf.append(Span(self._name, self._cat, self._t0, end,
                        threading.get_ident() % 100000, self._id,
                        self._parent, self._attrs))
        return False


def _annotate(name, span_id, parent, attrs, **more):
    """The span as the profiler's trace gets it (a session is live)."""
    if "request" in attrs:
        more["request"] = attrs["request"]
    return _annotation(ANNOTATION_PREFIX + name, span_id=span_id,
                       parent_id=parent, **more)


def record_span(name, cat="frontend", start_us=None, end_us=None,
                parent=None, **attrs):
    """Record a span with EXPLICIT timestamps — for intervals measured
    before the recording site runs (e.g. a request's queue wait, whose
    start is stamped at admit but whose span can only be emitted at
    dispatch).  ``parent`` may be a local span id, a wire token (kept as
    a remote parent, stitched at export), or ``None`` to parent under
    the calling thread's current stack top.  Returns the new span id, or
    ``None`` while tracing is off (constant-time guard).  Under a live
    profiler session the span also leaves a mark in the profiler's
    trace, at the moment of this call, with its timestamps as
    metadata."""
    session = _session_live()
    if not (session or _enabled):
        return None
    now = int(time.monotonic() * 1e6)
    if end_us is None:
        end_us = now
    if start_us is None:
        start_us = end_us
    if parent is None:
        st = getattr(_tls, "stack", None)
        parent = st[-1] if st else 0
    elif isinstance(parent, str):
        # wire token: a same-pid token parents locally, else remote
        try:
            pid_s, span_s = parent.split(":", 1)
            pid, sid = int(pid_s), int(span_s)
            parent = (sid if pid == os.getpid() else parent) \
                if pid > 0 and sid > 0 else 0
        except ValueError:
            parent = 0
    sid = next(_ids)
    if session:
        with _annotate(name, sid, parent, attrs, start_us=int(start_us),
                       end_us=int(end_us)):
            pass
    buf = _buf()
    if len(buf) == buf.maxlen:
        _M_DROPPED.inc()
    buf.append(Span(name, cat, int(start_us), int(end_us),
                    threading.get_ident() % 100000, sid, parent, attrs))
    return sid


def spans():
    """Snapshot (list) of the recorded spans, oldest first."""
    buf = _buf()
    with _lock:
        return list(buf)


def clear_spans():
    buf = _buf()
    with _lock:
        buf.clear()
