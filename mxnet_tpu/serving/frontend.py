"""HTTP front-end: the serving tier's wire surface.

A tiny stdlib ``http.server`` endpoint (same loopback posture as
``observability.exporters.start_metrics_server``) in front of a
:class:`~.scheduler.Scheduler` or a
:class:`~.replication.ServingRouter`:

``POST /v1/predict``
    JSON body ``{"model": ..., "inputs": {name: nested lists},
    "deadline_ms": ...}`` → ``{"model": ..., "outputs": [...]}``.
    Raw-tensor bodies are supported two ways.  The preferred wire is
    ``Content-Type: application/x-mxtpu-frame`` with ``?model=m``: the
    body is one PR-17 binary frame (see ``docs/how_to/wire_format.md``)
    whose ``pairs`` carry the named inputs as raw tensor bytes; the
    response is a frame whose ``vals`` carry every output zero-copy —
    the same codec the async-PS wire uses, so header overhead is the
    fixed 54-byte struct instead of an ``.npy`` header per tensor.
    Corrupt frames answer 400 (typed ``CorruptMessageError``).  The
    older ``Content-Type: application/octet-stream`` path with query
    parameters ``?model=m&input=data`` is kept for one release: the
    body is one ``.npy``-serialized per-sample array (``numpy.save``
    bytes), the response the first output as ``.npy`` bytes
    (``X-MXTPU-Outputs`` carries the count) — no JSON float round-trip
    on either hot path.
``POST /v1/generate``
    JSON body ``{"model": ..., "prompt": [token ids],
    "max_new_tokens": ..., "eos_id": ..., "deadline_ms": ...}`` →
    a **chunked** ``application/x-ndjson`` stream, one
    ``{"token": id}`` line per generated token as the decode loop
    produces it, closed by a ``{"done": true, "finish_reason": ...,
    "tokens": [...]}`` summary line.  Tokens reach the client
    mid-generation (chunked transfer encoding, one chunk a token);
    a client that disconnects mid-stream cancels the request, which
    retires the sequence and frees its KV-cache blocks at the next
    decode iteration.  Served when ``target`` (or the optional
    ``generator=``) is a
    :class:`~.generation.GenerationScheduler`.

    Which thread does what: the connection's handler thread parses,
    submits, waits for the first outcome (so that a prefill-time
    failure still maps onto its HTTP status), and sends the status
    line, the headers and the first token.  Then it hands its socket
    to the front end's one **stream writer** (:class:`_StreamWriter`)
    and sleeps until the response is complete.  The writer is woken
    once a delivery of the generation loop (once a decode step,
    whatever the number of streams), sends every stream the chunks of
    the tokens released to it since (non-blocking: a socket that takes
    no bytes keeps its remainder and holds up no one), and ends a
    finished stream with its summary line and the last chunk.  So a
    decode step over 128 streams wakes one thread, not 128
    (``serving_stream_tokens_total`` over
    ``serving_stream_writer_wakeups_total`` is the streams served a
    wake-up; ``serving_stream_blocked_total`` counts sends that took
    only part of their bytes).
``GET /v1/models``
    The registry listing (name, input signature, buckets, max_queue).
``GET /healthz`` / ``GET /readyz``
    Liveness vs readiness: ``healthz`` answers 200 while the process
    serves HTTP at all; ``readyz`` answers 503 while draining/fenced,
    which is how a load balancer is told to stop sending — the other
    half of drain mode.

Typed serving errors map to the wire via their ``http_status``
(429 overload/quota, 503 draining/dead, 504 deadline, 404 unknown
model); the body is ``{"error": ..., "type": ...}``.  Every 429-class
reply carries a ``Retry-After`` header: for a quota shed it is the
token bucket's actual refill time (rounded up to whole seconds), for
an overload shed the ``MXNET_TPU_SERVING_RETRY_AFTER_S`` default — a
well-behaved client backs off exactly as long as the budget needs.

**Multi-tenancy**: callers name their tenant with an optional
``X-MXTPU-Tenant`` header; the id is sanitized
(:func:`~.tenancy.clean_tenant`) and carried through admission, the
weighted-fair queues, quotas, spans and the ``serving.access`` event.
Requests without the header ride as tenant ``"default"`` — the
single-tenant wire contract is unchanged.

Per-request observability: every ``/v1/predict`` request runs inside a
root ``serving.request`` span and answers with an
``X-MXTPU-Request-Id`` header — on typed errors too, so a shed request
is support-debuggable.  The id IS the root span's wire token when
tracing is on (paste it into the merged Chrome trace), a
``"pid:rN"`` counter otherwise.  Callers may send an optional
``X-MXTPU-Trace`` header carrying a PR-5 ``"pid:span_id"`` token; the
root span then parents under the caller's span (malformed tokens are
silently ignored, never a 4xx — the wire contract).  The ingress is
gated by ``MXNET_TPU_SERVING_TRACE_HEADER`` (default on).  Each
request also emits one ``serving.access`` event (status, latency,
model, shed reason) into the structured ops log.
"""

from __future__ import annotations

import io
import itertools
import json
import os
import threading
import time

import numpy as _np

from ..base import MXNetError
from .. import kvstore_wire as _wire
# the submodule path matters: the package exports an ``events()``
# accessor FUNCTION under the same name as the submodule
from ..observability.events import emit as _emit_event
from ..observability import metrics as _metrics
from ..observability import tracing as _tracing
from . import admission as _admission
from . import tenancy as _tenancy

__all__ = ["ServingFrontend", "start_frontend", "trace_header_enabled"]

# raw-tensor wire books: the serving analogue of kv_wire_bytes_total —
# bytes of binary-frame and .npy request/response bodies on the
# raw-tensor hot paths (JSON predict bodies are excluded; their float
# round-trip is the thing these paths exist to avoid).  Both content
# types share the counter, so the frame path's header savings show up
# directly as fewer bytes per request.  Handles pre-resolved at import.
_M_SERVING_WIRE = _metrics.counter(
    "serving_wire_bytes_total",
    "Raw-tensor (binary-frame or .npy) bytes crossing the serving "
    "frontend by direction (recv = request body, send = response "
    "body)", ["dir"])
_H_SWIRE_RECV = _M_SERVING_WIRE.labels("recv")
_H_SWIRE_SEND = _M_SERVING_WIRE.labels("send")

# fallback request-id counter for when tracing is off (the id is then
# "pid:rN" — still unique, just not resolvable in a trace)
_req_ids = itertools.count(1)

# the stream writer's books: tokens over wake-ups is how many streams
# one wake-up of the one writer served (near the decode batch's rows
# where it works; 1 would be a wake-up a token)
_M_STREAM_TOKENS = _metrics.counter(
    "serving_stream_tokens_total",
    "Token chunks of /v1/generate responses sent by the front end's "
    "stream writer (every token of a stream but its first, which its "
    "handler thread sends)")
_M_STREAM_WAKEUPS = _metrics.counter(
    "serving_stream_writer_wakeups_total",
    "Times the front end's stream writer was woken: by a delivery of "
    "the generation loop (once a decode step, whatever the streams; a "
    "request's end rides the next one), by a lane going idle behind a "
    "request's end, or by a stream handed over with tokens owed")
_M_STREAM_BLOCKED = _metrics.counter(
    "serving_stream_blocked_total",
    "Sends of the stream writer that took only part of their bytes or "
    "none (the socket's buffer was full: the remainder is kept for "
    "that stream alone)")

#: a stream with bytes owed is tried again this often (seconds)
_BLOCKED_RETRY_S = 0.01


def _chunk(data):
    """``data`` in chunked-transfer framing: hex length, CRLF, data,
    CRLF."""
    return b"%x\r\n%s\r\n" % (len(data), data)


def _token_chunk(token):
    # what json.dumps({"token": token}) gives, without the encoder
    return _chunk(b'{"token": %d}\n' % token)


def _kv_hints(exc):
    """Occupancy hint fields for a :class:`~mxnet_tpu.ops.kv_cache.
    CacheExhaustedError` response body (empty for other errors): how
    full the block pool was when the allocation was rejected, so a
    client can back off proportionally instead of blind-retrying."""
    occ = getattr(exc, "kv_cache_occupancy", None)
    if occ is None:
        return {}
    return {"kv_cache_occupancy": round(float(occ), 4),
            "kv_cache_blocks_free": getattr(exc, "kv_cache_blocks_free",
                                            None),
            "kv_cache_blocks_total": getattr(exc,
                                             "kv_cache_blocks_total",
                                             None)}


def trace_header_enabled():
    """``MXNET_TPU_SERVING_TRACE_HEADER``: accept the caller's
    ``X-MXTPU-Trace`` token as the root span's remote parent (default
    on; ``0`` ignores the header entirely)."""
    return os.environ.get("MXNET_TPU_SERVING_TRACE_HEADER", "1") != "0"


class _Stream(object):
    """One streaming response in the writer's hands: the request, the
    socket, how many of ``req.generated`` have been put into a send
    (``sent``), the bytes a send did not take (``owed``), and what the
    handler thread reads when ``over`` is set."""

    __slots__ = ("req", "sock", "model", "sent", "owed", "closing",
                 "t_progress", "status", "shed", "over")

    def __init__(self, req, sock, model, sent):
        self.req, self.sock, self.model, self.sent = req, sock, model, sent
        self.owed = b""
        self.closing = False
        self.t_progress = time.monotonic()
        self.status, self.shed = 200, None
        self.over = threading.Event()


class _StreamWriter(object):
    """The one thread that sends what every streaming ``/v1/generate``
    response holds after its first token.

    It sleeps on one event.  The generation loop sets it once a
    delivery (beside its next device call: the wake-up every stream's
    request was given, :meth:`GenerationRequest.stream_to`; a request's
    end rides the next delivery, or is told at once where the lane goes
    idle), and so does a hand-over that finds tokens owed.
    Woken, it walks its streams: each gets the chunks of
    ``generated[sent:released]`` (several tokens, if it fell behind, in
    one send) and, once its request is over, the summary line and the
    last chunk.  Sends never block: what a socket does not take is kept
    for that stream and tried again every ``_BLOCKED_RETRY_S``; a
    stream that moved no byte for ``timeout`` seconds, or whose client
    is gone, is cancelled (499, ``shed="disconnect"``)."""

    def __init__(self, timeout):
        self._timeout = timeout
        self._streams = []
        self._lock = threading.Lock()
        self._event = threading.Event()
        self._closed = False
        self.wake = self._event.set
        self._thread = threading.Thread(
            target=self._run, name="mxtpu-serving-stream", daemon=True)
        self._thread.start()

    def stream(self, req, sock, model, sent):
        """Called by a handler thread once the first ``sent`` tokens
        (one or none) are on the wire: hand the rest of the response to
        the writer and sleep until it is complete.  Returns the
        :class:`_Stream` (``status``, ``shed``)."""
        stream = _Stream(req, sock, model, sent)
        blocking = sock.gettimeout()
        sock.setblocking(False)
        with self._lock:
            self._streams.append(stream)
        # registered after the stream is in the list and before the
        # look at what is owed: whatever the loop releases from here on
        # wakes the writer, whatever it released before is seen here
        req.stream_to(self.wake)
        if req.done or req.released > sent:
            self.wake()
        stream.over.wait()
        sock.settimeout(blocking)
        return stream

    def close(self):
        """Let the streams in hand run to their end, then stop."""
        with self._lock:
            self._closed = True
        self.wake()

    def _run(self):
        owed = False
        while True:
            if owed:
                wait = _BLOCKED_RETRY_S
            else:
                wait = self._timeout if self._streams else None
            if self._event.wait(wait):
                self._event.clear()
                _M_STREAM_WAKEUPS.inc()
            with _tracing.span("stream.flush", cat="serving") as sp:
                with self._lock:
                    streams = list(self._streams)
                    if self._closed and not streams:
                        return
                now = time.monotonic()
                tokens = 0
                for stream in streams:
                    tokens += self._serve(stream, now)
                if tokens:
                    _M_STREAM_TOKENS.inc(tokens)
                live = [s for s in streams if not s.over.is_set()]
                if len(live) < len(streams):
                    with self._lock:
                        self._streams = [s for s in self._streams
                                         if not s.over.is_set()]
                owed = any(s.owed for s in live)
                sp.set(streams=len(streams), chunks=tokens)

    def _serve(self, stream, now):
        """Send ``stream`` what it is owed; returns the token chunks put
        into the send."""
        req = stream.req
        data = stream.owed
        count = 0
        if not stream.closing:
            done = req.done          # before released: final once set
            released = req.released
            count = released - stream.sent
            if count:
                data += b"".join(map(
                    _token_chunk, req.generated[stream.sent:released]))
                stream.sent = released
            if done:
                data += self._tail(stream) + b"0\r\n\r\n"
                stream.closing = True
        if data:
            try:
                took = stream.sock.send(data)
            except BlockingIOError:
                took = 0
            except OSError:
                # client went away mid-stream: cancel() retires the
                # sequence and frees its cache blocks at the next
                # decode iteration
                self._drop(stream)
                return 0
            stream.owed = data[took:]
            if took:
                stream.t_progress = now
            if stream.owed:
                _M_STREAM_BLOCKED.inc()
            elif stream.closing:
                self._end(stream)
                return count
        if now - stream.t_progress > self._timeout:
            self._drop(stream)       # stuck: as a client that went away
        return count

    @staticmethod
    def _tail(stream):
        req = stream.req
        if req.error is None:
            tail = {"done": True, "model": stream.model,
                    "finish_reason": req.finish_reason,
                    "tokens": list(req.generated)}
        else:
            # generation failed after the 200 was committed: the error
            # rides the stream, the tail line carries the typed error
            # instead of a token list
            exc = req.error
            stream.shed = _admission.reject_reason(exc)
            tail = {"done": True, "model": stream.model,
                    "finish_reason": "error", "error": str(exc),
                    "type": type(exc).__name__}
            tail.update(_kv_hints(exc))
        return _chunk(json.dumps(tail).encode("utf-8") + b"\n")

    def _drop(self, stream):
        stream.req.cancel()
        stream.status, stream.shed = 499, "disconnect"
        self._end(stream)

    @staticmethod
    def _end(stream):
        stream.req.stream_to(None)
        stream.over.set()


class ServingFrontend(object):
    """Handle for a running front-end: ``.port``, ``.url``,
    ``.close()``.  Also a context manager.  A closed front end lets go
    of its server and of ``target``: the handle may outlive them (a
    caller that keeps it keeps no scheduler, and so no backend's
    weights and cache pools, on the device)."""

    def __init__(self, httpd, thread, target, writer):
        self._httpd = httpd
        self._thread = thread
        self._writer = writer
        self.target = target
        self.port = httpd.server_address[1]
        self.url = "http://%s:%d" % (httpd.server_address[0], self.port)

    def close(self):
        if self._httpd is None:
            return
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)
        self._writer.close()
        # the server's handler class closes over the target
        self._httpd = self._thread = self.target = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def _target_request(target, model, inputs, deadline_ms, timeout,
                    tenant=None):
    # Scheduler and ServingRouter share the request() signature
    return target.request(model, inputs, deadline_ms=deadline_ms,
                          timeout=timeout, tenant=tenant)


def _target_models(target):
    if hasattr(target, "registry"):               # Scheduler
        return target.registry.describe()
    group = getattr(target, "_group", None)       # ServingRouter
    if group is not None:
        live = group.live()
        if live:
            return live[0][1].registry.describe()
    return []


def _target_ready(target):
    if hasattr(target, "ready"):                  # Scheduler
        return bool(target.ready())
    group = getattr(target, "_group", None)       # ServingRouter
    if group is not None:
        return any(s.ready() for _, s in group.live())
    return False


def start_frontend(target, port=None, addr="127.0.0.1", timeout=30.0,
                   generator=None):
    """Serve the v1 API for ``target`` (a Scheduler or ServingRouter)
    on a daemon thread; returns a :class:`ServingFrontend`.

    ``port=None`` reads ``MXNET_TPU_SERVING_PORT`` (default 0 = a
    kernel-assigned free port, reported via ``.port``).  Loopback-bound
    unless ``addr`` says otherwise — the endpoint is unauthenticated.

    ``generator`` optionally serves ``/v1/generate`` from a separate
    :class:`~.generation.GenerationScheduler`; by default generation is
    served from ``target`` itself when it has a generation lane.
    """
    import http.server
    import os
    import urllib.parse

    if port is None:
        port = int(os.environ.get("MXNET_TPU_SERVING_PORT", "0"))

    class _Handler(http.server.BaseHTTPRequestHandler):
        def _reply(self, status, body, ctype, extra=()):
            self._status = status
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            rid = getattr(self, "_rid", None)
            if rid:
                self.send_header("X-MXTPU-Request-Id", rid)
            for k, v in extra:
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _reply_json(self, status, payload, extra=()):
            self._reply(status, json.dumps(payload).encode("utf-8"),
                        "application/json; charset=utf-8", extra=extra)

        def _reply_error(self, exc):
            status = getattr(exc, "http_status", None)
            if status is None:
                status = 400 if isinstance(exc, MXNetError) else 500
            self._shed = _admission.reject_reason(exc)
            extra = ()
            if status == 429:
                # quota sheds carry the bucket's actual refill time,
                # overload sheds the env default — either way a 429 is
                # never headerless (tested contract; since PR 20 the
                # cache-exhaustion path rides it too)
                extra = (("Retry-After",
                          str(_admission.retry_after_s(exc))),)
            payload = {"error": str(exc), "type": type(exc).__name__}
            payload.update(_kv_hints(exc))
            self._reply_json(status, payload, extra=extra)

        def do_GET(self):
            self._rid = None     # keep-alive: no id leak from a POST
            path, _, _query = self.path.partition("?")
            if path == "/v1/models":
                self._reply_json(200, {"models": _target_models(target)})
            elif path == "/healthz":
                self._reply_json(200, {"status": "ok"})
            elif path == "/readyz":
                if _target_ready(target):
                    self._reply_json(200, {"status": "ready"})
                else:
                    self._reply_json(503, {"status": "not ready"})
            else:
                self.send_error(404)

        def do_POST(self):
            path, _, query = self.path.partition("?")
            if path not in ("/v1/predict", "/v1/generate"):
                self.send_error(404)
                return
            t0 = time.monotonic()
            self._model = None
            self._shed = None
            self._status = 500
            self._tenant = _tenancy.clean_tenant(
                self.headers.get("X-MXTPU-Tenant"))
            # the caller's trace token (when the gate is open) parents
            # the root span; attach_wire_context silently ignores
            # malformed tokens — never a 4xx over a bad trace header
            tok = (self.headers.get("X-MXTPU-Trace")
                   if trace_header_enabled() else None)
            with _tracing.attach_wire_context(tok):
                with _tracing.span("serving.request", cat="serving",
                                   method="POST") as root:
                    self._rid = (_tracing.capture_wire_context()
                                 or "%d:r%d" % (os.getpid(),
                                                next(_req_ids)))
                    try:
                        length = int(self.headers.get(
                            "Content-Length", "0"))
                        body = self.rfile.read(length)
                        ctype = (self.headers.get("Content-Type")
                                 or "").lower()
                        if path == "/v1/generate":
                            self._generate(body)
                        elif ctype.startswith(
                                "application/x-mxtpu-frame"):
                            self._predict_frame(body, query)
                        elif ctype.startswith(
                                "application/octet-stream"):
                            self._predict_raw(body, query)
                        else:
                            self._predict_json(body)
                    except MXNetError as exc:
                        self._reply_error(exc)
                    except (ValueError, KeyError, TypeError) as exc:
                        self._reply_json(400, {"error": str(exc),
                                               "type": type(exc).__name__})
                    root.set(model=self._model, status=self._status,
                             request_id=self._rid, tenant=self._tenant)
                    _emit_event(
                        "serving.access", status=self._status,
                        latency_ms=round((time.monotonic() - t0) * 1e3,
                                         3),
                        model=self._model, request_id=self._rid,
                        tenant=self._tenant, shed=self._shed)

        def _predict_json(self, body):
            payload = json.loads(body.decode("utf-8"))
            model = self._model = payload["model"]
            inputs = {n: _np.asarray(v, dtype=_np.float32)
                      for n, v in payload["inputs"].items()}
            outs = _target_request(target, model, inputs,
                                   payload.get("deadline_ms"), timeout,
                                   tenant=self._tenant)
            self._reply_json(200, {
                "model": model,
                "outputs": [_np.asarray(o).tolist() for o in outs]})

        def _generate(self, body):
            payload = json.loads(body.decode("utf-8"))
            model = self._model = payload["model"]
            gen = generator if generator is not None else target
            if not hasattr(gen, "generate"):
                raise _admission.UnknownModelError(
                    "this endpoint has no generation lane "
                    "(target is %s)" % type(gen).__name__)
            # submit raises the typed admission errors (429/503/504)
            # BEFORE any byte of the response is written, so they still
            # map onto proper HTTP statuses via _reply_error
            req = gen.submit(
                model,
                _np.asarray(payload["prompt"], dtype=_np.int32),
                max_new_tokens=payload.get("max_new_tokens"),
                eos_id=payload.get("eos_id"),
                deadline_ms=payload.get("deadline_ms"),
                tenant=self._tenant)
            # first-outcome gating: wait for the first token BEFORE
            # committing the status line, so a prefill-time failure
            # (cache exhaustion in the generation loop) maps onto its
            # typed HTTP status — a CacheExhaustedError 429 with
            # Retry-After and occupancy hints — instead of riding an
            # already-committed 200's error tail
            released, _ = req.wait(1, timeout)
            if not released and req.error is not None:
                raise req.error
            self._status = 200
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Transfer-Encoding", "chunked")
            if self._rid:
                self.send_header("X-MXTPU-Request-Id", self._rid)
            self.end_headers()
            sent = min(released, 1)
            if sent:
                # the handler's wfile is unbuffered: a write is a send
                # of its own, a segment of its own on the wire and a
                # wake-up of the reader, so the client has the first
                # token mid-generation, not after it
                try:
                    self.wfile.write(_token_chunk(req.generated[0]))
                except (BrokenPipeError, ConnectionResetError):
                    pass        # gone already: the writer's send says so
            # every byte after the first token is the stream writer's
            stream = writer.stream(req, self.connection, model, sent)
            self._status = stream.status
            self._shed = stream.shed
            if stream.status != 200:
                self.close_connection = True

        def _predict_frame(self, body, query):
            # PR-17 binary-frame path: inputs ride the frame's pairs
            # zero-copy, outputs ride the response frame's vals.  A
            # corrupt body raises CorruptMessageError (an MXNetError)
            # out of decode_frame, which _reply_error maps to a 400.
            q = urllib.parse.parse_qs(query)
            model = self._model = q["model"][0]
            deadline = q.get("deadline_ms", [None])[0]
            _H_SWIRE_RECV.inc(float(len(body)))
            msg = _wire.decode_frame(bytes(body))
            pairs = msg.get("pairs") or []
            if not pairs:
                raise MXNetError(
                    "binary predict frame carries no input pairs")
            inputs = {str(n): _np.asarray(v) for n, v in pairs}
            outs = _target_request(
                target, model, inputs,
                float(deadline) if deadline is not None else None,
                timeout, tenant=self._tenant)
            out_bytes = _wire.encode_frame({
                "model": model,
                "vals": [_np.ascontiguousarray(_np.asarray(o))
                         for o in outs]})
            _H_SWIRE_SEND.inc(float(len(out_bytes)))
            self._reply(200, out_bytes, "application/x-mxtpu-frame",
                        extra=(("X-MXTPU-Outputs", str(len(outs))),))

        def _predict_raw(self, body, query):
            q = urllib.parse.parse_qs(query)
            model = self._model = q["model"][0]
            name = q.get("input", ["data"])[0]
            deadline = q.get("deadline_ms", [None])[0]
            _H_SWIRE_RECV.inc(float(len(body)))
            row = _np.load(io.BytesIO(body), allow_pickle=False)
            outs = _target_request(
                target, model, {name: row},
                float(deadline) if deadline is not None else None, timeout,
                tenant=self._tenant)
            buf = io.BytesIO()
            _np.save(buf, _np.asarray(outs[0]))
            out_bytes = buf.getvalue()
            _H_SWIRE_SEND.inc(float(len(out_bytes)))
            self._reply(200, out_bytes, "application/octet-stream",
                        extra=(("X-MXTPU-Outputs", str(len(outs))),))

        def log_message(self, *args):  # requests don't belong on stderr
            pass

    httpd = http.server.ThreadingHTTPServer((addr, int(port)), _Handler)
    writer = _StreamWriter(timeout)
    thread = threading.Thread(target=httpd.serve_forever,
                              name="mxtpu-serving-http", daemon=True)
    thread.start()
    return ServingFrontend(httpd, thread, target, writer)
