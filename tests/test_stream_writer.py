"""One writer for all token streams (serving/frontend.py:_StreamWriter).

After its first token a streaming ``/v1/generate`` response is sent by
the front end's one writer thread, woken once a delivery of the
generation loop, and not by its connection's handler thread:

- **the bytes on the wire** are what the handler used to send: a chunk
  a token, the summary line, the last chunk;
- **no token lost or doubled** over 32 concurrent streams of unequal
  lengths, nor at a hand-over that tokens are released beside;
- **a client that goes away** cancels its request alone; **one that
  stops reading** holds up no other stream, and is cancelled once it
  has taken no byte for the front end's ``timeout``;
- **an error after the 200** rides the tail;
- **the books**: tokens sent, wake-ups (one a decode step; a stream adds
  one where its hand-over found tokens owed, the lane one where it goes
  idle), blocked sends; a handler thread sleeps in one wait
  from the hand-over to the end of its response.
"""

import http.client
import json
import socket
import sys
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import chaos, observability as obs, serving
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.observability import metrics as om
from mxnet_tpu.serving import frontend
from mxnet_tpu.serving.generation import GenerationRequest

VOCAB, SEQ_LEN, EMBED, HEADS, LAYERS = 64, 48, 16, 2, 2
STREAMS = 32


@pytest.fixture(scope="module")
def lm():
    cfg = tfm.lm_config(num_classes=VOCAB, seq_len=SEQ_LEN,
                        num_embed=EMBED, num_heads=HEADS,
                        num_layers=LAYERS)
    return cfg, tfm.init_lm_params(cfg, seed=0)


@pytest.fixture(scope="module")
def served(lm):
    """A scheduler with a decode batch of up to 32 rows behind a front
    end, shared by the tests of this file."""
    cfg, params = lm
    sched = serving.GenerationScheduler()
    be = serving.LMBackend(params, cfg, block_size=4, num_blocks=512,
                           model="streams")
    sched.register("lm", be, decode_buckets=[1, 2, 4, 8, 16, STREAMS],
                   prefill_buckets=[8, 16])
    sched.warmup("lm")
    fe = serving.start_frontend(sched, timeout=30.0)
    yield sched, be, fe
    fe.close()
    sched.close()


def _counters():
    return {name: om.REGISTRY.get("serving_stream_%s_total" % name).value
            for name in ("tokens", "writer_wakeups", "blocked")}


def _since(before):
    return {k: v - before[k] for k, v in _counters().items()}


def _booked(before, tokens):
    """The counters' deltas once the writer has booked ``tokens``: it
    books a walk's tokens when the walk is over, a moment after the last
    reader has its tail."""
    deadline = time.monotonic() + 10
    while _since(before)["tokens"] < tokens \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    return _since(before)


def _old_chunk(data):
    # the framing as the handler thread wrote it before there was a
    # writer: hex length, CRLF, data, CRLF
    return b"%x\r\n%s\r\n" % (len(data), data)


def _post(port, payload):
    body = json.dumps(payload).encode()
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    sock.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                 b"Host: t\r\nContent-Type: application/json\r\n"
                 b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
    return sock


def _read_all(sock):
    buf = b""
    # (a request id may end in 0 too: the header's end is no last chunk)
    while not buf.endswith(b"\r\n0\r\n\r\n"):
        data = sock.recv(65536)
        if not data:
            break
        buf += data
    return buf


def _lines(raw):
    """The ndjson lines of a raw chunked response, after a check that
    its body is whole chunks and the last chunk."""
    head, _, body = raw.partition(b"\r\n\r\n")
    assert head.split(b" ")[1] == b"200"
    lines = []
    while True:
        size, _, body = body.partition(b"\r\n")
        n = int(size, 16)
        if n == 0:
            assert body == b"\r\n"
            return lines
        assert body[n:n + 2] == b"\r\n"
        lines.append(json.loads(body[:n]))
        body = body[n + 2:]


# ------------------------------------------------------------ the wire

def test_the_wire_bytes_are_the_handlers_old_framing(served):
    """One HTTP chunk a token holding ``{"token": N}\\n``, the summary
    line, ``0\\r\\n\\r\\n``: byte for byte what ``json.dumps`` and the
    handler's ``_chunk`` produced."""
    sched, _, fe = served
    want = sched.generate("lm", [3, 9, 1, 7], max_new_tokens=9)
    sock = _post(fe.port, {"model": "lm", "prompt": [3, 9, 1, 7],
                           "max_new_tokens": 9})
    raw = _read_all(sock)
    sock.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    assert b"Transfer-Encoding: chunked" in head
    assert b"Content-Type: application/x-ndjson" in head
    assert b"X-MXTPU-Request-Id: " in head
    tail = {"done": True, "model": "lm", "finish_reason": "length",
            "tokens": want}
    expected = b"".join(
        _old_chunk(json.dumps({"token": int(t)}).encode("utf-8") + b"\n")
        for t in want)
    expected += _old_chunk(json.dumps(tail).encode("utf-8") + b"\n")
    expected += b"0\r\n\r\n"
    assert body == expected


# ------------------------------------------------- many streams at once

def _mix(i):
    prompt = [1 + (i * 7 + j) % (VOCAB - 1) for j in range(2 + i % 5)]
    return prompt, 2 + (i * 5) % 39          # 2..40 new tokens


def test_32_streams_of_unequal_lengths_get_their_tokens_in_order(
        served, monkeypatch):
    """Each of 32 concurrent streams receives exactly its request's
    ``generated``, in order; every third hand-over is held back while
    the loop goes on releasing tokens, which are then neither lost nor
    sent twice.  The books: the writer sent every token but the first
    of each stream, and was woken at most once a decode step and twice
    a stream (a hand-over that found tokens owed; a request's end with
    no device call behind it to wake the writer beside, the lane going
    idle)."""
    sched, be, fe = served
    want = [sched.generate("lm", p, max_new_tokens=m)
            for p, m in map(_mix, range(STREAMS))]
    real = frontend._StreamWriter.stream
    held = []

    def slow_hand_over(self, req, sock, model, sent):
        if len(held) % 3 == 0:
            time.sleep(0.05)
        held.append(len(req.generated) - sent)
        return real(self, req, sock, model, sent)

    monkeypatch.setattr(frontend._StreamWriter, "stream", slow_hand_over)
    steps = om.REGISTRY.get("generation_decode_steps_total").labels("lm")
    before, steps0 = _counters(), steps.value
    got = [None] * STREAMS

    def one(i):
        prompt, new = _mix(i)
        sock = _post(fe.port, {"model": "lm", "prompt": prompt,
                               "max_new_tokens": new})
        got[i] = _lines(_read_all(sock))
        sock.close()

    with chaos.inject("serving.decode", "delay", prob=1.0, seed=1,
                      delay=0.005):
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(STREAMS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    assert all(g is not None for g in got)
    for i, lines in enumerate(got):
        tail = lines[-1]
        assert tail["done"] and tail["finish_reason"] == "length"
        assert [l["token"] for l in lines[:-1]] == tail["tokens"] == want[i]
    assert len(held) == STREAMS and max(held) >= 1, \
        "no hand-over ever had tokens released beside it"
    owed = sum(len(w) - 1 for w in want)
    delta = _booked(before, owed)
    assert delta["tokens"] == owed
    assert 1 <= delta["writer_wakeups"] \
        <= (steps.value - steps0) + 2 * STREAMS
    assert delta["blocked"] == 0
    assert be.cache.stats()["used"] == 0


def test_a_handler_thread_sleeps_in_one_wait_until_its_response_ends(
        served, monkeypatch):
    """From the hand-over to the end of the response the connection's
    handler thread is in one ``Event.wait`` (sampled while 24 tokens
    stream out, a decode step every 10 ms), and it waits once: it is
    woken when the response is complete, not per token."""
    sched, _, fe = served
    waits, handlers = [], []
    real_stream, real_hand_over = frontend._Stream, \
        frontend._StreamWriter.stream

    class Counted(threading.Event):
        def wait(self, timeout=None):
            waits.append(threading.get_ident())
            return threading.Event.wait(self, timeout)

    def counted_stream(*args):
        stream = real_stream(*args)
        stream.over = Counted()
        return stream

    def stream(self, *args):
        handlers.append(threading.get_ident())
        return real_hand_over(self, *args)

    monkeypatch.setattr(frontend, "_Stream", counted_stream)
    monkeypatch.setattr(frontend._StreamWriter, "stream", stream)
    seen = []
    with chaos.inject("serving.decode", "delay", prob=1.0, seed=1,
                      delay=0.01):
        sock = _post(fe.port, {"model": "lm", "prompt": [5, 2],
                               "max_new_tokens": 24})
        reader = threading.Thread(target=lambda: seen.append(
            _lines(_read_all(sock))))
        reader.start()
        while reader.is_alive():
            if handlers:
                frame = sys._current_frames().get(handlers[0])
                names = []
                while frame is not None:
                    names.append(frame.f_code.co_name)
                    frame = frame.f_back
                if "_generate" in names and "stream" in names:
                    waits.append(("sample", "wait" in names))
            time.sleep(0.003)
        reader.join()
    sock.close()
    assert len(seen[0]) == 25
    samples = [w[1] for w in waits if isinstance(w, tuple)]
    assert len(samples) >= 10 and all(samples)
    assert [w for w in waits if not isinstance(w, tuple)] == handlers[:1]


# ---------------------------------------------- clients that misbehave

def test_a_client_that_closes_mid_stream_cancels_its_request_alone(served):
    """The stream whose client went away is cancelled (499,
    ``shed="disconnect"``) and its blocks are freed; the streams beside
    it finish with every token."""
    sched, be, fe = served
    want = [sched.generate("lm", [7, i + 1], max_new_tokens=30)
            for i in range(2)]
    obs.clear_events()
    with chaos.inject("serving.decode", "delay", prob=1.0, seed=1,
                      delay=0.02):
        others = [_post(fe.port, {"model": "lm", "prompt": [7, i + 1],
                                  "max_new_tokens": 30}) for i in range(2)]
        gone = _post(fe.port, {"model": "lm", "prompt": [5, 2],
                               "max_new_tokens": 40})
        buf = b""
        while buf.count(b'{"token"') < 3:
            buf += gone.recv(4096)
        assert be.cache.stats()["used"] > 0
        # an abortive close: the next send to it fails at once
        gone.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        b"\x01\x00\x00\x00\x00\x00\x00\x00")
        gone.close()
        got = [_lines(_read_all(s)) for s in others]
    for s in others:
        s.close()
    for lines, w in zip(got, want):
        assert [l["token"] for l in lines[:-1]] == w
        assert lines[-1]["finish_reason"] == "length"
    deadline = time.monotonic() + 15
    while be.cache.stats()["used"] and time.monotonic() < deadline:
        time.sleep(0.01)
    assert be.cache.stats()["used"] == 0, "disconnect leaked KV blocks"
    deadline = time.monotonic() + 15
    while len(obs.events("serving.access")) < 3 \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    access = sorted((e.fields["status"], e.fields["shed"])
                    for e in obs.events("serving.access"))
    assert access == [(200, None), (200, None), (499, "disconnect")]


def _by_hand(count, start=0):
    """A request as the loop would fill it, with no loop: the test
    pushes and releases its tokens itself."""
    req = GenerationRequest("by_hand", np.array([1], np.int32), count,
                            None, None)
    for t in range(start):
        req._push(t)
    req._deliver()
    return req


def _release(req, tokens):
    for t in tokens:
        req._push(t)
    wake = req._deliver()
    if wake is not None:
        wake()


def _small_pair():
    ours, theirs = socket.socketpair()
    ours.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    theirs.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    return ours, theirs


def _hand_over(writer, req, sock, out):
    thread = threading.Thread(
        target=lambda: out.append(writer.stream(req, sock, "m", 0)))
    thread.start()
    return thread


def test_a_client_that_stops_reading_delays_no_other_stream():
    """One stream's socket is full (its reader reads nothing): the
    sends to it are counted as blocked and its remainder is kept, while
    another stream gets each of its tokens within the retry interval.
    When the slow reader reads again it gets every byte, in order."""
    writer = frontend._StreamWriter(timeout=30.0)
    before = _counters()
    slow_sock, slow_peer = _small_pair()
    fast_sock, fast_peer = socket.socketpair()
    big = 60000                       # ~1.5 MB of chunks: no buffer's size
    slow, fast = _by_hand(big), _by_hand(50)
    done = []
    threads = [_hand_over(writer, slow, slow_sock, done),
               _hand_over(writer, fast, fast_sock, done)]
    _release(slow, range(big))
    fast_peer.settimeout(5)
    waited = []
    buf = b""
    for t in range(50):
        t0 = time.monotonic()
        _release(fast, [t])
        want = buf + frontend._token_chunk(t)
        while buf != want:
            buf += fast_peer.recv(4096)
        waited.append(time.monotonic() - t0)
    assert max(waited) < 1.0, "a full socket held another stream up"
    assert _since(before)["blocked"] >= 1
    assert not done and slow.released == big
    fast._finish("length")
    slow._finish("length")
    got = [_lines(b"HTTP/1.1 200 OK\r\n\r\n" + read + _read_all(peer))
           for read, peer in ((buf, fast_peer), (b"", slow_peer))]
    for t in threads:
        t.join(timeout=30)
    assert [l["token"] for l in got[0][:-1]] == list(range(50))
    assert [l["token"] for l in got[1][:-1]] == list(range(big))
    assert got[1][-1]["tokens"] == list(range(big))
    assert sorted(s.status for s in done) == [200, 200]
    assert _booked(before, big + 50)["tokens"] == big + 50
    assert slow_sock.gettimeout() is None      # handed back as it came
    writer.close()
    writer._thread.join(timeout=5)
    assert not writer._thread.is_alive()


def test_a_stream_stuck_for_the_timeout_is_cancelled():
    """A socket that takes no byte for the front end's ``timeout`` is
    treated as a client that went away: the request is cancelled, the
    handler gets 499 / ``disconnect``; so is one whose peer is closed."""
    writer = frontend._StreamWriter(timeout=0.3)
    stuck_sock, stuck_peer = _small_pair()
    gone_sock, gone_peer = socket.socketpair()
    stuck, gone = _by_hand(60000), _by_hand(8)
    done = []
    threads = [_hand_over(writer, stuck, stuck_sock, done),
               _hand_over(writer, gone, gone_sock, done)]
    gone_peer.close()
    _release(stuck, range(60000))
    _release(gone, range(8))
    for t in threads:
        t.join(timeout=30)
    assert sorted((s.status, s.shed) for s in done) \
        == [(499, "disconnect")] * 2
    assert stuck.cancelled and gone.cancelled
    assert stuck._wake is None and gone._wake is None
    stuck_peer.close()
    writer.close()


def test_tokens_released_beside_the_hand_over_are_sent_once():
    """The loop releases tokens (and ends the request) while the
    handler thread is inside the hand-over: whatever the interleaving
    (a short switch interval, eight hand-overs at a time), the peer
    reads every token once, in order, then the tail."""
    writer = frontend._StreamWriter(timeout=30.0)
    failures, whole = [], []

    def one(round_):
        ours, peer = socket.socketpair()
        req = _by_hand(200, start=1)

        def loop():
            for t in range(1, 200):
                _release(req, [t])
                if t % 50 == round_ % 50:
                    time.sleep(0.0005)
            req._finish("length")

        pusher = threading.Thread(target=loop)
        pusher.start()
        time.sleep(0.0002 * (round_ % 5))
        writer.stream(req, ours, "m", 1)
        pusher.join(timeout=30)
        lines = _lines(b"HTTP/1.1 200 OK\r\n\r\n" + _read_all(peer))
        if ([l["token"] for l in lines[:-1]] != list(range(1, 200))
                or lines[-1]["tokens"] != list(range(200))):
            failures.append((round_, lines))
        whole.append(round_)
        ours.close()
        peer.close()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for base in range(0, 48, 8):
            threads = [threading.Thread(target=one, args=(base + i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not failures and len(whole) == 48
    writer.close()


# ------------------------------------------------ an error after the 200

def test_a_generation_error_after_the_200_rides_the_tail(served):
    """Decode fails for good once the first token is out: the status
    stays 200, the tokens so far are streamed, the tail carries the
    typed error, and the stream ends with the last chunk."""
    sched, be, fe = served
    obs.clear_events()
    with chaos.inject("serving.decode", "raise", prob=1.0, seed=3):
        sock = _post(fe.port, {"model": "lm", "prompt": [4, 4, 2],
                               "max_new_tokens": 12})
        raw = _read_all(sock)
        sock.close()
    lines = _lines(raw)
    assert [sorted(l) for l in lines[:-1]] == [["token"]]
    tail = lines[-1]
    assert tail["done"] and tail["finish_reason"] == "error"
    assert tail["type"] == "MXNetError" and "decode step failed" \
        in tail["error"]
    assert "tokens" not in tail
    deadline = time.monotonic() + 15
    while not obs.events("serving.access") and time.monotonic() < deadline:
        time.sleep(0.01)
    assert [e.fields["status"] for e in obs.events("serving.access")] \
        == [200]
    # the lane serves on, and over the same connection's front end
    conn = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=30)
    conn.request("POST", "/v1/generate",
                 json.dumps({"model": "lm", "prompt": [1, 2],
                             "max_new_tokens": 3}),
                 {"Content-Type": "application/json"})
    assert len(conn.getresponse().read().strip().split(b"\n")) == 4
    conn.close()
    assert be.cache.stats()["used"] == 0


def test_requests_one_after_another_and_the_other_endpoints(served):
    """A caller's next request (a token alone, too: nothing left for
    the writer but the tail) and the plain endpoints are served as
    before."""
    sched, _, fe = served
    conn = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=30)
    for new in (5, 1, 7):
        conn.request("POST", "/v1/generate",
                     json.dumps({"model": "lm", "prompt": [9, 8, 7],
                                 "max_new_tokens": new}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        lines = [json.loads(l) for l in resp.read().decode().split("\n")
                 if l]
        assert resp.status == 200 and len(lines) == new + 1
        assert lines[-1]["tokens"] == [l["token"] for l in lines[:-1]]
    conn.request("GET", "/healthz")
    assert conn.getresponse().read() == b'{"status": "ok"}'
    conn.close()
