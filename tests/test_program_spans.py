"""The program's own spans (``observability/tracing.py``) record
whenever a JAX profiler session is live, lie in the profiler's trace
under ``mx:``, and cover the serving loop (``serving/generation.py``,
``frontend.py:_StreamWriter``); the benchmark's two readers of them
(``benchmark/program_spans.py``, ``readers/program_span_stat.py``,
``readers/program_gap_share.py``) on a recorded serving stretch.

- **A session is the switch**: under ``jax.profiler.start_trace`` a tiny
  ``LMBackend`` behind ``GenerationScheduler`` and the front end records
  every span of the table in ``docs/how_to/observability.md`` with its
  parent; the ``.xplane.pb`` holds the same spans as ``mx:`` events.
- **Off is off**: with no session and tracing not enabled the ring stays
  empty and a decode step's fetch asks the device nothing extra.
- **The readers** recover a known clock offset, give idle shares that
  add up to the device's idle share and per-step times that add up to
  the step, agree with ``trace_reduce.attribute_gaps``, and read nothing
  from an empty ring, a ring that dropped spans, or samples that do not
  match the trace.
"""

import collections
import http.client
import json
import os
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

from benchmark import program_spans, trace_reduce
from benchmark.spec import load_module

from mxnet_tpu import serving
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, SEQ_LEN = 64, 48

# span -> its parent, as the table has them (None: a root)
TABLE = {
    "generation.idle": None,
    "generation.iterate": None,
    "generation.queue": "serving.request",
    "generation.prefill": "generation.iterate",
    "prefill.dispatch": "generation.prefill",
    "prefill.fetch": "generation.prefill",
    "generation.prefill_write": "generation.iterate",
    "decode.build": "generation.iterate",
    "generation.decode": "generation.iterate",
    "decode.dispatch": "generation.decode",
    "decode.deliver": "generation.decode",
    "decode.wait": "generation.decode",
    "decode.copy": "generation.decode",
    "decode.publish": "generation.iterate",
    "stream.flush": None,
}


@pytest.fixture(scope="module")
def served():
    """A scheduler and a front end over a tiny model, warmed."""
    cfg = tfm.lm_config(num_classes=VOCAB, seq_len=SEQ_LEN, num_embed=16,
                        num_heads=2, num_layers=2)
    backend = serving.LMBackend(tfm.init_lm_params(cfg, seed=0), cfg,
                                block_size=4, num_blocks=64, model="lm")
    sched = serving.GenerationScheduler()
    sched.register("lm", backend, decode_buckets=[1, 2, 4],
                   prefill_buckets=[8, 16])
    sched.warmup("lm")
    fe = serving.start_frontend(sched, timeout=30)
    yield sched, backend, fe
    fe.close()
    sched.close()


def _generate(fe, prompt, new):
    conn = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=30)
    try:
        conn.request("POST", "/v1/generate", json.dumps(
            {"model": "lm", "prompt": prompt, "max_new_tokens": new}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        lines = [json.loads(raw) for raw in resp]
    finally:
        conn.close()
    assert resp.status == 200 and lines[-1]["done"]
    return lines[-1]["tokens"]


def _four_callers(fe):
    threads = [threading.Thread(target=_generate,
                                args=(fe, [1, 2, 3, i + 1], 12))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    """Four requests over HTTP under a profiler session, then the lane
    idle for two waits: the ring's spans and the ``mx:`` events of the
    session's ``.xplane.pb`` (name -> list of metadata)."""
    import jax
    from jax.profiler import ProfileData

    _, _, fe = served
    logdir = str(tmp_path_factory.mktemp("session"))
    tracing.clear_spans()
    jax.profiler.start_trace(logdir)
    try:
        _four_callers(fe)
        time.sleep(0.12)
    finally:
        jax.profiler.stop_trace()
    spans = tracing.spans()
    tracing.clear_spans()
    events = collections.defaultdict(list)
    data = ProfileData.from_file(trace_reduce.find_xplane(logdir))
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.ANNOTATION_PREFIX):
                    events[ev.name].append(dict(ev.stats))
    return spans, events


@pytest.mark.parametrize("name", sorted(TABLE))
def test_a_session_records_every_span_of_the_table_under_its_parent(
        traced, name):
    spans, _ = traced
    by_id = {s.span_id: s for s in spans}
    mine = [s for s in spans if s.name == name]
    assert mine, "no %s span was recorded" % name
    for s in mine:
        parent = by_id.get(s.parent_id)
        if TABLE[name] is None:
            assert s.parent_id == 0
        else:
            assert parent is not None and parent.name == TABLE[name], (
                name, s.parent_id)
        assert s.end_us >= s.start_us
        if name != "stream.flush":
            assert s.attrs["model"] == "lm"


def test_spans_of_the_loop_nest_inside_their_parents(traced):
    spans, _ = traced
    by_id = {s.span_id: s for s in spans}
    loop = [s for s in spans if s.name in TABLE
            and s.name not in ("generation.queue", "stream.flush")]
    assert len({s.tid for s in loop}) == 1          # one loop thread
    for s in loop:
        parent = by_id.get(s.parent_id)
        if parent is not None:
            assert parent.start_us <= s.start_us <= s.end_us <= parent.end_us


def test_queue_and_prefill_spans_carry_the_requests_token(traced):
    spans, _ = traced
    roots = {"%d:%d" % (os.getpid(), s.span_id)
             for s in spans if s.name == "serving.request"}
    assert len(roots) == 4
    for name in ("generation.queue", "generation.prefill",
                 "generation.prefill_write"):
        tokens = [s.attrs["request"] for s in spans if s.name == name]
        assert sorted(tokens) == sorted(roots), name
    queue = [s for s in spans if s.name == "generation.queue"]
    assert all(s.attrs["tenant"] == "default" for s in queue)
    assert all(0 <= s.end_us - s.start_us < 30e6 for s in queue)


def test_the_attributes_that_were_there_stay(traced):
    spans, _ = traced
    prefill = [s for s in spans if s.name == "generation.prefill"]
    assert all(s.attrs["attempt"] == 0 and s.attrs["bucket"] == 8
               and s.attrs["length"] == 4 and "error" not in s.attrs
               for s in prefill)
    decode = [s for s in spans if s.name == "generation.decode"]
    assert all(s.attrs["attempt"] == 0 and s.attrs["rows"]
               == len(s.attrs["requests"]) for s in decode)
    iterate = [s for s in spans if s.name == "generation.iterate"]
    assert sum(s.attrs["admitted"] for s in iterate) == 4
    assert {s.attrs["ahead"] for s in spans
            if s.name == "decode.dispatch"} <= {0, 1}
    flush = [s for s in spans if s.name == "stream.flush"]
    assert sum(s.attrs["chunks"] for s in flush) > 0
    assert max(s.attrs["streams"] for s in flush) <= 4


def test_the_xplane_holds_the_same_spans_as_mx_events(traced):
    spans, events = traced
    recorded = collections.Counter(s.name for s in spans)
    for name in TABLE:
        # a span still open when the session stopped is in the ring only
        assert 0 < len(events["mx:" + name]) <= recorded[name], name
    ids = {s.span_id: s for s in spans}
    for stats in events["mx:decode.wait"]:
        s = ids[int(stats["span_id"])]
        assert s.name == "decode.wait"
        assert int(stats["parent_id"]) == s.parent_id
    for stats in events["mx:generation.queue"]:
        assert stats["request"] == ids[int(stats["span_id"])].attrs["request"]
        assert int(stats["end_us"]) >= int(stats["start_us"])


def test_with_no_session_and_tracing_off_nothing_is_recorded(
        served, monkeypatch):
    import jax

    _, backend, fe = served
    assert not tracing.tracing_enabled()
    calls = []
    block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or block(x))
    tracing.clear_spans()
    _four_callers(fe)
    assert tracing.spans() == []
    assert calls == []        # _fetch asked the device nothing extra
    assert tracing.capture_context() is None
    assert tracing.capture_wire_context() is None
    assert tracing.record_span("generation.queue") is None


def test_a_session_that_ends_stops_the_recording(served, tmp_path):
    import jax

    _, _, fe = served
    tracing.clear_spans()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.tracing_enabled()
        assert tracing.capture_context() == 0
        _generate(fe, [5, 6, 7], 3)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.tracing_enabled()
    time.sleep(0.1)           # spans open at the stop have closed
    before = len(tracing.spans())
    assert before > 0
    _generate(fe, [5, 6, 7], 3)
    assert len(tracing.spans()) == before


def test_enable_tracing_records_into_the_ring_without_a_session(served):
    _, _, fe = served
    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        _generate(fe, [9, 8, 7], 3)
    finally:
        tracing.disable_tracing()
    names = {s.name for s in tracing.spans()}
    assert {"generation.iterate", "decode.wait", "generation.queue"} <= names


def test_profiler_capture_of_a_serving_process_shows_the_mx_spans(served):
    """``/profile?ms=N`` (``efficiency.capture_profile``): the program's
    spans are in the capture itself, with nothing else turned on."""
    from mxnet_tpu.observability import efficiency

    _, _, fe = served
    done = threading.Event()

    def traffic():
        while not done.is_set():
            _generate(fe, [3, 1, 4], 6)

    caller = threading.Thread(target=traffic)
    caller.start()
    try:
        trace, source = efficiency.capture_profile(300)
    finally:
        done.set()
        caller.join()
    assert source == "jax_profiler"
    names = {e.get("name") for e in trace["traceEvents"]}
    assert {"mx:generation.iterate", "mx:decode.dispatch",
            "mx:decode.wait", "mx:stream.flush"} <= names


def test_bench_table_no_longer_hides_the_benchmark_package():
    """ROADMAP D13: ``tools/bench_table.py`` loaded first, then the
    package, in a process of their own."""
    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('bt', %r)\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "from benchmark.spec import load_module\n"
        "import benchmark_score\n"
        "print('found', load_module.__module__)\n"
        % os.path.join(ROOT, "tools", "bench_table.py"))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "found benchmark.spec" in out.stdout


# ----------------------------------------------------------------------
# the readers' arithmetic on made-up spans


def _row(name, a, b, ident, parent=0, tid=7):
    return {"name": name, "a": a, "b": b, "id": ident, "parent": parent,
            "tid": tid}


def test_innermost_gives_each_stretch_to_the_span_that_started_last():
    rows = [_row("iterate", 0, 100, 1), _row("decode", 10, 80, 2, 1),
            _row("wait", 20, 50, 3, 2), _row("copy", 50, 60, 4, 2),
            _row("iterate", 120, 130, 5)]
    assert program_spans.innermost(rows) == [
        (0, 10, "iterate"), (10, 20, "decode"), (20, 50, "wait"),
        (50, 60, "copy"), (60, 80, "decode"), (80, 100, "iterate"),
        (120, 130, "iterate")]


def test_covered_splits_gaps_by_segment_and_keeps_what_none_covers():
    segments = [(0, 10, "a"), (10, 20, "b"), (30, 40, "a")]
    gaps = [(5, 12), (18, 33), (38, 50)]
    assert program_spans.covered(gaps, segments) == {
        "a": 5 + 3 + 2, "b": 2 + 2, "": 10 + 10}


def test_only_the_loops_own_spans_take_part():
    rows = [_row("generation.iterate", 0, 100, 1),
            _row("generation.decode", 10, 80, 2, 1),
            _row("generation.queue", 0, 90, 3, 50),      # a request's
            _row("stream.flush", 5, 9, 4, 0, tid=8),
            _row("serving.request", 0, 95, 50, 0, tid=9),
            _row("elsewhere", 20, 30, 6, 2, tid=8),
            _row("generation.idle", 100, 150, 7)]
    assert [s["name"] for s in program_spans.loop_spans(rows)] == [
        "generation.iterate", "generation.decode", "generation.idle"]


@pytest.mark.parametrize("lost", [0, 1])
def test_clock_offset_is_found_among_the_runs_samples(lost):
    rng = np.random.RandomState(3)
    ends = np.cumsum(rng.randint(7_000_000, 9_000_000, 400))
    traced = ends[150:250] + 123_456_789_000 + rng.randint(-3000, 3000, 100)
    traced = traced[:len(traced) - lost]
    assert abs(program_spans.clock_offset_ns(ends.tolist(), traced.tolist())
               - 123_456_789_000) < 3000
    near = (int(ends[140]), int(ends[260]))
    assert abs(program_spans.clock_offset_ns(
        ends.tolist(), traced.tolist(), near=near) - 123_456_789_000) < 3000


def test_clock_offset_refuses_calls_that_do_not_match():
    rng = np.random.RandomState(4)
    ends = np.cumsum(rng.randint(7_000_000, 9_000_000, 200))
    other = np.cumsum(rng.randint(7_000_000, 9_000_000, 50)) + 10 ** 12
    assert program_spans.clock_offset_ns(ends.tolist(),
                                         other.tolist()) is None
    assert program_spans.clock_offset_ns(ends.tolist(), [5]) is None
    assert program_spans.clock_offset_ns([1, 2], other.tolist()) is None


# ----------------------------------------------------------------------
# the readers on a recorded serving stretch


SAMPLE = os.path.join(ROOT, "benchmark", "data", "trace_sample_serve.json")


@pytest.fixture(scope="module")
def sample():
    with open(SAMPLE) as f:
        return json.load(f)


@pytest.fixture
def ctx(sample, monkeypatch):
    """What ``run.py`` hands the readers, with the sample's spans in the
    program's ring."""
    rows = [tracing.Span(name, "serving", a, b, tid, ident, parent, {})
            for name, a, b, tid, ident, parent in sample["ring"]]
    monkeypatch.setattr(tracing, "spans", lambda: list(rows))
    return {"trace": sample["trace"],
            "spans": types.SimpleNamespace(
                samples={"decode_call": [tuple(s) for s in
                                         sample["decode_call_samples"]]}),
            "compiles_in_window": {"spans_dropped_total": 0.0},
            "reduced": trace_reduce.reduce(sample["trace"])}


def _reader(name):
    return load_module(os.path.join(ROOT, "benchmark", "readers",
                                    name + ".py"), "reader_" + name).read


def _metric(name, ctx):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        doc = json.load(f)
    return _reader(doc["reader"])(ctx, doc["params"])


IDLE = ["idle_in_decode_dispatch_share", "idle_in_decode_wait_share",
        "idle_in_decode_copy_share", "idle_in_loop_share",
        "idle_in_prefill_share", "idle_no_work_share",
        "idle_unspanned_share"]
PER_STEP = ["decode_dispatch_ms", "decode_wait_ms", "decode_copy_ms",
            "loop_self_ms", "prefill_ms_per_step"]


def test_the_sample_recovers_its_clock_offset(sample, ctx):
    loaded = program_spans.load(ctx)
    assert abs(loaded["offset_ns"] - sample["expected"]["offset_ns"]) <= 2000


@pytest.mark.parametrize("name", IDLE + PER_STEP + [
    "queue_wait_ms_p50", "queue_wait_ms_p90", "prefill_span_ms_p50",
    "prefill_dispatch_ms_p50", "prefill_fetch_ms_p50", "loop_ms_per_step"])
def test_the_sample_reads_what_it_was_recorded_to_read(sample, ctx, name):
    # the shares were worked with the whole run's offset, of 920 calls;
    # the sample's 25 calls give it to within 2 us, which moves a share
    # by thousandths of a point (the attribution itself is held to
    # 1e-9 below)
    assert _metric(name, ctx) == pytest.approx(
        sample["expected"]["metrics"][name], rel=1e-6,
        abs=5e-3 if name.startswith("idle_") else 1e-9)


def test_idle_shares_add_up_to_the_devices_idle_share(ctx):
    total = sum(_metric(name, ctx) for name in IDLE)
    assert total == pytest.approx(
        _reader("device_idle_share")(ctx, {}), abs=1e-6)
    assert _metric("idle_unspanned_share", ctx) < 1.0
    assert _metric("idle_in_decode_wait_share", ctx) > 0


def test_idle_shares_agree_with_the_reductions_own_attribution(ctx):
    """``trace_reduce.attribute_gaps`` over the loop's spans gives the
    same seconds, stretch by stretch to the span that started last."""
    loaded = program_spans.load(ctx)
    window = tuple(ctx["trace"]["window_ns"])
    host = [[s["name"], s["a"], s["b"] - s["a"]] for s in loaded["loop"]]
    events = ctx["trace"]["devices"]["0"]
    slow = trace_reduce.attribute_gaps(
        trace_reduce.idle_gaps(events, window), host, window)
    fast, _ = program_spans.gap_shares(ctx)
    assert set(fast) - {""} == set(slow) - {"unattributed"}
    for name, seconds in slow.items():
        assert fast["" if name == "unattributed" else name] \
            == pytest.approx(seconds, abs=1e-9)


def test_per_step_times_add_up_to_the_step(ctx):
    parts = sum(_metric(name, ctx) for name in PER_STEP)
    idle = _reader("program_span_stat")(ctx, {
        "spans": ["generation.idle"], "stat": "per_step", "scale": 1000.0})
    assert parts + (idle or 0.0) == pytest.approx(
        _metric("loop_ms_per_step", ctx), rel=0.02)


def test_nothing_is_read_from_an_empty_ring(ctx, monkeypatch):
    monkeypatch.setattr(tracing, "spans", lambda: [])
    assert _metric("decode_wait_ms", ctx) is None
    assert _metric("idle_in_decode_wait_share", ctx) is None
    assert _metric("idle_unspanned_share", ctx) is None


def test_nothing_is_read_from_a_ring_that_dropped_spans(ctx):
    ctx["compiles_in_window"]["spans_dropped_total"] = 3.0
    assert _metric("queue_wait_ms_p50", ctx) is None
    assert _metric("idle_in_loop_share", ctx) is None


def test_nothing_is_read_where_the_samples_do_not_match(ctx):
    rng = np.random.RandomState(5)
    ctx["spans"].samples["decode_call"] = [
        (end + float(rng.uniform(0.0002, 0.003)), dt)
        for end, dt in ctx["spans"].samples["decode_call"]]
    assert _metric("decode_copy_ms", ctx) is None
    assert _metric("idle_in_decode_copy_share", ctx) is None


def test_nothing_is_read_without_a_trace_or_the_harness_spans(ctx):
    assert _metric("decode_wait_ms", dict(ctx, trace=None)) is None
    assert _metric("idle_no_work_share", dict(ctx, spans=None)) is None


def test_every_new_metric_has_its_file_and_its_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entries = {m["name"]: m for m in doc["per_layer"]}
    serving_cells = [w["name"] for w in doc["workloads"]
                     if "serve" in w["traffic"]]
    for name in IDLE + PER_STEP + ["d2h_mb_per_step", "loop_ms_per_step"]:
        assert entries[name]["workloads"] == serving_cells, name
        assert entries[name]["moves"] == "serve_tokens_per_s"
    for name in ("queue_wait_ms_p50", "queue_wait_ms_p90",
                 "prefill_span_ms_p50", "prefill_dispatch_ms_p50",
                 "prefill_fetch_ms_p50"):
        assert entries[name]["moves"] == "ttft_p50_ms"
        assert entries[name]["source"] == "program_span"
    covered = set()
    for name in IDLE:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            spans = json.load(f)["params"]["spans"]
        assert not covered & set(spans)
        covered |= set(spans)
    # every span of the loop's thread is in exactly one share
    assert covered == set(TABLE) - {"generation.queue", "stream.flush"}
