"""Start-up says where it goes (mxnet_tpu/compile_cache.py): every
compile stage JAX reports, every answer of the persistent cache and
every warm-up call is booked, inside the program, to the start-up scope
that caused it.

- **Stages by scope**: a jit compiled inside a scope books ``trace`` /
  ``lower`` / ``xla`` seconds to that scope and its ``program``, one
  compiled outside any books to ``none``, a warm program books nothing,
  nested scopes book to the innermost, and a jit traced inside another's
  trace is counted once.
- **The cache's answers**: a second compile of the same program under a
  temporary cache directory counts a hit and ``cache_load`` seconds.
- **The entry points**: ``GenerationScheduler.warmup`` books one
  ``warmup.prefill`` / ``warmup.decode`` program a bucket and a warmed
  run leaves ``serve.cold`` empty; a ``ShardedTrainer`` books
  ``trainer.first_call`` once a cache, ``trainer.cost_analysis`` inside
  it, and ``trainer_compile_seconds`` takes that scope's clock.
- **Spans**: ``compile.<stage>`` under the scope's span, only while
  tracing is enabled.
- **The benchmark's reader** sums and maxes a rendered registry.
"""

import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import mxnet_tpu as mx
from mxnet_tpu import compile_cache as cc
from mxnet_tpu import observability as obs
from mxnet_tpu import serving
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.parallel.trainer import ShardedTrainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORK = ("trace", "lower", "xla")


def _value(family, *labels):
    return obs.REGISTRY.get(family).labels(*labels).value


def _stages(scope, program=None):
    """The seconds of the three working stages booked to ``scope`` (and
    ``program``)."""
    if program is None:
        return [_value("compile_stage_seconds_total", scope, s) for s in WORK]
    return [_value("compile_program_seconds_total", scope, program, s)
            for s in WORK]


def _live(family, **want):
    """The label sets of ``family``'s series that moved since the last
    reset and carry ``want``."""
    fam = obs.REGISTRY.get(family)
    out = []
    for key, child in fam._children.items():
        labels = dict(zip(fam.label_names, key))
        if child.value > 0 and want.items() <= labels.items():
            out.append(labels)
    return out


def _fresh(n):
    """A jitted function no other test compiled, and its argument."""
    return jax.jit(lambda x: jnp.tanh(x * n) + n), np.ones(n, np.float32)


# ---------------------------------------------------------------------------
# stages by scope
# ---------------------------------------------------------------------------

def _inside():
    fn, x = _fresh(31)
    with cc.scope("warmup.prefill", "prefill:31") as sc:
        fn(x)
    assert all(s > 0 for s in _stages("warmup.prefill"))
    assert _stages("warmup.prefill", "prefill:31") == _stages("warmup.prefill")
    assert [sc.stages[s] for s in WORK] == _stages("warmup.prefill")
    assert _value("compile_requests_total", "warmup.prefill") \
        == sc.requests == 1
    assert _stages("none") == [0, 0, 0]
    assert _value("startup_seconds_total", "warmup.prefill") == sc.seconds
    assert sum(sc.stages.values()) <= sc.seconds


def _outside():
    fn, x = _fresh(32)
    fn(x)
    assert all(s > 0 for s in _stages("none"))
    assert _value("compile_requests_total", "none") == 1
    assert _live("compile_stage_seconds_total") == _live(
        "compile_stage_seconds_total", scope="none")
    assert _live("compile_program_seconds_total") == []


def _warm():
    fn, x = _fresh(33)
    with cc.scope("warmup.decode", "decode:33"):
        fn(x)
    before = obs.REGISTRY.render()
    with cc.scope("warmup.decode", "decode:33") as again:
        fn(x)
    assert again.requests == 0 and not any(again.stages.values())
    after = obs.REGISTRY.render()
    moved = [a for a, b in zip(after.splitlines(), before.splitlines())
             if a != b]
    assert [m.split(" ")[0] for m in moved] == [
        'startup_seconds_total{scope="warmup.decode"}']


def _nested():
    outer_fn, x = _fresh(34)
    inner_fn, y = _fresh(35)
    with cc.scope("trainer.first_call", "step") as outer:
        outer_fn(x)
        with cc.scope("trainer.cost_analysis", "step") as inner:
            inner_fn(y)
    assert outer.children == [inner] and inner.children == []
    assert outer.requests == inner.requests == 1
    assert [inner.stages[s] for s in WORK] == _stages(
        "trainer.cost_analysis", "step")
    assert [outer.stages[s] for s in WORK] == _stages(
        "trainer.first_call", "step")
    assert inner.seconds < outer.seconds
    assert not cc.is_open()


def _trace_in_a_trace():
    """Every ``jnp`` function is a jit of its own: traced inside
    ``fn``'s trace, each reports its seconds, which are inside
    ``fn``'s."""
    reported = []

    def listen(event, seconds, **_):
        if event.endswith("jaxpr_trace_duration"):
            reported.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        fn, x = _fresh(36)
        with cc.scope("warmup.prefill", "prefill:36") as sc:
            fn(x)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(reported) > 1
    assert sc.stages["trace"] == max(reported) < sum(reported)


@pytest.mark.parametrize("case", [_inside, _outside, _warm, _nested,
                                  _trace_in_a_trace],
                         ids=lambda c: c.__name__.strip("_"))
def test_stages_are_booked_to_the_scope_that_caused_them(case):
    case()


def test_a_scope_is_a_decorator_too():
    fn, x = _fresh(37)

    @cc.scope("backend.build")
    def build(a, b=2):
        """doc"""
        assert cc.is_open()
        return fn(x), a + b

    assert build.__doc__ == "doc" and build(1, b=3)[1] == 4
    assert _value("compile_requests_total", "backend.build") == 1
    first = _value("startup_seconds_total", "backend.build")
    build(1)
    assert _value("startup_seconds_total", "backend.build") > first > 0


def test_metrics_off_books_nothing(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_METRICS", "0")
    fn, x = _fresh(38)
    with cc.scope("warmup.prefill", "prefill:38") as sc:
        fn(x)
    monkeypatch.delenv("MXNET_TPU_METRICS")
    assert sc.requests == 1 and sc.seconds > 0      # the table still reads
    assert _live("compile_stage_seconds_total") == []
    assert _live("startup_seconds_total") == []


# ---------------------------------------------------------------------------
# the persistent cache's answers
# ---------------------------------------------------------------------------

def test_a_second_compile_of_a_program_is_a_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as jcc

    knobs = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0,
             "jax_persistent_cache_min_entry_size_bytes": 0}
    was = {k: getattr(jax.config, k) for k in knobs}
    for k, v in knobs.items():
        jax.config.update(k, v)
    jcc.reset_cache()
    try:
        x = np.ones(39, np.float32)
        # two functions of one text: one module, one key, two jit caches
        first, second = (jax.jit(lambda x: jnp.sin(x) * 39.0)
                         for _ in range(2))
        with cc.scope("warmup.prefill", "prefill:39") as cold:
            first(x)
        with cc.scope("warmup.decode", "decode:39") as warm:
            second(x)
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        jcc.reset_cache()
    assert (cold.cache, cold.requests) == ({"hit": 0, "miss": 1}, 1)
    assert (warm.cache, warm.requests) == ({"hit": 1, "miss": 0}, 1)
    assert cold.stages["cache_load"] == 0
    assert 0 < warm.stages["cache_load"] <= warm.stages["xla"]
    assert _value("compile_cache_misses_total", "warmup.prefill") == 1
    assert _value("compile_cache_hits_total", "warmup.decode") == 1
    assert _value("compile_stage_seconds_total", "warmup.decode",
                  "cache_load") == warm.stages["cache_load"]
    assert _value("compile_program_seconds_total", "warmup.decode",
                  "decode:39", "cache_load") == warm.stages["cache_load"]
    rows = cc.table([cold, warm])
    assert rows[0].split() == ["scope", "program", "trace", "lower", "xla",
                               "cache_load", "cache", "wall_s"]
    assert rows[1].split()[:2] == ["warmup.prefill", "prefill:39"]
    assert rows[1].split()[-3:-1] == ["1", "miss"]
    assert rows[2].split()[-3:-1] == ["1", "hit"]


# ---------------------------------------------------------------------------
# the serving entry points
# ---------------------------------------------------------------------------

PREFILL, DECODE = [8, 16], [1, 2]


@pytest.fixture(scope="module")
def lm():
    cfg = tfm.lm_config(num_classes=64, seq_len=48, num_embed=16,
                        num_heads=2, num_layers=2)
    return cfg, tfm.init_lm_params(cfg, seed=0)


def _served(lm, model):
    cfg, params = lm
    sched = serving.GenerationScheduler()
    backend = serving.LMBackend(params, cfg, block_size=4, num_blocks=64,
                                model=model)
    sched.register("lm", backend, decode_buckets=DECODE,
                   prefill_buckets=PREFILL)
    return sched, backend


def test_warmup_books_a_program_a_bucket_and_serving_none(lm, caplog):
    sched, _ = _served(lm, "startup_warm")
    assert _value("startup_seconds_total", "backend.build") > 0
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.compile_cache"):
        cold = sched.warmup("lm")
    try:
        assert cold == len(PREFILL) + len(DECODE)
        for scope, kind, buckets in (("warmup.prefill", "prefill", PREFILL),
                                     ("warmup.decode", "decode", DECODE)):
            booked = _live("compile_program_seconds_total", scope=scope,
                           stage="xla")
            assert sorted(row["program"] for row in booked) == sorted(
                "%s:%d" % (kind, b) for b in buckets)
            assert _value("startup_seconds_total", scope) > 0
        # a bucket is its program and the pool write behind it: more
        # programs than the cold count says
        programs = sum(_value("compile_requests_total", scope)
                       for scope in ("warmup.prefill", "warmup.decode"))
        assert programs > cold
        table = [r.message for r in caplog.records
                 if r.name == "mxnet_tpu.compile_cache"]
        assert len(table) == 1 and table[0].startswith("warmup of 'lm'")
        assert [line.split()[1] for line in table[0].splitlines()[2:]] == [
            "prefill:8", "prefill:16", "decode:1", "decode:2"]
        warm = obs.REGISTRY.render()
        assert sched.generate("lm", [1, 2, 3], max_new_tokens=6)
        assert sched.warmup("lm") == 0
    finally:
        sched.close()
    assert _live("startup_seconds_total", scope="serve.cold") == []
    assert _live("compile_requests_total", scope="serve.cold") == []
    # serving and the second warm-up compiled nothing, anywhere
    compiled = [line for line in warm.splitlines()
                if line.startswith("compile_")]
    assert compiled and all(line in obs.REGISTRY.render()
                            for line in compiled)


def test_a_cold_call_outside_warmup_is_serve_cold(lm):
    _, backend = _served(lm, "startup_cold")
    tokens = np.zeros(8, np.int32)
    assert backend.prefill(tokens, 3)[3] is True
    booked = _live("compile_program_seconds_total", scope="serve.cold")
    assert {row["program"] for row in booked} == {"prefill:8"}
    assert _value("compile_requests_total", "serve.cold") >= 1
    first = _value("startup_seconds_total", "serve.cold")
    assert first > 0
    assert backend.prefill(tokens, 3)[3] is False
    assert _value("startup_seconds_total", "serve.cold") == first


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------

def _trainer():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(net, num_hidden=8, name="fc2"),
        name="softmax")
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    return ShardedTrainer(net, mesh, data_shapes={"data": (8, 6)},
                          label_shapes={"softmax_label": (8,)},
                          momentum=0.9)


def test_a_trainer_books_its_first_call_once_a_cache(caplog):
    trainer = _trainer()
    state = trainer.init(seed=0)
    build = _value("startup_seconds_total", "trainer.build")
    assert build > 0
    rs = np.random.RandomState(0)
    batch = trainer.place_batch({
        "data": rs.randn(8, 6).astype(np.float32),
        "softmax_label": rs.randint(0, 8, 8).astype(np.float32)})
    step, key = trainer.step_fn(), jax.random.PRNGKey(0)
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.compile_cache"):
        _, *state = step(*state, batch, key)
    first = _value("startup_seconds_total", "trainer.first_call")
    inside = _value("startup_seconds_total", "trainer.cost_analysis")
    assert 0 < inside < first
    # the analysis comes first and does the work; what the call itself
    # still pays behind it is JAX's to say, and is booked beside it
    assert all(s > 0 for s in _stages("trainer.cost_analysis", "step"))
    assert _value("compile_requests_total", "trainer.cost_analysis") == 1
    assert _stages("trainer.first_call", "step") == _stages(
        "trainer.first_call")
    assert sum(_stages("trainer.first_call")
               + _stages("trainer.cost_analysis")) < first
    # the histogram's clock is the scope's own
    seconds = obs.REGISTRY.get("trainer_compile_seconds").labels("step")
    assert (seconds.count, seconds.sum) == (1, first)
    assert _value("trainer_compiles_total", "step") == 1
    table = [r.message for r in caplog.records
             if r.name == "mxnet_tpu.compile_cache"]
    assert len(table) == 1 and table[0].startswith("first call of 'step'")
    assert [line.split()[0] for line in table[0].splitlines()[2:]] == [
        "trainer.first_call", "trainer.cost_analysis"]
    # a steady step books nothing, and construction is not start-up's
    # again
    step(*state, batch, key)
    assert _value("startup_seconds_total", "trainer.first_call") == first
    assert _value("startup_seconds_total", "trainer.build") == build
    assert _value("trainer_compiles_total", "step") == 1


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tracing", [False, True], ids=["off", "on"])
def test_stage_spans_lie_under_the_scopes_span(tracing):
    obs.clear_spans()
    if tracing:
        obs.enable_tracing()
    fn, x = _fresh(40 + tracing)
    with cc.scope("warmup.prefill", "prefill:40"):
        fn(x)
    spans = obs.spans()
    if not tracing:
        assert spans == []
        return
    by_name = {s.name: s for s in spans}
    parent = by_name["warmup.prefill"]
    assert parent.attrs == {"program": "prefill:40"}
    assert parent.cat == "startup"
    for stage in WORK:
        child = by_name["compile." + stage]
        assert child.parent_id == parent.span_id
        assert child.attrs["program"] == "prefill:40"
        assert parent.start_us <= child.start_us <= child.end_us \
            <= parent.end_us
        assert (child.end_us - child.start_us) / 1e6 == pytest.approx(
            _value("compile_stage_seconds_total", "warmup.prefill", stage),
            abs=2e-6)


# ---------------------------------------------------------------------------
# the package's import, and the benchmark's reader
# ---------------------------------------------------------------------------

def test_the_packages_import_is_booked():
    code = ("import mxnet_tpu as mx; "
            "from mxnet_tpu import observability as obs; "
            "print(obs.REGISTRY.get('startup_seconds_total')"
            ".labels('package.import').value)")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, timeout=300, check=True,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert 0.05 < float(out.stdout.split()[-1]) < 300


def _reader():
    from benchmark.spec import Spec

    return Spec(ROOT).reader("registry_series")


SECONDS = {("warmup.prefill", "prefill:8", "trace"): 1.0,
           ("warmup.prefill", "prefill:8", "xla"): 2.0,
           ("warmup.prefill", "prefill:8", "cache_load"): 1.5,
           ("warmup.prefill", "prefill:16", "lower"): 0.25,
           ("warmup.decode", "decode:4", "xla"): 0.5,
           ("none", "", "xla"): 64.0}
THREE = {"stage": list(WORK)}
NOT_NONE = {"scope": "none"}


@pytest.mark.parametrize("params, want", [
    ({}, 69.25),
    ({"without": NOT_NONE}, 5.25),
    ({"labels": {"stage": "xla"}, "without": NOT_NONE}, 2.5),
    ({"labels": {"scope": ["warmup.decode", "none"]}}, 64.5),
    ({"labels": THREE, "without": NOT_NONE, "by": "program",
      "stat": "max"}, 3.0),
    ({"labels": THREE, "by": "scope", "stat": "max", "scale": 0.5}, 32.0),
    ({"labels": {"stage": "parse"}}, 0.0),
    ({"labels": {"stage": "parse"}, "stat": "max"}, 0.0),
    ({"labels": {"stage": "xla"}, "without": NOT_NONE, "scale": 100.0,
      "share_of": ["test_startup_seconds_total"]}, 100.0),
    ({"labels": {"stage": "cache_load"}, "scale": 100.0,
      "share_of": ["test_startup_seconds_total",
                   "test_startup_other_total"]}, 37.5),
    ({"labels": {"stage": "parse"},
      "share_of": ["test_startup_seconds_total"]}, None),
    ({"name": "test_startup_no_such_total"}, None),
], ids=lambda p: None if not isinstance(p, dict) else
    ",".join(sorted(p)) or "all")
def test_registry_series_sums_and_maxes_a_rendered_registry(params, want):
    family = obs.REGISTRY.counter("test_startup_seconds_total", "for a test",
                                  ["scope", "program", "stage"])
    for labels, seconds in SECONDS.items():
        family.labels(*labels).inc(seconds)
    obs.REGISTRY.counter("test_startup_other_total", "for a test",
                         ["stage"]).labels("cache_load").inc(2.5)
    got = _reader()({}, dict({"name": "test_startup_seconds_total"},
                             **params))
    assert got == want


def test_the_startup_metrics_read_the_programs_families(lm):
    """Every ``startup_*`` metric of BENCHMARK.json names a family this
    program registers, through a reader that finds it."""
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    mine = [m for m in doc["per_layer"] if m["name"].startswith("startup_")]
    assert len(mine) == 12
    sched, _ = _served(lm, "startup_read")
    sched.warmup("lm")
    sched.close()
    cc.book("package.import", 1.5)
    from benchmark.spec import Spec

    spec = Spec(ROOT)
    for m in mine:
        assert (m["layer"], m["moves"], m["source"]) == (
            "entry points and compile cache", "setup_s", "program_counter")
        entry = spec.metric_file(m["name"])
        value = spec.reader(entry["reader"])({}, entry["params"])
        if m["name"] in ("startup_first_call_s", "startup_cost_analysis_s",
                         "startup_cache_load_s"):
            assert not value, m["name"]         # no trainer, no cache here
        elif m["name"] == "startup_cache_hit_share":
            assert value is None                # no cache was asked
        else:
            assert value > 0, m["name"]


# ---------------------------------------------------------------------------
# a cell's traced run, rehearsed at the tiny size on the CPU
# ---------------------------------------------------------------------------

TINY_CELLS = {"gpt2m-train": "tiny-gpt2-train",
              "gpt2m-serve-chat": "tiny-gpt2-serve"}


@pytest.fixture(scope="module")
def tiny_benchmark():
    """The real BENCHMARK.json cut to GPT-2's two cells, renamed to the
    tiny configuration and traffic under ``benchmark/tests/tiny``."""
    import json

    from benchmark.spec import Spec

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"], doc["run_seconds"] = ["."], 1
    doc["configs"] = [dict(c, name="tiny-gpt2", source="test only",
                           file="configs/tiny-gpt2.json")
                      for c in doc["configs"] if c["name"] == "gpt2-medium"]
    doc["workloads"] = [
        dict(w, name=TINY_CELLS[w["name"]], config="tiny-gpt2",
             traffic={"serve-chat-closed16": "serve-tiny"}.get(
                 w["traffic"], w["traffic"]))
        for w in doc["workloads"] if w["name"] in TINY_CELLS]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY_CELLS[w] for w in m["workloads"]
                              if w in TINY_CELLS]
    return Spec(os.path.join(ROOT, "benchmark", "tests", "tiny"), doc=doc)


@pytest.mark.parametrize("cell, own", [
    ("tiny-gpt2-serve", ["startup_warmup_s"]),
    ("tiny-gpt2-train", ["startup_first_call_s", "startup_cost_analysis_s"]),
])
def test_a_traced_run_reads_the_startup_metrics(tiny_benchmark, cell, own):
    from benchmark import run

    obs.reset_metrics()
    cc.book("package.import", 1.25)     # a reset took the import's own
    result = run.run_cell(tiny_benchmark, cell, 3000000050, 0.5, 1,
                          require_chip=False)
    read = {name: m["value"] for name, m in result["metrics"].items()
            if name.startswith("startup_")}
    # a rehearsal keeps no persistent cache: no share to read
    assert sorted(read) == sorted(own + [
        "startup_import_s", "startup_build_s", "startup_trace_s",
        "startup_lower_s", "startup_xla_s", "startup_cache_load_s",
        "startup_programs", "startup_worst_program_s"])
    assert read["startup_import_s"] == 1.25
    assert read["startup_cache_load_s"] == 0
    assert read["startup_programs"] >= 1
    stages = sum(read["startup_%s_s" % s] for s in WORK)
    assert 0 < read["startup_worst_program_s"] <= stages
    assert stages <= read["startup_build_s"] + read[own[0]]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    if cell == "tiny-gpt2-serve":
        # the inside twin of the benchmark's clock around warmup()
        outside = result["metrics"]["compile_s"]["value"]
        assert outside - 0.5 < read["startup_warmup_s"] <= outside
        assert _live("startup_seconds_total", scope="serve.cold") == []
    else:
        assert read["startup_cost_analysis_s"] < read["startup_first_call_s"]
