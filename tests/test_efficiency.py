"""Compute-efficiency accounting plane: per-jit-cache HLO cost
analysis (FLOPs recorded exactly once per compile), measured MFU, the
goodput ledger (productive + badput reconcile with the fit wall within
5% on every fit path, chaos included), the ``/profile`` endpoint, the
bench schema-4 keys, worker-rank metrics serving, and the bench trend
gate — plus the ``MXNET_TPU_METRICS=0`` constant-time guard for every
new record path.

Everything runs in-process on the CPU backend (thread-backed kvstore
servers, seeded chaos), mirroring test_watchdog.py's strategy.
"""

import importlib.util
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import mxnet_tpu as mx
from mxnet_tpu import chaos
from mxnet_tpu import kvstore_async as ka
from mxnet_tpu import observability as obs
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.observability import efficiency as eff
from mxnet_tpu.observability import metrics as omet
from mxnet_tpu.parallel.trainer import ShardedTrainer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

B, D = 8, 6


def _mlp():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _mk(K=1, devices=2, **kw):
    kw.setdefault("momentum", 0.9)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("data",))
    return ShardedTrainer(_mlp(), mesh, data_shapes={"data": (B, D)},
                          label_shapes={"softmax_label": (B,)},
                          wd=1e-4, rescale_grad=1.0 / B,
                          pipeline_steps=K, **kw)


def _data_iter(rows=64, seed=3):
    rs = np.random.RandomState(seed)
    return NDArrayIter(rs.randn(rows, D).astype(np.float32),
                       rs.randint(0, 8, (rows,)).astype(np.float32),
                       batch_size=B)


def _gauge(name):
    fam = obs.REGISTRY.get(name)
    return fam._default.value if fam is not None and fam._default else None


# ---------------------------------------------------------------------------
# HLO cost accounting: exactly once per compile (tentpole acceptance)
# ---------------------------------------------------------------------------

def _counting_record_compile(monkeypatch):
    calls = []
    real = eff.record_compile

    def spy(cache, lower, steps=1):
        calls.append(cache)
        return real(cache, lower, steps=steps)

    monkeypatch.setattr(eff, "record_compile", spy)
    return calls


def test_compile_flops_recorded_once_per_compile_pipelined(monkeypatch):
    """Cost analysis fires on the warmup compile ONLY — a second epoch
    over the same shapes records nothing — and a pipeline-depth change
    (the epoch-tail flush) is a new jit cache, hence exactly one more
    record."""
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    calls = _counting_record_compile(monkeypatch)
    # 9 batches, K=2: four full flushes + one tail flush of depth 1
    _mk(K=2).fit(_data_iter(72), num_epoch=2, seed=0)
    assert len(calls) == 2, calls
    assert calls[0].startswith("pipe:2:")
    assert calls[1].startswith("pipe:1:")
    flops = obs.REGISTRY.get("trainer_compile_flops")
    for cache in calls:
        assert flops.labels(cache).value > 0, cache
    # compile counter agrees: one compile per cache, none steady-state
    compiles = obs.REGISTRY.get("trainer_compiles_total")
    for cache in calls:
        assert compiles.labels(cache).value == 1, cache
    assert eff.model_flops_per_step() > 0
    assert obs.REGISTRY.get(
        "trainer_compile_bytes_accessed").labels(calls[0]).value > 0
    assert obs.REGISTRY.get(
        "trainer_compile_arithmetic_intensity").labels(calls[0]).value > 0


def test_compile_flops_once_step_flops_exact_and_mfu_per_step(monkeypatch):
    """Per-step path: one 'step' cache compile, the derived
    trainer_step_model_flops equals that program's FLOPs exactly
    (steps-per-dispatch = 1), and the fit leaves a measured MFU gauge
    behind (peak pinned via MXNET_TPU_DEVICE_PEAK_FLOPS)."""
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    monkeypatch.setenv("MXNET_TPU_DEVICE_PEAK_FLOPS", "1e12")
    calls = _counting_record_compile(monkeypatch)
    _mk(K=1).fit(_data_iter(16), num_epoch=2, seed=0)
    assert calls == ["step"]
    per_exec = obs.REGISTRY.get("trainer_compile_flops").labels("step").value
    assert per_exec > 0
    assert eff.model_flops_per_step() == per_exec
    assert _gauge("model_flops_per_sec") > 0
    mfu = _gauge("model_flops_utilization")
    assert mfu is not None and 0 < mfu < 1  # tiny MLP on a 1 TFLOP peak
    rows, summary = eff.efficiency_table()
    assert rows and rows[0][1] > 0
    assert dict(summary)["mfu"] == mfu
    assert "mfu" in eff.format_efficiency()


def test_record_compile_fallback_and_off_switch(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    # a backend/trace that supports neither analysis tier books the
    # unsupported marker instead of raising
    def boom():
        raise RuntimeError("no cost analysis here")

    eff.record_compile("weird", boom)
    assert obs.REGISTRY.get(
        "trainer_compile_cost_unsupported_total").labels("weird").value == 1
    # MXNET_TPU_COST_ANALYSIS=0 skips entirely (no lower() call even)
    monkeypatch.setenv("MXNET_TPU_COST_ANALYSIS", "0")
    eff.record_compile("weird", boom)
    assert obs.REGISTRY.get(
        "trainer_compile_cost_unsupported_total").labels("weird").value == 1


def test_peak_flops_table_and_override(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_DEVICE_PEAK_FLOPS", raising=False)
    assert eff.peak_flops("TPU v5 lite") == 197e12
    assert eff.peak_flops("TPU v5p chip") == 459e12
    assert eff.peak_flops("NVIDIA H100 80GB") == 989e12
    # the CPU has no peak (so no MFU), and a device the table does not
    # know is an error rather than a made-up denominator
    assert eff.peak_flops("cpu") is None
    assert eff.peak_flops() is None     # this suite runs on the CPU
    with pytest.raises(mx.MXNetError, match="mystery device"):
        eff.peak_flops("mystery device")
    monkeypatch.setenv("MXNET_TPU_DEVICE_PEAK_FLOPS", "123e9")
    assert eff.peak_flops("TPU v5p chip") == 123e9


# ---------------------------------------------------------------------------
# goodput ledger: the books reconcile with the fit wall (tentpole)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [1, 2])
def test_goodput_reconciles_with_fit_wall(K, monkeypatch, tmp_path):
    """Productive + every badput cause must account the fit() wall
    within 5% on BOTH the per-step and pipelined paths — the warmup
    compile books as cause=recompile (so goodput_ratio < 1), and the
    K=1 run checkpoints so the epoch-end save books as
    cause=checkpoint."""
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    ckpt = str(tmp_path) if K == 1 else None
    _mk(K=K).fit(_data_iter(80), num_epoch=1, seed=0, checkpoint_dir=ckpt)
    ok, wall, accounted = obs.goodput_reconciles(tol=0.05)
    assert ok, ("goodput books off: wall=%.4f accounted=%.4f"
                % (wall, accounted))
    assert wall > 0
    bad = obs.REGISTRY.get("badput_seconds_total")
    assert bad.labels("recompile").value > 0
    if ckpt is not None:
        assert bad.labels("checkpoint").value > 0
    ratio = _gauge("goodput_ratio")
    assert 0.0 < ratio < 1.0
    prod = obs.REGISTRY.get("goodput_productive_seconds_total").total()
    assert prod > 0
    # every emitted cause belongs to the documented list of causes
    with bad._lock:
        causes = {k[0] for k, c in bad._children.items() if c.value > 0}
    assert causes <= set(eff.BADPUT_CAUSES)
    rows = eff.goodput_table()
    assert rows[0][0] == "productive" and rows[-1][0] == "wall"
    assert "productive" in eff.format_goodput()


@pytest.mark.chaos
def test_seeded_chaos_books_kv_retry_and_failover_badput(monkeypatch):
    """Acceptance: a kvstore-backed fit under a seeded primary kill
    books the retry envelope as badput{cause=kv_retry} and the failover
    window as badput{cause=failover} — and the books still reconcile
    with the fit wall."""
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    monkeypatch.setenv("MXNET_TPU_KV_REPLICAS", "2")
    monkeypatch.delenv("MXNET_TPU_ASYNC_PS_ADDRS", raising=False)
    # the short RPC clocks every kvstore test runs under — without them
    # the killed primary eats the 120 s default MXNET_TPU_PS_DEADLINE
    # before the failover (and its badput rows) can happen
    monkeypatch.setenv("MXNET_TPU_PS_CALL_TIMEOUT", "2")
    monkeypatch.setenv("MXNET_TPU_PS_DEADLINE", "3")
    monkeypatch.setenv("MXNET_TPU_PS_DEAD_AFTER", "2")
    monkeypatch.setenv("MXNET_TPU_KV_REPL_SYNC", "1")
    ka.reset_membership()
    rs = np.random.RandomState(3)
    X = rs.randn(32, D).astype(np.float32)
    Y = rs.randint(0, 8, (32,)).astype(np.float32)
    kv = mx.kv.create("dist_async")
    assert kv._async is not None and len(kv._async_replicas) == 2
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1,
                                      rescale_grad=1.0 / B, wd=0.0))
    it = NDArrayIter({"data": X}, {"softmax_label": Y}, batch_size=B)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tr = ShardedTrainer(_mlp(), mesh, data_shapes={"data": (B, D)},
                        label_shapes={"softmax_label": (B,)},
                        rescale_grad=1.0 / B)
    inj = chaos.inject("kvstore.server_kill", "raise", seed=0,
                       match="s0:primary:push", limit=1)
    try:
        tr.fit(it, num_epoch=2, seed=5, log_every=0, kvstore=kv)
    finally:
        inj.remove()
    assert inj.fires == 1, "the seeded kill never fired"
    assert obs.REGISTRY.get("kv_failover_total").value == 1
    bad = obs.REGISTRY.get("badput_seconds_total")
    assert bad.labels("kv_retry").value > 0
    assert bad.labels("failover").value > 0
    assert obs.REGISTRY.get("kv_retry_seconds_total").total() > 0
    assert obs.REGISTRY.get("kv_failover_seconds_total").total() > 0
    ok, wall, accounted = obs.goodput_reconciles(tol=0.05)
    assert ok, ("chaos goodput books off: wall=%.4f accounted=%.4f"
                % (wall, accounted))


# ---------------------------------------------------------------------------
# MXNET_TPU_METRICS=0: every new record path is a constant-time guard
# ---------------------------------------------------------------------------

def test_metrics_disabled_is_constant_time(monkeypatch):
    calls = []
    monkeypatch.setattr(omet.Counter, "_record",
                        lambda self, v: calls.append("counter"))
    monkeypatch.setattr(omet.Gauge, "_record",
                        lambda self, v, op: calls.append("gauge"))
    monkeypatch.setattr(omet.Histogram, "_record",
                        lambda self, v: calls.append("histogram"))
    monkeypatch.setenv("MXNET_TPU_METRICS", "0")

    led = eff.ledger()
    assert led is eff._NULL_LEDGER
    led.step(1.0, {"data_wait": 0.5})
    led.bad("checkpoint", 1.0)
    assert led.close(2.0) is None
    eff.record_compile("step", lambda: 1 / 0)  # lower() never invoked
    eff.record_step_rate(4, 0.25)
    assert eff.model_flops_per_step() is None
    # a full fit through every instrumented seam records nothing
    _mk(K=2).fit(_data_iter(16), num_epoch=1, seed=0)
    assert calls == []


# ---------------------------------------------------------------------------
# /profile endpoint + worker-rank serving
# ---------------------------------------------------------------------------

def test_profile_endpoint_returns_mergeable_trace(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    obs.enable_tracing()
    with obs.span("eff.profile_test"):
        pass
    with obs.start_metrics_server(port=0) as srv:
        resp = urllib.request.urlopen(
            srv.url.replace("/metrics", "/profile?ms=10"), timeout=60)
        source = resp.headers.get("X-Profile-Source")
        body = json.loads(resp.read().decode("utf-8"))
    assert source in ("jax_profiler", "span_ring")
    assert isinstance(body.get("traceEvents"), list)
    merged = obs.merge_chrome_traces(
        [body, obs.export_chrome_trace(include_native=False)])
    assert merged["traceEvents"]


def test_capture_profile_falls_back_while_capture_in_flight(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    obs.enable_tracing()
    with obs.span("eff.inflight"):
        pass
    assert eff._PROFILE_LOCK.acquire(blocking=False)
    try:
        trace, source = eff.capture_profile(5)
    finally:
        eff._PROFILE_LOCK.release()
    assert source == "span_ring"
    assert any(e.get("name") == "eff.inflight"
               for e in trace["traceEvents"])


def test_worker_serves_metrics_alerts_and_profile(monkeypatch):
    from mxnet_tpu.parallel import collectives

    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    monkeypatch.setenv("MXNET_TPU_METRICS_PORT", "0")
    monkeypatch.setenv("MXNET_TPU_WATCHDOG", "1")
    collectives._WORKER_METRICS.update(server=None, watchdog=None)
    srv = collectives.serve_worker_metrics()
    try:
        assert srv is not None
        assert collectives.serve_worker_metrics() is srv  # idempotent
        text = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        assert "# HELP" in text
        alerts = json.loads(urllib.request.urlopen(
            srv.url.replace("/metrics", "/alerts"), timeout=10)
            .read().decode())
        assert isinstance(alerts["alerts"], list)
        assert alerts["rules"] == 21  # incl. efficiency, SLO burn, wire, quarantine + memory rules
        prof = json.loads(urllib.request.urlopen(
            srv.url.replace("/metrics", "/profile?ms=5"), timeout=60)
            .read().decode())
        assert isinstance(prof.get("traceEvents"), list)
    finally:
        if collectives._WORKER_METRICS["watchdog"] is not None:
            collectives._WORKER_METRICS["watchdog"].stop()
        srv.close()
        collectives._WORKER_METRICS.update(server=None, watchdog=None)


def test_worker_metrics_noop_without_port(monkeypatch):
    from mxnet_tpu.parallel import collectives

    monkeypatch.delenv("MXNET_TPU_METRICS_PORT", raising=False)
    collectives._WORKER_METRICS.update(server=None, watchdog=None)
    assert collectives.serve_worker_metrics() is None


# ---------------------------------------------------------------------------
# federation: cluster_mfu / cluster_mfu_min
# ---------------------------------------------------------------------------

def test_federation_derives_cluster_mfu(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    mk = ("# TYPE model_flops_utilization gauge\n"
          "model_flops_utilization %s\n")
    out = obs.federate([
        {"shard": 0, "role": "primary", "epoch": 1, "text": mk % "0.5"},
        {"shard": 1, "role": "primary", "epoch": 1, "text": mk % "0.3"},
        # a reset-but-never-measured gauge renders 0 — it must NOT drag
        # the cluster minimum to zero
        {"shard": 2, "role": "primary", "epoch": 1, "text": mk % "0"},
    ])
    assert 'cluster_mfu{member="0:primary:1"} 0.5' in out
    assert 'cluster_mfu{member="1:primary:1"} 0.3' in out
    assert 'member="2:primary:1"' not in out
    assert "cluster_mfu_min 0.3" in out


def test_federation_without_mfu_emits_no_mfu_rows(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    out = obs.federate([{"shard": 0, "role": "primary", "epoch": 0,
                         "text": "kv_failover_total 0\n"}])
    assert "cluster_mfu" not in out


# ---------------------------------------------------------------------------
# bench: schema-4 keys from cost analysis
# ---------------------------------------------------------------------------

def _run_bench(extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_STEPS="2", BENCH_BATCH="2", **extra_env)
    out = subprocess.run([sys.executable, os.path.join(_REPO, "bench.py")],
                         env=env, capture_output=True, text=True,
                         timeout=240, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    return json.loads(lines[-1])


def test_bench_emits_efficiency_keys():
    """schema_version 4: additive mfu / goodput_ratio /
    model_flops_per_step keys, derived from the compiled program's cost
    analysis (the CPU backend supports it, so the FLOPs are there; the
    CPU has no peak, so ``mfu`` is null).  The
    pipelined branch exercises the in-bench ledger's multi-step
    bookkeeping; the per-step branch goes through the same
    _efficiency_keys seam and is covered by test_bench_smoke."""
    rec = _run_bench({"BENCH_PIPELINE": "3"})
    assert rec["schema_version"] >= 4
    assert rec["model_flops_per_step"] > 0
    assert rec["mfu"] is None           # a CPU run has no utilization
    assert 0.0 < rec["goodput_ratio"] <= 1.0


# ---------------------------------------------------------------------------
# trend gate (tools/bench_table.py --trend / make bench-trend)
# ---------------------------------------------------------------------------

def _load_bench_table():
    spec = importlib.util.spec_from_file_location(
        "bench_table_under_test",
        os.path.join(_REPO, "tools", "bench_table.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_round(root, n, row):
    with open(os.path.join(str(root), "BENCH_r%02d.json" % n), "w") as f:
        json.dump({"n": n, "parsed": row}, f)


def test_trend_gate_passes_and_flags_regressions(tmp_path):
    bt = _load_bench_table()
    _write_round(tmp_path, 1, {"value": 100.0, "step_ms_p99": 10.0,
                               "git_sha": "aaa"})
    _write_round(tmp_path, 2, {"value": 102.0, "step_ms_p99": 9.5,
                               "mfu": 0.5, "git_sha": "bbb"})
    ok, lines = bt.trend_gate(bt.load_bench_rounds(root=str(tmp_path)))
    assert ok
    # mfu exists only in the newest round — reported, not gated
    assert any("new key" in l for l in lines if "mfu" in l)

    # a >10% throughput drop in the newest round fails the gate
    _write_round(tmp_path, 3, {"value": 80.0, "step_ms_p99": 9.0,
                               "git_sha": "ccc"})
    ok, lines = bt.trend_gate(bt.load_bench_rounds(root=str(tmp_path)))
    assert not ok
    assert any("REGRESSED" in l and "value" in l for l in lines)

    # latency regressions gate in the OTHER direction
    _write_round(tmp_path, 3, {"value": 103.0, "step_ms_p99": 20.0,
                               "git_sha": "ccc"})
    ok, lines = bt.trend_gate(bt.load_bench_rounds(root=str(tmp_path)))
    assert not ok
    assert any("REGRESSED" in l and "step_ms_p99" in l for l in lines)


def test_trend_gate_covers_wire_keys_down_is_good(tmp_path):
    """The schema-11 wire keys gate in the down-is-good direction:
    bytes/step or codec-share creeping UP past tolerance fails the
    gate (the whole point of the measured binary-wire baseline)."""
    bt = _load_bench_table()
    for key in ("kv_bytes_per_step", "kv_header_overhead_pct",
                "kv_codec_ms_share", "kv_rpcs_per_flush_p50"):
        assert bt.TREND_KEYS[key] is False
    _write_round(tmp_path, 1, {"value": 100.0,
                               "kv_bytes_per_step": 1000.0,
                               "kv_codec_ms_share": 0.10,
                               "git_sha": "aaa"})
    _write_round(tmp_path, 2, {"value": 100.0,
                               "kv_bytes_per_step": 2000.0,
                               "kv_codec_ms_share": 0.10,
                               "git_sha": "bbb"})
    ok, lines = bt.trend_gate(bt.load_bench_rounds(root=str(tmp_path)))
    assert not ok
    assert any("REGRESSED" in l and "kv_bytes_per_step" in l
               for l in lines)
    # shrinking the wire is an improvement, never a regression
    _write_round(tmp_path, 2, {"value": 100.0,
                               "kv_bytes_per_step": 500.0,
                               "kv_codec_ms_share": 0.05,
                               "git_sha": "bbb"})
    ok, lines = bt.trend_gate(bt.load_bench_rounds(root=str(tmp_path)))
    assert ok, "\n".join(lines)


def test_trend_gate_dedupes_rounds_by_git_sha(tmp_path):
    bt = _load_bench_table()
    # r1+r2 are the same commit re-measured: best value stands, so the
    # r3 comparison baseline is 105, and zero-value (failed-run)
    # captures never become baselines at all
    _write_round(tmp_path, 1, {"value": 105.0, "git_sha": "aaa"})
    _write_round(tmp_path, 2, {"value": 95.0, "git_sha": "aaa"})
    _write_round(tmp_path, 3, {"value": 0.0, "git_sha": "bbb"})
    _write_round(tmp_path, 4, {"value": 104.0, "git_sha": "ccc"})
    rounds = bt.load_bench_rounds(root=str(tmp_path))
    assert [n for n, _ in rounds] == [1, 4]
    ok, lines = bt.trend_gate(rounds)
    assert ok
    assert any("105" in l for l in lines)


def test_trend_gate_on_real_history():
    """The checked-in BENCH_r*.json history must pass its own gate —
    `make bench-trend` is only useful if the repo's actual rounds keep
    it green."""
    bt = _load_bench_table()
    ok, lines = bt.trend_gate()
    assert ok, "\n".join(lines)


# ---------------------------------------------------------------------------
# make efficiency script contract
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_make_efficiency_script_contract():
    """tools/efficiency_report.py (the ``make efficiency`` target) must
    run a fit, print both tables, and exit 0 with the books balanced."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TPU_METRICS="1")
    out = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "tools", "efficiency_report.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "HLO cost accounting" in out.stdout
    assert "goodput ledger:" in out.stdout
    assert "drift" in out.stdout
