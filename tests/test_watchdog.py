"""Performance watchdog plane: step-time attribution (falsifiable
against the wall-clock step histogram), jit-compile and memory
accounting, federation-side straggler detection, and the declarative
SLO alert engine — plus the satellites (launcher trace tracks,
``make watchdog`` script contract).

Everything runs in-process on the CPU backend: thread-backed kvstore
servers for the straggler path (same strategy as
test_distributed_observability.py), seeded chaos for the slow shard,
and injectable clocks for the burn-rate/sustain windows.
"""

import importlib.util
import io
import json
import os
import types
import urllib.request

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import mxnet_tpu as mx
from mxnet_tpu import chaos
from mxnet_tpu import observability as obs
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.kvstore_async import AsyncClient, AsyncServer
from mxnet_tpu.observability import attribution
from mxnet_tpu.observability import federation
from mxnet_tpu.observability import flight_recorder
from mxnet_tpu.observability import metrics as omet
from mxnet_tpu.observability import watchdog as wmod
from mxnet_tpu.parallel.trainer import ShardedTrainer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _mk(K=1, **kw):
    kw.setdefault("momentum", 0.9)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    return ShardedTrainer(_mlp(), mesh, data_shapes={"data": (8, 6)},
                          label_shapes={"softmax_label": (8,)},
                          wd=1e-4, rescale_grad=1.0 / 8,
                          pipeline_steps=K, **kw)


def _data_iter(rows=64, seed=3):
    rs = np.random.RandomState(seed)
    return NDArrayIter(rs.randn(rows, 6).astype(np.float32),
                       rs.randint(0, 8, (rows,)).astype(np.float32),
                       batch_size=8)


def _phase_sum():
    fam = obs.REGISTRY.get("trainer_step_phase_seconds")
    return sum(c.sum for c in fam._children.values())


def _wall():
    return obs.REGISTRY.get("trainer_step_seconds")._default


# ---------------------------------------------------------------------------
# step-time attribution: the books must balance (tentpole acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [1, 2])
def test_attribution_reconciles_with_wall_clock(K):
    """Phases + the 'unattributed' residual must sum to the
    trainer_step_seconds sum within 5% — the falsifiability contract
    that catches a phase timer silently losing coverage."""
    _mk(K=K).fit(_data_iter(80), num_epoch=1, seed=0)
    wall = _wall()
    assert wall.count == 10
    covered = _phase_sum()
    assert wall.sum > 0
    assert abs(covered - wall.sum) <= 0.05 * wall.sum, (
        "attribution books off: phases+residual=%.4f wall=%.4f"
        % (covered, wall.sum))


def test_attribution_phases_recorded_per_path():
    _mk(K=2).fit(_data_iter(), num_epoch=1, seed=0)
    fam = obs.REGISTRY.get("trainer_step_phase_seconds")
    # pipelined path: feeder wait, dispatch, readback + residual —
    # placement happens feeder-side (prefetch_place_seconds_total)
    for phase in ("data_wait", "compute", "flush", "unattributed"):
        assert fam.labels(phase).count > 0, phase
    assert obs.REGISTRY.get("prefetch_place_seconds_total").value > 0


def test_attribution_table_and_format():
    _mk(K=1).fit(_data_iter(16), num_epoch=1, seed=0)
    rows = obs.attribution_table()
    assert rows[-1][0] == "wall" and rows[-1][1] == 2
    phases = {r[0] for r in rows}
    assert "compute" in phases
    # shares are fractions of the wall sum
    for _, _, _, share in rows:
        assert share is None or 0.0 <= share <= 1.0 + 1e-9
    text = obs.format_attribution()
    assert "compute" in text and "wall" in text


def test_attributor_is_shared_null_when_disabled(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_METRICS", "0")
    att = attribution.attributor()
    assert att is attribution._NULL
    with att.phase("compute"):
        pass
    att.close(1.0)          # records nothing, raises nothing
    monkeypatch.setenv("MXNET_TPU_METRICS", "1")
    assert attribution.attributor() is not attribution._NULL


# ---------------------------------------------------------------------------
# compile accounting: steady state records NOTHING
# ---------------------------------------------------------------------------

def test_recompile_accounting_warmup_then_steady_state():
    tr = _mk(K=2)
    tr.fit(_data_iter(), num_epoch=1, seed=0)
    compiles = obs.REGISTRY.get("trainer_compiles_total")
    assert compiles.labels("pipe:2:2").value == 1
    assert int(compiles.total()) == 1
    # steady state: a second fit reuses every trace — zero new compiles
    tr.fit(_data_iter(seed=5), num_epoch=1, seed=1)
    assert int(compiles.total()) == 1
    # the compile paid its wall time into the histogram exactly once
    hist = obs.REGISTRY.get("trainer_compile_seconds")
    assert hist.labels("pipe:2:2").count == 1


def test_recompile_accounting_depth_change_adds_exactly_one():
    tr = _mk(K=2)
    tr.fit(_data_iter(), num_epoch=1, seed=0)
    compiles = obs.REGISTRY.get("trainer_compiles_total")
    assert int(compiles.total()) == 1
    tr.pipeline_steps = 4          # mid-session depth change
    tr.fit(_data_iter(seed=5), num_epoch=1, seed=1)
    assert compiles.labels("pipe:4:4").value == 1
    assert int(compiles.total()) == 2


def test_recompile_accounting_per_step_path():
    tr = _mk(K=1)
    tr.fit(_data_iter(16), num_epoch=1, seed=0)
    compiles = obs.REGISTRY.get("trainer_compiles_total")
    assert compiles.labels("step").value == 1
    tr.fit(_data_iter(16, seed=5), num_epoch=1, seed=1)
    assert int(compiles.total()) == 1


# ---------------------------------------------------------------------------
# memory accounting
# ---------------------------------------------------------------------------

def test_memory_sampled_at_flush_boundaries():
    _mk(K=2).fit(_data_iter(), num_epoch=1, seed=0)
    live = obs.REGISTRY.get("memory_live_buffer_bytes")
    assert live.labels("all").value > 0
    wm = obs.REGISTRY.get("memory_live_buffer_watermark_bytes")
    assert wm.value >= live.labels("all").value


def test_sample_memory_on_demand():
    x = jax.numpy.ones((128,), jax.numpy.float32)  # noqa: F841 (held live)
    obs.sample_memory()
    assert obs.REGISTRY.get(
        "memory_live_buffer_bytes").labels("all").value >= 128 * 4


# ---------------------------------------------------------------------------
# rule engine units (injectable clock)
# ---------------------------------------------------------------------------

def test_rule_threshold_fires_and_resolves():
    g = omet.gauge("wd_probe_lag", "probe", ["follower"])
    g.labels("f0").set(100.0)
    wd = obs.Watchdog([obs.Rule("lag", "wd_probe_lag", stat="max",
                                threshold=64.0)])
    (alert,) = wd.evaluate(now=0.0)
    assert alert.name == "lag" and alert.value == 100.0
    assert obs.REGISTRY.get("cluster_alert").labels(
        "lag", "warning").value == 1
    g.labels("f0").set(3.0)
    assert wd.evaluate(now=1.0) == []
    assert obs.REGISTRY.get("cluster_alert").labels(
        "lag", "warning").value == 0


def test_rule_fires_exactly_once_per_episode():
    g = omet.gauge("wd_probe_edge", "probe")
    g.set(10.0)
    wd = obs.Watchdog([obs.Rule("edge", "wd_probe_edge", threshold=5.0)])
    for now in (0.0, 1.0, 2.0):      # stays red: one rising edge
        assert len(wd.evaluate(now=now)) == 1
    fired = obs.REGISTRY.get("cluster_alerts_fired_total")
    assert fired.labels("edge").value == 1
    g.set(0.0)
    wd.evaluate(now=3.0)
    g.set(10.0)
    wd.evaluate(now=4.0)             # second episode: second edge
    assert fired.labels("edge").value == 2


def test_rule_for_s_sustain_window():
    g = omet.gauge("wd_probe_sustain", "probe")
    g.set(10.0)
    wd = obs.Watchdog([obs.Rule("s", "wd_probe_sustain", threshold=5.0,
                                for_s=10.0)])
    assert wd.evaluate(now=0.0) == []        # true but not sustained yet
    assert wd.evaluate(now=5.0) == []
    assert len(wd.evaluate(now=11.0)) == 1   # sustained past for_s


def test_rule_increase_burn_rate_window():
    state = {"v": 0.0}

    def src():
        return ("# TYPE wd_probe_drops_total counter\n"
                "wd_probe_drops_total %s\n" % state["v"])

    wd = obs.Watchdog([obs.Rule("drops", "wd_probe_drops_total",
                                kind="increase", threshold=0.0,
                                window_s=60.0)], source=src)
    assert wd.evaluate(now=0.0) == []        # flat
    state["v"] = 5.0
    (alert,) = wd.evaluate(now=1.0)          # rose within the window
    assert alert.value == 5.0
    # window slides past the rise: flat again, resolves
    assert wd.evaluate(now=120.0) == []


def test_rule_regression_vs_rolling_baseline():
    state = {"v": 1.0}

    def src():
        return ("# TYPE wd_probe_step gauge\n"
                "wd_probe_step %s\n" % state["v"])

    wd = obs.Watchdog([obs.Rule("reg", "wd_probe_step", kind="regression",
                                factor=2.0, min_samples=3,
                                window_s=600.0)], source=src)
    for now in (0.0, 1.0, 2.0):              # build the baseline
        assert wd.evaluate(now=now) == []
    state["v"] = 10.0
    (alert,) = wd.evaluate(now=3.0)
    assert alert.value == 10.0
    assert alert.threshold == pytest.approx(2.0)   # factor x baseline(1.0)


def test_rule_absent_metric_resolves():
    wd = obs.Watchdog([obs.Rule("ghost", "wd_probe_never_registered",
                                threshold=0.0)])
    assert wd.evaluate(now=0.0) == []


def test_rule_selector_and_histogram_stats():
    h = omet.histogram("wd_probe_lat_seconds", "probe", ["kind"])
    for _ in range(90):
        h.labels("shard").observe(0.001)
    for _ in range(10):
        h.labels("shard").observe(9.0)
    h.labels("other").observe(50.0)
    wd = obs.Watchdog([
        obs.Rule("p99", "wd_probe_lat_seconds", stat="p99",
                 selector={"kind": "shard"}, threshold=1.0),
        obs.Rule("cnt", "wd_probe_lat_seconds", stat="count",
                 selector={"kind": "shard"}, threshold=1000.0),
    ])
    alerts = {a.name: a for a in wd.evaluate(now=0.0)}
    assert "p99" in alerts           # bucket ub holding the tail obs
    assert alerts["p99"].value == 10.0   # 9.0s obs land in the le=10 bucket
    assert "cnt" not in alerts       # 100 observations < 1000


def test_rule_validation():
    with pytest.raises(ValueError):
        obs.Rule("x", "m", kind="nope")
    with pytest.raises(ValueError):
        obs.Rule("x", "m", severity="nope")
    with pytest.raises(ValueError):
        obs.Rule("x", "m", op="!=")


def test_default_rules_clean_registry_fires_nothing():
    wd = obs.Watchdog(obs.default_rules())
    assert wd.evaluate(now=0.0) == []
    assert wd.evaluate(now=1.0) == []
    names = [r.name for r in wd.rules]
    assert names == ["spans_dropped", "heartbeat_stale",
                     "replication_lag", "step_p99_regression",
                     "straggler", "mfu_regression",
                     "snapshot_quarantine", "goodput_floor",
                     "stream_stall",
                     "request_p99_slo", "inter_token_p99",
                     "queue_saturation", "quota_shed_surge",
                     "wire_bytes_regression", "wire_codec_share",
                     "oom_proximity", "kv_cache_pressure",
                     "slo_availability_fast_burn",
                     "slo_availability_slow_burn",
                     "slo_latency_fast_burn", "slo_latency_slow_burn"]


# ---------------------------------------------------------------------------
# /alerts endpoint
# ---------------------------------------------------------------------------

def test_alerts_endpoint_serves_firing_json():
    g = omet.gauge("wd_probe_http", "probe")
    g.set(10.0)
    wd = obs.Watchdog([obs.Rule("http_rule", "wd_probe_http",
                                threshold=5.0, severity="critical")])
    with wd.serve(port=0) as srv:
        body = urllib.request.urlopen(
            srv.url.replace("/metrics", "/alerts"), timeout=5).read()
        payload = json.loads(body)
        assert payload["firing"] == 1 and payload["rules"] == 1
        (alert,) = payload["alerts"]
        assert alert["name"] == "http_rule"
        assert alert["severity"] == "critical"
        # /metrics still serves on the same endpoint
        text = urllib.request.urlopen(srv.url, timeout=5).read().decode()
        assert "cluster_alert" in text


def test_alerts_endpoint_404_without_watchdog():
    with obs.start_metrics_server(port=0) as srv:
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                srv.url.replace("/metrics", "/alerts"), timeout=5)


# ---------------------------------------------------------------------------
# straggler detection over the federated plane (tentpole acceptance:
# seeded slow shard -> skew row names it -> terminal alert fires once ->
# exactly one flight bundle)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_straggler_chaos_fires_terminal_alert_once(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    s0 = AsyncServer(secret="t", server_id=0).start()
    s1 = AsyncServer(secret="t", server_id=1).start()
    try:
        c0 = AsyncClient(s0.address, rank=0, heartbeat=False, secret="t")
        c1 = AsyncClient(s1.address, rank=0, heartbeat=False, secret="t")
        c0.init([("w", np.zeros(4, np.float32))])
        c1.init([("w", np.zeros(4, np.float32))])
        # seeded slow shard: every pull served by s0 sleeps 50ms inside
        # dispatch; s1 stays fast
        with chaos.inject("kvstore.server_kill", "delay", prob=1.0,
                          seed=0, delay=0.05, match="s0:primary:pull"):
            for _ in range(4):
                c0.pull(["w"])
                c1.pull(["w"])
        c0.close()
        c1.close()
    finally:
        s0.stop()
        s1.stop()

    # both servers share this process's registry: dedup scrapes it once,
    # the kv_serve_seconds 'server' label still splits the shards
    fed = obs.FederatedCollector([
        {"shard": 0, "role": "primary", "epoch": 0,
         "registry": obs.REGISTRY},
        {"shard": 1, "role": "primary", "epoch": 0,
         "registry": obs.REGISTRY},
    ])
    text = fed.render()
    assert 'cluster_shard_serve_seconds{server="0"}' in text
    assert 'cluster_shard_serve_seconds{server="1"}' in text
    assert 'cluster_straggler_skew{kind="shard"}' in text
    # the skew row NAMES the injected shard
    assert 'cluster_straggler_info{kind="shard",member="0"} 1' in text
    assert 'member="1"' not in text

    wd = obs.Watchdog([obs.Rule("straggler", "cluster_straggler_skew",
                                stat="max", threshold=2.0,
                                severity="terminal")], source=fed)
    assert len(wd.evaluate()) == 1
    assert len(wd.evaluate()) == 1          # stays red, no second edge
    assert obs.REGISTRY.get("cluster_alerts_fired_total").labels(
        "straggler").value == 1
    assert obs.REGISTRY.get("cluster_alert").labels(
        "straggler", "terminal").value == 1
    # terminal severity routed exactly ONE postmortem bundle
    bundles = [d for d in os.listdir(str(tmp_path))
               if d.startswith("flight_watchdog.straggler")]
    assert len(bundles) == 1
    with open(os.path.join(str(tmp_path), bundles[0],
                           "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["kind"] == "watchdog.straggler"
    assert "straggler" in manifest["extra"]["alert"]


def test_no_straggler_rows_when_shards_are_even(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_WATCHDOG_STRAGGLER_SKEW", "1e9")
    s0 = AsyncServer(secret="t", server_id=0).start()
    s1 = AsyncServer(secret="t", server_id=1).start()
    try:
        c0 = AsyncClient(s0.address, rank=0, heartbeat=False, secret="t")
        c1 = AsyncClient(s1.address, rank=0, heartbeat=False, secret="t")
        c0.init([("w", np.zeros(4, np.float32))])
        c1.init([("w", np.zeros(4, np.float32))])
        c0.close()
        c1.close()
    finally:
        s0.stop()
        s1.stop()
    text = obs.federate([
        {"shard": 0, "role": "primary", "epoch": 0,
         "registry": obs.REGISTRY},
    ])
    # skew still rendered (it's a health series), info row is gated
    assert 'cluster_straggler_skew{kind="shard"}' in text
    assert "cluster_straggler_info" not in text


# ---------------------------------------------------------------------------
# disabled plane: constant-time guards end to end
# ---------------------------------------------------------------------------

def test_disabled_plane_records_nothing(monkeypatch):
    calls = []
    monkeypatch.setattr(omet.Counter, "_record",
                        lambda self, v: calls.append("counter"))
    monkeypatch.setattr(omet.Gauge, "_record",
                        lambda self, v, op: calls.append("gauge"))
    monkeypatch.setattr(omet.Histogram, "_record",
                        lambda self, v: calls.append("histogram"))
    scrapes = []
    monkeypatch.setattr(federation, "_scrape_one",
                        lambda t, timeout: scrapes.append(t) or "")
    bundles = []
    monkeypatch.setattr(flight_recorder, "_write_bundle",
                        lambda k, e, x: bundles.append(k) or "/dev/null")
    monkeypatch.setenv("MXNET_TPU_METRICS", "0")
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", "/tmp/never")

    _mk(K=2).fit(_data_iter(16), num_epoch=1, seed=0)
    obs.sample_memory()
    wd = obs.Watchdog([obs.Rule("straggler", "cluster_straggler_skew",
                                severity="terminal", threshold=0.0)])
    assert wd.evaluate() == []
    assert obs.federate([{"shard": 0, "role": "primary", "epoch": 0,
                          "url": "http://127.0.0.1:1/metrics"}]) == ""
    assert calls == []
    assert scrapes == []
    assert bundles == []


# ---------------------------------------------------------------------------
# satellites: launcher trace tracks, make-watchdog script contract
# ---------------------------------------------------------------------------

def _load_launch():
    spec = importlib.util.spec_from_file_location(
        "launch_under_test", os.path.join(_REPO, "tools", "launch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_launcher_assigns_server_trace_tracks(monkeypatch):
    launch = _load_launch()
    monkeypatch.delenv("MXNET_TPU_TRACE_TRACK", raising=False)
    envs = []

    class _FakeProc:
        def __init__(self, argv, env=None, **kw):
            envs.append(env)
            with open(env["MXNET_TPU_SERVER_ADDR_FILE"], "w") as f:
                f.write("127.0.0.1:9%03d" % len(envs))

        def poll(self):
            return None

        def kill(self):
            pass

    monkeypatch.setattr(launch.subprocess, "Popen", _FakeProc)
    args = types.SimpleNamespace(num_servers=2, num_replicas=2,
                                 metrics_port_base=0)
    _, worker_env = launch.launch_servers(args)
    tracks = [e["MXNET_TPU_TRACE_TRACK"] for e in envs]
    # primaries spawn first (shard order), then the standbys
    assert tracks == ["server0:primary", "server1:primary",
                      "server0:standby", "server1:standby"]
    assert "MXNET_TPU_ASYNC_PS_ADDRS" in worker_env


def test_launcher_assigns_worker_trace_tracks(monkeypatch):
    launch = _load_launch()
    monkeypatch.delenv("MXNET_TPU_TRACE_TRACK", raising=False)
    envs = []

    class _FakeProc:
        returncode = 0

        def __init__(self, argv, env=None, **kw):
            envs.append(env)
            self.stdout = io.BytesIO(b"")
            self.stderr = io.BytesIO(b"")

        def wait(self):
            return 0

    monkeypatch.setattr(launch.subprocess, "Popen", _FakeProc)
    args = types.SimpleNamespace(num_workers=2, num_servers=0,
                                 platform="cpu", metrics_port_base=0,
                                 tag_output=False)
    assert launch.launch_local(args, ["true"]) == 0
    assert [e["MXNET_TPU_TRACE_TRACK"] for e in envs] == ["worker0",
                                                          "worker1"]


def test_launcher_respects_operator_track_override(monkeypatch):
    launch = _load_launch()
    monkeypatch.setenv("MXNET_TPU_TRACE_TRACK", "mine")
    envs = []

    class _FakeProc:
        returncode = 0

        def __init__(self, argv, env=None, **kw):
            envs.append(env)
            self.stdout = io.BytesIO(b"")
            self.stderr = io.BytesIO(b"")

        def wait(self):
            return 0

    monkeypatch.setattr(launch.subprocess, "Popen", _FakeProc)
    args = types.SimpleNamespace(num_workers=1, num_servers=0,
                                 platform="cpu", metrics_port_base=0,
                                 tag_output=False)
    launch.launch_local(args, ["true"])
    assert envs[0]["MXNET_TPU_TRACE_TRACK"] == "mine"


@pytest.mark.slow
def test_make_watchdog_script_contract():
    """tools/watchdog_fit.py (the ``make watchdog`` target) must run a
    fit, print the attribution table, and exit 0 with the books
    balanced."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TPU_METRICS="1")
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "watchdog_fit.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "step-time attribution:" in out.stdout
    assert "compiles accounted:" in out.stdout
