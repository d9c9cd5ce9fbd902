"""bench.py CPU smoke: the benchmark must keep its one-line JSON
contract (driver-parsed) in both per-step and BENCH_PIPELINE modes.
Tiny shapes + BENCH_STEPS=2 keep each subprocess a few seconds."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_STEPS="2", BENCH_BATCH="2", **extra_env)
    out = subprocess.run([sys.executable, os.path.join(_REPO, "bench.py")],
                         env=env, capture_output=True, text=True,
                         timeout=240, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert lines, out.stdout
    return json.loads(lines[-1])


@pytest.mark.parametrize("pipeline", [1, 4])
def test_bench_json_contract(pipeline):
    rec = _run_bench({"BENCH_PIPELINE": str(pipeline)})
    assert rec["metric"] == "resnet8_cpu_smoke_throughput"
    assert rec["unit"] == "img/s"
    assert rec["value"] > 0
    assert rec["vs_baseline"] > 0
    # additive observability keys (same contract, new fields)
    assert rec["step_ms_p50"] > 0
    assert rec["step_ms_p99"] >= rec["step_ms_p50"]
    assert rec["tokens_per_sec"] > 0
    # additive observability counters: a clean bench fires no chaos and
    # drops no spans, but the keys must always be present
    assert rec["chaos_fired_total"] == 0
    assert rec["spans_dropped_total"] == 0
    # additive provenance keys: schema revision + the commit measured,
    # and the device the row was measured on
    assert rec["schema_version"] >= 3
    assert isinstance(rec["git_sha"], str) and rec["git_sha"]
    assert rec["platform"] == "cpu" and rec["device_kind"]
    assert rec["device_count"] >= 1
    # pipeline_steps only appears when the pipelined path actually ran
    if pipeline > 1:
        assert rec["pipeline_steps"] == pipeline
    else:
        assert "pipeline_steps" not in rec


def test_bench_failure_is_a_failure():
    """No guard: a run that fails exits non-zero and prints no row —
    neither an error row under exit 0 nor a stored number."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_STEPS="1",
               BENCH_BATCH="2", BENCH_LAYOUT="no-such-layout")
    out = subprocess.run([sys.executable, os.path.join(_REPO, "bench.py")],
                         env=env, capture_output=True, text=True,
                         timeout=240, cwd=_REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no-such-layout" in out.stderr


def test_bench_serving_keys():
    """BENCH_SERVING=1: the schema-5 serving keys, the >= 2x continuous-
    batching acceptance floor over the batch-1 sequential baseline, and
    the zero-recompiles-after-warmup steady-state contract."""
    rec = _run_bench({"BENCH_SERVING": "1", "BENCH_REQUESTS": "128"})
    assert rec["schema_version"] >= 5
    assert rec["metric"] == "serving_cpu_smoke_throughput"
    assert rec["unit"] == "req/s"
    assert rec["requests_per_sec"] > 0
    assert rec["request_ms_p99"] >= rec["request_ms_p50"] > 0
    assert 0.0 < rec["batch_occupancy"] <= 1.0
    assert rec["recompiles_after_warmup"] == 0
    assert rec["requests_per_sec"] >= 2.0 * rec["requests_per_sec_sequential"], (
        "continuous batching lost its edge: %.1f vs sequential %.1f req/s"
        % (rec["requests_per_sec"], rec["requests_per_sec_sequential"]))


def test_bench_generate_keys():
    """BENCH_GENERATE=1: the schema-10 generation keys and the >= 2x
    acceptance floor over the naive re-prefill-per-token baseline, taken
    on the token-positions each path pushes through the model for a
    generated token.  The wall-clock ratio of the two (``speedup_vs_
    naive``) is a printed number only: on a shared CPU at smoke sizes it
    read 1.24-2.26x over three runs of one tree."""
    rec = _run_bench({"BENCH_GENERATE": "1", "BENCH_GEN_TOKENS": "16",
                      "BENCH_GEN_USERS": "4"})
    assert rec["schema_version"] >= 10
    assert rec["metric"] == "generation_cpu_smoke_throughput"
    assert rec["unit"] == "tokens/s"
    assert rec["tokens_per_sec"] > 0
    assert rec["tokens_per_sec_per_user"] > 0
    assert rec["tokens_per_sec_naive"] > 0
    assert rec["speedup_vs_naive"] > 0
    assert rec["inter_token_ms_p99"] > 0
    assert rec["prefill_ms_p50"] > 0
    assert 0.0 < rec["kv_cache_occupancy"] <= 1.0
    assert rec["recompiles_after_warmup"] == 0
    assert rec["positions_per_token"] >= 1.0
    assert rec["positions_per_token_naive"] \
        >= 2.0 * rec["positions_per_token"], (
        "the paged-cache decode lane lost its edge: %.2f positions a "
        "token against the naive path's %.2f (%.2fx by the clock)"
        % (rec["positions_per_token"], rec["positions_per_token_naive"],
           rec["speedup_vs_naive"]))


def test_bench_wire_keys():
    """BENCH_WIRE=1: the schema-11 wire keys are present and >0 on the
    CPU smoke, the schema-13 additions (compression ratio, coalesced
    RPC savings) are live under the lane's default PR-17 stack, and
    the byte books reconcile with the socket truth (the lane's
    falsifiability gate rides in the JSON row)."""
    rec = _run_bench({"BENCH_WIRE": "1"})
    assert rec["schema_version"] >= 13
    assert rec["metric"] == "kv_wire_bytes_per_step"
    assert rec["unit"] == "B/step"
    assert rec["kv_bytes_per_step"] > 0
    assert rec["kv_header_overhead_pct"] > 0
    assert rec["kv_codec_ms_share"] > 0
    assert rec["kv_rpcs_per_flush_p50"] > 0
    # the lane defaults to int8 push compression + coalescing, so both
    # schema-13 keys must show real wins, not placeholders
    assert rec["kv_compress_ratio"] > 1.0
    assert rec["kv_coalesce_rpcs_saved"] > 0
    assert rec["wire_reconciles"] is True
    assert rec["codec_reconciles"] is True


def test_bench_snapshot_keys():
    """BENCH_SNAPSHOT=1: the schema-14 durability keys — save, frozen
    window, and cold-restore-onto-3-shards latencies — all live on the
    CPU smoke, with the re-stripe round-trip asserted inside the lane."""
    rec = _run_bench({"BENCH_SNAPSHOT": "1", "BENCH_SNAPSHOT_KEYS": "8",
                      "BENCH_SNAPSHOT_PUSHES": "64"})
    assert rec["schema_version"] >= 14
    assert rec["metric"] == "snapshot_save"
    assert rec["unit"] == "ms"
    assert rec["snapshot_save_ms"] > 0
    assert rec["snapshot_restore_ms"] > 0
    # the frozen window is the delta cut only — it must be a fraction
    # of the full save, or the two-phase design has regressed into a
    # stop-the-world snapshot
    assert 0 < rec["snapshot_frozen_ms"] < rec["snapshot_save_ms"]
    assert rec["snapshot_restripe_ok"] is True


def test_bench_kernels_keys():
    """BENCH_KERNELS=1: the schema-15 fused-kernel keys.  Parity is the
    gate (the lane exits nonzero without it, so returncode==0 already
    proves the quick grid is green); the optimizer pair must show the
    fused tree's measured CPU win over the eager per-param dispatch —
    the one kernel claim this lane is allowed to make off-TPU."""
    rec = _run_bench({"BENCH_KERNELS": "1", "BENCH_KERNEL_REPS": "5"})
    assert rec["schema_version"] >= 15
    assert rec["metric"] == "kernels_parity"
    assert rec["unit"] == "ok"
    assert rec["fused_parity_ok"] is True
    assert rec["fused_parity_cases"] > 0
    assert rec["attn_prefill_ms"] > 0
    assert rec["paged_decode_tokens_per_sec"] > 0
    assert rec["fused_opt_step_ms"] > 0
    assert rec["stock_opt_step_ms"] > 0
    # the measured CPU claim: one jitted fused tree step beats O(n)
    # eager per-param updates
    assert rec["fused_opt_step_ms"] < rec["stock_opt_step_ms"]
    # per-variant compile-FLOPs rows (attention variants gate on these,
    # not on CPU wall time)
    assert isinstance(rec["variant_compile_flops"], dict)


def test_bench_fairness_keys():
    """BENCH_FAIRNESS=1: the schema-12 multi-tenant keys — isolation
    ratio, quota shed rate, KV-affinity hit ratio — all live and
    bounded on the CPU smoke."""
    rec = _run_bench({"BENCH_FAIRNESS": "1", "BENCH_FAIR_REQUESTS": "32"})
    assert rec["schema_version"] >= 12
    assert rec["metric"] == "fairness_cpu_smoke_throughput"
    assert rec["unit"] == "req/s"
    assert rec["value"] > 0
    assert rec["fairness_p99_ratio"] > 0
    assert 0.0 <= rec["quota_shed_rate"] <= 1.0
    assert rec["kv_affinity_hit_ratio"] > 0


def test_bench_git_sha_override():
    rec = _run_bench({"BENCH_GIT_SHA": "cafef00d"})
    assert rec["git_sha"] == "cafef00d"


def test_bench_vs_baseline_published():
    """Fresh bench number vs the BASELINE.json published reference for
    the SAME metric.  The tolerance is deliberately generous (8x): this
    guards against the bench silently measuring nothing (zeros, wrong
    units, dead path), not against hardware variance between
    containers."""
    with open(os.path.join(_REPO, "BASELINE.json")) as f:
        published = json.load(f).get("published", {})
    rec = _run_bench({})
    ref = published.get(rec["metric"])
    if not ref:
        pytest.skip("no published baseline for metric %r" % rec["metric"])
    assert rec["value"] >= float(ref["value"]) / 8.0, (
        "bench %s=%.2f collapsed vs published %.2f"
        % (rec["metric"], rec["value"], ref["value"]))
    assert rec["unit"] == ref["unit"]
