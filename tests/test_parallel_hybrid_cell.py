"""``falcon-h1-34b-pp12`` and its cell ``falcon-h1-serve-answers32``: the
configuration against the catalog's row, the cell against the issue, the
benchmark's cost arithmetic and readers at this model's shapes, and the
cell rehearsed through the benchmark's own command at a tiny size on
the CPU.  Split from ``test_parallel_hybrid.py`` (the family) by kind,
so that neither holds a tier-1 worker long."""

import copy
import json
import os
import shutil

import numpy as np
import pytest

import jax.numpy as jnp

from test_gated_delta_moe import _prefill
from test_parallel_hybrid import (CONFIG, FAMILY, REFERENCE, ROOT, TINY,
                                  _tokens, make_params)

CELL = "falcon-h1-serve-answers32"
_NEW_METRICS = ("ssm_share.falcon", "ssm_decode_roofline.falcon",
                "ssm_prefill_tokens_per_step.falcon",
                "gqa_paged_decode_roofline.falcon",
                "parallel_mixer_share.falcon", "head_share.falcon")


def _published():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    from benchmark.spec import load_module

    return load_module(FAMILY, "family_parallel_hybrid_cell")


# ----------------------------------------------------------------------
# the configuration and the cell


def test_configuration_keeps_every_published_number_but_the_depth():
    """Every value of the catalog's row is in the file under its key,
    but for the one key ``reduced`` names: every width, head count and
    the vocabulary are as published."""
    cfg = _published()
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"] == list(cfg["reduced_why"])
    assert cfg["num_hidden_layers"] in (5, 6)
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    assert cfg["deployment"]["pipeline_stages"] * 6 == 72
    # what the cost readers read the mixer's sizes by is what the
    # published keys say
    assert (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"]) == (
        cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
        cfg["mamba_n_groups"], cfg["mamba_d_conv"])
    assert set(cfg["hybrid_override_pattern"]) == {"M"}


def test_the_cell_is_the_issues():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-pp12", "serve-answers-closed32-10k", 1)
    entry = next(c for c in doc["configs"]
                 if c["name"] == "falcon-h1-34b-pp12")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == _published()["source"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["kind"], traffic["clients"], traffic["requests"],
            traffic["stagger_s"]) == ("serve-closed", 32, 192, 0.125)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.8, "min": 256,
        "max": 8192}
    assert traffic["new_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.6, "min": 64,
        "max": 2048}
    assert traffic["max_total_tokens"] == 10240 == _published()["n_positions"]
    assert traffic["shared_prefix_tokens"] == 0
    assert traffic["decode_buckets"] == [32]
    assert traffic["prefill_buckets"] == [512, 1024, 1536, 3072, 4096, 6144,
                                          8192]
    assert traffic["traced_seconds"] == 8
    serve = _published()["deployment"]["serve"]
    assert serve["state_slots"] == 32 and serve["block_size"] == 16
    assert serve["checked_logit_parts"] == 32 and 261120 % 32 == 0
    reports = {m["name"] for m in doc["end_to_end"] + doc["per_layer"]
               if CELL in m.get("workloads", [])}
    assert "serve_tokens_per_s" in reports and "ttft_p50_ms" not in reports
    assert set(_NEW_METRICS) | {"state_gb_per_step", "decode_copy_ms",
                                "d2h_mb_per_step", "peak_hbm_gb.serve",
                                "paged_decode_attn_share.serve"} <= reports
    assert "moe_grouped_extra_runs_per_layer" not in reports
    for m in doc["per_layer"]:
        if m["name"] in _NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"


def test_the_mix_holds_what_the_pools_are_sized_for():
    """The 192 dealt pairs: a mean request of about 2,700 + 600 tokens,
    none over the context limit; 32 of them at a time reserve 109k
    tokens on average, and the pools hold 9,344 blocks of 16."""
    from benchmark.spec import load_module

    driver = load_module(os.path.join(ROOT, "benchmark", "drivers",
                                      "serve-closed.py"), "driver_falcon")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-answers-closed32-10k.json")) as f:
        traffic = json.load(f)
    requests = driver.request_set(traffic, 1000, 1)
    prompts = [len(r["prompt"]) for r in requests]
    news = [r["max_new_tokens"] for r in requests]
    assert 2600 < np.mean(prompts) < 2800 and 580 < np.mean(news) < 640
    assert min(prompts) == 256 and max(prompts) == 8192
    assert max(p + n for p, n in zip(prompts, news)) <= 10240
    blocks = [-(-(p + n) // 16) for p, n in zip(prompts, news)]
    serve = _published()["deployment"]["serve"]
    # every round of 32 (one request a caller) fits with room to spare
    for r in range(6):
        assert sum(blocks[32 * r:32 * (r + 1)]) < 0.9 * serve["num_blocks"]
    assert serve["num_blocks"] * 16 * 12288 == pytest.approx(1.84e9, rel=0.01)


# ----------------------------------------------------------------------
# the benchmark's arithmetic and readers at this model's shapes


def test_cost_arithmetic():
    from benchmark import flops, gated_delta_costs, ssm_costs

    cfg = _published()
    assert ssm_costs.state_layers(cfg) == cfg["num_hidden_layers"] == 6
    assert ssm_costs.state_values(cfg) == 32 * 128 * 256 == 1048576
    assert ssm_costs.tail_values(cfg) == 3 * 5120
    assert ssm_costs.state_bytes(cfg) == 6 * (4194304 + 30720)
    # a full decode step of one layer: 32 states read and written
    ops, moved = ssm_costs.ssm_decode_cost(cfg, 32)
    assert (ops, moved) == (5 * 32 * 1048576, 2 * 4 * 32 * 1048576)
    peaks = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
    least, by = flops.roofline_seconds(ops, moved, peaks)
    assert by == "memory" and abs(least - 0.3277e-3) < 0.001e-3
    # 32 rows at 3,100 cached tokens each, one layer: 2 KB a token
    ops, moved = gated_delta_costs.gqa_decode_cost(
        cfg, context_tokens=99200, rows=32)
    assert ops == 2 * 99200 * 20 * 2 * 128
    assert moved == (99200 * 2 * 512 + 32 * 20 * 2 * 128) * 2
    least, by = flops.roofline_seconds(ops, moved, peaks)
    assert by == "memory" and abs(least - 0.2482e-3) < 0.001e-3


def _trace(events):
    end = max(at + dur for _, at, dur in events)
    return {"window_ns": [0, end], "devices": {"0": events}, "host": []}


def test_readers_of_the_new_metrics(capsys):
    """On the names a traced run of the cell recorded on the chip (PR
    48): the shares count what their patterns
    name (the mixers' share both branches' kernels, the head's share the
    operations over the 261,120 columns and no other product), the
    rooflines come out under 100% and say which peak bounds them, and
    every reader returns nothing where there is nothing to read (a
    program without the counters or the operations, a run without a
    trace)."""
    from benchmark.spec import Spec

    spec = Spec(ROOT)
    peaks = spec.peaks("TPU v5 lite")

    def read(metric, ctx):
        doc = spec.metric_file(metric)
        return spec.reader(doc["reader"])(ctx, doc.get("params", {}))

    with open(os.path.join(ROOT, "benchmark", "data",
                           "falcon_trace_names.json")) as f:
        recorded = [(e["name"], e["ns"]) for e in json.load(f)["events"]]
    events, at = [], 0
    for name, dur in recorded:
        events.append([name, at, dur])
        at += dur + 1000
    names = [e[0] for e in events]
    steps = sum(n.startswith("%ssm_decode") for n in names) / 6.0
    assert steps == 1
    counters = {
        "generation_state_bytes_total": steps * 2 * 32 * 6 * 4225024.0,
        "generation_decode_steps_total": steps,
        "generation_decode_context_tokens_total": steps * 32 * 3100.0,
        "generation_tokens_total": steps * 32.0,
        "ssm_prefill_tokens_total": 6 * 2700.0}
    ctx = {"trace": _trace(events), "peaks": peaks,
           "compiles_in_window": counters}
    got = {m: read(m, ctx) for m in _NEW_METRICS}
    out = capsys.readouterr().out
    assert "ssm decode roofline: bound by memory" in out
    assert "gqa decode roofline: bound by memory" in out
    assert got["ssm_prefill_tokens_per_step.falcon"] \
        == pytest.approx(2700.0 / steps)
    window = float(at - 1000)     # the last operation's end

    def share(*prefixes):
        return 100.0 * sum(d for n, _, d in events
                           if n.startswith(prefixes)) / window

    assert got["ssm_share.falcon"] == pytest.approx(
        share("%ssm_decode", "%ssm_prefill"))
    assert got["parallel_mixer_share.falcon"] == pytest.approx(share(
        "%ssm_decode", "%ssm_prefill", "%paged_decode_gqa_attention",
        "%gqa_prefill_attention"))
    # the head: the decode step's product and the greedy choice over
    # it, a prefill's one row; not the feed-forward's fusions
    head = [n for n in names if "261120]" in n.split(" fusion(")[0]
            or "fusion(f32[32,261120]" in n]
    assert len(head) == 3
    assert got["head_share.falcon"] == pytest.approx(
        100.0 * sum(d for n, _, d in events if n in head) / window)
    for name in ("ssm_decode_roofline.falcon",
                 "gqa_paged_decode_roofline.falcon"):
        assert 0 < got[name] <= 100, (name, got[name])
    # the parent's program: no such counter, no such operation
    bare = {"trace": _trace([["%fusion.1 = f32[8,8] fusion(%p)", 0, 50]]),
            "peaks": peaks, "compiles_in_window": {
                "generation_decode_steps_total": 100.0}}
    for name in _NEW_METRICS:
        assert read(name, bare) is None, name
        assert read(name, {"peaks": peaks}) is None, name


def test_the_family_draws_each_kind_and_hands_the_driver_a_part(family):
    """The benchmark's family: a weight's kind is its name without its
    layer (the key of its deviation), ``in_weight`` a deviation a part;
    where the configuration gives ``checked_logit_parts`` the backend
    hands its caller, of every decode row, the part of the vocabulary
    its position names."""
    kinds = {name: family.weight_kind(name) for name in (
        "embed_weight", "pred_weight", "final_norm_gamma", "l0_norm_gamma",
        "l11_in_weight", "l3_dt_weight", "l2_conv_bias", "l0_A_log",
        "l0_dt_bias", "l5_D", "l4_ssm_norm_gamma", "l1_k_weight")}
    assert kinds == {
        "embed_weight": "embed_weight", "pred_weight": "pred_weight",
        "final_norm_gamma": "one", "l0_norm_gamma": "one",
        "l11_in_weight": "in_weight", "l3_dt_weight": "dt_weight",
        "l2_conv_bias": "conv_bias", "l0_A_log": "decay", "l0_dt_bias": "dt",
        "l5_D": "one", "l4_ssm_norm_gamma": "one", "l1_k_weight": "k_weight"}
    cfg = _published()
    assert family.in_part_rows(cfg) == (4096, 4096, 512, 512)
    assert family.deviation(cfg, "in_weight") == tuple(
        cfg["draw"]["deviation"]["in_weight." + part] for part in "zxBC")
    # the parts of in_weight are drawn each at its own deviation
    params = make_params(family, seed=2)
    rows = np.asarray(params["l0_in_weight"])
    want = family.deviation(TINY, "in_weight")
    for (lo, hi), deviation in zip(((0, 32), (32, 64), (64, 80), (80, 96)),
                                   want):
        assert rows[lo:hi].std() == pytest.approx(deviation, rel=0.15)
    tiny = copy.deepcopy(TINY)
    tiny["deployment"]["serve"]["checked_logit_parts"] = 4
    be = family.build_backend(tiny, tiny["deployment"]["serve"], params,
                              "ph_kept", lambda base: base)
    be.cache.allocate("s", 8)
    _prefill(be, "s", _tokens(4), 8)
    table = be.cache.block_table("s", be.max_blocks_per_seq)[None]
    out = be.decode([3], [5], table, [6])[0]
    assert out.values.shape == (1, 16) and out[0].part == slice(16, 32)


# ----------------------------------------------------------------------
# the new cell rehearsed through the benchmark's own command, at the
# tiny size on the CPU


@pytest.fixture(scope="module")
def tiny_benchmark(tmp_path_factory):
    """The real BENCHMARK.json cut to the new cell, its configuration
    the tiny one (the real reference beside it), its traffic a few
    short requests."""
    from benchmark.spec import Spec

    root = tmp_path_factory.mktemp("tiny_benchmark")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(str(root), sub))
    tiny = copy.deepcopy(TINY)
    tiny["deployment"]["serve"]["state_slots"] = 4
    # the driver is handed a part of each decode row, as in the cell
    tiny["deployment"]["serve"]["checked_logit_parts"] = 4
    with open(os.path.join(str(root), "configs", "tiny-falcon.json"),
              "w") as f:
        json.dump(tiny, f)
    shutil.copy(REFERENCE, os.path.join(str(root), "configs",
                                        "tiny-falcon.reference.py"))
    shutil.copy(os.path.join(ROOT, "benchmark", "tests", "tiny",
                             "peaks.json"), str(root))
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-answers-closed32-10k.json")) as f:
        traffic = json.load(f)
    traffic.update(
        clients=4, requests=8, stagger_s=0.01, max_total_tokens=64,
        prompt_tokens=dict(traffic["prompt_tokens"], median=12, min=4,
                           max=30),
        new_tokens=dict(traffic["new_tokens"], median=6, min=3, max=10),
        prefill_buckets=[16, 32], decode_buckets=[4], traced_seconds=0.3,
        checked_requests=3, request_timeout_s=60)
    with open(os.path.join(str(root), "traffic", "serve-tiny-10k.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(str(root), "limits",
                           "tiny-falcon-serve.json"), "w") as f:
        json.dump({"served_token_logit_gap": 1e-3,
                   "served_logit_abs_err": 1e-3}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"], doc["run_seconds"] = ["."], 1
    doc["configs"] = [dict(c, name="tiny-falcon", source="test only",
                           file="configs/tiny-falcon.json")
                      for c in doc["configs"]
                      if c["name"] == "falcon-h1-34b-pp12"]
    doc["workloads"] = [dict(w, name="tiny-falcon-serve",
                             config="tiny-falcon", traffic="serve-tiny-10k")
                        for w in doc["workloads"] if w["name"] == CELL]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-falcon-serve"] \
                if CELL in m["workloads"] else []
    return Spec(str(root), doc=doc)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearsed_on_the_cpu(tiny_benchmark, trace, capsys):
    """A seed past 32 signed bits, as the driver's are."""
    from benchmark import run

    result = run.run_cell(tiny_benchmark, "tiny-falcon-serve",
                          3000000048 + trace, 1.5, trace,
                          require_chip=False)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    # a request sent as the 1.5 s window closes may be answered 503 by
    # the closing front end (the driver's race of PERF.md section 7)
    assert result["failed"] == 0 or "replica is draining" in out, out
    assert result["attempted"] > 0, out
    assert "served_logit_abs_err" in out and " ok" in out
    metrics = result["metrics"]
    if trace:
        assert metrics["compiles_in_window"]["value"] == 0
        assert metrics["staged_gb_per_step"]["value"] == 0
        assert metrics["kv_occupancy_peak"]["value"] > 0
        assert 4 < metrics["decode_context_tokens_mean"]["value"] < 64
        # what a step reads and writes of state: at most 4 rows of 3
        # layers of 448 float32 values, each way
        per_row = 3 * 448 * 4 * 2
        assert 0 < metrics["state_gb_per_step"]["value"] \
            <= 4 * per_row / 1e9
        assert metrics["ssm_prefill_tokens_per_step.falcon"]["value"] >= 0
        assert metrics["d2h_mb_per_step"]["value"] > 0
        # no device trace on a CPU: nothing read, nothing raised
        for name in ("ssm_share.falcon", "ssm_decode_roofline.falcon",
                     "gqa_paged_decode_roofline.falcon",
                     "parallel_mixer_share.falcon", "head_share.falcon",
                     "device_idle_share.serve"):
            assert name not in metrics
    else:
        assert metrics["serve_tokens_per_s"]["value"] > 0
        assert "ttft_p50_ms" not in metrics
        assert metrics["setup_s"]["value"] > 0
    json.dumps(result)


def test_large_logits_go_to_the_host_in_blocks():
    """The reference's head: logits that fit the device are one
    product; over ``DEVICE_LOGITS_BYTES`` they are made ``HEAD_BLOCK``
    rows at a time (each block put in the host's memory as it is made):
    the same numbers."""
    from benchmark.spec import load_module

    reference = load_module(REFERENCE, "reference_falcon_head")
    rng = np.random.RandomState(0)
    params = {"pred_weight": jnp.asarray(rng.randn(64, 32), jnp.float32)}
    h = jnp.asarray(rng.randn(24, 32), jnp.float32)
    whole = reference.head(TINY, params, h)
    assert whole.shape == (24, 64)
    reference.DEVICE_LOGITS_BYTES, reference.HEAD_BLOCK = 1024, 8
    try:
        blocks = reference.head(TINY, params, h)
    except Exception as exc:  # noqa: BLE001 — a backend without host memory
        pytest.skip("this backend places nothing in the host's memory: %s"
                    % exc)
    np.testing.assert_allclose(blocks, whole, rtol=1e-6, atol=1e-6)
