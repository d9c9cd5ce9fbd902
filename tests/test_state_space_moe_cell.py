"""``nemotron3-super-ep4`` and its cell
``nemotron3-super-serve-docs64``: the configuration against the
catalog's row, the parameters counted, the state's bytes, the
benchmark's cost arithmetic and readers for what the model adds, the
controls of the limits, and the cell rehearsed through the benchmark's
own command at a tiny size on the CPU.  Split from
``test_state_space_moe.py`` (the family and its operator) by kind, so
that neither holds a tier-1 worker long."""

import copy
import json
import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.models import state_space_moe as sm
from mxnet_tpu.parallel import moe

from test_gated_delta_moe import _prefill
from test_state_space_moe import (BIAS, CONFIG, REFERENCE, ROOT, SCALE, TINY,
                                  _tokens, program_config)

CELL = "nemotron3-super-serve-docs64"


@pytest.fixture(scope="module")
def reference():
    from benchmark.spec import load_module

    return load_module(REFERENCE, "reference_nemotron_cell")


@pytest.fixture(scope="module")
def model():
    cfg = program_config(TINY)
    return cfg, sm.init_params(cfg, 0, jnp.float32, SCALE, BIAS)


def _published():
    with open(CONFIG) as f:
        return json.load(f)


def _family():
    from benchmark.spec import load_module

    return load_module(os.path.join(ROOT, "benchmark", "models",
                                    "state_space_moe.py"), "family_ssm_t")


# ----------------------------------------------------------------------
# the configuration and its counts


def test_configuration_keeps_the_published_widths():
    """Every value of the catalog's row is in the file under its key,
    but for the four keys ``reduced`` names; no width is among them."""
    cfg = _published()
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "num_nextn_predict_layers"]
    assert sorted(cfg["reduced_why"]) == sorted(cfg["reduced"])
    assert cfg["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    program = _family().program_config(cfg)
    assert program["layer_kinds"].count("M") == 5
    assert program["layer_kinds"].count("E") == 5
    assert program["held"] == (0, 128) and program["num_experts"] == 512
    assert program["seq_len"] == 17408


def test_the_cell_is_the_issues():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron3-super-ep4", "serve-docs-closed64-17k", 1)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["clients"], traffic["requests"],
            traffic["stagger_s"]) == (64, 384, 0.125)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.8, "min": 512,
        "max": 16384}
    assert traffic["new_tokens"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.6, "min": 48,
        "max": 1024}
    assert traffic["max_total_tokens"] == 17408
    assert traffic["decode_buckets"] == [64]
    assert traffic["prefill_buckets"][0] == 1024 \
        and traffic["prefill_buckets"][-1] == 16384
    serve = _published()["deployment"]["serve"]
    assert serve["state_slots"] == 64
    assert 32768 % serve["checked_logit_parts"] == 0
    reports = {m["name"] for m in doc["end_to_end"] + doc["per_layer"]
               if CELL in m.get("workloads", [])}
    assert "serve_tokens_per_s" in reports and "ttft_p50_ms" not in reports
    assert {"ssm_share.nemotron", "ssm_decode_roofline.nemotron",
            "moe_expert_roofline.nemotron", "state_gb_per_step",
            "gqa_paged_decode_roofline.nemotron",
            "moe_grouped_extra_runs_per_layer"} <= reports


def test_parameter_count_of_the_cut_and_of_the_published_model():
    """ISSUE 45's arithmetic: 109.6M a Mamba-2 layer, 35.65M an
    attention layer, 54.5M an expert layer outside its routed experts of
    5.505M each, 134.2M each of embedding and head: 4,648M parameters
    here, 9.30 GB in bfloat16; and 120.7B for the 88 layers, 512 experts
    and 131,072 rows as published.  The benchmark's cost function of the
    expert counts what the family holds."""
    from benchmark import relu2_expert_costs

    family = _family()
    cfg = _published()
    count = {k: int(np.prod(s))
             for k, s in family.weight_shapes(cfg).items()}

    def layer(i, *parts):
        return sum(v for k, v in count.items() if k.startswith("l%d_" % i)
                   and k[len("l%d_" % i):].startswith(parts))

    mamba = layer(0, "")
    assert mamba == 18560 * 4096 + 8192 * 4096 + 10240 * 5 + 3 * 128 \
        + 8192 + 4096
    assert abs(mamba - 109.6e6) < 0.05e6
    assert layer(7, "") == 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    assert abs(layer(7, "") - 35.65e6) < 0.01e6
    outside = layer(1, "router_", "latent_", "shared_", "norm_")
    assert abs(outside - 54.5e6) < 0.05e6
    assert layer(1, "experts_") == 128 * 2 * 1024 * 2688
    assert layer(1, "experts_") == 128 * relu2_expert_costs.expert_parameters(
        cfg)
    assert relu2_expert_costs.expert_weight_bytes(cfg) == 11010048
    assert count["embed_weight"] == count["pred_weight"] == 32768 * 4096
    total = sum(count.values())
    assert abs(total - 4648e6) < 1e6 and abs(2 * total - 9.30e9) < 0.01e9
    whole = dict(cfg, **cfg["published"])
    whole["deployment"] = dict(cfg["deployment"], experts={
        "published": 512, "held": 512, "first": 0})
    published = sum(int(np.prod(s))
                    for s in family.weight_shapes(whole).values())
    assert abs(published - 120.7e9) < 0.05e9


def test_a_token_is_1_kb_and_a_sequences_state_21_mb():
    cfg = _family().program_config(_published())
    definition = sm.lm_definition(cfg)
    assert definition.cache_layers == 1 and definition.state.layers == 5
    assert definition.state.rows == (
        ((8, 128, 1024), np.dtype(np.float32)),
        ((60, 512), np.dtype(jnp.bfloat16)))
    assert definition.state.bytes == 5 * (4194304 + 61440) == 21278720
    row = definition.cache_row
    assert (row.kind, row.width, row.pools) == ("kv", 256, 2)
    assert row.bytes == 1024
    # 64 slots in two versions: the pool a decode step reads and writes
    assert 64 * 2 * definition.state.bytes == 2723676160


def test_what_the_rules_of_the_expert_layer_choose_at_these_shapes():
    """A 64-row decode step routes 352 held pairs, 2.75 an expert, and
    is expected to reach 94% of the held: every row through every held
    expert.  A prefill keeps the held experts' rows twice over up to the
    74,880 rows of the widest array a run holds (the hidden rows, 2688
    wide: 384 MB), so the 16,384 bucket's 90,112 expected rows are two
    runs; the tiles are whole contractions of 1024 and 2688."""
    assert moe.few_rows_hit_most(64, 22, 512)
    assert not moe.few_rows_hit_most(1024, 22, 512)
    reached = 1.0 - (1.0 - 22 / 512.0) ** 64
    assert abs(reached - 0.94) < 0.005
    row = 2688 * 2
    kept = {t: moe.grouped_kept_rows(t * 22, 128, 512, row)
            for t in (1024, 6144, 8192, 16384)}
    assert kept == {1024: 11264, 6144: 67584, 8192: 74880, 16384: 74880}
    assert 74880 * row <= moe.GROUPED_ROW_BYTES < (74880 + 128) * row
    assert moe.grouped_tiling(74880, 1024, 2688) == (128, 1024, 2688)
    assert moe.grouped_tiling(74880, 2688, 1024) == (128, 2688, 1024)


def test_seeded_routing_spreads_over_the_experts():
    """The configuration's ``assumed`` weights (normal(0, 0.02) router
    over unit-RMS rows, selection bias normal(0, 0.01)): over 4,096
    seeded rows choosing 22 of 512 an expert takes 0.50-1.56 times its
    even share of 176, and the 128 held get a quarter of the pairs
    within 3%."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    h = jax.random.normal(keys[0], (4096, 4096), jnp.float32)
    router = 0.02 * jax.random.normal(keys[1], (512, 4096), jnp.float32)
    bias = 0.01 * jax.random.normal(keys[2], (512,), jnp.float32)
    chosen, gates = moe.route_group_limited(
        h @ router.T, bias, top_k=22, scale=5.0)
    load = np.bincount(np.asarray(chosen).reshape(-1), minlength=512)
    share = load / (4096 * 22 / 512.0)
    assert share.min() > 0.4 and share.max() < 1.7, (share.min(),
                                                     share.max())
    assert abs(load[:128].sum() / load.sum() - 0.25) < 0.0075
    np.testing.assert_allclose(gates.sum(1), 5.0, rtol=1e-5)


# ----------------------------------------------------------------------
# the benchmark's arithmetic and readers for what this model adds


def test_cost_arithmetic():
    from benchmark import flops, gated_delta_costs, relu2_expert_costs, \
        ssm_costs

    cfg = _published()
    assert ssm_costs.state_layers(cfg) == 5
    assert ssm_costs.state_values(cfg) == 128 * 64 * 128
    assert ssm_costs.tail_values(cfg) == 3 * 10240
    assert ssm_costs.state_bytes(cfg) == 21278720
    # a full decode step of one layer: 64 states read and written
    ops, moved = ssm_costs.ssm_decode_cost(cfg, 64)
    assert (ops, moved) == (5 * 64 * 1048576, 2 * 4 * 64 * 1048576)
    peaks = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
    least, by = flops.roofline_seconds(ops, moved, peaks)
    assert by == "memory" and abs(least - 0.6555e-3) < 0.001e-3
    # every held expert hit, 352 pairs
    ops, moved = relu2_expert_costs.routed_experts_cost(cfg, 128, 352)
    assert ops == 352 * 2 * 2 * 1024 * 2688
    assert moved == 128 * 11010048 + 352 * (2 * 1024 + 2 * 2688) * 2
    least, by = flops.roofline_seconds(ops, moved, peaks)
    assert by == "memory" and abs(least - 1.727e-3) < 0.005e-3
    # 64 rows at 5,000 cached tokens each, the one layer: 1 KB a token
    ops, moved = gated_delta_costs.gqa_decode_cost(
        cfg, context_tokens=320000, rows=64)
    assert ops == 2 * 320000 * 32 * 2 * 128
    assert moved == (320000 * 2 * 256 + 64 * 32 * 2 * 128) * 2


_NEW_METRICS = ("ssm_share.nemotron", "ssm_decode_roofline.nemotron",
                "ssm_prefill_tokens_per_step.nemotron",
                "moe_expert_share.nemotron", "moe_expert_roofline.nemotron",
                "moe_tokens_per_held_expert.nemotron",
                "moe_held_experts_hit_share.nemotron",
                "gqa_paged_decode_roofline.nemotron")


def _trace(events):
    end = max(at + dur for _, at, dur in events)
    return {"window_ns": [0, end], "devices": {"0": events}, "host": []}


def _recorded_events():
    """(name, nanoseconds) of the operations the metrics tell apart, as
    a traced run of the cell names them (recorded on the chip, PR
    45)."""
    with open(os.path.join(ROOT, "benchmark", "data",
                           "nemotron_trace_names.json")) as f:
        return [(e["name"], e["ns"]) for e in json.load(f)["events"]]


def test_readers_of_the_new_metrics(capsys):
    """On the recorded names: the shares count what their patterns
    name, the rooflines come out under 100% and say which peak bounds
    them, and every reader returns nothing where there is nothing to
    read (a program without the counters, a run without a trace)."""
    from benchmark.spec import Spec

    spec = Spec(ROOT)
    peaks = spec.peaks("TPU v5 lite")

    def read(metric, ctx):
        doc = spec.metric_file(metric)
        return spec.reader(doc["reader"])(ctx, doc.get("params", {}))

    events, at = [], 0
    for name, dur in _recorded_events():
        events.append([name, at, dur])
        at += dur + 1000
    names = [e[0] for e in events]
    steps = sum(n.startswith("%ssm_decode") for n in names) / 5.0
    assert steps >= 1
    counters = {"generation_state_bytes_total": steps * 2 * 64 * 21278720.0,
                "generation_decode_steps_total": steps,
                "generation_decode_context_tokens_total":
                    steps * 64 * 5000.0,
                "generation_tokens_total": steps * 64.0,
                "ssm_prefill_tokens_total": 5 * 5400.0,
                "moe_layer_steps_total": steps * 5 + 5,
                "moe_local_experts_hit_total": (steps * 5 + 5) * 124.0,
                "moe_local_assignments_total":
                    steps * 5 * 352.0 + 5 * 5400 * 5.5,
                "moe_grouped_extra_runs_total": 0.0}
    ctx = {"trace": _trace(events), "peaks": peaks,
           "compiles_in_window": counters}
    got = {m: read(m, ctx) for m in _NEW_METRICS}
    out = capsys.readouterr().out
    assert "expert roofline: bound by" in out
    assert "ssm decode roofline: bound by memory" in out
    assert "gqa decode roofline: bound by memory" in out
    assert got["moe_held_experts_hit_share.nemotron"] \
        == pytest.approx(100 * 124 / 128.0)
    assert got["ssm_prefill_tokens_per_step.nemotron"] \
        == pytest.approx(5400.0 / steps)
    for name in ("ssm_share.nemotron", "moe_expert_share.nemotron"):
        assert 0 < got[name] < 100, (name, got[name])
    for name in ("ssm_decode_roofline.nemotron",
                 "gqa_paged_decode_roofline.nemotron",
                 "moe_expert_roofline.nemotron"):
        assert 0 < got[name] <= 100, (name, got[name])
    # the parent's program: no such counter, no such operation
    bare = {"trace": _trace([["%fusion.1 = f32[8,8] fusion(%p)", 0, 50]]),
            "peaks": peaks, "compiles_in_window": {
                "generation_decode_steps_total": 100.0}}
    for name in _NEW_METRICS:
        assert read(name, bare) is None, name
        assert read(name, {"peaks": peaks}) is None, name


def test_the_scan_kernel_has_a_share_of_its_own():
    """``ssm_prefill_scan_share.nemotron`` (PR 47) reads the kernel by
    its scope's name, as a traced run of the cell names it; the shape
    patterns of ``ssm_share.nemotron`` do not hold it (its outputs are a
    tuple), and a program whose scan is XLA's body reads nothing."""
    from benchmark.spec import Spec

    spec = Spec(ROOT)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "ssm_prefill_scan_share.nemotron"]
    assert entry == [{
        "name": "ssm_prefill_scan_share.nemotron", "unit": "%",
        "better": "lower", "source": "device_trace",
        "layer": "state-space scan", "moves": "serve_tokens_per_s",
        "workloads": [CELL]}]

    def read(metric, events):
        doc = spec.metric_file(metric)
        return spec.reader(doc["reader"])({"trace": _trace(events)},
                                          doc.get("params", {}))

    kernel = ("%ssm_prefill.12 = (bf16[4096,8192]{1,0:T(8,128)(2,1)}, "
              "f32[8,128,1024]{2,1,0:T(8,128)}) custom-call(%bitcast.875, "
              "%multiply_convert_fusion.9)")
    events = [[kernel, 0, 700], ["%fusion.1 = f32[8,8] fusion(%p)", 700, 300]]
    assert read("ssm_prefill_scan_share.nemotron", events) \
        == pytest.approx(70.0)
    assert read("ssm_share.nemotron", events) is None
    assert read("ssm_prefill_scan_share.nemotron", events[1:]) is None


# ----------------------------------------------------------------------
# the controls of the limits


def test_reference_one_precision_down_is_not_the_reference(reference):
    """The control of the cell's limits: the reference with every
    operand rounded to float8 (the recurrence's with its state too)
    moves the logits by far more than bfloat16 does."""
    cfg = program_config(TINY)
    params = sm.init_params(cfg, 4, jnp.bfloat16, SCALE, BIAS)
    toks = _tokens(16, seed=4)[None]
    def run(mode):
        return np.asarray(jax.jit(lambda p, t: reference.logits(
            TINY, p, t, mode))(params, toks))

    exact = run("float32")
    err = {mode: float(np.median(np.abs(run(mode) - exact)))
           for mode in ("bfloat16", "float8")}
    assert err["float8"] > 3 * err["bfloat16"] > 0, err
    with pytest.raises(ValueError, match="unknown mode"):
        reference.logits(TINY, params, toks, "float16")


def test_reference_with_a_lost_state_moves_only_what_follows(reference,
                                                             model):
    """The other control: the state zeroed before token 9 leaves the
    logits of the tokens before it as they are and moves those after."""
    toks = _tokens(20, seed=6)[None]
    sound = np.asarray(jax.jit(lambda p, t: reference.logits(TINY, p, t))(
        model[1], toks))
    lost = np.asarray(jax.jit(lambda p, t: reference.logits(
        TINY, p, t, "float32", 9))(model[1], toks))
    np.testing.assert_array_equal(lost[0, :9], sound[0, :9])
    assert np.abs(lost[0, 9:] - sound[0, 9:]).max() > 0.1


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
    tiny["deployment"]["serve"]["checked_logit_parts"] = 5
    with open(os.path.join(str(root), "configs", "tiny-nemotron.json"),
              "w") as f:
        json.dump(tiny, f)
    shutil.copy(REFERENCE, os.path.join(str(root), "configs",
                                        "tiny-nemotron.reference.py"))
    shutil.copy(os.path.join(ROOT, "benchmark", "tests", "tiny",
                             "peaks.json"), str(root))
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-docs-closed64-17k.json")) as f:
        traffic = json.load(f)
    traffic.update(
        clients=4, requests=8, stagger_s=0.01, max_total_tokens=64,
        prompt_tokens=dict(traffic["prompt_tokens"], median=12, min=4,
                           max=30),
        new_tokens=dict(traffic["new_tokens"], median=6, min=3, max=10),
        prefill_buckets=[16, 32], decode_buckets=[4], traced_seconds=0.3,
        checked_requests=3, request_timeout_s=60)
    with open(os.path.join(str(root), "traffic", "serve-tiny-17k.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(str(root), "limits",
                           "tiny-nemotron-serve.json"), "w") as f:
        json.dump({"served_token_logit_gap": 1e-3,
                   "served_logit_abs_err": 1e-3}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"], doc["run_seconds"] = ["."], 1
    doc["configs"] = [dict(c, name="tiny-nemotron", source="test only",
                           file="configs/tiny-nemotron.json")
                      for c in doc["configs"]
                      if c["name"] == "nemotron3-super-ep4"]
    doc["workloads"] = [dict(w, name="tiny-nemotron-serve",
                             config="tiny-nemotron",
                             traffic="serve-tiny-17k")
                        for w in doc["workloads"] if w["name"] == CELL]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-nemotron-serve"] \
                if CELL in m["workloads"] else []
    return Spec(str(root), doc=doc)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearsed_on_the_cpu(tiny_benchmark, trace, capsys):
    from benchmark import run

    result = run.run_cell(tiny_benchmark, "tiny-nemotron-serve",
                          3000000045 + trace, 1.5, trace,
                          require_chip=False)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0, out
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
        assert metrics["ssm_prefill_tokens_per_step.nemotron"]["value"] >= 0
        assert 0 < metrics["moe_tokens_per_held_expert.nemotron"]["value"]
        assert 0 < metrics["moe_held_experts_hit_share.nemotron"]["value"]
        assert metrics["moe_grouped_extra_runs_per_layer"]["value"] >= 0
        # no device trace on a CPU: nothing read, nothing raised
        for name in ("ssm_share.nemotron", "ssm_decode_roofline.nemotron",
                     "gqa_paged_decode_roofline.nemotron",
                     "moe_expert_roofline.nemotron",
                     "moe_expert_share.nemotron", "device_idle_share.serve"):
            assert name not in metrics
    else:
        assert metrics["serve_tokens_per_s"]["value"] > 0
        assert "ttft_p50_ms" not in metrics
        assert metrics["setup_s"]["value"] > 0
    json.dumps(result)


def test_the_family_hands_the_driver_a_part_of_each_row(model):
    """Where the configuration gives ``checked_logit_parts`` the backend
    the family builds hands its caller, of every decode row, the part of
    the vocabulary its position names; the draws are the siblings', by
    their kinds."""
    family = _family()
    tiny = copy.deepcopy(TINY)
    tiny["deployment"]["serve"]["checked_logit_parts"] = 5
    be = family.build_backend(tiny, tiny["deployment"]["serve"], model[1],
                              "ssm_kept", lambda base: base)
    be.cache.allocate("s", 8)
    _prefill(be, "s", _tokens(4), 8)
    table = be.cache.block_table("s", be.max_blocks_per_seq)[None]
    out = be.decode([3], [4], table, [5])[0]
    assert out.values.shape == (1, 10) and out[0].part == slice(40, 50)
    kinds = {name: family.weight_kind(name) for name in (
        "l0_A_log", "l0_dt_bias", "l0_D", "l0_ssm_norm_gamma",
        "l1_router_bias", "l0_conv_bias", "l0_in_weight")}
    assert kinds == {"l0_A_log": "decay", "l0_dt_bias": "dt", "l0_D": "one",
                     "l0_ssm_norm_gamma": "one", "l1_router_bias": "bias",
                     "l0_conv_bias": "normal", "l0_in_weight": "normal"}
    weights = family.make_weights(tiny, 5)
    assert weights["l0_A_log"].dtype == weights["l1_router_bias"].dtype \
        == jnp.float32
    decay = np.exp(-np.exp(np.asarray(weights["l0_A_log"])) * 0.7)
    assert 0.55 < decay.min() and decay.max() < 1.0
    assert float(weights["l0_D"].min()) == 1.0


def test_serve_tool_loads_the_family_by_configuration(tmp_path):
    """``tools/serve.py --lm name=config.json``: the configuration file
    names its family, the family's module builds the backend with both
    kinds of cache."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_tool", os.path.join(ROOT, "tools", "serve.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    backend = tool.lm_backend("tiny_tool_ssm", "%s:7" % path)
    assert isinstance(backend, serving.LMBackend)
    assert backend.cache.row.kind == "kv" and backend.cache.num_slots == 8
    assert backend.cfg["held"] == (0, 8) and backend.cfg["seq_len"] == 64
    logits, k, v, _, state = backend.prefill(np.zeros(8, np.int32), 3)
    assert logits.shape == (50,) and k.shape == v.shape == (1, 8, 16)
    assert [s.shape for s in state] == [(3, 2, 8, 16), (3, 3, 64)]
