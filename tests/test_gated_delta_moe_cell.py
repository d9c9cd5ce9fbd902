"""The cell of the hybrid decoder of Gated DeltaNet layers over sparse
experts (``models/gated_delta_moe.py``): that the benchmark's comparison
sees a lost or rounded state, the configuration and its counts, the
benchmark's arithmetic and readers for what the model adds, and the
cell rehearsed through the benchmark's own command at a tiny size.  The
model against its plain reference, the rule's forms, the retries and
the cache are in ``test_gated_delta_moe.py`` (one file until PR 43: two,
so that neither holds a worker of the tier-1 run for five minutes)."""

import copy
import json
import os
import shutil

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.models import gated_delta_moe as gm

from test_gated_delta_moe import (  # noqa: F401  (the two are fixtures)
    REFERENCE, ROOT, SCALE, TINY, _backend, _prefill, _reference_logits,
    _step, _tokens, held_config, model, program_config, reference)


# ----------------------------------------------------------------------
# (j) the comparison sees the new mechanism


@pytest.mark.parametrize("fault", ["zeroed", "bfloat16"])
def test_a_lost_or_rounded_state_fails_the_tiny_limits(model, reference,
                                                       fault):
    """The control that the cell's ``correct`` sees the recurrent
    state: carried state zeroed after the prefill, or kept in bfloat16
    between steps, moves the served logits past the limit the tiny cell
    runs under (1e-3); left alone they are within it."""
    be = _backend(model, "gdm_fault_" + fault)
    toks = _tokens(14, 13)
    want = _reference_logits(reference, model[1], toks)
    be.cache.allocate("s", 14)
    _prefill(be, "s", toks[:8], 8)
    worst = 0.0
    for t in range(8, 14):
        pools = be.cache.state_pools
        if fault == "zeroed" and t == 8:
            be.cache.swap_state(tuple(jnp.zeros_like(p) for p in pools))
        elif fault == "bfloat16":
            be.cache.swap_state(tuple(
                p.astype(jnp.bfloat16).astype(p.dtype) for p in pools))
        worst = max(worst, float(np.abs(
            _step(be, "s", toks[t], t) - want[t]).max()))
    assert worst > 1e-3, worst


# ----------------------------------------------------------------------
# (k) the configuration and its counts


def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-ep4.json")) as f:
        return json.load(f)


def _family():
    from benchmark.spec import load_module

    return load_module(os.path.join(ROOT, "benchmark", "models",
                                    "gated_delta_moe.py"), "family_gdm")


def test_configuration_keeps_the_published_widths():
    """Every number of the catalog's row is in the file under its key,
    but for the keys ``reduced`` names; no width is among them."""
    cfg = _published()
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert not [k for k in cfg["reduced"]
                if k.endswith(("_dim", "_rank", "_size", "_heads"))
                and k != "vocab_size"]
    assert set(cfg["reduced"]) == set(cfg["published"]) \
        == set(cfg["reduced_why"])
    share = cfg["deployment"]["experts"]
    assert share == {"published": 512, "held": cfg["num_experts"],
                     "first": 0}
    assert cfg["vocab_size"] * cfg["deployment"]["vocab_shards"] == 151936
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "qwen3-next-ep4")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_parameter_count_of_the_cut_and_of_the_published_model():
    """ISSUE 30's arithmetic: 33.7M a DeltaNet layer and 27.3M a
    full-attention layer outside their experts, 4.2M of router and
    shared expert, 402.7M of held experts a layer, 155.6M of embedding
    and head: 3,667M parameters here, 7.33 GB in bfloat16; and 79.67B
    for the 48 layers, 512 experts and whole vocabulary as published."""
    family = _family()
    cfg = _published()
    count = {k: int(np.prod(s))
             for k, s in family.weight_shapes(cfg).items()}

    def layer(i, *parts):
        return sum(v for k, v in count.items() if k.startswith("l%d_" % i)
                   and (not parts or k[len("l%d_" % i):].startswith(parts)))

    experts = layer(0, "experts_")
    assert experts == 128 * 3 * 2048 * 512 == 402653184
    router = layer(0, "router_", "shared_")
    assert abs(router - 4.2e6) < 0.01e6
    norms = layer(0, "mixer_norm", "ffn_norm")
    assert abs(layer(0) - experts - router - norms - 33.7e6) < 0.03e6
    assert abs(layer(3) - experts - router - norms - 27.26e6) < 0.01e6
    assert layer(0) == layer(1) == layer(4) and layer(3) == layer(7)
    assert count["embed_weight"] + count["pred_weight"] == 155582464
    assert abs(sum(count.values()) - 3667e6) < 1e6
    whole = dict(cfg, **cfg["published"])
    whole["deployment"] = {"experts": {"published": 512, "held": 512,
                                       "first": 0}}
    total = sum(int(np.prod(s))
                for s in family.weight_shapes(whole).values())
    assert abs(total - 79.67e9) < 0.01e9


def test_state_of_a_sequence_is_13_mb_and_a_token_4_kb():
    cfg = _family().program_config(_published())
    definition = gm.lm_definition(cfg)
    assert definition.cache_layers == 2 and definition.state.layers == 6
    assert definition.state.rows == (
        ((32, 128, 128), np.dtype(np.float32)),
        ((48, 512), np.dtype(jnp.bfloat16)))
    assert definition.state.bytes == 6 * (524288 * 4 + 24576 * 2)
    row = definition.cache_row
    assert (row.kind, row.width, row.pools) == ("kv", 512, 2)
    assert definition.cache_layers * row.bytes == 4096


# ----------------------------------------------------------------------
# (m) the benchmark's arithmetic and readers for what this model adds


def test_cost_arithmetic():
    from benchmark import flops
    from benchmark import gated_delta_costs as costs

    cfg = _published()
    assert costs.linear_layers(cfg) == 6
    assert costs.state_values(cfg) == 524288
    assert costs.tail_values(cfg) == 24576
    assert costs.state_bytes(cfg) == 6 * (4 * 524288 + 2 * 24576)
    ops, moved = costs.delta_decode_cost(cfg, row_layers=128 * 6)
    assert ops == 7 * 524288 * 768
    # the kernel's own traffic: the tail is moved outside it
    assert moved == 2 * 4 * 524288 * 768
    peaks = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
    least, by = flops.roofline_seconds(ops, moved, peaks)
    assert by == "memory" and abs(least - 3.93e-3) < 0.05e-3
    # 128 rows at 1,400 cached tokens each, one layer
    ops, moved = costs.gqa_decode_cost(cfg, context_tokens=179200, rows=128)
    assert ops == 2 * 179200 * 16 * 512
    assert moved == (179200 * 1024 + 128 * 16 * 512) * 2
    assert flops.roofline_seconds(ops, moved, peaks)[1] == "memory"


def _trace(events):
    end = max(at + dur for _, at, dur in events)
    return {"window_ns": [0, end], "devices": {"0": events}, "host": []}


def test_readers_of_the_new_metrics(capsys):
    """On a made-up trace: the shares count what their patterns name,
    the rooflines come out under 100% and say which peak bounds them,
    and every reader returns nothing where there is nothing to read (a
    program without the counters, a run without a trace)."""
    from benchmark.spec import Spec

    spec = Spec(ROOT)
    peaks = spec.peaks("TPU v5 lite")

    def read(metric, ctx):
        doc = spec.metric_file(metric)
        return spec.reader(doc["reader"])(ctx, doc.get("params", {}))

    events, at = [], 0
    for name, dur in _made_up_events():
        events.append([name, at, dur])
        at += dur + 1000
    counters = {"generation_state_bytes_total": 100 * 2 * 128 * 12730368.0,
                "generation_decode_steps_total": 100.0,
                "generation_decode_context_tokens_total": 100 * 128 * 1400.0,
                "generation_tokens_total": 100 * 128.0,
                "moe_layer_steps_total": 800.0,
                "moe_local_experts_hit_total": 800 * 110.0,
                "moe_local_assignments_total": 800 * 320.0}
    ctx = {"trace": _trace(events), "peaks": peaks,
           "compiles_in_window": counters}
    got = {m: read(m, ctx) for m in _NEW_METRICS}
    out = capsys.readouterr().out
    assert "state decode roofline: bound by memory" in out
    assert "gqa decode roofline: bound by memory" in out
    assert got["state_gb_per_step"] == pytest.approx(3.259, abs=0.001)
    assert got["moe_tokens_per_held_expert.qwen3next"] == 2.5
    assert got["moe_held_experts_hit_share.qwen3next"] \
        == pytest.approx(100 * 110 / 128.0)
    for name in ("gdn_share.serve", "moe_expert_share.qwen3next"):
        assert 0 < got[name] < 100, name
    for name in ("gdn_decode_roofline.serve",
                 "gqa_paged_decode_roofline.serve",
                 "moe_expert_roofline.qwen3next"):
        assert 0 < got[name] <= 100, (name, got[name])
    # the parent's program: no such counter, no such operation
    bare = {"trace": _trace([["%fusion.1 = f32[8,8] fusion(%p)", 0, 50]]),
            "peaks": peaks, "compiles_in_window": {
                "generation_decode_steps_total": 100.0}}
    for ctx in (bare, {"trace": None, "peaks": peaks,
                       "compiles_in_window": counters}, {}):
        for name in _NEW_METRICS:
            if ctx is not bare and name.startswith(("state_gb", "moe_tok",
                                                    "moe_held")) and ctx:
                continue
            assert read(name, dict(ctx, peaks=peaks)) is None, name


_NEW_METRICS = ("gdn_share.serve", "gdn_decode_roofline.serve",
                "gqa_paged_decode_roofline.serve", "state_gb_per_step",
                "moe_expert_share.qwen3next", "moe_expert_roofline.qwen3next",
                "moe_tokens_per_held_expert.qwen3next",
                "moe_held_experts_hit_share.qwen3next")


def _made_up_events():
    """(name, nanoseconds) of one decode step's operations as the
    patterns of the new metrics know them: see each metric's file."""
    with open(os.path.join(ROOT, "benchmark", "data",
                           "qwen3next_trace_names.json")) as f:
        return [(e["name"], e["ns"]) for e in json.load(f)["events"]]


# ----------------------------------------------------------------------
# (l) the new family rehearsed through the benchmark's own command, at
# the tiny size on the CPU


@pytest.fixture(scope="module")
def tiny_benchmark(tmp_path_factory):
    """The real BENCHMARK.json cut to the new cell, its configuration
    the tiny one above (the real reference beside it), its traffic a
    few short requests."""
    from benchmark.spec import Spec

    root = tmp_path_factory.mktemp("tiny_benchmark")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(str(root), sub))
    tiny = held_config(first=0, count=8)     # a share: 8 of 16 held
    tiny["deployment"]["serve"]["state_slots"] = 4
    # the driver is handed a part of each decode row, as in the cell
    tiny["deployment"]["serve"]["checked_logit_parts"] = 5
    with open(os.path.join(str(root), "configs", "tiny-qwen.json"),
              "w") as f:
        json.dump(tiny, f)
    shutil.copy(REFERENCE, os.path.join(str(root), "configs",
                                        "tiny-qwen.reference.py"))
    shutil.copy(os.path.join(ROOT, "benchmark", "tests", "tiny",
                             "peaks.json"), str(root))
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-reason-closed128-8k.json")) as f:
        traffic = json.load(f)
    traffic.update(
        clients=4, requests=8, stagger_s=0.01, max_total_tokens=64,
        prompt_tokens=dict(traffic["prompt_tokens"], median=12, min=4,
                           max=30),
        new_tokens=dict(traffic["new_tokens"], median=6, min=3, max=10),
        prefill_buckets=[16, 32], decode_buckets=[4], traced_seconds=0.3,
        checked_requests=3, request_timeout_s=60)
    with open(os.path.join(str(root), "traffic", "serve-tiny-8k.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(str(root), "limits", "tiny-qwen-serve.json"),
              "w") as f:
        json.dump({"served_token_logit_gap": 1e-3,
                   "served_logit_abs_err": 1e-3}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"], doc["run_seconds"] = ["."], 1
    doc["configs"] = [dict(c, name="tiny-qwen", source="test only",
                           file="configs/tiny-qwen.json")
                      for c in doc["configs"]
                      if c["name"] == "qwen3-next-ep4"]
    doc["workloads"] = [dict(w, name="tiny-qwen-serve", config="tiny-qwen",
                             traffic="serve-tiny-8k")
                        for w in doc["workloads"]
                        if w["name"] == "qwen3next-serve-reason128"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-qwen-serve"] \
                if "qwen3next-serve-reason128" in m["workloads"] else []
    return Spec(str(root), doc=doc)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearsed_on_the_cpu(tiny_benchmark, trace, capsys):
    from benchmark import run

    result = run.run_cell(tiny_benchmark, "tiny-qwen-serve",
                          3000000019 + trace, 1.5, trace,
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
        assert 0 <= metrics["decode_ahead_share"]["value"] < 100
        # what a step reads and writes of recurrent state: at most 4
        # rows of 3 layers of (4 x 8 x 8 + 3 x 64) float32, each way
        per_row = 3 * (256 + 192) * 4 * 2
        assert 0 < metrics["state_gb_per_step"]["value"] <= 4 * per_row / 1e9
        assert 0 < metrics["moe_tokens_per_held_expert.qwen3next"]["value"]
        assert 0 < metrics["moe_held_experts_hit_share.qwen3next"]["value"]
        # every tiny expert is held: the grouped form runs whole
        assert metrics["moe_grouped_extra_runs_per_layer"]["value"] == 0
        # no device trace on a CPU: nothing read, nothing raised
        for name in ("gdn_share.serve", "gdn_decode_roofline.serve",
                     "gqa_paged_decode_roofline.serve",
                     "moe_expert_roofline.qwen3next",
                     "device_idle_share.serve"):
            assert name not in metrics
    else:
        assert metrics["serve_tokens_per_s"]["value"] > 0
        # the cell reports no first-token time (PERF.md §6: its median
        # sits on a prefill-bucket edge and spreads past half its bound)
        assert "ttft_p50_ms" not in metrics
        assert metrics["setup_s"]["value"] > 0
    json.dumps(result)


def test_kept_parts_are_the_programs_logits(model):
    """What the family hands the driver in place of a decode step's
    logits where the configuration gives ``checked_logit_parts``: of
    every row the part of the vocabulary its position names, exact; a
    row's difference from a reference row is taken over that part, and
    successive positions cover every column."""
    family = _family()
    logits = np.random.RandomState(0).randn(4, 50).astype(np.float32)
    kept = family.KeptLogits(logits, [7, 0, 13, 9], 5)
    assert len(kept) == 4 and kept.values.shape == (4, 10)
    for row, part in enumerate((2, 0, 3, 4)):
        np.testing.assert_array_equal(
            kept.values[row], logits[row, 10 * part:10 * part + 10])
    ref = np.random.RandomState(1).randn(50).astype(np.float32)
    np.testing.assert_array_equal(kept[2] - ref,
                                  logits[2, 30:40] - ref[30:40])
    assert np.abs(kept[2] - ref).max() <= np.abs(logits[2] - ref).max()
    seen = np.zeros(50, bool)
    for position in range(20, 25):
        seen[family.kept_part(position, 5, 50)] = True
    assert seen.all()
    with pytest.raises(TypeError, match="a part of each row"):
        np.asarray(kept)
    with pytest.raises(ValueError, match="equal parts"):
        family.KeptLogits(logits, [1, 2, 3, 4], 7)
    tiny = copy.deepcopy(TINY)
    tiny["deployment"]["serve"]["checked_logit_parts"] = 5
    be = family.build_backend(tiny, tiny["deployment"]["serve"], model[1],
                              "gdm_kept", lambda base: base)
    be.cache.allocate("s", 8)
    _prefill(be, "s", _tokens(4), 8)
    table = be.cache.block_table("s", be.max_blocks_per_seq)[None]
    out = be.decode([3], [4], table, [5])[0]
    assert isinstance(out, family.KeptLogits)
    assert out.values.shape == (1, 10) and out[0].part == slice(40, 50)
    serve = _published()["deployment"]["serve"]
    assert 37984 % serve["checked_logit_parts"] == 0


def test_reference_one_precision_down_is_not_the_reference(reference):
    """The control of the cell's limits: the reference with every
    operand rounded to float8 (those of the recurrence's products with
    its state too) moves the logits by far more than bfloat16 does."""
    cfg = program_config(TINY)
    params = gm.init_params(cfg, 4, jnp.bfloat16, SCALE)
    toks = _tokens(16, seed=4)[None]
    exact = np.asarray(reference.logits(TINY, params, toks, "float32"))
    err = {mode: float(np.median(np.abs(np.asarray(
        reference.logits(TINY, params, toks, mode)) - exact)))
        for mode in ("bfloat16", "float8")}
    assert err["float8"] > 3 * err["bfloat16"] > 0, err
    with pytest.raises(ValueError, match="unknown mode"):
        reference.logits(TINY, params, toks, "float16")


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
    backend = tool.lm_backend("tiny_tool_gdm", "%s:7" % path)
    assert isinstance(backend, serving.LMBackend)
    assert backend.cache.row.kind == "kv" and backend.cache.num_slots == 8
    assert backend.cfg["held"] == (0, 16) and backend.cfg["seq_len"] == 64
    logits, k, v, _, state = backend.prefill(np.zeros(8, np.int32), 3)
    assert logits.shape == (50,) and k.shape == v.shape == (1, 8, 32)
    assert [s.shape for s in state] == [(3, 4, 8, 8), (3, 3, 64)]
