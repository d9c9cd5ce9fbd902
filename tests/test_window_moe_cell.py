"""The cell of the decoder of sliding-window and global attention over
ReGLU experts (``models/window_moe.py``): that both faults of the
builder's chip runs fail the tiny limits, the cell rehearsed through
the benchmark's own command at a tiny size, what the family hands the
driver, the reference one precision down, and the serve tool.  The
model against its plain reference, the cache's layer groups and the
retries are in ``test_window_moe.py``, the kernels and the arithmetic
in ``test_window_moe_kernels.py`` (one file until PR 43: three, so that
none holds a worker of the tier-1 run for five minutes)."""

import copy
import json
import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.models import window_moe as wm
from mxnet_tpu.ops import attention as att

from test_window_moe import (  # noqa: F401  (the two are fixtures)
    REFERENCE, ROOT, SCALE, TINY, TOL, _backend, _family, _prefill,
    _reference_logits, _step, _tokens, model, program_config, reference)


# ----------------------------------------------------------------------
# (h) both faults of the builder's chip runs fail the tiny limits


def _served_errors(model, reference, fault, monkeypatch, prompt):
    """Largest |program - reference| a token over ``prompt`` tokens and
    30 decode steps, with ``fault`` put into the program."""
    name = "wm_fault_%s" % fault
    if fault == "no-band":
        # a window layer's prefill without the band: plain causal
        real = att.gqa_prefill_attention
        monkeypatch.setattr(
            wm, "gqa_prefill_attention",
            lambda q, k, v, scale, window=None: real(q, k, v, scale))
    be = _backend(model, name)
    if fault == "ring-off-by-one":
        # the ring written one entry off after its first wrap
        ring = be.cache._groups[1]
        real_entry = ring.entry

        def entry(positions, block_size):
            index = positions // block_size
            return np.where(index >= ring.ring, (index + 1) % ring.ring,
                            real_entry(positions, block_size))
        ring.entry = entry
    toks = list(_tokens(prompt, 23))
    be.cache.allocate("s", 120)
    got = [_prefill(be, "s", toks, 64)]
    for t in range(prompt, prompt + 30):
        toks.append(int(np.argmax(got[-1])))
        got.append(_step(be, "s", toks[-1], t))
    want = _reference_logits(reference, model[1], toks)
    return np.abs(np.stack(got) - want[prompt - 1:]).max(axis=1)


@pytest.mark.parametrize("fault", ["sound", "no-band", "ring-off-by-one"])
def test_both_faults_fail_the_tiny_limits(model, reference, fault,
                                          monkeypatch):
    """The tiny cell's limit on the logits' error is 1e-3.  A sound
    program reads under it; a window layer's prefill without the band
    reads over it from the first token (a prompt of 52 against a window
    of 32); a ring written one entry off after its first wrap (a prompt
    of 40 fills entries 0-2; the step at position 48 begins block 3,
    which belongs in entry 0) reads over it from the step at position
    49, the first to look for a key of the misplaced block."""
    prompt = 40 if fault == "ring-off-by-one" else 52
    jax.clear_caches()
    try:
        err = _served_errors(model, reference, fault, monkeypatch, prompt)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    if fault == "sound":
        assert err.max() < 1e-3
    elif fault == "no-band":
        assert err[0] > 1e-3 and err.max() > 1e-2
    else:
        # err[0] is the prefill's token, err[k] the step at 39 + k
        assert err[:10].max() < 1e-3 and err[10:].max() > 1e-2


# ----------------------------------------------------------------------
# (k) the new cell rehearsed through the benchmark's own command, at the
# tiny size on the CPU


@pytest.fixture(scope="module")
def tiny_benchmark(tmp_path_factory):
    """The real BENCHMARK.json cut to the new cell, its configuration
    the tiny one above (the real reference beside it), its traffic a
    few short requests, some of them over the tiny window."""
    from benchmark.spec import Spec

    root = tmp_path_factory.mktemp("tiny_benchmark")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(str(root), sub))
    tiny = copy.deepcopy(TINY)
    # the driver is handed a part of each decode row, as in the cell
    tiny["deployment"]["serve"]["checked_logit_parts"] = 5
    tiny["deployment"]["serve"]["num_blocks"] = [40, 16]
    with open(os.path.join(str(root), "configs", "tiny-st.json"), "w") as f:
        json.dump(tiny, f)
    shutil.copy(REFERENCE, os.path.join(str(root), "configs",
                                        "tiny-st.reference.py"))
    shutil.copy(os.path.join(ROOT, "benchmark", "tests", "tiny",
                             "peaks.json"), str(root))
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-mixed-closed48-14k.json")) as f:
        traffic = json.load(f)
    assert traffic["clients"] == 48 and traffic["decode_buckets"] == [48]
    assert traffic["max_total_tokens"] == 14336 < 16384
    assert traffic["prefill_buckets"] == [512, 1024, 2048, 4096, 6144, 8192,
                                          12288]
    traffic.update(
        clients=4, requests=8, stagger_s=0.01, max_total_tokens=128,
        prompt_tokens=dict(traffic["prompt_tokens"], median=30, min=6,
                           max=90),
        new_tokens=dict(traffic["new_tokens"], median=10, min=4, max=30),
        prefill_buckets=[16, 64, 96], decode_buckets=[4],
        traced_seconds=0.3, checked_requests=3, request_timeout_s=60)
    with open(os.path.join(str(root), "traffic", "serve-tiny-14k.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(str(root), "limits", "tiny-st-serve.json"),
              "w") as f:
        json.dump({"served_token_logit_gap": 1e-3,
                   "served_logit_abs_err": 1e-3}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"], doc["run_seconds"] = ["."], 1
    doc["configs"] = [dict(c, name="tiny-st", source="test only",
                           file="configs/tiny-st.json")
                      for c in doc["configs"]
                      if c["name"] == "smallthinker-21b-ep4"]
    doc["workloads"] = [dict(w, name="tiny-st-serve", config="tiny-st",
                             traffic="serve-tiny-14k")
                        for w in doc["workloads"]
                        if w["name"] == "smallthinker-serve-mixed48"]
    assert len(doc["configs"]) == 1 and len(doc["workloads"]) == 1
    assert doc["workloads"][0]["chips"] == 1
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-st-serve"] \
                if "smallthinker-serve-mixed48" in m["workloads"] else []
    return Spec(str(root), doc=doc)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearsed_on_the_cpu(tiny_benchmark, trace, capsys):
    from benchmark import run

    result = run.run_cell(tiny_benchmark, "tiny-st-serve",
                          3000000041 + trace, 1.5, trace,
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
        assert 0 < metrics["window_kv_occupancy_peak.smallthinker"][
            "value"] <= 100
        assert 6 < metrics["decode_context_tokens_mean"]["value"] < 128
        assert 0 < metrics["window_keys_walked_share.smallthinker"][
            "value"] <= 100
        assert 0 <= metrics["decode_ahead_share"]["value"] < 100
        # all 8 tiny experts held: 3 pairs a row over a "16" that the
        # metric's file names for the real cell
        assert 0 < metrics["moe_tokens_per_held_expert.smallthinker"][
            "value"]
        assert 0 < metrics["moe_held_experts_hit_share.smallthinker"]["value"]
        # every tiny expert is held: the grouped form runs whole
        assert metrics["moe_grouped_extra_runs_per_layer"]["value"] == 0
        # no device trace on a CPU: nothing read, nothing raised
        for name in ("gqa_paged_decode_roofline.smallthinker",
                     "window_paged_decode_roofline.smallthinker",
                     "window_prefill_roofline.smallthinker",
                     "window_decode_attn_share.smallthinker",
                     "moe_expert_roofline.smallthinker",
                     "device_idle_share.serve"):
            assert name not in metrics
    else:
        assert metrics["serve_tokens_per_s"]["value"] > 0
        # the cell reports no first-token time: a prompt mix this wide
        # puts the median near a bucket's edge
        assert "ttft_p50_ms" not in metrics
        assert metrics["setup_s"]["value"] > 0
    json.dumps(result)


def test_the_family_hands_the_driver_a_part_of_each_row(model):
    """Where the configuration gives ``checked_logit_parts`` the backend
    the family builds hands its caller, of every decode row, the part of
    the vocabulary its position names; ``num_blocks`` is the pair."""
    family = _family()
    tiny = copy.deepcopy(TINY)
    tiny["deployment"]["serve"]["checked_logit_parts"] = 5
    be = family.build_backend(tiny, tiny["deployment"]["serve"], model[1],
                              "wm_kept", lambda base: base)
    assert [g["blocks"] for g in be.cache.stats()["groups"]] == [32, 16]
    be.cache.allocate("s", 8)
    _prefill(be, "s", _tokens(4), 8)
    table = be.cache.block_table("s", be.max_blocks_per_seq)[None]
    out = be.decode([3], [4], table, [5])[0]
    assert out.values.shape == (1, 10) and out[0].part == slice(40, 50)


def test_reference_one_precision_down_is_not_the_reference(reference):
    """The float8 control mode moves the logits by far more than the
    float32 sides differ; bfloat16, the stated precision, lies between."""
    cfg = program_config(TINY)
    params = wm.init_params(cfg, 4, jnp.float32, SCALE)
    toks = _tokens(40, 25)[None]
    exact = np.asarray(reference.logits(TINY, params, toks))
    stated = np.asarray(reference.logits(TINY, params, toks, "bfloat16"))
    lower = np.asarray(reference.logits(TINY, params, toks, "float8"))
    assert TOL < np.abs(stated - exact).max() < np.abs(lower - exact).max()
    assert np.abs(lower - exact).max() > 0.1
    with pytest.raises(ValueError, match="unknown mode"):
        reference.logits(TINY, params, toks, "float16")


def test_serve_tool_loads_the_family_by_configuration(tmp_path):
    """``tools/serve.py --lm name=<configuration file>`` builds this
    family from the file's ``family`` key, like its siblings."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_tool", os.path.join(ROOT, "tools", "serve.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    path = tmp_path / "tiny-st.json"
    path.write_text(json.dumps(TINY))
    be = tool.lm_backend("tiny_tool_wm", "%s:7" % path)
    assert isinstance(be, serving.LMBackend)
    assert be.definition.cache_groups == (((0, 1), None), ((2, 3, 4), 32))
    assert be.max_blocks_per_seq == 11 and be.cfg["held"] == (0, 8)
    logits, k, v, counts = be.prefill(np.zeros(16, np.int32), 3)
    assert logits.shape == (50,) and k.shape == v.shape == (5, 16, 16)
