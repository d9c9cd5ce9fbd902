"""The yardstick's arithmetic against hand-worked numbers."""

import json
import os

import pytest

from benchmark import flops, segments
from benchmark.spec import CODE_DIR, Spec, SpecError

ROOT = os.path.dirname(str(CODE_DIR))


def test_window_rate_median_and_stall():
    # five segments of 10 steps: four take 2.0 s, one stalls to 4.0 s
    secs = [2.0, 2.0, 4.0, 2.0, 2.0]
    assert segments.median_rate(secs, 10) == 5.0
    # the run's reading is all the steps over all the time: the stall
    # moves it, and not the median
    assert segments.window_rate(secs, 10) == pytest.approx(50 / 12.0)
    assert segments.stall_share(secs, 10) == pytest.approx(1 - (50 / 12.0) / 5)
    # time between the segments is the window's too
    assert segments.window_rate(secs, 10, window_s=12.5) == 4.0
    assert segments.stall_share(secs, 10, window_s=12.5) == pytest.approx(0.2)
    # steady segments: nothing lost
    assert segments.stall_share([2.0] * 7, 10) == pytest.approx(0.0)
    with pytest.raises(ValueError):
        segments.median_rate([], 10)
    with pytest.raises(ValueError):
        segments.window_rate([], 10)


def test_the_end_to_end_rate_is_over_the_whole_window():
    """A stalled segment lowers ``train_tokens_per_s``; the median
    segment's rate, a per-layer metric, stays."""
    spec = Spec(ROOT)
    ctx = {"segment_seconds": [2.0, 2.0, 4.0, 2.0, 2.0], "window_s": 12.5,
           "steps_per_segment": 10, "items_per_step": 8192}
    read = {name: spec.reader(spec.metric_file(name)["reader"])(ctx, {})
            for name in ("train_tokens_per_s", "median_segment_tokens_per_s",
                         "stall_share.tokens")}
    assert read["train_tokens_per_s"] == 8192 * 4.0
    assert read["median_segment_tokens_per_s"] == 8192 * 5.0
    assert read["stall_share.tokens"] == pytest.approx(20.0)
    groups = {m["name"]: g for g in ("end_to_end", "per_layer")
              for m in spec.doc[g]}
    assert groups["train_tokens_per_s"] == "end_to_end"
    assert groups["median_segment_tokens_per_s"] == "per_layer"


def test_gpt2_flops_by_hand():
    cfg = {"n_embd": 1024, "n_layer": 24, "vocab_size": 50257}
    # per layer: qkv 3*d*d, out d*d, ffn 2*d*4d = 12 d^2 multiply-adds
    per_layer = 2 * 12 * 1024 * 1024
    attention = 2 * 2 * 1024 * (1024 + 1) / 2        # causal, T = 1024
    head = 2 * 1024 * 50257
    want = 24 * (per_layer + attention) + head
    assert flops.gpt2_forward_flops_per_token(cfg, 1024) == want
    assert want == pytest.approx(757_286_912, rel=1e-9)
    assert flops.gpt2_train_flops_per_token(cfg, 1024) == 3 * want


def test_flash_attention_flops_and_bytes_by_hand():
    # batch 8, 16 heads, T 1024, head 64, causal
    pairs = 1024 * 1025 / 2
    fwd = flops.flash_attention_flops(8, 16, 1024, 64)
    assert fwd == 2 * 2 * 8 * 16 * pairs * 64
    assert flops.flash_attention_flops(8, 16, 1024, 64, backward=True) \
        == 2.5 * fwd
    tensor = 8 * 16 * 1024 * 64 * 2
    assert flops.flash_attention_bytes(8, 16, 1024, 64) \
        == 4 * tensor + 8 * 16 * 1024 * 4
    peaks = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
    least, bound = flops.roofline_seconds(
        fwd, flops.flash_attention_bytes(8, 16, 1024, 64), peaks)
    # 17.2 GFLOP / 197 TFLOP/s = 87.3 us against 67.6 MB / 819 GB/s = 82.6 us
    assert bound == "compute" and least == pytest.approx(fwd / 197e12)
    assert least == pytest.approx(87.3e-6, rel=1e-2)
    assert flops.roofline_seconds(fwd / 2, 4 * tensor, peaks)[1] == "memory"


def test_resnet50_flops_by_hand():
    cfg = {"num_layers": 50, "image_size": 224, "num_classes": 1000}
    got = flops.resnet_forward_flops_per_image(cfg)
    # stem 118.0M multiply-adds; stage 1: unit1 (4096+36864+16384+16384)
    # *3136 and two more of (16384+36864+16384)*3136 ...; the published
    # figure for ResNet-50 is 3.8e9 multiply-adds with the projection
    # shortcuts on the strided input as here ~ 4.1e9
    stem = 112 * 112 * 49 * 3 * 64
    stage1 = 3136 * ((64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
                     + 2 * (256 * 64 + 9 * 64 * 64 + 64 * 256))
    assert got > 2 * (stem + stage1)
    assert got == pytest.approx(2 * 4.09e9, rel=0.02)
    assert flops.resnet_train_flops_per_image(cfg) == 3 * got


def test_peaks_table_knows_v5e_and_refuses_the_rest():
    spec = Spec(ROOT)
    assert spec.peaks("TPU v5 lite")["flops_per_s"]["bfloat16"] == 197e12
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SpecError):
        spec.peaks("TPU v9 imaginary")
    with pytest.raises(SpecError):
        spec.peaks("cpu")


def test_benchmark_json_agrees_with_its_files():
    """Every metric has its file and reader, every cell its config,
    traffic, limits, driver and reference, and names and units keep to
    the allowed characters."""
    spec = Spec(ROOT)
    assert json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())[
        "paths"] == ["benchmark"]
    for name, cell in spec.cells.items():
        cfg = spec.config(cell["config"])
        traffic = spec.traffic(cell["traffic"])
        spec.limits(name)
        spec.driver(traffic["kind"])
        spec.model(cfg["family"])
        assert os.path.isfile(os.path.join(ROOT, cfg["reference"]))
        assert str(spec.reference(cell["config"])[1]).endswith(
            cfg["reference"])
        for key in ("source", "reduced", "assumed", "reference"):
            assert key in cfg, (name, key)
        groups = [spec.cell_metrics(name, g)
                  for g in ("end_to_end", "per_layer")]
        assert any(m["name"] == "setup_s" for m in groups[0])
        assert len(groups[0]) >= 2 and groups[1]
        for m in groups[0] + groups[1]:
            doc = spec.metric_file(m["name"])
            spec.reader(doc["reader"])
            assert doc["what"], m["name"]


@pytest.mark.parametrize("bad", ["", "a b", "a,b", "a/b", "µs", "x" * 65])
def test_names_outside_the_allowed_set_are_refused(bad, tmp_path):
    from benchmark.spec import check_name

    with pytest.raises(SpecError):
        check_name(bad)


@pytest.mark.parametrize("unit", ["tokens per s", "µs", "", "u" * 17, "a,b"])
def test_units_outside_the_allowed_set_are_refused(unit):
    from benchmark.spec import check_unit

    with pytest.raises(SpecError):
        check_unit(unit, "m")


def test_host_watch_counts_the_collectors_pauses(capsys):
    """Every run says what the host did to its window: a full collection
    inside it is counted and timed, and the lines are printed once."""
    import gc

    from benchmark import harness

    watch = harness.HostWatch()
    watch.open()
    gc.collect()
    watch.close()
    watch.close()
    out = capsys.readouterr().out.splitlines()
    assert [line.split(":")[0] for line in out] == ["collector", "machine"]
    assert "1 of them full" in out[0]
    assert watch._collected not in gc.callbacks
