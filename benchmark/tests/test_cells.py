"""Every kind of cell rehearsed end to end at tiny sizes on the CPU; the
harness's refusals; and the comparison seen to fail when the timed path
is broken underneath it."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import run
from benchmark.spec import CODE_DIR, Spec, SpecError

ROOT = os.path.dirname(str(CODE_DIR))
TINY = os.path.join(str(CODE_DIR), "tests", "tiny")
SEED = 3000000019            # more than 32 signed bits hold


@pytest.mark.parametrize("cell,metric", [
    ("tiny-gpt2-train", "train_tokens_per_s"),
    ("tiny-resnet-train", "train_img_per_s"),
    ("tiny-gpt2-serve", "serve_tokens_per_s")])
def test_cell_runs_end_to_end(spec, cell, metric, capsys):
    result = run.run_cell(spec, cell, SEED, 0.6, 0, require_chip=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["metrics"][metric]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    if cell == "tiny-gpt2-serve":       # the latency users feel is guarded
        assert result["metrics"]["ttft_p50_ms"]["value"] > 0
    assert result["device"]["platform"] == "cpu"
    out = capsys.readouterr().out
    assert "compare config=" in out and "limit=" in out
    json.dumps(result)


def test_traced_run_reports_per_layer_metrics_and_no_compile(spec):
    result = run.run_cell(spec, "tiny-gpt2-train", SEED + 1, 0.4, 1,
                          require_chip=False)
    metrics = result["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    assert "stall_share.tokens" in metrics and "train_mfu.tokens" in metrics
    assert "median_segment_tokens_per_s" in metrics
    assert "train_tokens_per_s" not in metrics      # end-to-end: --trace 0
    # no device trace on a CPU: the readers of the trace return nothing
    assert "device_idle_share.tokens" not in metrics
    assert "busy_s" not in result["device"]


def test_broken_step_is_not_correct(spec, monkeypatch):
    """A step that returns its state unchanged: the parameters' change
    reads 0 against the reference's, and ``correct`` comes out false."""
    driver = spec.driver("train")
    real = driver.Program.one_step

    def frozen(self, batch=None):
        import jax

        keep = {k: np.asarray(v) for k, v in self.params.items()}
        real(self, batch)
        self.params = {k: jax.device_put(v, self.pshard[k])
                       for k, v in keep.items()}

    monkeypatch.setattr(driver.Program, "one_step", frozen)
    monkeypatch.setattr(spec, "driver", lambda kind: driver)
    result = run.run_cell(spec, "tiny-gpt2-train", SEED + 2, 0.2, 0,
                          require_chip=False)
    assert result["correct"] is False


def test_altered_served_token_is_not_correct(spec, monkeypatch):
    """A token altered where it is produced (the backend's logits
    rolled by one): the served tokens are no longer the reference's
    best, and ``correct`` comes out false."""
    from mxnet_tpu import serving

    real = serving.LMBackend.decode

    def rolled(self, *args, **kwargs):
        out = real(self, *args, **kwargs)
        return (np.roll(out[0], 1, axis=-1),) + tuple(out[1:])

    monkeypatch.setattr(serving.LMBackend, "decode", rolled)
    result = run.run_cell(spec, "tiny-gpt2-serve", SEED + 3, 0.6, 0,
                          require_chip=False)
    assert result["correct"] is False


def test_lower_precision_reference_fails_the_training_limits(spec):
    """The control at a size a test can hold: the reference in float8
    products, put in the program's place, against the float32 one."""
    from benchmark import compare

    cfg = spec.config("tiny-gpt2")
    model = spec.model("gpt2")
    train = cfg["deployment"]["train"]
    reference, _ = spec.reference("tiny-gpt2")
    weights = model.make_weights(cfg, SEED)
    make = model.batch_maker(cfg, train, SEED)
    batches = [make() for _ in range(3)]
    sides = {mode: compare.follow_steps(
        reference, cfg, weights, batches, train["optimizer"], mode=mode,
        block_rows=2) for mode in ("float32", "float8")}
    limits = {"loss_rel_gap": 1e-3, "first_grad_norm_gap": 1e-3,
              "param_change_norm_gap": 1e-3}
    rows = compare.training_rows(sides["float8"], sides["float32"], limits)
    assert not compare.report("tiny-gpt2", "control", rows)
    same = compare.training_rows(sides["float32"], sides["float32"], limits)
    assert compare.report("tiny-gpt2", "control", same)


def test_unknown_cell_reader_and_kind_are_refused(spec):
    with pytest.raises(SpecError, match="unknown workload"):
        run.run_cell(spec, "no-such-cell", 0, 0.1, 0, require_chip=False)
    with pytest.raises(SpecError, match="unknown reader"):
        spec.reader("no_such_reader")
    with pytest.raises(SpecError):
        spec.driver("no-such-kind")


def test_command_line_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cell in Spec(ROOT).cells:
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seed", str(SEED), "--seconds", "1",
             "--trace", "0"], env=env, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode != 0
        assert '"metrics"' not in proc.stdout
        break          # one process start is enough: the look is the same
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "no-such-cell"], env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0 and '"metrics"' not in proc.stdout


def test_four_virtual_devices_hold_a_sharded_cell(tiny_doc, tmp_path):
    """A training cell over data=2 x model=2 on four virtual devices
    (what a four-chip cell's files would ask for)."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices (XLA_FLAGS)")
    doc = tiny_doc
    cfg = json.load(open(os.path.join(TINY, "configs", "tiny-gpt2.json")))
    cfg["deployment"]["train"]["mesh"] = {"data": 2, "model": 2}
    os.makedirs(tmp_path / "configs")
    (tmp_path / "configs" / "tiny-gpt2x4.json").write_text(json.dumps(cfg))
    (tmp_path / "configs" / "tiny-gpt2x4.reference.py").write_text(
        open(os.path.join(TINY, "configs", "tiny-gpt2.reference.py")).read())
    doc["configs"].append({"name": "tiny-gpt2x4", "source": "test only",
                           "file": "configs/tiny-gpt2x4.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "tiny-gpt2-train-4", "chips": 4,
                             "config": "tiny-gpt2x4", "why": "test",
                             "traffic": "train-fresh-batches"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "tiny-gpt2-train" in m.get("workloads", ()):
            m["workloads"].append("tiny-gpt2-train-4")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    os.makedirs(tmp_path / "limits")
    (tmp_path / "limits" / "tiny-gpt2-train-4.json").write_text(
        open(os.path.join(TINY, "limits", "tiny-gpt2-train.json")).read())
    (tmp_path / "peaks.json").write_text(
        open(os.path.join(TINY, "peaks.json")).read())
    result = run.run_cell(Spec(str(tmp_path)), "tiny-gpt2-train-4", SEED,
                          0.3, 0, require_chip=False)
    assert result["correct"] is True
    assert result["device"]["count"] == 4
