"""Example gates, file 3 of 6.  ``conftest._run_example`` says what a
gate is, why it runs in a fresh subprocess and what the ``timeout``
beside it is.  The gates are dealt by measured time, not by theme:
tier-1 hands a whole file to one worker, so the files are kept about
equal, the long gates first."""

from conftest import _run_example


def test_speech_recognition_example():
    """Mini DeepSpeech (conv front-end -> BiGRU -> per-frame FC -> CTC):
    greedy-decoded character error rate drops below 12% on synthetic
    utterances with variable-duration tokens.  ``num_epochs`` is a
    ceiling: the run stops at the first epoch at or under ``stop_cer``,
    the 11th (0.063; the 10th reads 0.112), so there is nothing to cut
    (PR 24)."""
    stats = _run_example("speech_recognition.py",
                         "num_epochs=14, stop_cer=0.08, log=False",
                         timeout=240)
    assert stats["cer"] < 0.12, stats


def test_quantize_transformer_example():
    """PTQ on the transformer LM (the quantized FC path: FFN pairs +
    vocab head; attention stays float inside the fused op) — int8
    next-token accuracy within a point of fp32 on a trained tiny LM.
    Chip throughput rows come from the same example's --benchmark mode
    via tools/bench_table.py."""
    stats = _run_example("quantize_transformer.py",
                         "epochs=4, n_train=512, log=False", timeout=60)
    assert stats["fp32_acc"] > 0.9, stats
    assert stats["int8_acc"] >= stats["fp32_acc"] - 0.01, stats


def test_torch_module_example():
    """Hybrid net with torch nn.Linear layers as trainable graph nodes
    (reference example/torch/torch_module.py): trains to >=0.95 with
    the torch parameters updated by the framework's optimizer."""
    stats = _run_example("torch_module.py", "epochs=8, log=False",
                         timeout=60)
    assert stats["acc"] >= 0.95, stats
