"""Example gates, file 1 of 6.  ``conftest._run_example`` says what a
gate is, why it runs in a fresh subprocess and what the ``timeout``
beside it is.  The gates are dealt by measured time, not by theme:
tier-1 hands a whole file to one worker, so the files are kept about
equal, the long gates first."""

from conftest import _run_example


def test_autoencoder_example():
    """Layer-wise pretraining + fine-tuning beats same-width PCA on a
    curved manifold (nonlinearity is doing real work).  No smaller size
    clears the bar (0.0922 against 0.0966 here): 6 + 35 epochs read
    0.1000, 10 + 25 read 0.1034, 4 + 30 read 0.1052 (PR 24)."""
    stats = _run_example("autoencoder.py",
                         "pretrain_epochs=10, finetune_epochs=35, log=False",
                         timeout=60)
    assert stats["ae_mse"] < 0.9 * stats["pca_mse"], stats


def test_train_pipeline_example():
    """Pipeline-parallel training walkthrough (capability the reference
    lacks): heterogeneous stage_idx-routed stages over a 4-way pipe mesh,
    1F1B + Adam + Factor schedule converge, and GPipe reproduces the same
    final accuracy on the identical seed."""
    stats = _run_example("train_pipeline.py", "steps=60, log=False",
                         timeout=60, func="train")
    assert stats["accuracy"] > 0.9, stats
    assert stats["loss"] < stats["first_loss"] / 10, stats
    gpipe = _run_example("train_pipeline.py",
                         "steps=60, schedule='gpipe', log=False",
                         timeout=60, func="train")
    assert gpipe["accuracy"] > 0.9, gpipe
    # fully seed-deterministic data/batches: schedule equivalence must
    # hold end-to-end, not just "both converge"
    assert abs(gpipe["accuracy"] - stats["accuracy"]) < 1e-6, (stats, gpipe)


def test_quantization_example():
    """PTQ workflow: symmetric int8 calibration, fake-quant path
    (reference quantize/dequantize parity) and the int8-MXU path agree
    to fp32 rounding, and int8 accuracy matches fp32."""
    stats = _run_example("quantization.py", "epochs=10, log=False",
                         timeout=60)
    assert stats["path_delta"] < 1e-5, stats
    assert stats["int8_acc"] > stats["fp32_acc"] - 0.02, stats
    assert stats["fp32_acc"] > 0.9, stats


def test_neural_style_example():
    """Input-optimization via inputs_need_grad: the combined
    style(Gram)+content objective drops by more than half."""
    stats = _run_example("neural_style.py", "steps=100, log=False",
                         timeout=60)
    assert stats["final_loss"] < 0.5 * stats["initial_loss"], stats
