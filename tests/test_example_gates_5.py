"""Example gates, file 5 of 6.  ``conftest._run_example`` says what a
gate is, why it runs in a fresh subprocess and what the ``timeout``
beside it is.  The gates are dealt by measured time, not by theme:
tier-1 hands a whole file to one worker, so the files are kept about
equal, the long gates first."""

from conftest import _run_example


def test_recommender_mf_example():
    """Matrix-factorization recommender: learned embeddings beat the
    global-mean and per-item-mean baselines by a wide margin."""
    stats = _run_example("recommender_mf.py",
                         "epochs=10, batch=128, log=False", timeout=60)
    assert stats["rmse"] < 0.7 * stats["rmse_item"], stats
    assert stats["rmse"] < 1.0, stats


def test_kaggle_ndsb2_example():
    """NDSB-2 cardiac volume: frame-difference trick (SliceChannel +
    pairwise subtract + Concat) + per-bin sigmoid CDF regression
    (LogisticRegressionOutput) beats the best constant CDF predictor
    under the reference's isotonic-corrected CRPS.  8 epochs read a
    CRPS of 0.0383 in five runs of five, further under both bars than
    the 0.0454 of 12 epochs."""
    stats = _run_example("kaggle_ndsb2.py", "epochs=8, log=False",
                         timeout=90)
    assert stats["crps"] < 0.8 * stats["crps_const"], stats
    assert stats["crps"] < 0.055, stats


def test_stochastic_depth_example():
    """StochasticDepthModule (BaseModule composition with a host-side
    per-batch gate over two jitted branches): the gated chain still
    converges, the gate actually closes at ~death_rate during training,
    and eval uses the deterministic expectation path."""
    stats = _run_example("stochastic_depth.py",
                         "epochs=8, death_rate=0.3, log=False", timeout=90)
    assert stats["val_acc"] > 0.9, stats
    # 2 blocks x 8 epochs x 12 batches = 192 draws; Bernoulli(0.3)
    # mean is within ~3 sigma bounds below
    assert 0.15 < stats["closed_frac"] < 0.45, stats
    assert stats["n_gate_draws"] >= 150, stats


def test_quantization_conv_example():
    """Conv-path PTQ: _contrib_quantized_conv + quantized FC carry a
    small convnet to fp32-matching accuracy on the int8 MXU path."""
    stats = _run_example("quantization.py", "epochs=8, log=False",
                         timeout=60, func="run_conv")
    assert stats["fp32_acc"] > 0.9, stats
    assert stats["int8_acc"] > stats["fp32_acc"] - 0.05, stats


def test_svm_mnist_example():
    """SVMOutput heads (both hinge forms) are drop-in replacements for
    softmax on the same trunk."""
    accs = _run_example("svm_mnist.py", "epochs=6, log=False", timeout=60)
    for name, acc in accs.items():
        assert acc > 0.9, accs
