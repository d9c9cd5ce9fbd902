"""Example gates, file 4 of 6.  ``conftest._run_example`` says what a
gate is, why it runs in a fresh subprocess and what the ``timeout``
beside it is.  The gates are dealt by measured time, not by theme:
tier-1 hands a whole file to one worker, so the files are kept about
equal, the long gates first."""

from conftest import _run_example


def test_kaggle_ndsb1_example():
    """NDSB-1 full competition pipeline: class-folder tree -> stratified
    .lst split -> im2rec RecordIO at short-edge-48 -> DSB convnet via
    Module.fit -> test-set prediction -> Kaggle submission CSV with
    normalized probability rows.  The last epoch's accuracy swings with
    the epoch count (80 validation images): 12 epochs read 0.9625 and
    0.958 in five runs of five, 10 read 0.875 and 0.875, 14 read 0.8125
    and 0.8125, one image over the bar."""
    stats = _run_example(
        "kaggle_ndsb1.py",
        "epochs=12, n_per_class=40, n_test=48, width_mult=0.5, log=False",
        timeout=240)
    assert stats["val_acc"] > 0.8, stats
    assert stats["test_acc"] > 0.7, stats
    assert stats["n_submission_rows"] == 48, stats


def test_cnn_text_classification_example():
    """Kim-CNN (n-gram convs + max-over-time pooling) learns planted
    signature trigrams position-invariantly.  3 epochs read 1.0 in
    five runs of five, as 4 and 5 do."""
    stats = _run_example("cnn_text_classification.py",
                         "epochs=3, log=False", timeout=60)
    assert stats["val_acc"] > 0.95, stats


def test_rnn_time_major_example():
    """Time-major (TNC) and batch-major (NTC) LM builds are numerically
    identical given the same parameters (the reference's rnn-time-major
    demo point, minus the cuDNN speed asymmetry XLA erases), and both
    train to near the synthetic Markov chain's true entropy."""
    stats = _run_example("rnn_time_major.py", "epochs=6, log=False",
                         timeout=60)
    assert stats["parity_gap"] < 1e-5, stats
    assert stats["ppl_tnc"] < 1.35 * stats["true_ppl"], stats
    assert stats["ppl_ntc"] < 1.35 * stats["true_ppl"], stats


def test_nce_loss_example():
    """NCE with k=8 sampled negatives learns the full-vocab ranking: the
    true next token ranks (near-)first across the whole vocabulary."""
    stats = _run_example("nce_loss.py", "steps=300, log=False", timeout=60)
    assert stats["mrr"] > 0.8, stats
