"""Example gates, file 6 of 6.  ``conftest._run_example`` says what a
gate is, why it runs in a fresh subprocess and what the ``timeout``
beside it is.  The gates are dealt by measured time, not by theme:
tier-1 hands a whole file to one worker, so the files are kept about
equal, the long gates first."""

from conftest import _run_example


def test_fcn_xs_example():
    """FCN with Deconvolution upsampling + Crop skip fusion segments
    per-pixel: accuracy and foreground IoU bars.  4 epochs read 0.964
    and 0.776 in five runs of five (0.966 and 0.790 at 6 epochs)."""
    stats = _run_example("fcn_xs.py", "epochs=4, log=False", timeout=60)
    assert stats["pix_acc"] > 0.93, stats
    assert stats["fg_miou"] > 0.6, stats


def test_bi_lstm_sort_example():
    """Bidirectional LSTM emits the sorted sequence (per-position order
    statistics need whole-sequence context).  5 epochs clear the
    accuracy bar with margin: 0.930 in five runs of five (0.936 at 6
    epochs, 0.948 at 8)."""
    stats = _run_example("bi_lstm_sort.py", "epochs=5, log=False",
                         timeout=90)
    assert stats["elem_acc"] > 0.85, stats


def test_multi_task_example():
    """Shared trunk + two softmax heads trained jointly; both heads
    converge.  4 epochs read 1.0 and 1.0 in five runs of five, as 6
    do."""
    stats = _run_example("multi_task.py", "epochs=4, log=False",
                         timeout=60)
    assert stats["cls_acc"] > 0.9, stats
    assert stats["parity_acc"] > 0.9, stats


def test_quantize_resnet_example():
    """Model-level PTQ (contrib.quantization): BN fold + symmetric
    calibration + int8 graph rewrite on a trained ResNet-8; int8 top-1
    must stay within a point of fp32 (chip-measured throughput rows come
    from the same example's --benchmark mode via tools/bench_table.py)."""
    stats = _run_example("quantize_resnet.py",
                         "epochs=4, n_train=512, log=False", timeout=90)
    assert stats["fp32_acc"] > 0.9, stats
    assert stats["int8_acc"] >= stats["fp32_acc"] - 0.01, stats


def test_bayesian_methods_example():
    """SGLD samples the Welling-Teh bimodal posterior (not optimizing:
    nonzero spread, mass near the modes), HMC's Metropolis step both
    accepts and rejects while the predictive mean fits, and the SGLD
    teacher ensemble distills into a student within a point of its
    accuracy (Bayesian Dark Knowledge)."""
    stats = _run_example("bayesian_methods.py", "log=False", timeout=90)
    assert stats["sgld_near_mode"] > 0.6, stats
    assert 0.02 < stats["sgld_spread"] < 1.0, stats
    assert 0.55 < stats["hmc_accept"] < 0.995, stats
    assert stats["hmc_rmse"] < 0.2, stats
    assert stats["teacher_acc"] > 0.9, stats
    assert stats["student_acc"] > stats["teacher_acc"] - 0.05, stats
