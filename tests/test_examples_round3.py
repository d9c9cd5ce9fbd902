"""Round-3 carried examples (reference example/ dirs; VERDICT r2 #9):
cnn_text_classification, nce-loss, autoencoder, fcn-xs, multi-task,
neural-style, bi-lstm-sort, svm_mnist — each with a behavioral
convergence/quality gate on synthetic data (no-egress).

Each gate runs its example in a FRESH subprocess: one pytest process
compiling every example's graphs on top of the rest of the suite
eventually segfaults XLA:CPU's backend compiler (observed
deterministically around the ~300th test; jax.clear_caches() does not
help — the leak is in global compiler state).  Isolation also keeps the
examples honest: each must work from a cold start, like a user run.
"""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(name, call, func="run", timeout=900):
    """Execute examples/<name>'s entry point in a subprocess; return
    stats.  ``timeout`` is per-gate: the heavy convergence gates get a
    right-sized limit so the slowest gate stays under half its limit on
    a loaded box (a gate passing only on an idle machine is a latent
    red suite — VERDICT r4 #6)."""
    code = (
        "import sys, json\n"
        "sys.path.insert(0, %r)\n"
        "import importlib.util\n"
        "spec = importlib.util.spec_from_file_location('ex', %r)\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "sys.modules['ex'] = mod\n"
        "spec.loader.exec_module(mod)\n"
        "stats = mod.%s(%s)\n"
        "stats.pop('image', None)\n"
        "print('STATS ' + json.dumps({k: float(v) for k, v in stats.items()}))\n"
        % (_REPO, os.path.join(_REPO, "examples", name), func, call)
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout, cwd=_REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    line = [l for l in r.stdout.splitlines() if l.startswith("STATS ")]
    assert line, r.stdout
    return json.loads(line[-1][6:])


def test_cnn_text_classification_example():
    """Kim-CNN (n-gram convs + max-over-time pooling) learns planted
    signature trigrams position-invariantly."""
    stats = _run_example("cnn_text_classification.py",
                         "epochs=5, log=False")
    assert stats["val_acc"] > 0.95, stats


def test_nce_loss_example():
    """NCE with k=8 sampled negatives learns the full-vocab ranking: the
    true next token ranks (near-)first across the whole vocabulary."""
    stats = _run_example("nce_loss.py", "steps=300, log=False")
    assert stats["mrr"] > 0.8, stats


def test_autoencoder_example():
    """Layer-wise pretraining + fine-tuning beats same-width PCA on a
    curved manifold (nonlinearity is doing real work)."""
    stats = _run_example("autoencoder.py",
                         "pretrain_epochs=10, finetune_epochs=35, log=False",
                         timeout=1200)  # ~550 s measured under load
    assert stats["ae_mse"] < 0.9 * stats["pca_mse"], stats


def test_multi_task_example():
    """Shared trunk + two softmax heads trained jointly; both heads
    converge."""
    stats = _run_example("multi_task.py", "epochs=6, log=False")
    assert stats["cls_acc"] > 0.9, stats
    assert stats["parity_acc"] > 0.9, stats


def test_fcn_xs_example():
    """FCN with Deconvolution upsampling + Crop skip fusion segments
    per-pixel: accuracy and foreground IoU bars."""
    stats = _run_example("fcn_xs.py", "epochs=6, log=False",
                         timeout=1200)  # ~450 s measured under load
    assert stats["pix_acc"] > 0.93, stats
    assert stats["fg_miou"] > 0.6, stats


def test_neural_style_example():
    """Input-optimization via inputs_need_grad: the combined
    style(Gram)+content objective drops by more than half."""
    stats = _run_example("neural_style.py", "steps=100, log=False")
    assert stats["final_loss"] < 0.5 * stats["initial_loss"], stats


def test_bi_lstm_sort_example():
    """Bidirectional LSTM emits the sorted sequence (per-position order
    statistics need whole-sequence context).  8 epochs keeps the gate at
    ~200 s — under a quarter of the subprocess limit even on a busy box
    (15 epochs ran ~700 s against the 900 s limit: a latent timeout) —
    while clearing the accuracy bar with margin (0.949 measured)."""
    stats = _run_example("bi_lstm_sort.py", "epochs=8, log=False")
    assert stats["elem_acc"] > 0.85, stats


def test_svm_mnist_example():
    """SVMOutput heads (both hinge forms) are drop-in replacements for
    softmax on the same trunk."""
    accs = _run_example("svm_mnist.py", "epochs=6, log=False")
    for name, acc in accs.items():
        assert acc > 0.9, accs


def test_dec_clustering_example():
    """DEC recipe (AE pretrain -> k-means centroid init -> KL(P||Q)
    refinement): the learned embedding clusters data whose raw Euclidean
    structure is swamped by nuisance variance, and refinement improves
    on its own k-means init."""
    stats = _run_example("dec_clustering.py", "log=False",
                         timeout=1200)  # ~530 s measured under load
    assert stats["dec_acc"] > stats["raw_acc"] + 0.3, stats
    assert stats["dec_acc"] >= stats["init_acc"] - 0.02, stats
    assert stats["dec_acc"] > 0.7, stats


def test_recommender_mf_example():
    """Matrix-factorization recommender: learned embeddings beat the
    global-mean and per-item-mean baselines by a wide margin."""
    stats = _run_example("recommender_mf.py",
                         "epochs=10, batch=128, log=False")
    assert stats["rmse"] < 0.7 * stats["rmse_item"], stats
    assert stats["rmse"] < 1.0, stats


def test_stochastic_depth_example():
    """StochasticDepthModule (BaseModule composition with a host-side
    per-batch gate over two jitted branches): the gated chain still
    converges, the gate actually closes at ~death_rate during training,
    and eval uses the deterministic expectation path."""
    stats = _run_example("stochastic_depth.py",
                         "epochs=8, death_rate=0.3, log=False")
    assert stats["val_acc"] > 0.9, stats
    # 2 blocks x 8 epochs x 12 batches = 192 draws; Bernoulli(0.3)
    # mean is within ~3 sigma bounds below
    assert 0.15 < stats["closed_frac"] < 0.45, stats
    assert stats["n_gate_draws"] >= 150, stats


def test_bayesian_methods_example():
    """SGLD samples the Welling-Teh bimodal posterior (not optimizing:
    nonzero spread, mass near the modes), HMC's Metropolis step both
    accepts and rejects while the predictive mean fits, and the SGLD
    teacher ensemble distills into a student within a point of its
    accuracy (Bayesian Dark Knowledge)."""
    stats = _run_example("bayesian_methods.py", "log=False")
    assert stats["sgld_near_mode"] > 0.6, stats
    assert 0.02 < stats["sgld_spread"] < 1.0, stats
    assert 0.55 < stats["hmc_accept"] < 0.995, stats
    assert stats["hmc_rmse"] < 0.2, stats
    assert stats["teacher_acc"] > 0.9, stats
    assert stats["student_acc"] > stats["teacher_acc"] - 0.05, stats


def test_speech_recognition_example():
    """Mini DeepSpeech (conv front-end -> BiGRU -> per-frame FC -> CTC):
    greedy-decoded character error rate drops below 12% on synthetic
    utterances with variable-duration tokens."""
    stats = _run_example("speech_recognition.py",
                         "num_epochs=14, stop_cer=0.08, log=False",
                         timeout=1800)  # ~690 s measured under load
    assert stats["cer"] < 0.12, stats


def test_kaggle_ndsb2_example():
    """NDSB-2 cardiac volume: frame-difference trick (SliceChannel +
    pairwise subtract + Concat) + per-bin sigmoid CDF regression
    (LogisticRegressionOutput) beats the best constant CDF predictor
    under the reference's isotonic-corrected CRPS."""
    stats = _run_example("kaggle_ndsb2.py", "epochs=12, log=False")
    assert stats["crps"] < 0.8 * stats["crps_const"], stats
    assert stats["crps"] < 0.055, stats


def test_rnn_time_major_example():
    """Time-major (TNC) and batch-major (NTC) LM builds are numerically
    identical given the same parameters (the reference's rnn-time-major
    demo point, minus the cuDNN speed asymmetry XLA erases), and both
    train to near the synthetic Markov chain's true entropy."""
    stats = _run_example("rnn_time_major.py", "epochs=6, log=False")
    assert stats["parity_gap"] < 1e-5, stats
    assert stats["ppl_tnc"] < 1.35 * stats["true_ppl"], stats
    assert stats["ppl_ntc"] < 1.35 * stats["true_ppl"], stats


def test_speech_demo_example():
    """Kaldi-pipeline acoustic model (reference example/speech-demo):
    features written as REAL Kaldi binary ark/scp (pure-numpy reader —
    the reference needs a compiled Kaldi), round-tripped, trained
    through an LSTM acoustic model, posteriors written back to ark and
    verified; frame accuracy >= 0.9."""
    stats = _run_example("speech_demo.py", "epochs=6, log=False")
    assert stats["frame_acc"] >= 0.9, stats


def test_torch_module_example():
    """Hybrid net with torch nn.Linear layers as trainable graph nodes
    (reference example/torch/torch_module.py): trains to >=0.95 with
    the torch parameters updated by the framework's optimizer."""
    stats = _run_example("torch_module.py", "epochs=8, log=False")
    assert stats["acc"] >= 0.95, stats


def test_kaggle_ndsb1_example():
    """NDSB-1 full competition pipeline: class-folder tree -> stratified
    .lst split -> im2rec RecordIO at short-edge-48 -> DSB convnet via
    Module.fit -> test-set prediction -> Kaggle submission CSV with
    normalized probability rows."""
    stats = _run_example(
        "kaggle_ndsb1.py",
        "epochs=14, n_per_class=40, n_test=48, width_mult=0.5, log=False")
    assert stats["val_acc"] > 0.8, stats
    assert stats["test_acc"] > 0.7, stats
    assert stats["n_submission_rows"] == 48, stats


def test_benchmark_sweep_driver():
    """Multi-worker throughput sweep driver (reference benchmark.py): runs
    train_imagenet over 1 and 2 local workers through tools/launch.py
    --tag-output, attributes Speedometer lines per rank, writes the CSV.
    Scaling efficiency itself is not gated — the box has one core."""
    import csv as _csv
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "sweep.csv")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, "examples", "image_classification",
                          "benchmark.py"),
             "--networks", "mlp", "--worker-counts", "1,2",
             "--num-examples", "512", "--batch-size", "64",
             "--disp-batches", "2", "--output", out],
            capture_output=True, text=True, env=env, timeout=800,
            cwd=_REPO)
        assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
        with open(out) as f:
            rows = list(_csv.DictReader(f))
        assert [int(x["workers"]) for x in rows] == [1, 2]
        assert all(float(x["samples_per_sec"]) > 0 for x in rows)


def test_quantization_example():
    """PTQ workflow: symmetric int8 calibration, fake-quant path
    (reference quantize/dequantize parity) and the int8-MXU path agree
    to fp32 rounding, and int8 accuracy matches fp32."""
    stats = _run_example("quantization.py", "epochs=10, log=False")
    assert stats["path_delta"] < 1e-5, stats
    assert stats["int8_acc"] > stats["fp32_acc"] - 0.02, stats
    assert stats["fp32_acc"] > 0.9, stats


def test_quantization_conv_example():
    """Conv-path PTQ: _contrib_quantized_conv + quantized FC carry a
    small convnet to fp32-matching accuracy on the int8 MXU path."""
    stats = _run_example("quantization.py", "epochs=8, log=False",
                         func="run_conv")
    assert stats["fp32_acc"] > 0.9, stats
    assert stats["int8_acc"] > stats["fp32_acc"] - 0.05, stats


def test_train_pipeline_example():
    """Pipeline-parallel training walkthrough (capability the reference
    lacks): heterogeneous stage_idx-routed stages over a 4-way pipe mesh,
    1F1B + Adam + Factor schedule converge, and GPipe reproduces the same
    final accuracy on the identical seed."""
    stats = _run_example("train_pipeline.py",
                         "steps=60, log=False", func="train")
    assert stats["accuracy"] > 0.9, stats
    assert stats["loss"] < stats["first_loss"] / 10, stats
    gpipe = _run_example("train_pipeline.py",
                         "steps=60, schedule='gpipe', log=False",
                         func="train")
    assert gpipe["accuracy"] > 0.9, gpipe
    # fully seed-deterministic data/batches: schedule equivalence must
    # hold end-to-end, not just "both converge"
    assert abs(gpipe["accuracy"] - stats["accuracy"]) < 1e-6, (stats, gpipe)


def test_quantize_transformer_example():
    """PTQ on the transformer LM (the quantized FC path: FFN pairs +
    vocab head; attention stays float inside the fused op) — int8
    next-token accuracy within a point of fp32 on a trained tiny LM.
    Chip throughput rows come from the same example's --benchmark mode
    via tools/bench_table.py."""
    stats = _run_example("quantize_transformer.py",
                         "epochs=4, n_train=512, log=False")
    assert stats["fp32_acc"] > 0.9, stats
    assert stats["int8_acc"] >= stats["fp32_acc"] - 0.01, stats


def test_quantize_resnet_example():
    """Model-level PTQ (contrib.quantization): BN fold + symmetric
    calibration + int8 graph rewrite on a trained ResNet-8; int8 top-1
    must stay within a point of fp32 (chip-measured throughput rows come
    from the same example's --benchmark mode via tools/bench_table.py)."""
    stats = _run_example("quantize_resnet.py",
                         "epochs=4, n_train=512, log=False")
    assert stats["fp32_acc"] > 0.9, stats
    assert stats["int8_acc"] >= stats["fp32_acc"] - 0.01, stats
