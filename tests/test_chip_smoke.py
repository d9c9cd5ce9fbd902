"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script is the quickest proof that the trainer and the generation
server still start on the chip; this keeps its control flow honest where
there is none.  ``main(argv, sizes=TINY)`` is its test-only size
argument: the same phases and checks in seconds, on whatever platform
JAX finds.  The platform check still stands, so every rehearsal here
ends non-zero and none may print ``"ok": true`` — a CPU run must never
read as a chip result.  Each rehearsal is a child process, like the
driver's run: the persistent compilation
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says instead of into this
suite's process.
"""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, tmp_path, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               **(extra_env or {}))
    out = subprocess.run([sys.executable] + args, env=env, cwd=_REPO,
                         capture_output=True, text=True, timeout=300)
    rows = [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    return out, rows


def _rehearse(argv, tmp_path, extra_env=None):
    code = ("import sys, chip_smoke; "
            "sys.exit(chip_smoke.main(%r, sizes=chip_smoke.TINY))" % (argv,))
    out, rows = _run(["-c", code], tmp_path, extra_env)
    # not a chip: the run fails, whatever the phases did, and says so
    assert out.returncode == 1, out.stderr[-2000:]
    assert '"ok": true' not in out.stdout
    assert "platform: cpu" in out.stderr
    return out, rows


def _phase_rows(rows):
    return {r["phase"]: r for r in rows if "phase" in r}


def test_phases_device_lines_and_kernel_check_run(tmp_path):
    out, rows = _rehearse([], tmp_path)
    head = rows[0]
    assert head["device"]["platform"] == "cpu" and head["chips"] == 1
    # the cache is placed from outside and the program sets no other
    assert head["compile_cache_dir"] == str(tmp_path / "cache")
    phases = _phase_rows(rows)
    assert list(phases) == ["train_lm", "train_resnet", "serve_lm", "fit"]
    for name, row in phases.items():
        assert row["passed"], (name, row.get("error"), out.stderr[-2000:])
        assert row["device"] == head["device"]
        assert set(row["compile_cache"]) == {"requests", "hits", "misses"}
        assert row["native"]
    for name in ("train_lm", "train_resnet"):
        losses = phases[name]["losses"]
        assert losses[-1] < losses[0]
        assert phases[name]["step_s"] > 0 and "compile_s" in phases[name]
    serve = phases["serve_lm"]
    assert serve["requests"] == 4 and serve["recompiles_after_warmup"] == 0
    assert serve["first_token_exact"] == "4/4"
    assert serve["decode_logit_err"] <= serve["logit_atol"]
    assert phases["fit"]["accuracy"] > 0.95
    # which hot paths lower to their kernel is read at the end, after
    # every phase: none off the chip
    assert rows[-1] == {"kernels_chosen": {
        "flash_attention": False, "stable_causal_attention": False,
        "paged_decode_attention": False}}


def test_four_chip_option_runs_only_the_sharded_phase(tmp_path):
    out, rows = _rehearse(
        ["--chips", "4"], tmp_path,
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert rows[0]["chips"] == 4 and rows[0]["device"]["count"] == 4
    phases = _phase_rows(rows)
    assert list(phases) == ["sharded_lm"]
    row = phases["sharded_lm"]
    assert row["passed"], (row.get("error"), out.stderr[-2000:])
    assert row["mesh"] == {"data": 2, "model": 2}
    # parameters and the batch really lie on four devices, sliced
    assert row["params_spread"]["devices"] == 4
    assert row["params_spread"]["sliced"] > 0
    assert row["batch_spread"] == {"arrays": 2, "devices": 4, "sliced": 2}
    assert len(row["four_chips"]["losses"]) == 3
    assert len(row["one_chip"]["losses"]) == 3


def test_without_a_chip_nothing_runs_and_nothing_is_printed(tmp_path):
    """As the driver runs it (no size argument) where JAX finds no
    accelerator: a non-zero exit before any phase, no result on stdout."""
    out, rows = _run([os.path.join(_REPO, "chip_smoke.py")], tmp_path)
    assert out.returncode == 2
    assert out.stdout == "" and rows == []
    assert "no TPU" in out.stderr
