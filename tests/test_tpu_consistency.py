"""Cross-backend consistency tier on the REAL chip (the reference's GPU
tier, ``tests/python/gpu/test_operator_gpu.py`` — SURVEY.md §4 row 3:
the same graphs cross-checked between backends on actual hardware, not
just cpu-vs-cpu).  The sweep runs in ONE child WITHOUT the suite's CPU
pin: this process stays on the CPU backend, so it never holds the chip
the child needs, and the child itself says when it found no TPU.
"""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cpu_vs_tpu_consistency_sweep():
    env = dict(os.environ)
    # undo the suite's CPU pins so the child can reach the chip
    for k in ("JAX_PLATFORMS", "XLA_FLAGS"):
        env.pop(k, None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "tests", "tpu", "consistency_on_chip.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO)
    if "SKIP_NO_TPU" in r.stdout:
        pytest.skip("no TPU attached: %s" % r.stdout.strip())
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert "CONSISTENCY_OK" in r.stdout, r.stdout
