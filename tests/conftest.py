"""Test configuration: run on a virtual 8-device CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (SURVEY.md §4: the
reference's 'multiple ctx on one box' strategy)."""

import json
import os
import subprocess
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# the suite is a CPU suite whether or not JAX_PLATFORMS=cpu was exported
jax.config.update("jax_platforms", "cpu")

import numpy as _np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1")
    config.addinivalue_line(
        "markers", "chaos: fault-injection test (seeded, deterministic)")


@pytest.fixture(autouse=True)
def _chaos_clean():
    """Programmatic chaos rules never leak across tests."""
    import mxnet_tpu.chaos as chaos

    chaos.clear()
    yield
    chaos.clear()


@pytest.fixture(autouse=True)
def _metrics_clean():
    """Metric values and trace spans never leak across tests.  reset()
    zeroes values but keeps families + pre-resolved handles wired, so
    module-level instrumentation (engine lanes, kvstore) stays live."""
    yield
    from mxnet_tpu import observability as obs

    obs.reset_metrics()
    obs.disable_tracing()
    obs.clear_spans()
    obs.clear_events()


@pytest.fixture(autouse=True)
def _seed():
    _np.random.seed(42)
    import mxnet_tpu as mx

    mx.random.seed(42)


@pytest.fixture(autouse=True, scope="module")
def _bound_compiler_state():
    """Drop jit caches between test modules to bound memory growth.

    NOTE: this alone did NOT stop the XLA:CPU backend-compiler segfault
    seen around the ~300th test when the heavy example gates compiled
    in-process — that needed true subprocess isolation (see
    ``_run_example``).  Kept as hygiene: it caps live-executable
    memory across the rest of the suite at a small recompile cost."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def topo():
    """A described TPU v5e 2x2, for the ``test_chip_compile*.py`` files'
    compiles; the persistent compilation cache is off around them (an
    entry written by such a compile cannot be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip("cannot describe a v5e topology here: %s" % exc)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the platform test (``ops.platform.pallas_mode``) answer as
    on the chip: every rule takes its TPU branch, real kernels and not
    interpret mode."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_example(name):
    """Import an examples/ script as a module (shared by the example-gate
    tests; registered in sys.modules so dataclass/pickle paths work)."""
    import importlib.util

    path = os.path.join(_REPO, "examples", name)
    spec = importlib.util.spec_from_file_location(
        "example_" + os.path.splitext(os.path.basename(name))[0], path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _run_example(name, call, timeout, func="run"):
    """Run ``examples/<name>``'s ``func(<call>)`` in a FRESH subprocess
    and return the stats it reports.

    Why a subprocess: one pytest process compiling every example's
    graphs on top of the rest of the suite eventually segfaults
    XLA:CPU's backend compiler (observed deterministically around the
    ~300th test; ``jax.clear_caches()`` does not help — the leak is in
    global compiler state).  Isolation also keeps the examples honest:
    each must work from a cold start, like a user run.

    ``timeout`` (seconds) is the gate's own budget, written beside it:
    about three times what the gate takes under the tier-1 command, at
    least 60 and never over 300 (``test_docs.py`` holds every gate to
    that), so a gate that hangs fails as itself and the rest of the
    suite still runs.  The gates live in the six
    ``test_example_gates_*.py`` files, three to five a file: tier-1
    (``-n 6 --dist loadfile``) hands a whole file to one worker, and a
    file takes 45-120 s of it, the longest gate 60-80 s (PR 44; they
    took 380-570 s a file while every eager optimizer step of every
    parameter was a compile of its own).
    """
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
