"""Test configuration: run on a virtual 8-device CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (SURVEY.md §4: the
reference's 'multiple ctx on one box' strategy)."""

import os

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
    test_examples_round3.py).  Kept as hygiene: it caps live-executable
    memory across the rest of the suite at a small recompile cost."""
    yield
    jax.clear_caches()


def load_example(name):
    """Import an examples/ script as a module (shared by the example-gate
    tests; registered in sys.modules so dataclass/pickle paths work)."""
    import importlib.util
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "examples", name)
    spec = importlib.util.spec_from_file_location(
        "example_" + os.path.splitext(os.path.basename(name))[0], path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod
