"""Where JAX's persistent compilation cache lives.

A cold process recompiles every jitted step (ResNet-50 and a 12-layer
LM are minutes of compile on a chip); the persistent cache keeps the
executables across processes.  Its directory is part of the cache key,
so it must not move between runs: it is placed from outside through
``JAX_COMPILATION_CACHE_DIR`` or, failing that, at one fixed path
under the checkout.
"""

from __future__ import annotations

import os

__all__ = ["enable"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable():
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX reads the variable
    itself and nothing is set in code.  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored).  Call before the first
    compile: the entry points do (``chip_smoke.py``, ``bench.py``,
    ``tools/serve.py``, the examples' ``common/fit.py``)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
