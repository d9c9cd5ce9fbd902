"""The one platform test behind every choice of kernel.

A hot-path function that has a Pallas kernel states its rule in its own
body: *the kernel runs here and the shape is one it takes -> the
kernel; otherwise -> the reference body* (``docs/how_to/kernels.md``).
"Runs here" is :func:`pallas_mode`, and nothing else in the package asks
which platform it is on to pick a kernel.
"""

import jax

__all__ = ["pallas_mode"]


def pallas_mode():
    """How this process runs a Pallas kernel: ``"chip"`` on a TPU (the
    kernel is compiled for it), ``None`` anywhere else (callers take
    their reference body).  It reads no environment variable and caches
    nothing, so a test steers it by patching: ``jax.default_backend``
    to compile a TPU program for a described chip, or this function to
    return ``"interpret"``, under which every rule picks its kernel and
    runs it with ``interpret=True`` off the chip."""
    return "chip" if jax.default_backend() == "tpu" else None
