"""The parity harness of the Pallas kernels (:mod:`.parity`): each
kernel's module registers it there against the reference body its
function falls back to.  ``docs/how_to/kernels.md`` says how a hot path
chooses between the two and how to add a kernel.
"""

from . import parity                               # noqa: F401
from .parity import register_parity, run_parity    # noqa: F401

__all__ = ["parity", "register_parity", "run_parity"]
