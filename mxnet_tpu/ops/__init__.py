"""Operator registry + compute rules (the NNVM registry, XLA edition)."""

from .registry import OP_REGISTRY, Op, ParamSpec, get_op, list_ops, register

# importing these modules populates the registry
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import rnn_op  # noqa: F401
from . import attention  # noqa: F401
from . import paged_attention  # noqa: F401  (no op: the decode steps)
from . import gated_delta  # noqa: F401  (no op: the gated delta rule)
from . import state_space  # noqa: F401  (no op: the selective scan)
from . import contrib_op  # noqa: F401

# not an op: the generation lane's paged KV-cache allocator
from . import kv_cache  # noqa: F401
