"""mxnet_tpu — a TPU-native deep learning framework with the capabilities of
MXNet v0.9.x (NDArray+Symbol duality, Module/fit, KVStore, data iterators),
rebuilt on jax/XLA/pjit/Pallas.  See repo README.md and SURVEY.md.

Import as ``import mxnet_tpu as mx`` — the namespace mirrors the reference's
``python/mxnet/__init__.py``.
"""

# first and last statements: what an import of the package costs (JAX's
# own, where nothing imported it before), booked with the start-up scopes
import time as _time

_T_IMPORT = _time.perf_counter()

# Multi-process bootstrap MUST precede any XLA backend touch, so it runs
# before everything else when the launcher env is present (parity: the
# reference's MXInitPSEnv handshake with the dmlc tracker env,
# tools/launch.py → DMLC_PS_ROOT_URI; here tools/launch.py →
# MXNET_TPU_COORDINATOR and jax.distributed).
import os as _os

if _os.environ.get("MXNET_TPU_COORDINATOR"):
    import jax as _jax

    _jax.distributed.initialize(
        _os.environ["MXNET_TPU_COORDINATOR"],
        int(_os.environ.get("MXNET_TPU_NUM_PROCS", "1")),
        int(_os.environ.get("MXNET_TPU_PROC_ID", "0")))
    # flag for init_process_group that bootstrap already happened (it must
    # not re-initialize — a second call after backend touch is an error)
    _os.environ["_MXNET_TPU_DIST_READY"] = "1"

from . import base
from .base import MXNetError
from . import context
from .context import Context, cpu, gpu, tpu, current_context, num_tpus
from . import ops
from . import ndarray
from . import ndarray as nd
from . import name
from . import attribute
from .attribute import AttrScope
from . import symbol
from . import symbol as sym
from . import executor
from .executor import Executor
from . import random
from . import random as rnd
from . import io
from . import recordio
from . import initializer
from . import optimizer
from . import optimizer as opt
from . import metric
from . import lr_scheduler
from . import callback
from . import kvstore as kv
from . import kvstore
from . import model
from .model import FeedForward
from . import executor_manager
from . import module
from . import module as mod
from . import monitor
from . import monitor as mon
from .monitor import Monitor
from . import observability
from . import profiler
from . import visualization
from . import visualization as viz
from . import rnn
from . import image as img
from . import image
from . import operator
from .operator import CustomOp, CustomOpProp
from . import predict
from . import deploy
from . import serving
from . import kvstore_server
from . import engine
from . import chaos
from . import compile_cache
from . import rtc
from . import torch_bridge
from . import torch_bridge as th
from . import parallel
from . import stream
from . import deployd
from . import contrib
from . import models
from . import test_utils

__version__ = "0.1.0"

# populate mx.nd.* / mx.sym.* from the op registry (parity:
# _init_ndarray_module / _init_symbol_module)
ndarray._init_module()
symbol._init_module()

# re-export common symbol constructors at top level like the reference
from .symbol import Variable, Group  # noqa: E402

compile_cache.book("package.import", _time.perf_counter() - _T_IMPORT)
