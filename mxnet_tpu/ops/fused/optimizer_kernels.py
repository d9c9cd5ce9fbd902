"""Fused optimizer-step kernel (trainer hot path b).

``sgd_mom_update``/``fused`` folds the whole momentum update — grad
rescale, clip, weight decay, momentum, parameter add — into one Pallas
pass over a row-block grid (:mod:`.rowgrid`): three reads, two writes
per element, no intermediate HLO buffers.
Op convention (dispatched through ``Op.apply``), ``bitwise`` class: the
kernel replays ``ops/tensor.py``'s ``_prep_grad`` + ``_sgd_mom_update``
spelling op for op.

The trainer-level "no param-tree round trips" fused step — one jitted
dispatch for the whole parameter tree instead of one op per parameter —
lives in ``parallel/trainer.py`` (``fused_sgd_mom_tree``); this module
is the per-op kernel the registry seam selects.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from ..registry import register_variant
from .parity import register_parity
from .rowgrid import row_call

__all__ = ["fused_sgd_mom_update"]


def _sgd_mom_kernel(w_ref, g_ref, m_ref, ow_ref, om_ref, *,
                    lr, wd, momentum, rescale, clip):
    # stock spelling: ops/tensor.py _prep_grad + _sgd_mom_update
    g = g_ref[...] * rescale
    if clip is not None and clip > 0:
        g = jnp.clip(g, -clip, clip)
    new_mom = momentum * m_ref[...] - lr * (g + wd * w_ref[...])
    ow_ref[...] = w_ref[...] + new_mom
    om_ref[...] = new_mom


def fused_sgd_mom_update(attrs, w, g, mom, block_rows=None):
    """Op-convention variant of ``sgd_mom_update`` → (weight, mom)."""
    kernel = functools.partial(
        _sgd_mom_kernel, lr=attrs["lr"], wd=attrs["wd"],
        momentum=attrs["momentum"], rescale=attrs["rescale_grad"],
        clip=attrs.get("clip_gradient"))
    return tuple(row_call(kernel, [w.dtype, mom.dtype], [w, g, mom],
                          whole_rows=False, block_rows=block_rows))


register_variant("sgd_mom_update", "fused", fused_sgd_mom_update,
                 backends=("tpu",), parity="bitwise")


def fused_sgd_mom_tree(attrs, params, grads, moms, ok=None):
    """Plain-convention variant: the trainer's whole-tree fused
    momentum step (``parallel/trainer.py fused_sgd_mom_tree``) — a
    hand-fused jitted composite, not a Pallas kernel, so it is eligible
    on every backend."""
    from ...parallel import trainer as _trainer

    return _trainer.fused_sgd_mom_tree(attrs, params, grads, moms, ok)


register_variant("sgd_mom_tree_update", "fused", fused_sgd_mom_tree,
                 backends=("cpu", "tpu"), parity="bitwise")


# ----------------------------------------------------------------------
# parity grid: ragged 1-D and 2-D params, clip on/off, wd on/off
# ----------------------------------------------------------------------


def _seed(case):
    import zlib

    return zlib.adler32(repr(case).encode())


def _sgd_mom_case(case):
    import numpy as np

    from .. import tensor as _tensor

    shape, lr, wd, momentum, rescale, clip, block_rows = case
    rng = np.random.default_rng(_seed(case))
    w = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    mom = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    attrs = {"lr": lr, "wd": wd, "momentum": momentum,
             "rescale_grad": rescale, "clip_gradient": clip}
    stock = functools.partial(_tensor._sgd_mom_update, attrs)
    fused = functools.partial(fused_sgd_mom_update, attrs,
                              block_rows=block_rows)
    return stock, fused, (w, g, mom)


register_parity(
    "sgd_mom_update", "fused", _sgd_mom_case,
    grid=(
        # last field is block_rows: None = one block, as derived
        ((1031,), 0.1, 0.0, 0.9, 1.0, -1.0, None),     # ragged 1-D
        ((17, 33), 0.01, 1e-4, 0.9, 1.0, -1.0, None),  # ragged 2-D, wd
        ((64, 8), 0.05, 1e-4, 0.99, 0.5, 0.25, None),  # rescale + clip
        ((3, 5, 7), 0.1, 0.0, 0.0, 1.0, 1.0, None),    # mom 0, clip on
        ((17, 33), 0.01, 1e-4, 0.9, 1.0, -1.0, 8),     # 2 blocks + 1 row
    ))


def _sgd_mom_tree_case(case):
    import numpy as np

    from ...parallel import trainer as _trainer

    guard, clip = case
    rng = np.random.default_rng(_seed(case))
    shapes = {"w1": (64,), "w2": (7, 9), "w3": (128, 3), "b": (5,)}

    def tree():
        return {n: jnp.asarray(rng.standard_normal(s), jnp.float32)
                for n, s in shapes.items()}

    params, grads, moms = tree(), tree(), tree()
    attrs = {"lr": 0.05, "wd": 1e-4, "momentum": 0.9,
             "rescale_grad": 1.0, "clip_gradient": clip}
    ok = None if guard is None else jnp.asarray(guard)
    stock = functools.partial(_trainer.sgd_mom_tree_stock, attrs)
    fused = functools.partial(_trainer.fused_sgd_mom_tree, attrs)
    return stock, fused, (params, grads, moms, ok)


register_parity(
    "sgd_mom_tree_update", "fused", _sgd_mom_tree_case,
    grid=(
        (None, -1.0),    # no guard
        (True, -1.0),    # guard passes: update applies
        (False, 0.5),    # guard trips: every leaf keeps old state
        (True, 0.25),    # guard + clip
    ))
