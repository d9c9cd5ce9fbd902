"""Fused layernorm / activation epilogues (transformer hot path c).

Three small Pallas kernels that fold the elementwise epilogues XLA
would otherwise schedule as separate HLOs:

* ``LayerNorm``/``fused`` — the registry op (op convention): fp32
  mean/var + ``lax.rsqrt`` + affine in one VMEM pass.  Minor-axis norm
  only; other ``axis`` values delegate to stock inside the variant.
* ``lm_layer_norm``/``fused`` — the LM's ``_lm_ln`` twin
  (``models/transformer.py``): same math spelled with ``jnp.sqrt`` on
  already-fp32 activations, because the generation lane's bitwise gate
  pins that exact spelling.
* ``lm_gelu_bias``/``fused`` — the FFN epilogue ``gelu(h + bias)``.

All three replay stock's op sequence exactly, so they are ``bitwise``
class; the parity harness holds them to byte equality on the CPU
interpret path.  Each runs over a row-block grid (:mod:`.rowgrid`): the
rows are independent, so tiling changes no bit, and a block is sized to
the chip's scoped VMEM rather than to the array
(``tests/test_chip_compile.py`` compiles them at the LM's widths).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax import nn as jnn

from ..registry import register_variant
from .parity import register_parity
from .rowgrid import row_call

__all__ = ["fused_layer_norm_op", "fused_lm_layer_norm",
           "fused_lm_gelu_bias"]

_LN_EPS = 1e-5   # transformer.py's _LN_EPS; asserted equal in parity


def _ln_op_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    # stock spelling: ops/attention.py _layer_norm (fp32 + lax.rsqrt)
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    o_ref[...] = (y * g_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def fused_layer_norm_op(attrs, data, gamma, beta, block_rows=None):
    """Op-convention variant of the ``LayerNorm`` registry op.

    Differentiable: the training graph reaches this op under
    ``jax.grad`` and ``pallas_call`` has no transpose rule, so the
    backward is stock's own VJP on the saved inputs."""
    from .. import attention as _att

    axis = attrs["axis"]
    if axis not in (-1, data.ndim - 1) or data.dtype == jnp.float16:
        # a non-minor axis is the registry op's generality, and the
        # chip has no fp16 vector loads (Mosaic: "Invalid vector type
        # for load"): both are stock's job
        return _att._layer_norm(attrs, data, gamma, beta)
    kernel = functools.partial(_ln_op_kernel, eps=attrs["eps"])
    stock = functools.partial(_att._layer_norm, attrs)

    def primal(data, gamma, beta):
        return row_call(kernel, [data.dtype], [data], [gamma, beta],
                        block_rows=block_rows)[0]

    ln = jax.custom_vjp(primal)
    ln.defvjp(lambda *args: (primal(*args), args),
              lambda args, g: jax.vjp(stock, *args)[1](g))
    return ln(data, gamma, beta)


register_variant("LayerNorm", "fused", fused_layer_norm_op,
                 backends=("tpu",), parity="bitwise")


def _lm_ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    # stock spelling: models/transformer.py _lm_ln (fp32 in, jnp.sqrt)
    x = x_ref[...]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    o_ref[...] = y * g_ref[...] + b_ref[...]


def fused_lm_layer_norm(x, gamma, beta, block_rows=None):
    """Plain-convention twin of ``transformer._lm_ln`` (fp32 LM path)."""
    kernel = functools.partial(_lm_ln_kernel, eps=_LN_EPS)
    return row_call(kernel, [x.dtype], [x], [gamma, beta],
                    block_rows=block_rows)[0]


register_variant("lm_layer_norm", "fused", fused_lm_layer_norm,
                 backends=("tpu",), parity="bitwise")


def _gelu_bias_kernel(h_ref, b_ref, o_ref):
    o_ref[...] = jnn.gelu(h_ref[...] + b_ref[...])


def fused_lm_gelu_bias(h, bias, block_rows=None):
    """FFN epilogue ``gelu(h + bias)`` in one pass (``_lm_ffn``)."""
    return row_call(_gelu_bias_kernel, [h.dtype], [h], [bias],
                    whole_rows=False, block_rows=block_rows)[0]


register_variant("lm_gelu_bias", "fused", fused_lm_gelu_bias,
                 backends=("tpu",), parity="bitwise")


# ----------------------------------------------------------------------
# parity grids
# ----------------------------------------------------------------------


def _seed(case):
    import zlib

    return zlib.adler32(repr(case).encode())


def _ln_op_case(case):
    import numpy as np

    from .. import attention as _att

    dtype, shape, block_rows = case
    rng = np.random.default_rng(_seed(case))
    c = shape[-1]
    data = jnp.asarray(rng.standard_normal(shape), jnp.float32) \
        .astype(dtype)
    gamma = jnp.asarray(rng.standard_normal((c,)), jnp.float32)
    beta = jnp.asarray(rng.standard_normal((c,)), jnp.float32)
    attrs = {"axis": -1, "eps": 1e-5}
    stock = functools.partial(_att._layer_norm, attrs)
    fused = functools.partial(fused_layer_norm_op, attrs,
                              block_rows=block_rows)
    return stock, fused, (data, gamma, beta)


register_parity(
    "LayerNorm", "fused", _ln_op_case,
    grid=(
        # (dtype, shape, block_rows): None = one block, as derived
        ("float32", (4, 7, 33), None),   # ragged minor dim
        ("float32", (2, 128), None),
        ("bfloat16", (3, 5, 64), None),
        ("float16", (2, 9, 17), None),
        ("float32", (4, 7, 33), 8),      # 28 rows: 3 blocks + ragged 4
        ("bfloat16", (5, 16, 64), 16),   # 80 rows: 5 whole blocks
    ))


def _lm_ln_case(case):
    import numpy as np

    def stock(x, gamma, beta):
        from ...models import transformer as _t

        return _t._lm_ln_stock(x, gamma, beta)

    shape, block_rows = case
    rng = np.random.default_rng(_seed(case))
    c = shape[-1]
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    gamma = jnp.asarray(rng.standard_normal((c,)), jnp.float32)
    beta = jnp.asarray(rng.standard_normal((c,)), jnp.float32)
    fused = functools.partial(fused_lm_layer_norm, block_rows=block_rows)
    return stock, fused, (x, gamma, beta)


register_parity(
    "lm_layer_norm", "fused", _lm_ln_case,
    grid=(((2, 16, 32), None), ((1, 1, 32), None), ((3, 21, 33), None),
          ((3, 21, 33), 8)))             # 63 rows: 7 blocks + ragged 7


def _gelu_case(case):
    import numpy as np

    def stock(h, bias):
        from ...models import transformer as _t

        return _t._lm_gelu_bias_stock(h, bias)

    shape, block_rows = case
    rng = np.random.default_rng(_seed(case))
    f = shape[-1]
    h = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((f,)), jnp.float32)
    fused = functools.partial(fused_lm_gelu_bias, block_rows=block_rows)
    return stock, fused, (h, bias)


register_parity(
    "lm_gelu_bias", "fused", _gelu_case,
    grid=(((2, 16, 128), None), ((1, 1, 64), None), ((3, 17, 65), None),
          ((3, 17, 65), 8)))             # 51 rows: 6 blocks + ragged 3
