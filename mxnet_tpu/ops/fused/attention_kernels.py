"""Fused attention variants: flash prefill + block-table paged decode.

Two generation-lane hot paths from ISSUE 19:

* ``stable_causal_attention``/``fused`` — the prefill score matrix is
  the lane's compute floor (O(T^2) materialised fp32).  The variant
  reroutes self-attention prefill (q and k the same length) onto the
  existing Pallas flash kernel (``ops/attention.py``): online softmax,
  O(block) VMEM.  Flash reorders the reduction, so this variant is
  ``tolerance`` class — the generation lane keeps its bitwise
  prefill/decode contract by selecting it only where that contract is
  not in play (TPU serving, or explicit override).
* ``paged_decode_attention``/``fused`` — a Pallas kernel that gathers
  K/V pages through the block table with scalar-prefetch index maps
  (one page DMA per (sequence, page) grid step) instead of the stock
  XLA gather that materialises ``[B, max_blocks, blk, H, D]`` twice.
  The final grid step replays stock's exact fp32 score/softmax/PV
  spelling on the gathered pages, so the variant is ``bitwise`` — the
  PR-14 decode-parity contract survives kernel replacement.

Both run under ``interpret=True`` off-TPU, which is how the parity
harness pins them on CPU.  The prefill variant is eligible on ``"tpu"``
only (CPU interpret is an emulation, not a win); the paged-decode
kernel is eligible nowhere — the chip's compiler refuses it, see its
registration below.  ``MXNET_TPU_OPS_FUSED_OVERRIDE`` forces either
anywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import attention as _att
from ..registry import register_variant
from .parity import register_parity
from .rowgrid import interpret as _interpret

__all__ = ["fused_prefill_attention", "fused_paged_decode_attention"]


# ----------------------------------------------------------------------
# prefill: flash kernel behind the stable-attention signature
# ----------------------------------------------------------------------


def fused_prefill_attention(q, k, v, sm_scale=None):
    """Flash-kernel twin of :func:`~mxnet_tpu.ops.attention.
    stable_causal_attention` (fp32 out, ``[B, H, T, D]``).

    Prefill continuation (k longer than q) keeps stock's offset causal
    mask — the flash kernel's mask starts both clocks at zero, so that
    shape delegates rather than mis-masking.
    """
    if q.shape[2] != k.shape[2]:
        return _att._stable_causal_attention_stock(q, k, v,
                                                   sm_scale=sm_scale)
    if sm_scale is None:
        sm_scale = 1.0 / float(q.shape[-1]) ** 0.5
    out = _att.flash_attention(q, k, v, causal=True, sm_scale=sm_scale,
                               interpret=_interpret())
    return out.astype(jnp.float32)


register_variant("stable_causal_attention", "fused",
                 fused_prefill_attention, backends=("tpu",),
                 parity="tolerance")


# ----------------------------------------------------------------------
# paged decode: block-table gather as a scalar-prefetch Pallas kernel
# ----------------------------------------------------------------------


def _paged_decode_kernel(bt_ref, cl_ref, q_ref, ks_ref, vs_ref, clv_ref,
                         kp_ref, vp_ref, o_ref, k_scr, v_scr, *,
                         sm_scale, bsz, max_blocks, blk):
    """Grid ``(B, max_blocks)``: step ``(b, j)`` lands page
    ``block_tables[b, j]`` (already staged into VMEM by the
    scalar-prefetch index map) into the gather scratch; the last step
    scatters the current token at ``context_len - 1`` and replays
    stock's exact fp32 score/softmax/PV ops on the full gathered batch
    so the output bits match ``paged_decode_attention`` exactly."""
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    j = pl.program_id(1)
    k_scr[b, pl.ds(j * blk, blk)] = kp_ref[0]
    v_scr[b, pl.ds(j * blk, blk)] = vp_ref[0]

    @pl.when(j == max_blocks - 1)
    def _scatter_current():
        pos = cl_ref[b] - 1
        k_scr[b, pl.ds(pos, 1)] = ks_ref[b][None]
        v_scr[b, pl.ds(pos, 1)] = vs_ref[b][None]

    @pl.when(jnp.logical_and(b == bsz - 1, j == max_blocks - 1))
    def _attend():
        kmax = max_blocks * blk
        k = k_scr[...].transpose(0, 2, 1, 3)      # [B, H, Kmax, D]
        v = v_scr[...].transpose(0, 2, 1, 3)
        q = q_ref[...]
        cl = clv_ref[...][:, 0]
        # stock's exact spelling (ops/attention.py paged_decode_attention)
        s = _att._stable_scores(q[:, :, None, :], k) * sm_scale
        pos = lax.broadcasted_iota(jnp.int32, (1, 1, 1, kmax), 3)
        s = jnp.where(pos < cl[:, None, None, None], s, _att._NEG_INF)
        p = _att._stable_softmax(s)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
        o_ref[...] = out[:, :, 0, :]


def fused_paged_decode_attention(q, k_step, v_step, k_pages, v_pages,
                                 block_tables, context_lens,
                                 sm_scale=None):
    """Pallas twin of :func:`~mxnet_tpu.ops.attention.
    paged_decode_attention` — same signature, bitwise-equal output.

    The gather scratch holds ``[B, max_blocks * blk, H, D]`` per side,
    which bounds batch x context by VMEM.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if sm_scale is None:
        sm_scale = 1.0 / float(q.shape[-1]) ** 0.5
    bsz, max_blocks = block_tables.shape
    blk = k_pages.shape[1]
    heads, dim = k_pages.shape[2], k_pages.shape[3]
    kmax = max_blocks * blk
    block_tables = block_tables.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)
    cl_vec = context_lens.reshape(bsz, 1)
    kernel = functools.partial(
        _paged_decode_kernel, sm_scale=float(sm_scale), bsz=bsz,
        max_blocks=max_blocks, blk=blk)
    full = lambda b, j, bt, cl: (0,) * 3  # noqa: E731 - whole-array blocks
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # block_tables, context_lens
        grid=(bsz, max_blocks),
        in_specs=[
            pl.BlockSpec((bsz, heads, dim), full),          # q
            pl.BlockSpec((bsz, heads, dim), full),          # k_step
            pl.BlockSpec((bsz, heads, dim), full),          # v_step
            pl.BlockSpec((bsz, 1), lambda b, j, bt, cl: (0, 0)),
            # the page gather: the index map picks this step's page
            pl.BlockSpec((1, blk, heads, dim),
                         lambda b, j, bt, cl: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, blk, heads, dim),
                         lambda b, j, bt, cl: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bsz, heads, dim), full),
        scratch_shapes=[
            pltpu.VMEM((bsz, kmax, heads, dim), k_pages.dtype),
            pltpu.VMEM((bsz, kmax, heads, dim), v_pages.dtype),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bsz, heads, dim), jnp.float32),
        grid_spec=grid_spec,
        interpret=_interpret(),
    )(block_tables, context_lens, q, k_step, v_step, cl_vec, k_pages,
      v_pages)


# Eligible on no backend: the v5e compiler refuses this kernel at every
# shape (``tpu.matmul: Up to 1 batch dim supported`` for the batched
# score, and the ``[B, max_blocks * blk, H, D]`` x2 gather scratch cannot
# fit VMEM at LM width).  It stays registered so the interpret-mode
# parity grid keeps pinning the block-table gather until the decode
# kernel is redesigned (ROADMAP S1/D3); an override still forces it.
register_variant("paged_decode_attention", "fused",
                 fused_paged_decode_attention, backends=(),
                 parity="bitwise")


# ----------------------------------------------------------------------
# parity grids (ragged tails on purpose)
# ----------------------------------------------------------------------


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32) \
        .astype(dtype)


def _case_seed(case):
    import zlib

    return zlib.adler32(repr(case).encode())


def _prefill_case(case):
    import numpy as np

    dtype, b, h, t, d = case
    rng = np.random.default_rng(_case_seed(case))
    q = _rand(rng, (b, h, t, d), dtype)
    k = _rand(rng, (b, h, t, d), dtype)
    v = _rand(rng, (b, h, t, d), dtype)
    # low-precision inputs dominate the error even though both paths
    # emit fp32 — class the tolerance by the input dtype
    tol = (2e-2, 2e-2) if dtype == "bfloat16" else None
    return (_att._stable_causal_attention_stock, fused_prefill_attention,
            (q, k, v), tol)


register_parity(
    "stable_causal_attention", "fused", _prefill_case,
    grid=(
        ("float32", 1, 2, 64, 16),
        ("float32", 2, 4, 128, 32),
        ("float32", 1, 2, 67, 16),       # ragged T (block tail)
        ("float32", 2, 2, 200, 8),       # ragged T, narrow head
        ("bfloat16", 1, 2, 128, 32),
    ))


def _paged_case(case):
    import numpy as np

    dtype, h, d, blk, max_blocks, ctx = case
    bsz = len(ctx)
    rng = np.random.default_rng(_case_seed(case) + 1)
    num_blocks = bsz * max_blocks + 1
    k_pages = _rand(rng, (num_blocks, blk, h, d), dtype)
    v_pages = _rand(rng, (num_blocks, blk, h, d), dtype)
    # distinct live pages per sequence; table rows past the context
    # keep page 0 (the pad convention), whose garbage both paths must
    # mask off identically
    bt = np.zeros((bsz, max_blocks), np.int32)
    nxt = 1
    for i, c in enumerate(ctx):
        used = -(-int(c) // blk)
        for jj in range(used):
            bt[i, jj] = nxt
            nxt += 1
    q = _rand(rng, (bsz, h, d), dtype)
    k_step = _rand(rng, (bsz, h, d), dtype)
    v_step = _rand(rng, (bsz, h, d), dtype)
    args = (q, k_step, v_step, k_pages, v_pages, jnp.asarray(bt),
            jnp.asarray(list(ctx), dtype=jnp.int32))
    return (_att._paged_decode_attention_stock,
            fused_paged_decode_attention, args)


register_parity(
    "paged_decode_attention", "fused", _paged_case,
    grid=(
        ("float32", 2, 16, 8, 3, (5, 20)),       # ragged contexts
        ("float32", 4, 32, 16, 2, (1, 17, 32)),  # ctx=1 and full tail
        ("float32", 2, 8, 4, 4, (3, 16, 9)),
        ("bfloat16", 2, 64, 8, 2, (3, 9)),       # bf16 pool, fp32 math
    ))
