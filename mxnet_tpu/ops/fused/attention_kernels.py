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
* ``paged_decode_attention``/``fused`` — the block-table walk of
  ``ops/attention.py`` (``_walk_pages``) under GPT-2's float32 key and
  value pools: one program a row reads the blocks that hold the row's
  live tokens, from the pool where it lies, and folds them chunk by
  chunk into an online softmax; the stock XLA body gathers, re-lays and
  scores every block of the table and masks afterwards (89% of the
  serving chip's busy time, PR 26's trace).  The online softmax
  reorders the sums, so the variant is ``tolerance`` class: on the
  chip the decode no longer holds the bitwise prefill/decode contract,
  which lives on the CPU's stock bodies (ROADMAP D2).

Both are eligible on ``"tpu"`` only and run under ``interpret=True``
off-TPU, which is how the parity harness pins them on CPU (CPU
interpret is an emulation, not a win).  ``MXNET_TPU_OPS_FUSED_OVERRIDE``
forces either anywhere.
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
# paged decode: the block-table walk over float32 key and value pools
# ----------------------------------------------------------------------


def fused_paged_decode_attention(q, k_step, v_step, k_pages, v_pages,
                                 block_tables, context_lens,
                                 sm_scale=None):
    """Pallas twin of :func:`~mxnet_tpu.ops.attention.
    paged_decode_attention` — same signature, float32 out — over the
    walk of :func:`~mxnet_tpu.ops.attention._walk_pages`: only the
    blocks that hold a row's live tokens are read, from the pool where
    it lies.

    A cached row is ``heads * dim`` values wide and a head owns ``dim``
    = 64 of them, half a lane tile, so nothing here is reshaped to
    ``[.., heads, dim]``.  The chunk's keys are multiplied by the row's
    query on the VPU, all heads at once, and a product with the 0/1
    selector ``[heads, heads * dim]`` sums each head's lanes; the same
    selector spreads a head's softmax weight over its lanes for p.v.
    The model is served in float32 and a default-precision product of
    float32 operands is a bfloat16 one on the chip, so the float32
    operand goes through the MXU as three bfloat16 terms that sum to
    it: the selector's entries are exact in bfloat16, so these are
    float32 sums and copies in half the passes of ``HIGHEST`` (which
    splits the selector too: 4.19 against 2.85 ms a step of 24 layers
    on a v5e, both 9e-7 from a float64 softmax).  On a TPU, pools whose
    pages are not whole tiles keep the stock body."""
    bsz, heads, dim = q.shape
    width = heads * dim
    flat = k_pages.shape[:2] + (width,)
    k_pages, v_pages = k_pages.reshape(flat), v_pages.reshape(flat)
    if not (_interpret() or _att._walk_tiles(k_pages, v_pages)):
        return _att._paged_decode_attention_stock(
            q, k_step, v_step, k_pages, v_pages, block_tables,
            context_lens, sm_scale=sm_scale)
    if sm_scale is None:
        sm_scale = 1.0 / float(dim) ** 0.5

    def rows(x):
        return x.astype(jnp.float32).reshape(bsz, 1, width)

    out = _kv_decode_walk(rows(q), rows(k_step), rows(v_step), k_pages,
                          v_pages, block_tables, context_lens, heads=heads,
                          scale=float(sm_scale), interpret=_interpret())
    return out.reshape(bsz, heads, dim)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "interpret"))
def _kv_decode_walk(q, k_step, v_step, k_pages, v_pages, block_tables,
                    context_lens, heads, scale, interpret):
    """``q`` / ``k_step`` / ``v_step`` as float32 rows ``[B, 1, W]``,
    the pools ``[num_blocks, block_size, W]``.  Jitted so that a
    model's layers share one trace and one lowering of the kernel: 24
    of them took a warm start 3.7 s longer, every one lowered anew."""
    from jax.experimental.pallas import tpu as pltpu

    width = q.shape[-1]
    dim = width // heads
    f32, bf16 = jnp.float32, jnp.bfloat16
    lane_head = lax.broadcasted_iota(jnp.int32, (heads, width), 1) // dim
    selector = (lane_head == lax.broadcasted_iota(
        jnp.int32, (heads, width), 0)).astype(bf16)

    def by_selector(x, sel, contract):
        # float32 ``x`` as three bfloat16 terms that sum to it, each
        # through the MXU against the selector, accumulated in float32
        hi = x.astype(bf16)
        rest = x - hi.astype(f32)
        mid = rest.astype(bf16)
        terms = (hi, mid, (rest - mid.astype(f32)).astype(bf16))
        return sum(lax.dot_general(t, sel, (contract, ((), ())),
                                   preferred_element_type=f32)
                   for t in terms)

    def head_sums(x, sel):                  # [n, W] -> [n, H]
        return by_selector(x, sel, ((1,), (1,)))

    def over_lanes(x, sel):                 # [n, H] -> [n, W]
        return by_selector(x, sel, ((1,), (0,)))

    def init(row_refs, state):
        q_ref, k_ref, v_ref, sel_ref = row_refs
        m_ref, l_ref, acc_ref = state
        own = jnp.broadcast_to(k_ref[0] * q_ref[0], (8, width))
        m_ref[...] = head_sums(own, sel_ref[...])[:1] * scale
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = v_ref[0]

    def chunk(row_refs, held, state, live):
        sel = row_refs[3][...]
        m_ref, l_ref, acc_ref = state
        keys, values = held[0][...].astype(f32), held[1][...].astype(f32)
        tokens = keys.shape[0]
        s = head_sums(keys * row_refs[0][0], sel) * scale       # [T, H]
        if live is not None:
            at = lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)
            s = jnp.where(at < live, s, _att._NEG_INF)
            values = jnp.where(at < live, values, 0.0)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)                         # [1, H]
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
        m_ref[...] = m_new
        # one product spreads the weights and the rescale of what was
        # accumulated: the selector is loaded once for both
        spread = over_lanes(jnp.concatenate(
            [p, jnp.broadcast_to(alpha, (8, heads))], axis=0), sel)
        acc_ref[...] = spread[tokens:tokens + 1] * acc_ref[...] + jnp.sum(
            spread[:tokens] * values, axis=0, keepdims=True)

    def finish(row_refs, state, out_ref):
        _, l_ref, acc_ref = state
        sums = over_lanes(jnp.broadcast_to(l_ref[...], (8, heads)),
                          row_refs[3][...])[:1]
        out_ref[0] = acc_ref[...] / sums

    with jax.named_scope("paged_decode_attention"):
        return _att._walk_pages(
            init, chunk, finish, (q, k_step, v_step), (selector,),
            (k_pages, v_pages), block_tables, context_lens,
            jax.ShapeDtypeStruct(q.shape, f32),
            [pltpu.VMEM((1, heads), f32), pltpu.VMEM((1, heads), f32),
             pltpu.VMEM((1, width), f32)],
            interpret=interpret)


register_variant("paged_decode_attention", "fused",
                 fused_paged_decode_attention, backends=("tpu",),
                 parity="tolerance")


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
        # the served width (16 heads of 64, blocks of 16): no cached
        # token, a block less one, exactly a block, a block and one,
        # the whole table
        ("float32", 16, 64, 16, 4, (1, 16, 17, 18, 64)),
    ))
