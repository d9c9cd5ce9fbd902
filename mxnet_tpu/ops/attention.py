"""Attention ops: Pallas flash attention + ring attention (context parallel).

The reference (2017-era MXNet) has **no** attention or sequence/context
parallelism — SURVEY.md §2.4 lists them as capability gaps the TPU build must
cover natively (§7.10).  Long sequences in the reference are handled only by
bucketing and model-parallel LSTM; here they are handled the TPU way:

* ``flash_attention`` — blockwise-softmax attention.  On TPU the forward is a
  Pallas kernel (online softmax, MXU matmuls) and the backward is one more
  (dq, dk and dv from one walk of the scores, reusing the forward's saved
  log-sum-exp), O(block) VMEM but for a head's dq; each walks, inside the
  program, only the tiles of the score square a causal row can see
  (``causal_walk``; gauge ``flash_attention_walked_share``).  Elsewhere a
  numerically identical jax fallback runs.
* ``ring_attention`` — context-parallel attention for sequences sharded along
  a mesh ``seq`` axis: K/V blocks rotate around the ring via ``ppermute``
  while each device's query block folds them into an online softmax.  Used
  inside ``shard_map``; communication rides ICI and overlaps with compute.
* ``stable_causal_attention`` / ``latent_prefill_attention`` — the
  prefills of the two served models; the decode steps over the paged
  cache are in :mod:`~mxnet_tpu.ops.paged_attention`.
* ``MultiHeadAttention`` / ``LayerNorm`` / ``MoE`` symbol ops so
  transformer models compose the same way the reference's CNN/RNN layers
  do.

A function with a Pallas kernel holds its whole choice of body in its
own definition — where the kernel runs
(:func:`~mxnet_tpu.ops.platform.pallas_mode`) and the shape is one it
takes, the kernel; otherwise the plain body — and registers the pair
with the parity harness (:mod:`~mxnet_tpu.ops.fused.parity`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import platform as _platform
from ..observability import metrics as _metrics
from .fused.parity import case_rng, register_parity
from .registry import ParamSpec as P, register

__all__ = ["flash_attention", "ring_attention", "stable_causal_attention",
           "latent_prefill_attention", "stable_scores", "stable_softmax",
           "band_tiles", "NEG_INF"]

NEG_INF = -1e30
# Mosaic tiles the last two block dims as (8 sublanes, 128 lanes).  Inside
# a kernel a per-row vector (m, l, lse, delta) is held across a 128-lane
# trailing dim (the layout jax's own TPU flash kernel uses); across
# pallas_call boundaries it is a [1, T] row, 128 times less to write
# and read, and a kernel turns it on its way in or out.
_LANE = 128


def _causal_mask(bq, bk, q_offset, k_offset, keys_first=False,
                 window=None):
    """Boolean [bq, bk] mask: query global pos >= key global pos
    (``keys_first``: the same, [bk, bq]); with a ``window``, also less
    than ``window`` past it: a row sees itself and the ``window - 1``
    keys before it (a band)."""
    shape, rows, cols = ((bk, bq), 1, 0) if keys_first else ((bq, bk), 0, 1)
    qi = q_offset + lax.broadcasted_iota(jnp.int32, shape, rows)
    ki = k_offset + lax.broadcasted_iota(jnp.int32, shape, cols)
    if window is None:
        return qi >= ki
    return (qi >= ki) & (qi - ki < window)


# ----------------------------------------------------------------------
# plain-jax reference path (also the backward's recompute)
# ----------------------------------------------------------------------


def _attention_fwd_ref(q, k, v, causal, sm_scale, return_lse=False,
                       window=None):
    """Exact softmax attention on [B, H, T, D] tensors, fp32 softmax."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = _causal_mask(q.shape[2], k.shape[2], 0, 0, window=window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / l
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    if return_lse:
        return out, (m + jnp.log(l))[..., 0]  # [B, H, T] fp32
    return out


# ----------------------------------------------------------------------
# shape-stable attention for the generation lane (prefill/decode parity)
# ----------------------------------------------------------------------
#
# The autoregressive lane promises *bitwise* parity between incremental
# decode through the paged cache and a full-sequence forward pass.  On
# XLA:CPU the dot-general behind ``einsum("bhqd,bhkd->bhqk", ...)`` picks
# different reduction strategies for different q-lengths, so the same
# row's score differs in the last bit between a T-row prefill and a
# 1-row decode step.  A multiply-and-reduce over the head dim is an
# independent per-(b,h,q,k) reduction and compiles to the same sequence
# of adds regardless of how many query rows ride along — that, plus an
# elementwise fp32 softmax and ``-1e30`` masking applied *before* the
# row max (masked lanes underflow to exact 0.0 in exp, contributing
# exact zeros to the p·v contraction), makes every op here stable across
# both the query-length axis and key-dim padding.  Prefill, full
# forward, and paged decode all route through these two helpers so the
# three paths cannot drift.  (jaxlib 0.9: the scores and the softmax are
# still stable; XLA:CPU now groups the adds of the p.v dot by the key
# count, so the gate — tests/test_generation.py — holds the three paths
# to the last few float32 bits, no longer to all of them.)


def stable_scores(q, k):
    """fp32 [B, H, T, K] scores via mul-reduce (bitwise stable in T/K)."""
    return jnp.sum(q.astype(jnp.float32)[:, :, :, None, :] *
                   k.astype(jnp.float32)[:, :, None, :, :], axis=-1)


def stable_softmax(s):
    """Row softmax of fp32 scores; masked lanes must already be -1e30."""
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def stable_causal_attention(q, k, v, sm_scale=None):
    """Exact causal attention on ``[B, H, T, D]``, float32 out: the
    generation lane's prefill / full-forward path.

    On a TPU a self-attention prefill (``q`` and ``k`` the same length)
    is :func:`_flash_dispatch`'s: the flash kernel from 1024 tokens,
    the exact einsum softmax below that (within float32 rounding of the
    body below; the parity harness's class ``tolerance``).  Elsewhere,
    and for a prefill continuation (``k`` longer than ``q``: the flash
    kernel's mask starts both clocks at zero), the shape-stable body:
    it materialises the score matrix, but its output bits do not depend
    on the query length, the property the CPU's paged-decode parity
    gate relies on.
    """
    if sm_scale is None:
        sm_scale = 1.0 / float(q.shape[-1]) ** 0.5
    mode = _platform.pallas_mode()
    if mode and q.shape[2] == k.shape[2]:
        return _flash_dispatch(q, k, v, True, float(sm_scale),
                               mode == "interpret").astype(jnp.float32)
    return _stable_causal_attention(q, k, v, sm_scale)


def _stable_causal_attention(q, k, v, sm_scale):
    s = stable_scores(q, k) * sm_scale
    mask = _causal_mask(q.shape[2], k.shape[2], k.shape[2] - q.shape[2], 0)
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = stable_softmax(s)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


# ----------------------------------------------------------------------
# latent attention (MLA): the expanded prefill (the absorbed paged
# decode is in ops/paged_attention.py)
# ----------------------------------------------------------------------


def latent_prefill_attention(q, k, v, sm_scale):
    """Causal attention of a latent-attention prefill in the expanded
    form: ``q``/``k`` ``[B, H, T, nope + rope]``, ``v`` ``[B, H, T,
    v_dim]`` narrower than the keys.  Never holds ``[H, T, T]`` scores
    where the flash kernel runs (a TPU, T >= 1024); below that, and
    elsewhere, the exact softmax."""
    return _flash_dispatch(q, k, v, True, float(sm_scale), False,
                           scope="latent_prefill_attention")


def gqa_prefill_attention(q, k, v, sm_scale, window=None):
    """Causal grouped-query attention of a prefill: ``q`` ``[B, Hq, T,
    D]``, ``k``/``v`` ``[B, Hkv, T, D]``, key-value head ``g`` serving
    the query heads ``g * Hq / Hkv`` and the ``Hq / Hkv - 1`` after it
    (each key-value head is repeated for its queries: 16 MB a pool at
    4096 tokens of 2 heads of 256).  Never holds ``[H, T, T]`` scores
    where the flash kernel runs (a TPU, T >= 1024); below that, and
    elsewhere, the exact softmax.

    ``window`` (static; a sliding-window layer): a row sees itself and
    the ``window - 1`` keys before it.  The kernel then walks the band
    alone (:func:`_key_walk`) under a scope of its own,
    ``gqa_window_prefill_attention``, whatever the prompt's length: a
    prompt no longer than the window has the whole triangle in its
    band, and is the causal kernel under that name."""
    per = q.shape[1] // k.shape[1]
    scope = "gqa_prefill_attention"
    if window is not None:
        scope = "gqa_window_prefill_attention"
        if window >= q.shape[2]:
            window = None
    return _flash_dispatch(q, jnp.repeat(k, per, axis=1),
                           jnp.repeat(v, per, axis=1), True,
                           float(sm_scale), False, scope=scope,
                           window=window)


# ----------------------------------------------------------------------
# the walk: which chunks of the other axis a run of rows (or keys) meets
# ----------------------------------------------------------------------
#
# A flash program keeps a block of each axis in VMEM and walks one of
# them in chunks *inside* the program, a run of rows (or keys) of the
# other at a time.  Under a causal mask the walk is bounded by the
# diagonal: a run of query rows meets the key chunks that begin at or
# before its last row, and only those the diagonal cuts are masked (the
# ones wholly below it take no iota, no compare and no select); a run of
# keys meets the query chunks from the diagonal down.  A walk is a tuple
# of ``(chunk, masked)`` tiles, known when the kernel is built: the
# programs of a grid make only a few different walks (on the diagonal,
# below it, at a ragged tail), each is unrolled in the kernel under a
# test of ``program_id``, and the whole schedule is the compiler's (a
# ``lax.fori_loop`` with its trip count from ``program_id`` walked the
# same tiles 1.6-2.3 times slower on the v5e: PERF.md §6, PR 38).
#
# A sliding window makes the triangle a band (row ``r`` sees the keys
# ``r - window + 1 .. r``): a run's walk then *begins* at the first
# chunk its first row sees, and the chunks the band's lower edge cuts
# take the mask as those on the diagonal do.  The forward alone walks a
# band; the backward refuses a window.


def _chunks_under(x, chunk, n, partly=False):
    """Of ``n`` chunks of ``chunk`` from 0: how many end at or below
    ``x`` (``partly``: begin below it)."""
    if partly:
        x = x + chunk - 1
    return min(max(x, 0) // chunk, n)


def _key_walk(row0, rows, chunk, n, causal, kv_len=None, window=None):
    """The walk of the query rows ``[row0, row0 + rows)`` over ``n`` key
    chunks, rows counted from the first key: the chunks every row sees
    in full, then those cut by the diagonal (or by a ragged tail at
    ``kv_len``), which take the mask.  Under a ``window`` the chunks no
    row of the run sees any more (they end at or before ``row0 -
    window``) are left out, and those the band's lower edge cuts (they
    begin at or before the last row's ``- window``) take the mask."""
    whole = end = n
    begin = cut = 0
    if window is not None:
        if not causal:
            raise ValueError("a window is a causal band")
        begin = _chunks_under(row0 - window + 1, chunk, n)
        cut = _chunks_under(row0 + rows - window, chunk, n, partly=True)
    if causal:
        whole = _chunks_under(row0 + 1, chunk, n)
        end = _chunks_under(row0 + rows, chunk, n, partly=True)
    if kv_len is not None:
        whole = min(whole, _chunks_under(kv_len, chunk, n))
        end = min(end, _chunks_under(kv_len, chunk, n, partly=True))
    return tuple((c, c >= whole or c < cut) for c in range(begin, end))


def _query_walk(col0, cols, chunk, n, causal):
    """The walk of the keys ``[col0, col0 + cols)`` over ``n`` query
    chunks, keys counted from the first query row: from the diagonal
    down, first the chunks it cuts, then those that see every key."""
    begin = whole = 0
    if causal:
        begin = _chunks_under(col0, chunk, n)
        whole = _chunks_under(col0 + cols - 1, chunk, n, partly=True)
    return tuple((c, c < whole) for c in range(begin, n))


def causal_walk(T, Tk, run, chunk, causal=True, keys_resident=False,
                window=None):
    """``(walked, masked, pairs)``: of the ``pairs`` (run, chunk) tiles
    of a ``T x Tk`` score square, how many a kernel computes and how
    many of those it masks.  Query runs over key chunks (the forward),
    or with ``keys_resident`` key runs over query chunks (the
    backward).  Counted with the walks the kernels unroll; ``window`` is
    the forward's band (the backward's walk has none)."""
    if keys_resident:
        if window is not None:
            raise NotImplementedError("the backward's walk has no window")
        n = -(-T // chunk)
        walks = [_query_walk(col0, run, chunk, n, causal)
                 for col0 in range(0, Tk, run)]
    else:
        n = -(-Tk // chunk)
        walks = [_key_walk(row0, run, chunk, n, causal,
                           Tk if Tk % chunk else None, window)
                 for row0 in range(0, T, run)]
    return (sum(len(w) for w in walks),
            sum(masked for w in walks for _, masked in w), len(walks) * n)


_M_WALKED = _metrics.gauge(
    "flash_attention_walked_share",
    "Share of the score square's (run, chunk) tiles the flash kernel "
    "built last computes (the rest lie above the causal diagonal), by "
    "kernel: fwd, bwd", ["kernel"])


_M_WINDOW_TILES = _metrics.gauge(
    "flash_attention_window_tiles",
    "(run, chunk) tiles of the banded (sliding-window) forward built "
    "last for a prompt of this length: those it walks, and those of "
    "them it masks (on the diagonal or on the band's lower edge)",
    ["tiles", "tokens"])


def _grid_walks(n_q, n_k, block_q, block_k, walks_of, causal, tailed=False):
    """The different walks the programs of an ``n_q x n_k`` grid make:
    ``[(walks, lead, last, test)]``.  ``walks_of(lead, last)`` gives a
    program's walks (one a run) from ``lead``, its first query row
    counted from its first key, and whether its key block is the last
    (which matters where the keys are ``tailed``: padded past a ragged
    end); ``test(qi, ki)`` is true in the programs that make ``walks``
    (python ints in, a python bool out).  Programs that walk nothing
    are in no group; a group the diagonal cuts has one ``lead``."""
    groups = {}
    for i in range(n_q):
        for j in range(n_k):
            lead = i * block_q - j * block_k
            last = j == n_k - 1 if tailed else None
            walks = walks_of(lead, last)
            if any(walks):
                cut = causal and any(masked for w in walks for _, masked in w)
                groups.setdefault((walks, lead if cut else None, last),
                                  []).append(lead)

    def test(leads, last):
        lo, hi = min(leads), max(leads)

        def at(qi, ki):
            lead = qi * block_q - ki * block_k
            # walks move one way with lead: a group is a range of it
            ok = lead == lo if lo == hi else (lead >= lo) & (lead <= hi)
            if last is None:
                return ok
            return ok & (ki == n_k - 1 if last else ki != n_k - 1)
        return at

    return [(walks, min(leads), last, test(leads, last))
            for (walks, _, last), leads in groups.items()]


def _lanes(x, n):
    """``[rows, n]`` of a per-row value held across ``_LANE`` lanes."""
    if n <= _LANE:
        return x[:, :n]
    if n % _LANE == 0:
        return jnp.concatenate([x] * (n // _LANE), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _row(x):
    """The ``[1, rows]`` row of a per-row value held across ``_LANE``
    lanes: the form it crosses a ``pallas_call`` boundary in."""
    rows = x.shape[0]
    if rows % _LANE:
        return x.T[:1, :]
    # lane j of rows 128 g + j is kept and the sublanes summed: a select
    # and an add a vreg where the transpose costs three times that
    eye = (lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 0)
           == lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 1))
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, x[g:g + _LANE, :], 0.0), axis=0,
                 keepdims=True) for g in range(0, rows, _LANE)], axis=1)


def _fit(T, block, *units):
    """``(block, units)`` for an axis of ``T`` cut by ``units``: a short
    axis is one block, of the whole units it fills or, under one unit,
    of itself; a unit that does not divide the block becomes the
    block."""
    most = max(units)
    block = max(8, T) if T <= most else min(block, -(-T // most) * most)
    return block, tuple(u if block % u == 0 else block for u in units)


# ----------------------------------------------------------------------
# Pallas TPU forward kernel
# ----------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                  sm_scale, causal, rows, chunk, n_q, n_k, walks, tail,
                  window=None):
    """One (batch*head, q-block, k-block) program of the online softmax.

    The k-block grid dimension is sequential ("arbitrary"); VMEM scratch
    (m/l/acc) carries the running max, denominator, and weighted sum across
    k steps, so VMEM holds only one q-block and one k/v-block at a time —
    sequence length is bounded by HBM, not the 16 MB VMEM.  Inside the
    program each run of ``rows`` query rows walks the key block in
    chunks of ``chunk`` (``walks``, of ``_grid_walks``): the scores in
    flight are ``[rows, chunk]``, never the whole block pair.  ``m`` and
    ``l`` are held across ``_LANE`` lanes, the form the log-sum-exp
    leaves in.

    ``rest`` is ``(lse_ref, m_scr, l_scr, acc_scr)`` when the caller asked
    for the log-sum-exp residual (the VJP forward) and just the three
    scratch refs otherwise."""
    import jax.experimental.pallas as pl

    if len(rest) == 4:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref = None
        m_scr, l_scr, acc_scr = rest
    block_k, dv = k_ref.shape[1], v_ref.shape[2]
    qi = pl.program_id(1) if n_q > 1 else 0
    ki = pl.program_id(2) if n_k > 1 else 0

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(run, q, at, keep):
        # matmuls take the INPUT dtype (bf16 rides the MXU at full rate;
        # an fp32 pre-cast would quarter it) and accumulate fp32; all
        # softmax math stays fp32
        v = v_ref[0, at, :]
        s = jax.lax.dot_general(
            q, k_ref[0, at, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [rows, chunk]
        if keep is not None:
            # a row's walk begins at a key it sees (column 0), so its
            # running max is finite before any chunk masks it whole,
            # and exp() sends the masked scores to exact 0.  (Under a
            # window a run's first chunk may hold no key its last rows
            # still see: what they add there, exp(0) a key, the first
            # chunk with a key they do see wipes, alpha being exp(-1e30
            # - m) = 0 exactly, and every row sees itself.)
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[run, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, chunk))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[run, :] = l_scr[run, :] * alpha + jnp.sum(
            p, axis=1, keepdims=True)
        acc_scr[run, :] = (
            acc_scr[run, :] * _lanes(alpha, dv) + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        m_scr[run, :] = m_new

    def walk(runs, lead, last):
        # the keys the block holds: ``tail`` in the last, padded past them
        held = tail if last else block_k
        for a, tiles in enumerate(runs):
            run = pl.ds(a * rows, rows)
            q = q_ref[0, run, :]
            for c, masked in tiles:
                keep = None
                if masked and causal:
                    keep = _causal_mask(rows, chunk, lead + a * rows,
                                        c * chunk, window=window)
                if (c + 1) * chunk > held:
                    # ragged tail: padded key columns contribute nothing
                    valid = c * chunk + lax.broadcasted_iota(
                        jnp.int32, (rows, chunk), 1) < held
                    keep = valid if keep is None else keep & valid
                fold(run, q, pl.ds(c * chunk, chunk), keep)

    for runs, lead, last, test in walks:
        pl.when(test(qi, ki))(functools.partial(walk, runs, lead, last))

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / _lanes(l, dv)).astype(o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp residual for the backward kernel (padded rows
            # get -inf + 0; they are sliced off before use)
            lse_ref[0] = _row(m_scr[...] + jnp.log(l))


def _flash_blocks(Tk, D):
    """``(block_q, block_k, rows, chunk)`` of the forward: the blocks a
    program keeps, and the run of query rows and the chunk of keys it
    walks them by.  From ``tools/flash_sweep.py`` on the attached v5e
    (PERF.md §6, PR 38, holds its tables): a head of 64 is quickest by
    tiles of 256 x 256 (at T = 1024 a head computes 10 of 16 and masks
    4), heads of 192/128 by tiles of 512 x 512 over key blocks of 2048:
    the wider the head, the more of a tile's time is the array's and
    the less a larger tile's spills cost; a head of 256 reads the same
    either way."""
    tile = 256 if D <= 64 else 512
    block_k = 2048
    if D > 192:
        # a 256-wide head: the blocks of q, k and v and the accumulator
        # stay well inside the v5e's 16 MiB of scoped VMEM at 1024
        block_k = 1024
    if Tk > block_k and Tk % block_k:
        # a ragged key tail pads to whole blocks: smaller ones pad less
        block_k = 1024
    return 1024, block_k, tile, tile


def band_tiles(T, D, window):
    """``(walked, masked, causal)``: the (run, chunk) tiles the forward
    walks over a prompt of ``T`` tokens with heads of ``D`` under a
    sliding ``window``, those of them it masks, and what a causal walk
    of the same prompt walks; by the tiles :func:`_flash_blocks` gives
    the kernel, whichever body runs."""
    block_q, block_k, rows, chunk = _flash_blocks(T, D)
    _, (rows,) = _fit(T, block_q, rows)
    _, (chunk,) = _fit(T, block_k, chunk)
    walked, masked, _ = causal_walk(
        T, T, rows, chunk, window=window if window < T else None)
    return walked, masked, causal_walk(T, T, rows, chunk)[0]


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "interpret", "return_lse", "blocks", "scope",
    "window"))
def _flash_fwd_pallas(q, k, v, causal, sm_scale, interpret=False,
                      return_lse=False, blocks=None, scope=None,
                      window=None):
    """Pallas forward on [B, H, T, D].  T is padded to block multiples.
    Jitted so that a model's layers share one trace and one lowering of
    the kernel; ``scope`` names it in a device trace.

    ``blocks`` is ``(block_q, block_k, rows, chunk)``; left out, as
    every caller but the sweep and the tests leaves it, ``_flash_blocks``
    chooses from what it can see (``Tk``, ``D``).  ``window``: the band
    of a sliding-window layer (self-attention: ``T == Tk``); its tiles
    are counted in the gauge under the kernel ``fwd_window``."""
    import jax.experimental.pallas as pl

    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    Tk, Dv = k.shape[2], v.shape[3]   # values may be narrower than keys
    block_q, block_k, rows, chunk = blocks or _flash_blocks(Tk, D)
    block_q, (rows,) = _fit(T, block_q, rows)
    block_k, (chunk,) = _fit(Tk, block_k, chunk)
    # ragged shapes: pad to block multiples.  Padded q rows are sliced off
    # the output; padded key columns are masked inside the kernel (kv_len).
    Tp = -(-T // block_q) * block_q
    Tkp = -(-Tk // block_k) * block_k
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    if Tkp != Tk:
        pad = ((0, 0), (0, 0), (0, Tkp - Tk), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    qf = q.reshape(B * H, Tp, D)
    kf = k.reshape(B * H, Tkp, D)
    vf = v.reshape(B * H, Tkp, Dv)
    n_q, n_k = Tp // block_q, Tkp // block_k
    walked, masked, pairs = causal_walk(T, Tk, rows, chunk, causal,
                                        window=window)
    if window is None:
        _M_WALKED.labels("fwd").set(walked / pairs)
    else:
        if return_lse or T != Tk:
            raise NotImplementedError(
                "a window is the forward's, over a prompt's own keys")
        _M_WALKED.labels("fwd_window").set(walked / pairs)
        _M_WINDOW_TILES.labels("walked", str(T)).set(walked)
        _M_WINDOW_TILES.labels("masked", str(T)).set(masked)

    tail = Tk - (n_k - 1) * block_k    # the keys the last block holds

    def walks_of(lead, last):
        return tuple(
            _key_walk(lead + row0, rows, chunk, block_k // chunk, causal,
                      tail if last else None, window)
            for row0 in range(0, block_q, rows))

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, rows=rows,
        chunk=chunk, n_q=n_q, n_k=n_k, tail=tail, window=window,
        walks=_grid_walks(n_q, n_k, block_q, block_k, walks_of, causal,
                          Tkp != Tk))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    def kv_block(b, i, j):
        # a key block wholly above the diagonal is not walked: name the
        # last one that is, which is already there, and nothing is copied
        if causal:
            j = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
        if window is not None:
            # nor is one wholly left of the band
            j = jnp.maximum(j, (i * block_q - window + 1) // block_k)
        return b, j, 0

    out_shape = [jax.ShapeDtypeStruct((B * H, Tp, Dv), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0))]
    if return_lse:
        out_shape.append(jax.ShapeDtypeStruct((B * H, 1, Tp), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)))
    with jax.named_scope(scope or "flash_attention"):
        res = pl.pallas_call(
            kernel,
            out_shape=out_shape,
            grid=(B * H, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), kv_block),
                pl.BlockSpec((1, block_k, Dv), kv_block),
            ],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANE), jnp.float32),
                pltpu.VMEM((block_q, _LANE), jnp.float32),
                pltpu.VMEM((block_q, Dv), jnp.float32),
            ],
            interpret=interpret,
            **kwargs,
        )(qf, kf, vf)
    out = res[0].reshape(B, H, Tp, Dv)[:, :, :T]
    if return_lse:
        return out, res[1].reshape(B, H, Tp)[:, :, :T]
    return out


# ----------------------------------------------------------------------
# flash_attention: public entry with custom VJP
# ----------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sm_scale, interpret):
    return _flash_dispatch(q, k, v, causal, sm_scale, interpret)


def _flash_dispatch(q, k, v, causal, sm_scale, interpret, scope=None,
                    window=None):
    """The forward's choice of body: ``interpret`` asks for the kernel
    whatever the length (under the interpreter off the chip); else on a
    TPU the kernel from 1024 tokens (the VJP forward's threshold too;
    past 8K the blocked kernel is the only option, exact attention
    OOMs), and the exact softmax below that and elsewhere.  ``scope``
    names the caller's attention in a device trace, whichever body;
    ``window`` makes the causal triangle a band, in either body (no
    caller with a backward pass gives one)."""
    on_chip = _platform.pallas_mode() == "chip"
    if interpret:
        return _flash_fwd_pallas(q, k, v, causal, sm_scale,
                                 interpret=not on_chip, scope=scope,
                                 window=window)
    if on_chip and (q.shape[2] >= 1024 or k.shape[2] >= 1024):
        return _flash_fwd_pallas(q, k, v, causal, sm_scale, scope=scope,
                                 window=window)
    with jax.named_scope(scope or "flash_attention"):
        return _attention_fwd_ref(q, k, v, causal, sm_scale, window=window)


def _flash_fwd_vjp(q, k, v, causal, sm_scale, interpret):
    """Forward for the VJP: same dispatch as the primal, but every path
    also emits the per-row log-sum-exp so the backward never has to
    re-derive the softmax statistics."""
    on_chip = _platform.pallas_mode() == "chip"
    if interpret:
        out, lse = _flash_fwd_pallas(q, k, v, causal, sm_scale,
                                     interpret=not on_chip,
                                     return_lse=True)
    elif on_chip and (q.shape[2] >= 1024 or k.shape[2] >= 1024):
        # same T>=1024 crossover as the primal: the Pallas bwd kernel
        # consumes the kernel's lse directly, and skipping the [T, T]
        # XLA softmax materialization pays off
        out, lse = _flash_fwd_pallas(q, k, v, causal, sm_scale,
                                     return_lse=True)
    else:
        out, lse = _attention_fwd_ref(q, k, v, causal, sm_scale,
                                      return_lse=True)
    return out, (q, k, v, out, lse)


# ----------------------------------------------------------------------
# Pallas TPU backward kernel (dq, dk and dv from one pass)
# ----------------------------------------------------------------------


def _bwd_p_ds(q, do, lse, delta, k, v, cut_at, *, sm_scale):
    """The backward's tile math for one (keys, query rows) tile: the
    attention weights ``p`` and score gradients ``ds``, both ``[keys,
    rows]``: what dK's and dV's products multiply from the left and
    dQ's from the right, so that none of the three has to turn a tile.
    ``lse`` (+inf on padded q rows) and ``delta`` are ``[1, rows]``
    rows.  ``cut_at`` is the tile's ``(first row, first key)`` where the
    diagonal cuts it, and None where every row sees every key.  (A
    ragged key tail needs no mask here: the padded rows of ``k`` and
    ``v`` are zeros, so they add nothing to ``dq``, and their own
    ``dk``/``dv`` rows are sliced off.)"""
    # matmul operands stay in the input dtype (bf16 at full MXU rate),
    # accumulating fp32; softmax statistics math is fp32 throughout
    s = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    p = jnp.exp(s - lse)
    if cut_at is not None:
        p = jnp.where(_causal_mask(q.shape[0], k.shape[0], *cut_at,
                                   keys_first=True), p, 0.0)
    dp = jax.lax.dot_general(
        v, do, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * sm_scale
    return p, ds


def _flash_bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                      dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                      sm_scale, cols, chunk, n_q, n_k, walks):
    """One (batch*head, k-block, q-block) program of the backward: both
    block axes are sequential.  VMEM scratch accumulates dk/dv for the
    resident k-block while q/do/lse/delta blocks stream past (lse and
    delta as ``[1, block_q]`` rows), and dq for every query row of the
    (batch*head) across all of its programs: zeroed at its first, rounded
    into ``dq_ref`` (the whole head's block) at its last.  Inside, each
    run of ``cols`` keys walks the query block in chunks of ``chunk``
    from the diagonal down (``walks``, of ``_grid_walks``), the scores
    held keys first; a tile's ``p`` and ``ds`` are made once and feed all
    three gradients.  dq is kept turned, ``[D, T]``: its product is
    ``k^T ds``, the tile as it lies and the run's keys turned once a
    run (``ds^T k`` turns a tile a product: 0.13 ms of a 0.74 ms call
    at T = 1024, PERF.md §6, PR 46), and the head's sum is turned back
    once, on its way out."""
    import jax.experimental.pallas as pl

    block_q = q_ref.shape[1]
    kj = pl.program_id(1) if n_k > 1 else 0
    qi = pl.program_id(2) if n_q > 1 else 0

    @pl.when((kj == 0) & (qi == 0))
    def _init_head():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def walk(runs, lead):
        for b, tiles in enumerate(runs):
            if not tiles:
                continue
            run = pl.ds(b * cols, cols)
            k = k_ref[0, run, :]
            v = v_ref[0, run, :]
            kt = k.T
            for c, masked in tiles:
                at = pl.ds(c * chunk, chunk)
                q = q_ref[0, at, :]
                do = do_ref[0, at, :]
                p, ds = _bwd_p_ds(
                    q, do, lse_ref[0, :, at], delta_ref[0, :, at], k, v,
                    (lead + c * chunk, b * cols) if masked else None,
                    sm_scale=sm_scale)                    # [cols, chunk]
                ds = ds.astype(q.dtype)
                dv_scr[run, :] += jnp.dot(
                    p.astype(do.dtype), do,
                    preferred_element_type=jnp.float32)
                dk_scr[run, :] += jnp.dot(
                    ds, q, preferred_element_type=jnp.float32)
                # the chunk's rows in the head: where dq's scratch has them
                rows = c * chunk
                if n_q > 1:
                    rows = pl.multiple_of(qi * block_q + rows, chunk)
                dq_scr[:, pl.ds(rows, chunk)] += jnp.dot(
                    kt, ds, preferred_element_type=jnp.float32)

    for runs, lead, _, test in walks:
        pl.when(test(qi, kj))(functools.partial(walk, runs, lead))

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when((kj == n_k - 1) & (qi == n_q - 1))
    def _finish_head():
        # a block of rows at a time: the turned block is a temporary
        for rows in range(0, n_q * block_q, block_q):
            dq_ref[0, rows:rows + block_q, :] = dq_scr[
                :, rows:rows + block_q].T.astype(dq_ref.dtype)


# ``(block_q, block_k, (query rows, keys))`` of the backward: the blocks a
# program keeps and the tile it walks them by, runs of that many keys
# over chunks of that many query rows: 256 x 256, the quickest of the
# sweep at a head of 64, the one width a cell trains at (PERF.md §6,
# PR 46; wider heads are not measured)
_BWD_BLOCKS = (1024, 1024, (256, 256))
# what a kernel may take of VMEM unasked on the v5e; the backward asks
# for more where the head's dq does not fit in it beside the blocks
_VMEM_UNASKED = 16 * 2 ** 20


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "interpret", "blocks"))
def _flash_bwd_pallas(q, k, v, o, lse, do, causal, sm_scale,
                      interpret=False, blocks=None):
    """One-pass Pallas flash backward on [B, H, T, D]: one kernel makes
    dq, dk and dv from one walk of the scores (five products a tile) —
    the backward twin of ``_flash_fwd_pallas``, jitted as it is (ends
    the plain-jax recompute that MFU-capped the transformer bench; the
    measured figure lives in the ``model_flops_utilization`` gauge /
    bench.py's ``mfu`` key, not here — see docs/PERF.md "MFU is
    measured, not quoted").  VMEM holds a block of each axis and, in
    float32 and turned, the dq of a whole head, ``[D, T]`` (256 KB at
    T = 1024, D = 64, 8 MB at T = 32768), beside its block on the way
    out: the call asks for the VMEM that takes (``vmem_limit_bytes``:
    more than a kernel gets unasked from T = 11 K or so) and compiles
    for a v5e up to T = 131072 at a head of 64; past the chip's VMEM
    the compiler refuses the kernel.

    ``blocks`` is ``(block_q, block_k, (query rows, keys))``, the last
    the tile the walk goes by: runs of that many keys over chunks of
    that many query rows.  Left out, as every caller but the sweep and
    the tests leaves it, it is ``_BWD_BLOCKS``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    Tk, Dv = k.shape[2], v.shape[3]
    bq, bk, tile = blocks or _BWD_BLOCKS
    bq, (chunk,) = _fit(T, bq, tile[0])
    bk, (cols,) = _fit(Tk, bk, tile[1])
    Tp = -(-T // bq) * bq
    Tkp = -(-Tk // bk) * bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if Tp != T:
        pad3 = ((0, 0), (0, 0), (0, Tp - T), (0, 0))
        q = jnp.pad(q, pad3)
        do = jnp.pad(do, pad3)
        # +inf lse on padded q rows makes p = exp(s - inf) = 0 there, so
        # the pads contribute nothing to dk/dv and their dq rows (sliced
        # off below) stay zero
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, Tp - T)),
                      constant_values=jnp.inf)
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, Tp - T)))
    if Tkp != Tk:
        pad3 = ((0, 0), (0, 0), (0, Tkp - Tk), (0, 0))
        k = jnp.pad(k, pad3)
        v = jnp.pad(v, pad3)
    BH = B * H
    qf = q.reshape(BH, Tp, D)
    dof = do.reshape(BH, Tp, Dv)
    kf = k.reshape(BH, Tkp, D)
    vf = v.reshape(BH, Tkp, Dv)
    # per-row vectors cross as [BH, 1, Tp] rows: what the kernel, whose
    # scores lie keys first, takes as they are
    lsef, deltaf = lse.reshape(BH, 1, Tp), delta.reshape(BH, 1, Tp)
    n_q = Tp // bq
    n_k = Tkp // bk
    walked, _, pairs = causal_walk(T, Tk, cols, chunk, causal, True)
    _M_WALKED.labels("bwd").set(walked / pairs)

    kwargs = {}
    if not interpret:
        # the blocks and dq's block twice (the pipeline's two buffers;
        # a row of VMEM is whole lanes, whatever the head's width), the
        # three accumulators once, and room for the tiles in flight
        def lanes(width):
            return -(-width // _LANE) * _LANE

        held = lanes(D) + lanes(Dv)
        vmem = (2 * q.dtype.itemsize * (Tp * lanes(D) + (bq + 2 * bk) * held)
                + 16 * bq + 4 * (Tp * D + bk * held) + 4 * 2 ** 20)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(vmem, _VMEM_UNASKED))

    def key_runs(lead, _):
        return tuple(_query_walk(col0 - lead, cols, chunk, bq // chunk,
                                 causal) for col0 in range(0, bk, cols))

    # blocks wholly above the diagonal are not walked: their index names
    # the nearest block that is, and nothing is copied for them
    def q_block(b, j, i):
        return b, jnp.maximum(i, j * bk // bq) if causal else i, 0

    def q_row(b, j, i):
        return b, 0, q_block(b, j, i)[1]

    def k_block(b, j, i):
        return b, j, 0

    kernel = functools.partial(
        _flash_bwd_kernel, sm_scale=sm_scale, cols=cols, chunk=chunk,
        n_q=n_q, n_k=n_k,
        walks=_grid_walks(n_q, n_k, bq, bk, key_runs, causal))
    dq, dk, dv = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((BH, Tp, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, Tkp, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tkp, Dv), v.dtype)],
        grid=(BH, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_block),                        # q
            pl.BlockSpec((1, bq, Dv), q_block),                       # do
            pl.BlockSpec((1, 1, bq), q_row),                          # lse
            pl.BlockSpec((1, 1, bq), q_row),                          # delta
            pl.BlockSpec((1, bk, D), k_block),                        # k
            pl.BlockSpec((1, bk, Dv), k_block),                       # v
        ],
        out_specs=[pl.BlockSpec((1, Tp, D), lambda b, j, i: (b, 0, 0)),
                   pl.BlockSpec((1, bk, D), k_block),
                   pl.BlockSpec((1, bk, Dv), k_block)],
        scratch_shapes=[pltpu.VMEM((D, Tp), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, Dv), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(qf, dof, lsef, deltaf, kf, vf)

    dq = dq.reshape(B, H, Tp, D)[:, :, :T]
    dk = dk.reshape(B, H, Tkp, D)[:, :, :Tk]
    dv = dv.reshape(B, H, Tkp, Dv)[:, :, :Tk]
    return dq, dk, dv


_BWD_BLOCK_K = 512


def _flash_bwd_scan(q, k, v, o, lse, do, causal, sm_scale):
    """Plain-jax blockwise backward (CPU fallback): one scan over K blocks
    reusing the saved lse — never materializes the [T, T] matrix."""
    B, H, T, D = q.shape
    Tk = k.shape[2]
    bk = min(_BWD_BLOCK_K, Tk)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    if Tk % bk:
        bk = Tk  # ragged small sequence: single block

    n_k = Tk // bk
    kb = k.astype(jnp.float32).reshape(B, H, n_k, bk, D).transpose(2, 0, 1, 3, 4)
    vb = v.astype(jnp.float32).reshape(B, H, n_k, bk, D).transpose(2, 0, 1, 3, 4)
    k_offs = jnp.arange(n_k) * bk
    qi = lax.broadcasted_iota(jnp.int32, (T, bk), 0)
    ki_local = lax.broadcasted_iota(jnp.int32, (T, bk), 1)

    def scores(k_blk, k_off):
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk,
                       preferred_element_type=jnp.float32) * sm_scale
        if causal:
            mask = (qi >= k_off + ki_local)[None, None]
            s = jnp.where(mask, s, NEG_INF)
        return s

    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)  # [B,H,T]

    # accumulate dq; emit dk/dv per block
    def grad_step(dq, xs):
        k_blk, v_blk, k_off = xs
        s = scores(k_blk, k_off)
        p = jnp.exp(s - lse[..., None])
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, v_blk)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk) * sm_scale
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * sm_scale
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, H, T, D), jnp.float32)
    dq, (dkb, dvb) = lax.scan(grad_step, dq0, (kb, vb, k_offs))
    dk = dkb.transpose(1, 2, 0, 3, 4).reshape(B, H, Tk, D)
    dv = dvb.transpose(1, 2, 0, 3, 4).reshape(B, H, Tk, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_bwd_vjp(causal, sm_scale, interpret, res, do):
    """Backward dispatch: the one-pass Pallas kernel on TPU (and under
    ``interpret=True`` for CPU testing); plain-jax blockwise scan
    elsewhere."""
    q, k, v, o, lse = res
    on_chip = _platform.pallas_mode() == "chip"
    if interpret:
        return _flash_bwd_pallas(q, k, v, o, lse, do, causal, sm_scale,
                                 interpret=not on_chip)
    if on_chip:
        return _flash_bwd_pallas(q, k, v, o, lse, do, causal, sm_scale)
    return _flash_bwd_scan(q, k, v, o, lse, do, causal, sm_scale)


_flash.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)


def flash_attention(q, k, v, causal=False, sm_scale=None, interpret=False):
    """Softmax attention over [B, H, T, D] tensors.

    On TPU both directions run as Pallas flash kernels (O(T) memory): the
    online-softmax forward plus one backward pass that makes dq, dk and dv
    from the forward's log-sum-exp.  ``interpret=True`` forces the Pallas kernels in
    interpreter mode (CPU testing).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    return _flash(q, k, v, bool(causal), float(sm_scale), bool(interpret))


def _prefill_case(case):
    dtype, b, h, t, d = case
    rng = case_rng(case)
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
               .astype(dtype) for _ in range(3))
    scale = 1.0 / float(d) ** 0.5

    def kernel(q, k, v):
        return _flash_fwd_pallas(
            q, k, v, True, scale,
            interpret=_platform.pallas_mode() != "chip").astype(jnp.float32)

    # low-precision inputs dominate the error even though both bodies
    # emit fp32 — class the tolerance by the input dtype
    tol = (2e-2, 2e-2) if dtype == "bfloat16" else None
    return (functools.partial(_stable_causal_attention, sm_scale=scale),
            kernel, (q, k, v), tol)


# the flash forward under stable_causal_attention's contract (the
# generation lane's prefill), against the shape-stable body
register_parity(
    "flash_prefill_attention", _prefill_case, parity="tolerance",
    grid=(
        ("float32", 1, 2, 64, 16),
        ("float32", 2, 4, 128, 32),
        ("float32", 1, 2, 67, 16),       # ragged T (block tail)
        ("float32", 2, 2, 200, 8),       # ragged T, narrow head
        ("bfloat16", 1, 2, 128, 32),
    ))


# ----------------------------------------------------------------------
# ring attention (context parallel, inside shard_map)
# ----------------------------------------------------------------------


def ring_attention(q, k, v, axis_name, causal=False, sm_scale=None):
    """Blockwise ring attention for use **inside** ``shard_map``.

    Each device holds the local sequence shard ``q/k/v: [B, H, T_local, D]``
    of a sequence sharded along mesh axis ``axis_name``.  K/V rotate around
    the ring with ``lax.ppermute`` while the local queries fold each visiting
    block into an online softmax — the all-gather-free long-context pattern
    (PAPERS.md ring-attention family).  Differentiable (pure jax + scan).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, H, Tl, D = q.shape
    qf = q.astype(jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        o, m, l, kc, vc = carry
        # kc originated on device (my - s) mod n
        src = (my - s) % n
        sc = jnp.einsum("bhqd,bhkd->bhqk", qf, kc.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qi = my * Tl + lax.broadcasted_iota(jnp.int32, (Tl, Tl), 0)
            ki = src * Tl + lax.broadcasted_iota(jnp.int32, (Tl, Tl), 1)
            mask = (qi >= ki)[None, None]
            sc = jnp.where(mask, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
        o_new = o * alpha[..., None] + pv
        k_next = lax.ppermute(kc, axis_name, perm)
        v_next = lax.ppermute(vc, axis_name, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    # derive the initial carry from q so it inherits q's varying-manual-axes
    # type (newer jax rejects scan carries whose vma set changes)
    o0 = qf * 0.0
    m0 = qf[..., 0] * 0.0 + NEG_INF
    l0 = qf[..., 0] * 0.0
    (o, m, l, _, _), _ = lax.scan(
        jax.checkpoint(step), (o0, m0, l0, k, v), jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l[..., None]).astype(q.dtype)


# ----------------------------------------------------------------------
# symbol ops: LayerNorm, MultiHeadAttention
# ----------------------------------------------------------------------


@register(
    "LayerNorm",
    arg_names=["data", "gamma", "beta"],
    params={"axis": P("int", -1), "eps": P("float", 1e-5)},
)
def _layer_norm(attrs, data, gamma, beta):
    """Layer normalization (absent in the 2017 reference; required by the
    transformer capability layer)."""
    axis = attrs["axis"]
    x = data.astype(jnp.float32)
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + attrs["eps"])
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    return (y * gamma.reshape(shape).astype(jnp.float32)
            + beta.reshape(shape).astype(jnp.float32)).astype(data.dtype)


def _mha_input_names(attrs):
    names = ["data", "qkv_weight", "out_weight"]
    if not attrs.get("no_bias", True):
        names += ["qkv_bias", "out_bias"]
    return names


@register(
    "MultiHeadAttention",
    aliases=["_contrib_MultiHeadAttention"],
    arg_names=["data", "qkv_weight", "out_weight"],
    input_names_fn=_mha_input_names,
    params={
        "num_heads": P("int", required=True),
        "causal": P("bool", False),
        "no_bias": P("bool", True),
        # mesh axis for context parallelism; '' disables
        "context_parallel_axis": P("str", ""),
        "interpret": P("bool", False),
    },
    mesh_aware=True,
)
def _multi_head_attention(attrs, data, qkv_weight, out_weight,
                          qkv_bias=None, out_bias=None):
    """Self-attention layer on [B, T, C]: fused QKV projection → flash or
    ring attention → output projection.

    When ``context_parallel_axis`` names an axis of the active default mesh
    (``mx.parallel.set_default_mesh``), attention runs as ring attention
    under ``shard_map`` with the sequence dimension sharded along that axis —
    the long-context path the reference lacks (SURVEY.md §5 'Long-context').
    """
    B, T, C = data.shape
    H = attrs["num_heads"]
    D = C // H
    # mixed precision: fp32 master weights cast to the activation dtype
    # (bf16 einsums accumulate fp32 on the MXU; fp16 projections compute in
    # fp32 and cast back — the FC note in ops/nn.py)
    out_dtype = data.dtype
    if data.dtype == jnp.float16:
        data = data.astype(jnp.float32)
    qkv_weight = qkv_weight.astype(data.dtype)
    out_weight = out_weight.astype(data.dtype)
    qkv = jnp.einsum("btc,fc->btf", data, qkv_weight)
    if qkv_bias is not None:
        qkv = qkv + qkv_bias.astype(data.dtype)
    qkv = qkv.reshape(B, T, 3, H, D).transpose(2, 0, 3, 1, 4)  # [3,B,H,T,D]
    q, k, v = qkv[0], qkv[1], qkv[2]

    axis = attrs.get("context_parallel_axis") or ""
    mesh = _default_mesh()
    if axis and mesh is not None and axis in mesh.axis_names \
            and mesh.shape[axis] > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec

        # keep the batch sharded along the data axis too — otherwise every
        # data-parallel group would all-gather and redundantly compute the
        # full batch's attention
        batch_axis = None
        for cand in ("data", "batch"):
            if cand in mesh.axis_names and cand != axis \
                    and B % mesh.shape[cand] == 0:
                batch_axis = cand
                break
        spec = PartitionSpec(batch_axis, None, axis, None)
        fn = shard_map(
            functools.partial(ring_attention, axis_name=axis,
                              causal=attrs["causal"]),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        out = fn(q, k, v)
    else:
        attn = functools.partial(flash_attention, causal=attrs["causal"],
                                 interpret=attrs.get("interpret", False))
        if mesh is not None and mesh.size > 1:
            # GSPMD cannot partition a Mosaic kernel: each device runs
            # the kernel on its own batch rows and heads
            from jax import shard_map
            from jax.sharding import PartitionSpec

            def split(names, dim):
                for cand in names:
                    if cand in mesh.axis_names \
                            and dim % mesh.shape[cand] == 0:
                        return cand
                return None

            spec = PartitionSpec(split(("data", "batch"), B),
                                 split(("model",), H), None, None)
            attn = shard_map(attn, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)
        out = attn(q, k, v)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, C)
    out = jnp.einsum("btc,fc->btf", out, out_weight)
    if out_bias is not None:
        out = out + out_bias.astype(out.dtype)
    return out.astype(out_dtype)


def _default_mesh():
    from ..parallel import get_default_mesh

    return get_default_mesh()


# ----------------------------------------------------------------------
# MoELayer symbol op: expert-parallel FFN inside Symbol graphs
# ----------------------------------------------------------------------


@register(
    "MoELayer",
    aliases=["_contrib_MoELayer"],
    arg_names=["data", "gate_weight", "w1_weight", "w2_weight"],
    num_outputs=2,
    output_names=["output", "aux_loss"],
    params={
        "num_experts": P("int", required=True),
        "hidden_size": P("int", required=True),
        "capacity_factor": P("float", 2.0),
        "expert_axis": P("str", "expert"),
        "top_k": P("int", 1),
    },
    mesh_aware=True,
)
def _moe_layer(attrs, data, gate_weight, w1_weight, w2_weight):
    """Mixture-of-experts FFN as a graph node (capability-gap op — the
    reference has no MoE).  data (B, S, d); gate_weight (d, E);
    w1_weight (E, d, h); w2_weight (E, h, d).  Outputs the mixed tokens
    plus the load-balancing aux loss (add it to the objective via
    ``MakeLoss``).  When the ambient mesh has an ``expert`` axis
    (``ShardedTrainer`` sets it), GSPMD all-to-alls the expert buffers
    across it."""
    from ..parallel import get_default_mesh
    from ..parallel.moe import moe_ffn

    params = {"router": gate_weight, "w1": w1_weight, "w2": w2_weight}
    # moe_ffn itself checks the axis is present on the mesh
    out, aux_loss = moe_ffn(params, data,
                            capacity_factor=attrs["capacity_factor"],
                            expert_axis=attrs["expert_axis"],
                            mesh=get_default_mesh(),
                            top_k=attrs["top_k"])
    return out, aux_loss[None]
