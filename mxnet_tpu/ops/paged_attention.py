"""Paged decode attention: one decode step read through a block table.

The models ``LMBackend`` serves decode over a paged cache
(:mod:`~mxnet_tpu.ops.kv_cache`): GPT-2 over float32 key and value
pools (:func:`paged_decode_attention`), the latent-attention model over
one pool of latent rows in the absorbed form
(:func:`latent_paged_decode_attention`), a grouped-query model over key
and value pools whose rows hold its few key-value heads
(:func:`gqa_paged_decode_attention`; two bodies, by whether a head is
whole lane tiles).  Each public function holds
its whole choice of body: where a Pallas kernel runs
(:func:`~mxnet_tpu.ops.platform.pallas_mode`) and a page of every pool
is whole tiles (:func:`_walk_tiles`), the block-table walk
(:func:`_walk_pages`) under the model's own arithmetic; otherwise the
XLA body, which gathers every block of the table and masks.  The XLA
body of GPT-2 is also the CPU's decode-parity contract
(``tests/test_generation.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import platform as _platform
from ..observability import metrics as _metrics
from .attention import NEG_INF, stable_scores, stable_softmax
from .fused.parity import case_rng, register_parity

__all__ = ["paged_decode_attention", "latent_paged_decode_attention",
           "gqa_paged_decode_attention"]

# Mosaic tiles the last two dims of a block as (8 sublanes, 128 lanes)
_LANE = 128


def paged_decode_attention(q, k_step, v_step, k_pages, v_pages,
                           block_tables, context_lens, sm_scale=None):
    """One decode step's attention, K/V gathered through the block table.

    - ``q`` / ``k_step`` / ``v_step``: ``[B, H, D]`` — this step's
      single query per sequence and its freshly projected K/V (the
      caller scatters them into the pool on the device, with
      ``PagedKVCache.write_tokens``, in a dispatch of its own behind
      this one: a step never writes the pool it reads, and a step
      dispatched again stores the same rows in the same slots).
    - ``k_pages`` / ``v_pages``: ``[num_blocks, block_size, H, D]`` —
      one layer's slice of the shared :class:`~mxnet_tpu.ops.kv_cache.
      PagedKVCache` pool (device-resident), as of before this step.
    - ``block_tables``: ``int32 [B, max_blocks]`` — per-sequence page
      lists, zero-padded (pad rows are masked off below).
    - ``context_lens``: ``int32 [B]`` — valid tokens per sequence,
      INCLUDING the current one (whose K/V arrives via ``k_step``).

    Returns ``[B, H, D]``.  The current token is scattered into the
    gathered keys at position ``context_len - 1`` so the valid keys form
    the same contiguous prefix a full-sequence forward sees — identical
    reduction order, and the padded-key masking keeps garbage in
    unwritten page tails away from the output bits.

    On a TPU, with pages that are whole tiles, the block-table walk
    (:func:`_kv_decode_pallas` over :func:`_walk_pages`) reads the
    row's live blocks only and folds them into an online softmax, equal
    to the XLA body within float32 rounding (the parity harness's class
    ``tolerance``).  The decode-parity contract with the prefill is the
    XLA body's, on the CPU.
    """
    heads, dim = q.shape[1:]
    if sm_scale is None:
        sm_scale = 1.0 / float(dim) ** 0.5
    # a cached row is H*D values wide and that is how the pool lies:
    # the reshape undoes the caller's
    flat = k_pages.shape[:2] + (heads * dim,)
    k_pages, v_pages = k_pages.reshape(flat), v_pages.reshape(flat)
    mode = _platform.pallas_mode()
    if mode and _walk_tiles(k_pages, v_pages):
        return _kv_decode_pallas(q, k_step, v_step, k_pages, v_pages,
                                 block_tables, context_lens,
                                 float(sm_scale), mode == "interpret")
    return _kv_decode_xla(q, k_step, v_step, k_pages, v_pages,
                          block_tables, context_lens, sm_scale)


def _kv_decode_xla(q, k_step, v_step, k_pages, v_pages, block_tables,
                   context_lens, sm_scale):
    bsz, max_blocks = block_tables.shape
    heads, dim = q.shape[1], q.shape[2]
    kmax = max_blocks * k_pages.shape[1]
    rows = jnp.arange(bsz)
    positions = context_lens - 1
    # gather whole blocks as rows of H*D, where the pool lies (a gather
    # over [.., H, D] re-lays the layer's whole pool on a TPU first: its
    # D = 64 is half a lane tile)
    k, v = k_pages[block_tables], v_pages[block_tables]
    k = k.reshape(bsz, kmax, heads, dim)
    v = v.reshape(bsz, kmax, heads, dim)
    k = k.at[rows, positions].set(k_step)
    v = v.at[rows, positions].set(v_step)
    k = k.transpose(0, 2, 1, 3)            # [B, H, Kmax, D]
    v = v.transpose(0, 2, 1, 3)
    s = stable_scores(q[:, :, None, :], k) * sm_scale   # [B, H, 1, Kmax]
    pos = lax.broadcasted_iota(jnp.int32, (1, 1, 1, kmax), 3)
    s = jnp.where(pos < context_lens[:, None, None, None], s, NEG_INF)
    p = stable_softmax(s)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out[:, :, 0, :]


def latent_paged_decode_attention(q, row_step, pages, block_tables,
                                  context_lens, sm_scale, kv_rank):
    """One decode step of latent attention in the absorbed form, over
    the latent pool.

    - ``q`` ``[B, H, W]``: per head ``[q_nope . W_uk | rotated q_rope]``,
      ``W = kv_rank + rope_dim``: every head reads the same cache row.
    - ``row_step`` ``[B, W]``: this token's row ``[N(c_kv) | rotated
      k_rope]`` (written to the pool by the caller after the step).
    - ``pages`` ``[num_blocks, block_size, W]``: the pool as of before
      the step; ``block_tables`` ``int32 [B, max_blocks]``;
      ``context_lens`` ``int32 [B]`` counting the current token.

    Returns ``p . c_kv`` ``[B, H, kv_rank]`` (the caller applies
    ``W_uv``).  Scores and softmax in float32; the current token enters
    as a score of its own, so the pool is read as it lies.  On a TPU,
    with pages that are whole tiles, the block-table walk
    (:func:`_walk_pages`) reads the blocks that hold live tokens and no
    other; elsewhere XLA gathers every table block and masks."""
    mode = _platform.pallas_mode()
    if mode and _walk_tiles(pages):
        return _latent_decode_pallas(
            q, row_step, pages, block_tables, context_lens,
            float(sm_scale), int(kv_rank), mode == "interpret")
    with jax.named_scope("latent_decode_attention"):
        return _latent_decode_xla(q, row_step, pages, block_tables,
                                  context_lens, sm_scale, kv_rank)


def _latent_decode_xla(q, row_step, pages, block_tables, context_lens,
                       sm_scale, kv_rank):
    bsz, max_blocks = block_tables.shape
    kmax = max_blocks * pages.shape[1]
    rows = pages[block_tables].reshape(bsz, kmax, -1)
    s = jnp.einsum("bhw,bkw->bhk", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    pos = lax.broadcasted_iota(jnp.int32, (1, 1, kmax), 2)
    s = jnp.where(pos < (context_lens - 1)[:, None, None], s, NEG_INF)
    s_self = jnp.einsum("bhw,bw->bh", q, row_step,
                        preferred_element_type=jnp.float32) * sm_scale
    m = jnp.maximum(jnp.max(s, axis=-1), s_self)
    p = jnp.exp(s - m[..., None])
    p_self = jnp.exp(s_self - m)
    out = jnp.einsum("bhk,bkc->bhc", p.astype(rows.dtype),
                     rows[..., :kv_rank],
                     preferred_element_type=jnp.float32)
    out = out + p_self[..., None] * row_step[:, None, :kv_rank]
    return (out / (jnp.sum(p, axis=-1) + p_self)[..., None]
            ).astype(q.dtype)


def gqa_paged_decode_attention(q, k_step, v_step, k_pages, v_pages,
                               block_tables, context_lens, sm_scale,
                               window=None):
    """One decode step of grouped-query attention over key and value
    pools.

    - ``q`` ``[B, Hq, D]``; ``k_step``/``v_step`` ``[B, Hkv, D]`` this
      token's keys and values (written to the pools by the caller after
      the step); key-value head ``g`` serves the query heads ``g * Hq /
      Hkv`` and the ``Hq / Hkv - 1`` after it.
    - ``k_pages``/``v_pages`` ``[num_blocks, block_size, Hkv * D]``: a
      cached row holds every key-value head, and lies as it is indexed;
      ``block_tables`` ``int32 [B, max_blocks]``; ``context_lens``
      ``int32 [B]`` counting the current token.

    Returns ``[B, Hq, D]`` in ``q``'s dtype.  Scores and softmax in
    float32, the products in the pools' dtype; the current token enters
    as a score of its own, so the pools are read as they lie.  On a TPU,
    with pages that are whole tiles, the block-table walk
    (:func:`_walk_pages`) reads the blocks that hold live tokens and no
    other, under the body :func:`_gqa_walk_body` names for the head's
    width; elsewhere XLA gathers every table block and masks.

    ``window`` (static; a sliding-window layer): the row at position
    ``p = context_lens - 1`` sees the cached keys ``p - window + 1 .. p
    - 1`` and itself, and ``block_tables`` is a **ring**: token ``j``
    lies in entry ``(j // block_size) mod ring`` of its row, ``ring``
    the table's width, which has to hold the window and a block more
    (``window / block_size + 1`` entries, or the row's whole horizon
    where that is shorter: the block a step writes never holds a key
    it still reads).  The walk visits the
    ring's live entries alone, ``window / block_size + 1`` blocks at the
    most whatever the context, in ring order (a softmax has none), and
    masks by the key's position, so that the keys that have left the
    window and still lie in their block do not count.  A row whose
    whole horizon fits the ring never wraps, and its table reads as any
    other.  Under the scope ``paged_decode_gqa_window``."""
    mode = _platform.pallas_mode()
    if window is not None:
        blk = k_pages.shape[1]
        if window % blk or window < blk:
            raise ValueError("a window of %d tokens is not whole blocks "
                             "of %d" % (window, blk))
        if mode and _walk_tiles(k_pages, v_pages):
            if q.shape[-1] % _LANE:
                raise NotImplementedError(
                    "the ring walk is built for heads of whole lane tiles")
            return _gqa_decode_pallas(
                q, k_step, v_step, k_pages, v_pages, block_tables,
                context_lens, float(sm_scale), mode == "interpret",
                window=int(window))
        with jax.named_scope("paged_decode_gqa_window"):
            return _gqa_decode_xla(q, k_step, v_step, k_pages, v_pages,
                                   block_tables, context_lens, sm_scale,
                                   window)
    if mode and _walk_tiles(k_pages, v_pages):
        return _gqa_walk_body(q.shape[-1])(
            q, k_step, v_step, k_pages, v_pages, block_tables, context_lens,
            float(sm_scale), mode == "interpret")
    with jax.named_scope("paged_decode_gqa_attention"):
        return _gqa_decode_xla(q, k_step, v_step, k_pages, v_pages,
                               block_tables, context_lens, sm_scale)


def _ring_positions(context_lens, ring, blk):
    """``int32 [B, ring * blk]``: the position of the token that lies
    in each slot of a row's ring as of a step at position ``p =
    context_lens - 1``: entry ``e`` holds the newest block ``b <= (p -
    1) // blk`` with ``b mod ring == e`` (negative: none yet)."""
    top = (context_lens - 2) // blk                  # the newest block
    entry = lax.broadcasted_iota(jnp.int32, (1, ring, 1), 1)
    block = top[:, None, None] - (top[:, None, None] - entry) % ring
    at = block * blk + lax.broadcasted_iota(jnp.int32, (1, 1, blk), 2)
    return at.reshape(context_lens.shape[0], ring * blk)


def _gqa_decode_xla(q, k_step, v_step, k_pages, v_pages, block_tables,
                    context_lens, sm_scale, window=None):
    bsz, max_blocks = block_tables.shape
    groups, dim = k_step.shape[1:]
    kmax = max_blocks * k_pages.shape[1]
    f32 = jnp.float32
    keys = k_pages[block_tables].reshape(bsz, kmax, groups, dim)
    values = v_pages[block_tables].reshape(bsz, kmax, groups, dim)
    qg = q.reshape(bsz, groups, -1, dim)                # [B, G, R, D]
    s = jnp.einsum("bgrd,bkgd->bgrk", qg.astype(keys.dtype), keys,
                   preferred_element_type=f32) * sm_scale
    cached = (context_lens - 1)[:, None, None, None]
    if window is None:
        pos = lax.broadcasted_iota(jnp.int32, (1, 1, 1, kmax), 3)
        seen = pos < cached
    else:
        pos = _ring_positions(context_lens, max_blocks,
                              k_pages.shape[1])[:, None, None, :]
        seen = (pos >= 0) & (pos < cached) & (cached - pos < window)
        # what a masked slot holds is anyone's: no bit of it may count
        values = jnp.where(seen[:, 0, 0, :, None, None], values, 0)
    s = jnp.where(seen, s, NEG_INF)
    s_self = jnp.einsum("bgrd,bgd->bgr", qg.astype(f32),
                        k_step.astype(f32)) * sm_scale
    m = jnp.maximum(jnp.max(s, axis=-1), s_self)
    p = jnp.exp(s - m[..., None])
    p_self = jnp.exp(s_self - m)
    out = jnp.einsum("bgrk,bkgd->bgrd", p.astype(values.dtype), values,
                     preferred_element_type=f32)
    out = out + p_self[..., None] * v_step.astype(f32)[:, :, None, :]
    out = out / (jnp.sum(p, axis=-1) + p_self)[..., None]
    return out.reshape(q.shape).astype(q.dtype)


# ----------------------------------------------------------------------
# paged decode on a TPU: one block-table walk under every decode body
# ----------------------------------------------------------------------
#
# A decode step attends over ``context_len - 1`` cached tokens a row,
# which lie in the first ``ceil((context_len - 1) / block_size)`` blocks
# of the row's table; the table is as wide as the longest sequence the
# server admits.  The XLA bodies gather, re-lay and score every table
# block and mask afterwards.  The walk below runs one program a row:
# tables and lengths are scalar-prefetched, the pools stay in HBM, and a
# loop over the row's live blocks alone copies a chunk of pages into
# VMEM while the body folds the chunk before it into an online softmax.
# A block that holds no live token costs neither a copy nor arithmetic.
# The current token is the walk's initial state (``init``), so the pool
# is read as of before the step.
#
# The copies stay in flight across chunks and rows: under a row's last
# chunk the walk fetches the first chunk of the row after it (the
# programs run in order on one core, and the chunk buffers and their
# semaphores are scratch that outlives a program), so that only row 0
# begins with nothing in flight.  A chunk whose pages are all live is
# started without a branch a page and awaited once a pool; a row's last
# chunk goes page by page, since its tail must not be read, and where
# no more than half of it is live the body folds that half alone.

#: bytes of pool pages (all pools of the cache together) a chunk of the
#: walk holds; a second chunk is in flight behind it.  A page is 2 x 64
#: KB (GPT-2 medium's float32 key and value rows), 2 x 16 KB (512-wide
#: bfloat16 rows) or 20 KB (a 640-wide bfloat16 latent row): one page a
#: loop step would leave the loop's own cost and a copy's latency larger
#: than the arithmetic on it.  2 MiB since a row's first chunk is
#: fetched under the row before it (1 MiB while every larger chunk paid
#: for itself at the head of every row): over contexts as they are
#: served the best of 1, 2 and 4 MiB at four of the seven served shapes
#: and within 1.5% of it at two; the ring gains 6% more from 4 MiB,
#: which costs five shapes 7-12% (``tools/walk_sweep.py``).
_WALK_CHUNK_BYTES = 2 << 20

_M_CHUNK_TOKENS = _metrics.gauge(
    "paged_decode_walk_chunk_tokens",
    "Cached tokens a chunk of the block-table walk built last holds, by "
    "the kernel's scope", ["kernel"])
_M_CHUNK_WAITS = _metrics.gauge(
    "paged_decode_walk_waits_per_chunk",
    "Waits the walk built last makes for a chunk whose pages are all "
    "live (one a pool), by the kernel's scope", ["kernel"])
_M_ROWS_AHEAD = _metrics.gauge(
    "paged_decode_walk_rows_ahead",
    "Rows ahead of the one it folds whose first chunk the walk built "
    "last keeps in flight, by the kernel's scope", ["kernel"])


def _walk_tiles(*pools):
    """Whether a page of every pool is whole tiles of the chip's
    memory (8 x 128 words of 32 bits): a page is copied as it lies."""
    return all(
        p.shape[-1] % _LANE == 0
        and p.shape[-2] % (8 * 4 // jnp.dtype(p.dtype).itemsize) == 0
        for p in pools)


def _walk_chunk_pages(pools, max_blocks):
    """Pages a chunk: what of a power of two fits the chunk's bytes, at
    most the table."""
    page = sum(p.shape[1] * p.shape[2] * jnp.dtype(p.dtype).itemsize
               for p in pools)
    fit = max(1, _WALK_CHUNK_BYTES // page)
    return min(1 << (fit.bit_length() - 1), max_blocks)


def _walk_kernel(tables_ref, lens_ref, *refs, n_rows, n_pools, pages, blk,
                 max_blocks, init, chunk, finish, window=None):
    """One row of the batch.  ``refs``: the row's operands and those
    every row shares, the pools (in HBM), the output, then scratch: a
    two-slot chunk buffer a pool, the copies' semaphores ``[pool,
    slot]``, the slot the next row begins in, and the body's state.
    With a ``window`` the table is a ring of ``max_blocks`` entries:
    the walk begins at the block that holds the oldest key the row
    still sees, reads block ``b`` from entry ``b mod max_blocks``, and
    hands the body of its first chunk the tokens to skip there.

    Row ``b``'s first chunk is in flight when its program begins
    (started under the last chunk of the nearest row before it that
    walks at all, or by a row between them that walks nothing; row 0
    starts its own), so the slot a row begins in is walk state: its
    last chunk sits in one slot while the next row's first lands in the
    other."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    row_refs = refs[:n_rows]
    pool_refs = refs[n_rows:n_rows + n_pools]
    out_ref = refs[n_rows + n_pools]
    scratch = refs[n_rows + n_pools + 1:]
    bufs, sem, slot_ref, state = scratch[:n_pools], scratch[n_pools], \
        scratch[n_pools + 1], scratch[n_pools + 2:]
    b = pl.program_id(0)
    tokens = pages * blk

    def walk_of(row):
        # what the walk of ``row`` reads: its cached tokens, the oldest
        # it sees, the page that holds it, its live pages and chunks
        cached = jnp.maximum(lens_ref[row] - 1, 0)
        live_pages = (cached + blk - 1) // blk
        first_page = oldest = 0
        if window is not None:
            oldest = jnp.maximum(cached - window + 1, 0)
            first_page = oldest // blk
            live_pages = live_pages - first_page
        return cached, oldest, first_page, live_pages, \
            (live_pages + pages - 1) // pages

    def start(row, first_page, live_pages, c, slot):
        # chunk ``c`` of ``row``: each live page from where its table
        # entry says it lies
        entry = c * pages
        if window is not None:
            entry = lax.rem(first_page + entry, max_blocks)

        def page(i, to):
            at = entry + i
            if window is not None:      # the ring wraps inside a chunk
                at = jnp.where(at >= max_blocks, at - max_blocks, at)
            at = tables_ref[row * max_blocks + at]
            for n in range(n_pools):
                pltpu.make_async_copy(
                    pool_refs[n].at[at], bufs[n].at[slot, pl.ds(to, blk)],
                    sem.at[n, slot]).start()

        live = live_pages - c * pages

        @pl.when(live >= pages)
        def _():
            for i in range(pages):
                page(i, i * blk)

        @pl.when(live < pages)
        def _():
            lax.fori_loop(0, live, lambda i, _: page(
                i, pl.multiple_of(i * blk, blk)), None)

    def wait(live_pages, c, slot):
        # a wait needs the copy's size alone: a whole chunk's copies
        # are awaited as one of the slot's size a pool
        live = live_pages - c * pages

        def a_page(i, _):
            for n in range(n_pools):
                pltpu.make_async_copy(
                    pool_refs[n].at[0], bufs[n].at[slot, pl.ds(0, blk)],
                    sem.at[n, slot]).wait()

        @pl.when(live >= pages)
        def _():
            for n in range(n_pools):
                pltpu.make_async_copy(bufs[n].at[slot], bufs[n].at[slot],
                                      sem.at[n, slot]).wait()

        @pl.when(live < pages)
        def _():
            lax.fori_loop(0, live, a_page, None)

    def fold(slot, skip, live):
        # the chunk in ``slot``, of whose tokens those from ``skip`` to
        # before ``live`` count: folded whole where all do, else
        # masked; a last chunk no more than half live as its first
        # half alone, at half the arithmetic
        held = [buf.at[slot] for buf in bufs]
        half = pages // 2 * blk
        whole = live >= tokens
        if window is not None:
            whole = whole & (skip == 0)
        counted = live if window is None else (skip, live)

        @pl.when(whole)
        def _():
            chunk(row_refs, held, state, None)

        @pl.when(jnp.logical_not(whole) & (live > half))
        def _():
            chunk(row_refs, held, state, counted)

        if half:
            @pl.when(live <= half)
            def _():
                chunk(row_refs, [ref.at[pl.ds(0, half)] for ref in held],
                      state, counted)

    def either(pred, this, that):
        return [jnp.where(pred, x, y) for x, y in zip(this, that)]

    cached, oldest, first_page, live_pages, n_chunks = walk_of(b)
    own = (b, first_page, live_pages)
    # the row whose first chunk this one fetches: none after the last
    ahead = jnp.minimum(b + 1, pl.num_programs(0) - 1)
    _, _, ahead_first, ahead_live, ahead_chunks = walk_of(ahead)
    ahead_chunks = jnp.where(ahead > b, ahead_chunks, 0)
    ahead = (ahead, ahead_first, ahead_live)
    slot0 = jnp.where(b == 0, 0, slot_ref[0])
    slot_ref[0] = lax.rem(slot0 + n_chunks, 2)

    # row 0 starts its own first chunk; a row that walks nothing hands
    # the turn on
    walks = n_chunks > 0

    @pl.when(jnp.where(walks, b == 0, ahead_chunks > 0))
    def _():
        start(*either(walks, own, ahead), 0, slot0)

    init(row_refs, state)

    def step(c, carry):
        slot = lax.rem(slot0 + c, 2)
        last = c + 1 == n_chunks

        @pl.when(jnp.logical_not(last) | (ahead_chunks > 0))
        def _():
            start(*either(last, ahead, own), jnp.where(last, 0, c + 1),
                  1 - slot)

        wait(live_pages, c, slot)
        # the walk's first chunk begins with the keys of its block that
        # have left the window
        fold(slot, 0 if window is None
             else jnp.where(c == 0, oldest - first_page * blk, 0),
             cached - first_page * blk - c * tokens)
        return carry

    lax.fori_loop(0, n_chunks, step, 0)
    finish(row_refs, state, out_ref)


def _walk_pages(scope, init, chunk, finish, rows, shared, pools,
                block_tables, context_lens, out, state, interpret=False,
                window=None):
    """Run ``init`` / ``chunk`` / ``finish`` over every row's live
    blocks, as one kernel under the name ``scope``.

    ``rows``: per-row operands ``[B, r, c]``, handed to the bodies as
    ``[1, r, c]`` refs, followed by the ``shared`` ones, whole;
    ``pools``: ``[num_blocks, block_size, W]`` arrays read through
    ``block_tables`` ``int32 [B, max_blocks]`` up to ``context_lens -
    1`` tokens; ``out``: the ``[B, r, c]`` result's
    ``ShapeDtypeStruct``; ``state``: the bodies' VMEM scratch.
    ``chunk(row_refs, held, state, live)`` folds ``pages * block_size``
    tokens (``held``: one ``[tokens, W]`` ref a pool) into the state;
    ``live`` is None where every token counts and else the number that
    do, the rest being unspecified bits.  With a ``window`` (tokens)
    ``block_tables`` is a ring a row (:func:`gqa_paged_decode_attention`)
    and ``live`` is None or ``(skip, end)``: the chunk's tokens ``skip
    .. end - 1`` count (:func:`_counted`)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, max_blocks = block_tables.shape
    blk = pools[0].shape[1]
    pages = _walk_chunk_pages(pools, max_blocks)
    _M_CHUNK_TOKENS.labels(scope).set(pages * blk)
    _M_CHUNK_WAITS.labels(scope).set(len(pools))
    _M_ROWS_AHEAD.labels(scope).set(1)
    kernel = functools.partial(
        _walk_kernel, n_rows=len(rows) + len(shared), n_pools=len(pools),
        pages=pages, blk=blk, max_blocks=max_blocks, init=init, chunk=chunk,
        finish=finish, window=window)

    def one_row(x):
        return pl.BlockSpec((1,) + x.shape[1:],
                            lambda b, tables, lens: (b, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz,),
        in_specs=[one_row(x) for x in rows]
        + [pl.BlockSpec(x.shape, lambda b, tables, lens, n=x.ndim: (0,) * n)
           for x in shared]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=one_row(out),
        scratch_shapes=[pltpu.VMEM((2, pages * blk, p.shape[2]), p.dtype)
                        for p in pools]
        + [pltpu.SemaphoreType.DMA((len(pools), 2)),
           pltpu.SMEM((1,), jnp.int32)] + list(state))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    # the scope names the kernel in a trace; it has to be the innermost
    with jax.named_scope(scope):
        return pl.pallas_call(
            kernel, out_shape=out, grid_spec=grid_spec, interpret=interpret,
            **kwargs)(block_tables.reshape(-1).astype(jnp.int32),
                      context_lens.astype(jnp.int32), *rows, *shared, *pools)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _kv_decode_pallas(q, k_step, v_step, k_pages, v_pages, block_tables,
                      context_lens, sm_scale, interpret=False):
    """GPT-2's decode over the walk: ``q`` / ``k_step`` / ``v_step``
    ``[B, H, D]``, the float32 pools ``[num_blocks, block_size, H*D]``.

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
    on a v5e, both 9e-7 from a float64 softmax).  Jitted so that a
    model's layers share one trace and one lowering of the kernel: 24
    of them took a warm start 3.7 s longer, every one lowered anew."""
    from jax.experimental.pallas import tpu as pltpu

    bsz, heads, dim = q.shape
    width, scale = heads * dim, sm_scale
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
            s = jnp.where(at < live, s, NEG_INF)
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

    rows = tuple(x.astype(f32).reshape(bsz, 1, width)
                 for x in (q, k_step, v_step))
    out = _walk_pages(
        "paged_decode_attention", init, chunk, finish, rows, (selector,),
        (k_pages, v_pages), block_tables, context_lens,
        jax.ShapeDtypeStruct((bsz, 1, width), f32),
        [pltpu.VMEM((1, heads), f32), pltpu.VMEM((1, heads), f32),
         pltpu.VMEM((1, width), f32)],
        interpret=interpret)
    return out.reshape(bsz, heads, dim)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "kv_rank", "interpret"))
def _latent_decode_pallas(q, row_step, pages, block_tables, context_lens,
                          sm_scale, kv_rank, interpret=False):
    """The absorbed decode over the walk: a chunk of 640-wide rows is
    the keys of every head and, its first ``kv_rank`` columns, the
    values; both products ride the MXU in the rows' dtype, scores,
    softmax and accumulator stay float32.  Jitted so that a model's
    layers share one trace and one lowering of the kernel."""
    from jax.experimental.pallas import tpu as pltpu

    bsz, heads, _ = q.shape

    def init(row_refs, state):
        q_ref, step_ref = row_refs
        m_ref, l_ref, acc_ref = state
        step = step_ref[0].astype(jnp.float32)              # [1, W]
        m_ref[...] = jnp.sum(q_ref[0].astype(jnp.float32) * step, axis=1,
                             keepdims=True) * sm_scale
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(step[:, :kv_rank], acc_ref.shape)

    def chunk(row_refs, held, state, live):
        m_ref, l_ref, acc_ref = state
        rows = held[0][...]                                 # [T, W]
        values = rows[:, :kv_rank]
        s = lax.dot_general(
            row_refs[0][0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [H, T]
        if live is not None:
            at = lax.broadcasted_iota(jnp.int32, (1, rows.shape[0]), 1)
            s = jnp.where(at < live, s, NEG_INF)
            at = lax.broadcasted_iota(jnp.int32, (rows.shape[0], 1), 0)
            values = jnp.where(at < live, values, 0)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
            p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def finish(row_refs, state, out_ref):
        _, l_ref, acc_ref = state
        out_ref[0] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)

    return _walk_pages(
        "latent_decode_attention", init, chunk, finish,
        (q, row_step[:, None, :]), (), (pages,), block_tables, context_lens,
        jax.ShapeDtypeStruct((bsz, heads, kv_rank), q.dtype),
        [pltpu.VMEM((heads, 1), jnp.float32),
         pltpu.VMEM((heads, 1), jnp.float32),
         pltpu.VMEM((heads, kv_rank), jnp.float32)],
        interpret=interpret)


def _counted(live, shape, axis):
    """Which of a chunk's tokens, counted along ``axis`` of ``shape``,
    count: those before ``live``, or from ``live[0]`` to before
    ``live[1]`` where the walk is a window's."""
    at = lax.broadcasted_iota(jnp.int32, shape, axis)
    if isinstance(live, tuple):
        return (at >= live[0]) & (at < live[1])
    return at < live


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "interpret", "window"))
def _gqa_decode_pallas(q, k_step, v_step, k_pages, v_pages, block_tables,
                       context_lens, sm_scale, interpret=False,
                       window=None):
    """The grouped-query decode over the walk: a chunk's key rows hold
    every key-value head side by side (``D`` a whole number of lane
    tiles), so a head's keys are a slice of lanes and its ``Hq / Hkv``
    queries one small product with them on the MXU, in the pools' dtype;
    scores, softmax and accumulator stay float32.  Jitted so that a
    model's layers share one trace and one lowering of the kernel.

    A key-value head's queries are a run of rows of the ``[Hq, .]``
    state, whole sublane tiles where ``Hq / Hkv`` is a multiple of 8.
    Where it is not and there are several key-value heads (7 queries a
    head), every head's run is padded to whole tiles with dead rows
    (queries of zeros: a uniform softmax nobody reads), which costs the
    MXU nothing (it takes 8 rows a pass either way), the VPU an eighth
    more softmax, and XLA a pad of the queries and a slice of the
    output, ``[B, Hq, D]`` each.  ``window``: the walk over a ring."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, heads, dim = q.shape
    groups = k_step.shape[1]
    per = heads // groups
    f32 = jnp.float32
    dead = -per % 8 if groups > 1 else 0
    if dead:
        q = jnp.pad(q.reshape(bsz, groups, per, dim),
                    ((0, 0), (0, 0), (0, dead), (0, 0)))
        out = _gqa_decode_pallas(
            q.reshape(bsz, groups * (per + dead), dim), k_step, v_step,
            k_pages, v_pages, block_tables, context_lens, sm_scale,
            interpret, window)
        return out.reshape(bsz, groups, per + dead, dim)[:, :, :per] \
            .reshape(bsz, heads, dim)

    def group(ref, g):                  # a group's rows of [Hq, .] state
        return ref.at[pl.ds(g * per, per)]

    def init(row_refs, state):
        q_ref, k_ref, v_ref = row_refs
        m_ref, l_ref, acc_ref = state
        l_ref[...] = jnp.ones_like(l_ref)
        for g in range(groups):
            lanes = slice(g * dim, (g + 1) * dim)
            q_g = q_ref[0, g * per:(g + 1) * per, :]            # [R, D]
            group(m_ref, g)[...] = jnp.sum(
                q_g * k_ref[0][:, lanes].astype(f32), axis=1,
                keepdims=True) * sm_scale
            group(acc_ref, g)[...] = jnp.broadcast_to(
                v_ref[0][:, lanes].astype(f32), (per, dim))

    def chunk(row_refs, held, state, live):
        m_ref, l_ref, acc_ref = state
        keys, values = held[0][...], held[1][...]               # [T, W]
        tokens = keys.shape[0]
        if live is not None:
            values = jnp.where(_counted(live, (tokens, 1), 0), values, 0)
        for g in range(groups):
            lanes = slice(g * dim, (g + 1) * dim)
            q_g = row_refs[0][0, g * per:(g + 1) * per, :]
            s = lax.dot_general(
                q_g.astype(keys.dtype), keys[:, lanes],
                (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * sm_scale          # [R, T]
            if live is not None:
                s = jnp.where(_counted(live, (1, tokens), 1), s, NEG_INF)
            m_prev = group(m_ref, g)[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            group(l_ref, g)[...] = alpha * group(l_ref, g)[...] \
                + jnp.sum(p, axis=1, keepdims=True)
            group(acc_ref, g)[...] = alpha * group(acc_ref, g)[...] \
                + lax.dot_general(
                    p.astype(values.dtype), values[:, lanes],
                    (((1,), (0,)), ((), ())), preferred_element_type=f32)
            group(m_ref, g)[...] = m_new

    def finish(row_refs, state, out_ref):
        _, l_ref, acc_ref = state
        out_ref[0] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)

    width = groups * dim
    # the queries in float32: a group's rows are then whole sublane
    # tiles whatever the pools' dtype packs
    rows = (q.astype(f32), k_step.reshape(bsz, 1, width),
            v_step.reshape(bsz, 1, width))
    return _walk_pages(
        "paged_decode_gqa_attention" if window is None
        else "paged_decode_gqa_window", init, chunk, finish, rows, (),
        (k_pages, v_pages), block_tables, context_lens,
        jax.ShapeDtypeStruct((bsz, heads, dim), q.dtype),
        [pltpu.VMEM((heads, 1), f32), pltpu.VMEM((heads, 1), f32),
         pltpu.VMEM((heads, dim), f32)],
        interpret=interpret, window=window)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _gqa_packed_decode_pallas(q, k_step, v_step, k_pages, v_pages,
                              block_tables, context_lens, sm_scale,
                              interpret=False):
    """The grouped-query decode over the walk where a key-value head is
    narrower than a lane tile (``D`` 64: two heads a tile, eight in a
    512-wide row), so that a head's keys are no slice of lanes a kernel
    can take.  Every query head is spread over the whole cached row
    instead, its own key-value head's lanes holding it and the others
    zero: the scores of all ``Hq`` heads are then one product with the
    chunk's key rows as they lie (the zeros add nothing, exactly), and
    ``p . values`` one product with its value rows, of which a head
    keeps its own key-value head's lanes.  Both ride the MXU in the
    pools' dtype with the chunk's rows as the stationary operand, which
    they are in the sliced body too: the ``Hkv`` times more
    multiplications stream through tiles that are loaded anyway.
    Scores, softmax and accumulator stay float32.  Jitted so that a
    model's layers share one trace and one lowering of the kernel."""
    from jax.experimental.pallas import tpu as pltpu

    bsz, heads, dim = q.shape
    groups = k_step.shape[1]
    per, width = heads // groups, groups * dim
    f32 = jnp.float32
    own = lax.broadcasted_iota(jnp.int32, (heads, width), 1) // dim \
        == lax.broadcasted_iota(jnp.int32, (heads, width), 0) // per
    q_wide = jnp.where(own, jnp.tile(q, (1, 1, groups)), 0)

    def init(row_refs, state):
        q_ref, k_ref, v_ref = row_refs
        m_ref, l_ref, acc_ref = state
        m_ref[...] = jnp.sum(q_ref[0].astype(f32) * k_ref[0].astype(f32),
                             axis=1, keepdims=True) * sm_scale
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(v_ref[0].astype(f32), acc_ref.shape)

    def chunk(row_refs, held, state, live):
        m_ref, l_ref, acc_ref = state
        keys, values = held[0][...], held[1][...]               # [T, W]
        tokens = keys.shape[0]
        s = lax.dot_general(
            row_refs[0][0].astype(keys.dtype), keys,
            (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * sm_scale              # [Hq, T]
        if live is not None:
            at = lax.broadcasted_iota(jnp.int32, (1, tokens), 1)
            s = jnp.where(at < live, s, NEG_INF)
            at = lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)
            values = jnp.where(at < live, values, 0)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
            p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m_ref[...] = m_new

    def finish(row_refs, state, out_ref):
        _, l_ref, acc_ref = state
        out_ref[0] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)

    rows = (q_wide, k_step.reshape(bsz, 1, width),
            v_step.reshape(bsz, 1, width))
    wide = _walk_pages(
        "paged_decode_gqa_attention", init, chunk, finish, rows, (),
        (k_pages, v_pages), block_tables, context_lens,
        jax.ShapeDtypeStruct((bsz, heads, width), q.dtype),
        [pltpu.VMEM((heads, 1), f32), pltpu.VMEM((heads, 1), f32),
         pltpu.VMEM((heads, width), f32)],
        interpret=interpret)
    # a head's output is its own key-value head's lanes of its row
    wide = wide.reshape(bsz, groups, per, groups, dim)
    return jnp.stack([wide[:, g, :, g, :] for g in range(groups)],
                     axis=1).reshape(bsz, heads, dim)


def _gqa_walk_body(dim):
    """The walk's grouped-query body for heads ``dim`` wide: a
    key-value head that is whole lane tiles is a slice of a cached row
    (:func:`_gqa_decode_pallas`), a narrower one is not
    (:func:`_gqa_packed_decode_pallas`)."""
    return _gqa_decode_pallas if dim % _LANE == 0 \
        else _gqa_packed_decode_pallas


# ----------------------------------------------------------------------
# parity grids: each kernel against its XLA body (ragged tails on
# purpose; the widest case of each is the served shape, which
# tests/test_chip_compile.py compiles for the described chip)
# ----------------------------------------------------------------------


def _paged_case_pool(rng, dtype, blk, max_blocks, ctx, width):
    """A pool of ``len(ctx) * max_blocks + 1`` random pages and a table
    with distinct live pages per sequence; table entries past the
    context keep page 0 (the pad convention), whose garbage both bodies
    must mask off identically."""
    import numpy as np

    bsz = len(ctx)
    pool = (bsz * max_blocks + 1, blk, width)
    tables = np.zeros((bsz, max_blocks), np.int32)
    nxt = 1
    for i, c in enumerate(ctx):
        for j in range(-(-int(c) // blk)):
            tables[i, j] = nxt
            nxt += 1

    def rand(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32) \
            .astype(dtype)

    return rand, pool, jnp.asarray(tables), \
        jnp.asarray(list(ctx), dtype=jnp.int32)


def _interpret():
    return _platform.pallas_mode() != "chip"


def _kv_case(case):
    dtype, h, d, blk, max_blocks, ctx = case
    rand, pool, tables, lens = _paged_case_pool(
        case_rng(case), dtype, blk, max_blocks, ctx, h * d)
    k_pages, v_pages = rand(pool), rand(pool)
    q, k_step, v_step = (rand((len(ctx), h, d)) for _ in range(3))
    scale = 1.0 / float(d) ** 0.5
    return (functools.partial(_kv_decode_xla, sm_scale=scale),
            functools.partial(_kv_decode_pallas, sm_scale=scale,
                              interpret=_interpret()),
            (q, k_step, v_step, k_pages, v_pages, tables, lens))


register_parity(
    "paged_decode_attention", _kv_case, parity="tolerance",
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


def _latent_case(case):
    dtype, heads, width, kv_rank, blk, max_blocks, ctx = case
    rand, pool, tables, lens = _paged_case_pool(
        case_rng(case), dtype, blk, max_blocks, ctx, width)
    pages = rand(pool)
    q, row_step = rand((len(ctx), heads, width)), rand((len(ctx), width))
    tol = (3e-2, 3e-2) if dtype == "bfloat16" else (1e-4, 1e-4)
    return (functools.partial(_latent_decode_xla, sm_scale=0.1,
                              kv_rank=kv_rank),
            functools.partial(_latent_decode_pallas, sm_scale=0.1,
                              kv_rank=kv_rank, interpret=_interpret()),
            (q, row_step, pages, tables, lens), tol)


register_parity(
    "latent_decode_attention", _latent_case, parity="tolerance",
    grid=(
        ("float32", 4, 48, 32, 8, 3, (5, 20)),       # ragged contexts
        ("float32", 2, 40, 24, 4, 4, (1, 16, 9)),    # no cached token
        # the served row (640 wide, 512 of it values, blocks of 16)
        ("bfloat16", 16, 640, 512, 16, 4, (1, 17, 64)),
    ))


def _gqa_case(case):
    dtype, heads, groups, dim, blk, max_blocks, ctx = case
    rand, pool, tables, lens = _paged_case_pool(
        case_rng(case), dtype, blk, max_blocks, ctx, groups * dim)
    k_pages, v_pages = rand(pool), rand(pool)
    q = rand((len(ctx), heads, dim))
    k_step, v_step = (rand((len(ctx), groups, dim)) for _ in range(2))
    tol = (3e-2, 3e-2) if dtype == "bfloat16" else (1e-4, 1e-4)
    scale = 1.0 / float(dim) ** 0.5
    return (functools.partial(_gqa_decode_xla, sm_scale=scale),
            functools.partial(_gqa_walk_body(dim), sm_scale=scale,
                              interpret=_interpret()),
            (q, k_step, v_step, k_pages, v_pages, tables, lens), tol)


register_parity(
    "paged_decode_gqa_attention", _gqa_case, parity="tolerance",
    grid=(
        ("float32", 4, 2, 64, 8, 3, (5, 20)),        # ragged contexts
        ("float32", 6, 1, 128, 4, 4, (1, 16, 9)),    # one group, no cache
        # the served heads (16 queries over 2 key-value heads of 256,
        # 512-wide bfloat16 rows, blocks of 16)
        ("bfloat16", 16, 2, 256, 16, 4, (1, 16, 17, 18, 64)),
        # key-value heads narrower than a lane tile (the packed body):
        # the served heads of 64 (32 queries over 8 key-value heads,
        # 512-wide bfloat16 rows, blocks of 16), and three queries a
        # head of 32
        ("bfloat16", 32, 8, 64, 16, 4, (1, 16, 17, 18, 64)),
        ("float32", 12, 4, 32, 8, 3, (2, 24, 11)),
    ))
