"""Block-allocated paged KV cache for autoregressive decode.

The vLLM idea (Kwon et al., SOSP '23) sized for this runtime: instead of
one contiguous ``[max_len, heads, dim]`` buffer per sequence — which
fragments HBM and caps concurrency at ``pool / max_len`` — K/V state
lives in fixed-size **token blocks** drawn from a shared pool.  Each
sequence owns an ordered **block table** (list of block ids); logical
token position ``t`` lives at ``(table[t // block_size], t % block_size)``.
Allocation is a free-list pop, release is a free-list push, and a full
pool surfaces as the typed :class:`CacheExhaustedError` (HTTP 429)
through the serving admission machinery rather than an OOM.

Layout: one pair of pools per cache, shaped

    ``k_pages / v_pages : [num_layers, num_blocks, block_size, heads * dim]``

A token's K (or V) of one layer is one row of ``heads * dim`` values, a
block is ``block_size`` such rows lying together.  Heads and head
dimension are merged because the device lays an array out by its shape
alone: with a trailing ``[heads, dim]`` and ``dim`` = 64, half a lane
tile, a TPU keeps the *block* axis innermost, and every gather through
a block table and every write of a token then re-lays a whole layer or
the whole pool first (compiled for a v5e, PR 25: a pool-sized temporary
and four pool-sized copies per write).  Rows of ``heads * dim`` lie as
they are indexed, so a block is gathered, and a token's row written,
where it lies.

**The pools live on the device** (``jax.Array``); the allocator, the
block tables, the lengths and the gauges live on the host.  A decode
step hands the device its per-batch ``int32`` block tables (a few KB)
and :func:`~mxnet_tpu.ops.paged_attention.paged_decode_attention`
reads K/V rows through the table inside the jitted step (on a TPU a kernel walks
the table and copies the blocks that hold live tokens; elsewhere XLA
gathers every table block) — the pool shape is static, so decode
dispatches never recompile as sequences come and go.
Writes (:meth:`PagedKVCache.write_prefill`, :meth:`PagedKVCache.
write_tokens`) take the K/V a dispatch produced *as device arrays* and
scatter them into the pools in one jitted call that **donates** both
pools and re-binds them to its outputs: the pool is updated in place,
never exists twice, and no K/V byte visits the host.  The host computes
only the target slots, from the tables it owns.

**A model with recurrent layers keeps two kinds of state** and the cache
holds both.  Its per-token layers have the paged pools above (over
those layers only: a layer that caches nothing per token takes no
block); its recurrent layers keep a fixed-size state per *sequence*
(:class:`StateRows`), which lies in a second pool of **state slots**,
``[state layers * 2 * slots + 1, ...]`` an array of the state (the last
row is no sequence's: a decode batch's pad rows write there): a sequence
takes one slot when its blocks are allocated and gives it back when
they are freed (one ``allocate``, one ``free``; a pool without a free
slot is the same typed 429).  A slot has **two versions**, chosen by
the parity of the position: the decode step at position ``p`` reads
version ``p % 2`` and writes ``(p + 1) % 2``, and a prefill of
``length`` tokens writes version ``length % 2``.  A key row written
again holds the same values; a recurrent update applied again would
not, and with two versions a step dispatched again still finds what it
read (``serving.generation.LMBackend`` has the whole account).  The
decode program itself updates the state pool, donated to it
(:meth:`PagedKVCache.swap_state`); a prefill's state is written by
:meth:`PagedKVCache.write_prefill`.

**A model whose layers keep their rows for different lengths of time
has layer groups.**  A sliding-window layer needs the last ``window``
tokens of a sequence, a global layer all of them; one pool, in which a
block id means the same tokens in every layer and lives until the
sequence ends, would keep a window layer's rows as long as a global
layer's.  The model names its groups (``groups``: ``(layers,
window)`` each, ``layers`` the rows of its programs' ``k_rows`` that
are the group's, ``window`` ``None`` or a token count), and every group
has a pool, a free list and a table a sequence of its own, under one
:meth:`PagedKVCache.allocate` and one :meth:`PagedKVCache.free`.  In a
window group a sequence's blocks are a **ring** of ``min(ceil(horizon /
block_size), window / block_size + 1)`` entries, reserved whole at
admission like the rest of its horizon: token ``p`` lies in entry ``(p
// block_size) mod ring``, nothing is freed or replaced while the
sequence lives, and a sequence's table row (:meth:`PagedKVCache.
block_table`: the groups' tables side by side, ``[table_width]``) is
still made once.  The entry a step writes never holds a key the step
(or one dispatched again behind it) still reads: the ring is a block
longer than the window.  A sequence whose horizon is under the window
costs a window group what it costs a global one.  ``k_pages`` /
``v_pages``, ``num_blocks`` and ``stats()["occupancy"]`` are the
**first** group's (a model puts its global layers there: their blocks
grow with the context); ``stats()["groups"]`` has every group's.  A
model with one group (every layer, no window) has the one pool, table
and free list it always had.

The cache is **backend state**: ``serving.generation.LMBackend`` owns
one, the ``ModelRegistry`` swap machinery replaces cache and weights
together, and the generation lane re-prefills live sequences after a
hot-swap (stale pages are never mixed with new weights).

Chaos site ``serving.kv_alloc`` fires at the top of :meth:`allocate`
(name = sequence id) so tests can drill the exhaustion/429 path and
allocation delay without filling the pool.
"""

from __future__ import annotations

import collections
import os
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from .. import chaos
from ..base import MXNetError
from ..observability import memory as _memory
from ..observability import metrics as _metrics

__all__ = ["CacheExhaustedError", "CachePoolLostError", "CacheRow",
           "StateRows", "PagedKVCache", "default_block_size",
           "default_num_blocks"]


class CacheRow(collections.namedtuple("CacheRow", "kind width dtype pools")):
    """What one token of one layer leaves in the cache, as the model
    defines it: ``pools`` rows of ``width`` values of ``dtype``.

    ``("kv", heads * dim, float32, 2)`` is a key row and a value row;
    ``("latent", kv_rank + rope_dim, bfloat16, 1)`` is one row of a
    latent-attention model (the compressed key-value vector and the
    rotated shared key), which has no value pool.  The allocator, the
    block tables and the donated write are the same for every row."""

    __slots__ = ()

    @property
    def bytes(self):
        return self.pools * self.width * np.dtype(self.dtype).itemsize


class StateRows(collections.namedtuple("StateRows", "layers rows")):
    """What one sequence keeps between steps in the layers that hold a
    fixed-size state instead of a row per token: ``layers`` such layers,
    each keeping one array of every ``(shape, dtype)`` in ``rows``.

    ``(6, (((32, 128, 128), float32), ((48, 512), bfloat16)))`` is six
    Gated DeltaNet layers: the ``[heads, key, value]`` matrix of the
    delta rule and the rows the short convolution saw last."""

    __slots__ = ()

    @property
    def bytes(self):
        """Bytes of one version of one sequence's state."""
        return self.layers * sum(
            int(np.prod(shape)) * np.dtype(dtype).itemsize
            for shape, dtype in self.rows)


class CacheExhaustedError(MXNetError):
    """No free KV-cache blocks (or, for a model with recurrent layers,
    no free state slot) for a new sequence or a grown one.

    Carries ``http_status = 429`` so the serving front-end maps it like
    the other typed admission rejections (the client should back off and
    retry; accepted sequences are never evicted to make room).
    """

    http_status = 429


class CachePoolLostError(MXNetError):
    """A pool write failed after its buffers were donated.

    The consumed buffers cannot be written again, so the cache has
    already replaced them with a zeroed pool: the pages of every live
    sequence are gone and their owner must fail them (the generation
    lane does), exactly as if the backend had been swapped.
    """


def default_block_size():
    """Tokens per cache block (``MXNET_TPU_GEN_BLOCK_SIZE``, default 16)."""
    return int(os.environ.get("MXNET_TPU_GEN_BLOCK_SIZE", "16"))


def default_num_blocks():
    """Blocks in the shared pool (``MXNET_TPU_GEN_BLOCKS``, default 64)."""
    return int(os.environ.get("MXNET_TPU_GEN_BLOCKS", "64"))


_M_OCC = _metrics.gauge(
    "serving_kv_cache_occupancy",
    "Fraction of KV-cache blocks in use, by model", ["model"])
_M_BLOCKS = _metrics.gauge(
    "serving_kv_cache_used_blocks",
    "KV-cache blocks currently allocated, by model", ["model"])
_M_EXHAUSTED = _metrics.counter(
    "serving_kv_cache_exhausted_total",
    "Allocations rejected because the block pool was empty, by model",
    ["model"])
_M_HEADROOM = _metrics.gauge(
    "serving_kv_cache_headroom",
    "Fraction of KV-cache blocks still free (1 - occupancy), by model",
    ["model"])
_M_FRAG = _metrics.gauge(
    "serving_kv_cache_fragmentation",
    "Internal fragmentation of allocated blocks: 1 - tokens_written / "
    "(used_blocks * block_size); 0 when nothing is allocated, by model",
    ["model"])
_M_ALLOCS = _metrics.counter(
    "serving_kv_cache_alloc_blocks_total",
    "Blocks handed out by the free list, by model", ["model"])
_M_FREES = _metrics.counter(
    "serving_kv_cache_free_blocks_total",
    "Blocks returned to the free list, by model", ["model"])
_M_ROW_BYTES = _metrics.gauge(
    "kv_cache_row_bytes",
    "Bytes one token of one layer takes in the KV cache (every pool of "
    "its row), by model", ["model"])
_M_CACHE_LAYERS = _metrics.gauge(
    "kv_cache_layers",
    "Rows one token keeps in the KV cache (the pools' leading axis): the "
    "model's layers, fewer where some keep state instead, more where a "
    "layer has several attention sublayers, by model", ["model"])
_M_SESS_BLOCKS = _metrics.histogram(
    "serving_kv_blocks_per_session",
    "Blocks one sequence held when it was freed, by model", ["model"],
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))


_M_GROUP_BLOCKS = _metrics.gauge(
    "serving_kv_cache_group_used_blocks",
    "KV-cache blocks currently allocated in one layer group's pool (a "
    "model with several groups only), by model and group",
    ["model", "group"])
_M_GROUP_PEAK = _metrics.gauge(
    "serving_kv_cache_group_occupancy_peak",
    "Largest fraction of one layer group's blocks in use since the cache "
    "was built (a model with several groups only), by model and group",
    ["model", "group"])
_M_RING_WRAPS = _metrics.counter(
    "serving_kv_cache_ring_wraps_total",
    "Ring entries of a window group's tables that a decode step began "
    "to write over (the block they held has left the window), summed "
    "over window groups, by model", ["model"])


_M_SLOTS = _metrics.gauge(
    "serving_state_slots_used",
    "Recurrent-state slots held by live sequences, by model", ["model"])
_M_STATE_BYTES = _metrics.gauge(
    "serving_state_bytes",
    "Bytes of recurrent state held by live sequences (both versions of "
    "every slot in use), by model", ["model"])
_M_STATE_PREFILL = _metrics.counter(
    "serving_state_prefill_slots_total",
    "State slots written by a prefill, by model", ["model"])


def _scatter_pages(k_pages, v_pages, k, v, blocks, offsets):
    """``k``/``v`` ``[L, N, ...]`` (one row's values each) into slot
    ``(blocks[n], offsets[n])`` of every layer.  A slot whose block id
    is out of range (the pad rows of a decode bucket, the pad positions
    of a prefill bucket) is dropped: it writes nowhere.  A row of one
    pool has no ``v_pages`` and no ``v`` (both ``None``)."""
    layers, count = k.shape[:2]
    # the layer is an index like block and offset, so that the indexed
    # axes are the pool's leading ones and a row is written where it
    # lies; sliced (``[:, blocks, offsets]``) the pool is transposed to
    # bring the layer axis inside, and back: two copies of it
    at = (jnp.arange(layers)[:, None], blocks[None, :], offsets[None, :])

    def put(pages, rows):
        if pages is None:
            return None
        rows = rows.reshape(layers, count, -1).astype(pages.dtype)
        return pages.at[at].set(rows, mode="drop")

    return put(k_pages, k), put(v_pages, v)


# one program per N (a prefill or decode bucket); the pools are donated,
# so the outputs alias them and the write is in place
_write_pages = jax.jit(_scatter_pages, donate_argnums=(0, 1))


def _group_rows(rows, layers):
    """The rows ``[L, N, ...]`` of a program's that are one group's
    ``layers``: a slice where they lie together (a model may order its
    rows by group), else a gather."""
    if rows is None:
        return None
    if layers == tuple(range(layers[0], layers[-1] + 1)):
        return rows[layers[0]:layers[-1] + 1]
    return rows[np.asarray(layers)]


def _scatter_groups(k_pools, v_pools, k, v, blocks, offsets, layers):
    """:func:`_scatter_pages` a layer group: ``k``/``v`` ``[L, N, ...]``
    over the layers of all groups, ``layers[g]`` the rows that are group
    ``g``'s, into its pools at its own slots ``(blocks[g], offsets[g])``."""
    done = [_scatter_pages(kp, vp, _group_rows(k, ls), _group_rows(v, ls),
                           b, o)
            for kp, vp, b, o, ls in zip(k_pools, v_pools, blocks, offsets,
                                        layers)]
    return tuple(d[0] for d in done), tuple(d[1] for d in done)


# one program per N for all the groups of a model that has several
_write_groups = jax.jit(_scatter_groups, donate_argnums=(0, 1),
                        static_argnames=("layers",))


class _Group(object):
    """One layer group of a cache: its pools, free list and tables."""

    def __init__(self, layers, window, num_blocks, block_size, max_tokens):
        self.layers = tuple(int(i) for i in layers)
        self.window = None if window is None else int(window)
        self.num_blocks = int(num_blocks)
        self.k_pages = self.v_pages = None
        self.free = list(range(self.num_blocks - 1, -1, -1))
        self.tables = {}       # seq_id -> [block ids]
        self.owner = {}        # block id -> seq_id, for every block in use
        self.peak = 0          # most blocks in use at once, so far
        whole = None if max_tokens is None \
            else -(-int(max_tokens) // block_size)
        self.ring = None
        if window is not None:
            if self.window < block_size or self.window % block_size:
                raise MXNetError("a window of %d tokens is not whole "
                                 "blocks of %d" % (self.window, block_size))
            if whole is None:
                raise MXNetError("a cache with a window group needs "
                                 "max_tokens, the longest sequence")
            self.ring = self.window // block_size + 1
        #: the group's columns of a sequence's table row
        self.width = whole if self.ring is None else min(self.ring, whole)

    def blocks_for(self, num_tokens, block_size):
        blocks = -(-max(num_tokens, 1) // block_size)
        return blocks if self.ring is None else min(blocks, self.ring)

    def entry(self, positions, block_size):
        """The table entry that holds each of ``positions`` (numpy)."""
        index = positions // block_size
        return index if self.ring is None else index % self.ring

    @property
    def used(self):
        return self.num_blocks - len(self.free)


def _scatter_state(pools, rows, at):
    """A prefill's state rows (``[state layers, ...]`` an array of
    ``rows``) into rows ``at`` ``int32 [state layers]`` of the state
    pools."""
    return tuple(pool.at[at].set(row.astype(pool.dtype))
                 for pool, row in zip(pools, rows))


_write_state = jax.jit(_scatter_state, donate_argnums=(0,))


class PagedKVCache(object):
    """Free-list block allocator + per-sequence block tables + the pools.

    Thread-safe: the generation lane allocates/frees from its loop
    thread while the front-end frees on client disconnect.  All index
    math is host-side numpy; the pools ``k_pages``/``v_pages`` are
    device arrays that stay on the device between dispatches and are
    donated to, and re-bound from, every write (on XLA:CPU a device
    array is host memory, so there is no second path).
    """

    def __init__(self, num_layers, num_heads=None, head_dim=None,
                 block_size=None, num_blocks=None, dtype=np.float32,
                 model="default", row=None, state=None, state_slots=None,
                 groups=None, max_tokens=None):
        self.block_size = int(block_size or default_block_size())
        self.num_layers = int(num_layers)
        # one group of every layer unless the model names several;
        # ``num_blocks`` is then a number a group
        groups = tuple(groups or ((tuple(range(self.num_layers)), None),))
        sizes = num_blocks if isinstance(num_blocks, (tuple, list)) \
            else [num_blocks or default_num_blocks()] * len(groups)
        if len(sizes) != len(groups) or self.block_size <= 0 \
                or min(int(n) for n in sizes) <= 0:
            raise MXNetError("PagedKVCache needs positive block_size/"
                             "num_blocks, one num_blocks a layer group "
                             "(got %d/%r for %d group(s))"
                             % (self.block_size, num_blocks, len(groups)))
        if sorted(i for layers, _ in groups for i in layers) \
                != list(range(self.num_layers)):
            raise MXNetError("the layer groups %r do not name each of %d "
                             "layers once" % (groups, self.num_layers))
        self._groups = [_Group(layers, window, n, self.block_size,
                               max_tokens)
                        for (layers, window), n in zip(groups, sizes)]
        self.num_blocks = self._groups[0].num_blocks
        # the row is the model's to define; heads and head size alone
        # describe the plain key row + value row
        self.row = row or CacheRow("kv", int(num_heads) * int(head_dim),
                                   dtype, 2)
        self.model = model
        self._dtype = np.dtype(self.row.dtype)
        _M_ROW_BYTES.labels(model).set(self.row.bytes)
        _M_CACHE_LAYERS.labels(model).set(self.num_layers)
        # the recurrent layers' state, one slot a sequence (none for a
        # model whose every layer keeps rows per token)
        self.state = state
        if state and not state_slots:
            raise MXNetError("PagedKVCache needs state_slots for a model "
                             "with recurrent state: one a live sequence, "
                             "at least the largest decode bucket")
        self.num_slots = int(state_slots) if state else 0
        self._zero_pools()
        self._zero_state()
        self._lock = threading.Lock()
        self._free_slots = list(range(self.num_slots - 1, -1, -1))
        self._slots = {}       # seq_id -> state slot
        self._lengths = {}     # seq_id -> tokens written
        self._wraps = _M_RING_WRAPS.labels(model)
        self._occ = _M_OCC.labels(model)
        self._used = _M_BLOCKS.labels(model)
        self._exhausted = _M_EXHAUSTED.labels(model)
        self._headroom = _M_HEADROOM.labels(model)
        self._frag = _M_FRAG.labels(model)
        self._allocs = _M_ALLOCS.labels(model)
        self._frees = _M_FREES.labels(model)
        self._sess_blocks = _M_SESS_BLOCKS.labels(model)
        # book the device-resident page pools into the memory ledger
        # (live jax arrays: the "all" row the reconcile gate sums); the
        # finalizer releases the row when the cache (hot-swap, backend
        # teardown) is collected
        self._ledger_key = id(self)
        _memory.tag("kv_cache", self._ledger_key, self.pool_bytes)
        weakref.finalize(self, _memory.untag, "kv_cache",
                         self._ledger_key)
        if state:
            self._slots_used = _M_SLOTS.labels(model)
            self._state_held = _M_STATE_BYTES.labels(model)
            self._state_prefills = _M_STATE_PREFILL.labels(model)
            _memory.tag("recurrent_state", self._ledger_key,
                        self.state_bytes)
            weakref.finalize(self, _memory.untag, "recurrent_state",
                             self._ledger_key)

    # the first group's pools under the names the one pool always had
    # (what reads them: the benchmark's driver, tests, chip_smoke.py);
    # the programs are handed :meth:`program_pools`

    @property
    def k_pages(self):
        return self._groups[0].k_pages

    @property
    def v_pages(self):
        return self._groups[0].v_pages

    @property
    def table_width(self):
        """Columns of a sequence's table row (:meth:`block_table`) where
        it is sized by the cache: every group's side by side (known with
        ``max_tokens`` only)."""
        return sum(g.width for g in self._groups)

    def program_pools(self):
        """``(k_pages, v_pages)`` as the model's decode program takes
        them: the arrays of a cache with one group, a tuple of arrays,
        one a group, of a cache with several."""
        if len(self._groups) == 1:
            return self.k_pages, self.v_pages
        return (tuple(g.k_pages for g in self._groups),
                tuple(g.v_pages for g in self._groups))

    def _pool_shape(self, group):
        return (len(group.layers), group.num_blocks, self.block_size,
                int(self.row.width))

    def _zero_pools(self):
        # drop the old pair first: two pools never exist at once
        for g in self._groups:
            g.k_pages = g.v_pages = None
            g.k_pages = jnp.zeros(self._pool_shape(g), self._dtype)
            if self.row.pools == 2:
                g.v_pages = jnp.zeros(self._pool_shape(g), self._dtype)

    def _zero_state(self):
        self.state_pools = None
        if self.state:
            # one row more than the slots': where pad rows write
            rows = self.state.layers * 2 * self.num_slots + 1
            self.state_pools = tuple(
                jnp.zeros((rows,) + tuple(shape), dtype)
                for shape, dtype in self.state.rows)

    @property
    def pool_bytes(self):
        return self.row.pools * self._dtype.itemsize * sum(
            int(np.prod(self._pool_shape(g))) for g in self._groups)

    @property
    def state_bytes(self):
        """Bytes of the state pool: two versions of every slot, and the
        pad rows' row."""
        return sum(int(p.nbytes) for p in self.state_pools or ())

    # -- allocation --------------------------------------------------

    def allocate(self, seq_id, num_tokens):
        """Reserve capacity for ``num_tokens`` total tokens of ``seq_id``
        in every layer group (a window group: its ring, at most).

        Idempotent growth: call again with a larger total to extend.
        Raises :class:`CacheExhaustedError` (and allocates nothing) if
        a group's free list cannot cover the extension — a failed grow
        never strands partially-allocated blocks.
        """
        chaos.visit("serving.kv_alloc", name=str(seq_id))
        with self._lock:
            grows = [g.blocks_for(num_tokens, self.block_size)
                     - len(g.tables.get(seq_id, ())) for g in self._groups]
            short = next((i for i, (g, grow) in enumerate(
                zip(self._groups, grows)) if grow > len(g.free)), None)
            no_slot = bool(self.state) and seq_id not in self._slots \
                and not self._free_slots
            if short is not None or no_slot:
                self._exhausted.inc()
                g = self._groups[short or 0]
                of_group = "" if len(self._groups) == 1 \
                    else " in layer group %d" % (short or 0)
                err = CacheExhaustedError(
                    "kv cache exhausted: seq %r needs a state slot, none "
                    "free of %d" % (seq_id, self.num_slots) if no_slot else
                    "kv cache exhausted: seq %r needs %d more block(s)%s, "
                    "%d free of %d" % (seq_id, grows[short], of_group,
                                       len(g.free), g.num_blocks))
                # occupancy hints the serving front-end forwards in the
                # 429 error body so clients can back off proportionally
                # (of the group that ran out)
                err.kv_cache_occupancy = g.used / float(g.num_blocks)
                err.kv_cache_blocks_free = len(g.free)
                err.kv_cache_blocks_total = g.num_blocks
                raise err
            for g, grow in zip(self._groups, grows):
                if grow > 0:
                    fresh = [g.free.pop() for _ in range(grow)]
                    g.tables[seq_id] = g.tables.get(seq_id, []) + fresh
                    g.owner.update(dict.fromkeys(fresh, seq_id))
                    g.peak = max(g.peak, g.used)
                    self._lengths.setdefault(seq_id, 0)
                    self._allocs.inc(grow)
            if self.state and seq_id not in self._slots:
                self._slots[seq_id] = self._free_slots.pop()
            self._set_gauges_locked()

    def free(self, seq_id):
        """Return ``seq_id``'s blocks, of every layer group (and its
        state slot), to the pools; returns the block ids freed in the
        first group (empty for an unknown sequence — freeing is always
        safe to call from retire paths)."""
        with self._lock:
            self._lengths.pop(seq_id, None)
            slot = self._slots.pop(seq_id, None)
            if slot is not None:
                self._free_slots.append(slot)
            freed = []
            for g in self._groups:
                table = g.tables.pop(seq_id, None) or []
                freed.append(table)
                for block in table:
                    del g.owner[block]
                g.free.extend(reversed(table))
            count = sum(len(table) for table in freed)
            if count:
                self._frees.inc(count)
                self._sess_blocks.observe(count)
            self._set_gauges_locked()
            return list(freed[0])

    def _set_gauges_locked(self):
        first = self._groups[0]
        if len(self._groups) > 1:
            for i, g in enumerate(self._groups):
                _M_GROUP_BLOCKS.labels(self.model, str(i)).set(g.used)
                _M_GROUP_PEAK.labels(self.model, str(i)).set(
                    g.peak / float(g.num_blocks))
        used = first.used
        self._used.set(used)
        self._occ.set(used / float(self.num_blocks))
        self._headroom.set(len(first.free) / float(self.num_blocks))
        if used:
            written = sum(self._lengths.values())
            self._frag.set(1.0 - written / float(used * self.block_size))
        else:
            self._frag.set(0.0)
        if self.state:
            self._slots_used.set(len(self._slots))
            self._state_held.set(2 * len(self._slots) * self.state.bytes)

    # -- reads -------------------------------------------------------

    def length(self, seq_id):
        return self._lengths.get(seq_id, 0)

    def sequences(self):
        with self._lock:
            return sorted(self._groups[0].tables)

    def block_table(self, seq_id, max_blocks):
        """Padded ``int32[max_blocks]`` table for a decode dispatch, a
        new array every call.

        Pad entries point at block 0 — harmless, because decode
        attention masks scores past the context length before softmax
        (``-1e30`` → exp underflows to exact ``0.0``), so whatever those
        rows hold never reaches the output bits.

        The generation loop calls this once a sequence, when it joins
        the decode batch, and keeps the row (a sequence reserved whole
        at admission is never grown, so its row holds until
        :meth:`free`); a decode step calls it for no one.  The other
        callers are ``warmup()`` and code that drives a backend by
        hand, which build the table of every call.
        """
        table = self._groups[0].tables.get(seq_id)
        if table is None:
            raise MXNetError("unknown sequence %r" % (seq_id,))
        if len(self._groups) > 1:
            # the groups' tables side by side, each as wide as the
            # cache made it (a window group's: its ring)
            if max_blocks != self.table_width:
                raise MXNetError(
                    "a table row of this cache's %d layer groups is %d "
                    "wide, not %d" % (len(self._groups), self.table_width,
                                      max_blocks))
            out, at = np.zeros(max_blocks, dtype=np.int32), 0
            for g in self._groups:
                out[at:at + len(g.tables[seq_id])] = g.tables[seq_id]
                at += g.width
            return out
        if len(table) > max_blocks:
            raise MXNetError(
                "sequence %r spans %d blocks > table width %d"
                % (seq_id, len(table), max_blocks))
        out = np.zeros(max_blocks, dtype=np.int32)
        out[:len(table)] = table
        return out

    def state_slots(self, block_tables, positions):
        """``int32 [B]``: the state slot of the sequence each row of a
        decode call belongs to, found through the first block of its
        table (the slot rides with the block table); ``num_slots`` for a
        pad row (position 0), which the decode program reads as "writes
        nowhere".  ``LMBackend.decode`` calls this once for every table
        array it is handed and keeps the answer with the table: a
        sequence holds its slot as long as its blocks, so the rows of
        an unchanged table have unchanged slots."""
        tables = np.asarray(block_tables)
        out = np.full(len(tables), self.num_slots, dtype=np.int32)
        with self._lock:
            for row in np.flatnonzero(np.asarray(positions)):
                owner = self._groups[0].owner.get(int(tables[row, 0]))
                if owner is None:
                    raise MXNetError(
                        "state_slots: row %d reads block %d, which is "
                        "free" % (row, tables[row, 0]))
                out[row] = self._slots[owner]
        return out

    def swap_state(self, pools):
        """Re-bind the state pools to what the decode program that was
        handed them (donated) gave back."""
        self.state_pools = tuple(pools)

    def state_lost(self, exc):
        """A program failed after the state pools were donated to it:
        rebuild them zeroed and return the error to raise (every live
        sequence lost its state); ``None`` if they are intact."""
        if not any(p.is_deleted() for p in self.state_pools or ()):
            return None
        self._zero_state()
        return CachePoolLostError(
            "kv cache %r: a program failed after the state pool was "
            "donated (%s: %s); the pool was rebuilt zeroed and every "
            "live sequence lost its state"
            % (self.model, type(exc).__name__, exc))

    # -- writes ------------------------------------------------------

    def _write_locked(self, k, v, blocks, offsets):
        """Scatter into the donated pools and re-bind them: ``blocks``
        and ``offsets`` are a list of vectors, one a layer group.
        Returns the host bytes handed to the device (the slot
        indices)."""
        groups = self._groups
        try:
            if len(groups) == 1:
                g = groups[0]
                g.k_pages, g.v_pages = _write_pages(
                    g.k_pages, g.v_pages, k, v, blocks[0], offsets[0])
            else:
                k_pools, v_pools = _write_groups(
                    *self.program_pools(), k, v, tuple(blocks),
                    tuple(offsets), layers=tuple(g.layers for g in groups))
                for g, k_pool, v_pool in zip(groups, k_pools, v_pools):
                    g.k_pages, g.v_pages = k_pool, v_pool
        except Exception as exc:
            if not any(p is not None and p.is_deleted() for g in groups
                       for p in (g.k_pages, g.v_pages)):
                raise       # refused before donation: pool untouched
            self._zero_pools()
            self._lengths = dict.fromkeys(self._lengths, 0)
            raise CachePoolLostError(
                "kv cache %r: a pool write failed after donation (%s: "
                "%s); the pool was rebuilt zeroed and every live "
                "sequence lost its pages"
                % (self.model, type(exc).__name__, exc)) from exc
        return sum(a.nbytes for a in blocks) \
            + sum(a.nbytes for a in offsets)

    def write_prefill(self, seq_id, k, v, length, state=None):
        """Store prompt K/V: ``k``/``v`` device arrays ``[L, T, heads *
        dim]`` (or ``[L, T, heads, dim]``) as the prefill dispatch
        produced them, ``T`` its bucket; positions ``< length`` are
        written, the bucket's pad positions are dropped; a window group
        takes the prompt's last blocks, as many as the sequence's ring
        has entries (the tokens before them have left every window a
        decode step will look through).  ``state``
        (a model with recurrent layers): the prompt's state rows taken
        at ``length``, written to version ``length % 2`` of the
        sequence's slot, where the decode step at position ``length``
        reads them.

        Requires a prior :meth:`allocate` covering ``length`` tokens.
        Call it only after the prefill dispatch succeeded: it targets
        the sequence's own reserved slots and nothing else, so a retried
        write stores the same values again.  Returns the host bytes
        handed to the device (two ``int32[T]`` index vectors).
        """
        bucket, length = int(k.shape[1]), int(length)
        with self._lock:
            positions = np.arange(length)
            blocks, offsets = [], []
            for g in self._groups:
                at = np.full(bucket, g.num_blocks, dtype=np.int32)
                table = g.tables.get(seq_id)
                if (table is None or length > bucket or len(table)
                        < g.blocks_for(length, self.block_size)):
                    raise MXNetError(
                        "write_prefill(%r, %d tokens of a bucket of %d) "
                        "exceeds allocation" % (seq_id, length, bucket))
                # the first token kept: a ring holds the last blocks
                first = max(0, -(-length // self.block_size)
                            - len(table)) * self.block_size
                at[first:length] = np.asarray(table, dtype=np.int32)[
                    g.entry(positions[first:], self.block_size)]
                blocks.append(at)
                offsets.append(np.zeros(bucket, dtype=np.int32))
                offsets[-1][:length] = positions % self.block_size
            staged = self._write_locked(k, v, blocks, offsets)
            if self.state:
                staged += self._write_state_locked(
                    self._slots[seq_id], length % 2, state)
            self._lengths[seq_id] = max(self._lengths.get(seq_id, 0),
                                        length)
        return staged

    def _write_state_locked(self, slot, version, rows):
        at = ((np.arange(self.state.layers, dtype=np.int32) * 2 + version)
              * self.num_slots + slot).astype(np.int32)
        try:
            self.state_pools = _write_state(self.state_pools, tuple(rows),
                                            at)
        except Exception as exc:
            lost = self.state_lost(exc)
            if lost is None:
                raise
            raise lost from exc
        self._state_prefills.inc()
        return at.nbytes

    def write_tokens(self, block_tables, positions, k, v):
        """Store one decode step's K/V for the whole batch in one call:
        ``k``/``v`` device arrays ``[L, B, heads * dim]`` (or ``[L, B,
        heads, dim]``) as the decode dispatch produced them, row ``i``
        going to token position ``positions[i]`` of the sequence whose
        padded table (:meth:`block_table`) is ``block_tables[i]``: the
        arrays the dispatch itself read through, so a step can be
        written by whoever dispatched it, with no sequence named.  A
        row at position 0 is a pad row of the bucket (a live row follows
        a prefill of at least one token) and writes nowhere.  A row
        whose slot lies in no allocated block is refused before
        anything is written.  Writing a slot again with the same values
        changes nothing, so a step dispatched twice (a retry, a queued
        step thrown away and run again) is harmless.  Returns the host
        bytes handed to the device (two ``int32[B]`` index vectors)."""
        tables = np.asarray(block_tables, dtype=np.int32)
        positions = np.asarray(positions, dtype=np.int32)
        bucket = int(k.shape[1])
        if tables.ndim != 2 or positions.shape != (bucket,) \
                or len(tables) != bucket:
            raise MXNetError(
                "write_tokens: tables %r, positions %r, %d rows"
                % (tables.shape, positions.shape, bucket))
        groups = self._groups
        # a cache with one group takes a table of any width (its
        # caller's); the groups of several lie side by side in a row
        widths = [tables.shape[1]] if len(groups) == 1 \
            else [g.width for g in groups]
        if sum(widths) != tables.shape[1]:
            raise MXNetError(
                "write_tokens: a table row of this cache's %d layer "
                "groups is %d wide, not %d"
                % (len(groups), sum(widths), tables.shape[1]))
        live = np.flatnonzero(positions)
        offsets = positions % self.block_size
        blocks, column, wraps = [], 0, 0
        for g, width in zip(groups, widths):
            index = g.entry(positions, self.block_size)
            if positions.min() < 0 or index.max() >= width:
                raise MXNetError(
                    "write_tokens: positions %d..%d outside a table of %d "
                    "blocks" % (positions.min(), positions.max(), width))
            at = np.full(bucket, g.num_blocks, dtype=np.int32)
            at[live] = tables[live, column + index[live]]
            blocks.append(at)
            column += width
            if g.ring is not None:
                # a step that begins a block whose entry held another
                wraps += int(((offsets[live] == 0) & (
                    positions[live] // self.block_size >= g.ring)).sum())
        with self._lock:
            for g, at in zip(groups, blocks):
                owners = [g.owner.get(b) for b in at[live].tolist()]
                if None in owners:
                    row = live[owners.index(None)]
                    raise MXNetError(
                        "write_tokens: row %d at position %d exceeds "
                        "allocation (block %d is free)"
                        % (row, positions[row], at[row]))
            staged = self._write_locked(k, v, blocks,
                                        [offsets] * len(groups))
            for seq_id, pos in zip(owners, positions[live].tolist()):
                if pos >= self._lengths[seq_id]:
                    self._lengths[seq_id] = pos + 1
        if wraps:
            self._wraps.inc(wraps)
        return staged

    # -- introspection ----------------------------------------------

    def stats(self):
        """The gauges as a dict.  ``blocks``, ``used``, ``free``,
        ``occupancy``, ``headroom`` and ``fragmentation`` are the
        **first** layer group's (a model with window layers puts its
        global layers there: the blocks that grow with the context);
        ``groups`` has ``blocks``, ``used``, ``occupancy``, ``peak``
        (the largest occupancy so far), ``window`` and ``layers`` of
        every group, the first among them."""
        with self._lock:
            first = self._groups[0]
            used = first.used
            written = sum(self._lengths.values())
            return {"blocks": self.num_blocks, "used": used,
                    "free": len(first.free),
                    "occupancy": used / float(self.num_blocks),
                    "headroom": len(first.free) / float(self.num_blocks),
                    "fragmentation": (1.0 - written
                                      / float(used * self.block_size))
                                     if used else 0.0,
                    "sequences": len(first.tables),
                    "groups": [
                        {"layers": len(g.layers), "window": g.window,
                         "blocks": g.num_blocks, "used": g.used,
                         "occupancy": g.used / float(g.num_blocks),
                         "peak": g.peak / float(g.num_blocks)}
                        for g in self._groups],
                    "block_size": self.block_size,
                    "pool_bytes": self.pool_bytes,
                    "state_slots": self.num_slots,
                    "state_slots_used": len(self._slots),
                    "state_bytes": self.state_bytes}
