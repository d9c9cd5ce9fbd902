"""What the generation lane needs to know of a language model.

:class:`~mxnet_tpu.serving.LMBackend` serves any model that hands it an
:class:`LMDefinition`: the functions its jitted programs are built from
and the row its paged cache keeps per token.  The backend, the
scheduler and the cache's allocator know nothing else of the model.
"""

import collections

__all__ = ["LMDefinition"]


class LMDefinition(collections.namedtuple(
        "LMDefinition", "cfg forward prefill decode cache_row book "
                        "prepare cache_layers state cache_groups",
        defaults=(None, None, None))):
    """One model, as the generation lane serves it.

    - ``cfg``: a dict with at least ``seq_len`` (the context limit the
      block tables are sized for), ``num_layers`` and ``num_classes``
      (the vocabulary admission checks token ids against).
    - ``forward(params, tokens [B, T]) -> logits [B, T, V]``: the whole
      sequence, no cache.
    - ``prefill(params, tokens [T], length) -> (logits [V], k_rows,
      v_rows, counts)``: one padded prompt; the logits after token
      ``length - 1``; the cache rows ``[L, T, width]`` of every pool of
      the row (``v_rows`` is ``None`` for a row of one pool).
    - ``decode(params, tokens [B], positions [B], k_pages, v_pages,
      block_tables, context_lens) -> (logits [B, V], k_rows [L, B,
      width], v_rows, counts)``: one token a sequence through the paged
      pools, read as of before the step.
    - ``cache_row``: the :class:`~mxnet_tpu.ops.kv_cache.CacheRow`.
    - ``book(model, counts)``: adds a call's ``counts`` (a small integer
      vector the program computed, copied back with the logits) to the
      model's counters; ``None`` where the programs count nothing
      (their ``counts`` is ``None`` then).
    - ``prepare(params) -> params``: applied once before the weights are
      placed (a quantized head); ``None`` for as they are.
    - ``cache_layers``: how many of the model's layers keep a row per
      token (the leading axis of ``k_rows`` and of the paged pools);
      ``None`` for all ``num_layers`` of them.
    - ``state``: the :class:`~mxnet_tpu.ops.kv_cache.StateRows` of the
      layers that keep a fixed-size **state per sequence** instead
      (recurrent layers), ``None`` for a model without any.  With one,
      ``prefill`` returns a fifth value, the state rows of the prompt
      taken at ``length`` (a tuple, one ``[state layers, ...]`` array a
      row of ``state.rows``), and ``decode`` takes two more arguments
      and returns one more value: ``decode(params, tokens, positions,
      k_pages, v_pages, block_tables, context_lens, state_pools, slots)
      -> (logits, k_rows, v_rows, counts, state_pools)``.  The pools are
      the cache's (``[state layers * 2 * slots + 1, ...]``: two versions a
      slot, row ``(layer * 2 + version) * slots + slot``, and a last
      row for pad rows to write), donated to
      the call and written where they lie: row ``i`` reads version
      ``positions[i] % 2`` of slot ``slots[i]`` and writes the other;
      a slot id of ``slots`` or more is a pad row.
    - ``cache_groups``: ``None`` where every cached layer keeps its
      rows as long as the sequence lives (one pool, one table), else
      the **layer groups** of the cache (:mod:`~mxnet_tpu.ops.kv_cache`),
      ``((layers, window), ...)``: ``layers`` the rows of ``k_rows``
      that are the group's (each of ``cache_layers`` in one group; a
      model may order its rows by group, and a group whose rows lie
      together is written without a gather), ``window`` ``None`` or the
      tokens a layer of the group looks back over, the current one
      counted (a sliding-window layer).  The cache then keeps a pool a
      group, and the programs see the groups: ``decode`` takes
      ``k_pages`` and ``v_pages`` as tuples, one pool ``[group's
      layers, group's blocks, block_size, width]`` a group, and
      ``block_tables`` ``int32 [B, table_width]`` with the groups'
      tables side by side in a row, each ``ceil(seq_len / block_size)``
      wide but for a window group's, whose table is a ring of at most
      ``window / block_size + 1`` entries (token ``p`` in entry ``(p //
      block_size) mod ring``).  Put the group without a window first:
      the cache's gauges of one pool (``occupancy``) are the first
      group's.
    """

    __slots__ = ()
