"""A batch of rows stepped through a pool of per-sequence states that
stays where it lies: the Pallas scaffold the recurrent layers' decode
kernels share (:func:`~mxnet_tpu.ops.gated_delta.gated_delta_update`,
:func:`~mxnet_tpu.ops.state_space.ssm_update`).

The grid runs over the batch's rows.  Row ``i``'s state block is fetched
from ``pool[read[i]]`` and stored to ``pool[write[i]]`` by the pipeline's
own copies (the next row's fetch in flight behind this row's arithmetic),
the two index vectors are scalar-prefetched, and the pool is aliased to
the output, so that the rows of the pool no one writes stay as they
are.  A pad row of the batch names a row of the pool no sequence owns.
"""

from __future__ import annotations

import jax

__all__ = ["rows_through_pool"]


def rows_through_pool(kernel, scalars, rows, pool, read, write, outs, *,
                      scope, interpret=False, vmem_limit_bytes=None):
    """Run ``kernel`` once a row of the batch with that row's state.

    ``kernel(read_ref, write_ref, *scalar_refs, *row_refs, state_ref,
    *out_refs, new_state_ref)``: ``scalars`` are float32 vectors kept
    in SMEM whole (the kernel indexes them by ``program_id(0)``);
    ``rows`` are arrays ``[B, ...]`` handed over a row at a time (block
    ``[1, ...]``, the trailing axes whole); ``state_ref`` is
    ``pool[read[i]]`` and ``new_state_ref`` goes to ``pool[write[i]]``,
    both ``[1, ...]``; ``outs`` are the ``jax.ShapeDtypeStruct`` of
    the per-row outputs ``[B, ...]``.  ``read``/``write`` int32 ``[B]``,
    in range.  Returns ``(*outs, pool)``; with ``pool`` donated by the
    caller it is updated where it lies.  ``scope`` names the kernel in
    a trace (the innermost scope); ``vmem_limit_bytes`` where the
    double-buffered state blocks pass the compiler's default."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz = rows[0].shape[0]

    def row(shape):
        block = tuple(shape[1:])
        return pl.BlockSpec((1,) + block,
                            lambda i, *_: (i,) + (0,) * len(block))

    state_at = (1,) + tuple(pool.shape[1:])
    rest = (0,) * (pool.ndim - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(scalars),
        grid=(bsz,),
        in_specs=[row(a.shape) for a in rows]
        + [pl.BlockSpec(state_at, lambda i, rd, *_: (rd[i],) + rest)],
        out_specs=[row(o.shape) for o in outs]
        + [pl.BlockSpec(state_at, lambda i, rd, wr, *_: (wr[i],) + rest)])
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            **({} if vmem_limit_bytes is None
               else {"vmem_limit_bytes": vmem_limit_bytes}))
    # the scope names the kernel in a trace; it has to be the innermost
    with jax.named_scope(scope):
        return pl.pallas_call(
            kernel,
            out_shape=list(outs)
            + [jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            grid_spec=grid_spec,
            input_output_aliases={2 + len(scalars) + len(rows): len(outs)},
            interpret=interpret, **kwargs)(
            read.astype("int32"), write.astype("int32"), *scalars, *rows,
            pool)
