"""Row-block grid for the row-wise Pallas epilogues.

LayerNorm, GELU+bias and the momentum step treat every row of their
``[..., C]`` operands independently, so one ``pallas_call`` over a grid
of row blocks computes the same bits as one whole-array program while
staging only a block in VMEM.  The chip's compiler refuses the
whole-array form at LM widths (an ``8x2048x1024`` activation is 64 MiB
against 16 MiB of scoped VMEM on a v5e); ``tests/test_chip_compile.py``
holds the blocked form to those widths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["interpret", "row_call"]

#: bytes of blocked operands one grid step may stage.  Pallas double-
#: buffers every block and the kernels keep fp32 temporaries of the same
#: size, so a quarter of the v5e's 16 MiB scoped VMEM is the room.
_BLOCK_BYTES = 4 << 20
_LANES = 128        # minor-dim tile
_SUBLANES = 8       # second-minor tile of a 32-bit dtype
_ROWS = 32          # second-minor tile of the narrowest dtype (int8)


def interpret():
    """Pallas interpret mode everywhere but on the chip."""
    return jax.default_backend() != "tpu"


def _blocks(rows, cols, elem_bytes, whole_rows):
    """Block ``(br, bc)`` of a ``[rows, cols]`` view whose operands,
    ``elem_bytes`` per element together, fit :data:`_BLOCK_BYTES`.
    Columns are split only for elementwise kernels (``whole_rows``
    false) whose narrowest stripe is still too wide — a long 1-D
    parameter viewed as ``[1, N]``."""
    bc = cols
    lanes = -(-cols // _LANES) * _LANES
    if not whole_rows and _SUBLANES * lanes * elem_bytes > _BLOCK_BYTES:
        bc = lanes = max(_LANES, _BLOCK_BYTES // (_SUBLANES * elem_bytes)
                         // _LANES * _LANES)
    br = _BLOCK_BYTES // (lanes * elem_bytes)
    if br >= rows:
        return rows, bc
    return max(_ROWS, br // _ROWS * _ROWS), bc


def row_call(kernel, out_dtypes, tensors, vectors=(), whole_rows=True,
             block_rows=None):
    """``kernel(*tensor_refs, *vector_refs, *out_refs)`` over row blocks.

    ``tensors`` share one shape ``[..., C]`` and are blocked
    ``(br, bc)`` over their ``[R, C]`` view; ``vectors`` are ``[C]``
    and arrive as ``(1, bc)`` so they broadcast against a block; one
    output per ``out_dtypes`` entry, shaped like the tensors.  A ragged
    last block reads unspecified rows and its out-of-range writes are
    dropped — harmless because no row reads another.  ``block_rows``
    overrides the derived row count: the parity grid uses it to put a
    ragged multi-block run on a small array.

    Under a multi-device default mesh the call runs inside
    ``shard_map`` (GSPMD cannot partition a Mosaic kernel): each device
    takes its slice of dim 0 along ``data`` when that divides, and the
    whole array otherwise."""
    import jax.experimental.pallas as pl

    def local(*arrays):
        shape = arrays[0].shape
        cols = shape[-1] if shape else 1
        rows = 1
        for s in shape[:-1]:
            rows *= s
        elem_bytes = sum(max(jnp.dtype(d).itemsize, 4) for d in
                         [t.dtype for t in tensors] + list(out_dtypes))
        br, bc = _blocks(rows, cols, elem_bytes, whole_rows)
        if block_rows is not None:
            br = min(block_rows, rows)
        blocked = pl.BlockSpec((br, bc), lambda i, j: (i, j))
        vec = pl.BlockSpec((1, bc), lambda i, j: (0, j))
        outs = pl.pallas_call(
            kernel,
            grid=(-(-rows // br), -(-cols // bc)),
            in_specs=[blocked] * len(tensors) + [vec] * len(vectors),
            out_specs=[blocked] * len(out_dtypes),
            out_shape=[jax.ShapeDtypeStruct((rows, cols), d)
                       for d in out_dtypes],
            interpret=interpret(),
        )(*[t.reshape(rows, cols) for t in arrays[:len(tensors)]],
          *[v.reshape(1, cols) for v in arrays[len(tensors):]])
        return [o.reshape(shape) for o in outs]

    from ...parallel import get_default_mesh

    mesh = get_default_mesh()
    if mesh is not None and mesh.size > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        shape = tensors[0].shape
        split = P("data") if (
            shape and "data" in mesh.axis_names
            and shape[0] % mesh.shape["data"] == 0) else P()
        local = shard_map(
            local, mesh=mesh,
            in_specs=(split,) * len(tensors) + (P(),) * len(vectors),
            out_specs=[split] * len(out_dtypes), check_vma=False)
    return local(*tensors, *vectors)
