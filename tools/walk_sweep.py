"""Sweep: device time of the block-table walk alone (``ops/paged_attention.
py``: one kernel under four decode bodies and the ring) over body x rows x
context x chunk bytes, at the shapes the six serving configurations run.

What ``_WALK_CHUNK_BYTES`` and the walk's copy schedule rest on (PERF.md
§6 holds the tables this printed on the attached v5e).  Every row of a
case has the same context, so a call's time over its rows is a cost a
row plus a cost a chunk times the row's chunks: the two are fitted over
the contexts and printed under each (shape, chunk bytes).  The last case
of each is ``mixed``: the rows' contexts spread evenly from a quarter to
seven quarters of the cell's mean decode context, so that rows end
inside a chunk as they do when served (a row's last chunk costs what it
holds rounded up to half a chunk or a whole one).  Times are the device
durations of the kernel's events in a profiler trace
(``tools/flash_sweep.py``'s reduction), never the host clock; ``read``
is the share of a call's time its live pages' bytes take at 819 GB/s.

    python tools/walk_sweep.py          # every shape, the walk's own chunk
    python tools/walk_sweep.py --chunk-kib 512 1024 2048 4096
    python tools/walk_sweep.py --root .pr42/parent      # another checkout's
    python tools/walk_sweep.py --shapes smallthinker-ring --contexts 1024 4096
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

F32, BF16 = "float32", "bfloat16"
BLK = 16
HBM_BYTES_PER_S = 819e9         # one v5e chip (benchmark/peaks.json)

# the decode bucket, heads and table of every serving cell
# (benchmark/configs/*.json, benchmark/traffic/*.json) and its
# ``decode_context_tokens_mean`` (ledger, PR 41); a latent row is 640
# wide, 512 of it values
SHAPES = {
    "gpt2m-kv": dict(body="kv", rows=16, heads=16, dim=64, table=64,
                     dtype=F32, mean=366),
    "dots-latent128": dict(body="latent", rows=64, heads=128, table=256,
                           mean=1444),
    "longcat-latent64": dict(body="latent", rows=64, heads=64, table=512,
                             mean=2291),
    "qwen3next-gqa2x256": dict(body="gqa", rows=128, heads=16, groups=2,
                               dim=256, table=512, mean=1232),
    "lfm2-gqa8x64": dict(body="gqa", rows=64, heads=32, groups=8, dim=64,
                         table=512, mean=1309),
    "smallthinker-gqa4x128": dict(body="gqa", rows=48, heads=28, groups=4,
                                  dim=128, table=1024, mean=4394),
    "smallthinker-ring": dict(body="gqa", rows=48, heads=28, groups=4,
                              dim=128, table=257, window=4096, mean=4394),
}
MAX_CONTEXT = 8192


def _contexts(shape):
    """Cached tokens a row: an eighth, a quarter, a half and the whole
    of what the table (or the sweep) holds; a ring past its wrap too."""
    top = min(shape["table"] * BLK, MAX_CONTEXT)
    if "window" in shape:
        top = 2 * shape["window"]
    return [top // 8, top // 4, top // 2, top]


def _mixed(shape):
    """Cached tokens of every row, evenly from a quarter to seven
    quarters of the cell's mean (the table's width at the most)."""
    import numpy as np

    cached = np.linspace(shape["mean"] / 4, 7 * shape["mean"] / 4,
                         shape["rows"]).astype(np.int64)
    return cached if "window" in shape \
        else np.minimum(cached, shape["table"] * BLK - 1)


def _live_pages(shape, cached):
    pages = -(-cached // BLK)
    if "window" in shape:
        pages -= max(cached - shape["window"] + 1, 0) // BLK
    return pages


def _walk_kind(name):
    """A device event of the sweep's programs: the walk is their one
    custom call (the padding and slicing around a body are XLA's)."""
    return "walk" if " custom-call(" in name else None


def _case(paged, shape, contexts, interpret, seed=0):
    """``(fn, pools, {context: args})``: the body's jitted kernel, its
    pools and its operands by context (``"mixed"``: :func:`_mixed`),
    pools and tables sized for the largest context, every row's blocks
    scattered over the pool as an allocator leaves them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows, table = shape["rows"], shape["table"]
    dtype = shape.get("dtype", BF16)
    mixed = _mixed(shape)
    held = min(table, -(-max(max(contexts), int(mixed.max())) // BLK))
    rng = np.random.default_rng(seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def rand(*dims):
        return jax.random.normal(next(keys), dims, jnp.float32).astype(dtype)

    tables = np.zeros((rows, table), np.int32)
    tables[:, :held] = 1 + rng.permutation(rows * held).reshape(rows, held)
    blocks = rows * held + 1
    if shape["body"] == "latent":
        operands = (rand(rows, shape["heads"], 640), rand(rows, 640),
                    rand(blocks, BLK, 640))
        fn = lambda *a: paged._latent_decode_pallas(*a, 0.07, 512, interpret)
    else:
        heads, dim = shape["heads"], shape["dim"]
        groups = shape.get("groups", heads)
        operands = (rand(rows, heads, dim), rand(rows, groups, dim),
                    rand(rows, groups, dim), rand(blocks, BLK, groups * dim),
                    rand(blocks, BLK, groups * dim))
        scale = 1.0 / float(dim) ** 0.5
        if shape["body"] == "kv":
            fn = lambda *a: paged._kv_decode_pallas(*a, scale, interpret)
        elif "window" in shape:
            fn = lambda *a: paged._gqa_decode_pallas(
                *a, scale, interpret, window=shape["window"])
        else:
            fn = lambda *a: paged._gqa_walk_body(dim)(*a, scale, interpret)
    tables = jnp.asarray(tables)
    by_context = {
        c: operands + (tables, jnp.full((rows,), c + 1, jnp.int32))
        for c in contexts}
    by_context["mixed"] = operands + (
        tables, jnp.asarray(mixed + 1, jnp.int32))
    return jax.jit(fn), [a for a in operands if a.shape[0] == blocks], \
        by_context


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(HERE),
                    help="the checkout whose mxnet_tpu is swept")
    ap.add_argument("--shapes", nargs="*", default=sorted(SHAPES),
                    choices=sorted(SHAPES))
    ap.add_argument("--chunk-kib", nargs="*", type=int, default=None,
                    help="values of _WALK_CHUNK_BYTES (default: the "
                         "walk's own)")
    ap.add_argument("--contexts", nargs="*", type=int, default=None,
                    help="cached tokens a row (default: by the table)")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.abspath(args.root), HERE]

    import jax
    import numpy as np

    # (flash_sweep puts its own checkout first: the walk is imported
    # before it)
    from mxnet_tpu.ops import paged_attention as paged
    import flash_sweep

    dev = jax.devices()[0]
    print("device: %s %s; jax %s; walk of %s" % (
        dev.platform, dev.device_kind, jax.__version__,
        os.path.relpath(paged.__file__)))
    sizes = [None] if args.chunk_kib is None \
        else [kib << 10 for kib in args.chunk_kib]
    print("%-24s %9s %8s %8s %9s %8s %6s" % (
        "shape", "chunk KiB", "context", "chunks", "us a call", "us a row",
        "read"))
    for name in args.shapes:
        shape = SHAPES[name]
        contexts = args.contexts or _contexts(shape)
        mixed = _mixed(shape)
        for chunk_bytes in sizes:
            if chunk_bytes is not None:
                paged._WALK_CHUNK_BYTES = chunk_bytes
            jax.clear_caches()      # the kernels are jitted
            # (off the chip the kernels run under the interpreter, up to the
            # trace's reading: a rehearsal of the calls)
            fn, pools, by_context = _case(paged, shape, contexts,
                                          flash_sweep.OFF_CHIP)
            pages = paged._walk_chunk_pages(pools, shape["table"])
            page_bytes = sum(p[0].nbytes for p in pools)
            got = flash_sweep.run(
                [(c, fn, by_context[c]) for c in contexts + ["mixed"]],
                args.iters, kind=_walk_kind)
            points = []
            for c in contexts + ["mixed"]:
                us = got[c]["walk"]
                live = [_live_pages(shape, int(x)) for x in (
                    mixed if c == "mixed" else [c] * shape["rows"])]
                read = sum(live) * page_bytes / HBM_BYTES_PER_S
                n_chunks = sum(-(-x // pages) for x in live) / shape["rows"]
                if c != "mixed":
                    points.append((n_chunks, us / shape["rows"]))
                print("%-24s %9d %8s %8.1f %9.1f %8.2f %5.1f%%" % (
                    name, pages * page_bytes >> 10, c, n_chunks, us,
                    us / shape["rows"], 100 * read / (us * 1e-6)),
                    flush=True)
            if len({n for n, _ in points}) > 1:
                a_chunk, a_row = np.polyfit(*zip(*points), 1)
                print("%-24s %9d fitted: %.2f us a row + %.2f us a chunk "
                      "(the chunk's read: %.2f us)" % (
                          name, pages * page_bytes >> 10, a_row, a_chunk,
                          pages * page_bytes / HBM_BYTES_PER_S * 1e6),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
