"""The copy schedule of the block-table walk (``ops/paged_attention.py:
_walk_kernel``) across rows and chunks: a row's first chunk is fetched
under the row before it, a whole chunk is awaited as one, a last chunk
no more than half live is folded as a half.  Every body of the walk and
the ring at two pages a chunk, on the CPU under the Pallas interpreters;
cases of ``tests/test_paged_decode.py``'s parity tests in a file of
their own because a file is the unit of distribution of the tier-1 run
(its helpers are that file's)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import paged_attention as att

from test_paged_decode import (BLK, GQA_HEADS, _f32, _gqa_case, _kv_case,
                               _kv_kernel, _kv_xla, _latent_case, _tables)


@pytest.fixture(scope="module", autouse=True)
def two_pages_a_chunk():
    """The walk cut to two pages (32 tokens) a chunk for the whole file:
    the kernels are jitted, so traces made under another chunking are
    dropped before and after."""
    patch = pytest.MonkeyPatch()
    patch.setattr(att, "_walk_chunk_pages", lambda pools, n: 2)
    jax.clear_caches()
    yield
    patch.undo()
    jax.clear_caches()


# context lengths at two pages (32 tokens) a chunk and tables of 8 blocks
SCHEDULES = {
    # 1, 2, 3, 2, 3, 1 chunks: rows begin in slots 0, 1, 1, 0, 0, 1
    "odd-even-odd": (33, 65, 97, 40, 70, 20),
    "empty-between": (40, 1, 70),
    "two-empty-between": (40, 1, 0, 70),
    "empty-first": (1, 40, 70),
    "empty-last": (40, 70, 0),
    "short-after-long": (120, 10),
    "long-after-short": (10, 120),
    "one-row": (50,),
    "one-empty-row": (1,),
    "whole-chunks-only": (33, 65, 97),
    # a last chunk under, at and over the half (16 tokens) that is
    # folded alone, as a row's only chunk and behind a whole one
    "either-side-of-half": (16, 17, 18, 48, 49, 50),
}
WINDOW = 32     # a ring of three blocks under the ring walk's cases


def _ring_tables(ctx):
    """A ring of ``WINDOW / BLK + 1`` entries a row, as many of them
    live as the row's tokens have reached."""
    ring = WINDOW // BLK + 1
    bt = np.zeros((len(ctx), ring), np.int32)
    nxt = 1
    for i, c in enumerate(ctx):
        n = min(ring, -(-max(c - 1, 0) // BLK))
        bt[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return bt


def _schedule_case(body, ctx):
    """``(kernel(args, interpret), xla(args), args, pools, tolerance)``
    of one of the walk's bodies at a small width: ``pools`` are the
    pools' places among ``args``."""
    if body == "kv":
        args = _kv_case(ctx, heads=2, dim=64)
        bt, _ = _tables(ctx, 8, cached_only=True)
        args[5] = jnp.asarray(bt)
        return _kv_kernel, _kv_xla, args, (3, 4), 2e-5
    if body == "latent":
        args = _latent_case(ctx, heads=8)
        return (lambda *a, interpret: att._latent_decode_pallas(
            *a, 0.07, 512, interpret=interpret),
            lambda *a: att._latent_decode_xla(*a, 0.07, 512),
            args, (2,), 2e-2)
    window = WINDOW if body == "ring" else None
    heads = dict(heads=28, groups=4, dim=128, scale=128 ** -0.5) \
        if window else dict(GQA_HEADS[body])
    scale = heads.pop("scale")
    args = _gqa_case(ctx, **heads)
    kernel = att._gqa_walk_body(heads["dim"])
    if window:
        args[5] = jnp.asarray(_ring_tables(ctx))
        return (lambda *a, interpret: kernel(*a, scale, interpret,
                                             window=window),
                lambda *a: att._gqa_decode_xla(*a, scale, window),
                args, (3, 4), 2e-2)
    return (lambda *a, interpret: kernel(*a, scale, interpret=interpret),
            lambda *a: att._gqa_decode_xla(*a, scale), args, (3, 4), 2e-2)


def _dead_poisoned(args, pools):
    """``args`` with NaN in every block of the pools that no table
    entry names (block 0, the tables' pad, among them)."""
    named = set(np.asarray(args[-2]).ravel().tolist()) - {0}
    out = list(args)
    for at in pools:
        pool = np.array(_f32(args[at]))
        pool[[n for n in range(pool.shape[0]) if n not in named]] = np.nan
        out[at] = jnp.asarray(pool, args[at].dtype)
    return out


_SCHEDULE_BODIES = ["kv", "latent", "2x256", "8x64", "ring"]


@pytest.mark.parametrize("ctx", sorted(SCHEDULES))
@pytest.mark.parametrize("body", _SCHEDULE_BODIES)
def test_rows_hand_their_first_chunk_on(body, ctx):
    """Every body of the walk and the ring at two pages a chunk over
    batches that take the schedule through its turns: both starting
    slots in both orders, a row that walks nothing between, before and
    after rows that do, one chunk after many and the reverse, a batch of
    one row, whole chunks alone.  Dead blocks hold NaN and the result is
    finite, the same bits as over a clean pool, and the XLA body's (a
    pad row, context 0, has no XLA answer under GPT-2's body, which
    scatters the current token into the gathered keys)."""
    kernel, xla, args, pools, tol = _schedule_case(body, SCHEDULES[ctx])
    ref = xla(*args)
    clean = kernel(*args, interpret=True)
    got = kernel(*_dead_poisoned(args, pools), interpret=True)
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_array_equal(_f32(got), _f32(clean))
    rows = np.asarray(SCHEDULES[ctx]) > 0
    np.testing.assert_allclose(_f32(got)[rows], _f32(ref)[rows], rtol=tol,
                               atol=tol)


# contexts whose ring walk begins at entry 2 of 3 (the first chunk wraps
# the table: entries 2, 0) under rows that do not wrap, and the reverse
RINGS = {
    "wrapping-first-chunk-after-plain": (20, 120, 40, 125),
    "plain-after-wrapping-first-chunk": (120, 20),
    "wrapping-alone": (120,),
    "every-entry": (90, 100, 120, 90, 125),
}


@pytest.mark.parametrize("ctx", sorted(RINGS))
def test_a_ring_rows_first_chunk_is_fetched_by_its_own_ring(ctx):
    """The chunk a row fetches ahead is the next row's: its first page
    and its entries ``mod`` the ring, not the fetching row's."""
    kernel, xla, args, pools, tol = _schedule_case("ring", RINGS[ctx])
    ref = xla(*args)
    got = kernel(*_dead_poisoned(args, pools), interpret=True)
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_allclose(_f32(got), _f32(ref), rtol=tol, atol=tol)


@pytest.mark.parametrize("ctx", ["odd-even-odd", "two-empty-between",
                                 "one-row"])
@pytest.mark.parametrize("body", _SCHEDULE_BODIES)
def test_every_copy_is_awaited_and_the_semaphores_end_level(
        body, ctx, capfd):
    """Under the TPU interpreter a copy lands when it is awaited and not
    before, a buffer starts as NaN and a semaphore counts bytes: a chunk
    folded before its wait, a wait for more than was started (it would
    hang) or a copy nobody awaits (the count left over is reported when
    the kernel ends) all show."""
    from jax.experimental.pallas import tpu as pltpu

    params = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   uninitialized_memory="nan")
    kernel, xla, args, _, tol = _schedule_case(body, SCHEDULES[ctx])
    ref = xla(*args)
    got = jax.block_until_ready(kernel(*args, interpret=params))
    rows = np.asarray(SCHEDULES[ctx]) > 0
    np.testing.assert_allclose(_f32(got)[rows], _f32(ref)[rows], rtol=tol,
                               atol=tol)
    assert "non-zero count" not in capfd.readouterr().out
