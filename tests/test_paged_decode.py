"""The paged decode kernels (the block-table walk of
``ops/paged_attention.py`` under GPT-2's key and value pools and under
the latent pool) against the XLA bodies they stand in for on a TPU, on
the CPU under Pallas interpret mode: ragged contexts, several chunks a row, dead blocks poisoned with
NaN, buffers that start as NaN.  Nothing here says anything about speed;
``tests/test_chip_compile.py`` compiles both for the described chip."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu  # noqa: F401  (registers ops)
from mxnet_tpu.ops import paged_attention as att

BLK = 16
SCALE = 0.125       # 1 / sqrt(64), what paged_decode_attention derives
# context lengths (they count the current token) at blocks of 16: no
# cached token, a block less one, exactly a block, a block and one, ...
RAGGED = {
    "one": (1,),
    "block": (17,),
    "block-and-one": (18,),
    "full-table": (8 * BLK,),
    "mixed": (1, 16, 17, 18, 8 * BLK, 50, 100),
}


def _tables(ctx, max_blocks, cached_only):
    """Distinct live blocks a row, block 0 as the table's pad.  The
    kernels read ``ceil((c - 1) / blk)`` blocks of a row, the stock
    GPT-2 body also scatters into block ``(c - 1) // blk``."""
    bt = np.zeros((len(ctx), max_blocks), np.int32)
    nxt = 1
    for i, c in enumerate(ctx):
        n = -(-max(c - 1, 0) // BLK) if cached_only else -(-c // BLK)
        bt[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return bt, nxt


def _poison(pool, ctx, bt):
    """NaN in every block no row's cached tokens reach."""
    live = {int(b) for i, c in enumerate(ctx)
            for b in bt[i, :-(-max(c - 1, 0) // BLK)]}
    pool = np.array(pool)
    for n in range(pool.shape[0]):
        if n not in live:
            pool[n] = np.nan
    return pool


def _kv_case(ctx, dtype="float32", heads=16, dim=64, max_blocks=8, seed=0):
    rng = np.random.default_rng(seed)
    bt, n = _tables(ctx, max_blocks, cached_only=False)
    shape = (n + 1, BLK, heads * dim)    # a pool as the cache holds it

    def rand(s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32).astype(dtype)

    return [rand((len(ctx), heads, dim)) for _ in range(3)] + [
        rand(shape), rand(shape), jnp.asarray(bt),
        jnp.asarray(ctx, jnp.int32)]


def _latent_case(ctx, dtype="bfloat16", heads=8, width=640, max_blocks=8,
                 seed=0):
    rng = np.random.default_rng(seed)
    bt, n = _tables(ctx, max_blocks, cached_only=True)

    def rand(s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32).astype(dtype)

    return [rand((len(ctx), heads, width)), rand((len(ctx), width)),
            rand((n + 1, BLK, width)), jnp.asarray(bt),
            jnp.asarray(ctx, jnp.int32)]


def _gqa_case(ctx, dtype="bfloat16", heads=16, groups=2, dim=256,
              max_blocks=8, seed=0):
    """The served heads: 16 queries over 2 key-value heads of 256, rows
    of 512 as the pools hold them."""
    rng = np.random.default_rng(seed)
    bt, n = _tables(ctx, max_blocks, cached_only=True)
    shape = (n + 1, BLK, groups * dim)

    def rand(s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32).astype(dtype)

    return [rand((len(ctx), heads, dim)), rand((len(ctx), groups, dim)),
            rand((len(ctx), groups, dim)), rand(shape), rand(shape),
            jnp.asarray(bt), jnp.asarray(ctx, jnp.int32)]


def _pallas_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


def _kv_xla(*args):
    return att._kv_decode_xla(*args, SCALE)


def _kv_kernel(*args, interpret=True):
    return att._kv_decode_pallas(*args, SCALE, interpret)


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.fixture(params=["one-chunk", "two-pages-a-chunk"])
def chunking(request, monkeypatch):
    """The walk with the whole table in one chunk, and cut so that a
    row takes several chunks and ends inside one."""
    if request.param == "two-pages-a-chunk":
        # the kernels are jitted: a trace made under the other chunking
        # must not answer for this one
        monkeypatch.setattr(att, "_walk_chunk_pages", lambda pools, n: 2)
        jax.clear_caches()
    yield request.param
    if request.param == "two-pages-a-chunk":
        jax.clear_caches()


# ----------------------------------------------------------------------
# against the XLA bodies


@pytest.mark.parametrize("ctx", sorted(RAGGED))
def test_kv_kernel_equals_the_xla_body(ctx, chunking):
    """16 heads of 64 in float32, the served width (H.D = 1024)."""
    args = _kv_case(RAGGED[ctx])
    ref = _kv_xla(*args)
    got = _kv_kernel(*args)
    assert got.dtype == ref.dtype and got.shape == ref.shape
    np.testing.assert_allclose(_f32(got), _f32(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("ctx", sorted(RAGGED))
def test_latent_kernel_equals_the_xla_body(ctx, dtype, chunking):
    """640-wide rows (512 of them the values), as the pool holds them."""
    args = _latent_case(RAGGED[ctx], dtype)
    ref = att._latent_decode_xla(*args, 0.07, 512)
    got = att._latent_decode_pallas(*args, 0.07, 512, interpret=True)
    assert got.dtype == ref.dtype and got.shape == ref.shape
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(_f32(got), _f32(ref), rtol=tol, atol=tol)


# the two grouped-query bodies of the walk: a key-value head that is
# whole lane tiles (16 queries over 2 heads of 256) and one narrower
# than a tile (32 queries over 8 heads of 64: two heads a tile)
GQA_HEADS = {"2x256": dict(heads=16, groups=2, dim=256, scale=0.0625),
             "8x64": dict(heads=32, groups=8, dim=64, scale=0.125)}


def _gqa_heads(name):
    heads = dict(GQA_HEADS[name])
    return heads, heads.pop("scale")


# every context in both dtypes for the sliced body; for the packed one
# every context in float32 and the mixed batch in bfloat16 (the file
# stays inside its share of the tier-1 clock)
_GQA_CASES = [(ctx, dtype, "2x256") for ctx in sorted(RAGGED)
              for dtype in ("bfloat16", "float32")] \
    + [(ctx, "float32", "8x64") for ctx in sorted(RAGGED)] \
    + [("mixed", "bfloat16", "8x64")]


@pytest.mark.parametrize("ctx,dtype,heads", _GQA_CASES)
def test_gqa_kernel_equals_the_xla_body(ctx, dtype, heads, chunking):
    """Grouped queries over 512-wide rows: 8 query heads share a
    key-value head's 256 lanes, or 4 share its 64 (no cached token, one
    live block, ragged tails, a full table)."""
    heads, scale = _gqa_heads(heads)
    args = _gqa_case(RAGGED[ctx], dtype, **heads)
    body = att._gqa_walk_body(heads["dim"])
    assert (body is att._gqa_decode_pallas) == (heads["dim"] == 256)
    ref = att._gqa_decode_xla(*args, scale)
    got = body(*args, scale, interpret=True)
    assert got.dtype == ref.dtype and got.shape == ref.shape
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(_f32(got), _f32(ref), rtol=tol, atol=tol)


def test_gqa_xla_body_is_plain_attention_with_repeated_heads():
    """The XLA body against softmax(q k^T / 16) v written out, each
    key-value head repeated for its 8 query heads."""
    ctx = RAGGED["mixed"]
    q, k_step, v_step, k_pool, v_pool, bt, lens = _gqa_case(ctx, "float32")
    got = _f32(att._gqa_decode_xla(q, k_step, v_step, k_pool, v_pool, bt,
                                   lens, 0.0625))
    for i, c in enumerate(ctx):
        blocks = np.asarray(bt)[i]
        keys = np.concatenate([_f32(k_pool)[blocks].reshape(-1, 2, 256)
                               [:c - 1], _f32(k_step)[i][None]])
        values = np.concatenate([_f32(v_pool)[blocks].reshape(-1, 2, 256)
                                 [:c - 1], _f32(v_step)[i][None]])
        for h in range(16):
            s = keys[:, h // 8] @ _f32(q)[i, h] * 0.0625
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ values[:, h // 8]
            np.testing.assert_allclose(got[i, h], want, rtol=1e-4,
                                       atol=1e-5)


@pytest.mark.parametrize("heads", sorted(GQA_HEADS))
def test_gqa_kernel_reads_no_dead_block(heads, chunking):
    heads, scale = _gqa_heads(heads)
    body = att._gqa_walk_body(heads["dim"])
    ctx = RAGGED["mixed"]
    args = _gqa_case(ctx, **heads)
    clean = body(*args, scale, interpret=True)
    bt = np.asarray(args[5])
    for at in (3, 4):
        args[at] = jnp.asarray(_poison(_f32(args[at]), ctx, bt),
                               args[at].dtype)
    got = body(*args, scale, interpret=True)
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_array_equal(_f32(got), _f32(clean))
    assert np.isnan(_f32(att._gqa_decode_xla(*args, scale))).any()


def test_where_pallas_runs_heads_of_64_take_the_packed_body(monkeypatch):
    """The platform test patched to the interpreter: the public entry
    point walks the pools under the packed body for heads narrower than
    a lane tile (one ``pallas_call``, the XLA body's result within the
    kernel's class), and keeps the XLA body off the chip."""
    from mxnet_tpu.ops import platform

    heads, scale = _gqa_heads("8x64")
    args = _gqa_case((5, 20, 40), **heads)

    def fn(*a):
        return att.gqa_paged_decode_attention(*a, scale)

    # (a function's trace is kept: each count traces one of its own)
    assert _pallas_calls(lambda *a: fn(*a), *args) == 0
    monkeypatch.setattr(platform, "pallas_mode", lambda: "interpret")
    assert _pallas_calls(lambda *a: fn(*a), *args) == 1
    np.testing.assert_allclose(
        _f32(fn(*args)), _f32(att._gqa_decode_xla(*args, scale)),
        rtol=2e-2, atol=2e-2)


def test_kv_kernel_takes_a_bfloat16_pool():
    """A pool one precision down: float32 arithmetic, float32 out."""
    args = _kv_case(RAGGED["mixed"], "bfloat16", heads=2, dim=64)
    ref = _kv_xla(*args)
    got = _kv_kernel(*args)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(_f32(got), _f32(ref), rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# dead blocks, pad rows, fresh buffers


def test_kv_kernel_reads_no_dead_block(chunking):
    """Every block that a row's cached tokens do not reach holds NaN:
    the kernel's output is what it was.  The stock body gathers those
    blocks and its p.v turns 0 x NaN into NaN: that is what it costs."""
    ctx = RAGGED["mixed"]
    args = _kv_case(ctx)
    bt, _ = _tables(ctx, 8, cached_only=True)
    args[5] = jnp.asarray(bt)
    clean = _kv_kernel(*args)
    args[3] = jnp.asarray(_poison(args[3], ctx, bt))
    args[4] = jnp.asarray(_poison(args[4], ctx, bt))
    got = _kv_kernel(*args)
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_array_equal(_f32(got), _f32(clean))
    assert np.isnan(_f32(_kv_xla(*args))).any()


def test_latent_kernel_reads_no_dead_block(chunking):
    ctx = RAGGED["mixed"]
    args = _latent_case(ctx)
    clean = att._latent_decode_pallas(*args, 0.07, 512, interpret=True)
    args[2] = jnp.asarray(_poison(_f32(args[2]), ctx, np.asarray(args[3])),
                          args[2].dtype)
    got = att._latent_decode_pallas(*args, 0.07, 512, interpret=True)
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_array_equal(_f32(got), _f32(clean))
    assert np.isnan(_f32(att._latent_decode_xla(*args, 0.07, 512))).any()


@pytest.mark.parametrize("kernel", ["kv", "latent"])
def test_a_row_without_cached_tokens_comes_out_finite(kernel):
    """A pad row (context 0) and a first step (context 1) attend over
    the current token alone: its value."""
    ctx = (0, 1, 40)
    if kernel == "kv":
        args = _kv_case(ctx)
        got, own = _kv_kernel(*args), args[2]
    else:
        args = _latent_case(ctx)
        got = att._latent_decode_pallas(*args, 0.07, 512, interpret=True)
        own = jnp.broadcast_to(args[1][:, None, :512], got.shape)
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_allclose(_f32(got[:2]), _f32(own[:2]), rtol=1e-6)


@pytest.mark.parametrize("kernel", ["kv", "latent"])
def test_the_tail_of_a_chunk_is_never_read(kernel):
    """Under the TPU interpreter a buffer starts as NaN, as it may on
    the chip: what the walk did not copy into a chunk stays out of the
    result."""
    from jax.experimental.pallas import tpu as pltpu

    nan_start = pltpu.InterpretParams(uninitialized_memory="nan")
    ctx = RAGGED["mixed"]
    if kernel == "kv":
        args = _kv_case(ctx, heads=2, dim=64)
        ref = _kv_xla(*args)
        got = _kv_kernel(*args, interpret=nan_start)
        tol = 2e-5
    else:
        args = _latent_case(ctx, heads=8)
        ref = att._latent_decode_xla(*args, 0.07, 512)
        got = att._latent_decode_pallas(*args, 0.07, 512,
                                        interpret=nan_start)
        tol = 2e-2
    np.testing.assert_allclose(_f32(got), _f32(ref), rtol=tol, atol=tol)


# ----------------------------------------------------------------------
# which path runs


def test_chunk_pages_follow_from_the_page_bytes():
    """A chunk holds what of a power of two pages fits its 2 MiB: 16
    pages of GPT-2's two float32 pools (256 tokens), 64 of the latent
    pool's and of two pools of 512-wide bfloat16 rows (1024), never
    more than the table."""
    kv = jax.ShapeDtypeStruct((24 * 680, 16, 1024), jnp.float32)
    latent = jax.ShapeDtypeStruct((6 * 9600, 16, 640), jnp.bfloat16)
    gqa = jax.ShapeDtypeStruct((4 * 20224, 16, 512), jnp.bfloat16)
    assert att._walk_chunk_pages((kv, kv), 64) == 16
    assert att._walk_chunk_pages((latent,), 256) == 64
    assert att._walk_chunk_pages((gqa, gqa), 1024) == 64
    assert att._walk_chunk_pages((gqa, gqa), 257) == 64
    assert att._walk_chunk_pages((latent,), 4) == 4
    assert att._walk_tiles(kv, latent)
    assert not att._walk_tiles(
        jax.ShapeDtypeStruct((9, 16, 576), jnp.bfloat16))
    assert not att._walk_tiles(
        jax.ShapeDtypeStruct((9, 8, 640), jnp.bfloat16))


def test_the_walk_says_how_it_copies():
    """The three gauges are set when a kernel is built, under its
    scope's name: a chunk's tokens, one wait a pool for a whole chunk,
    one row ahead."""
    from mxnet_tpu.observability import metrics

    jax.clear_caches()      # the gauges are set when a kernel is traced
    args = _gqa_case((5, 40))
    att._gqa_decode_pallas(*args, 0.0625, interpret=True)
    att._latent_decode_pallas(*_latent_case((5, 40)), 0.07, 512,
                              interpret=True)
    text = metrics.dump_metrics()
    for line in (
            'paged_decode_walk_chunk_tokens{kernel="paged_decode_gqa_'
            'attention"} 128',
            'paged_decode_walk_waits_per_chunk{kernel="paged_decode_gqa_'
            'attention"} 2',
            'paged_decode_walk_rows_ahead{kernel="paged_decode_gqa_'
            'attention"} 1',
            'paged_decode_walk_waits_per_chunk{kernel="latent_decode_'
            'attention"} 1'):
        assert line in text, line


def test_off_the_chip_the_xla_bodies_run():
    """On the CPU neither entry point reaches a kernel: the decode
    parity of ``tests/test_generation.py`` is the XLA body's."""
    kv = _kv_case((5, 20))
    pools = [p.reshape(p.shape[:2] + (16, 64)) for p in kv[3:5]]
    public = kv[:3] + pools + kv[5:]
    assert _pallas_calls(att.paged_decode_attention, *public) == 0
    np.testing.assert_array_equal(
        _f32(att.paged_decode_attention(*public)), _f32(_kv_xla(*kv)))
    args = _latent_case((5, 20), "float32")
    assert _pallas_calls(
        lambda *a: att.latent_paged_decode_attention(*a, 0.07, 512),
        *args) == 0
    np.testing.assert_array_equal(
        _f32(att.latent_paged_decode_attention(*args, 0.07, 512)),
        _f32(att._latent_decode_xla(*args, 0.07, 512)))


@pytest.mark.parametrize("model", ["kv", "latent"])
def test_where_pallas_runs_the_rule_takes_the_kernel(model, monkeypatch):
    """The platform test patched to the interpreter: both entry points
    take their kernel for pages of whole tiles (one ``pallas_call``, the
    XLA body's result within the kernel's class) and keep the XLA body
    for a pool whose pages are not."""
    from mxnet_tpu.ops import platform

    monkeypatch.setattr(platform, "pallas_mode", lambda: "interpret")
    if model == "kv":
        kv = _kv_case((5, 20, 40))
        pools = [p.reshape(p.shape[:2] + (16, 64)) for p in kv[3:5]]
        args, fn = kv[:3] + pools + kv[5:], att.paged_decode_attention
        ref, tol = _kv_xla(*kv), 2e-5
        ragged = _kv_case((5, 20, 40), heads=2, dim=24)
        ragged[3:5] = [p.reshape(p.shape[:2] + (2, 24)) for p in ragged[3:5]]
    else:
        args = _latent_case((5, 20, 40))
        ref, tol = att._latent_decode_xla(*args, 0.07, 512), 2e-2
        ragged = _latent_case((5, 20, 40), width=576)

        def fn(*a):
            return att.latent_paged_decode_attention(*a, 0.07, 512)

    assert _pallas_calls(fn, *args) == 1
    np.testing.assert_allclose(_f32(fn(*args)), _f32(ref), rtol=tol,
                               atol=tol)
    assert _pallas_calls(fn, *ragged) == 0
