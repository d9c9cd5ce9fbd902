"""AOT compiles for a described TPU v5e — what interpret mode cannot see.

The chip's compiler is installed here and compiles for a chip that is
described, not attached (``/opt/skills/guides/on-chip-measurement`` §2.3).
Every Pallas kernel on ``chip_smoke.py``'s path is compiled at the
smoke's widths, every kernel of the parity harness at its widest grid
shape and the block-table walks at the serving cells' shapes in this
file; the cells' whole programs at the benchmark's buckets, a file a
model family (``test_chip_compile_lm.py``, ``_latent``, ``_gated_delta``,
``_shortcut``, ``_short_conv``, ``_window``: a file is the unit of
distribution of the tier-1 run), where the choice of body each hot path
states (the platform and the shape) is read off the compiled program: scoped-VMEM overflows, unsupported vector
types and Mosaic kernels that GSPMD cannot partition are refused HERE,
at no chip time, while they pass every interpret-mode test.  Nothing
runs, so these say nothing about results or speed; a compile that passes
is not a chip run.

Code that asks ``jax.default_backend()`` sees the CPU under such a
compile, so the tests steer it (``on_tpu``, a monkeypatch) — the program
has no option for that.  The ``topo`` and ``on_tpu`` fixtures are in
``conftest.py``, what the files share in ``chip_compile_helpers.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import mxnet_tpu  # noqa: F401  (registers ops and the kernels' parity)
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import paged_attention as paged
from mxnet_tpu.ops.fused import parity

from chip_compile_helpers import (
    BF16, F32, _POOL, _big_moves, _compile, _named_calls, _pool_sized, _s)


# ----------------------------------------------------------------------
# flash attention, forward and backward


def _flash_fwd(q, k, v):
    return att._flash_fwd_pallas(q, k, v, True, 0.125, return_lse=True)


def _flash_bwd(q, k, v, o, lse, do):
    return att._flash_bwd_pallas(q, k, v, o, lse, do, True, 0.125)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", [
    (8, 16, 1024, 64),      # gpt2m-train's attention: one block a head,
                            # the walk unrolled in it
    (8, 16, 2048, 64),      # two blocks an axis: on and below the diagonal
    (1, 16, 32768, 64),     # the long-context configuration
    (4, 8, 2176, 64),       # ragged T: refused at block_k=2048 before PR 21
], ids=["T1024", "T2048", "T32768", "T2176-ragged"])
def test_flash_kernels_compile(topo, on_tpu, shape, direction):
    one = SingleDeviceSharding(topo.devices[0])
    x, lse = _s(shape, BF16), _s(shape[:3], F32)
    if direction == "fwd":
        compiled = _compile(_flash_fwd, (x, x, x), one)
    else:
        # dq, dk and dv leave one kernel, which keeps a head's dq in
        # VMEM: at T32768 8 MiB of scratch and 16 MiB of its block on
        # the way out, more than a kernel gets unasked, so that the
        # compile succeeds says the call asked
        compiled = _compile(_flash_bwd, (x, x, x, x, lse, x), one)
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_gpt2_prefill_takes_flash_at_the_smoke_width(topo, on_tpu):
    """``chip_smoke.py``'s serve_lm prefill (one prompt of 1536, 16
    heads of 64, float32) is past the 1024 crossover: there
    ``stable_causal_attention`` is the flash forward, and no ``[T, T]``
    score matrix is held."""
    x = _s((1, 16, 1536, 64), F32)
    compiled = _compile(att.stable_causal_attention, (x, x, x),
                        SingleDeviceSharding(topo.devices[0]))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "1536,1536]" not in text


# ----------------------------------------------------------------------
# every Pallas kernel of the parity harness at its widest grid shape:
# nothing is chosen on a TPU that this compile refuses


def _pallas_kernels():
    return sorted(name for name, reg in
                  parity.parity_registrations().items() if reg.pallas)


def _widest(reg):
    """The parity-grid case with the most elements in its arguments."""
    def size(case):
        return sum(int(np.prod(x.shape)) for x in
                   jax.tree_util.tree_leaves(reg.builder(case)[2]))

    return max(reg.grid, key=size)


@pytest.mark.parametrize("kernel", _pallas_kernels())
def test_every_kernel_compiles_at_its_widest_parity_shape(
        topo, on_tpu, kernel):
    reg = parity.parity_registrations()[kernel]
    _, fn, args = reg.builder(_widest(reg))[:3]
    compiled = _compile(fn, args, SingleDeviceSharding(topo.devices[0]))
    assert "tpu_custom_call" in compiled.as_text(), \
        "%s compiled without its kernel" % kernel


# ----------------------------------------------------------------------
# the serving pool at GPT-2 medium's size: resident, written in place


@pytest.mark.parametrize("rows", [16, 768],
                         ids=["decode-bucket", "prefill-bucket"])
def test_pool_write_is_in_place_on_the_chip(topo, rows):
    """The donated write compiled for a v5e at the benchmark's sizes
    aliases both pools to its outputs, moves no pool-sized array and
    needs no pool-sized temporary: the pool never exists twice.  (With
    pools shaped ``[L, N, bs, H, D]`` the same program held a 2.1 GB
    temporary and four pool-sized copies.)"""
    from mxnet_tpu.ops import kv_cache

    one = SingleDeviceSharding(topo.devices[0])
    pool = jax.ShapeDtypeStruct(_POOL, F32, sharding=one)
    kv = jax.ShapeDtypeStruct((_POOL[0], rows, _POOL[3]), F32, sharding=one)
    idx = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one)
    compiled = kv_cache._write_pages.lower(
        pool, pool, kv, kv, idx, idx).compile()
    text = compiled.as_text()
    assert "input_output_alias={ {0}: (0, {}, may-alias), " \
           "{1}: (1, {}, may-alias) }" in text.splitlines()[0]
    assert _pool_sized(text) == []
    mem = compiled.memory_analysis()
    pool_bytes = 4 * int(np.prod(_POOL))
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 4


_LATENT_POOL = (6 * 9600, 16, 640)     # layers x blocks, block size, W


def _kv_kernel(q, k_step, v_step, k_pool, v_pool, tables, lens):
    heads = (-1, _POOL[2], 16, 64)
    return paged.paged_decode_attention(
        q, k_step, v_step, k_pool.reshape(heads), v_pool.reshape(heads),
        tables, lens)


def _latent_kernel(q, row, pool, tables, lens):
    return paged.latent_paged_decode_attention(q, row, pool, tables, lens,
                                               0.1, 512)


_PAGED_KERNELS = {
    # gpt2m-serve-chat: 16 rows, 64-block tables, two float32 pools
    "kv": (_kv_kernel, "paged_decode_attention",
           (_s((16, 16, 64), F32),) * 3 + (_s(_POOL, F32),) * 2
           + (_s((16, 64), jnp.int32), _s((16,), jnp.int32))),
    # dots-vlm1-serve-chat64: 64 rows, 256-block tables, one bf16 pool
    "latent": (_latent_kernel, "latent_decode_attention",
               (_s((64, 128, 640), BF16), _s((64, 640), BF16),
                _s(_LATENT_POOL, BF16), _s((64, 256), jnp.int32),
                _s((64,), jnp.int32))),
    # longcat-serve-agent64: 64 rows of 64 heads, 512-block tables, the
    # 8 sublayers' pools of 16,000 blocks as one
    "latent_64_heads": (_latent_kernel, "latent_decode_attention",
                        (_s((64, 64, 640), BF16), _s((64, 640), BF16),
                         _s((8 * 16000, 16, 640), BF16),
                         _s((64, 512), jnp.int32), _s((64,), jnp.int32))),
}


@pytest.mark.parametrize("kernel", sorted(_PAGED_KERNELS))
def test_paged_decode_kernels_compile_at_the_cells_shapes(
        topo, on_tpu, kernel):
    """Both bodies of the block-table walk at the serving cells' sizes:
    one custom call under its scope's name, the pools handed to it as
    they lie (nothing of a megabyte is copied or re-laid, no temporary:
    the program is the kernel)."""
    fn, name, args = _PAGED_KERNELS[kernel]
    compiled = _compile(fn, args, SingleDeviceSharding(topo.devices[0]))
    text = compiled.as_text()
    assert _named_calls(text, name) == 1
    assert _big_moves(text, 2 ** 20) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def _ragged_kv(q, k_step, v_step, k_pool, v_pool, tables, lens):
    return paged.paged_decode_attention(q, k_step, v_step, k_pool, v_pool,
                                        tables, lens)


_RAGGED_POOLS = {
    # 12 heads of 40: a cached row of 480 values is 3.75 lane tiles
    "kv": (_ragged_kv,
           (_s((16, 12, 40), F32),) * 3 + (_s((680, 16, 12, 40), F32),) * 2
           + (_s((16, 64), jnp.int32), _s((16,), jnp.int32))),
    # the latent row before it was padded to 640: 4.5 lane tiles
    "latent": (lambda q, row, pool, tables, lens:
               paged.latent_paged_decode_attention(q, row, pool, tables,
                                                   lens, 0.1, 512),
               (_s((64, 128, 576), BF16), _s((64, 576), BF16),
                _s((9600, 16, 576), BF16), _s((64, 256), jnp.int32),
                _s((64,), jnp.int32))),
}


@pytest.mark.parametrize("model", sorted(_RAGGED_POOLS))
def test_a_pool_of_ragged_pages_gets_the_xla_body(topo, on_tpu, model):
    """The shape half of the rule: a pool whose pages are not whole
    tiles cannot be copied as it lies, so on a TPU too both entry
    points take their XLA body (the program compiles, with no custom
    call)."""
    fn, args = _RAGGED_POOLS[model]
    compiled = _compile(fn, args, SingleDeviceSharding(topo.devices[0]))
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("kernel", sorted(_PAGED_KERNELS))
def test_a_kernel_that_fails_to_trace_raises_to_the_caller(
        topo, on_tpu, monkeypatch, kernel):
    """There is no fallback book: a kernel body that raises while it is
    traced fails the program that asked for it, at the shapes the
    serving cells run, and is never turned into a slower program."""
    def broken(*args, **kwargs):
        raise RuntimeError("the walk cannot be traced")

    monkeypatch.setattr(paged, "_walk_pages", broken)
    jax.clear_caches()      # the kernels are jitted: drop sound traces
    fn, _, args = _PAGED_KERNELS[kernel]
    try:
        with pytest.raises(RuntimeError, match="cannot be traced"):
            _compile(fn, args, SingleDeviceSharding(topo.devices[0]))
    finally:
        jax.clear_caches()


def _gqa_kernel(q, k_step, v_step, k_pool, v_pool, tables, lens):
    return paged.gqa_paged_decode_attention(q, k_step, v_step, k_pool,
                                            v_pool, tables, lens, 0.0625)


_GQA_POOL = (2 * 24576, 16, 512)        # layers x blocks, block size, W
# qwen3next-serve-reason128: 128 rows, 512-block tables, two bf16 pools
_PAGED_KERNELS["gqa"] = (
    _gqa_kernel, "paged_decode_gqa_attention",
    (_s((128, 16, 256), BF16),) + (_s((128, 2, 256), BF16),) * 2
    + (_s(_GQA_POOL, BF16),) * 2
    + (_s((128, 512), jnp.int32), _s((128,), jnp.int32)))


# lfm2-serve-chat64: 64 rows of 32 query heads over 8 key-value heads
# of 64 (two a lane tile: the walk's packed body), 512-block tables, the
# three attention layers' 9,600-block pools as one
_PAGED_KERNELS["gqa_heads_of_64"] = (
    lambda *args: paged.gqa_paged_decode_attention(*args, 0.125),
    "paged_decode_gqa_attention",
    (_s((64, 32, 64), BF16),) + (_s((64, 8, 64), BF16),) * 2
    + (_s((3 * 9600, 16, 512), BF16),) * 2
    + (_s((64, 512), jnp.int32), _s((64,), jnp.int32)))


@pytest.mark.parametrize("kernel,beside", [("gqa", 2 ** 21),
                                           ("gqa_heads_of_64", 2 ** 23)])
def test_gqa_paged_decode_kernel_compiles_at_the_cells_shape(topo, on_tpu,
                                                             kernel, beside):
    """Both grouped-query bodies of the block-table walk at their cells'
    sizes: one custom call under the scope's name, the pools handed to
    it as they lie (the packed body's spread queries and its outputs'
    own lanes are 2 MB each beside it)."""
    fn, name, args = _PAGED_KERNELS[kernel]
    compiled = _compile(fn, args, SingleDeviceSharding(topo.devices[0]))
    text = compiled.as_text()
    assert _named_calls(text, name) == 1
    assert _big_moves(text, beside // 2) == []
    assert compiled.memory_analysis().temp_size_in_bytes < beside
