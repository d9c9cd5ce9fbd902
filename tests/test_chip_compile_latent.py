"""The latent-attention, sparse-expert model (``models/latent_moe.py``)
compiled for the described v5e at ``dots-vlm1-serve-chat64``'s sizes:
11 GB of abstract weights, nothing allocated.  The prefill at every
bucket, the decode step over the pool where it lies, and the largest
prefill without its score matrix.  A file of its own beside
``test_chip_compile.py`` (the kernels' compiles) because a file is the
unit of distribution of the tier-1 run."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from chip_compile_helpers import (
    BF16, F32, _big_moves, _grouped_tiles_are_the_rules, _holds,
    _named_calls, _traffic)

_DOTS_BUCKETS = _traffic("serve-chat-closed64-4k.json")["prefill_buckets"]


def _latent_moe_shapes(one, **cut):
    """``dots-vlm1-ep16`` as the benchmark builds it: the program's
    configuration and its weights as shapes on the described chip
    (``cut``: fields of the file to override, a shallower model)."""
    import json

    from benchmark.spec import load_module

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "dots-vlm1-ep16.json")) as f:
        doc = dict(json.load(f), **cut)
    family = load_module(os.path.join(root, "benchmark", "models",
                                      "latent_moe.py"), "family_latent_moe")
    params = {k: jax.ShapeDtypeStruct(
        v, F32 if k.endswith("router_bias") else BF16, sharding=one)
        for k, v in family.weight_shapes(doc).items()}
    return doc, family.program_config(doc), params


@pytest.mark.parametrize("bucket", _DOTS_BUCKETS)
def test_latent_prefill_buckets_take_flash_from_1024(topo, on_tpu, bucket):
    """``dots-vlm1-serve-chat64``'s prefill at every bucket, one dense
    and one expert layer at the model's widths: the flash kernel under
    its scope's name from 1024 tokens, the exact softmax (a ``[128, T,
    T]`` score matrix, no custom call of that name) below."""
    from mxnet_tpu.models import latent_moe as lm

    one = SingleDeviceSharding(topo.devices[0])
    _, cfg, params = _latent_moe_shapes(one, num_hidden_layers=2)
    text = jax.jit(lambda p, t, n: lm.prefill(p, t, n, cfg)).lower(
        params, jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    ).compile().as_text()
    scores = r"f32\[(1,)?128,%d,%d\]" % (bucket, bucket)
    if bucket >= 1024:
        assert _named_calls(text, "latent_prefill_attention") \
            == cfg["num_layers"]
        assert not _holds(text, scores)
    else:
        assert _named_calls(text, "latent_prefill_attention") == 0
        assert _holds(text, scores)


def test_latent_decode_step_reads_the_pool_where_it_lies(topo, on_tpu):
    """The decode program of ``dots-vlm1-serve-chat64`` (64 rows,
    256-block tables, the 9600-block latent pool of 640-wide bfloat16
    rows, its attention the kernel a TPU runs): no pool-sized copy, no
    ``[heads, T, T]`` temporary, and under a gigabyte of temporaries in
    all.  (With 576-wide rows, 4.5 lane
    tiles, the chip lays the pool out with its block axis innermost and
    the same program re-lays all of it, 1 GB, before the gathers of
    every step.)"""
    from mxnet_tpu.models import latent_moe as lm
    from mxnet_tpu.serving import generation

    one = SingleDeviceSharding(topo.devices[0])
    doc, cfg, params = _latent_moe_shapes(one)
    serve = doc["deployment"]["serve"]
    width = lm.cache_row_width(cfg)
    assert width == 640 and width % 128 == 0

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = (cfg["num_layers"], serve["num_blocks"], serve["block_size"],
            width)
    rows = s((64,))
    compiled = jax.jit(generation.with_greedy_ids(
        lm.lm_definition(cfg).decode)).lower(
        params, rows, rows, s(pool, BF16), None,
        s((64, cfg["seq_len"] // serve["block_size"])), rows).compile()
    assert [o.shape for o in compiled.out_info[:2]] == [
        (64, cfg["vocab_size"]), (64,)]           # logits, greedy ids
    pool_bytes = 2 * int(np.prod(pool))
    assert _big_moves(compiled.as_text(), pool_bytes // 8) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30
    # the decode attention is the block-table walk, once a layer
    assert _named_calls(compiled.as_text(), "latent_decode_attention") \
        == cfg["num_layers"]
    assert mem.argument_size_in_bytes > 11.9e9    # weights and pool
    # the routed experts' products of a 64-row step: every held expert
    # over every row, three batched products a layer that read the
    # weights where they lie (no copy above), and no grouped kernel
    text = compiled.as_text()
    assert "ragged-dot" not in text
    entry = text[text.index("ENTRY"):]
    assert entry.count("expert_layer/td,gdh->gth/dot_general") \
        == 2 * (cfg["num_layers"] - 1)
    assert entry.count("expert_layer/gth,ghd->gtd/dot_general") \
        == cfg["num_layers"] - 1


def test_latent_prefill_holds_no_score_matrix(topo, on_tpu):
    """The largest prefill bucket (3328 tokens): the attention is the
    flash kernel on 192-wide queries and keys and 128-wide values,
    named by its scope, and the program's temporaries stay far under
    the 5.7 GB a ``[128, 3328, 3328]`` float32 score matrix takes."""
    from mxnet_tpu.models import latent_moe as lm

    one = SingleDeviceSharding(topo.devices[0])
    _, cfg, params = _latent_moe_shapes(one)
    compiled = jax.jit(lambda p, t, n: lm.prefill(p, t, n, cfg)).lower(
        params, jax.ShapeDtypeStruct((3328,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile()
    text = compiled.as_text()
    assert text.count("%latent_prefill_attention") >= cfg["num_layers"]
    # the routed experts' products are the chip's grouped-matmul kernels
    assert text.count("ragged-dot") >= 15
    _grouped_tiles_are_the_rules(text, 3328 * 8, 16, 256, 7168, 2048)
    assert "f32[128,3328,3328]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
