"""The shortcut-connected latent model (``models/shortcut_latent_moe.py``)
compiled for the described v5e at ``longcat-serve-agent64``'s sizes and
the published widths: every prefill bucket and the decode bucket of 64.
A file of its own beside ``test_chip_compile.py`` (the kernels'
compiles) because a file is the unit of distribution of the tier-1 run
and these seven compiles take three minutes."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from chip_compile_helpers import (
    _big_moves, _grouped_tiles_are_the_rules, _holds, _named_calls, _traffic)

BF16, F32 = jnp.bfloat16, jnp.float32


def _shortcut_shapes(one, **cut):
    """``longcat-flash-ep32`` as the benchmark builds it: the file, the
    program's configuration and its weights as shapes on the described
    chip (``cut``: fields of the file to override, a shallower model)."""
    import json

    from benchmark.spec import load_module

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "longcat-flash-ep32.json")) as f:
        doc = dict(json.load(f), **cut)
    family = load_module(os.path.join(
        root, "benchmark", "models", "shortcut_latent_moe.py"),
        "family_shortcut_latent_moe")
    params = {k: jax.ShapeDtypeStruct(
        v, F32 if k.endswith("router_bias") else BF16, sharding=one)
        for k, v in family.weight_shapes(doc).items()}
    return doc, family.program_config(doc), params


_LONGCAT = _traffic("serve-agent-closed64-8k.json")


@pytest.mark.parametrize("bucket", _LONGCAT["prefill_buckets"][:-1])
def test_shortcut_prefill_buckets_compile(topo, on_tpu, bucket):
    """``longcat-serve-agent64``'s prefill at every bucket but the
    largest (which the next test compiles at full depth), one layer
    (two latent sublayers) at the published widths: the flash kernel
    under its scope's name from 1024 tokens, once a sublayer, the exact
    softmax (a ``[64, T, T]`` score matrix) below; the held experts'
    products are the chip's grouped kernels; rows ``[2, T, 640]`` go to
    the pool."""
    from mxnet_tpu.models import shortcut_latent_moe as sm
    from mxnet_tpu.parallel import moe

    one = SingleDeviceSharding(topo.devices[0])
    _, cfg, params = _shortcut_shapes(one, num_layers=1)
    compiled = jax.jit(lambda p, t, n: sm.prefill(p, t, n, cfg)).lower(
        params, jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile()
    text = compiled.as_text()
    assert compiled.out_info[1].shape == (2, bucket, 640)
    scores = r"f32\[(1,)?64,%d,%d\]" % (bucket, bucket)
    if bucket >= 1024:
        assert _named_calls(text, "latent_prefill_attention") == 2
        assert not _holds(text, scores)
    else:
        assert _named_calls(text, "latent_prefill_attention") == 0
        assert _holds(text, scores)
    assert "ragged-dot" in text
    # a run keeps a 24th of the prompt's pairs: no runs of tokens
    assert moe.grouped_kept_rows(bucket * 12, 16, 768, 6144 * 2) \
        == bucket // 2
    _grouped_tiles_are_the_rules(text, bucket * 12, 16, 768, 6144, 2048)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2 ** 30


def test_shortcut_decode_step_walks_a_pool_a_sublayer(topo, on_tpu):
    """The decode program of ``longcat-serve-agent64`` at full depth (64
    rows, 512-block tables, the 8 x 16,000-block latent pool of
    640-wide bfloat16 rows): the block-table walk at 64 heads is the
    Pallas body, once a *sublayer* (8 custom calls under the scope's
    name for 4 layers), no pool-sized copy, every held expert over every
    row in three batched products a layer and no grouped kernel, under a
    gigabyte of temporaries beside 12.9 GB of weights and pool."""
    from mxnet_tpu.models import shortcut_latent_moe as sm
    from mxnet_tpu.serving import generation

    one = SingleDeviceSharding(topo.devices[0])
    doc, cfg, params = _shortcut_shapes(one)
    serve = doc["deployment"]["serve"]
    definition = sm.lm_definition(cfg)
    assert definition.cache_layers == 8 == 2 * cfg["num_layers"]
    assert definition.cache_row.width == 640

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = (definition.cache_layers, serve["num_blocks"],
            serve["block_size"], 640)
    rows = s((_LONGCAT["decode_buckets"][0],))
    compiled = jax.jit(generation.with_greedy_ids(definition.decode)).lower(
        params, rows, rows, s(pool, BF16), None,
        s((64, cfg["seq_len"] // serve["block_size"])), rows).compile()
    assert [o.shape for o in compiled.out_info[:3]] == [
        (64, cfg["vocab_size"]), (64,), (8, 64, 640)]  # logits, ids, rows
    text = compiled.as_text()
    pool_bytes = 2 * int(np.prod(pool))
    assert _big_moves(text, pool_bytes // 8) == []
    assert _named_calls(text, "latent_decode_attention") == 8
    assert "ragged-dot" not in text
    entry = text[text.index("ENTRY"):]
    assert entry.count("shortcut_experts/td,gdh->gth/dot_general") \
        == 2 * cfg["num_layers"]
    assert entry.count("shortcut_experts/gth,ghd->gtd/dot_general") \
        == cfg["num_layers"]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30
    assert mem.argument_size_in_bytes > 12.9e9    # weights and pool


def test_shortcut_prefill_at_full_depth_fits_beside_weights_and_pool(
        topo, on_tpu):
    """The largest prefill bucket (6144 tokens) at full depth: a flash
    kernel a sublayer, no score matrix, and temporaries that, with a run
    of the held experts' pairs 3,072 rows of the 73,728, are no more
    than the 1,121,119,232 bytes they were with the pairs in three runs
    of tokens (PR 42; whole, 73,728 sorted pairs were 3 GB of them):
    12.97 GB of weights and pool leave the chip room for them."""
    from mxnet_tpu.models import shortcut_latent_moe as sm

    one = SingleDeviceSharding(topo.devices[0])
    _, cfg, params = _shortcut_shapes(one)
    bucket = _LONGCAT["prefill_buckets"][-1]
    assert bucket == 6144
    compiled = jax.jit(lambda p, t, n: sm.prefill(p, t, n, cfg)).lower(
        params, jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile()
    text = compiled.as_text()
    assert _named_calls(text, "latent_prefill_attention") == 8
    assert "f32[64,6144,6144]" not in text
    assert compiled.out_info[1].shape == (8, 6144, 640)
    _grouped_tiles_are_the_rules(text, 6144 * 12, 16, 768, 6144, 2048)
    assert compiled.memory_analysis().temp_size_in_bytes <= 1121119232
