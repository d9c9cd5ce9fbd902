"""The short-convolution / grouped-query model (``models/short_conv_moe.py``)
compiled for the described v5e at ``lfm2-serve-chat64``'s sizes and the
published widths: the decode bucket of 64 and the prefill buckets where
the attention changes body.  A file of its own beside
``test_chip_compile.py`` (the kernels' compiles) because a file is the
unit of distribution of the tier-1 run and these compiles take a few
minutes."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from chip_compile_helpers import (
    _big_moves, _grouped_tiles_are_the_rules, _named_calls, _traffic)

BF16, F32 = jnp.bfloat16, jnp.float32

_LFM2 = _traffic("serve-chat-closed64-5k.json")


def _short_conv_shapes(one):
    """``lfm2-8b-a1b-pp2`` as the benchmark builds it: the file, the
    program's configuration and its weights as shapes on the described
    chip."""
    import json

    from benchmark.spec import load_module

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2-8b-a1b-pp2.json")) as f:
        doc = json.load(f)
    family = load_module(os.path.join(root, "benchmark", "models",
                                      "short_conv_moe.py"), "family_scm")
    params = {k: jax.ShapeDtypeStruct(
        v, F32 if family.weight_kind(k) == "bias" else BF16, sharding=one)
        for k, v in family.weight_shapes(doc).items()}
    return doc, family.program_config(doc), params


def test_short_conv_decode_step_reads_and_writes_both_pools_where_they_lie(
        topo, on_tpu):
    """The decode program of ``lfm2-serve-chat64`` (64 rows, 512-block
    tables, the 9,600-block pools of 512-wide bfloat16 rows over the
    three attention layers, the state pool of 64 slots in two versions
    over the eleven convolution layers, donated): the state pool comes
    out aliased to what went in and is nowhere copied whole (XLA's
    gather, step and scatter update it in place), the key and value
    pools are read as they lie by the grouped-query walk in its body
    for heads of 64, once an attention layer, and every expert runs over
    every row in three batched products a layer, no grouped kernel."""
    from mxnet_tpu.models import short_conv_moe as sc
    from mxnet_tpu.serving import generation

    one = SingleDeviceSharding(topo.devices[0])
    doc, cfg, params = _short_conv_shapes(one)
    serve = doc["deployment"]["serve"]
    definition = sc.lm_definition(cfg)
    assert (definition.cache_layers, definition.state.layers) == (3, 11)

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = s((definition.cache_layers, serve["num_blocks"],
              serve["block_size"], definition.cache_row.width), BF16)
    rows = definition.state.layers * 2 * serve["state_slots"] + 1
    state = tuple(s((rows,) + shape, dtype)
                  for shape, dtype in definition.state.rows)
    bucket = _LFM2["decode_buckets"][0]
    b = s((bucket,))
    compiled = jax.jit(generation.with_greedy_ids(definition.decode),
                       donate_argnums=(7,)).lower(
        params, b, b, pool, pool,
        s((bucket, cfg["seq_len"] // serve["block_size"])), b, state,
        b).compile()
    assert [o.shape for o in compiled.out_info[:4]] == [
        (64, 65536), (64,), (3, 64, 512), (3, 64, 512)]
    text = compiled.as_text()
    state_bytes = int(np.prod(state[0].shape)) * 2
    assert state_bytes == (2 * 64 * 11 + 1) * 8192
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert _big_moves(text, state_bytes // 2) == []
    assert _named_calls(text, "paged_decode_gqa_attention") == 3
    assert "ragged-dot" not in text
    assert mem.temp_size_in_bytes < 2 ** 30
    assert mem.argument_size_in_bytes > 10.2e9    # weights, pools, state


@pytest.mark.parametrize("bucket", [512, 1024, 4096])
def test_short_conv_prefill_buckets_compile(topo, on_tpu, bucket):
    """The prefill at the bucket below the flash kernel's first, at that
    one (the mix's median) and at the largest: 32 heads of 64 run the
    flash kernel under its scope's name from 1024 tokens, once an
    attention layer, and hold no ``[32, T, T]`` score matrix there; the
    experts' products are the chip's grouped kernels; the state rows
    ``[11, 8, 512]`` and the cache rows ``[3, T, 512]`` go to the
    pools."""
    from mxnet_tpu.models import short_conv_moe as sc

    one = SingleDeviceSharding(topo.devices[0])
    _, cfg, params = _short_conv_shapes(one)
    assert bucket in _LFM2["prefill_buckets"]
    compiled = jax.jit(lambda p, t, n: sc.prefill(p, t, n, cfg)).lower(
        params, jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile()
    text = compiled.as_text()
    assert compiled.out_info[1].shape == (3, bucket, 512)
    assert compiled.out_info[4][0].shape == (11, 8, 512)
    flash = text.count("%gqa_prefill_attention")
    scores = "f32[32,%d,%d]" % (bucket, bucket) in text \
        or "f32[1,32,%d,%d]" % (bucket, bucket) in text
    assert (flash >= 3, scores) == ((True, False) if bucket >= 1024
                                    else (False, True))
    assert text.count("ragged-dot") >= 3 * 12
    _grouped_tiles_are_the_rules(text, bucket * 4, 32, 32, 2048, 1792)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
