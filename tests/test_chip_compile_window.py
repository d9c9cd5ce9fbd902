"""The sliding-window / global grouped-query model
(``models/window_moe.py``) compiled for the described v5e at
``smallthinker-serve-mixed48``'s sizes and the published widths: the
decode bucket of 48 over the two layer groups' pools, the prefill
buckets where the attention changes body and where the band leaves the
triangle, and the pool write of both groups in one program.  A file of
its own beside ``test_chip_compile.py`` (the kernels' compiles) because
a file is the unit of distribution of the tier-1 run and these compiles
take a few minutes."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from chip_compile_helpers import (
    _big_moves, _grouped_tiles_are_the_rules, _named_calls, _traffic)

BF16 = jnp.bfloat16

_MIX = _traffic("serve-mixed-closed48-14k.json")


def _window_shapes(one):
    """``smallthinker-21b-ep4`` as the benchmark builds it: the file, the
    program's configuration and its weights as shapes on the described
    chip."""
    import json

    from benchmark.spec import load_module

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "smallthinker-21b-ep4.json")) as f:
        doc = json.load(f)
    family = load_module(os.path.join(root, "benchmark", "models",
                                      "window_moe.py"), "family_wm")
    params = {k: jax.ShapeDtypeStruct(v, BF16, sharding=one)
              for k, v in family.weight_shapes(doc).items()}
    return doc, family.program_config(doc), params


def _pools(definition, serve, one):
    return tuple(
        jax.ShapeDtypeStruct((len(layers), blocks, serve["block_size"],
                              definition.cache_row.width), BF16,
                             sharding=one)
        for (layers, _), blocks in zip(definition.cache_groups,
                                       serve["num_blocks"]))


def test_window_decode_step_walks_both_groups_where_they_lie(topo, on_tpu):
    """The decode program of ``smallthinker-serve-mixed48`` (48 rows, a
    table row of 1,024 global entries and a ring of 257, the pools of
    20,224 and 12,032 blocks of 512-wide bfloat16 rows over the 4 global
    and the 12 window layers): both pools are read as they lie, the
    global layers by the grouped-query walk and the window layers by
    the walk over the ring under its own name, once a layer each, seven
    queries a key-value head in runs padded to whole tiles; no pool is
    copied; every held expert runs over every row in batched products,
    no grouped kernel."""
    from mxnet_tpu.models import window_moe as wm
    from mxnet_tpu.serving import generation

    one = SingleDeviceSharding(topo.devices[0])
    doc, cfg, params = _window_shapes(one)
    serve = doc["deployment"]["serve"]
    definition = wm.lm_definition(cfg)
    assert definition.cache_groups == (
        ((0, 1, 2, 3), None), (tuple(range(4, 16)), 4096))
    assert wm.table_widths(cfg, 16) == (1024, 257)

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pools = _pools(definition, serve, one)
    bucket = _MIX["decode_buckets"][0]
    b = s((bucket,))
    compiled = jax.jit(generation.with_greedy_ids(definition.decode)).lower(
        params, b, b, pools, pools, s((bucket, 1024 + 257)), b).compile()
    assert [o.shape for o in compiled.out_info[:4]] == [
        (48, 37984), (48,), (16, 48, 512), (16, 48, 512)]
    text = compiled.as_text()
    window_pool = 12 * 12032 * 16 * 512 * 2
    assert _big_moves(text, window_pool // 8) == []
    assert _named_calls(text, "paged_decode_gqa_attention") == 4
    assert _named_calls(text, "paged_decode_gqa_window") == 12
    assert "ragged-dot" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30
    # weights 4.09 GB, pools 2 x (1.33 + 2.37) GB
    assert 11.4e9 < mem.argument_size_in_bytes < 11.6e9


@pytest.mark.parametrize("bucket", [512, 1024, 4096, 6144, 12288])
def test_window_prefill_buckets_compile(topo, on_tpu, bucket):
    """The prefill at the bucket below the flash kernel's first, at that
    one, at the window (the band is still the whole triangle), just past
    it, and at the largest: 28 heads of 128 run the flash kernel from
    1024 tokens, the 4 global layers under the causal name and the 12
    window layers under the window's, and hold no ``[28, T, T]`` score
    matrix there; the experts' products are the chip's grouped kernels
    under the rule's tiles; the cache rows ``[16, T, 512]`` go to the
    pools; the program's temporaries leave the cell its 14.3 GB."""
    from mxnet_tpu.models import window_moe as wm

    one = SingleDeviceSharding(topo.devices[0])
    _, cfg, params = _window_shapes(one)
    assert bucket in _MIX["prefill_buckets"]
    compiled = jax.jit(lambda p, t, n: wm.prefill(p, t, n, cfg)).lower(
        params, jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile()
    text = compiled.as_text()
    assert compiled.out_info[1].shape == (16, bucket, 512)
    assert compiled.out_info[3].shape == (8,)
    scores = "f32[28,%d,%d]" % (bucket, bucket) in text \
        or "f32[1,28,%d,%d]" % (bucket, bucket) in text
    kernels = (text.count("%gqa_prefill_attention") >= 4,
               text.count("%gqa_window_prefill_attention") >= 12)
    assert (kernels, scores) == (((True, True), False) if bucket >= 1024
                                 else ((False, False), True))
    assert text.count("ragged-dot") >= 3 * 16
    _grouped_tiles_are_the_rules(text, bucket * 6, 16, 64, 2560, 768)
    # 11.5 GB of weights and pools are resident beside it
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2.1 * 2 ** 30
    if bucket == 12288:     # no more than with every pair's rows (PR 42)
        assert temp <= 1540893184


def test_both_groups_pool_write_is_in_place_on_the_chip(topo):
    """The donated write of a decode step's rows into both groups'
    pools, one program: every pool comes out aliased to what went in
    and none is copied."""
    from mxnet_tpu.models import window_moe as wm
    from mxnet_tpu.ops import kv_cache

    one = SingleDeviceSharding(topo.devices[0])
    doc, cfg, _ = _window_shapes(one)
    serve = doc["deployment"]["serve"]
    definition = wm.lm_definition(cfg)
    pools = _pools(definition, serve, one)
    rows = jax.ShapeDtypeStruct((16, 48, 512), BF16, sharding=one)
    at = tuple(jax.ShapeDtypeStruct((48,), jnp.int32, sharding=one)
               for _ in pools)
    compiled = kv_cache._write_groups.lower(
        pools, pools, rows, rows, at, at,
        layers=tuple(layers for layers, _ in definition.cache_groups)
    ).compile()
    pool_bytes = sum(int(np.prod(p.shape)) * 2 for p in pools)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert _big_moves(compiled.as_text(), pool_bytes // 16) == []
    assert mem.temp_size_in_bytes < 2 ** 26
