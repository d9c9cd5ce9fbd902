"""The hybrid DeltaNet / grouped-query model
(``models/gated_delta_moe.py``) compiled for the described v5e at
``qwen3next-serve-reason128``'s sizes: the decode step that updates the
state pool where it lies, and the prefill buckets that take the flash
kernel.  A file of its own beside ``test_chip_compile.py`` (the kernels'
compiles) because a file is the unit of distribution of the tier-1
run."""

import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from chip_compile_helpers import (
    BF16, F32, _big_moves, _grouped_tiles_are_the_rules, _named_calls)


def _gated_delta_shapes(one):
    """``qwen3-next-ep4`` as the benchmark builds it: the file, the
    program's configuration and its weights as shapes on the described
    chip."""
    import json

    from benchmark.spec import load_module

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "qwen3-next-ep4.json")) as f:
        doc = json.load(f)
    family = load_module(os.path.join(root, "benchmark", "models",
                                      "gated_delta_moe.py"), "family_gdm")
    params = {k: jax.ShapeDtypeStruct(
        v, F32 if family.weight_kind(k) in ("decay", "dt") else BF16,
        sharding=one) for k, v in family.weight_shapes(doc).items()}
    return doc, family.program_config(doc), params


def test_gated_delta_decode_step_updates_the_state_where_it_lies(topo,
                                                                 on_tpu):
    """The decode program of ``qwen3next-serve-reason128`` (128 rows,
    512-block tables, the 24,576-block pools of 512-wide bfloat16 rows
    over the two full-attention layers, the 3.3 GB state pool of 128
    slots in two versions over the six DeltaNet layers, donated): the
    state pool comes out aliased to what went in and is nowhere copied
    whole, the key and value pools are read as they lie by the
    grouped-query walk, once a full layer, and the temporaries stay
    under a gigabyte and a half (a layer's gathered state rows are 268
    MB)."""
    from mxnet_tpu.models import gated_delta_moe as gm
    from mxnet_tpu.serving import generation

    one = SingleDeviceSharding(topo.devices[0])
    doc, cfg, params = _gated_delta_shapes(one)
    serve = doc["deployment"]["serve"]
    definition = gm.lm_definition(cfg)

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = s((definition.cache_layers, serve["num_blocks"],
              serve["block_size"], definition.cache_row.width), BF16)
    rows = definition.state.layers * 2 * serve["state_slots"] + 1
    state = tuple(s((rows,) + shape, dtype)
                  for shape, dtype in definition.state.rows)
    b = s((128,))
    compiled = jax.jit(generation.with_greedy_ids(definition.decode),
                       donate_argnums=(7,)).lower(
        params, b, b, pool, pool,
        s((128, cfg["seq_len"] // serve["block_size"])), b, state,
        b).compile()
    assert [o.shape for o in compiled.out_info[:2]] == [
        (128, cfg["vocab_size"]), (128,)]         # logits, greedy ids
    text = compiled.as_text()
    state_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in state)
    assert state_bytes == 2 * 128 * definition.state.bytes \
        + definition.state.bytes // 6
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert _big_moves(text, state_bytes // 8) == []
    assert mem.temp_size_in_bytes < 1.5 * 2 ** 30
    assert _named_calls(text, "paged_decode_gqa_attention") \
        == definition.cache_layers
    # the state update is the kernel, once a DeltaNet layer, on the pool
    # where it lies
    assert _named_calls(text, "gated_delta_decode") \
        == definition.state.layers
    assert mem.argument_size_in_bytes > 12e9      # weights, pools, state
    # the routed experts' products of a 128-row step: every held expert
    # over every row, and no grouped kernel
    assert "ragged-dot" not in text


def test_gated_delta_prefill_holds_no_score_matrix(topo, on_tpu):
    """The largest prefill bucket (4096 tokens): the full-attention
    layers run the flash kernel on 256-wide heads under their scope's
    name, the DeltaNet layers the chunked scan, and no ``[16, T, T]``
    score matrix is held (1 GB in float32 at 4096 tokens); the 1024
    bucket takes the kernel too."""
    from mxnet_tpu.models import gated_delta_moe as gm

    one = SingleDeviceSharding(topo.devices[0])
    _, cfg, params = _gated_delta_shapes(one)
    for bucket in (1024, 4096):
        compiled = jax.jit(lambda p, t, n: gm.prefill(p, t, n, cfg)).lower(
            params, jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile()
        text = compiled.as_text()
        assert text.count("%gqa_prefill_attention") >= 2
        assert "f32[16,%d,%d]" % (bucket, bucket) not in text
        assert "f32[1,16,%d,%d]" % (bucket, bucket) not in text
    assert text.count("ragged-dot") >= 3 * cfg["num_layers"]
    _grouped_tiles_are_the_rules(text, 4096 * 10, 128, 512, 2048, 512)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30
