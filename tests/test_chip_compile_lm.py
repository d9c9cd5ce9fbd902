"""GPT-2 medium's programs compiled for the described v5e at
``gpt2m-serve-chat``'s sizes: the whole decode step over the pool where
it lies, with either body of the block-table walk, the prefill at every
bucket, and ``chip_smoke.py --chips 4``'s training step on a mesh of
four described chips.  A file of its own beside ``test_chip_compile.py``
(the kernels' compiles) because a file is the unit of distribution of
the tier-1 run and these compiles take a few minutes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import mxnet_tpu  # noqa: F401  (registers ops)

from chip_compile_helpers import (
    F32, _POOL, _holds, _named_calls, _pool_sized, _traffic)


class _Shapes(object):
    """``init_lm_params`` for its names and shapes alone."""

    def __init__(self, seed):
        pass

    def randn(self, *shape):
        return np.broadcast_to(np.float32(0), shape)


@pytest.mark.parametrize("body", ["xla", "kernel"])
def test_decode_step_reads_the_pool_where_it_lies(topo, monkeypatch,
                                                  request, body):
    """GPT-2 medium's whole decode step at the benchmark's sizes (16
    rows, 64-block tables, the 680-block pool): no layer of the pool is
    re-laid before it is read and none is sliced out of it, with the
    XLA body's gather and with the kernel a TPU runs (24 custom calls
    named by their scope).  (Sliced as ``k_pages[i]`` the program copied
    each layer's 44 MB out of the pool every step and held all 48
    copies, 1.8 GB, as temporaries.)"""
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.serving import generation

    if body == "kernel":
        request.getfixturevalue("on_tpu")
    monkeypatch.setattr(np.random, "RandomState", _Shapes)
    cfg = tfm.lm_config(num_classes=50257, seq_len=1024, num_embed=1024,
                        num_heads=16, num_layers=_POOL[0])
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = {k: s(v.shape, v.dtype)
              for k, v in tfm.init_lm_params(cfg).items()}

    # the program LMBackend.decode runs: the step, its K/V as the
    # cache's rows, the greedy ids beside the logits
    step = generation.with_greedy_ids(tfm.lm_definition(cfg).decode)
    rows = s((16,), jnp.int32)
    compiled = jax.jit(step).lower(
        params, rows, rows, s(_POOL), s(_POOL), s((16, 64), jnp.int32),
        rows).compile()
    text = compiled.as_text()
    assert _pool_sized(text) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    if body == "kernel":
        # exactly its 24 decode kernels and no other custom call (the
        # LayerNorm and GELU row kernels went in PR 28)
        assert _named_calls(text, "paged_decode_attention") == _POOL[0]
        assert text.count("tpu_custom_call") == _POOL[0]
        assert temp < 64 * 2 ** 20      # the gathered keys are gone
    else:
        assert temp < 512 * 2 ** 20


_GPT2_BUCKETS = _traffic("serve-chat-closed16.json")["prefill_buckets"]


@pytest.mark.parametrize("bucket", _GPT2_BUCKETS)
def test_gpt2_prefill_buckets_run_the_exact_softmax(topo, on_tpu,
                                                    monkeypatch, bucket):
    """``gpt2m-serve-chat``'s prefill at every bucket (all under 1024),
    two layers at the model's width: ``stable_causal_attention`` hands a
    TPU prefill to ``_flash_dispatch``, which below 1024 tokens takes the
    einsum softmax: no flash custom call, and not the CPU contract's
    mul-reduce over ``[B, H, T, K, D]`` either."""
    from mxnet_tpu.models import transformer as tfm

    monkeypatch.setattr(np.random, "RandomState", _Shapes)
    cfg = tfm.lm_config(num_classes=50257, seq_len=1024, num_embed=1024,
                        num_heads=16, num_layers=2)
    one = SingleDeviceSharding(topo.devices[0])
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
              for k, v in tfm.init_lm_params(cfg).items()}
    text = jax.jit(lambda p, t: tfm.lm_prefill(p, t, cfg)).lower(
        params, jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one)
    ).compile().as_text()
    assert "tpu_custom_call" not in text
    assert not _holds(text, r"f32\[(1,)?16,%d,%d,64\]" % (bucket, bucket))
    assert _holds(text, r"f32\[(1,)?16,%d,%d\]" % (bucket, bucket))


# ----------------------------------------------------------------------
# four chips: a Mosaic kernel under a mesh must sit in shard_map


def _lower_step(trainer):
    """Lower a ShardedTrainer's fused step from shapes alone (no array
    can be placed on a described device)."""
    from mxnet_tpu.parallel import default_mesh

    trainer.step_fn()
    pshard, _, ashard, dshard = trainer._step_shardings()
    params = {n: jax.ShapeDtypeStruct(
        tuple(trainer.arg_shapes[n]), trainer._param_dtype(n),
        sharding=pshard[n]) for n in trainer.param_names}
    aux = {n: jax.ShapeDtypeStruct(
        tuple(s), trainer.aux_dtypes.get(n, "float32"), sharding=ashard[n])
        for n, s in trainer.aux_shapes.items()}
    batch = {n: jax.ShapeDtypeStruct(
        tuple(trainer.arg_shapes[n]), trainer.arg_dtypes.get(n, "float32"),
        sharding=dshard[n]) for n in trainer._input_names}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(trainer.mesh, P()))
    with default_mesh(trainer.mesh):
        return trainer._jit_step_raw.lower(
            params, trainer.opt_state_struct(), aux, batch, key)


def test_sharded_lm_step_compiles(topo, on_tpu):
    """``chip_smoke.py --chips 4`` in small: an LM step on a data=2 x
    model=2 mesh of four described chips, long enough (T=1024) to take
    the flash kernels.  GSPMD refuses to partition a Mosaic kernel
    ("wrap the call in a shard_map"), which no CPU run can show."""
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    batch, seq, vocab = 4, 1024, 512
    sym = transformer.get_symbol(
        num_classes=vocab, seq_len=seq, num_embed=128, num_heads=2,
        num_layers=1, dtype="bfloat16")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    trainer = ShardedTrainer(
        sym, mesh, data_shapes={"data": (batch, seq)},
        label_shapes={"softmax_label": (batch, seq)},
        type_dict={"data": "int32"}, learning_rate=1e-3, momentum=0.9,
        rescale_grad=1.0 / (batch * seq))
    text = _lower_step(trainer).compile().as_text()
    # the flash forward and its backward, one kernel each
    assert text.count("tpu_custom_call") == 2


# ----------------------------------------------------------------------
# the latent-attention, sparse-expert model at the benchmark's sizes:
# 11 GB of abstract weights, nothing allocated
