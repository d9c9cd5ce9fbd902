"""The GPT-2 family through the program: ``models.transformer`` trained
by ``ShardedTrainer`` and served by ``LMBackend`` behind
``GenerationScheduler`` and the HTTP front end.

The weights are the benchmark's input, made on the device from the seed
in one jitted call under the program's checkpoint names; the program
and the plain reference both get them.
"""

import numpy as np

INIT_STD = 0.02          # GPT-2's initializer_range


def weight_shapes(cfg):
    d, v, t = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    ffn = cfg.get("n_inner") or 4 * d
    shapes = {"embed_weight": (v, d), "pos_embed_weight": (1, t, d),
              "final_ln_gamma": (d,), "final_ln_beta": (d,),
              "pred_weight": (v, d), "pred_bias": (v,)}
    for i in range(cfg["n_layer"]):
        p = "l%d_" % i
        shapes.update({
            p + "ln1_gamma": (d,), p + "ln1_beta": (d,),
            p + "ln2_gamma": (d,), p + "ln2_beta": (d,),
            p + "attn_qkv_weight": (3 * d, d), p + "attn_out_weight": (d, d),
            p + "ffn1_weight": (ffn, d), p + "ffn1_bias": (ffn,),
            p + "ffn2_weight": (d, ffn), p + "ffn2_bias": (d,)})
    return shapes


def weight_maker(cfg):
    """float32 weights from the seed, on the device, as a function of the
    key (made inside whatever program needs them):
    normal(0, 0.02) matrices and embeddings, LayerNorm gains 1, every
    bias 0."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if name.endswith("_gamma"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith(("_beta", "_bias")):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return make


def make_weights(cfg, seed, shardings=None):
    """The seed's weights on the device, one jitted call."""
    import jax

    return jax.jit(weight_maker(cfg), out_shardings=shardings)(
        weight_key(seed))


def weight_key(seed):
    import jax

    return jax.random.PRNGKey(seed % (2 ** 31))


# ----------------------------------------------------------------------
# training


def build_trainer(cfg, train, mesh):
    """The trainer as a user builds it (``chip_smoke.py``,
    ``bench.py``'s transformer lane): bf16 activations over float32
    master weights, SGD with momentum on the fused tree path."""
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    batch, seq = train["batch"], train["seq_len"]
    sym = transformer.get_symbol(
        num_classes=cfg["vocab_size"], seq_len=seq, num_embed=cfg["n_embd"],
        num_heads=cfg["n_head"], num_layers=cfg["n_layer"],
        dtype=train["dtype"])
    opt = train["optimizer"]
    trainer = ShardedTrainer(
        sym, mesh, data_shapes={"data": (batch, seq)},
        label_shapes={"softmax_label": (batch, seq)},
        type_dict={"data": "int32"}, learning_rate=opt["learning_rate"],
        momentum=opt["momentum"], wd=opt.get("wd", 0.0),
        rescale_grad=1.0 / (batch * seq))
    return trainer, {"items_per_step": batch * seq, "label": "softmax_label"}


def weights_for_training(cfg, train):
    """The positions table holds the trained length only."""
    return dict(cfg, n_positions=train["seq_len"])


def batch_maker(cfg, train, seed):
    """A function that returns a new seeded host batch at every call."""
    rng = np.random.RandomState(seed % (2 ** 32))
    shape = (train["batch"], train["seq_len"])
    vocab = cfg["vocab_size"]

    def make():
        return {"data": rng.randint(0, vocab, shape).astype(np.int32),
                "softmax_label": rng.randint(0, vocab, shape)
                .astype(np.float32)}

    return make


def train_flops_per_item(cfg, train):
    from benchmark import flops

    return flops.gpt2_train_flops_per_token(cfg, train["seq_len"])


def attention_calls(cfg, train, chips):
    """Shapes of the flash calls of one step, for the roofline:
    ``n_layer`` forward and ``n_layer`` backward calls, each chip
    taking its share of batch x heads."""
    return {"calls": cfg["n_layer"], "chips": chips,
            "batch": train["batch"],
            "heads": cfg["n_head"], "seq_len": train["seq_len"],
            "head_dim": cfg["n_embd"] // cfg["n_head"]}


# ----------------------------------------------------------------------
# serving


def lm_config(cfg):
    from mxnet_tpu.models import transformer

    return transformer.lm_config(
        num_classes=cfg["vocab_size"], seq_len=cfg["n_positions"],
        num_embed=cfg["n_embd"], num_heads=cfg["n_head"],
        num_layers=cfg["n_layer"])


def build_backend(cfg, serve, weights, model_name, wrap):
    """``LMBackend`` as ``tools/serve.py`` builds it — float32 numpy
    weights, a host KV pool — subclassed by ``wrap`` so the benchmark
    can put spans and counts around ``prefill`` and ``decode``."""
    from mxnet_tpu import serving

    # copies in ordinary host memory: np.asarray of a device array is a
    # view of the runtime's transfer buffer, and the device reads that
    # back five times slower than it reads numpy's own memory (my chip
    # run, PR 23: 2-3.6 s a decode call against 0.5 s)
    params = {k: np.array(v, copy=True) for k, v in weights.items()}
    return wrap(serving.LMBackend)(
        params, lm_config(cfg), block_size=serve["block_size"],
        num_blocks=serve["num_blocks"], model=model_name)
