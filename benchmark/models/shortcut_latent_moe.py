"""The shortcut-connected latent-attention, sparse-expert family through
the program: ``models.shortcut_latent_moe`` served by ``LMBackend``
behind ``GenerationScheduler`` and the HTTP front end, as one chip's
share of an expert-parallel deployment (``deployment.experts`` of the
configuration: the router's published real experts, the experts held
here; the identity experts are the configuration's ``zero_expert_num``
and are whole on every chip).

The weights are the benchmark's input, made on the device from the seed
in bfloat16 under the program's checkpoint names; the program and the
plain reference both get them.  They are 10.35 GB for
``longcat-flash-ep32``, so a process keeps the seed's weights it made
last and hands the same arrays to whoever asks for that seed again (the
reference, after the window): the chip cannot hold them twice.
"""

import gc

INIT_STD = 0.02          # matrices and embeddings
BIAS_STD = 0.001         # e_score_correction_bias: moves choices, makes
#                          none (softmax scores over 768 outputs: the
#                          configuration's `assumed` has the readings)

_made = {}               # seed -> weights, the last seed only


def program_config(cfg):
    """The program's configuration of the benchmark's file: the router
    as wide as published, the held experts, the deployment's context
    limit."""
    from mxnet_tpu.models import shortcut_latent_moe

    share = cfg["deployment"]["experts"]
    if share["held"] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    published = dict(cfg, n_routed_experts=share["published"])
    return shortcut_latent_moe.lm_config(
        published, seq_len=cfg["n_positions"],
        held=(share["first"], share["held"]))


def weight_shapes(cfg):
    from mxnet_tpu.models import shortcut_latent_moe

    return shortcut_latent_moe.param_shapes(program_config(cfg))


def _draw(key, shape, kind, dtype):
    import jax
    import jax.numpy as jnp

    if kind == "gain":
        return jnp.ones(shape, dtype)
    if kind == "bias":
        return BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    return (INIT_STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def weight_key(seed):
    import jax

    return jax.random.PRNGKey(seed % (2 ** 31))


def make_weights(cfg, seed):
    """The seed's weights on the device, in the dtype the deployment
    serves in (bfloat16): normal(0, 0.02) matrices and embeddings, gains
    1, the router's selection bias normal(0, 0.001) in float32.  A leaf
    a call (one program for all would hold the float32 normals of every
    leaf at once), one compiled program a shape.  The same arrays when
    the seed is asked for again."""
    import jax

    if seed not in _made:
        _made.clear()               # the former seed's go first, and
        gc.collect()                # what a former run left in cycles
        draw = jax.jit(_draw, static_argnums=(1, 2, 3))
        key = weight_key(seed)
        dtype = cfg["deployment"]["serve"]["dtype"]
        _made[seed] = {
            name: draw(jax.random.fold_in(key, i), shape,
                       "gain" if name.endswith("_gamma") else
                       "bias" if name.endswith("router_bias") else "matrix",
                       dtype)
            for i, (name, shape) in enumerate(
                sorted(weight_shapes(cfg).items()))}
    return dict(_made[seed])


def build_backend(cfg, serve, weights, model_name, wrap):
    """``LMBackend`` handed this model's definition (bfloat16 weights, a
    bfloat16 latent pool of two rows a token and layer), subclassed by
    ``wrap`` so the benchmark can put spans and counts around
    ``prefill`` and ``decode``."""
    import jax.numpy as jnp

    from mxnet_tpu import serving
    from mxnet_tpu.models import shortcut_latent_moe

    definition = shortcut_latent_moe.lm_definition(
        program_config(cfg), jnp.dtype(serve["dtype"]))
    return wrap(serving.LMBackend)(
        weights, definition=definition, block_size=serve["block_size"],
        num_blocks=serve["num_blocks"], model=model_name)
