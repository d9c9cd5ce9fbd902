"""The gated short-convolution / grouped-query / sparse-expert family
through the program: ``models.short_conv_moe`` served by ``LMBackend``
behind ``GenerationScheduler`` and the HTTP front end, as one pipeline
stage of a deployment (``deployment`` of the configuration: the layers
that are here, the router's published width, the experts held here).

The weights are the benchmark's input, made on the device from the seed
in the deployment's dtype under the program's checkpoint names; the
program and the plain reference both get them.  They are 9.33 GB for
``lfm2-8b-a1b-pp2``, so a process keeps the seed's weights it made last
and hands the same arrays to whoever asks for that seed again (the
reference, after the window): the chip cannot hold them twice.

**What the driver keeps of a decode step's logits.**  ``[64 rows,
65,536]`` float32 is 16.8 MB a step, 45 GB over the ~2,700 steps of a
run on a host of 40.  Where the configuration's ``deployment.serve``
gives ``checked_logit_parts`` ``P``, the backend this module builds
hands the driver one of ``P`` equal parts of the vocabulary of every row
instead, the part its position names: the rule and the classes are
``benchmark/models/gated_delta_moe.py``'s (``kept_part``,
``KeptLogits``), which says what the comparison still sees.
"""

import gc

from benchmark.models.gated_delta_moe import keeping_parts
# normal(0, 0.02) matrices and embedding, gains 1, a float32 selection
# bias normal(0, 0.01) (not a no-op: sigmoid scores): the latent
# family's draw, by its kinds
from benchmark.models.latent_moe import _draw, weight_key

_made = {}               # seed -> weights, the last seed only


def program_config(cfg):
    """The program's configuration of the benchmark's file: the router
    as wide as published, the held experts, the deployment's context
    limit."""
    from mxnet_tpu.models import short_conv_moe

    share = cfg["deployment"]["experts"]
    if share["held"] != cfg["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    published = dict(cfg, num_experts=share["published"])
    return short_conv_moe.lm_config(
        published, seq_len=cfg["n_positions"],
        held=(share["first"], share["held"]))


def weight_shapes(cfg):
    from mxnet_tpu.models import short_conv_moe

    return short_conv_moe.param_shapes(program_config(cfg))


def weight_kind(name):
    return "gain" if name.endswith("_gamma") else \
        "bias" if name.endswith("expert_bias") else "matrix"


def make_weights(cfg, seed):
    """The seed's weights on the device, in the dtype the deployment
    serves in (bfloat16): normal(0, 0.02) matrices and embedding, gains
    1, the router's selection bias normal(0, 0.01) in float32.  A leaf a
    call (one program for all would hold the float32 normals of every
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
            name: draw(jax.random.fold_in(key, i), shape, weight_kind(name),
                       dtype)
            for i, (name, shape) in enumerate(
                sorted(weight_shapes(cfg).items()))}
    return dict(_made[seed])


def build_backend(cfg, serve, weights, model_name, wrap):
    """``LMBackend`` handed this model's definition (weights, key and
    value pools and a convolution-state pool of ``state_slots`` slots in
    the deployment's dtype), subclassed by ``wrap`` so the benchmark can
    put spans and counts around ``prefill`` and ``decode``."""
    import jax.numpy as jnp

    from mxnet_tpu import serving
    from mxnet_tpu.models import short_conv_moe

    definition = short_conv_moe.lm_definition(program_config(cfg),
                                              jnp.dtype(serve["dtype"]))
    base = serving.LMBackend
    if serve.get("checked_logit_parts"):
        base = keeping_parts(base, serve["checked_logit_parts"])
    return wrap(base)(
        weights, definition=definition, block_size=serve["block_size"],
        num_blocks=serve["num_blocks"], model=model_name,
        state_slots=serve["state_slots"])
