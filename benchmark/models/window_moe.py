"""The sliding-window / global grouped-query / ReGLU-expert family
through the program: ``models.window_moe`` served by ``LMBackend``
behind ``GenerationScheduler`` and the HTTP front end, as one chip's
share of an expert-parallel deployment (``deployment`` of the
configuration: the layers that are here, the router's published width,
the experts held here, the vocabulary's slice).

The weights are the benchmark's input, made on the device from the seed
in the deployment's dtype under the program's checkpoint names; the
program and the plain reference both get them.  They are 4.09 GB for
``smallthinker-21b-ep4`` beside 7.4 GB of key and value pools, so a
process keeps the seed's weights it made last and hands the same arrays
to whoever asks for that seed again (the reference, after the window).
Every call first collects what is left in cycles: the reference's
16,384-wide forward needs the pools' room, and a closed scheduler (and
with it its backend and both pools) is let go only by the collector.

**Two pools.**  ``deployment.serve.num_blocks`` is a pair, the global
layers' blocks and the window layers' (the cache's two layer groups, in
that order): ``LMBackend`` hands it to the cache as it is.

**What the driver keeps of a decode step's logits.**  ``[48 rows,
37,984]`` float32 is 7.3 MB a step.  Where the configuration's
``deployment.serve`` gives ``checked_logit_parts`` ``P``, the backend
this module builds hands the driver one of ``P`` equal parts of the
vocabulary of every row instead, the part its position names: the rule
and the classes are ``benchmark/models/gated_delta_moe.py``'s
(``kept_part``, ``KeptLogits``), which says what the comparison still
sees.
"""

import gc

from benchmark.models.gated_delta_moe import keeping_parts
# normal(0, 0.02) matrices, embedding and head, gains 1: the latent
# family's draw, by its kinds (this family has no bias)
from benchmark.models.latent_moe import _draw, weight_key

_made = {}               # seed -> weights, the last seed only


def program_config(cfg):
    """The program's configuration of the benchmark's file: the router
    as wide as published, the held experts, the deployment's context
    limit."""
    from mxnet_tpu.models import window_moe

    share = cfg["deployment"]["experts"]
    if share["held"] != cfg["moe_num_primary_experts"]:
        raise ValueError("moe_num_primary_experts counts the experts held "
                         "here")
    published = dict(cfg, moe_num_primary_experts=share["published"])
    return window_moe.lm_config(
        published, seq_len=cfg["n_positions"],
        held=(share["first"], share["held"]))


def weight_shapes(cfg):
    from mxnet_tpu.models import window_moe

    return window_moe.param_shapes(program_config(cfg))


def make_weights(cfg, seed):
    """The seed's weights on the device, in the dtype the deployment
    serves in (bfloat16): normal(0, 0.02) matrices, embedding and head,
    gains 1.  A leaf a call (one program for all would hold the float32
    normals of every leaf at once), one compiled program a shape.  The
    same arrays when the seed is asked for again."""
    import jax

    # what a run left in cycles goes before anything is made or handed
    # on: the closed scheduler's backend, 7.4 GB of pools with it
    gc.collect()
    if seed not in _made:
        _made.clear()               # the former seed's go first
        gc.collect()
        draw = jax.jit(_draw, static_argnums=(1, 2, 3))
        key = weight_key(seed)
        dtype = cfg["deployment"]["serve"]["dtype"]
        _made[seed] = {
            name: draw(jax.random.fold_in(key, i), shape,
                       "gain" if name.endswith("_gamma") else "matrix",
                       dtype)
            for i, (name, shape) in enumerate(
                sorted(weight_shapes(cfg).items()))}
    return dict(_made[seed])


def build_backend(cfg, serve, weights, model_name, wrap):
    """``LMBackend`` handed this model's definition (weights and the two
    layer groups' key and value pools in the deployment's dtype),
    subclassed by ``wrap`` so the benchmark can put spans and counts
    around ``prefill`` and ``decode``."""
    import jax.numpy as jnp

    from mxnet_tpu import serving
    from mxnet_tpu.models import window_moe

    definition = window_moe.lm_definition(program_config(cfg),
                                          jnp.dtype(serve["dtype"]))
    base = serving.LMBackend
    if serve.get("checked_logit_parts"):
        base = keeping_parts(base, serve["checked_logit_parts"])
    return wrap(base)(
        weights, definition=definition, block_size=serve["block_size"],
        num_blocks=serve["num_blocks"], model=model_name)
