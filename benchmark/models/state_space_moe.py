"""The Mamba-2 / attention / latent-expert family through the program:
``models.state_space_moe`` served by ``LMBackend`` behind
``GenerationScheduler`` and the HTTP front end, as one chip's share of
an expert-parallel deployment (``deployment`` of the configuration: the
layers that are here, the router's published width, the experts held
here, the vocabulary's slice).

The weights are the benchmark's input, made on the device from the seed
in the deployment's dtype under the program's checkpoint names; the
program and the plain reference both get them.  They are 9.30 GB for
``nemotron3-super-ep4`` beside 2.7 GB of state, so a process keeps the
seed's weights it made last and hands the same arrays to whoever asks
for that seed again (the reference, after the window).  Every call
first collects what is left in cycles: the reference's 17,408-wide
forward needs the pools' room, and a closed scheduler (and with it its
backend and its pools) is let go only by the collector.

The draws are the siblings', by their kinds: gains, the skip ``D`` and
matrices, ``A_log`` and ``dt_bias`` as
``benchmark/models/gated_delta_moe.py`` draws them (``A = exp(A_log)``
uniform in log between 0.001 and 0.7, ``dt_bias`` normal(0, 0.1)), the
router's selection bias as ``benchmark/models/latent_moe.py`` draws
one (normal(0, 0.01), float32).  What the driver keeps of a decode
step's logits (``deployment.serve.checked_logit_parts``) is
``gated_delta_moe.py``'s rule and classes too.
"""

import gc

from benchmark.models import gated_delta_moe as _state_family
from benchmark.models import latent_moe as _latent_family
from benchmark.models.gated_delta_moe import keeping_parts, weight_key

_made = {}               # seed -> weights, the last seed only

_KINDS = (("_gamma", "one"), ("_D", "one"), ("A_log", "decay"),
          ("dt_bias", "dt"), ("router_bias", "bias"))


def program_config(cfg):
    """The program's configuration of the benchmark's file: the router
    as wide as published, the held experts, the deployment's context
    limit."""
    from mxnet_tpu.models import state_space_moe

    share = cfg["deployment"]["experts"]
    if share["held"] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    published = dict(cfg, n_routed_experts=share["published"])
    return state_space_moe.lm_config(
        published, seq_len=cfg["n_positions"],
        held=(share["first"], share["held"]))


def weight_shapes(cfg):
    from mxnet_tpu.models import state_space_moe

    return state_space_moe.param_shapes(program_config(cfg))


def weight_kind(name):
    return next((kind for suffix, kind in _KINDS if name.endswith(suffix)),
                "normal")


def _draw(key, shape, kind, dtype):
    family = _latent_family if kind == "bias" else _state_family
    return family._draw(key, shape, kind, dtype)


def make_weights(cfg, seed):
    """The seed's weights on the device, in the dtype the deployment
    serves in (bfloat16), by :func:`weight_kind`; ``A_log``, ``dt_bias``
    and the selection bias float32.  A leaf a call (one program for all
    would hold the float32 normals of every leaf at once), one compiled
    program a shape.  The same arrays when the seed is asked for
    again."""
    import jax

    # what a run left in cycles goes before anything is made or handed
    # on: the closed scheduler's backend, its pools with it
    gc.collect()
    if seed not in _made:
        _made.clear()               # the former seed's go first
        gc.collect()
        draw = jax.jit(_draw, static_argnums=(1, 2, 3))
        key = weight_key(seed)
        dtype = cfg["deployment"]["serve"]["dtype"]
        _made[seed] = {
            name: draw(jax.random.fold_in(key, i), shape,
                       weight_kind(name), dtype)
            for i, (name, shape) in enumerate(
                sorted(weight_shapes(cfg).items()))}
    return dict(_made[seed])


def build_backend(cfg, serve, weights, model_name, wrap):
    """``LMBackend`` handed this model's definition (weights, key and
    value pools in the deployment's dtype, a state pool of
    ``state_slots`` slots: the recurrence's float32 state and the
    convolution's rows), subclassed by ``wrap`` so the benchmark can put
    spans and counts around ``prefill`` and ``decode``."""
    import jax.numpy as jnp

    from mxnet_tpu import serving
    from mxnet_tpu.models import state_space_moe

    definition = state_space_moe.lm_definition(program_config(cfg),
                                               jnp.dtype(serve["dtype"]))
    base = serving.LMBackend
    if serve.get("checked_logit_parts"):
        base = keeping_parts(base, serve["checked_logit_parts"])
    return wrap(base)(
        weights, definition=definition, block_size=serve["block_size"],
        num_blocks=serve["num_blocks"], model=model_name,
        state_slots=serve["state_slots"])
