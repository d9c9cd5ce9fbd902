"""The parallel Mamba-2 / attention family through the program:
``models.parallel_hybrid`` served by ``LMBackend`` behind
``GenerationScheduler`` and the HTTP front end, as one pipeline stage of
whole layers (``deployment`` of the configuration: no layer is shared,
so every head and every row of the vocabulary is here).

The weights are the benchmark's input, made on the device from the seed
in the deployment's dtype under the program's checkpoint names; the
program and the plain reference both get them.  They are 10.5 GB for
``falcon-h1-34b-pp12`` beside 1.6 GB of state, so a process keeps the
seed's weights it made last and hands the same arrays to whoever asks
for that seed again (the reference, after the window).  Every call
first collects what is left in cycles: the reference's 10,240-wide
forward needs the pools' room, and a closed scheduler (and with it its
backend and its pools) is let go only by the collector.

**The draws.**  Under the family's published multipliers a matrix's
scale is part of the model: ``k = key_multiplier W_k u`` with
``key_multiplier`` 0.011 says ``W_k``'s entries are ninety times
``W_q``'s where keys and queries are of a size, and normal(0, 0.02)
everywhere would leave every branch's update a thousandth of the
residual stream and the logits flat.  So the configuration's ``draw``
gives a deviation for each kind of matrix (:func:`weight_kind`;
``in_weight``'s four parts each their own), set so that the scaled
product has a stated deviation on a unit-RMS input; gains and the skip
``D`` are 1, ``A_log`` and ``dt_bias`` as
``benchmark/models/gated_delta_moe.py`` draws them.  What the driver
keeps of a decode step's logits
(``deployment.serve.checked_logit_parts``) is that module's rule and
classes too.
"""

import gc

from benchmark.models import gated_delta_moe as _state_family
from benchmark.models.gated_delta_moe import keeping_parts, weight_key

_made = {}               # seed -> weights, the last seed only

_KINDS = (("_gamma", "one"), ("_D", "one"), ("A_log", "decay"),
          ("dt_bias", "dt"))
#: the rows of ``in_weight``, as the configuration's ``draw`` names them
IN_PARTS = ("in_weight.z", "in_weight.x", "in_weight.B", "in_weight.C")


def program_config(cfg):
    """The program's configuration of the benchmark's file: the
    published keys, the deployment's context limit."""
    from mxnet_tpu.models import parallel_hybrid

    return parallel_hybrid.lm_config(cfg, seq_len=cfg["n_positions"])


def weight_shapes(cfg):
    from mxnet_tpu.models import parallel_hybrid

    return parallel_hybrid.param_shapes(program_config(cfg))


def weight_kind(name):
    """``one`` / ``decay`` / ``dt`` as the sibling draws them, else the
    parameter's name without its layer: the key of its deviation in the
    configuration's ``draw``."""
    for suffix, kind in _KINDS:
        if name.endswith(suffix):
            return kind
    return name.split("_", 1)[1] if name[0] == "l" and name[1].isdigit() \
        else name


def in_part_rows(cfg):
    """Rows of each of ``in_weight``'s four parts ``[z | x | B | C]``."""
    inner = cfg["mamba_d_ssm"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return (inner, inner, bc, bc)


def deviation(cfg, kind):
    """What a weight of ``kind`` is drawn with: a number, for
    ``in_weight`` one a part, None for the sibling's kinds."""
    table = cfg["draw"]["deviation"]
    if kind == "in_weight":
        return tuple(table[part] for part in IN_PARTS)
    return table.get(kind)


def _draw(key, shape, kind, dtype, scale, rows):
    """One leaf: normal(0, ``scale``), a tuple a deviation for each run
    of ``rows``; with no ``scale`` the sibling's draw of the kind."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if scale is None:
        return _state_family._draw(key, shape, kind, dtype)
    if isinstance(scale, tuple):
        scale = jnp.asarray(np.repeat(np.asarray(scale, np.float32),
                                      rows))[:, None]
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_weights(cfg, seed):
    """The seed's weights on the device, in the dtype the deployment
    serves in (bfloat16), each kind at its deviation; ``A_log`` and
    ``dt_bias`` float32.  A leaf a call (one program for all would hold
    the float32 normals of every leaf at once), one compiled program a
    shape and kind.  The same arrays when the seed is asked for
    again."""
    import jax

    # what a run left in cycles goes before anything is made or handed
    # on: the closed scheduler's backend, its pools with it
    gc.collect()
    if seed not in _made:
        _made.clear()               # the former seed's go first
        gc.collect()
        draw = jax.jit(_draw, static_argnums=(1, 2, 3, 4, 5))
        key = weight_key(seed)
        dtype = cfg["deployment"]["serve"]["dtype"]
        rows = in_part_rows(cfg)
        _made[seed] = {
            name: draw(jax.random.fold_in(key, i), shape, weight_kind(name),
                       dtype, deviation(cfg, weight_kind(name)), rows)
            for i, (name, shape) in enumerate(
                sorted(weight_shapes(cfg).items()))}
    return dict(_made[seed])


def build_backend(cfg, serve, weights, model_name, wrap):
    """``LMBackend`` handed this model's definition (weights, key and
    value pools in the deployment's dtype and a state pool of
    ``state_slots`` slots, both over every layer), subclassed by
    ``wrap`` so the benchmark can put spans and counts around
    ``prefill`` and ``decode``."""
    import jax.numpy as jnp

    from mxnet_tpu import serving
    from mxnet_tpu.models import parallel_hybrid

    definition = parallel_hybrid.lm_definition(program_config(cfg),
                                               jnp.dtype(serve["dtype"]))
    base = serving.LMBackend
    if serve.get("checked_logit_parts"):
        base = keeping_parts(base, serve["checked_logit_parts"])
    return wrap(base)(
        weights, definition=definition, block_size=serve["block_size"],
        num_blocks=serve["num_blocks"], model=model_name,
        state_slots=serve["state_slots"])
