"""Operations and bytes of the selective state-space update the
``state_space_moe`` family adds, from the configuration's sizes: what
the roofline of ``readers/ssm_decode_roofline.py`` divides by the peaks
(``flops.roofline_seconds``).  ``cfg`` is a configuration file of the
family (published keys).
"""

import json
from pathlib import Path


def configuration(name):
    """``configs/<name>.json`` beside this file."""
    path = Path(__file__).resolve().parent / "configs" / (name + ".json")
    return json.loads(path.read_text())


def state_layers(cfg):
    """The Mamba-2 layers among the ``num_hidden_layers`` built."""
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]].count(
        "M")


def state_values(cfg):
    """Values of one sequence's recurrent state in one layer: ``heads x
    head_dim x state_size``."""
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]


def tail_values(cfg):
    """Values of the convolution's tail a sequence keeps in one layer:
    ``conv_kernel - 1`` rows of ``[x | B | C]``."""
    return (cfg["conv_kernel"] - 1) * (
        cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
        + 2 * cfg["n_groups"] * cfg["ssm_state_size"])


def state_bytes(cfg, tail_bytes_per_value=2):
    """Bytes of one version of one sequence's state over all its
    state-space layers: the float32 state and the convolution's tail."""
    return state_layers(cfg) * (4 * state_values(cfg)
                                + tail_bytes_per_value * tail_values(cfg))


def ssm_decode_cost(cfg, row_layers):
    """(operations, bytes) of the one-step update over ``row_layers``
    (row, layer) pairs, as the update kernel runs it: the float32 state
    read once and written once; per value of the state the decay, the
    outer product's multiply and add, and the product with ``C`` and its
    sum (5 operations).  The convolution's tail is gathered and
    scattered outside that kernel and is in neither the measured time
    nor here."""
    return 5 * state_values(cfg) * row_layers, \
        2 * 4 * state_values(cfg) * row_layers
