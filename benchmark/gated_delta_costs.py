"""Operations and bytes of the two mechanisms the ``gated_delta_moe``
family adds, from the configuration's sizes: what the rooflines of
``readers/state_decode_roofline.py`` and
``readers/gqa_decode_roofline.py`` divide by the peaks
(``flops.roofline_seconds``).  ``cfg`` is a configuration file of the
family (published keys).
"""

import json
from pathlib import Path


def configuration(name):
    """``configs/<name>.json`` beside this file."""
    path = Path(__file__).resolve().parent / "configs" / (name + ".json")
    return json.loads(path.read_text())


def linear_layers(cfg):
    """The Gated DeltaNet layers among ``num_hidden_layers``."""
    every = cfg["full_attention_interval"]
    return sum((i + 1) % every != 0 for i in range(cfg["num_hidden_layers"]))


def state_values(cfg):
    """Values of one sequence's delta-rule state in one layer."""
    return cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def tail_values(cfg):
    """Values of the convolution's tail a sequence keeps in one layer."""
    key = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return (cfg["linear_conv_kernel_dim"] - 1) * (2 * key + value)


def state_bytes(cfg, tail_bytes_per_value=2):
    """Bytes of one version of one sequence's state over all its
    DeltaNet layers: the float32 matrix and the convolution's tail."""
    return linear_layers(cfg) * (4 * state_values(cfg)
                                 + tail_bytes_per_value * tail_values(cfg))


def delta_decode_cost(cfg, row_layers):
    """(operations, bytes) of the one-step gated delta update over
    ``row_layers`` (row, layer) pairs, as the update kernel runs it: the
    float32 state read once and written once; per value of the state the
    decay, the product with the key and its sum, the rank-one update and
    the product with the query and its sum (7 operations).  The
    convolution's tail is gathered and scattered outside that kernel and
    is in neither the measured time nor here."""
    return 7 * state_values(cfg) * row_layers, \
        2 * 4 * state_values(cfg) * row_layers


def gqa_decode_cost(cfg, context_tokens, rows, bytes_per_value=2):
    """(operations, bytes) of the grouped-query decode attention of ONE
    layer over a stretch: ``context_tokens`` is the live context summed
    over rows and steps, ``rows`` the rows summed over steps.  Every
    cached token is a key row and a value row of ``kv heads x head_dim``
    values read once; every query head multiplies its query with its
    key-value head's key and adds its value (``head_dim`` wide each); a
    row reads its queries and writes its outputs."""
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    width = cfg["num_key_value_heads"] * dim
    ops = 2 * context_tokens * heads * 2 * dim
    moved = (context_tokens * 2 * width + rows * heads * 2 * dim) \
        * bytes_per_value
    return ops, moved
