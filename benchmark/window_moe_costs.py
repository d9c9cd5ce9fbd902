"""Operations and bytes of the two mechanisms the ``window_moe`` family
adds, from the configuration's sizes: the flash kernel over a **band**
(a sliding-window layer's prefill) and the decode walk over a **ring**.
What ``readers/band_prefill_roofline.py`` and
``readers/window_decode_roofline.py`` divide by the peaks
(``flops.roofline_seconds``).  ``cfg`` is a configuration file of the
family (published keys).  The expert layer's costs are the latent
family's (``latent_moe_costs.routed_experts_cost``), the global walk's
the gated-delta family's (``gated_delta_costs.gqa_decode_cost``).
"""

from benchmark.gated_delta_costs import configuration, gqa_decode_cost

__all__ = ["configuration", "band_pairs", "band_prefill_cost",
           "window_decode_cost"]


def band_pairs(tokens, window):
    """(row, key) pairs a causal band of ``window`` keys a row (the
    row's own counted) holds over a prompt of ``tokens``: the whole
    triangle up to the window, ``window`` a row past it."""
    if tokens <= window:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def band_prefill_cost(cfg, tokens, bytes_per_value=2):
    """(operations, bytes) of ONE window layer's prefill attention over
    a prompt (bucket) of ``tokens``: a score and a weighted sum of
    ``head_dim`` for every query head and pair inside the band (``4 H D
    pairs``); the queries read and the outputs written once, every
    key-value head's keys and values read once (what the rule needs: a
    kernel that is handed them repeated for their queries reads more and
    shows under 100%)."""
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    ops = 4 * heads * dim * band_pairs(tokens, cfg["sliding_window_size"])
    moved = (2 * heads + 2 * groups) * tokens * dim * bytes_per_value
    return ops, moved


def window_decode_cost(cfg, window_tokens, rows, bytes_per_value=2):
    """(operations, bytes) of the grouped-query decode attention of ONE
    window layer over a stretch: ``window_tokens`` is ``min(context,
    sliding_window_size)`` summed over rows and steps (the keys the rule
    lets a row see, its own counted: a walk that also reads the expired
    keys of its oldest block, or a whole newest block, reads more and
    shows under 100%), ``rows`` the rows summed over steps.  The
    arithmetic of a key is the global walk's."""
    return gqa_decode_cost(cfg, window_tokens, rows, bytes_per_value)
