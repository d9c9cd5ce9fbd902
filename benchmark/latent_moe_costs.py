"""Operations and bytes of the two mechanisms the ``latent_moe`` family
adds, from the configuration's sizes: what the rooflines of
``readers/moe_expert_roofline.py`` and
``readers/latent_decode_roofline.py`` divide by the peaks
(``flops.roofline_seconds``).  ``cfg`` is a configuration file of the
family (published keys).
"""

import json
from pathlib import Path


def configuration(name):
    """``configs/<name>.json`` beside this file."""
    path = Path(__file__).resolve().parent / "configs" / (name + ".json")
    return json.loads(path.read_text())


def expert_weight_bytes(cfg, bytes_per_value=2):
    """Bytes of one routed expert's three matrices (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * bytes_per_value


def expert_flops_per_assignment(cfg):
    """Multiply-adds x 2 of one token through one routed expert."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_experts_cost(cfg, experts_hit, local_assignments,
                        bytes_per_value=2):
    """(operations, bytes) of the routed experts' products over a
    stretch: every held expert that got a token has its weights read
    once a layer and call; every (token, held expert) pair is one pass
    through an expert, reading and writing its activations."""
    d, h = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = local_assignments * expert_flops_per_assignment(cfg)
    moved = experts_hit * expert_weight_bytes(cfg, bytes_per_value) \
        + local_assignments * (2 * d + 3 * h) * bytes_per_value
    return ops, moved


def latent_decode_cost(cfg, context_tokens, rows, bytes_per_value=2):
    """(operations, bytes) of the absorbed decode attention of ONE
    layer over a stretch: ``context_tokens`` is the live context summed
    over rows and steps, ``rows`` the rows summed over steps.  Every
    cached token is one row of ``kv_lora_rank + qk_rope_head_dim``
    values read once; every head multiplies its query with it (that
    width) and adds it into its output (``kv_lora_rank`` wide); a row
    reads its queries and writes its attended latents."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    width = rank + cfg["qk_rope_head_dim"]
    ops = 2 * context_tokens * heads * (width + rank)
    moved = (context_tokens * width + rows * heads * (width + rank)) \
        * bytes_per_value
    return ops, moved
