"""Operations and bytes of a routed expert of two matrices that works in
a latent narrower than the model (``W2 relu(W1 u)^2``, ``u``
``moe_latent_size`` wide: the ``state_space_moe`` family's), from the
configuration's sizes: what ``readers/relu2_expert_roofline.py`` divides
by the peaks.  The siblings' ``latent_moe_costs`` counts three matrices
over ``hidden_size``; read through it this expert's share would stand
near six times too high.
"""

from benchmark.ssm_costs import configuration  # noqa: F401  (re-exported)


def expert_parameters(cfg):
    """Parameters of one routed expert: two matrices between the latent
    and ``moe_intermediate_size``."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def expert_weight_bytes(cfg, bytes_per_value=2):
    return expert_parameters(cfg) * bytes_per_value


def expert_flops_per_assignment(cfg):
    """A multiply and an add a parameter for one token through one
    expert."""
    return 2 * expert_parameters(cfg)


def routed_experts_cost(cfg, experts_hit, local_assignments,
                        bytes_per_value=2):
    """(operations, bytes) of the routed experts' products over a
    stretch: every held expert that got a token has its weights read
    once a layer and call; every (token, held expert) pair is one pass
    through an expert, reading its latent row, writing and reading its
    hidden row and writing its latent output."""
    d, h = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    ops = local_assignments * expert_flops_per_assignment(cfg)
    moved = experts_hit * expert_weight_bytes(cfg, bytes_per_value) \
        + local_assignments * (2 * d + 2 * h) * bytes_per_value
    return ops, moved
