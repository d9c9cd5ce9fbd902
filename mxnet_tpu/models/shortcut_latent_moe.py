"""A decoder of shortcut-connected expert layers, built from its
published configuration (the LongCat-Flash language-model block): every
layer has **two** latent-attention sublayers and two dense gated SiLU
feed-forwards, and an expert branch that leaves after the first
attention and rejoins after the second feed-forward::

    a1 = x  + MLA_0(N(x))
    h  = N(a1)
    m  = MoE(h)                      # the shortcut branch
    b1 = a1 + FFN_0(h)
    a2 = b1 + MLA_1(N(b1))
    y  = a2 + FFN_1(N(a2)) + m

The latent attention, RMSNorm, the rotary turn and the head are
:mod:`~mxnet_tpu.models.latent_moe`'s, with plain rotary (the
configuration has no ``rope_scaling``) and a factor on each low-rank
path (``mla_scale_q_lora`` / ``mla_scale_kv_lora``: ``sqrt(hidden /
rank)``).  Each sublayer has its own weights and its own cache row, so
the paged pool's leading axis is ``2 * num_layers``.

The router is a softmax over ``n_routed_experts + zero_expert_num``
outputs; a selection bias is added for the choice only, ``moe_topk``
are chosen without groups, the gates are the unbiased scores times
``routed_scaling_factor`` and are not renormalised.  A chosen id of
``n_routed_experts`` or more is an **identity expert**: it adds ``gate
* h`` and computes nothing, so a token's compute varies with its
choice.  No shared expert.

Pure functions of ``(params, cfg)`` like the other families':
:func:`prefill` runs one prompt in the expanded form and returns the
rows ``[2 * layers, T, W]`` the cache keeps, :func:`decode_step` one
token a sequence in the absorbed form over the sublayers' pools.
``cfg`` is :func:`lm_config` of the published keys; ``held = (first,
count)`` says which of the ``n_routed_experts`` real experts this chip
holds (one member of an expert-parallel deployment:
:func:`~mxnet_tpu.parallel.moe.dropless_experts`); the identity
experts' term is every member's to compute whole for its own tokens.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.kv_cache import CacheRow
from ..parallel import moe as _moe
from . import latent_moe as _latent
from .lm import LMDefinition

__all__ = ["lm_config", "lm_definition", "param_shapes", "init_params",
           "prefill", "decode_step", "full_logits"]

_PUBLISHED = (
    "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_layers", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "vocab_size",
    "n_routed_experts", "zero_expert_num", "moe_topk",
    "routed_scaling_factor", "rms_norm_eps", "rope_theta")
SUBLAYERS = 2            # latent attentions (and dense feed-forwards) a layer


def lm_config(published, seq_len, held=None):
    """The program's configuration from a published ``config.json`` (a
    dict): the keys the layers read, ``seq_len`` (the deployment's
    context limit) and ``held = (first, count)`` of the
    ``n_routed_experts`` real experts (all of them if not given)."""
    cfg = {key: published[key] for key in _PUBLISHED}
    if published.get("attention_method", "MLA") != "MLA" \
            or published.get("zero_expert_type", "identity") != "identity" \
            or published.get("rope_scaling") is not None:
        raise ValueError("only latent attention with plain rotary and "
                         "identity zero-compute experts are built")
    d = cfg["hidden_size"]
    # what latent_moe's attention reads beyond the published keys
    cfg["rope_scaling"] = None
    cfg["q_lora_scale"] = math.sqrt(d / cfg["q_lora_rank"]) \
        if published.get("mla_scale_q_lora") else None
    cfg["kv_lora_scale"] = math.sqrt(d / cfg["kv_lora_rank"]) \
        if published.get("mla_scale_kv_lora") else None
    cfg["seq_len"] = int(seq_len)
    cfg["num_classes"] = cfg["vocab_size"]
    cfg["held"] = tuple(held or (0, cfg["n_routed_experts"]))
    return cfg


def router_width(cfg):
    """The router's outputs: the real experts, then the identity ones."""
    return cfg["n_routed_experts"] + cfg["zero_expert_num"]


def param_shapes(cfg):
    """name -> shape.  Matrices are ``[out, in]`` like a checkpoint's;
    sublayer ``s`` of layer ``i`` is ``l<i>_s<s>_``; the held experts of
    a layer are stacked, ``[held, in, out]``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    ffn, wide = cfg["expert_ffn_hidden_size"], cfg["ffn_hidden_size"]
    held = cfg["held"][1]
    shapes = {"embed_weight": (v, d), "final_norm_gamma": (d,),
              "pred_weight": (v, d)}
    for i in range(cfg["num_layers"]):
        for s in range(SUBLAYERS):
            p = "l%d_s%d_" % (i, s)
            shapes.update({
                p + "attn_norm_gamma": (d,),
                p + "q_a_weight": (q_rank, d),
                p + "q_a_norm_gamma": (q_rank,),
                p + "q_b_weight": (heads * (nope + rope), q_rank),
                p + "kv_a_weight": (kv_rank + rope, d),
                p + "kv_a_norm_gamma": (kv_rank,),
                p + "kv_b_weight": (heads * (nope + cfg["v_head_dim"]),
                                    kv_rank),
                p + "o_weight": (d, heads * cfg["v_head_dim"]),
                p + "ffn_norm_gamma": (d,),
                p + "ffn_gate_weight": (wide, d),
                p + "ffn_up_weight": (wide, d),
                p + "ffn_down_weight": (d, wide)})
        p = "l%d_" % i
        shapes.update({
            p + "router_weight": (router_width(cfg), d),
            p + "router_bias": (router_width(cfg),),
            p + "experts_gate_weight": (held, d, ffn),
            p + "experts_up_weight": (held, d, ffn),
            p + "experts_down_weight": (held, ffn, d)})
    return shapes


def init_params(cfg, seed=0, dtype=jnp.bfloat16, scale=0.02,
                bias_scale=0.001):
    """Seeded parameters as a function would load them: normal(0,
    ``scale``) matrices, gains 1, the router's selection bias normal(0,
    ``bias_scale``) in float32 (so that it is not a no-op; softmax
    scores over hundreds of outputs are small, and so is a bias that
    moves choices without making them)."""
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_gamma"):
            out[name] = jnp.ones(shape, dtype)
        elif name.endswith("router_bias"):
            out[name] = bias_scale * jax.random.normal(k, shape, jnp.float32)
        else:
            out[name] = (scale * jax.random.normal(k, shape, jnp.float32)
                         ).astype(dtype)
    return out


# ----------------------------------------------------------------------
# layers


def _dense(params, p, h):
    return _moe.swiglu(h, params[p + "ffn_gate_weight"],
                       params[p + "ffn_up_weight"],
                       params[p + "ffn_down_weight"])


def _route(params, p, h, cfg):
    """``(chosen [T, k], gates [T, k])`` over the router's whole width."""
    logits = jnp.einsum("nc,ec->ne", h, params[p + "router_weight"],
                        preferred_element_type=jnp.float32)
    return _moe.route_softmax_topk(
        logits, top_k=cfg["moe_topk"], normalize=False,
        bias=params[p + "router_bias"], scale=cfg["routed_scaling_factor"])


def _experts(params, p, h, cfg, valid=None):
    """The shortcut branch ``MoE(h)`` and its six counts: the held
    experts' gated sum plus the identity experts' ``(sum of gates) *
    h``."""
    with jax.named_scope("shortcut_experts"):
        chosen, gates = _route(params, p, h, cfg)
        # k of the whole width: under even routing the chance a row
        # chooses a given real expert (12 / 768 = 8 real of 512)
        routed, counts = _moe.dropless_experts(
            h, chosen, gates, params[p + "experts_gate_weight"],
            params[p + "experts_up_weight"],
            params[p + "experts_down_weight"], cfg["held"], valid=valid,
            every_row=_moe.few_rows_hit_most(
                h.shape[0], cfg["moe_topk"], router_width(cfg)),
            n_experts=router_width(cfg))
        same, zero = _moe.identity_experts(
            h, chosen, gates, cfg["n_routed_experts"], valid)
    return routed + same, jnp.concatenate([counts, zero[None]])


def _layer(params, i, x, attend, cfg, valid=None):
    """One layer: ``attend(prefix, j, x) -> (update, cache row)`` is the
    latent attention of cached sublayer ``j`` (weights under ``prefix``)
    in whichever form the caller runs.  Returns ``(y, the two
    sublayers' rows, counts)``."""
    p = "l%d_" % i
    update, row0 = attend(p + "s0_", SUBLAYERS * i, x)
    a1 = x + update
    h = _latent._norm(a1, params[p + "s0_ffn_norm_gamma"], cfg)
    m, counts = _experts(params, p, h, cfg, valid)
    b1 = a1 + _dense(params, p + "s0_", h)
    update, row1 = attend(p + "s1_", SUBLAYERS * i + 1, b1)
    a2 = b1 + update
    y = a2 + _dense(params, p + "s1_", _latent._norm(
        a2, params[p + "s1_ffn_norm_gamma"], cfg)) + m
    return y, (row0, row1), counts


def _layers(params, x, attend, cfg, valid=None):
    """Every layer over ``x``: ``(y, rows [2 L, ...], counts)``."""
    rows, counts = [], 0
    for i in range(cfg["num_layers"]):
        x, pair, count = _layer(params, i, x, attend, cfg, valid)
        rows.extend(pair)
        counts = counts + count
    return x, jnp.stack(rows), counts


# ----------------------------------------------------------------------
# the model's entry points


def forward(params, tokens, cfg, length=None):
    """One prompt ``tokens`` int32 ``[T]`` in the expanded form:
    ``(hidden [T, d] before the final norm, rows [2 L, T, row width],
    counts)``.  Positions ``>= length`` are the bucket's pad: they are
    routed to no expert, real or identity."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    valid = None if length is None else positions < length

    def attend(p, _, x):
        return _latent._attention_prefill(params, p, x, positions, cfg)

    return _layers(params, params["embed_weight"][tokens], attend, cfg,
                   valid)


def prefill(params, tokens, length, cfg):
    """``(logits float32 [V] after token length - 1, rows [2 L, T, W],
    None, counts)``: one program a bucket, whatever the prompt's real
    length; only one row of logits is computed."""
    x, rows, counts = forward(params, tokens, cfg, length)
    logits = _latent._head(
        params, jax.lax.dynamic_slice_in_dim(x, length - 1, 1), cfg)
    return logits[0], rows, None, counts


def full_logits(params, tokens, cfg):
    """float32 logits ``[B, T, V]`` of ``tokens`` ``[B, T]``, no cache:
    the classifier-lane protocol and the tests' full forward."""
    return jnp.stack([_latent._head(params, forward(params, row, cfg)[0],
                                    cfg) for row in tokens])


def decode_step(params, tokens, positions, pages, block_tables,
                context_lens, cfg):
    """One token for each of ``B`` sequences through the latent pool
    ``pages [2 L, num_blocks, block_size, W]``, read as of before the
    step: sublayer ``s`` of layer ``i`` walks pool ``2 i + s``.  Returns
    ``(logits [B, V], rows [2 L, B, W], None, counts)``; the caller
    writes ``rows`` in a dispatch of its own, behind this one."""
    num_blocks = pages.shape[1]
    pool = pages.reshape((-1,) + pages.shape[2:])

    def attend(p, j, x):
        # every sublayer gathers from the whole pool through tables
        # offset to its blocks (a slice pages[j] is a copy of its pool)
        return _latent._attention_decode(
            params, p, x, positions, pool, block_tables + j * num_blocks,
            context_lens, cfg)

    x, rows, counts = _layers(params, params["embed_weight"][tokens],
                              attend, cfg)
    return _latent._head(params, x, cfg), rows, None, counts


def lm_definition(cfg, dtype=jnp.bfloat16):
    """This model as :class:`~mxnet_tpu.serving.LMBackend` serves it:
    one latent pool of ``[scaled N(c_kv) | rotated k_rope]`` rows in the
    ``dtype`` the parameters are stored in, a row a token and
    *sublayer* (``cache_layers`` is twice the layers), no value pool."""
    return LMDefinition(
        cfg=cfg,
        forward=lambda params, tokens: full_logits(params, tokens, cfg),
        prefill=lambda params, tokens, length: prefill(
            params, tokens, length, cfg),
        decode=lambda params, tokens, positions, k_pages, v_pages, tables,
        lens: decode_step(params, tokens, positions, k_pages, tables, lens,
                          cfg),
        cache_row=CacheRow("latent", _latent.cache_row_width(cfg),
                           np.dtype(dtype), 1),
        book=_moe.book_expert_counts, prepare=None,
        cache_layers=SUBLAYERS * cfg["num_layers"])
