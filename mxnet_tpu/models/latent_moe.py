"""A decoder of latent attention and sparse experts, built from its
published configuration (the DeepSeek-V3 language-model block, which
``dots.vlm1`` also uses): RMSNorm, multi-head latent attention with
rotary on a slice of the head (YaRN where the configuration has a
``rope_scaling``, plain where it has none), gated SiLU feed-forward
layers, dense first and then routed experts plus a shared one, an
untied bias-free head.

The latent attention (projections, expanded prefill, absorbed decode,
cache row), RMSNorm, the rotary turn and the head are this family's
and every other latent family's (``models/shortcut_latent_moe.py``
builds its layer from them): a configuration may also give
``q_lora_scale`` / ``kv_lora_scale``, factors on the two low-rank paths
behind their norms.

Pure functions of ``(params, cfg)``: :func:`prefill` runs one prompt in
the *expanded* form of the attention and returns the rows the cache
keeps; :func:`decode_step` runs one token per sequence in the *absorbed*
form over the latent pool (the same mathematics:
``tests/test_latent_moe.py`` holds one to the other).  ``params`` is a
flat dict under checkpoint-style names (:func:`param_shapes`); the
computing dtype is the dtype the parameters are stored in (bfloat16 as
served, float32 in the CPU tests), with float32 accumulation, router,
softmax and norm statistics.

``cfg`` is :func:`lm_config` of the published keys.  ``n_routed_experts``
is the router's width; ``held = (first, count)`` says which of those
experts this chip holds: the expert layers route over all of them and
add up only what the held ones give (one member of an expert-parallel
deployment: :func:`~mxnet_tpu.parallel.moe.dropless_experts`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import latent_prefill_attention
from ..ops.paged_attention import latent_paged_decode_attention
from ..ops.kv_cache import CacheRow
from ..parallel import moe as _moe
from .lm import LMDefinition

__all__ = ["lm_config", "lm_definition", "param_shapes", "init_params",
           "prefill", "decode_step", "yarn_inv_freq", "softmax_scale"]

_PUBLISHED = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "vocab_size",
    "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
    "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
    "rope_theta")


def lm_config(published, seq_len, held=None):
    """The program's configuration from a published ``config.json``
    (a dict): the keys the layers read, ``seq_len`` (the deployment's
    context limit) and ``held = (first, count)`` of the
    ``n_routed_experts`` (all of them if not given)."""
    cfg = {key: published[key] for key in _PUBLISHED}
    # no rope_scaling (absent or null) is plain rotary
    cfg["rope_scaling"] = published.get("rope_scaling")
    if published.get("scoring_func", "sigmoid") != "sigmoid" \
            or published.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("only sigmoid scores with the noaux_tc choice "
                         "are built")
    cfg["seq_len"] = int(seq_len)
    # the generation lane's own names for depth and vocabulary
    cfg["num_layers"] = cfg["num_hidden_layers"]
    cfg["num_classes"] = cfg["vocab_size"]
    cfg["held"] = tuple(held or (0, cfg["n_routed_experts"]))
    return cfg


def cache_row_width(cfg):
    """Values in a cache row: ``kv_rank + rope``, padded with zeros to
    whole 128-lane tiles.  A TPU lays an array out by its shape alone,
    and a pool whose rows are 576 = 4.5 tiles wide gets its *block* axis
    innermost: every decode step then re-lays the whole pool before its
    gathers (compiled for a v5e, PR 26).  640-wide rows lie as they are
    indexed, and take the memory the tiled 576 would."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def _pad_row(x, cfg):
    pad = cache_row_width(cfg) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _is_dense(cfg, layer):
    return layer < cfg["first_k_dense_replace"]


def param_shapes(cfg):
    """name -> shape.  Matrices are ``[out, in]`` like a checkpoint's;
    the held experts of a layer are stacked, ``[held, in, out]`` (the
    layout the grouped product reads)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    ffn, wide = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    shared = ffn * cfg["n_shared_experts"]
    held = cfg["held"][1]
    shapes = {"embed_weight": (v, d), "final_norm_gamma": (d,),
              "pred_weight": (v, d)}
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        shapes.update({
            p + "attn_norm_gamma": (d,),
            p + "q_a_weight": (q_rank, d), p + "q_a_norm_gamma": (q_rank,),
            p + "q_b_weight": (heads * (nope + rope), q_rank),
            p + "kv_a_weight": (kv_rank + rope, d),
            p + "kv_a_norm_gamma": (kv_rank,),
            p + "kv_b_weight": (heads * (nope + cfg["v_head_dim"]), kv_rank),
            p + "o_weight": (d, heads * cfg["v_head_dim"]),
            p + "ffn_norm_gamma": (d,)})
        if _is_dense(cfg, i):
            shapes.update({p + "ffn_gate_weight": (wide, d),
                           p + "ffn_up_weight": (wide, d),
                           p + "ffn_down_weight": (d, wide)})
        else:
            shapes.update({
                p + "router_weight": (cfg["n_routed_experts"], d),
                p + "router_bias": (cfg["n_routed_experts"],),
                p + "experts_gate_weight": (held, d, ffn),
                p + "experts_up_weight": (held, d, ffn),
                p + "experts_down_weight": (held, ffn, d),
                p + "shared_gate_weight": (shared, d),
                p + "shared_up_weight": (shared, d),
                p + "shared_down_weight": (d, shared)})
    return shapes


def init_params(cfg, seed=0, dtype=jnp.bfloat16, scale=0.02,
                bias_scale=0.01):
    """Seeded parameters as a function would load them: normal(0,
    ``scale``) matrices, gains 1, the router's selection bias normal(0,
    ``bias_scale``) in float32 (so that it is not a no-op)."""
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
# rotary positions: YaRN under a ``rope_scaling``, plain without one


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """``(inv_freq float32 [rope / 2], cos_sin_scale)``: between the two
    correction dimensions the ramp blends ``1 / theta^(2i / rope)`` and
    the same over ``factor``; cos and sin are multiplied by
    ``m(mscale) / m(mscale_all_dim)``.  Without a ``rope_scaling``:
    ``1 / theta^(2i / rope)`` and 1."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg.get("rope_scaling")
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if sc is None:
        return extra.astype(np.float32), 1.0
    original = sc["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0, 1)
    inv = extra / sc["factor"] * ramp + extra * (1 - ramp)
    scale = _yarn_mscale(sc["factor"], sc["mscale"]) \
        / _yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return inv.astype(np.float32), scale


def softmax_scale(cfg):
    """``(nope + rope)^-0.5``, times ``m(mscale_all_dim)^2`` under a
    ``rope_scaling``."""
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc is None:
        return scale
    return scale * _yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2


def _rotate(x, positions, cfg):
    """Rotary on the last axis of ``x`` ``[N, ..., rope]`` at
    ``positions`` ``[N]``: pairs ``(2i, 2i + 1)`` turn by ``position *
    inv_freq[i]``; the result holds the turned first members, then the
    second (queries and keys alike, so their products are the
    published ones)."""
    inv, scale = yarn_inv_freq(cfg)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv.size,)
    cos = (jnp.cos(angle) * scale).reshape(shape)
    sin = (jnp.sin(angle) * scale).reshape(shape)
    a = x[..., 0::2].astype(jnp.float32)
    b = x[..., 1::2].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


# ----------------------------------------------------------------------
# layers


def _norm(x, gamma, cfg, scale=None):
    """RMSNorm, statistics and gain in float32; ``scale`` is a factor
    on the gain (a low-rank path's), applied before the rounding."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + cfg["rms_norm_eps"])
    gain = gamma.astype(jnp.float32)
    if scale is not None:
        gain = gain * scale
    return (y * gain).astype(x.dtype)


def _dot(x, w):
    """``x [N, in]`` by ``w [out, in]``, float32 accumulation."""
    return jnp.einsum("nc,fc->nf", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _latent_projections(params, p, h, positions, cfg):
    """Queries ``[N, H, nope]`` and rotated ``[N, H, rope]``, and the
    cache row ``[N(c_kv) | rotated k_rope | 0]`` ``[N, row width]``.
    ``q_lora_scale`` multiplies the queries and ``kv_lora_scale``
    ``N(c_kv)`` (so the row holds the scaled latent); ``W_qb`` is
    linear, so the queries' factor rides in the float32 gain of
    ``N(c_q)``; ``k_rope`` takes neither."""
    heads, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    kv_rank = cfg["kv_lora_rank"]
    c_q = _norm(_dot(h, params[p + "q_a_weight"]),
                params[p + "q_a_norm_gamma"], cfg, cfg.get("q_lora_scale"))
    q = _dot(c_q, params[p + "q_b_weight"]).reshape(h.shape[0], heads, -1)
    kv_a = _dot(h, params[p + "kv_a_weight"])
    c_kv = _norm(kv_a[:, :kv_rank], params[p + "kv_a_norm_gamma"], cfg,
                 cfg.get("kv_lora_scale"))
    k_rope = _rotate(kv_a[:, kv_rank:], positions, cfg)
    row = _pad_row(jnp.concatenate([c_kv, k_rope], axis=-1), cfg)
    return q[..., :nope], _rotate(q[..., nope:], positions, cfg), row


def _kv_b(params, p, cfg):
    """``W_kvb`` per head: ``(W_uk [H, nope, rank], W_uv [H, v, rank])``."""
    w = params[p + "kv_b_weight"].reshape(
        cfg["num_attention_heads"], -1, cfg["kv_lora_rank"])
    return w[:, :cfg["qk_nope_head_dim"]], w[:, cfg["qk_nope_head_dim"]:]


def _attention_prefill(params, p, x, positions, cfg):
    """Expanded form over one prompt ``x [T, d]``; returns the update of
    the residual stream and the cache rows ``[T, row width]``."""
    h = _norm(x, params[p + "attn_norm_gamma"], cfg)
    q_nope, q_rope, row = _latent_projections(params, p, h, positions, cfg)
    kv_rank, heads = cfg["kv_lora_rank"], cfg["num_attention_heads"]
    w_uk, w_uv = _kv_b(params, p, cfg)
    c_kv = row[:, :kv_rank]
    k_rope = row[:, kv_rank:kv_rank + cfg["qk_rope_head_dim"]]

    def expand(w):
        return jnp.einsum("tc,hdc->htd", c_kv, w,
                          preferred_element_type=jnp.float32).astype(x.dtype)

    q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(1, 0, 2)
    k = jnp.concatenate([expand(w_uk), jnp.broadcast_to(
        k_rope[None], (heads,) + k_rope.shape)], axis=-1)
    o = latent_prefill_attention(q[None], k[None], expand(w_uv)[None],
                                 softmax_scale(cfg))[0]
    o = o.transpose(1, 0, 2).reshape(x.shape[0], -1)
    return _dot(o, params[p + "o_weight"]), row


def _attention_decode(params, p, x, positions, pages, block_tables,
                      context_lens, cfg):
    """Absorbed form, one token a sequence, ``x [B, d]``, over the
    latent pool: ``q_nope`` is taken through ``W_uk`` so that scores are
    products with the cache row itself, and ``W_uv`` is applied to the
    attended latent."""
    h = _norm(x, params[p + "attn_norm_gamma"], cfg)
    q_nope, q_rope, row = _latent_projections(params, p, h, positions, cfg)
    w_uk, w_uv = _kv_b(params, p, cfg)
    q_abs = jnp.einsum("bhd,hdc->bhc", q_nope, w_uk,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    attended = latent_paged_decode_attention(
        _pad_row(jnp.concatenate([q_abs, q_rope], axis=-1), cfg), row, pages,
        block_tables, context_lens, softmax_scale(cfg),
        cfg["kv_lora_rank"])
    o = jnp.einsum("bhc,hdc->bhd", attended, w_uv,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return _dot(o.reshape(x.shape[0], -1), params[p + "o_weight"]), row


def _feed_forward(params, i, x, cfg, valid=None):
    """The layer's feed-forward update and its expert counts (None for
    a dense layer)."""
    p = "l%d_" % i
    h = _norm(x, params[p + "ffn_norm_gamma"], cfg)
    if _is_dense(cfg, i):
        return _moe.swiglu(h, params[p + "ffn_gate_weight"],
                           params[p + "ffn_up_weight"],
                           params[p + "ffn_down_weight"]), None
    with jax.named_scope("expert_layer"):
        logits = jnp.einsum("nc,ec->ne", h, params[p + "router_weight"],
                            preferred_element_type=jnp.float32)
        chosen, gates = _moe.route_group_limited(
            logits, params[p + "router_bias"],
            top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
            topk_group=cfg["topk_group"],
            scale=cfg["routed_scaling_factor"],
            normalize=cfg["norm_topk_prob"])
        routed, counts = _moe.dropless_experts(
            h, chosen, gates, params[p + "experts_gate_weight"],
            params[p + "experts_up_weight"],
            params[p + "experts_down_weight"], cfg["held"], valid=valid,
            every_row=_moe.few_rows_hit_most(
                h.shape[0], cfg["num_experts_per_tok"],
                cfg["n_routed_experts"]),
            n_experts=cfg["n_routed_experts"])
        shared = _moe.swiglu(h, params[p + "shared_gate_weight"],
                             params[p + "shared_up_weight"],
                             params[p + "shared_down_weight"])
    return routed + shared, counts


def _head(params, x, cfg):
    x = _norm(x, params["final_norm_gamma"], cfg)
    return jnp.einsum("nc,vc->nv", x, params["pred_weight"],
                      preferred_element_type=jnp.float32)


def _sum_counts(counts):
    counts = [c for c in counts if c is not None]
    return sum(counts[1:], counts[0]) if counts else None


# ----------------------------------------------------------------------
# the model's entry points


def forward(params, tokens, cfg, length=None):
    """One prompt ``tokens`` int32 ``[T]`` in the expanded form:
    ``(hidden [T, d] before the final norm, rows [L, T, row width],
    counts)``.  Positions ``>= length`` are the bucket's pad: they are
    routed to no expert."""
    t = tokens.shape[0]
    positions = jnp.arange(t, dtype=jnp.int32)
    valid = None if length is None else positions < length
    x = params["embed_weight"][tokens]
    rows, counts = [], []
    for i in range(cfg["num_layers"]):
        update, row = _attention_prefill(params, "l%d_" % i, x, positions,
                                         cfg)
        x = x + update
        update, count = _feed_forward(params, i, x, cfg, valid)
        x = x + update
        rows.append(row)
        counts.append(count)
    return x, jnp.stack(rows), _sum_counts(counts)


def prefill(params, tokens, length, cfg):
    """``(logits float32 [V] after token length - 1, rows [L, T, W],
    None, counts)``: one program a bucket, whatever the prompt's real
    length; only one row of logits is computed."""
    x, rows, counts = forward(params, tokens, cfg, length)
    logits = _head(params, jax.lax.dynamic_slice_in_dim(x, length - 1, 1),
                   cfg)
    return logits[0], rows, None, counts


def full_logits(params, tokens, cfg):
    """float32 logits ``[B, T, V]`` of ``tokens`` ``[B, T]``, no cache:
    the classifier-lane protocol and the tests' full forward."""
    return jnp.stack([_head(params, forward(params, row, cfg)[0], cfg)
                      for row in tokens])


def decode_step(params, tokens, positions, pages, block_tables,
                context_lens, cfg):
    """One token for each of ``B`` sequences through the latent pool
    ``pages [L, num_blocks, block_size, W]``, read as of before the
    step.  Returns ``(logits [B, V], rows [L, B, W], None, counts)``;
    the caller writes ``rows`` in a dispatch of its own, behind this
    one (:meth:`~mxnet_tpu.serving.LMBackend.decode` does)."""
    x = params["embed_weight"][tokens]
    num_blocks = pages.shape[1]
    pool = pages.reshape((-1,) + pages.shape[2:])
    rows, counts = [], []
    for i in range(cfg["num_layers"]):
        # every layer gathers from the whole pool through tables offset
        # to its blocks (a slice pages[i] is a copy of the layer's pool)
        update, row = _attention_decode(
            params, "l%d_" % i, x, positions, pool,
            block_tables + i * num_blocks, context_lens, cfg)
        x = x + update
        update, count = _feed_forward(params, i, x, cfg)
        x = x + update
        rows.append(row)
        counts.append(count)
    return _head(params, x, cfg), jnp.stack(rows), None, _sum_counts(counts)


def lm_definition(cfg, dtype=jnp.bfloat16):
    """This model as :class:`~mxnet_tpu.serving.LMBackend` serves it:
    one latent pool of ``[N(c_kv) | rotated k_rope]`` rows in the
    ``dtype`` the parameters are stored in, no value pool."""
    return LMDefinition(
        cfg=cfg,
        forward=lambda params, tokens: full_logits(params, tokens, cfg),
        prefill=lambda params, tokens, length: prefill(
            params, tokens, length, cfg),
        decode=lambda params, tokens, positions, k_pages, v_pages, tables,
        lens: decode_step(params, tokens, positions, k_pages, tables, lens,
                          cfg),
        cache_row=CacheRow("latent", cache_row_width(cfg), np.dtype(dtype),
                           1),
        book=_moe.book_expert_counts, prepare=None)
