"""A hybrid decoder of gated short-convolution mixers and grouped-query
attention over sparse experts, built from its published configuration
(the LFM2 expert language model): RMSNorm, a mixer a layer named by the
published ``layer_types`` (``conv``: two projections around a gated
causal depthwise convolution of ``conv_L_cache`` taps; ``full_attention``:
QK-norm, rotary over the whole head, a few key-value heads), the first
``num_dense_layers`` layers with a dense SwiGLU and the others with a
sigmoid router that chooses by score plus a selection bias over SwiGLU
experts (no shared expert), an embedding that is also the head.

RMSNorm, the head and the expert counts' sum are the latent family's
(``models/latent_moe.py``), the rotary turn the gated-delta family's
(``models/gated_delta_moe.py``, here over the whole head), SwiGLU, the
router and the dropless expert layer ``parallel/moe.py``'s.

Pure functions of ``(params, cfg)``.  :func:`prefill` runs one padded
prompt: the attention layers return the key and value rows the paged
cache keeps **per token**, the convolution layers the **state a
sequence keeps**: the last ``conv_L_cache - 1`` gated inputs ``B * X``
of the convolution, taken at ``length`` (the bucket's pad positions come
after them and never enter).  :func:`decode_step` runs one token a
sequence: attention through the paged pools, the convolution through the
state pool, which it updates where it lies (the caller donates it).
``params`` is a flat dict under checkpoint-style names
(:func:`param_shapes`); the computing dtype is the dtype the parameters
are stored in (bfloat16 as served, float32 in the CPU tests), with
float32 accumulation, router, softmax, norm statistics and convolution
sum; the state is kept in the activations' dtype.

``cfg`` is :func:`lm_config` of the published keys.  ``num_experts`` is
the router's width; ``held = (first, count)`` says which of those
experts this chip holds (:func:`~mxnet_tpu.parallel.moe.
dropless_experts`).  The state pool keeps two versions a slot, by the
parity of the position, as every model with a state does
(:class:`~mxnet_tpu.serving.LMBackend`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import gqa_prefill_attention
from ..ops.kv_cache import CacheRow, StateRows
from ..ops import short_conv as _conv
from ..ops.paged_attention import gqa_paged_decode_attention
from ..parallel import moe as _moe
from . import latent_moe as _lm
from .gated_delta_moe import _rotate
from .lm import LMDefinition

__all__ = ["lm_config", "lm_definition", "param_shapes", "init_params",
           "prefill", "decode_step", "full_logits", "state_rows"]

_PUBLISHED = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "conv_L_cache", "num_dense_layers", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
    "rope_theta", "vocab_size")

#: what the renormalisation of the chosen gates adds to their sum
#: (``Lfm2MoeSparseMoeBlock``: ``+ 1e-6``)
GATE_SUM_EPS = 1e-6


def lm_config(published, seq_len, held=None):
    """The program's configuration from a published ``config.json`` (a
    dict): the keys the layers read, ``seq_len`` (the deployment's
    context limit), ``held = (first, count)`` of the ``num_experts``
    (all of them if not given) and ``layer_types``, the first
    ``num_hidden_layers`` of the published list."""
    cfg = {key: published[key] for key in _PUBLISHED}
    if published.get("conv_bias") or not published.get("use_expert_bias") \
            or published.get("rope_scaling"):
        raise ValueError("a convolution bias, a router without its "
                         "selection bias and rope scaling are not built")
    cfg["layer_types"] = tuple(
        published["layer_types"][:cfg["num_hidden_layers"]])
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"] \
            or set(cfg["layer_types"]) - {"conv", "full_attention"}:
        raise ValueError("layer_types names %d layers of kinds %s"
                         % (len(cfg["layer_types"]),
                            sorted(set(cfg["layer_types"]))))
    cfg["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
    if published.get("head_dim", cfg["head_dim"]) != cfg["head_dim"]:
        raise ValueError("head_dim is hidden_size / num_attention_heads")
    # the names the shared layers read theirs under
    cfg["rms_norm_eps"] = published["norm_eps"]
    cfg["partial_rotary_factor"] = 1.0
    cfg["seq_len"] = int(seq_len)
    # the generation lane's own names for depth and vocabulary
    cfg["num_layers"] = cfg["num_hidden_layers"]
    cfg["num_classes"] = cfg["vocab_size"]
    cfg["held"] = tuple(held or (0, cfg["num_experts"]))
    return cfg


def _is_dense(cfg, layer):
    return layer < cfg["num_dense_layers"]


def _tail_shape(cfg):
    """How the ``conv_L_cache - 1`` rows of ``hidden_size`` values a
    sequence keeps for the convolution lie in the state pool
    (:func:`~mxnet_tpu.ops.short_conv.tail_shape`)."""
    return _conv.tail_shape(cfg["conv_L_cache"] - 1, cfg["hidden_size"])


def state_rows(cfg, dtype=jnp.bfloat16):
    """What a sequence keeps between steps, per convolution layer: the
    :class:`~mxnet_tpu.ops.kv_cache.StateRows` the cache builds its
    state pool from.  The state is only the convolution's tail."""
    return StateRows(cfg["layer_types"].count("conv"),
                     ((_tail_shape(cfg), np.dtype(dtype)),))


def param_shapes(cfg):
    """name -> shape.  Matrices are ``[out, in]`` like a checkpoint's;
    the held experts of a layer are stacked, ``[held, in, out]`` (the
    layout the grouped product reads).  ``conv_in_weight``'s rows are
    ``[B | C | X]``, each part whole; ``conv_weight`` is the depthwise
    kernel ``[channels, taps]``, the last tap on the current token.  The
    head's matrix is ``embed_weight`` (tied)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    ffn, wide = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    held = cfg["held"][1]
    shapes = {"embed_weight": (v, d), "embedding_norm_gamma": (d,)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d_" % i
        shapes.update({p + "operator_norm_gamma": (d,),
                       p + "ffn_norm_gamma": (d,)})
        if kind == "conv":
            shapes.update({p + "conv_in_weight": (3 * d, d),
                           p + "conv_weight": (d, cfg["conv_L_cache"]),
                           p + "conv_out_weight": (d, d)})
        else:
            shapes.update({
                p + "q_weight": (heads * dim, d),
                p + "k_weight": (groups * dim, d),
                p + "v_weight": (groups * dim, d),
                p + "q_norm_gamma": (dim,), p + "k_norm_gamma": (dim,),
                p + "o_weight": (d, heads * dim)})
        if _is_dense(cfg, i):
            shapes.update({p + "ffn_gate_weight": (wide, d),
                           p + "ffn_up_weight": (wide, d),
                           p + "ffn_down_weight": (d, wide)})
        else:
            shapes.update({
                p + "router_weight": (cfg["num_experts"], d),
                p + "expert_bias": (cfg["num_experts"],),
                p + "experts_gate_weight": (held, d, ffn),
                p + "experts_up_weight": (held, d, ffn),
                p + "experts_down_weight": (held, ffn, d)})
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
        elif name.endswith("expert_bias"):
            out[name] = bias_scale * jax.random.normal(k, shape, jnp.float32)
        else:
            out[name] = (scale * jax.random.normal(k, shape, jnp.float32)
                         ).astype(dtype)
    return out


# ----------------------------------------------------------------------
# layers


def _conv_inputs(params, p, x, cfg):
    """The convolution mixer's projection of ``x [N, d]``: the gated
    input ``u = B * X`` of the convolution and the output gate ``C``,
    both ``[N, d]``."""
    h = _lm._norm(x, params[p + "operator_norm_gamma"], cfg)
    d = cfg["hidden_size"]
    bcx = _lm._dot(h, params[p + "conv_in_weight"])
    return bcx[:, :d] * bcx[:, 2 * d:], bcx[:, d:2 * d]


def _conv_out(params, p, gate, conv):
    """``W_out(C * conv)``, ``conv`` the convolution's float32 sum."""
    y = (gate.astype(jnp.float32) * conv).astype(gate.dtype)
    return _lm._dot(y, params[p + "conv_out_weight"])


def _conv_prefill(params, p, x, length, cfg):
    """One prompt ``x [T, d]`` from an empty state.  Returns the update
    of the residual stream and the ``conv_L_cache - 1`` gated inputs
    that went into the convolution last before ``length`` (zeros before
    the start), as they lie in the pool."""
    with jax.named_scope("short_conv_prefill"):
        u, gate = _conv_inputs(params, p, x, cfg)
        conv, tail = _conv.conv_prefill(u, params[p + "conv_weight"],
                                        length)
        return _conv_out(params, p, gate, conv), \
            tail.reshape(_tail_shape(cfg))


def _conv_decode(params, p, x, tail, cfg):
    """One token a sequence, ``x [B, d]``; ``tail`` ``[B, taps - 1, d]``
    the gated inputs before it.  Returns the update of the residual
    stream and the tail, advanced."""
    with jax.named_scope("short_conv_decode"):
        u, gate = _conv_inputs(params, p, x, cfg)
        conv, tail = _conv.conv_step(tail, u, params[p + "conv_weight"])
        return _conv_out(params, p, gate, conv), tail


def _attention_projections(params, p, x, positions, cfg):
    """Queries ``[N, Hq, D]`` and keys ``[N, Hkv, D]`` (normed over the
    head and rotated) and values ``[N, Hkv, D]``."""
    n = x.shape[0]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    h = _lm._norm(x, params[p + "operator_norm_gamma"], cfg)
    q = _lm._dot(h, params[p + "q_weight"]).reshape(n, heads, dim)
    k = _lm._dot(h, params[p + "k_weight"]).reshape(n, groups, dim)
    v = _lm._dot(h, params[p + "v_weight"]).reshape(n, groups, dim)
    q = _rotate(_lm._norm(q, params[p + "q_norm_gamma"], cfg), positions,
                cfg)
    k = _rotate(_lm._norm(k, params[p + "k_norm_gamma"], cfg), positions,
                cfg)
    return q, k, v


def _attention_prefill(params, p, x, positions, cfg):
    """One prompt ``x [T, d]``: the update of the residual stream and
    the key and value rows ``[T, Hkv * D]`` the cache keeps."""
    q, k, v = _attention_projections(params, p, x, positions, cfg)
    o = gqa_prefill_attention(
        q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
        v.transpose(1, 0, 2)[None], cfg["head_dim"] ** -0.5)[0]
    t = x.shape[0]
    o = o.transpose(1, 0, 2).reshape(t, -1).astype(x.dtype)
    return _lm._dot(o, params[p + "o_weight"]), k.reshape(t, -1), \
        v.reshape(t, -1)


def _attention_decode(params, p, x, positions, k_pool, v_pool, tables,
                      context_lens, cfg):
    """One token a sequence, ``x [B, d]``, over the paged key and value
    pools ``[blocks, block_size, Hkv * D]``."""
    q, k, v = _attention_projections(params, p, x, positions, cfg)
    o = gqa_paged_decode_attention(q, k, v, k_pool, v_pool, tables,
                                   context_lens, cfg["head_dim"] ** -0.5)
    b = x.shape[0]
    return _lm._dot(o.reshape(b, -1).astype(x.dtype),
                    params[p + "o_weight"]), k.reshape(b, -1), \
        v.reshape(b, -1)


def _feed_forward(params, i, x, cfg, valid=None):
    """The layer's feed-forward update and its expert counts (None for
    a dense layer)."""
    p = "l%d_" % i
    h = _lm._norm(x, params[p + "ffn_norm_gamma"], cfg)
    if _is_dense(cfg, i):
        return _moe.swiglu(h, params[p + "ffn_gate_weight"],
                           params[p + "ffn_up_weight"],
                           params[p + "ffn_down_weight"]), None
    with jax.named_scope("expert_layer"):
        logits = jnp.einsum("nc,ec->ne", h, params[p + "router_weight"],
                            preferred_element_type=jnp.float32)
        chosen, gates = _moe.route_group_limited(
            logits, params[p + "expert_bias"],
            top_k=cfg["num_experts_per_tok"],
            scale=cfg["routed_scaling_factor"],
            normalize=cfg["norm_topk_prob"], eps=GATE_SUM_EPS)
        return _moe.dropless_experts(
            h, chosen, gates, params[p + "experts_gate_weight"],
            params[p + "experts_up_weight"],
            params[p + "experts_down_weight"], cfg["held"], valid=valid,
            every_row=_moe.few_rows_hit_most(
                h.shape[0], cfg["num_experts_per_tok"],
                cfg["num_experts"]),
            n_experts=cfg["num_experts"])


def _head(params, x, cfg):
    """The output norm (the checkpoint's ``embedding_norm``) and the
    logits over the embedding's own rows."""
    return _lm._head({"final_norm_gamma": params["embedding_norm_gamma"],
                      "pred_weight": params["embed_weight"]}, x, cfg)


# ----------------------------------------------------------------------
# the model's entry points


def forward(params, tokens, cfg, length=None):
    """One prompt ``tokens`` int32 ``[T]``: ``(hidden [T, d] before the
    output norm, k_rows, v_rows [attention layers, T, Hkv * D], counts,
    (tail [conv layers, ...],))``.  Positions ``>= length`` are the
    bucket's pad: they are routed to no expert and the state is taken
    before them."""
    t = tokens.shape[0]
    positions = jnp.arange(t, dtype=jnp.int32)
    valid = None if length is None else positions < length
    x = params["embed_weight"][tokens]
    k_rows, v_rows, tails, counts = [], [], [], []
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d_" % i
        if kind == "conv":
            update, tail = _conv_prefill(params, p, x, length, cfg)
            tails.append(tail)
        else:
            update, k, v = _attention_prefill(params, p, x, positions, cfg)
            k_rows.append(k)
            v_rows.append(v)
        x = x + update
        update, count = _feed_forward(params, i, x, cfg, valid)
        x = x + update
        counts.append(count)
    return x, jnp.stack(k_rows), jnp.stack(v_rows), \
        _lm._sum_counts(counts), (jnp.stack(tails),)


def prefill(params, tokens, length, cfg):
    """``(logits float32 [V] after token length - 1, k_rows, v_rows,
    counts, state)``: one program a bucket, whatever the prompt's real
    length; only one row of logits is computed."""
    x, k_rows, v_rows, counts, state = forward(params, tokens, cfg, length)
    logits = _head(params, jax.lax.dynamic_slice_in_dim(x, length - 1, 1),
                   cfg)
    return logits[0], k_rows, v_rows, counts, state


def full_logits(params, tokens, cfg):
    """float32 logits ``[B, T, V]`` of ``tokens`` ``[B, T]``, no cache:
    the classifier-lane protocol and the tests' full forward."""
    return jnp.stack([_head(params, forward(params, row, cfg)[0], cfg)
                      for row in tokens])


def decode_step(params, tokens, positions, k_pages, v_pages, block_tables,
                context_lens, state, slots, cfg):
    """One token for each of ``B`` sequences: the attention layers
    through the paged pools ``[attention layers, num_blocks, block_size,
    Hkv * D]`` (read as of before the step; the caller writes the
    returned rows behind this program), the convolution layers through
    ``state = (tail,)``, ``[conv layers * 2 * num_slots + 1, ...]``: row
    ``(layer * 2 + version) * num_slots + slot``.  Row ``i`` reads
    version ``positions[i] % 2`` of slot ``slots[i]`` and writes the
    other; a ``slots[i]`` of ``num_slots`` or more is a pad row and
    writes the pool's last row.  Returns ``(logits [B, V], k_rows,
    v_rows [attention layers, B, Hkv * D], counts, state)``, ``state``
    the pool written where it lies when the caller donates it."""
    pool, = state
    n_conv = cfg["layer_types"].count("conv")
    n_slots = (pool.shape[0] - 1) // (2 * n_conv)
    live = slots < n_slots
    version = positions % 2
    x = params["embed_weight"][tokens]
    num_blocks = k_pages.shape[1]
    k_pool = k_pages.reshape((-1,) + k_pages.shape[2:])
    v_pool = v_pages.reshape((-1,) + v_pages.shape[2:])
    k_rows, v_rows, counts, at_attn, at_conv = [], [], [], 0, 0
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d_" % i
        if kind == "conv":
            base = at_conv * 2 * n_slots + slots
            read = jnp.where(live, base + version * n_slots, 0)
            write = jnp.where(live, base + (1 - version) * n_slots,
                              pool.shape[0] - 1)
            tail = pool[read].reshape(x.shape[0], -1, cfg["hidden_size"])
            update, tail = _conv_decode(params, p, x, tail, cfg)
            pool = pool.at[write].set(
                tail.reshape((-1,) + pool.shape[1:]))
            at_conv += 1
        else:
            # every layer gathers from the whole pool through tables
            # offset to its blocks (a slice k_pages[i] is a copy)
            update, k, v = _attention_decode(
                params, p, x, positions, k_pool, v_pool,
                block_tables + at_attn * num_blocks, context_lens, cfg)
            k_rows.append(k)
            v_rows.append(v)
            at_attn += 1
        x = x + update
        update, count = _feed_forward(params, i, x, cfg)
        x = x + update
        counts.append(count)
    return _head(params, x, cfg), jnp.stack(k_rows), jnp.stack(v_rows), \
        _lm._sum_counts(counts), (pool,)


def lm_definition(cfg, dtype=jnp.bfloat16):
    """This model as :class:`~mxnet_tpu.serving.LMBackend` serves it:
    key and value pools of ``Hkv * D``-wide rows over the attention
    layers alone, in the ``dtype`` the parameters are stored in, and
    beside them a state pool over the convolution layers, one slot a
    sequence."""
    return LMDefinition(
        cfg=cfg,
        forward=lambda params, tokens: full_logits(params, tokens, cfg),
        prefill=lambda params, tokens, length: prefill(
            params, tokens, length, cfg),
        decode=lambda params, tokens, positions, k_pages, v_pages, tables,
        lens, state, slots: decode_step(
            params, tokens, positions, k_pages, v_pages, tables, lens,
            state, slots, cfg),
        cache_row=CacheRow(
            "kv", cfg["num_key_value_heads"] * cfg["head_dim"],
            np.dtype(dtype), 2),
        book=_moe.book_expert_counts, prepare=None,
        cache_layers=cfg["layer_types"].count("full_attention"),
        state=state_rows(cfg, dtype))
