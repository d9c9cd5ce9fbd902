"""A hybrid decoder whose every layer runs **two mixers side by side**:
a Mamba-2 state-space mixer and a rotary grouped-query attention read
the same normed input, and their outputs, each under its published
multiplier, are summed into one residual update; a gated feed-forward
follows (the Falcon-H1 language models, ``model_type`` ``falcon_h1``).
With ``N`` an RMSNorm with a plain gain:

    x0  = embedding_multiplier E[token]
    h   = N_in(x)
    x'  = x + ssm_out_multiplier Mamba(h)
            + attention_out_multiplier Attn(attention_in_multiplier h)
    x'' = x' + MLP(N_ff(x'))
    logits = lm_head_multiplier W_head N_final(x)

- ``Mamba(h)``: ``[z | x | B | C | dt] = (W_in (ssm_in_multiplier h)) *
  m`` with ``m`` constant on each of the five parts
  (``ssm_multipliers``); ``[x | B | C]`` through a causal depthwise
  convolution of ``mamba_d_conv`` taps, its bias and SiLU; the
  selective state-space recurrence
  (:mod:`~mxnet_tpu.ops.state_space`) over ``mamba_n_heads`` heads of
  ``mamba_d_head`` channels (``mamba_d_ssm`` in all, not ``mamba_expand
  * hidden``) with a state of ``mamba_d_state`` a channel, ``B`` and
  ``C`` shared by the heads of one of ``mamba_n_groups`` groups;
  ``W_out(gain * RMSNorm_by_group(y * silu(z)))``: the gate first, the
  norm after it.  The mixer is the one-sublayer family's
  (``models/state_space_moe.py``), its projection scaled row by row.
- ``Attn(u)``: ``q = W_q u``, ``k = key_multiplier W_k u``, ``v = W_v
  u``; rotate-half rotary over the whole head; causal softmax at
  ``head_dim^-0.5``; ``num_attention_heads / num_key_value_heads``
  query heads a key-value head; no bias.
- ``MLP(u) = mlp_multipliers[1] W_down(W_up u * silu(mlp_multipliers[0]
  W_gate u))``.

**The fourteen multipliers** are applied where the modeling code applies
them, to the float32 sum of a product before it is rounded (``m``, the
key's, the gate's, the down projection's, the head's) or in the float32
gain of the norm that feeds a branch (``ssm_in_multiplier``,
``attention_in_multiplier``); none is folded into a stored matrix,
which would round the checkpoint's weights a second time.

Pure functions of ``(params, cfg)``, as the siblings are.  Every layer
keeps **both** a key row and a value row a token in the paged pools and
a recurrent state a sequence in the state pool (float32 ``[groups,
state, channels a group]`` and the convolution's last ``taps - 1``
rows): ``cache_layers`` and the state's layers are both ``num_layers``,
and one decode step walks the pools and updates the state in every
layer.  ``params`` is a flat dict under checkpoint-style names
(:func:`param_shapes`); the computing dtype is the dtype the parameters
are stored in, with float32 accumulation, softmax, norm statistics,
step, decay and state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import gqa_prefill_attention
from ..ops.kv_cache import CacheRow
from ..ops.paged_attention import gqa_paged_decode_attention
from . import latent_moe as _lm
from . import state_space_moe as _sm
from .gated_delta_moe import _rotate
from .lm import LMDefinition

__all__ = ["lm_config", "lm_definition", "param_shapes", "prefill",
           "decode_step", "full_logits", "state_rows", "book", "MULTIPLIERS"]

_PUBLISHED = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "mamba_d_ssm",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
    "mamba_d_conv", "mamba_chunk_size", "rms_norm_eps", "rope_theta",
    "vocab_size")

#: the published keys that scale a product: seven scalars, the five
#: ``ssm_multipliers`` (on ``z``, ``x``, ``B``, ``C``, ``dt``) and the two
#: ``mlp_multipliers`` (on the gate's product and on the down
#: projection's): fourteen numbers
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")


def lm_config(published, seq_len):
    """The program's configuration from a published ``config.json`` (a
    dict): the keys the layers read, the multipliers, ``seq_len`` (the
    deployment's context limit), and the state-space mixer's sizes under
    the names ``models/state_space_moe.py`` reads them by."""
    cfg = {key: published[key] for key in _PUBLISHED + MULTIPLIERS}
    if any(published.get(k) for k in (
            "attention_bias", "mlp_bias", "mamba_proj_bias",
            "projectors_bias", "mamba_norm_before_gate", "rope_scaling")) \
            or published.get("attn_layer_indices") is not None \
            or not published.get("mamba_rms_norm") \
            or not published.get("mamba_conv_bias") \
            or published.get("hidden_act") != "silu":
        raise ValueError(
            "a projection with a bias, a convolution without one, "
            "mamba_rms_norm false, mamba_norm_before_gate true, "
            "attn_layer_indices, rope_scaling and another activation than "
            "silu are not built")
    heads, dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, state = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    if heads * dim != cfg["mamba_d_ssm"] or heads % groups:
        raise ValueError("mamba_d_ssm is mamba_n_heads heads of "
                         "mamba_d_head, in mamba_n_groups whole groups")
    if len(cfg["ssm_multipliers"]) != 5 or len(cfg["mlp_multipliers"]) != 2:
        raise ValueError("five ssm_multipliers and two mlp_multipliers")
    cfg.update(
        # the shared mixer's names
        mamba_num_heads=heads, mamba_head_dim=dim, n_groups=groups,
        ssm_state_size=state, conv_kernel=cfg["mamba_d_conv"],
        chunk_size=cfg["mamba_chunk_size"],
        # the shared rotary turn's: the whole head turns
        partial_rotary_factor=1,
        # the generation lane's
        seq_len=int(seq_len), num_layers=cfg["num_hidden_layers"],
        num_classes=cfg["vocab_size"])
    # the five parts' factors over in_weight's rows [z | x | B | C], and
    # dt's; applied to the float32 products (_sm._mamba_inputs)
    m = [float(v) for v in cfg["ssm_multipliers"]]
    bc = groups * state
    cfg["in_proj_scales"] = (
        np.repeat(np.asarray(m[:4], np.float32),
                  [heads * dim, heads * dim, bc, bc]), np.float32(m[4]))
    return cfg


def state_rows(cfg, dtype=jnp.bfloat16):
    """What a sequence keeps between steps, in every layer: the shared
    mixer's :class:`~mxnet_tpu.ops.kv_cache.StateRows` over all
    ``num_layers``."""
    return _sm.state_rows(dict(cfg, layer_kinds=_sm.MAMBA * cfg["num_layers"]),
                          dtype)


def param_shapes(cfg):
    """name -> shape.  Matrices are ``[out, in]`` like a checkpoint's.
    ``in_weight``'s rows are ``[z | x | B | C]``, each part whole, and
    ``dt_weight`` is the last ``mamba_n_heads`` rows of the checkpoint's
    ``in_proj`` (apart, so that the step's product stays float32);
    ``conv_weight`` is the depthwise kernel ``[channels, taps]``, the
    last tap on the current token."""
    d, v, ffn = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, mheads = cfg["head_dim"], cfg["mamba_n_heads"]
    inner, _, channels = _sm._sizes(cfg)
    shapes = {"embed_weight": (v, d), "final_norm_gamma": (d,),
              "pred_weight": (v, d)}
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        shapes.update({
            p + "norm_gamma": (d,),
            p + "in_weight": (inner + channels, d),
            p + "dt_weight": (mheads, d),
            p + "conv_weight": (channels, cfg["mamba_d_conv"]),
            p + "conv_bias": (channels,),
            p + "A_log": (mheads,), p + "D": (mheads,),
            p + "dt_bias": (mheads,),
            p + "ssm_norm_gamma": (inner,),
            p + "out_weight": (d, inner),
            p + "q_weight": (heads * dim, d),
            p + "k_weight": (groups * dim, d),
            p + "v_weight": (groups * dim, d),
            p + "o_weight": (d, heads * dim),
            p + "ff_norm_gamma": (d,),
            p + "gate_weight": (ffn, d),
            p + "up_weight": (ffn, d),
            p + "down_weight": (d, ffn)})
    return shapes


# ----------------------------------------------------------------------
# layers


def _scaled(x, w, factor, dtype):
    """``factor * (x [N, in] . w [out, in])``: the factor on the float32
    sum, before it is rounded to ``dtype``."""
    return (jnp.einsum("nc,fc->nf", x, w, preferred_element_type=jnp.float32)
            * factor).astype(dtype)


def _projections(params, p, u, positions, cfg):
    """Queries ``[N, Hq, D]`` and keys ``[N, Hkv, D]``, rotated, and
    values ``[N, Hkv, D]`` of ``u`` (the normed residual under
    ``attention_in_multiplier``); the keys under ``key_multiplier``."""
    n = u.shape[0]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    q = _lm._dot(u, params[p + "q_weight"]).reshape(n, heads, dim)
    k = _scaled(u, params[p + "k_weight"], cfg["key_multiplier"],
                u.dtype).reshape(n, groups, dim)
    v = _lm._dot(u, params[p + "v_weight"]).reshape(n, groups, dim)
    return _rotate(q, positions, cfg), _rotate(k, positions, cfg), v


def _attention_out(params, p, o, cfg):
    """``attention_out_multiplier W_o o``, float32."""
    return _scaled(o, params[p + "o_weight"],
                   cfg["attention_out_multiplier"], jnp.float32)


def _attention_prefill(params, p, u, positions, cfg):
    """One prompt, ``u [T, d]``: the branch's float32 update and the key
    and value rows ``[T, Hkv * D]`` the cache keeps."""
    with jax.named_scope("attention_branch"):
        q, k, v = _projections(params, p, u, positions, cfg)
        o = gqa_prefill_attention(
            q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
            v.transpose(1, 0, 2)[None], cfg["head_dim"] ** -0.5)[0]
        t = u.shape[0]
        o = o.transpose(1, 0, 2).reshape(t, -1).astype(u.dtype)
        return _attention_out(params, p, o, cfg), k.reshape(t, -1), \
            v.reshape(t, -1)


def _attention_decode(params, p, u, positions, k_pool, v_pool, tables,
                      context_lens, cfg):
    """One token a sequence, ``u [B, d]``, over the paged key and value
    pools ``[blocks, block_size, Hkv * D]``."""
    with jax.named_scope("attention_branch"):
        q, k, v = _projections(params, p, u, positions, cfg)
        o = gqa_paged_decode_attention(q, k, v, k_pool, v_pool, tables,
                                       context_lens, cfg["head_dim"] ** -0.5)
        b = u.shape[0]
        return _attention_out(params, p, o.reshape(b, -1).astype(u.dtype),
                              cfg), k.reshape(b, -1), v.reshape(b, -1)


def _branch_inputs(params, p, x, cfg):
    """The one normed input as each branch reads it: under
    ``ssm_in_multiplier`` and under ``attention_in_multiplier``, each in
    the norm's float32 gain (one set of statistics: the two differ by
    the gain alone)."""
    gamma = params[p + "norm_gamma"]
    return _lm._norm(x, gamma, cfg, cfg["ssm_in_multiplier"]), \
        _lm._norm(x, gamma, cfg, cfg["attention_in_multiplier"])


def _mixed(x, mamba, attention, cfg):
    """``x + ssm_out_multiplier Mamba + Attn`` (the attention branch's
    update carries its multiplier already), summed in float32."""
    update = cfg["ssm_out_multiplier"] * mamba.astype(jnp.float32) \
        + attention
    return x + update.astype(x.dtype)


#: the most tokens a prefill's feed-forward takes at once: a longer
#: prompt runs it as stretches of one loop, so that the two
#: ``intermediate_size``-wide products (88 KB of float32 a token each at
#: 21,504) are a stretch's and not the prompt's.  1,024 and not 2,048:
#: the served cell's 8,192 bucket holds 0.1 GB less, which is what
#: keeps the chip's peak under 15 GB (PERF.md section 6, PR 48); the
#: stretch's products stay bound by the array, not by the weights' read
FF_SEGMENT = 1024


def _ff_segment(tokens):
    """The stretch the feed-forward of ``tokens`` rows runs in: all of
    them up to :data:`FF_SEGMENT`, else the largest divisor under that
    in whole tiles of 256 rows (all of them where there is none)."""
    if tokens <= FF_SEGMENT:
        return tokens
    return next((s for s in range(FF_SEGMENT, 0, -256) if tokens % s == 0),
                tokens)


def _feed_forward(params, p, x, cfg):
    """``x + mlp_multipliers[1] W_down(W_up u * silu(mlp_multipliers[0]
    W_gate u))`` with ``u = N_ff(x)``, ``x [N, d]``."""
    gate_factor, down_factor = cfg["mlp_multipliers"]

    def rows(u):
        gate = jnp.einsum("nc,fc->nf", u, params[p + "gate_weight"],
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("nc,fc->nf", u, params[p + "up_weight"],
                        preferred_element_type=jnp.float32)
        hidden = (up * jax.nn.silu(gate * gate_factor)).astype(u.dtype)
        return _scaled(hidden, params[p + "down_weight"], down_factor,
                       u.dtype)

    with jax.named_scope("feed_forward"):
        u = _lm._norm(x, params[p + "ff_norm_gamma"], cfg)
        n = x.shape[0]
        size = _ff_segment(n)
        if size == n:
            return x + rows(u)
        out = jax.lax.map(rows, u.reshape(n // size, size, -1))
        return x + out.reshape(n, -1)


def _head(params, x, cfg):
    """float32 logits ``lm_head_multiplier W_head N_final(x)``."""
    with jax.named_scope("lm_head"):
        return _lm._head(params, x, cfg) * cfg["lm_head_multiplier"]


def _embed(params, tokens, cfg):
    x = params["embed_weight"][tokens]
    return (x.astype(jnp.float32) * cfg["embedding_multiplier"]
            ).astype(x.dtype)


# ----------------------------------------------------------------------
# the model's entry points


def book(model, counts):
    """Add one call's ``counts``, the state-space scan's
    (:data:`~mxnet_tpu.models.state_space_moe.SSM_COUNTS`), to the
    counters."""
    _sm.book_ssm(model, counts)


def forward(params, tokens, cfg, length=None):
    """One prompt ``tokens`` int32 ``[T]``: ``(hidden [T, d] before the
    final norm, k_rows, v_rows [layers, T, Hkv * D], counts, (state
    [layers, G, N, W], tail [layers, ...]))``.  Positions ``>= length``
    are the bucket's pad: they leave the state as it is at ``length``."""
    t = tokens.shape[0]
    positions = jnp.arange(t, dtype=jnp.int32)
    x = _embed(params, tokens, cfg)
    k_rows, v_rows, states, tails = [], [], [], []
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        h_ssm, h_attn = _branch_inputs(params, p, x, cfg)
        with jax.named_scope("ssm_branch"):
            mamba, state, tail = _sm._mamba_prefill(params, p, h_ssm, length,
                                                    cfg)
        attention, k, v = _attention_prefill(params, p, h_attn, positions,
                                             cfg)
        x = _feed_forward(params, p, _mixed(x, mamba, attention, cfg), cfg)
        k_rows.append(k)
        v_rows.append(v)
        states.append(state)
        tails.append(tail)
    counts = _sm.ssm_counts(cfg, cfg["num_layers"], t,
                            t if length is None else length)
    return x, jnp.stack(k_rows), jnp.stack(v_rows), counts, \
        (jnp.stack(states), jnp.stack(tails))


def prefill(params, tokens, length, cfg):
    """``(logits float32 [V] after token length - 1, k_rows, v_rows,
    counts, state)``: one program a bucket, whatever the prompt's real
    length; only one row of logits is computed."""
    x, k_rows, v_rows, counts, state = forward(params, tokens, cfg, length)
    logits = _head(
        params, jax.lax.dynamic_slice_in_dim(x, length - 1, 1), cfg)
    return logits[0], k_rows, v_rows, counts, state


def full_logits(params, tokens, cfg):
    """float32 logits ``[B, T, V]`` of ``tokens`` ``[B, T]``, no cache:
    the classifier-lane protocol and the tests' full forward."""
    return jnp.stack([_head(params, forward(params, row, cfg)[0], cfg)
                      for row in tokens])


def decode_step(params, tokens, positions, k_pages, v_pages, block_tables,
                context_lens, state, slots, cfg):
    """One token for each of ``B`` sequences, every layer through both
    its stores: the paged pools ``[layers, num_blocks, block_size, Hkv *
    D]`` (read as of before the step; the caller writes the returned
    rows behind this program) and ``state = (S, tail)``, each ``[layers
    * 2 * num_slots + 1, ...]``: row ``(layer * 2 + version) * num_slots
    + slot``.  Row ``i`` reads version ``positions[i] % 2`` of slot
    ``slots[i]`` and writes the other; a ``slots[i]`` of ``num_slots``
    or more is a pad row and writes the pools' last row.  Returns
    ``(logits [B, V], k_rows, v_rows [layers, B, Hkv * D], counts,
    state)``, ``state`` the pools written where they lie when the caller
    donates them."""
    pool_s, pool_tail = state
    layers = cfg["num_layers"]
    n_slots = (pool_s.shape[0] - 1) // (2 * layers)
    live = slots < n_slots
    version = positions % 2
    x = _embed(params, tokens, cfg)
    num_blocks = k_pages.shape[1]
    k_pool = k_pages.reshape((-1,) + k_pages.shape[2:])
    v_pool = v_pages.reshape((-1,) + v_pages.shape[2:])
    channels = _sm._sizes(cfg)[2]
    k_rows, v_rows = [], []
    for i in range(layers):
        p = "l%d_" % i
        h_ssm, h_attn = _branch_inputs(params, p, x, cfg)
        base = i * 2 * n_slots + slots
        read = jnp.where(live, base + version * n_slots, 0)
        write = jnp.where(live, base + (1 - version) * n_slots,
                          pool_s.shape[0] - 1)
        with jax.named_scope("ssm_branch"):
            tail = pool_tail[read].reshape(x.shape[0], -1, channels)
            mamba, pool_s, tail = _sm._mamba_decode(
                params, p, h_ssm, pool_s, read, write, tail, cfg)
            pool_tail = pool_tail.at[write].set(
                tail.reshape((-1,) + pool_tail.shape[1:]))
        # every layer gathers from the whole pool through tables offset
        # to its blocks (a slice k_pages[i] is a copy)
        attention, k, v = _attention_decode(
            params, p, h_attn, positions, k_pool, v_pool,
            block_tables + i * num_blocks, context_lens, cfg)
        x = _feed_forward(params, p, _mixed(x, mamba, attention, cfg), cfg)
        k_rows.append(k)
        v_rows.append(v)
    return _head(params, x, cfg), jnp.stack(k_rows), jnp.stack(v_rows), \
        _sm.ssm_counts(cfg, layers, 0, 0), (pool_s, pool_tail)


def lm_definition(cfg, dtype=jnp.bfloat16):
    """This model as :class:`~mxnet_tpu.serving.LMBackend` serves it:
    key and value pools of ``Hkv * D``-wide rows and a state pool, one
    slot a sequence, **both over every layer**, in the ``dtype`` the
    parameters are stored in (the recurrence's state float32)."""
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
        book=book, prepare=None, cache_layers=cfg["num_layers"],
        state=state_rows(cfg, dtype))
