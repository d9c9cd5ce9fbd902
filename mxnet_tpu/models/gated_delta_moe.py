"""A hybrid decoder of Gated DeltaNet layers and gated grouped-query
attention over sparse experts, built from its published configuration
(the Qwen3-Next language model): zero-centred RMSNorm, three
linear-attention layers (a short causal convolution, the gated delta
rule over a recurrent state) to every full-attention layer (QK-norm,
rotary on a slice of the head, an output gate), a softmax top-k router
over SwiGLU experts plus a shared expert behind a sigmoid gate, an
untied bias-free head.

Pure functions of ``(params, cfg)``.  :func:`prefill` runs one padded
prompt: the full-attention layers return the key and value rows the
paged cache keeps **per token**, the DeltaNet layers the **state a
sequence keeps** (``S`` float32 ``[value heads, key dim, value dim]``
and the last ``kernel - 1`` rows that went into the convolution), taken
at ``length``: the bucket's pad positions pass through with ``beta =
0`` and ``g = 0`` and leave it untouched.  :func:`decode_step` runs one
token a sequence: full attention through the paged pools, the DeltaNet
layers through the state pool, which it updates where it lies (the
caller donates it).  ``params`` is a flat dict under checkpoint-style
names (:func:`param_shapes`); the computing dtype is the dtype the
parameters are stored in (bfloat16 as served, float32 in the CPU
tests), with float32 accumulation, router, softmax, norm statistics,
decay ``g`` and state.

``cfg`` is :func:`lm_config` of the published keys.  ``num_experts`` is
the router's width; ``held = (first, count)`` says which of those
experts this chip holds (:func:`~mxnet_tpu.parallel.moe.
dropless_experts`).  The multi-token-prediction module of the published
model is not built (the main model's logits do not depend on it).

**The state pool keeps two versions a slot**, by the parity of the
position: the step at position ``p`` reads version ``p % 2`` and writes
``(p + 1) % 2``, so a step dispatched again finds its input as it was
(:class:`~mxnet_tpu.serving.LMBackend` says what that buys and where it
ends).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import gqa_prefill_attention
from ..ops.gated_delta import gated_delta_chunked, gated_delta_update
from ..ops.kv_cache import CacheRow, StateRows
from ..ops import short_conv as _conv
from ..ops.paged_attention import gqa_paged_decode_attention
from ..parallel import moe as _moe
from .lm import LMDefinition

__all__ = ["lm_config", "lm_definition", "param_shapes", "init_params",
           "prefill", "decode_step", "full_logits", "state_rows"]

_PUBLISHED = (
    "hidden_size", "num_hidden_layers", "full_attention_interval",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "partial_rotary_factor", "rope_theta", "linear_conv_kernel_dim",
    "linear_key_head_dim", "linear_num_key_heads",
    "linear_num_value_heads", "linear_value_head_dim", "num_experts",
    "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "norm_topk_prob", "rms_norm_eps",
    "vocab_size")


def lm_config(published, seq_len, held=None):
    """The program's configuration from a published ``config.json``
    (a dict): the keys the layers read, ``seq_len`` (the deployment's
    context limit), ``held = (first, count)`` of the ``num_experts``
    (all of them if not given) and ``layer_types`` (``"full"`` where
    ``(i + 1) % full_attention_interval == 0``, else ``"linear"``)."""
    cfg = {key: published[key] for key in _PUBLISHED}
    if published.get("rope_scaling") or published.get("mlp_only_layers") \
            or published.get("decoder_sparse_step", 1) != 1:
        raise ValueError("rope scaling, dense-only layers and a sparse "
                         "step other than 1 are not built")
    cfg["seq_len"] = int(seq_len)
    # the generation lane's own names for depth and vocabulary
    cfg["num_layers"] = cfg["num_hidden_layers"]
    cfg["num_classes"] = cfg["vocab_size"]
    cfg["held"] = tuple(held or (0, cfg["num_experts"]))
    every = cfg["full_attention_interval"]
    cfg["layer_types"] = tuple("full" if (i + 1) % every == 0 else "linear"
                               for i in range(cfg["num_layers"]))
    return cfg


def _sizes(cfg):
    """(key width, value width, convolution channels) of a DeltaNet
    layer."""
    key = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return key, value, 2 * key + value


def _tail_shape(cfg):
    """How the ``kernel - 1`` rows of ``channels`` values a sequence
    keeps for the convolution lie in the state pool
    (:func:`~mxnet_tpu.ops.short_conv.tail_shape`)."""
    return _conv.tail_shape(cfg["linear_conv_kernel_dim"] - 1,
                            _sizes(cfg)[2])


def state_rows(cfg, dtype=jnp.bfloat16):
    """What a sequence keeps between steps, per DeltaNet layer: the
    :class:`~mxnet_tpu.ops.kv_cache.StateRows` the cache builds its
    state pool from."""
    return StateRows(cfg["layer_types"].count("linear"), (
        ((cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
          cfg["linear_value_head_dim"]), np.dtype(np.float32)),
        (_tail_shape(cfg), np.dtype(dtype))))


def param_shapes(cfg):
    """name -> shape.  Matrices are ``[out, in]`` like a checkpoint's;
    the held experts of a layer are stacked, ``[held, in, out]`` (the
    layout the grouped product reads).  ``qkvz_weight``'s rows are ``[q
    | k | v | z]`` and ``ba_weight``'s ``[b | a]``, each part whole (the
    published checkpoint interleaves them by key head: a permutation of
    rows)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    key, value, channels = _sizes(cfg)
    ffn, shared = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]
    held = cfg["held"][1]
    shapes = {"embed_weight": (v, d), "final_norm_gamma": (d,),
              "pred_weight": (v, d)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d_" % i
        shapes.update({p + "mixer_norm_gamma": (d,),
                       p + "ffn_norm_gamma": (d,)})
        if kind == "full":
            shapes.update({
                p + "q_weight": (heads * 2 * dim, d),
                p + "k_weight": (groups * dim, d),
                p + "v_weight": (groups * dim, d),
                p + "q_norm_gamma": (dim,), p + "k_norm_gamma": (dim,),
                p + "o_weight": (d, heads * dim)})
        else:
            vheads = cfg["linear_num_value_heads"]
            shapes.update({
                p + "qkvz_weight": (channels + value, d),
                p + "ba_weight": (2 * vheads, d),
                p + "conv_weight": (channels,
                                    cfg["linear_conv_kernel_dim"]),
                p + "A_log": (vheads,), p + "dt_bias": (vheads,),
                p + "gdn_norm_gamma": (cfg["linear_value_head_dim"],),
                p + "out_weight": (d, value)})
        shapes.update({
            p + "router_weight": (cfg["num_experts"], d),
            p + "experts_gate_weight": (held, d, ffn),
            p + "experts_up_weight": (held, d, ffn),
            p + "experts_down_weight": (held, ffn, d),
            p + "shared_gate_weight": (shared, d),
            p + "shared_up_weight": (shared, d),
            p + "shared_down_weight": (d, shared),
            p + "shared_expert_gate_weight": (1, d)})
    return shapes


#: the decay a DeltaNet head is drawn with: ``A = exp(A_log)`` uniform
#: in log between these, so that ``exp(g) = exp(-A softplus(a + dt))``
#: spans about 0.5 to 0.999 across heads (a checkpoint's spread is not
#: in its configuration)
DECAY_RATE = (0.001, 0.7)


def draw_param(key, name, shape, dtype, scale=0.02):
    """One seeded parameter, by its name: normal(0, ``scale``) matrices
    and norm offsets (a zero-centred gain is ``1 + w``), the plain gain
    of the DeltaNet output norm 1, ``A_log`` by :data:`DECAY_RATE` and
    ``dt_bias`` normal(0, 0.1), both float32."""
    if name.endswith("gdn_norm_gamma"):
        return jnp.ones(shape, dtype)
    if name.endswith("A_log"):
        low, high = np.log(DECAY_RATE[0]), np.log(DECAY_RATE[1])
        return jax.random.uniform(key, shape, jnp.float32, low, high)
    if name.endswith("dt_bias"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    return (scale * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def init_params(cfg, seed=0, dtype=jnp.bfloat16, scale=0.02):
    """Seeded parameters as a function would load them
    (:func:`draw_param`)."""
    key = jax.random.PRNGKey(seed)
    return {name: draw_param(jax.random.fold_in(key, i), name, shape, dtype,
                             scale)
            for i, (name, shape) in enumerate(
                sorted(param_shapes(cfg).items()))}


# ----------------------------------------------------------------------
# layers


def _norm(x, gamma, cfg, offset=1.0):
    """RMSNorm with the gain ``offset + gamma`` (zero-centred: 1)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + cfg["rms_norm_eps"])
    return (y * (offset + gamma.astype(jnp.float32))).astype(x.dtype)


def _dot(x, w, out=None):
    """``x [N, in]`` by ``w [out, in]``, float32 accumulation."""
    return jnp.einsum("nc,fc->nf", x, w,
                      preferred_element_type=jnp.float32
                      ).astype(out or x.dtype)


def _rotate(x, positions, cfg):
    """Rotary on the first ``partial_rotary_factor`` of the last axis of
    ``x`` ``[N, H, D]`` at ``positions`` ``[N]``: the halves ``(j, j +
    rot / 2)`` of that slice turn by ``position / theta^(2j / rot)``."""
    rot = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    half = rot // 2
    inv = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, rot, 2, dtype=np.float64) / rot)
    angle = positions.astype(jnp.float32)[:, None] \
        * inv.astype(np.float32)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:rot].astype(jnp.float32)
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                             axis=-1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _attention_projections(params, p, h, positions, cfg):
    """Queries ``[N, Hq, D]`` and keys ``[N, Hkv, D]`` (normed over the
    head and rotated), values ``[N, Hkv, D]`` and the output gate ``[N,
    Hq * D]``."""
    n = h.shape[0]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    q = _dot(h, params[p + "q_weight"]).reshape(n, heads, 2 * dim)
    gate = q[..., dim:].reshape(n, heads * dim)
    k = _dot(h, params[p + "k_weight"]).reshape(n, groups, dim)
    v = _dot(h, params[p + "v_weight"]).reshape(n, groups, dim)
    q = _rotate(_norm(q[..., :dim], params[p + "q_norm_gamma"], cfg),
                positions, cfg)
    k = _rotate(_norm(k, params[p + "k_norm_gamma"], cfg), positions, cfg)
    return q, k, v, gate


def _attention_out(params, p, o, gate):
    o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
    return _dot(o, params[p + "o_weight"])


def _attention_prefill(params, p, x, positions, cfg):
    """One prompt ``x [T, d]``: the update of the residual stream and
    the key and value rows ``[T, Hkv * D]`` the cache keeps."""
    h = _norm(x, params[p + "mixer_norm_gamma"], cfg)
    q, k, v, gate = _attention_projections(params, p, h, positions, cfg)
    o = gqa_prefill_attention(
        q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
        v.transpose(1, 0, 2)[None], cfg["head_dim"] ** -0.5)[0]
    o = o.transpose(1, 0, 2).reshape(x.shape[0], -1).astype(x.dtype)
    t = x.shape[0]
    return _attention_out(params, p, o, gate), k.reshape(t, -1), \
        v.reshape(t, -1)


def _attention_decode(params, p, x, positions, k_pool, v_pool, tables,
                      context_lens, cfg):
    """One token a sequence, ``x [B, d]``, over the paged key and value
    pools ``[blocks, block_size, Hkv * D]``."""
    h = _norm(x, params[p + "mixer_norm_gamma"], cfg)
    q, k, v, gate = _attention_projections(params, p, h, positions, cfg)
    o = gqa_paged_decode_attention(q, k, v, k_pool, v_pool, tables,
                                   context_lens, cfg["head_dim"] ** -0.5)
    b = x.shape[0]
    return _attention_out(params, p, o.reshape(b, -1).astype(x.dtype),
                          gate), k.reshape(b, -1), v.reshape(b, -1)


def _delta_inputs(params, p, h, cfg):
    """The DeltaNet layer's projections of ``h [N, d]``: what goes into
    the convolution ``[N, channels]``, the output gate ``z [N, value]``,
    and per value head the write strength ``beta`` and the log decay
    ``g``, float32 ``[N, Hv]``."""
    _, _, channels = _sizes(cfg)
    vheads = cfg["linear_num_value_heads"]
    mixed = _dot(h, params[p + "qkvz_weight"])
    ba = _dot(h, params[p + "ba_weight"], jnp.float32)
    beta = jax.nn.sigmoid(ba[:, :vheads])
    g = -jnp.exp(params[p + "A_log"].astype(jnp.float32)) \
        * jax.nn.softplus(ba[:, vheads:]
                          + params[p + "dt_bias"].astype(jnp.float32))
    return mixed[:, :channels], mixed[:, channels:], beta, g


def _delta_heads(conv, cfg):
    """The convolution's output ``[N, channels]`` as the rule's ``q``,
    ``k`` ``[N, Hv, dk]`` (L2-normalised over the head, ``q`` scaled by
    ``dk^-1/2``, a key head repeated for the value heads it serves) and
    ``v`` ``[N, Hv, dv]``."""
    n = conv.shape[0]
    key, _, _ = _sizes(cfg)
    kheads, vheads = cfg["linear_num_key_heads"], \
        cfg["linear_num_value_heads"]
    dk = cfg["linear_key_head_dim"]

    def unit(x):
        x = x.astype(jnp.float32).reshape(n, kheads, dk)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(x, vheads // kheads, axis=1)

    q = unit(conv[:, :key]) * dk ** -0.5
    k = unit(conv[:, key:2 * key])
    v = conv[:, 2 * key:].reshape(n, vheads, -1)
    return q, k, v


def _delta_out(params, p, o, z, cfg):
    """``W_out(RMSNorm(o) * w * silu(z))``: ``o`` float32 ``[N, Hv,
    dv]``, the norm over ``dv`` with the plain gain ``w``."""
    n = o.shape[0]
    gated = _norm(o, params[p + "gdn_norm_gamma"], cfg, offset=0.0) \
        * jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
    return _dot(gated.reshape(n, -1).astype(z.dtype),
                params[p + "out_weight"])


def _delta_prefill(params, p, x, valid, length, cfg):
    """One prompt ``x [T, d]`` through the chunked rule from an empty
    state.  Returns the update of the residual stream, the state ``[Hv,
    dk, dv]`` after token ``length - 1`` and the rows ``[kernel - 1,
    channels]`` that went into the convolution last (zeros before the
    start), as they lie in the pool."""
    h = _norm(x, params[p + "mixer_norm_gamma"], cfg)
    into, z, beta, g = _delta_inputs(params, p, h, cfg)
    conv, tail = _conv.conv_prefill(into, params[p + "conv_weight"], length)
    conv = jax.nn.silu(conv).astype(x.dtype)
    q, k, v = _delta_heads(conv, cfg)
    if valid is not None:           # the bucket's pad leaves the state
        beta = jnp.where(valid[:, None], beta, 0.0)
        g = jnp.where(valid[:, None], g, 0.0)
    o, state = gated_delta_chunked(q, k, v, g, beta)
    return _delta_out(params, p, o, z, cfg), state, \
        tail.reshape(_tail_shape(cfg))


def _delta_decode(params, p, x, pool, read, write, tail, cfg):
    """One token a sequence, ``x [B, d]``: row ``i``'s state is read
    from ``pool[read[i]]`` and written, advanced, to ``pool[write[i]]``
    (float32 ``[rows, Hv, dk, dv]``); ``tail`` ``[B, kernel - 1,
    channels]``.  Returns the update of the residual stream, the pool
    and the tail, advanced."""
    h = _norm(x, params[p + "mixer_norm_gamma"], cfg)
    into, z, beta, g = _delta_inputs(params, p, h, cfg)
    conv, tail = _conv.conv_step(tail, into, params[p + "conv_weight"])
    q, k, v = _delta_heads(jax.nn.silu(conv).astype(x.dtype), cfg)
    o, pool = gated_delta_update(q, k, v, g, beta, pool, read, write)
    return _delta_out(params, p, o, z, cfg), pool, tail


def _feed_forward(params, i, x, cfg, valid=None):
    """The expert layer's update and its counts."""
    p = "l%d_" % i
    h = _norm(x, params[p + "ffn_norm_gamma"], cfg)
    with jax.named_scope("expert_layer"):
        logits = jnp.einsum("nc,ec->ne", h, params[p + "router_weight"],
                            preferred_element_type=jnp.float32)
        chosen, gates = _moe.route_softmax_topk(
            logits, top_k=cfg["num_experts_per_tok"],
            normalize=cfg["norm_topk_prob"])
        routed, counts = _moe.dropless_experts(
            h, chosen, gates, params[p + "experts_gate_weight"],
            params[p + "experts_up_weight"],
            params[p + "experts_down_weight"], cfg["held"], valid=valid,
            every_row=_moe.few_rows_hit_most(
                h.shape[0], cfg["num_experts_per_tok"],
                cfg["num_experts"]),
            n_experts=cfg["num_experts"])
        shared = _moe.gated_shared_expert(
            h, params[p + "shared_gate_weight"],
            params[p + "shared_up_weight"],
            params[p + "shared_down_weight"],
            params[p + "shared_expert_gate_weight"])
    return routed + shared, counts


def _head(params, x, cfg):
    x = _norm(x, params["final_norm_gamma"], cfg)
    return jnp.einsum("nc,vc->nv", x, params["pred_weight"],
                      preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# the model's entry points


def forward(params, tokens, cfg, length=None):
    """One prompt ``tokens`` int32 ``[T]``: ``(hidden [T, d] before the
    final norm, k_rows, v_rows [full layers, T, Hkv * D], counts, (state
    [linear layers, Hv, dk, dv], tail [linear layers, ...]))``.
    Positions ``>= length`` are the bucket's pad: they are routed to no
    expert and leave the state as it is at ``length``."""
    t = tokens.shape[0]
    positions = jnp.arange(t, dtype=jnp.int32)
    valid = None if length is None else positions < length
    x = params["embed_weight"][tokens]
    k_rows, v_rows, states, tails, counts = [], [], [], [], None
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d_" % i
        if kind == "full":
            update, k, v = _attention_prefill(params, p, x, positions, cfg)
            k_rows.append(k)
            v_rows.append(v)
        else:
            update, state, tail = _delta_prefill(params, p, x, valid,
                                                 length, cfg)
            states.append(state)
            tails.append(tail)
        x = x + update
        update, count = _feed_forward(params, i, x, cfg, valid)
        x = x + update
        counts = count if counts is None else counts + count
    return x, jnp.stack(k_rows), jnp.stack(v_rows), counts, \
        (jnp.stack(states), jnp.stack(tails))


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
    """One token for each of ``B`` sequences: the full-attention layers
    through the paged pools ``[full layers, num_blocks, block_size, Hkv
    * D]`` (read as of before the step; the caller writes the returned
    rows behind this program), the DeltaNet layers through ``state =
    (S, tail)``, each ``[linear layers * 2 * num_slots, ...]``: row
    ``(layer * 2 + version) * num_slots + slot``.  Row ``i`` reads
    version ``positions[i] % 2`` of slot ``slots[i]`` and writes the
    other; a ``slots[i]`` of ``num_slots`` or more is a pad row and
    writes nowhere.  Returns ``(logits [B, V], k_rows, v_rows [full
    layers, B, Hkv * D], counts, state)``, ``state`` the pools written
    where they lie when the caller donates them."""
    pool_s, pool_tail = state
    n_linear = cfg["layer_types"].count("linear")
    n_slots = (pool_s.shape[0] - 1) // (2 * n_linear)
    live = slots < n_slots
    version = positions % 2
    x = params["embed_weight"][tokens]
    num_blocks = k_pages.shape[1]
    k_pool = k_pages.reshape((-1,) + k_pages.shape[2:])
    v_pool = v_pages.reshape((-1,) + v_pages.shape[2:])
    channels = _sizes(cfg)[2]
    k_rows, v_rows, counts, at_full, at_linear = [], [], None, 0, 0
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d_" % i
        if kind == "full":
            # every layer gathers from the whole pool through tables
            # offset to its blocks (a slice k_pages[i] is a copy)
            update, k, v = _attention_decode(
                params, p, x, positions, k_pool, v_pool,
                block_tables + at_full * num_blocks, context_lens, cfg)
            k_rows.append(k)
            v_rows.append(v)
            at_full += 1
        else:
            base = at_linear * 2 * n_slots + slots
            read = jnp.where(live, base + version * n_slots, 0)
            write = jnp.where(live, base + (1 - version) * n_slots,
                              pool_s.shape[0] - 1)
            tail = pool_tail[read].reshape(x.shape[0], -1, channels)
            update, pool_s, tail = _delta_decode(params, p, x, pool_s, read,
                                                 write, tail, cfg)
            pool_tail = pool_tail.at[write].set(
                tail.reshape((-1,) + pool_tail.shape[1:]))
            at_linear += 1
        x = x + update
        update, count = _feed_forward(params, i, x, cfg)
        x = x + update
        counts = count if counts is None else counts + count
    return _head(params, x, cfg), jnp.stack(k_rows), jnp.stack(v_rows), \
        counts, (pool_s, pool_tail)


def lm_definition(cfg, dtype=jnp.bfloat16):
    """This model as :class:`~mxnet_tpu.serving.LMBackend` serves it:
    key and value pools of ``Hkv * D``-wide rows over the full-attention
    layers alone, in the ``dtype`` the parameters are stored in, and
    beside them a state pool over the DeltaNet layers, one slot a
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
        cache_layers=cfg["layer_types"].count("full"),
        state=state_rows(cfg, dtype))
