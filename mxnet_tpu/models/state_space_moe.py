"""A hybrid decoder whose layers are **one sublayer each**: a Mamba-2
state-space mixer, an expert feed-forward that works in a latent
narrower than the residual, or a grouped-query attention without
positions, by a published pattern string (the Nemotron-H language
models, ``model_type`` ``nemotron_h``): RMSNorm with a plain gain;
every layer ``x + f(N(x))`` with ``f`` named by its character of
``hybrid_override_pattern`` (``M``, ``E``, ``*``); a final norm and an
untied bias-free head.

- ``M``: ``[z | x | B | C] = W_in h`` and ``dt = W_dt h`` (the
  checkpoint's ``in_proj``, its last ``mamba_num_heads`` rows apart so
  that the step's product stays float32); ``[x | B | C]`` through a
  causal depthwise convolution of ``conv_kernel`` taps, its bias and
  SiLU; the selective state-space recurrence
  (:mod:`~mxnet_tpu.ops.state_space`) over ``mamba_num_heads`` heads of
  ``mamba_head_dim`` channels with a state of ``ssm_state_size`` a
  channel, ``B`` and ``C`` shared by the heads of one of ``n_groups``
  groups; ``W_out(gain * RMSNorm_by_group(y * silu(z)))``.
- ``*``: ``num_attention_heads`` query heads over ``num_key_value_heads``
  key-value heads, causal, **no position applied** (the state-space
  layers carry the order), no bias.
- ``E``: a sigmoid router over ``n_routed_experts`` that chooses by
  score plus a selection bias (:func:`~mxnet_tpu.parallel.moe.
  route_group_limited`); the chosen experts are **two matrices without
  a gate**, ``W2 relu(W1 u)^2``, and work on ``u = W_down h``,
  ``moe_latent_size`` wide; their gated sum is projected back, ``W_up``,
  and a shared expert of the same form on the full width is added.

RMSNorm, the head and the expert counts' sum are the latent-attention
family's (``models/latent_moe.py``), the short convolution
``ops/short_conv.py``'s, the router and the dropless expert layer
``parallel/moe.py``'s.

Pure functions of ``(params, cfg)``.  :func:`prefill` runs one padded
prompt: the attention layers return the key and value rows the paged
cache keeps **per token**, the state-space layers the **state a
sequence keeps** (float32 ``[n_groups, ssm_state_size, channels a
group]`` and the last ``conv_kernel - 1`` rows that went into the
convolution), taken at ``length``: the bucket's pad positions pass
with ``dt = 0`` and leave it untouched.  :func:`decode_step` runs one
token a sequence: attention through the paged pools, the state-space
layers through the state pool, which it updates where it lies (the
caller donates it).  The cached layers, the state layers and the expert
layers are three different subsets of the depth.  ``params`` is a flat
dict under checkpoint-style names (:func:`param_shapes`); the computing
dtype is the dtype the parameters are stored in (bfloat16 as served,
float32 in the CPU tests), with float32 accumulation, router, softmax,
norm statistics, step ``dt``, decay and state.

``cfg`` is :func:`lm_config` of the published keys.  ``num_experts`` is
the router's width; ``held = (first, count)`` says which of those
experts this chip holds (:func:`~mxnet_tpu.parallel.moe.
dropless_experts`).  The multi-token-prediction module of the published
model is not built (the main model's logits do not depend on it).  The
state pool keeps two versions a slot, by the parity of the position, as
every model with a state does (:class:`~mxnet_tpu.serving.LMBackend`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import metrics as _metrics
from ..ops import short_conv as _conv
from ..ops import state_space as _ssm
from ..ops.attention import gqa_prefill_attention
from ..ops.kv_cache import CacheRow, StateRows
from ..ops.paged_attention import gqa_paged_decode_attention
from ..parallel import moe as _moe
from . import latent_moe as _lm
from .lm import LMDefinition

__all__ = ["lm_config", "lm_definition", "param_shapes", "init_params",
           "prefill", "decode_step", "full_logits", "state_rows", "book",
           "book_ssm", "ssm_counts", "SSM_COUNTS", "DECAY_RATE"]

_PUBLISHED = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "mamba_num_heads", "mamba_head_dim",
    "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "moe_latent_size", "moe_shared_expert_intermediate_size",
    "routed_scaling_factor", "norm_topk_prob", "n_group", "topk_group",
    "vocab_size")

#: the layer kinds, by their character of the published pattern
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def lm_config(published, seq_len, held=None):
    """The program's configuration from a published ``config.json`` (a
    dict): the keys the layers read, ``seq_len`` (the deployment's
    context limit), ``held = (first, count)`` of the
    ``n_routed_experts`` (all of them if not given) and
    ``layer_kinds``, the first ``num_hidden_layers`` characters of
    ``hybrid_override_pattern``."""
    cfg = {key: published[key] for key in _PUBLISHED}
    if any(published.get(k) for k in ("use_bias", "mlp_bias",
                                      "attention_bias", "mamba_proj_bias")) \
            or not published.get("use_conv_bias") \
            or published.get("mlp_hidden_act") != "relu2" \
            or published.get("mamba_hidden_act") != "silu" \
            or published.get("n_shared_experts") != 1:
        raise ValueError("a projection with a bias, a convolution without "
                         "one, another activation than relu2 / silu and "
                         "more or fewer than one shared expert are not "
                         "built")
    layers = cfg["num_hidden_layers"]
    kinds = tuple(published["hybrid_override_pattern"][:layers])
    if len(kinds) != layers or set(kinds) - {MAMBA, EXPERTS, ATTENTION}:
        raise ValueError("hybrid_override_pattern names %d layers of kinds "
                         "%s" % (len(kinds), sorted(set(kinds))))
    if cfg["mamba_num_heads"] % cfg["n_groups"]:
        raise ValueError("n_groups does not divide mamba_num_heads")
    cfg["layer_kinds"] = kinds
    cfg["rms_norm_eps"] = published["norm_eps"]
    # the names the shared layers and the generation lane read theirs
    # under
    cfg["num_experts"] = cfg["n_routed_experts"]
    cfg["seq_len"] = int(seq_len)
    cfg["num_layers"] = layers
    cfg["num_classes"] = cfg["vocab_size"]
    cfg["held"] = tuple(held or (0, cfg["num_experts"]))
    return cfg


def _sizes(cfg):
    """(inner width ``H P``, ``G N``, convolved channels) of a
    state-space layer."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    bc = cfg["n_groups"] * cfg["ssm_state_size"]
    return inner, bc, inner + 2 * bc


def _state_shape(cfg):
    return _ssm.state_shape(cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                            cfg["n_groups"], cfg["ssm_state_size"])


def _tail_shape(cfg):
    return _conv.tail_shape(cfg["conv_kernel"] - 1, _sizes(cfg)[2])


def state_rows(cfg, dtype=jnp.bfloat16):
    """What a sequence keeps between steps, per state-space layer: the
    :class:`~mxnet_tpu.ops.kv_cache.StateRows` the cache builds its
    state pool from (the recurrence's float32 state as
    :func:`~mxnet_tpu.ops.state_space.state_shape` lays it, and the
    convolution's tail)."""
    return StateRows(cfg["layer_kinds"].count(MAMBA), (
        (_state_shape(cfg), np.dtype(np.float32)),
        (_tail_shape(cfg), np.dtype(dtype))))


def param_shapes(cfg):
    """name -> shape.  Matrices are ``[out, in]`` like a checkpoint's;
    the held experts of a layer are stacked, ``[held, in, out]`` (the
    layout the grouped product reads).  ``in_weight``'s rows are ``[z |
    x | B | C]``, each part whole, and ``dt_weight`` is the last
    ``mamba_num_heads`` rows of the checkpoint's ``in_proj``;
    ``conv_weight`` is the depthwise kernel ``[channels, taps]``, the
    last tap on the current token."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    inner, _, channels = _sizes(cfg)
    mheads = cfg["mamba_num_heads"]
    latent, ffn = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    shared, held = cfg["moe_shared_expert_intermediate_size"], cfg["held"][1]
    shapes = {"embed_weight": (v, d), "final_norm_gamma": (d,),
              "pred_weight": (v, d)}
    for i, kind in enumerate(cfg["layer_kinds"]):
        p = "l%d_" % i
        shapes[p + "norm_gamma"] = (d,)
        if kind == MAMBA:
            shapes.update({
                p + "in_weight": (inner + channels, d),
                p + "dt_weight": (mheads, d),
                p + "conv_weight": (channels, cfg["conv_kernel"]),
                p + "conv_bias": (channels,),
                p + "A_log": (mheads,), p + "D": (mheads,),
                p + "dt_bias": (mheads,),
                p + "ssm_norm_gamma": (inner,),
                p + "out_weight": (d, inner)})
        elif kind == ATTENTION:
            shapes.update({
                p + "q_weight": (heads * dim, d),
                p + "k_weight": (groups * dim, d),
                p + "v_weight": (groups * dim, d),
                p + "o_weight": (d, heads * dim)})
        else:
            shapes.update({
                p + "router_weight": (cfg["num_experts"], d),
                p + "router_bias": (cfg["num_experts"],),
                p + "latent_down_weight": (latent, d),
                p + "latent_up_weight": (d, latent),
                p + "experts_up_weight": (held, latent, ffn),
                p + "experts_down_weight": (held, ffn, latent),
                p + "shared_up_weight": (shared, d),
                p + "shared_down_weight": (d, shared)})
    return shapes


#: the rate a state-space head is drawn with: ``-A = exp(A_log)``
#: uniform in log between these, so that under a step ``softplus(dt +
#: dt_bias)`` of about 0.7 (``dt_bias`` normal(0, 0.1)) a token's decay
#: ``exp(A step)`` spans about 0.6 to 0.999 across heads (a checkpoint's
#: spread is not in its configuration, and the modeling code's own draw,
#: ``A`` uniform in 1-16 under steps of 0.001-0.1, is an initialisation)
DECAY_RATE = (0.001, 0.7)


def draw_param(key, name, shape, dtype, scale=0.02, bias_scale=0.01):
    """One seeded parameter, by its name: normal(0, ``scale``) matrices
    and convolution bias, gains and the skip ``D`` 1, ``A_log`` by
    :data:`DECAY_RATE` and ``dt_bias`` normal(0, 0.1), the router's
    selection bias normal(0, ``bias_scale``); the last three float32."""
    if name.endswith(("_gamma", "_D")):
        return jnp.ones(shape, dtype)
    if name.endswith("A_log"):
        low, high = np.log(DECAY_RATE[0]), np.log(DECAY_RATE[1])
        return jax.random.uniform(key, shape, jnp.float32, low, high)
    if name.endswith("dt_bias"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name.endswith("router_bias"):
        return bias_scale * jax.random.normal(key, shape, jnp.float32)
    return (scale * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def init_params(cfg, seed=0, dtype=jnp.bfloat16, scale=0.02,
                bias_scale=0.01):
    """Seeded parameters as a function would load them
    (:func:`draw_param`)."""
    key = jax.random.PRNGKey(seed)
    return {name: draw_param(jax.random.fold_in(key, i), name, shape, dtype,
                             scale, bias_scale)
            for i, (name, shape) in enumerate(
                sorted(param_shapes(cfg).items()))}


# ----------------------------------------------------------------------
# layers


def _relu2(x, w_up, w_down):
    """``W_down relu(W_up x)^2`` with ``[out, in]`` weights, float32
    accumulation and activation, activations kept in ``x``'s dtype."""
    h = jnp.einsum("tc,fc->tf", x, w_up, preferred_element_type=jnp.float32)
    h = _moe.ACTIVATIONS["relu2"](h).astype(x.dtype)
    return jnp.einsum("tf,cf->tc", h, w_down,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _experts(params, p, h, cfg, valid=None):
    """The expert layer's update of the normed ``h [N, d]`` and its
    counts: the router and the shared expert read ``h``, the routed
    experts its projection to the latent."""
    with jax.named_scope("expert_layer"):
        logits = jnp.einsum("nc,ec->ne", h, params[p + "router_weight"],
                            preferred_element_type=jnp.float32)
        chosen, gates = _moe.route_group_limited(
            logits, params[p + "router_bias"],
            top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
            topk_group=cfg["topk_group"],
            scale=cfg["routed_scaling_factor"],
            normalize=cfg["norm_topk_prob"])
        with jax.named_scope("latent_down"):
            u = _lm._dot(h, params[p + "latent_down_weight"])
        routed, counts = _moe.dropless_experts(
            u, chosen, gates, None, params[p + "experts_up_weight"],
            params[p + "experts_down_weight"], cfg["held"], valid=valid,
            every_row=_moe.few_rows_hit_most(
                h.shape[0], cfg["num_experts_per_tok"],
                cfg["num_experts"]),
            n_experts=cfg["num_experts"], activation="relu2")
        with jax.named_scope("latent_up"):
            routed = _lm._dot(routed, params[p + "latent_up_weight"])
        shared = _relu2(h, params[p + "shared_up_weight"],
                        params[p + "shared_down_weight"])
    return routed + shared, counts


def _projections(params, p, h, cfg):
    """Queries ``[N, Hq, D]``, keys and values ``[N, Hkv, D]`` of the
    normed ``h``, as they are: no position enters the layer but through
    the mask."""
    n = h.shape[0]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    q = _lm._dot(h, params[p + "q_weight"]).reshape(n, heads, dim)
    k = _lm._dot(h, params[p + "k_weight"]).reshape(n, groups, dim)
    v = _lm._dot(h, params[p + "v_weight"]).reshape(n, groups, dim)
    return q, k, v


def _attention_prefill(params, p, h, cfg):
    """One prompt, ``h [T, d]`` normed: the update of the residual
    stream and the key and value rows ``[T, Hkv * D]`` the cache
    keeps."""
    q, k, v = _projections(params, p, h, cfg)
    o = gqa_prefill_attention(
        q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
        v.transpose(1, 0, 2)[None], cfg["head_dim"] ** -0.5)[0]
    t = h.shape[0]
    o = o.transpose(1, 0, 2).reshape(t, -1).astype(h.dtype)
    return _lm._dot(o, params[p + "o_weight"]), k.reshape(t, -1), \
        v.reshape(t, -1)


def _attention_decode(params, p, h, k_pool, v_pool, tables, context_lens,
                      cfg):
    """One token a sequence, ``h [B, d]`` normed, over the paged key and
    value pools ``[blocks, block_size, Hkv * D]``."""
    q, k, v = _projections(params, p, h, cfg)
    o = gqa_paged_decode_attention(q, k, v, k_pool, v_pool, tables,
                                   context_lens, cfg["head_dim"] ** -0.5)
    b = h.shape[0]
    return _lm._dot(o.reshape(b, -1).astype(h.dtype),
                    params[p + "o_weight"]), k.reshape(b, -1), \
        v.reshape(b, -1)


def _mamba_inputs(params, p, h, cfg):
    """The state-space layer's projections of ``h [N, d]``: the output
    gate ``z [N, inner]``, what goes into the convolution ``[N,
    channels]`` and the raw step ``[N, H]``, float32.  A family whose
    projection is scaled row by row (``cfg["in_proj_scales"]``: float32
    ``[inner + channels]`` for ``in_weight``'s rows and a factor for
    ``dt_weight``'s) has the float32 products scaled before they are
    rounded."""
    inner = _sizes(cfg)[0]
    mixed, dt = (jnp.einsum("nc,fc->nf", h, params[p + name],
                            preferred_element_type=jnp.float32)
                 for name in ("in_weight", "dt_weight"))
    scales = cfg.get("in_proj_scales")
    if scales is not None:
        mixed, dt = mixed * scales[0], dt * scales[1]
    mixed = mixed.astype(h.dtype)
    return mixed[:, :inner], mixed[:, inner:], dt


def _mamba_heads(params, p, conv, dt, dtype, cfg):
    """The convolution's float32 sum ``[N, channels]`` and the raw step
    as the recurrence's operands: ``x [N, H, P]``, ``B``, ``C`` ``[N, G,
    N_state]`` (bias, SiLU, rounded to ``dtype``), the step
    ``softplus(dt + dt_bias)`` float32 ``[N, H]``, and the heads' rate
    ``A`` and skip ``D``."""
    n = conv.shape[0]
    inner, bc, _ = _sizes(cfg)
    groups = cfg["n_groups"]
    bias = params[p + "conv_bias"].astype(jnp.float32)

    def part(lo, hi):
        # cut before the activation: each operand is then made where it
        # is read from, and not cut out of a ``[N, channels]`` array
        # (the scan's kernel takes its operands whole)
        return jax.nn.silu(conv[:, lo:hi] + bias[lo:hi]).astype(dtype)

    x = part(0, inner).reshape(n, cfg["mamba_num_heads"], -1)
    b = part(inner, inner + bc).reshape(n, groups, -1)
    c = part(inner + bc, inner + 2 * bc).reshape(n, groups, -1)
    dt = jax.nn.softplus(dt + params[p + "dt_bias"].astype(jnp.float32))
    return x, dt, -jnp.exp(params[p + "A_log"].astype(jnp.float32)), b, c, \
        params[p + "D"].astype(jnp.float32)


def _mamba_out(params, p, y, z, cfg):
    """``W_out(gain * RMSNorm_by_group(y * silu(z)))``: ``y`` ``[N, H,
    P]``, the norm over each of the ``n_groups`` groups of channels."""
    n = y.shape[0]
    gated = y.astype(jnp.float32).reshape(n, -1) \
        * jax.nn.silu(z.astype(jnp.float32))
    by_group = gated.reshape(n, cfg["n_groups"], -1)
    by_group = by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True)
        + cfg["rms_norm_eps"])
    normed = by_group.reshape(n, -1) \
        * params[p + "ssm_norm_gamma"].astype(jnp.float32)
    return _lm._dot(normed.astype(z.dtype), params[p + "out_weight"])


#: the most tokens a state-space layer of a prefill takes at once: a
#: longer prompt runs as stretches that hand state and tail on, so that
#: the layer's temporaries (604 MB of projections and as much again in
#: float32 at 16,384 tokens) are a stretch's and not the prompt's
SEGMENT = 4096


def _segment(tokens, cfg):
    """The stretch a prompt of ``tokens`` runs in: all of it up to
    :data:`SEGMENT`, else the largest divisor under that which is whole
    iterations of the scan (all of it where there is none)."""
    step = min(cfg["chunk_size"] * _ssm.BLOCK, SEGMENT)
    if tokens <= SEGMENT:
        return tokens
    return next((s for s in range(SEGMENT - SEGMENT % step, 0, -step)
                 if tokens % s == 0), tokens)


def _mamba_stretch(params, p, h, length, state, tail, cfg):
    """``h [S, d]`` normed, ``length`` of its positions tokens, from
    ``state`` and ``tail`` (None: empty).  Returns the update of the
    residual stream, the state after token ``length - 1`` and the rows
    ``[taps - 1, channels]`` that went into the convolution last before
    it."""
    z, into, dt = _mamba_inputs(params, p, h, cfg)
    conv, tail = _conv.conv_prefill(into, params[p + "conv_weight"], length,
                                    tail)
    y, state = _ssm.ssm_chunked(
        *_mamba_heads(params, p, conv, dt, h.dtype, cfg), state=state,
        length=length, chunk=cfg["chunk_size"])
    return _mamba_out(params, p, y, z, cfg), state, tail


def _mamba_prefill(params, p, h, length, cfg):
    """One prompt, ``h [T, d]`` normed, through the chunked scan from an
    empty state, :func:`_segment` tokens at a time.  Returns the update
    of the residual stream, the state after token ``length - 1`` and
    the rows that went into the convolution last before it, as they lie
    in the pool."""
    t = h.shape[0]
    size = _segment(t, cfg)
    if size == t:
        out, state, tail = _mamba_stretch(params, p, h, length, None, None,
                                          cfg)
        return out, state, tail.reshape(_tail_shape(cfg))

    def stretch(carry, inputs):
        h_s, start = inputs
        here = size if length is None else jnp.clip(length - start, 0, size)
        out, state, tail = _mamba_stretch(params, p, h_s, here, *carry, cfg)
        return (state, tail), out

    empty = (jnp.zeros(_state_shape(cfg), jnp.float32),
             jnp.zeros((cfg["conv_kernel"] - 1, _sizes(cfg)[2]), h.dtype))
    (state, tail), out = jax.lax.scan(
        stretch, empty, (h.reshape(t // size, size, -1),
                         jnp.arange(0, t, size, dtype=jnp.int32)))
    return out.reshape(t, -1), state, tail.reshape(_tail_shape(cfg))


def _mamba_decode(params, p, h, pool, read, write, tail, cfg):
    """One token a sequence, ``h [B, d]`` normed: row ``i``'s state is
    read from ``pool[read[i]]`` and written, advanced, to
    ``pool[write[i]]``; ``tail`` ``[B, taps - 1, channels]``.  Returns
    the update of the residual stream, the pool and the tail,
    advanced."""
    z, into, dt = _mamba_inputs(params, p, h, cfg)
    conv, tail = _conv.conv_step(tail, into, params[p + "conv_weight"])
    y, pool = _ssm.ssm_update(
        *_mamba_heads(params, p, conv, dt, h.dtype, cfg), pool, read, write)
    return _mamba_out(params, p, y, z, cfg), pool, tail


# ----------------------------------------------------------------------
# what the programs count: the expert layers' five, then three of a
# prefill's state-space layers (nothing in a decode step)

#: the counters that follow :data:`~mxnet_tpu.parallel.moe.EXPERT_COUNTS`
#: in the programs' ``counts`` vector
SSM_COUNTS = ("ssm_prefill_tokens_total", "ssm_prefill_chunks_run_total",
              "ssm_prefill_chunks_skipped_total")
_M_SSM = [_metrics.counter(name, text + ", by model", ["model"])
          for name, text in zip(SSM_COUNTS, (
              "Tokens prefills scanned through state-space layers: a "
              "prompt's tokens times its state-space layers (the bucket's "
              "pad positions pass and are not counted)",
              "Chunks of the state-space prefill scan whose products ran, "
              "over every state-space layer",
              "Chunks of a bucket that lay wholly in its pad and ran no "
              "product (the scan's kernel skips them; XLA's body runs "
              "every chunk), over every state-space layer"))]


def book_ssm(model, counts):
    """Add one call's :data:`SSM_COUNTS` to the counters."""
    for family, value in zip(_M_SSM, counts):
        family.labels(model).inc(int(value))


def book(model, counts):
    """Add one call's ``counts`` to the counters: the expert layers'
    (:func:`~mxnet_tpu.parallel.moe.book_expert_counts`), then
    :data:`SSM_COUNTS`."""
    n = len(_moe.EXPERT_COUNTS)
    _moe.book_expert_counts(model, counts[:n])
    book_ssm(model, counts[n:])


def ssm_counts(cfg, layers, bucket, length):
    """:data:`SSM_COUNTS` of a prefill of ``length`` tokens in a bucket
    of ``bucket`` (0 and 0: a decode step) through ``layers``
    state-space layers.  A stretch is whole chunks from the bucket's
    start, so the chunks that hold a token are the first ``ceil(length
    / chunk)``."""
    chunk = cfg["chunk_size"]
    chunks = -(-bucket // chunk)
    kernel = _ssm.scan_form(
        cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
        cfg["ssm_state_size"], chunk) == "kernel"
    ran = (length + chunk - 1) // chunk if kernel else chunks
    return layers * jnp.stack([jnp.asarray(v, jnp.int32)
                               for v in (length, ran, chunks - ran)])


def _counts(cfg, expert_counts, bucket, length):
    """A call's ``counts``: the expert layers' sum, then
    :func:`ssm_counts`."""
    total = _lm._sum_counts(expert_counts)
    if total is None:           # a cut without an expert layer
        total = jnp.zeros(len(_moe.EXPERT_COUNTS), jnp.int32)
    return jnp.concatenate([total, ssm_counts(
        cfg, cfg["layer_kinds"].count(MAMBA), bucket, length)])


# ----------------------------------------------------------------------
# the model's entry points


def forward(params, tokens, cfg, length=None):
    """One prompt ``tokens`` int32 ``[T]``: ``(hidden [T, d] before the
    final norm, k_rows, v_rows [attention layers, T, Hkv * D], counts,
    (state [state layers, G, N, W], tail [state layers, ...]))``.
    Positions ``>= length`` are the bucket's pad: they are routed to no
    expert and leave the state as it is at ``length``."""
    t = tokens.shape[0]
    valid = None if length is None \
        else jnp.arange(t, dtype=jnp.int32) < length
    x = params["embed_weight"][tokens]
    k_rows, v_rows, states, tails, counts = [], [], [], [], []
    for i, kind in enumerate(cfg["layer_kinds"]):
        p = "l%d_" % i
        h = _lm._norm(x, params[p + "norm_gamma"], cfg)
        if kind == MAMBA:
            update, state, tail = _mamba_prefill(params, p, h, length, cfg)
            states.append(state)
            tails.append(tail)
        elif kind == ATTENTION:
            update, k, v = _attention_prefill(params, p, h, cfg)
            k_rows.append(k)
            v_rows.append(v)
        else:
            update, count = _experts(params, p, h, cfg, valid)
            counts.append(count)
        x = x + update
    counts = _counts(cfg, counts, t, t if length is None else length)
    return x, jnp.stack(k_rows), jnp.stack(v_rows), counts, \
        (jnp.stack(states), jnp.stack(tails))


def prefill(params, tokens, length, cfg):
    """``(logits float32 [V] after token length - 1, k_rows, v_rows,
    counts, state)``: one program a bucket, whatever the prompt's real
    length; only one row of logits is computed."""
    x, k_rows, v_rows, counts, state = forward(params, tokens, cfg, length)
    logits = _lm._head(
        params, jax.lax.dynamic_slice_in_dim(x, length - 1, 1), cfg)
    return logits[0], k_rows, v_rows, counts, state


def full_logits(params, tokens, cfg):
    """float32 logits ``[B, T, V]`` of ``tokens`` ``[B, T]``, no cache:
    the classifier-lane protocol and the tests' full forward."""
    return jnp.stack([_lm._head(params, forward(params, row, cfg)[0], cfg)
                      for row in tokens])


def decode_step(params, tokens, positions, k_pages, v_pages, block_tables,
                context_lens, state, slots, cfg):
    """One token for each of ``B`` sequences: the attention layers
    through the paged pools ``[attention layers, num_blocks, block_size,
    Hkv * D]`` (read as of before the step; the caller writes the
    returned rows behind this program), the state-space layers through
    ``state = (S, tail)``, each ``[state layers * 2 * num_slots + 1,
    ...]``: row ``(layer * 2 + version) * num_slots + slot``.  Row ``i``
    reads version ``positions[i] % 2`` of slot ``slots[i]`` and writes
    the other; a ``slots[i]`` of ``num_slots`` or more is a pad row and
    writes the pools' last row.  Returns ``(logits [B, V], k_rows,
    v_rows [attention layers, B, Hkv * D], counts, state)``, ``state``
    the pools written where they lie when the caller donates them."""
    pool_s, pool_tail = state
    n_state = cfg["layer_kinds"].count(MAMBA)
    n_slots = (pool_s.shape[0] - 1) // (2 * n_state)
    live = slots < n_slots
    version = positions % 2
    x = params["embed_weight"][tokens]
    num_blocks = k_pages.shape[1]
    k_pool = k_pages.reshape((-1,) + k_pages.shape[2:])
    v_pool = v_pages.reshape((-1,) + v_pages.shape[2:])
    channels = _sizes(cfg)[2]
    k_rows, v_rows, counts, at_attn, at_state = [], [], [], 0, 0
    for i, kind in enumerate(cfg["layer_kinds"]):
        p = "l%d_" % i
        h = _lm._norm(x, params[p + "norm_gamma"], cfg)
        if kind == MAMBA:
            base = at_state * 2 * n_slots + slots
            read = jnp.where(live, base + version * n_slots, 0)
            write = jnp.where(live, base + (1 - version) * n_slots,
                              pool_s.shape[0] - 1)
            tail = pool_tail[read].reshape(x.shape[0], -1, channels)
            update, pool_s, tail = _mamba_decode(params, p, h, pool_s, read,
                                                 write, tail, cfg)
            pool_tail = pool_tail.at[write].set(
                tail.reshape((-1,) + pool_tail.shape[1:]))
            at_state += 1
        elif kind == ATTENTION:
            # every layer gathers from the whole pool through tables
            # offset to its blocks (a slice k_pages[i] is a copy)
            update, k, v = _attention_decode(
                params, p, h, k_pool, v_pool,
                block_tables + at_attn * num_blocks, context_lens, cfg)
            k_rows.append(k)
            v_rows.append(v)
            at_attn += 1
        else:
            update, count = _experts(params, p, h, cfg)
            counts.append(count)
        x = x + update
    return _lm._head(params, x, cfg), jnp.stack(k_rows), jnp.stack(v_rows), \
        _counts(cfg, counts, 0, 0), (pool_s, pool_tail)


def lm_definition(cfg, dtype=jnp.bfloat16):
    """This model as :class:`~mxnet_tpu.serving.LMBackend` serves it:
    key and value pools of ``Hkv * D``-wide rows over the attention
    layers alone, in the ``dtype`` the parameters are stored in, and
    beside them a state pool over the state-space layers, one slot a
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
        book=book, prepare=None,
        cache_layers=cfg["layer_kinds"].count(ATTENTION),
        state=state_rows(cfg, dtype))
