"""A decoder of sliding-window and global grouped-query attention over
sparse ReGLU experts whose router reads the attention's input, built
from its published configuration (the SmallThinker expert language
models): RMSNorm; a mixer a layer named by two published per-layer
lists, ``sliding_window_layout`` (1: the layer sees the last
``sliding_window_size`` tokens, the current one counted; 0: the whole
context) and ``rope_layout`` (1: queries and keys are turned, rotary
over the whole head; 0: **no position enters the layer** but through
the mask); every layer an expert layer (no dense one, no shared
expert) whose softmax router chooses from ``N_in(x)``, the normed input
the attention reads, *before* the attention, while the chosen experts,
``W_down(relu(W_gate h) * W_up h)``, are applied to the normed output
of the attention's residual; an embedding and a head of its own.

RMSNorm, the head and the expert counts' sum are the latent family's
(``models/latent_moe.py``), the rotary turn the gated-delta family's
(``models/gated_delta_moe.py``, here over the whole head), the router
and the dropless expert layer ``parallel/moe.py``'s.

Pure functions of ``(params, cfg)``.  A window layer and a global layer
keep their rows for different lengths of time, so the model has two
**layer groups** (:class:`~mxnet_tpu.models.lm.LMDefinition`,
``cache_groups``): the global layers' pools, whose blocks a sequence
holds for all its tokens, and the window layers', in which its blocks
are a ring of ``sliding_window_size / block_size + 1`` entries.  The
programs order their cache rows by group, the global layers' first:
:func:`prefill` returns ``k_rows``/``v_rows`` ``[global layers then
window layers, T, Hkv * D]``, and :func:`decode_step` takes the two
groups' pools as a tuple and a block table whose rows hold the global
table and the ring side by side.  A model whose layers are all of one
kind has one group and plain arrays.  ``params`` is a flat dict under
checkpoint-style names (:func:`param_shapes`); the computing dtype is
the dtype the parameters are stored in (bfloat16 as served, float32 in
the CPU tests), with float32 accumulation, router, softmax and norm
statistics.

``cfg`` is :func:`lm_config` of the published keys.  ``num_experts`` is
the router's width; ``held = (first, count)`` says which of those
experts this chip holds (:func:`~mxnet_tpu.parallel.moe.
dropless_experts`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import metrics as _metrics
from ..ops.attention import band_tiles, gqa_prefill_attention
from ..ops.kv_cache import CacheRow
from ..ops.paged_attention import gqa_paged_decode_attention
from ..parallel import moe as _moe
from . import latent_moe as _lm
from .gated_delta_moe import _rotate
from .lm import LMDefinition

__all__ = ["lm_config", "lm_definition", "param_shapes", "init_params",
           "prefill", "decode_step", "full_logits", "cache_groups",
           "table_widths", "book", "WINDOW_COUNTS"]

_PUBLISHED = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_ffn_hidden_size",
    "norm_topk_prob", "rms_norm_eps", "rope_theta", "sliding_window_size",
    "vocab_size")


def lm_config(published, seq_len, held=None):
    """The program's configuration from a published ``config.json`` (a
    dict): the keys the layers read, ``seq_len`` (the deployment's
    context limit), ``held = (first, count)`` of the
    ``moe_num_primary_experts`` (all of them if not given), and
    ``layer_windows`` / ``layer_rotary``, the first
    ``num_hidden_layers`` entries of ``sliding_window_layout`` and
    ``rope_layout`` as tuples of bools."""
    cfg = {key: published[key] for key in _PUBLISHED}
    if published.get("rope_scaling") \
            or not published.get("moe_primary_router_apply_softmax"):
        raise ValueError("rope scaling and a primary router without its "
                         "softmax are not built")
    layers = cfg["num_hidden_layers"]
    for name, key in (("layer_windows", "sliding_window_layout"),
                      ("layer_rotary", "rope_layout")):
        marks = tuple(published[key][:layers])
        if len(marks) != layers or set(marks) - {0, 1}:
            raise ValueError("%s names %d layers by %s, not %d by 0 or 1"
                             % (key, len(marks), sorted(set(marks)),
                                layers))
        cfg[name] = tuple(bool(m) for m in marks)
    cfg["num_experts"] = published["moe_num_primary_experts"]
    cfg["num_experts_per_tok"] = published["moe_num_active_primary_experts"]
    # the name the shared rotary turn reads its share of the head under
    cfg["partial_rotary_factor"] = 1.0
    cfg["seq_len"] = int(seq_len)
    # the generation lane's own names for depth and vocabulary
    cfg["num_layers"] = layers
    cfg["num_classes"] = cfg["vocab_size"]
    cfg["held"] = tuple(held or (0, cfg["num_experts"]))
    return cfg


def _layer_order(cfg):
    """The layers in the order of their cache rows: the global layers,
    then the window layers."""
    windows = cfg["layer_windows"]
    return [i for i, w in enumerate(windows) if not w] \
        + [i for i, w in enumerate(windows) if w]


def cache_groups(cfg):
    """The cache's layer groups (``LMDefinition.cache_groups``): the
    global layers' rows first, then the window layers' with their
    window; ``None`` for a model without a window layer."""
    windows = cfg["layer_windows"]
    n_global = windows.count(False)
    if n_global == len(windows):
        return None
    groups = [(tuple(range(n_global, len(windows))),
               cfg["sliding_window_size"])]
    if n_global:
        groups.insert(0, (tuple(range(n_global)), None))
    return tuple(groups)


def table_widths(cfg, block_size):
    """``(global, ring)``: the columns of a block-table row that are the
    global layers' table and the window layers' ring (0 for a kind the
    model has no layer of), as the cache lays them side by side."""
    whole = -(-cfg["seq_len"] // block_size)
    windows = cfg["layer_windows"]
    ring = min(whole, cfg["sliding_window_size"] // block_size + 1)
    return (whole if False in windows else 0,
            ring if True in windows else 0)


def param_shapes(cfg):
    """name -> shape.  Matrices are ``[out, in]`` like a checkpoint's;
    the held experts of a layer are stacked, ``[held, in, out]`` (the
    layout the grouped product reads).  The head has a matrix of its
    own, ``pred_weight`` (``tie_word_embeddings`` false)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, ffn = cfg["head_dim"], cfg["moe_ffn_hidden_size"]
    held = cfg["held"][1]
    shapes = {"embed_weight": (v, d), "final_norm_gamma": (d,),
              "pred_weight": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d_" % i
        shapes.update({
            p + "input_norm_gamma": (d,), p + "post_norm_gamma": (d,),
            p + "q_weight": (heads * dim, d),
            p + "k_weight": (groups * dim, d),
            p + "v_weight": (groups * dim, d),
            p + "o_weight": (d, heads * dim),
            p + "router_weight": (cfg["num_experts"], d),
            p + "experts_gate_weight": (held, d, ffn),
            p + "experts_up_weight": (held, d, ffn),
            p + "experts_down_weight": (held, ffn, d)})
    return shapes


def init_params(cfg, seed=0, dtype=jnp.bfloat16, scale=0.02):
    """Seeded parameters as a function would load them: normal(0,
    ``scale``) matrices, gains 1."""
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        if name.endswith("_gamma"):
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (scale * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            ).astype(dtype)
    return out


# ----------------------------------------------------------------------
# layers


def _route(params, p, h, cfg):
    """The layer's choice, made from ``h``, the normed input of its
    attention: ``(chosen int32 [N, k], gates float32 [N, k])`` over all
    the router's experts, the softmax renormalised over the chosen."""
    with jax.named_scope("expert_router"):
        logits = jnp.einsum("nc,ec->ne", h, params[p + "router_weight"],
                            preferred_element_type=jnp.float32)
        return _moe.route_softmax_topk(
            logits, top_k=cfg["num_experts_per_tok"],
            normalize=cfg["norm_topk_prob"])


def _projections(params, p, h, positions, rotary, cfg):
    """Queries ``[N, Hq, D]``, keys and values ``[N, Hkv, D]`` of the
    normed input ``h``; queries and keys turned where the layer is
    ``rotary``, and else as they are (no position)."""
    n = h.shape[0]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    q = _lm._dot(h, params[p + "q_weight"]).reshape(n, heads, dim)
    k = _lm._dot(h, params[p + "k_weight"]).reshape(n, groups, dim)
    v = _lm._dot(h, params[p + "v_weight"]).reshape(n, groups, dim)
    if rotary:
        q, k = _rotate(q, positions, cfg), _rotate(k, positions, cfg)
    return q, k, v


def _window(cfg, layer):
    return cfg["sliding_window_size"] if cfg["layer_windows"][layer] \
        else None


def _attention_prefill(params, i, h, positions, cfg):
    """One prompt, ``h [T, d]`` normed: the update of the residual
    stream and the key and value rows ``[T, Hkv * D]`` the cache
    keeps."""
    p = "l%d_" % i
    q, k, v = _projections(params, p, h, positions,
                           cfg["layer_rotary"][i], cfg)
    o = gqa_prefill_attention(
        q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
        v.transpose(1, 0, 2)[None], cfg["head_dim"] ** -0.5,
        window=_window(cfg, i))[0]
    t = h.shape[0]
    o = o.transpose(1, 0, 2).reshape(t, -1).astype(h.dtype)
    return _lm._dot(o, params[p + "o_weight"]), k.reshape(t, -1), \
        v.reshape(t, -1)


def _attention_decode(params, i, h, positions, k_pool, v_pool, tables,
                      context_lens, cfg):
    """One token a sequence, ``h [B, d]`` normed, over the layer's
    group's pools ``[blocks, block_size, Hkv * D]`` through its group's
    tables (a window layer's: rings)."""
    p = "l%d_" % i
    q, k, v = _projections(params, p, h, positions,
                           cfg["layer_rotary"][i], cfg)
    o = gqa_paged_decode_attention(q, k, v, k_pool, v_pool, tables,
                                   context_lens, cfg["head_dim"] ** -0.5,
                                   window=_window(cfg, i))
    b = h.shape[0]
    return _lm._dot(o.reshape(b, -1).astype(h.dtype),
                    params[p + "o_weight"]), k.reshape(b, -1), \
        v.reshape(b, -1)


def _experts(params, i, x, chosen, gates, cfg, valid=None):
    """The expert layer's update of ``x`` (the residual stream after
    the attention) under the choice made before it, and its counts."""
    p = "l%d_" % i
    h = _lm._norm(x, params[p + "post_norm_gamma"], cfg)
    with jax.named_scope("expert_layer"):
        return _moe.dropless_experts(
            h, chosen, gates, params[p + "experts_gate_weight"],
            params[p + "experts_up_weight"],
            params[p + "experts_down_weight"], cfg["held"], valid=valid,
            every_row=_moe.few_rows_hit_most(
                h.shape[0], cfg["num_experts_per_tok"],
                cfg["num_experts"]),
            n_experts=cfg["num_experts"], activation="relu")


def _layer(params, i, x, attention, cfg, valid=None):
    """One layer over the residual stream ``x``: ``attention(h)`` gives
    the mixer's update and its cache rows.  Returns ``(x, k, v,
    counts)``."""
    h = _lm._norm(x, params["l%d_input_norm_gamma" % i], cfg)
    # the routing is the attention input's; it is carried past the mixer
    chosen, gates = _route(params, "l%d_" % i, h, cfg)
    update, k, v = attention(h)
    x = x + update
    update, counts = _experts(params, i, x, chosen, gates, cfg, valid)
    return x + update, k, v, counts


def _by_group(cfg, rows):
    """A layer's rows, one a layer, stacked in the cache's order."""
    return jnp.stack([rows[i] for i in _layer_order(cfg)])


# ----------------------------------------------------------------------
# what the programs count: the expert layers' four, then three of a
# prefill's window layers (nothing in a decode step)

#: the counters that follow :data:`~mxnet_tpu.parallel.moe.EXPERT_COUNTS`
#: in the programs' ``counts`` vector
WINDOW_COUNTS = ("window_prefill_tiles_walked_total",
                 "window_prefill_tiles_masked_total",
                 "window_prefill_tiles_causal_total")
_M_WINDOW = [_metrics.counter(name, text + ", by model", ["model"])
             for name, text in zip(WINDOW_COUNTS, (
                 "(run, chunk) score tiles the window layers' prefill "
                 "attention walks, summed over layers and prompts (by the "
                 "flash kernel's tiles, whichever body ran)",
                 "Those of the walked tiles that take a mask: on the "
                 "diagonal or on the band's lower edge",
                 "Tiles a causal walk of the same prompts would have "
                 "walked: what the band is a share of"))]


def book(model, counts):
    """Add one call's ``counts`` to the counters: the expert layers'
    (:func:`~mxnet_tpu.parallel.moe.book_expert_counts`), then
    :data:`WINDOW_COUNTS`."""
    n = len(_moe.EXPERT_COUNTS)
    _moe.book_expert_counts(model, counts[:n])
    for family, value in zip(_M_WINDOW, counts[n:]):
        family.labels(model).inc(int(value))


def _prefill_tiles(cfg, tokens):
    """:data:`WINDOW_COUNTS` of one prompt of ``tokens``: known when
    the program is built."""
    walked, masked, causal = band_tiles(
        tokens, cfg["head_dim"], cfg["sliding_window_size"])
    layers = cfg["layer_windows"].count(True)
    return [layers * walked, layers * masked, layers * causal]


# ----------------------------------------------------------------------
# the model's entry points


def forward(params, tokens, cfg, length=None):
    """One prompt ``tokens`` int32 ``[T]``: ``(hidden [T, d] before the
    output norm, k_rows, v_rows [global layers then window layers, T,
    Hkv * D], counts)``.  Positions ``>= length`` are the bucket's pad:
    they are routed to no expert."""
    t = tokens.shape[0]
    positions = jnp.arange(t, dtype=jnp.int32)
    valid = None if length is None else positions < length
    x = params["embed_weight"][tokens]
    k_rows, v_rows, counts = [], [], []
    for i in range(cfg["num_hidden_layers"]):
        x, k, v, count = _layer(
            params, i, x, lambda h, i=i: _attention_prefill(
                params, i, h, positions, cfg), cfg, valid)
        k_rows.append(k)
        v_rows.append(v)
        counts.append(count)
    counts = jnp.concatenate([
        _lm._sum_counts(counts),
        jnp.asarray(_prefill_tiles(cfg, t), jnp.int32)])
    return x, _by_group(cfg, k_rows), _by_group(cfg, v_rows), counts


def prefill(params, tokens, length, cfg):
    """``(logits float32 [V] after token length - 1, k_rows, v_rows,
    counts)``: one program a bucket, whatever the prompt's real length;
    only one row of logits is computed."""
    x, k_rows, v_rows, counts = forward(params, tokens, cfg, length)
    logits = _lm._head(
        params, jax.lax.dynamic_slice_in_dim(x, length - 1, 1), cfg)
    return logits[0], k_rows, v_rows, counts


def full_logits(params, tokens, cfg):
    """float32 logits ``[B, T, V]`` of ``tokens`` ``[B, T]``, no cache:
    the classifier-lane protocol and the tests' full forward."""
    return jnp.stack([_lm._head(params, forward(params, row, cfg)[0], cfg)
                      for row in tokens])


def decode_step(params, tokens, positions, k_pages, v_pages, block_tables,
                context_lens, cfg):
    """One token for each of ``B`` sequences through the paged pools,
    read as of before the step (the caller writes the returned rows
    behind this program).  ``k_pages``/``v_pages``: ``(global, window)``,
    each ``[the group's layers, its blocks, block_size, Hkv * D]`` (one
    array where the model's layers are all of one kind);
    ``block_tables`` ``int32 [B, global table + ring]``, the two groups'
    tables side by side (:func:`table_widths`).  Returns ``(logits [B,
    V], k_rows, v_rows [global layers then window layers, B, Hkv * D],
    counts)``."""
    if not isinstance(k_pages, (tuple, list)):
        k_pages, v_pages = (k_pages,), (v_pages,)
    width, _ = table_widths(cfg, k_pages[0].shape[2])
    kinds = sorted(set(cfg["layer_windows"]))       # global first
    # a kind's pools flat over its layers, its columns of the table, its
    # blocks a layer: every layer gathers from the whole pool through
    # tables offset to its blocks (a slice k_pages[i] is a copy)
    tables = {False: block_tables[:, :width], True: block_tables[:, width:]}
    group = {kind: (k.reshape((-1,) + k.shape[2:]),
                    v.reshape((-1,) + v.shape[2:]), tables[kind], k.shape[1])
             for kind, k, v in zip(kinds, k_pages, v_pages)}
    seen = dict.fromkeys(kinds, 0)
    x = params["embed_weight"][tokens]
    k_rows, v_rows, counts = [], [], []
    for i, kind in enumerate(cfg["layer_windows"]):
        k_pool, v_pool, tables, blocks = group[kind]
        x, k, v, count = _layer(
            params, i, x, lambda h, i=i, at=seen[kind]: _attention_decode(
                params, i, h, positions, k_pool, v_pool,
                tables + at * blocks, context_lens, cfg), cfg)
        seen[kind] += 1
        k_rows.append(k)
        v_rows.append(v)
        counts.append(count)
    counts = jnp.concatenate([
        _lm._sum_counts(counts),
        jnp.zeros(len(WINDOW_COUNTS), jnp.int32)])
    return _lm._head(params, x, cfg), _by_group(cfg, k_rows), \
        _by_group(cfg, v_rows), counts


def lm_definition(cfg, dtype=jnp.bfloat16):
    """This model as :class:`~mxnet_tpu.serving.LMBackend` serves it:
    key and value pools of ``Hkv * D``-wide rows in the ``dtype`` the
    parameters are stored in, in two layer groups, the global layers'
    and the window layers' (``num_blocks`` is then a pair)."""
    return LMDefinition(
        cfg=cfg,
        forward=lambda params, tokens: full_logits(params, tokens, cfg),
        prefill=lambda params, tokens, length: prefill(
            params, tokens, length, cfg),
        decode=lambda params, tokens, positions, k_pages, v_pages, tables,
        lens: decode_step(params, tokens, positions, k_pages, v_pages,
                          tables, lens, cfg),
        cache_row=CacheRow(
            "kv", cfg["num_key_value_heads"] * cfg["head_dim"],
            np.dtype(dtype), 2),
        book=book, prepare=None, cache_layers=cfg["num_hidden_layers"],
        cache_groups=cache_groups(cfg))
