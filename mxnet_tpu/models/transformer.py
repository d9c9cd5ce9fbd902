"""Decoder-only transformer language model — the long-context flagship of the
capability layer (the 2017 reference has no attention models; SURVEY.md §2.4
lists sequence/context parallelism as a required capability gap).

Pre-norm GPT-style blocks over ``MultiHeadAttention`` (Pallas flash attention
on-chip; ring attention across a mesh ``seq`` axis when
``context_parallel_axis='seq'``).  Same Module/fit contract as the rest of the
model zoo: inputs ``data`` (batch, seq_len) int tokens and ``softmax_label``
(batch, seq_len); single ``SoftmaxOutput`` head named ``softmax``.
"""

import contextlib

from .. import symbol as sym
from ..attribute import AttrScope


def get_symbol(num_classes=32000, seq_len=1024, num_embed=512, num_heads=8,
               num_layers=6, dropout=0.0, causal=True,
               context_parallel_axis="", dtype="float32", head="softmax",
               ce_chunk=2048, remat="none", ffn="dense", num_experts=8,
               moe_top_k=1, moe_aux_scale=0.01, **kwargs):
    """``ffn='moe'`` swaps every block's dense FFN for a ``MoELayer``
    (``num_experts`` experts of the same 4x hidden, top-``moe_top_k``
    routing); the per-layer load-balancing losses sum into one
    ``MakeLoss`` output scaled by ``moe_aux_scale``, grouped after the
    LM head (ShardedTrainer sums all loss-op outputs).  On a mesh with
    an ``expert`` axis the experts shard over it; on one chip the same
    graph runs dense (routing + capacity + dispatch still execute —
    ``tools/bench_table.py``'s single-chip MoE row)."""
    if ffn not in ("dense", "moe"):
        raise ValueError("ffn must be 'dense' or 'moe', got %r" % (ffn,))
    aux_losses = []
    data = sym.Variable("data")
    x = sym.Embedding(data=data, input_dim=num_classes, output_dim=num_embed,
                      name="embed")
    pos = sym.Variable("pos_embed_weight", shape=(1, seq_len, num_embed))
    x = sym.broadcast_add(x, pos)
    if dtype != "float32":
        x = sym.Cast(x, dtype=dtype)

    if remat not in ("none", "block"):
        raise ValueError("remat must be 'none' or 'block', got %r" % (remat,))
    for i in range(num_layers):
        # remat='block': each layer becomes one __remat__ checkpoint
        # region (executor._remat_plan) — activations inside the block are
        # recomputed in backward, so live memory is one residual stream
        # per layer instead of every intermediate (the graph-executor
        # mirror option, reference graph_executor.cc:225-233)
        scope = (AttrScope(__remat__="l%d" % i) if remat == "block"
                 else contextlib.nullcontext())
        with scope:
            h = sym.LayerNorm(x, name="l%d_ln1" % i)
            h = sym.MultiHeadAttention(
                h, num_heads=num_heads, causal=causal,
                context_parallel_axis=context_parallel_axis,
                name="l%d_attn" % i)
            if dropout > 0:
                h = sym.Dropout(h, p=dropout, name="l%d_attndrop" % i)
            x = x + h
            h = sym.LayerNorm(x, name="l%d_ln2" % i)
            if ffn == "moe":
                m = sym.MoELayer(h, num_experts=num_experts,
                                 hidden_size=4 * num_embed,
                                 top_k=moe_top_k, name="l%d_moe" % i)
                h = m[0]
                aux_losses.append(m[1])
            else:
                h = sym.FullyConnected(h, num_hidden=4 * num_embed,
                                       flatten=False, name="l%d_ffn1" % i)
                h = sym.Activation(h, act_type="gelu", name="l%d_gelu" % i)
                h = sym.FullyConnected(h, num_hidden=num_embed,
                                       flatten=False, name="l%d_ffn2" % i)
            if dropout > 0:
                h = sym.Dropout(h, p=dropout, name="l%d_ffndrop" % i)
            x = x + h

    x = sym.LayerNorm(x, name="final_ln")
    pred = sym.Reshape(x, shape=(-1, num_embed))
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    if head not in ("softmax", "fused_ce"):
        raise ValueError("head must be 'softmax' or 'fused_ce', got %r"
                         % (head,))
    def with_aux(head_sym):
        if not aux_losses:
            return head_sym
        total = aux_losses[0]
        for a in aux_losses[1:]:
            total = total + a
        return sym.Group([head_sym,
                          sym.MakeLoss(total * moe_aux_scale,
                                       name="moe_aux")])

    if head == "fused_ce":
        # long-context head: chunked fused linear + softmax CE — never
        # materializes the [T, vocab] logits (O(chunk*V) live instead of
        # O(T*V)); output is per-token fp32 loss, which ShardedTrainer's
        # sum-of-outputs loss consumes directly.  Reuses the FC weight
        # layout (pred_weight [V, d]) so checkpoints swap between heads
        # (the softmax head's pred_bias has no fused counterpart).
        pred_w = sym.Variable("pred_weight",
                              shape=(num_classes, num_embed))
        return with_aux(sym._contrib_fused_lm_head(
            pred, pred_w, label, name="softmax", chunk=ce_chunk))
    # vocab projection in the model dtype (the largest matmul in the
    # model — in bf16 it runs at full MXU rate with fp32 accumulation);
    # logits cast up AFTER, so softmax/loss run in fp32
    pred = sym.FullyConnected(pred, num_hidden=num_classes, name="pred")
    if dtype != "float32":
        pred = sym.Cast(pred, dtype="float32")
    return with_aux(sym.SoftmaxOutput(data=pred, label=label, name="softmax"))


# ----------------------------------------------------------------------
# functional LM path: prefill + single-token decode for the generation
# lane (serving/generation.py)
# ----------------------------------------------------------------------
#
# The Symbol graph above trains the model; serving generation needs two
# *inference* entry points the executor does not offer: a prefill that
# returns every layer's K/V for the paged cache, and a single-token step
# that reads K/V back through a block table.  Both are plain functions
# over a params dict keyed by the SAME checkpoint names ``get_symbol``
# produces (``embed_weight``, ``l0_ln1_gamma``, ``l0_attn_qkv_weight``,
# ``pred_weight``, ...), so a trained ``save_checkpoint`` arg dict drops
# straight in.
#
# Off the chip every op is drawn from the shape-stable set in
# ``ops/attention.py`` (mul-reduce scores, elementwise fp32 softmax,
# ``einsum("btc,fc->btf")`` projections, minor-axis layernorm): the bits
# of token ``t``'s logits are the same whether computed in a T-row
# prefill, a full-sequence forward, or a 1-row decode step — the
# KV-cache correctness gate in tests/test_generation.py.  On a TPU the
# two attentions take their kernels where the shape allows
# (``stable_causal_attention``, ``paged_decode_attention``: each says
# its rule in its own body) and the contract is float32 rounding.

import numpy as np
import jax.numpy as jnp
from jax import nn as jnn

from ..ops.attention import stable_causal_attention
from ..ops.paged_attention import paged_decode_attention

_LN_EPS = 1e-5


def lm_config(num_classes=128, seq_len=64, num_embed=32, num_heads=4,
              num_layers=2):
    """Config dict shared by :func:`init_lm_params` / :func:`lm_prefill`
    / :func:`lm_decode_step`; mirrors :func:`get_symbol`'s signature."""
    if num_embed % num_heads:
        raise ValueError("num_embed %d not divisible by num_heads %d"
                         % (num_embed, num_heads))
    return {"num_classes": num_classes, "seq_len": seq_len,
            "num_embed": num_embed, "num_heads": num_heads,
            "num_layers": num_layers}


def init_lm_params(cfg, seed=0, scale=0.02):
    """Random fp32 params under the ``get_symbol`` checkpoint name
    scheme (numpy, so they serialize like any other arg dict)."""
    rng = np.random.RandomState(seed)
    c, v, t = cfg["num_embed"], cfg["num_classes"], cfg["seq_len"]

    def w(*shape):
        return (rng.randn(*shape) * scale).astype(np.float32)

    params = {"embed_weight": w(v, c), "pos_embed_weight": w(1, t, c),
              "final_ln_gamma": np.ones(c, np.float32),
              "final_ln_beta": np.zeros(c, np.float32),
              "pred_weight": w(v, c), "pred_bias": np.zeros(v, np.float32)}
    for i in range(cfg["num_layers"]):
        params.update({
            "l%d_ln1_gamma" % i: np.ones(c, np.float32),
            "l%d_ln1_beta" % i: np.zeros(c, np.float32),
            "l%d_ln2_gamma" % i: np.ones(c, np.float32),
            "l%d_ln2_beta" % i: np.zeros(c, np.float32),
            "l%d_attn_qkv_weight" % i: w(3 * c, c),
            "l%d_attn_out_weight" % i: w(c, c),
            "l%d_ffn1_weight" % i: w(4 * c, c),
            "l%d_ffn1_bias" % i: np.zeros(4 * c, np.float32),
            "l%d_ffn2_weight" % i: w(c, 4 * c),
            "l%d_ffn2_bias" % i: np.zeros(c, np.float32),
        })
    return params


def _lm_ln(x, gamma, beta):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + _LN_EPS)
    return y * gamma + beta


def _lm_qkv(x, qkv_weight, cfg):
    """Fused QKV projection of [B, T, C] → q, k, v each [B, H, T, D]."""
    b, t, c = x.shape
    h = cfg["num_heads"]
    d = c // h
    qkv = jnp.einsum("btc,fc->btf", x, qkv_weight)
    qkv = qkv.reshape(b, t, 3, h, d).transpose(2, 0, 3, 1, 4)
    return qkv[0], qkv[1], qkv[2]


def _lm_ffn(x, i, params):
    h = jnp.einsum("btc,fc->btf", x, params["l%d_ffn1_weight" % i])
    h = jnn.gelu(h + params["l%d_ffn1_bias" % i])
    h = jnp.einsum("btc,fc->btf", h, params["l%d_ffn2_weight" % i])
    return h + params["l%d_ffn2_bias" % i]


def _lm_logits(x, params, int8_head=False):
    """Vocab projection.  ``int8_head`` reads the quantized grid staged
    by :func:`quantize_lm_head` — int8 weights dequantized on the fly
    (the storage/bandwidth win), fp32 accumulate, shared scale."""
    if int8_head:
        wq = params["pred_weight_q"].astype(jnp.float32)
        return (jnp.einsum("btc,fc->btf", x, wq) * params["pred_scale"]
                + params["pred_bias"])
    return (jnp.einsum("btc,fc->btf", x, params["pred_weight"])
            + params["pred_bias"])


def lm_prefill(params, tokens, cfg, int8_head=False):
    """Full-sequence forward of ``tokens`` int32 ``[B, T]``.

    Returns ``(logits [B, T, V], k [L, B, T, H, D], v [L, B, T, H, D])``
    — K/V in cache page layout, ready for ``PagedKVCache.write_prefill``
    (per sequence: ``k[:, b]`` with its real length; the write drops
    the pad positions on the device).  This is also the lane's
    "naive" full forward: the parity gate compares its row ``t`` logits
    against decode step ``t``.
    """
    t = tokens.shape[1]
    x = params["embed_weight"][tokens] + params["pos_embed_weight"][:, :t]
    x = x.astype(jnp.float32)
    ks, vs = [], []
    for i in range(cfg["num_layers"]):
        h = _lm_ln(x, params["l%d_ln1_gamma" % i], params["l%d_ln1_beta" % i])
        q, k, v = _lm_qkv(h, params["l%d_attn_qkv_weight" % i], cfg)
        a = stable_causal_attention(q, k, v)
        b, heads, tt, d = a.shape
        a = a.transpose(0, 2, 1, 3).reshape(b, tt, heads * d)
        x = x + jnp.einsum("btc,fc->btf", a,
                           params["l%d_attn_out_weight" % i])
        h = _lm_ln(x, params["l%d_ln2_gamma" % i], params["l%d_ln2_beta" % i])
        x = x + _lm_ffn(h, i, params)
        ks.append(k.transpose(0, 2, 1, 3))   # [B, T, H, D] page layout
        vs.append(v.transpose(0, 2, 1, 3))
    x = _lm_ln(x, params["final_ln_gamma"], params["final_ln_beta"])
    return _lm_logits(x, params, int8_head), jnp.stack(ks), jnp.stack(vs)


def lm_decode_step(params, tokens, positions, k_pages, v_pages,
                   block_tables, context_lens, cfg, int8_head=False):
    """One decode step for a batch of sequences through the paged cache.

    ``tokens``/``positions`` int32 ``[B]`` (position = context_len - 1);
    ``k_pages``/``v_pages`` ``[L, num_blocks, block_size, H * D]`` as
    :class:`~mxnet_tpu.ops.kv_cache.PagedKVCache` holds them (``[L,
    num_blocks, block_size, H, D]`` is read the same);
    ``block_tables`` int32 ``[B, max_blocks]``; ``context_lens`` int32
    ``[B]`` counting the current token.  Returns ``(logits [B, V],
    k_step [L, B, H, D], v_step [L, B, H, D])``.  The pool is read as
    of before the step and not written here: the caller hands
    ``k_step``/``v_step``, still on the device, to
    ``PagedKVCache.write_tokens`` in a dispatch of its own
    (:meth:`~mxnet_tpu.serving.LMBackend.decode` does, right behind
    this one), so a dropped dispatch touches no sequence's blocks and a
    repeated one stores the same rows again.
    """
    x = (params["embed_weight"][tokens]
         + params["pos_embed_weight"][0][positions])[:, None, :]
    x = x.astype(jnp.float32)
    # every layer gathers from the whole pool through tables offset to
    # its blocks: a per-layer slice ``k_pages[i]`` is materialised on a
    # TPU, a copy of the layer's pool a layer a step
    heads, num_blocks = cfg["num_heads"], k_pages.shape[1]
    pool = (-1, k_pages.shape[2], heads, cfg["num_embed"] // heads)
    k_pool, v_pool = k_pages.reshape(pool), v_pages.reshape(pool)
    ks, vs = [], []
    for i in range(cfg["num_layers"]):
        h = _lm_ln(x, params["l%d_ln1_gamma" % i], params["l%d_ln1_beta" % i])
        q, k, v = _lm_qkv(h, params["l%d_attn_qkv_weight" % i], cfg)
        k1, v1 = k[:, :, 0], v[:, :, 0]      # [B, H, D]
        a = paged_decode_attention(
            q[:, :, 0], k1, v1, k_pool, v_pool,
            block_tables + i * num_blocks, context_lens)
        b, heads, d = a.shape
        a = a.reshape(b, 1, heads * d)
        x = x + jnp.einsum("btc,fc->btf", a,
                           params["l%d_attn_out_weight" % i])
        h = _lm_ln(x, params["l%d_ln2_gamma" % i], params["l%d_ln2_beta" % i])
        x = x + _lm_ffn(h, i, params)
        ks.append(k1)
        vs.append(v1)
    x = _lm_ln(x, params["final_ln_gamma"], params["final_ln_beta"])
    logits = _lm_logits(x, params, int8_head)
    return logits[:, 0], jnp.stack(ks), jnp.stack(vs)


def lm_definition(cfg, int8_head=False):
    """This model as :class:`~mxnet_tpu.serving.LMBackend` serves it:
    float32 key and value rows of ``num_embed`` values, the programs of
    :func:`lm_prefill` and :func:`lm_decode_step`."""
    from ..ops.kv_cache import CacheRow
    from .lm import LMDefinition

    def page_rows(kv):
        # K/V of one dispatch, [L, 1, T, H, D] (prefill) or [L, B, H, D]
        # (decode), as the cache's rows [L, N, H * D]: done inside the
        # dispatch, so that it emits them as they will be written (a
        # [.., H, 64] output gets a device layout the write re-lays)
        return kv.reshape(kv.shape[0], -1, kv.shape[-2] * kv.shape[-1])

    def prefill(params, tokens, length):
        # ``length`` is traced: one program per bucket, whatever the
        # prompt's real length, and only row ``length - 1`` of the
        # logits leaves the device
        logits, k, v = lm_prefill(params, tokens[None], cfg)
        return logits[0, length - 1], page_rows(k), page_rows(v), None

    def decode(params, tokens, positions, k_pages, v_pages, block_tables,
               context_lens):
        logits, k, v = lm_decode_step(
            params, tokens, positions, k_pages, v_pages, block_tables,
            context_lens, cfg, int8_head=int8_head)
        return logits, page_rows(k), page_rows(v), None

    return LMDefinition(
        cfg=dict(cfg),
        forward=lambda params, tokens: lm_prefill(params, tokens, cfg)[0],
        prefill=prefill, decode=decode,
        cache_row=CacheRow("kv", cfg["num_embed"], np.float32, 2),
        book=None, prepare=quantize_lm_head if int8_head else None)


def quantize_lm_head(params):
    """Opt-in int8 vocab head: stage ``pred_weight`` on the
    ``contrib.quantization`` symmetric int8/127 grid.

    Returns a new params dict with ``pred_weight_q`` (int8) and
    ``pred_scale`` added; ``lm_prefill``/``lm_decode_step`` read them
    when called with ``int8_head=True``.  The fp32 ``pred_weight`` stays
    for the parity gate — int8 logits are approximate by construction
    and excluded from the bitwise contract.
    """
    from ..contrib.quantization import quantize_weight_int8
    wq, scale = quantize_weight_int8(params["pred_weight"])
    out = dict(params)
    out["pred_weight_q"] = wq
    out["pred_scale"] = scale
    return out
