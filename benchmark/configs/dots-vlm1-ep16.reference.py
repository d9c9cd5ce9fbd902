"""Plain reference of the ``dots-vlm1-ep16`` configuration.

The language model of dots.vlm1 (the DeepSeek-V3 block) written straight
from its equations in ``jax.numpy``: float32 with every product at
``HIGHEST`` precision, the attention in its expanded form over the whole
sequence, a loop over the experts, no cache, no kernel, no batching
trick.  It imports nothing of the program and takes nothing the program
made: the weights are the benchmark's own
(``benchmark/models/latent_moe.py`` makes them from the seed) under the
names of the configuration's family.

Departures from the published model, as the configuration's file lists
them:

- no vision tower: the catalog gives no size of the NaViT encoder, so
  its equations cannot be written; the model is text-only, token ids in;
- no multi-token-prediction module (``num_nextn_predict_layers`` 0): a
  draft head the main model's logits do not depend on;
- **the share of a 16-chip deployment**: the router scores all
  ``deployment.experts.published`` (256) experts and chooses 8, and the
  sum over the chosen runs over those of ids ``first .. first + held``
  only (0-15).  What the other 240 would add is left out, here as in the
  program, and that partial result goes on to the next layer.  The
  embedding and the head hold the configuration's slice of the
  vocabulary.

So that a 4096-wide sequence fits beside 11 GB of bfloat16 weights, a
weight is taken to float32 where it is used (an expert at a time inside
``lax.map``) and the attention runs over blocks of heads and rows.

``mode`` selects the arithmetic.  ``float32`` is the reference; the
lower ones exist for the control of "How correct is decided":

    float32   float32 storage, products at HIGHEST
    bfloat16  bfloat16 storage and products (float32 accumulation); the
              router, the softmax and the norm statistics in float32:
              what the configuration states
    float8    bfloat16 storage; both operands of every product rounded
              to float8_e4m3fn first (one precision under the stated)
"""

import math

import jax
import jax.numpy as jnp

MODES = ("float32", "bfloat16", "float8")
HEAD_BLOCK, ROW_BLOCK = 8, 1024


def _arith(mode):
    """(storage dtype, operand rounding, product precision) of a mode."""
    if mode == "float32":
        return jnp.float32, (lambda a: a.astype(jnp.float32)), \
            jax.lax.Precision.HIGHEST
    if mode == "bfloat16":
        return jnp.bfloat16, (lambda a: a.astype(jnp.bfloat16)), None
    if mode == "float8":
        return (jnp.bfloat16,
                lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16),
                None)
    raise ValueError("unknown mode %r (one of %s)" % (mode, ", ".join(MODES)))


class _Math(object):
    def __init__(self, mode):
        self.store, self.rnd, self.prec = _arith(mode)

    def dot(self, spec, a, b, keep_float32=False):
        out = jnp.einsum(spec, self.rnd(a), self.rnd(b), precision=self.prec,
                         preferred_element_type=jnp.float32)
        return out if keep_float32 else out.astype(self.store)


def _rms_norm(x, gain, eps, store):
    x = x.astype(jnp.float32)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(store)


# ----------------------------------------------------------------------
# rotary positions: YaRN, as the published modeling code computes them


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(cfg, length):
    """cos, sin ``[length, rope]`` (frequencies repeated over the two
    halves) and the softmax scale."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    factor, orig = sc["factor"], sc["original_max_position_embeddings"]
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    freq_extra = 1.0 / base ** exponent
    freq_inter = 1.0 / (factor * base ** exponent)

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction(sc["beta_fast"])), 0)
    high = min(math.ceil(correction(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    keep = 1.0 - ramp                       # 1 where the frequency stays
    inv_freq = freq_inter * (1 - keep) + freq_extra * keep
    freqs = jnp.outer(jnp.arange(length, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    m = _mscale(factor, sc["mscale"]) / _mscale(factor, sc["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    if sc["mscale_all_dim"]:
        scale = scale * _mscale(factor, sc["mscale_all_dim"]) ** 2
    return jnp.cos(emb) * m, jnp.sin(emb) * m, scale


def _apply_rotary(x, cos, sin):
    """``x [T, ..., rope]``: the pairs (2i, 2i+1) are brought to (i,
    i + rope/2), then ``x cos + rotate_half(x) sin``."""
    shape = x.shape
    x = x.astype(jnp.float32).reshape(shape[:-1] + (shape[-1] // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(shape)
    half = shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    extra = (1,) * (len(shape) - 2)
    cos = cos.reshape((shape[0],) + extra + (shape[-1],))
    sin = sin.reshape((shape[0],) + extra + (shape[-1],))
    return x * cos + turned * sin


# ----------------------------------------------------------------------
# layers


def _attention(cfg, w, x, ar, rope):
    cos, sin, scale = rope
    heads, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rank, vdim = cfg["kv_lora_rank"], cfg["v_head_dim"]
    eps, store = cfg["rms_norm_eps"], ar.store
    t = x.shape[0]
    h = _rms_norm(x, w["attn_norm_gamma"], eps, store)
    c_q = _rms_norm(ar.dot("tc,fc->tf", h, w["q_a_weight"]),
                    w["q_a_norm_gamma"], eps, store)
    q = ar.dot("tc,fc->tf", c_q, w["q_b_weight"]).reshape(t, heads, -1)
    kv_a = ar.dot("tc,fc->tf", h, w["kv_a_weight"])
    c_kv = _rms_norm(kv_a[:, :rank], w["kv_a_norm_gamma"], eps, store)
    k_rope = _apply_rotary(kv_a[:, rank:], cos, sin).astype(store)
    q_rope = _apply_rotary(q[..., nope:], cos, sin).astype(store)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    w_kv = w["kv_b_weight"].reshape(heads, nope + vdim, rank)
    rows = jnp.arange(t)

    def head_block(block):
        w_blk, q_blk = block                  # [hb, nope+v, rank], [hb, T, .]
        kv = ar.dot("tc,hdc->htd", c_kv, w_blk)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope[None], (kv.shape[0],) + k_rope.shape)], axis=-1)
        v = kv[..., nope:]

        def row_block(q_rows):
            qb, at = q_rows                   # [hb, rb, .], [rb]
            s = ar.dot("hqd,hkd->hqk", qb, k, keep_float32=True) * scale
            s = jnp.where(at[None, :, None] >= rows[None, None, :], s,
                          -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return ar.dot("hqk,hkd->hqd", p.astype(store), v)

        rb = ROW_BLOCK if t % ROW_BLOCK == 0 else t
        blocks = q_blk.reshape(q_blk.shape[0], t // rb, rb, -1)
        out = jax.lax.map(row_block, (blocks.transpose(1, 0, 2, 3),
                                      rows.reshape(t // rb, rb)))
        return out.transpose(1, 0, 2, 3).reshape(q_blk.shape[0], t, vdim)

    hb = min(HEAD_BLOCK, heads)
    o = jax.lax.map(head_block, (
        w_kv.reshape(heads // hb, hb, nope + vdim, rank),
        q.transpose(1, 0, 2).reshape(heads // hb, hb, t, -1)))
    o = o.reshape(heads, t, vdim).transpose(1, 0, 2).reshape(t, -1)
    return ar.dot("tc,fc->tf", o, w["o_weight"])


def _swiglu(ar, h, gate, up, down, spec_in="tc,fc->tf", spec_out="tf,cf->tc"):
    a = jax.nn.silu(ar.dot(spec_in, h, gate, keep_float32=True)) \
        * ar.dot(spec_in, h, up, keep_float32=True)
    return ar.dot(spec_out, a.astype(ar.store), down)


def route(cfg, scores_logits, bias):
    """``(chosen [T, k], gates [T, k])`` over all the published experts:
    sigmoid scores; the choice on score + bias, limited to the best
    groups (a group scores the sum of its two largest); gates the scores
    without the bias, normalised and scaled."""
    k, groups = cfg["num_experts_per_tok"], cfg["n_group"]
    s = jax.nn.sigmoid(scores_logits.astype(jnp.float32))
    choice = s + bias.astype(jnp.float32)
    t, e = choice.shape
    by_group = choice.reshape(t, groups, e // groups)
    group_score = jnp.sort(by_group, axis=-1)[..., -2:].sum(-1)
    best = jnp.argsort(-group_score, axis=-1)[:, :cfg["topk_group"]]
    kept = (best[:, :, None] == jnp.arange(groups)[None, None, :]).any(1)
    choice = jnp.where(jnp.repeat(kept, e // groups, axis=1), choice,
                       -jnp.inf)
    chosen = jnp.argsort(-choice, axis=-1)[:, :k]
    gates = jnp.take_along_axis(s, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return chosen, gates * cfg["routed_scaling_factor"]


def _expert_layer(cfg, w, h, ar):
    """Shared expert + the chosen experts that are held here."""
    logits = jnp.einsum("tc,ec->te", h.astype(jnp.float32),
                        w["router_weight"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    chosen, gates = route(cfg, logits, w["router_bias"])
    first = cfg["deployment"]["experts"]["first"]

    def one(e_w):
        e, gate_w, up_w, down_w = e_w
        gate = jnp.where(chosen == first + e, gates, 0.0).sum(-1)
        y = _swiglu(ar, h, gate_w, up_w, down_w, "tc,cf->tf", "tf,fc->tc")
        return (y.astype(jnp.float32)
                * gate.astype(ar.store).astype(jnp.float32)[:, None])

    held = w["experts_gate_weight"].shape[0]
    parts = jax.lax.map(one, (jnp.arange(held), w["experts_gate_weight"],
                              w["experts_up_weight"],
                              w["experts_down_weight"]))
    shared = _swiglu(ar, h, w["shared_gate_weight"], w["shared_up_weight"],
                     w["shared_down_weight"])
    return (parts.sum(0) + shared.astype(jnp.float32)).astype(ar.store)


def _layer_weights(params, i):
    prefix = "l%d_" % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden(cfg, params, tokens, mode="float32"):
    """Final-norm activations ``[T, d]`` of ``tokens`` ``[T]``."""
    ar = _Math(mode)
    eps = cfg["rms_norm_eps"]
    x = params["embed_weight"][tokens].astype(ar.store)
    rope = yarn(cfg, tokens.shape[0])
    for i in range(cfg["num_hidden_layers"]):
        w = _layer_weights(params, i)
        x = x + _attention(cfg, w, x, ar, rope)
        h = _rms_norm(x, w["ffn_norm_gamma"], eps, ar.store)
        if i < cfg["first_k_dense_replace"]:
            x = x + _swiglu(ar, h, w["ffn_gate_weight"], w["ffn_up_weight"],
                            w["ffn_down_weight"])
        else:
            x = x + _expert_layer(cfg, w, h, ar)
    return _rms_norm(x, params["final_norm_gamma"], eps, ar.store)


def logits(cfg, params, tokens, mode="float32"):
    """float32 logits ``[B, T, V]`` of ``tokens`` int32 ``[B, T]``."""
    ar = _Math(mode)
    return jnp.stack([
        ar.dot("tc,vc->tv", hidden(cfg, params, row, mode),
               params["pred_weight"], keep_float32=True)
        for row in tokens])
