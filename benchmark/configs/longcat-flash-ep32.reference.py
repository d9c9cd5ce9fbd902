"""Plain reference of the ``longcat-flash-ep32`` configuration.

The language model of LongCat-Flash-Omni (LongCat-Flash's block) written
straight from its equations in ``jax.numpy``: float32 with every product
at ``HIGHEST`` precision, the attention in its expanded form over the
whole sequence, a loop over the experts, no cache, no kernel, no
batching trick.  It imports nothing of the program and takes nothing the
program made: the weights are the benchmark's own
(``benchmark/models/shortcut_latent_moe.py`` makes them from the seed)
under the names of the configuration's family.

A layer (``N`` RMSNorm, ``MLA_s`` and ``FFN_s`` the layer's two latent
attentions and two dense SwiGLUs, each with its own weights)::

    a1 = x  + MLA_0(N(x))
    h  = N(a1)
    m  = MoE(h)                      # the shortcut branch
    b1 = a1 + FFN_0(h)
    a2 = b1 + MLA_1(N(b1))
    y  = a2 + FFN_1(N(a2)) + m

``MLA(u)``: ``c_q = N(W_qa u)``; ``q = alpha_q W_qb c_q`` per head
``[q_nope | q_rope]``; ``[c_kv | k_r] = W_kva u``; ``c = alpha_kv
N(c_kv)``; per head ``[k_nope | v] = W_kvb c``; plain rotary (the
configuration has no ``rope_scaling``) on ``q_rope`` and on ``k_r``,
which every head shares and no factor scales; scores ``q.k / sqrt(nope
+ rope)``, causal softmax in float32; out through ``W_o``.  ``alpha_q =
sqrt(hidden / q_lora_rank)``, ``alpha_kv = sqrt(hidden /
kv_lora_rank)`` (``mla_scale_q_lora``, ``mla_scale_kv_lora``).

``MoE(h)``: ``s = softmax(W_r h)`` in float32 over the real and the
identity experts together; the ``moe_topk`` largest of ``s + b`` are
chosen (``b`` the selection bias, for the choice only; no groups); ``g_e
= routed_scaling_factor * s_e``; a chosen real expert adds ``g_e
SwiGLU_e(h)``, a chosen identity expert (an id of the published
``n_routed_experts`` or more) adds ``g_e h``.  No shared expert.

Departures from the published model, as the configuration's file lists
them:

- no audio or vision encoder and no codec decoder: the catalog's row
  gives the language model's sizes alone; token ids in;
- the gates are not renormalised over the chosen: the published
  ``config.json`` has no ``norm_topk_prob`` (listed under ``assumed``);
- **the share of a 32-chip deployment**: the router scores all
  ``deployment.experts.published`` (512) real and ``zero_expert_num``
  (256) identity experts and chooses 12; the sum over the chosen real
  experts runs over those of ids ``first .. first + held`` only (0-15)
  and what the other 496 would add is left out, here as in the program;
  **the identity experts' term is computed in full** (every chip of the
  deployment computes it whole for its own tokens).  That partial result
  goes on to the next layer.  The embedding and the head hold the
  configuration's slice of the vocabulary.

So that an 8192-wide sequence fits beside 10.35 GB of bfloat16 weights,
a weight is taken to float32 where it is used (an expert, or a block of
a dense feed-forward's width, at a time inside ``lax.scan``), and the
attention runs over blocks of heads and rows.

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
HEAD_BLOCK, ROW_BLOCK, WIDTH_BLOCK = 8, 1024, 2048
SUBLAYERS = 2


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


def _rms_norm(x, gain, eps, store, factor=1.0):
    x = x.astype(jnp.float32)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (factor * y * gain.astype(jnp.float32)).astype(store)


# ----------------------------------------------------------------------
# rotary positions: plain (no rope_scaling in the published config)


def rotary(cfg, length):
    """cos, sin ``[length, rope]`` (frequencies repeated over the two
    halves) and the softmax scale."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    inv_freq = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freqs = jnp.outer(jnp.arange(length, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb), \
        (cfg["qk_nope_head_dim"] + dim) ** -0.5


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


def lora_factors(cfg):
    """``(alpha_q, alpha_kv)``: ``sqrt(hidden / rank)`` where the
    configuration switches the factor on, else 1."""
    d = cfg["hidden_size"]
    return (math.sqrt(d / cfg["q_lora_rank"])
            if cfg.get("mla_scale_q_lora") else 1.0,
            math.sqrt(d / cfg["kv_lora_rank"])
            if cfg.get("mla_scale_kv_lora") else 1.0)


# ----------------------------------------------------------------------
# layers


def _attention(cfg, w, u, ar, rope):
    """``MLA(u)`` of one sublayer's weights ``w``, ``u`` already
    normed."""
    cos, sin, scale = rope
    heads, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rank, vdim = cfg["kv_lora_rank"], cfg["v_head_dim"]
    eps, store = cfg["rms_norm_eps"], ar.store
    alpha_q, alpha_kv = lora_factors(cfg)
    t = u.shape[0]
    c_q = _rms_norm(ar.dot("tc,fc->tf", u, w["q_a_weight"]),
                    w["q_a_norm_gamma"], eps, store)
    q = (alpha_q * ar.dot("tc,fc->tf", c_q, w["q_b_weight"],
                          keep_float32=True)).astype(store)
    q = q.reshape(t, heads, -1)
    kv_a = ar.dot("tc,fc->tf", u, w["kv_a_weight"])
    c_kv = _rms_norm(kv_a[:, :rank], w["kv_a_norm_gamma"], eps, store,
                     alpha_kv)
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


def _swiglu(ar, h, gate, up, down):
    """``W_down(silu(W_gate h) * W_up h)``, float32 out; ``gate``/``up``
    ``[in, f]`` and ``down`` ``[f, in]`` (an expert's layout)."""
    a = jax.nn.silu(ar.dot("tc,cf->tf", h, gate, keep_float32=True)) \
        * ar.dot("tc,cf->tf", h, up, keep_float32=True)
    return ar.dot("tf,fc->tc", a.astype(ar.store), down, keep_float32=True)


def _dense(ar, h, gate, up, down):
    """A dense SwiGLU with checkpoint-style ``[out, in]`` weights, a
    block of its width at a time (the blocks' down-projections add
    up)."""
    wide = gate.shape[0]
    blk = WIDTH_BLOCK if wide % WIDTH_BLOCK == 0 else wide

    def one(total, w):
        g, u, d = w
        return total + _swiglu(ar, h, g.T, u.T, d.T), None

    total, _ = jax.lax.scan(
        one, jnp.zeros(h.shape, jnp.float32),
        (gate.reshape(wide // blk, blk, -1), up.reshape(wide // blk, blk, -1),
         down.T.reshape(wide // blk, blk, -1).transpose(0, 2, 1)))
    return total.astype(ar.store)


def route(cfg, scores_logits, bias):
    """``(chosen [T, k], gates [T, k])`` over all the router's outputs:
    softmax scores; the choice on score + bias; gates the scores without
    the bias, scaled, not renormalised."""
    s = jax.nn.softmax(scores_logits.astype(jnp.float32), axis=-1)
    choice = s + bias.astype(jnp.float32)
    chosen = jnp.argsort(-choice, axis=-1)[:, :cfg["moe_topk"]]
    gates = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, gates * cfg["routed_scaling_factor"]


def _expert_layer(cfg, w, h, ar):
    """``MoE(h)``: the chosen real experts that are held here, and the
    chosen identity experts whole."""
    logits = jnp.einsum("tc,ec->te", h.astype(jnp.float32),
                        w["router_weight"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    chosen, gates = route(cfg, logits, w["router_bias"])
    share = cfg["deployment"]["experts"]

    def one(total, e_w):
        e, gate_w, up_w, down_w = e_w
        gate = jnp.where(chosen == share["first"] + e, gates, 0.0).sum(-1)
        y = _swiglu(ar, h, gate_w, up_w, down_w).astype(ar.store)
        return total + y.astype(jnp.float32) \
            * gate.astype(ar.store).astype(jnp.float32)[:, None], None

    held = w["experts_gate_weight"].shape[0]
    total, _ = jax.lax.scan(
        one, jnp.zeros(h.shape, jnp.float32),
        (jnp.arange(held), w["experts_gate_weight"], w["experts_up_weight"],
         w["experts_down_weight"]))
    same = jnp.where(chosen >= share["published"], gates, 0.0).sum(-1)
    total = total + same[:, None] * h.astype(jnp.float32)
    return total.astype(ar.store)


def _weights(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def layer(cfg, params, i, x, ar, rope):
    """One shortcut-connected layer; also returns the branch ``m``."""
    eps = cfg["rms_norm_eps"]
    w = _weights(params, "l%d_" % i)
    sub = [_weights(params, "l%d_s%d_" % (i, s)) for s in range(SUBLAYERS)]

    def mla(s, u):
        return _attention(cfg, sub[s], _rms_norm(
            u, sub[s]["attn_norm_gamma"], eps, ar.store), ar, rope)

    def ffn(s, u):
        return _dense(ar, u, sub[s]["ffn_gate_weight"],
                      sub[s]["ffn_up_weight"], sub[s]["ffn_down_weight"])

    a1 = x + mla(0, x)
    h = _rms_norm(a1, sub[0]["ffn_norm_gamma"], eps, ar.store)
    m = _expert_layer(cfg, w, h, ar)
    b1 = a1 + ffn(0, h)
    a2 = b1 + mla(1, b1)
    y = a2 + ffn(1, _rms_norm(a2, sub[1]["ffn_norm_gamma"], eps,
                              ar.store)) + m
    return y, m


def hidden(cfg, params, tokens, mode="float32"):
    """Final-norm activations ``[T, d]`` of ``tokens`` ``[T]``."""
    ar = _Math(mode)
    x = params["embed_weight"][tokens].astype(ar.store)
    rope = rotary(cfg, tokens.shape[0])
    for i in range(cfg["num_layers"]):
        x, _ = layer(cfg, params, i, x, ar, rope)
    return _rms_norm(x, params["final_norm_gamma"], cfg["rms_norm_eps"],
                     ar.store)


def logits(cfg, params, tokens, mode="float32"):
    """float32 logits ``[B, T, V]`` of ``tokens`` int32 ``[B, T]``."""
    ar = _Math(mode)
    return jnp.stack([
        ar.dot("tc,vc->tv", hidden(cfg, params, row, mode),
               params["pred_weight"], keep_float32=True)
        for row in tokens])
