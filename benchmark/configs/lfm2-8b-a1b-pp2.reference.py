"""Plain reference of the ``lfm2-8b-a1b-pp2`` configuration.

The language model of LFM2-8B-A1B written straight from its equations in
``jax.numpy``: float32 with every product at ``HIGHEST`` precision, the
short convolution as three shifted products over the whole sequence,
grouped-query attention as the plain softmax over the whole sequence in
blocks of rows, a loop over the experts, no cache, no state pool, no
kernel.  It imports nothing of the program and takes nothing the program
made: the weights are the benchmark's own
(``benchmark/models/short_conv_moe.py`` makes them from the seed) under
the names of the configuration's family.

The equations, layer ``i`` of ``num_hidden_layers`` (``N(x) = x /
sqrt(mean(x^2) + norm_eps) * w``; no bias anywhere):

- ``x += Mixer_i(N_op(x)); x += FFN_i(N_ffn(x))``; the mixer is the one
  ``layer_types[i]`` names.
- ``conv``: ``[B | C | X] = W_in h``; ``u = B * X``; ``v_t = sum_j w[:,
  j] * u_(t - (L - 1) + j)`` over the ``L = conv_L_cache`` taps
  (depthwise, causal, ``u`` before the prompt 0); ``W_out (C * v)``.
- ``full_attention``: ``q = W_q h`` as ``num_attention_heads`` heads,
  ``k = W_k h``, ``v = W_v h`` as ``num_key_value_heads`` heads of
  ``hidden_size / num_attention_heads``; ``q`` and ``k`` through ``N``
  over the head; rotary over the whole head (the halves paired, ``x cos
  + rotate_half(x) sin``, ``theta = rope_theta``); causal ``softmax(q
  k^T / sqrt(head)) v``, a key-value head serving ``heads / kv_heads``
  neighbouring query heads; ``W_o``.
- Feed-forward, layers below ``num_dense_layers``: ``W_2(silu(W_1 h) *
  W_3 h)``.  The others: ``s = sigmoid(W_r h)`` in float32 over all the
  experts, the ``num_experts_per_tok`` largest of ``s + expert_bias``,
  gates ``s`` at the chosen (without the bias) over their sum ``+
  1e-6``, times ``routed_scaling_factor``; the gated sum of the chosen
  experts' SwiGLUs.  No token is dropped; no shared expert.
- After the last layer ``N_out`` (``embedding_norm``), and logits over
  the embedding's rows (tied).

Departures from the published model, as the configuration's file lists
them:

- **the first of two pipeline stages**: layers ``0 .. num_hidden_layers
  - 1`` of the published 24 (``layer_types`` is the published list, of
  which the first ``num_hidden_layers`` entries are read), every expert,
  the whole vocabulary.  The ten layers left out and the head lie on the
  second stage; the head's matrix is the embedding's, which is resident
  here, so the logits are computed from this stage's last layer, here as
  in the program;
- ``W_in``'s rows are ``[B | C | X]``, each part whole (a permutation of
  the rows of a matrix that is random here);
- ``deployment.experts`` says which experts are held (all 32): the sum
  over a token's chosen experts runs over the held ones.

So that an 8192-wide sequence fits in what 9.3 GB of bfloat16 weights
leave of a chip, a weight is taken to float32 where it is used, the
held experts are added up one at a time (a ``lax.scan`` whose carry is
the sum), the attention runs over blocks of 256 rows and the logits over
blocks of 1024.

``mode`` selects the arithmetic.  ``float32`` is the reference; the
lower ones exist for the control of "How correct is decided":

    float32   float32 storage, products at HIGHEST
    bfloat16  bfloat16 storage and products (float32 accumulation); the
              router, the softmax, the norm statistics and the
              convolution's sum in float32: what the configuration
              states
    float8    bfloat16 storage; both operands of every product rounded
              to float8_e4m3fn first, the convolution's too (one
              precision under the stated)
"""

import jax
import jax.numpy as jnp

MODES = ("float32", "bfloat16", "float8")
ROW_BLOCK = 256
LOGIT_BLOCK = 1024
GATE_SUM_EPS = 1e-6


def _arith(mode):
    """(storage dtype, operand rounding, product precision) of a mode."""
    if mode == "float32":
        return jnp.float32, (lambda a: a.astype(jnp.float32)), \
            jax.lax.Precision.HIGHEST
    if mode == "bfloat16":
        return jnp.bfloat16, (lambda a: a.astype(jnp.bfloat16)), None
    if mode == "float8":
        return jnp.bfloat16, (lambda a: a.astype(jnp.float8_e4m3fn).astype(
            jnp.bfloat16)), None
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


def layer_kinds(cfg):
    """The mixers of the layers that are here: the first
    ``num_hidden_layers`` of the published list."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


# ----------------------------------------------------------------------
# the gated short convolution


def short_conv(u, w, rnd=None):
    """``v_t = sum_j w[:, j] * u_(t - (L - 1) + j)``: ``u`` ``[T, d]``,
    ``w`` ``[d, L]``, float32 out; ``rnd`` rounds the operands."""
    rnd = rnd or (lambda a: a)
    taps, t = w.shape[1], u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return sum(rnd(padded[j:j + t]).astype(jnp.float32)
               * rnd(w[:, j]).astype(jnp.float32) for j in range(taps))


def _conv_mixer(cfg, w, x, ar):
    d = cfg["hidden_size"]
    h = _rms_norm(x, w["operator_norm_gamma"], cfg["norm_eps"], ar.store)
    bcx = ar.dot("tc,fc->tf", h, w["conv_in_weight"])
    b, c, gated = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    v = short_conv(b * gated, w["conv_weight"], ar.rnd)
    y = (c.astype(jnp.float32) * v).astype(ar.store)
    return ar.dot("tc,fc->tf", y, w["conv_out_weight"])


# ----------------------------------------------------------------------
# grouped-query attention


def _rotary(x, cfg):
    """``x [T, H, D]`` turned over the whole head: ``x cos +
    rotate_half(x) sin``."""
    t, dim = x.shape[0], x.shape[-1]
    inv = 1.0 / float(cfg["rope_theta"]) ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freqs = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    x = x.astype(jnp.float32)
    half = dim // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + turned * jnp.sin(emb)


def _attention(cfg, w, x, ar):
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps, store = cfg["hidden_size"] // heads, cfg["norm_eps"], ar.store
    t = x.shape[0]
    h = _rms_norm(x, w["operator_norm_gamma"], eps, store)
    q = ar.dot("tc,fc->tf", h, w["q_weight"]).reshape(t, heads, dim)
    k = ar.dot("tc,fc->tf", h, w["k_weight"]).reshape(t, groups, dim)
    v = ar.dot("tc,fc->tf", h, w["v_weight"]).reshape(t, groups, dim)
    q = _rotary(_rms_norm(q, w["q_norm_gamma"], eps, store),
                cfg).astype(store)
    k = _rotary(_rms_norm(k, w["k_norm_gamma"], eps, store),
                cfg).astype(store)
    per = heads // groups
    k = jnp.repeat(k, per, axis=1).transpose(1, 0, 2)       # [H, T, D]
    v = jnp.repeat(v, per, axis=1).transpose(1, 0, 2)
    rows = jnp.arange(t)
    scale = dim ** -0.5

    def row_block(block):
        qb, at = block                        # [H, rb, D], [rb]
        s = ar.dot("hqd,hkd->hqk", qb, k, keep_float32=True) * scale
        s = jnp.where(at[None, :, None] >= rows[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return ar.dot("hqk,hkd->hqd", p.astype(store), v)

    rb = ROW_BLOCK if t % ROW_BLOCK == 0 else t
    blocks = q.transpose(1, 0, 2).reshape(heads, t // rb, rb, dim)
    o = jax.lax.map(row_block, (blocks.transpose(1, 0, 2, 3),
                                rows.reshape(t // rb, rb)))
    o = o.transpose(1, 0, 2, 3).reshape(heads, t, dim)
    o = o.transpose(1, 0, 2).reshape(t, heads * dim)
    return ar.dot("tc,fc->tf", o, w["o_weight"])


# ----------------------------------------------------------------------
# feed-forward: dense, then experts


def _swiglu(ar, h, gate, up, down, spec_in="tc,fc->tf", spec_out="tf,cf->tc"):
    a = jax.nn.silu(ar.dot(spec_in, h, gate, keep_float32=True)) \
        * ar.dot(spec_in, h, up, keep_float32=True)
    return ar.dot(spec_out, a.astype(ar.store), down)


def route(cfg, router_logits, bias):
    """``(chosen [T, k], gates [T, k])``: sigmoid scores in float32, the
    ``k`` largest of score plus bias (the lower id first among equals),
    the scores at the chosen over their sum plus 1e-6, times
    ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    choice = s + bias.astype(jnp.float32)
    chosen = jnp.argsort(-choice, axis=-1,
                         stable=True)[:, :cfg["num_experts_per_tok"]]
    gates = jnp.take_along_axis(s, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + GATE_SUM_EPS)
    return chosen, gates * cfg["routed_scaling_factor"]


def _expert_layer(cfg, w, x, ar):
    """The chosen experts that are held here, added up one at a time."""
    h = _rms_norm(x, w["ffn_norm_gamma"], cfg["norm_eps"], ar.store)
    logits = jnp.einsum("tc,ec->te", h.astype(jnp.float32),
                        w["router_weight"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    chosen, gates = route(cfg, logits, w["expert_bias"])
    first = cfg["deployment"]["experts"]["first"]

    def add_one(total, e_w):
        e, gate_w, up_w, down_w = e_w
        gate = jnp.where(chosen == first + e, gates, 0.0).sum(-1)
        y = _swiglu(ar, h, gate_w, up_w, down_w, "tc,cf->tf", "tf,fc->tc")
        return total + (y.astype(jnp.float32) * gate.astype(
            ar.store).astype(jnp.float32)[:, None]), None

    held = w["experts_gate_weight"].shape[0]
    routed, _ = jax.lax.scan(
        add_one, jnp.zeros(h.shape, jnp.float32),
        (jnp.arange(held), w["experts_gate_weight"], w["experts_up_weight"],
         w["experts_down_weight"]))
    return routed.astype(ar.store)


def _feed_forward(cfg, w, i, x, ar):
    if i >= cfg["num_dense_layers"]:
        return _expert_layer(cfg, w, x, ar)
    h = _rms_norm(x, w["ffn_norm_gamma"], cfg["norm_eps"], ar.store)
    return _swiglu(ar, h, w["ffn_gate_weight"], w["ffn_up_weight"],
                   w["ffn_down_weight"])


def _layer_weights(params, i):
    prefix = "l%d_" % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden(cfg, params, tokens, mode="float32"):
    """Output-norm activations ``[T, d]`` of ``tokens`` ``[T]``."""
    ar = _Math(mode)
    x = params["embed_weight"][tokens].astype(ar.store)
    for i, kind in enumerate(layer_kinds(cfg)):
        w = _layer_weights(params, i)
        mixer = _conv_mixer if kind == "conv" else _attention
        x = x + mixer(cfg, w, x, ar)
        x = x + _feed_forward(cfg, w, i, x, ar)
    return _rms_norm(x, params["embedding_norm_gamma"], cfg["norm_eps"],
                     ar.store)


def logits(cfg, params, tokens, mode="float32"):
    """float32 logits ``[B, T, V]`` of ``tokens`` int32 ``[B, T]``, over
    the embedding's rows, in blocks of rows of the sequence."""
    ar = _Math(mode)

    def one(row):
        h = hidden(cfg, params, row, mode)
        t = h.shape[0]
        lb = LOGIT_BLOCK if t % LOGIT_BLOCK == 0 else t
        out = jax.lax.map(
            lambda block: ar.dot("tc,vc->tv", block, params["embed_weight"],
                                 keep_float32=True),
            h.reshape(t // lb, lb, -1))
        return out.reshape(t, -1)

    return jnp.stack([one(row) for row in tokens])
