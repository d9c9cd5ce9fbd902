"""Plain reference of the ``smallthinker-21b-ep4`` configuration.

The language model of SmallThinker-21BA3B-Instruct written straight
from its equations in ``jax.numpy``: float32 with every product at
``HIGHEST`` precision, attention as the plain softmax over the whole
sequence in blocks of rows under a mask made from positions, a loop
over the experts, no cache, no ring, no kernel.  It imports nothing of
the program and takes nothing the program made: the weights are the
benchmark's own (``benchmark/models/window_moe.py`` makes them from the
seed) under the names of the configuration's family.

The equations, layer ``l`` of ``num_hidden_layers``, token position
``p`` (``N(x) = x / sqrt(mean(x^2) + rms_norm_eps) * w``; no bias
anywhere):

- ``h = N_in(x)``.
- **The router, before the attention**: ``r = W_r h`` over all
  ``moe_num_primary_experts`` experts in float32, the
  ``moe_num_active_primary_experts`` largest are chosen (the lower id
  first among equals), gates = softmax over the chosen logits (the
  softmax over all, ``moe_primary_router_apply_softmax``, renormalised
  over the chosen, ``norm_topk_prob``).
- Attention: ``q = W_q h`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = W_k h``, ``v = W_v h`` as ``num_key_value_heads``
  heads.  Where ``rope_layout[l]`` is 1, rotary over the whole head
  (the halves paired, ``x cos + rotate_half(x) sin``, ``theta =
  rope_theta``, no scaling); where it is 0, nothing: no position enters
  the layer but through the mask.  Scores ``q k / sqrt(head_dim)``; key
  ``j`` is seen if ``j <= p`` and, where ``sliding_window_layout[l]``
  is 1, if also ``p - j < sliding_window_size``.  A key-value head
  serves ``heads / kv_heads`` neighbouring query heads.  ``W_o``; ``x
  += attention``.
- ``h2 = N_post(x)``; ``y = sum over the chosen e of gate_e * W_down^e
  (relu(W_gate^e h2) * W_up^e h2)``: the choice made from ``h``, the
  experts applied to ``h2``.  No token is dropped; ``x += y``.
- After the last layer ``N_out``, and logits over the head's own rows
  (untied).

Departures from the published model, as the configuration's file lists
them:

- **one chip's share of a four-chip host**: layers ``0 ..
  num_hidden_layers - 1`` of the published 52 (both per-layer lists are
  the published ones, of which the first ``num_hidden_layers`` entries
  are read); ``deployment.experts`` says which experts are held (16 of
  64, ids 0-15): the sum over a token's chosen experts runs over the
  held ones, and what the absent 48 would add is left out; the
  vocabulary's slice held here (embedding and head of ``vocab_size``
  rows);
- the router reads ``N_in(x)``; no QK-norm, no projection bias; "sparse
  ReGLU" is the dense ``relu(.) * .``.

So that a 16,384-wide sequence fits in what the weights leave of a
chip, a weight is taken to float32 where it is used, the held experts
are added up one at a time (a ``lax.scan`` whose carry is the sum), the
attention runs over blocks of 256 rows (28 heads x 256 x 16,384 scores
at a time, not 28 x 16,384^2) and the logits over blocks of 1024.

``mode`` selects the arithmetic.  ``float32`` is the reference; the
lower ones exist for the control of "How correct is decided":

    float32   float32 storage, products at HIGHEST
    bfloat16  bfloat16 storage and products (float32 accumulation); the
              router, the softmax and the norm statistics in float32:
              what the configuration states
    float8    bfloat16 storage; both operands of every product rounded
              to float8_e4m3fn first (one precision under the stated)
"""

import jax
import jax.numpy as jnp

MODES = ("float32", "bfloat16", "float8")
ROW_BLOCK = 256
LOGIT_BLOCK = 1024


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


# ----------------------------------------------------------------------
# the router, ahead of the attention


def route(cfg, router_logits):
    """``(chosen [T, k], gates [T, k])``: the ``k`` largest logits (the
    lower id first among equals) and the softmax over them, in
    float32."""
    r = router_logits.astype(jnp.float32)
    chosen = jnp.argsort(-r, axis=-1, stable=True)[
        :, :cfg["moe_num_active_primary_experts"]]
    return chosen, jax.nn.softmax(jnp.take_along_axis(r, chosen, axis=1),
                                  axis=-1)


# ----------------------------------------------------------------------
# attention: global without positions, or a window with rotary


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


def seen(rows, keys, window):
    """The mask: row ``p`` sees key ``j`` if ``j <= p`` and, under a
    ``window``, ``p - j < window``.  ``rows`` ``[R]``, ``keys`` ``[K]``
    positions; bool ``[R, K]``."""
    back = rows[:, None] - keys[None, :]
    mask = back >= 0
    return mask if window is None else mask & (back < window)


def _attention(cfg, w, h, layer, ar):
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, store = cfg["head_dim"], ar.store
    t = h.shape[0]
    q = ar.dot("tc,fc->tf", h, w["q_weight"]).reshape(t, heads, dim)
    k = ar.dot("tc,fc->tf", h, w["k_weight"]).reshape(t, groups, dim)
    v = ar.dot("tc,fc->tf", h, w["v_weight"]).reshape(t, groups, dim)
    if cfg["rope_layout"][layer]:
        q, k = _rotary(q, cfg).astype(store), _rotary(k, cfg).astype(store)
    window = cfg["sliding_window_size"] \
        if cfg["sliding_window_layout"][layer] else None
    per = heads // groups
    k = jnp.repeat(k, per, axis=1).transpose(1, 0, 2)       # [H, T, D]
    v = jnp.repeat(v, per, axis=1).transpose(1, 0, 2)
    keys = jnp.arange(t)
    scale = dim ** -0.5

    def row_block(block):
        qb, at = block                        # [H, rb, D], [rb]
        s = ar.dot("hqd,hkd->hqk", qb, k, keep_float32=True) * scale
        s = jnp.where(seen(at, keys, window)[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return ar.dot("hqk,hkd->hqd", p.astype(store), v)

    rb = ROW_BLOCK if t % ROW_BLOCK == 0 else t
    blocks = q.transpose(1, 0, 2).reshape(heads, t // rb, rb, dim)
    o = jax.lax.map(row_block, (blocks.transpose(1, 0, 2, 3),
                                keys.reshape(t // rb, rb)))
    o = o.transpose(1, 0, 2, 3).reshape(heads, t, dim)
    o = o.transpose(1, 0, 2).reshape(t, heads * dim)
    return ar.dot("tc,fc->tf", o, w["o_weight"])


# ----------------------------------------------------------------------
# the experts: ReGLU, under the choice made before the attention


def _reglu(ar, h, gate, up, down):
    a = jax.nn.relu(ar.dot("tc,cf->tf", h, gate, keep_float32=True)) \
        * ar.dot("tc,cf->tf", h, up, keep_float32=True)
    return ar.dot("tf,fc->tc", a.astype(ar.store), down)


def _expert_layer(cfg, w, x, chosen, gates, ar):
    """The chosen experts that are held here, added up one at a time."""
    h = _rms_norm(x, w["post_norm_gamma"], cfg["rms_norm_eps"], ar.store)
    first = cfg["deployment"]["experts"]["first"]

    def add_one(total, e_w):
        e, gate_w, up_w, down_w = e_w
        gate = jnp.where(chosen == first + e, gates, 0.0).sum(-1)
        y = _reglu(ar, h, gate_w, up_w, down_w)
        return total + (y.astype(jnp.float32) * gate.astype(
            ar.store).astype(jnp.float32)[:, None]), None

    held = w["experts_gate_weight"].shape[0]
    routed, _ = jax.lax.scan(
        add_one, jnp.zeros(h.shape, jnp.float32),
        (jnp.arange(held), w["experts_gate_weight"], w["experts_up_weight"],
         w["experts_down_weight"]))
    return routed.astype(ar.store)


def _layer_weights(params, i):
    prefix = "l%d_" % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden(cfg, params, tokens, mode="float32"):
    """Output-norm activations ``[T, d]`` of ``tokens`` ``[T]``."""
    ar = _Math(mode)
    x = params["embed_weight"][tokens].astype(ar.store)
    for i in range(cfg["num_hidden_layers"]):
        w = _layer_weights(params, i)
        h = _rms_norm(x, w["input_norm_gamma"], cfg["rms_norm_eps"],
                      ar.store)
        chosen, gates = route(cfg, jnp.einsum(
            "tc,ec->te", h.astype(jnp.float32),
            w["router_weight"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        x = x + _attention(cfg, w, h, i, ar)
        x = x + _expert_layer(cfg, w, x, chosen, gates, ar)
    return _rms_norm(x, params["final_norm_gamma"], cfg["rms_norm_eps"],
                     ar.store)


def logits(cfg, params, tokens, mode="float32"):
    """float32 logits ``[B, T, V]`` of ``tokens`` int32 ``[B, T]``, over
    the head's rows, in blocks of rows of the sequence."""
    ar = _Math(mode)

    def one(row):
        h = hidden(cfg, params, row, mode)
        t = h.shape[0]
        lb = LOGIT_BLOCK if t % LOGIT_BLOCK == 0 else t
        out = jax.lax.map(
            lambda block: ar.dot("tc,vc->tv", block, params["pred_weight"],
                                 keep_float32=True),
            h.reshape(t // lb, lb, -1))
        return out.reshape(t, -1)

    return jnp.stack([one(row) for row in tokens])
