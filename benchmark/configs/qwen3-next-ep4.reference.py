"""Plain reference of the ``qwen3-next-ep4`` configuration.

The language model of Qwen3-Next written straight from its equations in
``jax.numpy``: float32 with every product at ``HIGHEST`` precision, the
Gated DeltaNet recurrence as a plain ``lax.scan`` over the tokens (one
rank-one update of the state a token), the short convolution as four
shifted products, the gated grouped-query attention over the whole
sequence in blocks of rows, a loop over the experts, no cache, no state
pool, no chunking, no kernel.  It imports nothing of the program and
takes nothing the program made: the weights are the benchmark's own
(``benchmark/models/gated_delta_moe.py`` makes them from the seed) under
the names of the configuration's family.

The equations, layer ``i`` of ``num_hidden_layers``:

- ``x += Mixer_i(N(x)); x += MoE(N(x))``; ``N(x) = x / sqrt(mean(x^2) +
  eps) * (1 + w)``; full attention where ``(i + 1) %
  full_attention_interval == 0``, else Gated DeltaNet; a final ``N`` and
  an untied head.
- Full attention: per head ``[q | gate] = W_q h``, ``k = W_k h``, ``v =
  W_v h``; ``q`` and ``k`` through ``N`` over the head; rotary on the
  first ``partial_rotary_factor`` of the head (the halves of that slice
  paired); causal ``softmax(q k^T / sqrt(head_dim)) v``, a key-value
  head serving ``heads / kv_heads`` neighbouring query heads; ``W_o
  (attn * sigmoid(gate))``.
- Gated DeltaNet: ``[q | k | v | z] = W_qkvz h``, ``[b | a] = W_ba h``;
  ``[q | k | v]`` through a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps and SiLU; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; ``q``, ``k`` L2-normalised over
  the head, ``q`` times ``dk^-1/2``, a key head serving ``value heads /
  key heads`` neighbouring value heads; per value head ``S <- exp(g) S;
  d = beta (v - S^T k); S <- S + k d^T; o = S^T q``; ``W_out(RMSNorm(o)
  w * SiLU(z))``.
- Expert layer: ``p = softmax(W_r h)`` over all the published experts,
  the ``num_experts_per_tok`` largest, gates ``p / sum p`` over the
  chosen; SwiGLU experts; plus ``sigmoid(w_sg h) * SwiGLU(h)``.

Departures from the published model, as the configuration's file lists
them:

- no multi-token-prediction module: a draft head the main model's
  logits do not depend on;
- ``W_qkvz``'s rows are ``[q | k | v | z]`` and ``W_ba``'s ``[b | a]``,
  each part whole; the published checkpoint interleaves them by key
  head (a permutation of the rows of a matrix that is random here);
- **the share of a 4-chip deployment**: the router scores all
  ``deployment.experts.published`` (512) experts and chooses 10, and the
  sum over the chosen runs over those of ids ``first .. first + held``
  only (0-127).  What the other 384 would add is left out, here as in
  the program, and that partial result goes on to the next layer.  The
  embedding and the head hold the configuration's slice of the
  vocabulary.

So that an 8192-wide sequence fits in the 3 GB that 7.3 GB of bfloat16
weights and the served program's pools leave of a chip, a weight is
taken to float32 where it is used, the held experts are added up one at
a time (a ``lax.scan`` whose carry is the sum) and the attention runs
over blocks of 256 rows.

``mode`` selects the arithmetic.  ``float32`` is the reference; the
lower ones exist for the control of "How correct is decided":

    float32   float32 storage, products at HIGHEST
    bfloat16  bfloat16 storage and products (float32 accumulation); the
              router, the softmax, the norm statistics, the decay and
              the recurrent state in float32: what the configuration
              states
    float8    bfloat16 storage; both operands of every product rounded
              to float8_e4m3fn first, those of the recurrence's products
              with its state too (one precision under the stated)
"""

import jax
import jax.numpy as jnp

MODES = ("float32", "bfloat16", "float8")
ROW_BLOCK = 256


def _arith(mode):
    """(storage dtype, operand rounding, product precision, rounding of
    the recurrence's operands) of a mode."""
    def keep(a):
        return a.astype(jnp.float32)

    if mode == "float32":
        return jnp.float32, keep, jax.lax.Precision.HIGHEST, keep
    if mode == "bfloat16":
        return jnp.bfloat16, (lambda a: a.astype(jnp.bfloat16)), None, keep
    if mode == "float8":
        def down(a):
            return a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

        return jnp.bfloat16, down, None, lambda a: down(a).astype(
            jnp.float32)
    raise ValueError("unknown mode %r (one of %s)" % (mode, ", ".join(MODES)))


class _Math(object):
    def __init__(self, mode):
        self.store, self.rnd, self.prec, self.state_rnd = _arith(mode)

    def dot(self, spec, a, b, keep_float32=False):
        out = jnp.einsum(spec, self.rnd(a), self.rnd(b), precision=self.prec,
                         preferred_element_type=jnp.float32)
        return out if keep_float32 else out.astype(self.store)


def _rms_norm(x, gain, eps, store, offset=1.0):
    x = x.astype(jnp.float32)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * (offset + gain.astype(jnp.float32))).astype(store)


def layer_kind(cfg, i):
    return "full" if (i + 1) % cfg["full_attention_interval"] == 0 \
        else "linear"


# ----------------------------------------------------------------------
# gated full attention


def _rotary(x, cfg):
    """``x [T, H, D]``: the first ``partial_rotary_factor`` of ``D``
    turned, ``x cos + rotate_half(x) sin`` on that slice."""
    t, dim = x.shape[0], x.shape[-1]
    rot = int(dim * cfg["partial_rotary_factor"])
    inv = 1.0 / float(cfg["rope_theta"]) ** (
        jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    freqs = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    part = x[..., :rot].astype(jnp.float32)
    half = rot // 2
    turned = jnp.concatenate([-part[..., half:], part[..., :half]], axis=-1)
    part = part * jnp.cos(emb) + turned * jnp.sin(emb)
    return jnp.concatenate([part, x[..., rot:].astype(jnp.float32)],
                           axis=-1)


def _attention(cfg, w, x, ar):
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps, store = cfg["head_dim"], cfg["rms_norm_eps"], ar.store
    t = x.shape[0]
    h = _rms_norm(x, w["mixer_norm_gamma"], eps, store)
    q = ar.dot("tc,fc->tf", h, w["q_weight"]).reshape(t, heads, 2 * dim)
    gate = q[..., dim:].reshape(t, heads * dim)
    k = ar.dot("tc,fc->tf", h, w["k_weight"]).reshape(t, groups, dim)
    v = ar.dot("tc,fc->tf", h, w["v_weight"]).reshape(t, groups, dim)
    q = _rotary(_rms_norm(q[..., :dim], w["q_norm_gamma"], eps, store),
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
    o = (o.astype(jnp.float32)
         * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(store)
    return ar.dot("tc,fc->tf", o, w["o_weight"])


# ----------------------------------------------------------------------
# Gated DeltaNet


def delta_rule(q, k, v, g, beta, rnd=None, state=None):
    """The recurrence, a token at a time.  ``q``/``k`` ``[T, H, dk]``,
    ``v`` ``[T, H, dv]``, ``g``/``beta`` ``[T, H]``, all float32;
    ``state`` ``[H, dk, dv]`` carried in (zeros if None); ``rnd`` rounds
    the operands of the products with the state.  Returns ``(o [T, H,
    dv], state)``."""
    rnd = rnd or (lambda a: a)
    hi = jax.lax.Precision.HIGHEST

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = s * jnp.exp(g_t)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", rnd(s), rnd(k_t), precision=hi)
        delta = beta_t[:, None] * (v_t - seen)
        s = s + jnp.einsum("hk,hv->hkv", rnd(k_t), rnd(delta), precision=hi)
        return s, jnp.einsum("hkv,hk->hv", rnd(s), rnd(q_t), precision=hi)

    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    return o, state


def _delta_net(cfg, w, x, ar):
    kheads, vheads = cfg["linear_num_key_heads"], \
        cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key, value = kheads * dk, vheads * dv
    taps, eps, store = cfg["linear_conv_kernel_dim"], cfg["rms_norm_eps"], \
        ar.store
    t = x.shape[0]
    h = _rms_norm(x, w["mixer_norm_gamma"], eps, store)
    mixed = ar.dot("tc,fc->tf", h, w["qkvz_weight"])
    ba = ar.dot("tc,fc->tf", h, w["ba_weight"], keep_float32=True)
    into, z = mixed[:, :2 * key + value], mixed[:, 2 * key + value:]
    beta = jax.nn.sigmoid(ba[:, :vheads])
    g = -jnp.exp(w["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, vheads:] + w["dt_bias"].astype(jnp.float32))
    padded = jnp.pad(into, ((taps - 1, 0), (0, 0)))
    conv = sum(ar.rnd(padded[j:j + t]).astype(jnp.float32)
               * ar.rnd(w["conv_weight"][:, j]).astype(jnp.float32)
               for j in range(taps))
    conv = jax.nn.silu(conv).astype(store)

    def unit(part):
        part = part.astype(jnp.float32).reshape(t, kheads, dk)
        part = part / jnp.sqrt(jnp.sum(part * part, axis=-1, keepdims=True)
                               + 1e-6)
        return jnp.repeat(part, vheads // kheads, axis=1)

    q = unit(conv[:, :key]) * dk ** -0.5
    k = unit(conv[:, key:2 * key])
    v = conv[:, 2 * key:].astype(jnp.float32).reshape(t, vheads, dv)
    o, _ = delta_rule(q, k, v, g, beta, ar.state_rnd)
    o = _rms_norm(o, w["gdn_norm_gamma"], eps, jnp.float32, offset=0.0) \
        * jax.nn.silu(z.astype(jnp.float32).reshape(t, vheads, dv))
    return ar.dot("tc,fc->tf", o.reshape(t, value).astype(store),
                  w["out_weight"])


# ----------------------------------------------------------------------
# experts


def _swiglu(ar, h, gate, up, down, spec_in="tc,fc->tf", spec_out="tf,cf->tc"):
    a = jax.nn.silu(ar.dot(spec_in, h, gate, keep_float32=True)) \
        * ar.dot(spec_in, h, up, keep_float32=True)
    return ar.dot(spec_out, a.astype(ar.store), down)


def route(cfg, router_logits):
    """``(chosen [T, k], gates [T, k])`` over all the published experts:
    softmax in float32, the ``k`` largest, their probabilities divided
    by their sum."""
    p = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    chosen = jnp.argsort(-p, axis=-1)[:, :cfg["num_experts_per_tok"]]
    gates = jnp.take_along_axis(p, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    return chosen, gates


def _expert_layer(cfg, w, x, ar):
    """The shared expert behind its gate + the chosen experts that are
    held here."""
    h = _rms_norm(x, w["ffn_norm_gamma"], cfg["rms_norm_eps"], ar.store)
    logits = jnp.einsum("tc,ec->te", h.astype(jnp.float32),
                        w["router_weight"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    chosen, gates = route(cfg, logits)
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
    shared = _swiglu(ar, h, w["shared_gate_weight"], w["shared_up_weight"],
                     w["shared_down_weight"]).astype(jnp.float32)
    shared = shared * jax.nn.sigmoid(ar.dot(
        "tc,fc->tf", h, w["shared_expert_gate_weight"], keep_float32=True))
    return (routed + shared).astype(ar.store)


def _layer_weights(params, i):
    prefix = "l%d_" % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden(cfg, params, tokens, mode="float32"):
    """Final-norm activations ``[T, d]`` of ``tokens`` ``[T]``."""
    ar = _Math(mode)
    x = params["embed_weight"][tokens].astype(ar.store)
    for i in range(cfg["num_hidden_layers"]):
        w = _layer_weights(params, i)
        mixer = _attention if layer_kind(cfg, i) == "full" else _delta_net
        x = x + mixer(cfg, w, x, ar)
        x = x + _expert_layer(cfg, w, x, ar)
    return _rms_norm(x, params["final_norm_gamma"], cfg["rms_norm_eps"],
                     ar.store)


def logits(cfg, params, tokens, mode="float32"):
    """float32 logits ``[B, T, V]`` of ``tokens`` int32 ``[B, T]``."""
    ar = _Math(mode)
    return jnp.stack([
        ar.dot("tc,vc->tv", hidden(cfg, params, row, mode),
               params["pred_weight"], keep_float32=True)
        for row in tokens])
