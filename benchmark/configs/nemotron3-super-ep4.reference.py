"""Plain reference of the ``nemotron3-super-ep4`` configuration.

The language model of NVIDIA-Nemotron-3-Super (``nemotron_h``) written
straight from its equations in ``jax.numpy``: float32 with every product
at ``HIGHEST`` precision, the Mamba-2 recurrence as a plain ``lax.scan``
over the tokens (one decay and one outer product of the state a token),
the short convolution as four shifted products, the attention over the
whole sequence in blocks of rows, a loop over the experts, no cache, no
state pool, no chunking, no kernel.  It imports nothing of the program
and takes nothing the program made: the weights are the benchmark's own
(``benchmark/models/state_space_moe.py`` makes them from the seed) under
the names of the configuration's family.

The equations, layer ``i`` of ``num_hidden_layers``, its kind the
``i``-th character of ``hybrid_override_pattern``:

- ``x += f_i(N(x))``; ``N(x) = x / sqrt(mean(x^2) + eps) * w``; a final
  ``N`` and an untied head.  No bias but the convolution's.
- ``M`` (Mamba-2): ``[z | x | B | C] = W_in h``, ``dt = W_dt h``; ``[x |
  B | C]`` through a causal depthwise convolution of ``conv_kernel``
  taps, its bias and SiLU; per head ``i`` of group ``g = i // (H / G)``:
  ``D_t = softplus(dt + dt_bias)``, ``a = exp(-D_t exp(A_log))``, ``S <-
  a S + D_t x (x) B_g``, ``y = S C_g + D x``; ``W_out(w * RMSNorm over
  each group's channels of (y * SiLU(z)))``.
- ``*`` (attention): ``q = W_q h`` (heads of ``head_dim``), ``k``, ``v``
  (key-value heads), **no position**; causal ``softmax(q k^T /
  sqrt(head_dim)) v``, a key-value head serving ``heads / kv_heads``
  neighbouring query heads; ``W_o``.
- ``E`` (experts in a latent): ``s = sigmoid(W_r h)`` over all the
  published experts, the ``num_experts_per_tok`` largest of ``s +
  bias``, gates ``s / sum s`` over the chosen times
  ``routed_scaling_factor``; ``u = W_down h``; expert ``e``: ``W2_e
  relu(W1_e u)^2``; ``W_up(sum of the gated experts) + W2s relu(W1s
  h)^2``.

Departures from the published model, as the configuration's file lists
them:

- no multi-token-prediction module: a draft head the main model's
  logits do not depend on;
- the checkpoint's ``in_proj`` is held as ``in_weight`` (rows ``[z | x |
  B | C]``) and ``dt_weight`` (its last ``mamba_num_heads`` rows): a
  split of the rows of a matrix that is random here;
- **the share of a 4-chip deployment**: the router scores all
  ``deployment.experts.published`` (512) experts and chooses 22, and the
  sum over the chosen runs over those of ids ``first .. first + held``
  only (0-127).  What the other 384 would add is left out, here as in
  the program, and that partial result goes on to the next layer.  The
  embedding and the head hold the configuration's slice of the
  vocabulary.

So that a 17,408-wide sequence fits beside 9.3 GB of bfloat16 weights,
a weight is taken to float32 where it is used, the held experts are
added up one at a time (a ``lax.scan`` whose carry is the sum) and the
attention runs over blocks of 128 rows.

``mode`` selects the arithmetic.  ``float32`` is the reference; the
lower ones exist for the control of "How correct is decided":

    float32   float32 storage, products at HIGHEST
    bfloat16  bfloat16 storage and products (float32 accumulation); the
              router, the softmax, the norm statistics, the step, the
              decay and the recurrent state in float32: what the
              configuration states
    float8    bfloat16 storage; both operands of every product rounded
              to float8_e4m3fn first, those of the recurrence's products
              with its state too (one precision under the stated)

``lost_at`` is the other control: a position at which every state-space
layer's state (the recurrence's and the convolution's rows) is set to
zero before the token there is taken, as a server does that loses a
prompt's state between its prefill and its first decode step.
"""

import jax
import jax.numpy as jnp

MODES = ("float32", "bfloat16", "float8")
ROW_BLOCK = 128


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


def _rms_norm(x, gain, eps, store):
    x = x.astype(jnp.float32)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(store)


def layer_kind(cfg, i):
    return cfg["hybrid_override_pattern"][i]


# ----------------------------------------------------------------------
# attention without positions


def _attention(cfg, w, h, ar):
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, store = cfg["head_dim"], ar.store
    t = h.shape[0]
    q = ar.dot("tc,fc->tf", h, w["q_weight"]).reshape(t, heads, dim)
    k = ar.dot("tc,fc->tf", h, w["k_weight"]).reshape(t, groups, dim)
    v = ar.dot("tc,fc->tf", h, w["v_weight"]).reshape(t, groups, dim)
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
# Mamba-2


def selective_scan(x, dt, a_rate, b, c, d_skip, rnd=None, state=None,
                   lost_at=None):
    """The recurrence, a token at a time.  ``x`` ``[T, H, P]``, ``dt``
    ``[T, H]`` (the step, after its softplus), ``a_rate``/``d_skip``
    ``[H]`` (``A < 0``), ``b``/``c`` ``[T, G, N]``, all float32;
    ``state`` ``[H, P, N]`` carried in (zeros if None); ``rnd`` rounds
    the operands of the products with the state; the state is zeroed
    before token ``lost_at``.  Returns ``(y [T, H, P], state)``."""
    rnd = rnd or (lambda a: a)
    hi = jax.lax.Precision.HIGHEST
    t, heads, p = x.shape
    groups, n = b.shape[1:]
    per = heads // groups

    def token(s, v):
        x_t, dt_t, b_t, c_t, at = v
        if lost_at is not None:
            s = jnp.where(at == lost_at, 0.0, s)
        b_h = jnp.repeat(b_t, per, axis=0)                  # [H, N]
        c_h = jnp.repeat(c_t, per, axis=0)
        s = s * jnp.exp(dt_t * a_rate)[:, None, None] \
            + jnp.einsum("hp,hn->hpn", rnd(dt_t[:, None] * x_t), rnd(b_h),
                         precision=hi)
        y = jnp.einsum("hpn,hn->hp", rnd(s), rnd(c_h), precision=hi)
        return s, y + d_skip[:, None] * x_t

    if state is None:
        state = jnp.zeros((heads, p, n), jnp.float32)
    state, y = jax.lax.scan(token, state, (x, dt, b, c, jnp.arange(t)))
    return y, state


def _mamba(cfg, w, h, ar, lost_at=None):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, bc = heads * p, groups * n
    taps, store = cfg["conv_kernel"], ar.store
    t = h.shape[0]
    # z and [x | B | C] as two products of in_weight's rows: one product
    # would hold 1.3 GB of float32 for a 17,408-wide sequence
    z = ar.dot("tc,fc->tf", h, w["in_weight"][:inner])
    into = ar.dot("tc,fc->tf", h, w["in_weight"][inner:])
    dt = ar.dot("tc,fc->tf", h, w["dt_weight"], keep_float32=True)

    def convolve(rows):
        padded = jnp.pad(rows, ((taps - 1, 0), (0, 0)))
        return sum(ar.rnd(padded[j:j + t]).astype(jnp.float32)
                   * ar.rnd(w["conv_weight"][:, j]).astype(jnp.float32)
                   for j in range(taps))

    conv = convolve(into)
    if lost_at is not None:     # the rows before it are gone
        after = jnp.arange(t)[:, None] >= lost_at
        conv = jnp.where(after, convolve(jnp.where(after, into, 0)), conv)
    xbc = jax.nn.silu(conv + w["conv_bias"].astype(jnp.float32)
                      ).astype(store).astype(jnp.float32)
    x = xbc[:, :inner].reshape(t, heads, p)
    b = xbc[:, inner:inner + bc].reshape(t, groups, n)
    c = xbc[:, inner + bc:].reshape(t, groups, n)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))
    y, _ = selective_scan(
        x, dt, -jnp.exp(w["A_log"].astype(jnp.float32)), b, c,
        w["D"].astype(jnp.float32), ar.state_rnd, lost_at=lost_at)
    gated = y.astype(store).astype(jnp.float32).reshape(t, inner) \
        * jax.nn.silu(z.astype(jnp.float32))
    by_group = gated.reshape(t, groups, inner // groups)
    by_group = by_group / jnp.sqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True)
        + cfg["norm_eps"])
    normed = by_group.reshape(t, inner) \
        * w["ssm_norm_gamma"].astype(jnp.float32)
    return ar.dot("tc,fc->tf", normed.astype(store), w["out_weight"])


# ----------------------------------------------------------------------
# experts in a latent


def _relu2(ar, h, up, down, spec_in="tc,fc->tf", spec_out="tf,cf->tc"):
    a = jnp.square(jax.nn.relu(ar.dot(spec_in, h, up, keep_float32=True)))
    return ar.dot(spec_out, a.astype(ar.store), down)


def route(cfg, router_logits, bias):
    """``(chosen [T, k], gates [T, k])`` over all the published experts:
    sigmoid scores in float32, the ``k`` largest of score plus bias
    (``n_group`` 1: no groups), the scores at the chosen divided by
    their sum, times ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    chosen = jnp.argsort(-(s + bias.astype(jnp.float32)),
                         axis=-1)[:, :cfg["num_experts_per_tok"]]
    gates = jnp.take_along_axis(s, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return chosen, gates * cfg["routed_scaling_factor"]


def _expert_layer(cfg, w, h, ar):
    """The shared expert on the full width + the chosen experts that are
    held here, in the latent and projected back."""
    logits = jnp.einsum("tc,ec->te", h.astype(jnp.float32),
                        w["router_weight"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    chosen, gates = route(cfg, logits, w["router_bias"])
    first = cfg["deployment"]["experts"]["first"]
    u = ar.dot("tc,fc->tf", h, w["latent_down_weight"])

    def add_one(total, e_w):
        e, up_w, down_w = e_w
        gate = jnp.where(chosen == first + e, gates, 0.0).sum(-1)
        y = _relu2(ar, u, up_w, down_w, "tc,cf->tf", "tf,fc->tc")
        return total + (y.astype(jnp.float32) * gate.astype(
            ar.store).astype(jnp.float32)[:, None]), None

    held = w["experts_up_weight"].shape[0]
    routed, _ = jax.lax.scan(
        add_one, jnp.zeros(u.shape, jnp.float32),
        (jnp.arange(held), w["experts_up_weight"], w["experts_down_weight"]))
    routed = ar.dot("tc,fc->tf", routed.astype(ar.store),
                    w["latent_up_weight"])
    shared = _relu2(ar, h, w["shared_up_weight"], w["shared_down_weight"])
    return (routed.astype(jnp.float32)
            + shared.astype(jnp.float32)).astype(ar.store)


def _layer_weights(params, i):
    prefix = "l%d_" % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden(cfg, params, tokens, mode="float32", lost_at=None):
    """Final-norm activations ``[T, d]`` of ``tokens`` ``[T]``."""
    ar = _Math(mode)
    x = params["embed_weight"][tokens].astype(ar.store)
    for i in range(cfg["num_hidden_layers"]):
        w = _layer_weights(params, i)
        h = _rms_norm(x, w["norm_gamma"], cfg["norm_eps"], ar.store)
        kind = layer_kind(cfg, i)
        if kind == "M":
            x = x + _mamba(cfg, w, h, ar, lost_at)
        elif kind == "*":
            x = x + _attention(cfg, w, h, ar)
        else:
            x = x + _expert_layer(cfg, w, h, ar)
    return _rms_norm(x, params["final_norm_gamma"], cfg["norm_eps"],
                     ar.store)


def logits(cfg, params, tokens, mode="float32", lost_at=None):
    """float32 logits ``[B, T, V]`` of ``tokens`` int32 ``[B, T]``."""
    ar = _Math(mode)
    return jnp.stack([
        ar.dot("tc,vc->tv", hidden(cfg, params, row, mode, lost_at),
               params["pred_weight"], keep_float32=True)
        for row in tokens])
