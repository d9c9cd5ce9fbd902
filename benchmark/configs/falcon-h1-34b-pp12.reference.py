"""Plain reference of the ``falcon-h1-34b-pp12`` configuration.

The language model of Falcon-H1 (``falcon_h1``) written straight from
its equations in ``jax.numpy``: float32 with every product at
``HIGHEST`` precision, the Mamba-2 recurrence as a plain ``lax.scan``
over the tokens (one decay and one outer product of the state a token),
the short convolution as four shifted products, the attention as a full
masked softmax over the whole sequence (in blocks of rows), no cache, no
state pool, no chunking, no kernel.  It imports nothing of the program
and takes nothing the program made: the weights are the benchmark's own
(``benchmark/models/parallel_hybrid.py`` makes them from the seed) under
the names of the configuration's family.

The equations, every layer alike, ``N`` an RMSNorm with a gain:

- ``x0 = embedding_multiplier E[token]``; ``h = N_in(x)``; ``x' = x +
  ssm_out_multiplier Mamba(h) + attention_out_multiplier
  Attn(attention_in_multiplier h)``; ``x'' = x' + MLP(N_ff(x'))``;
  ``logits = lm_head_multiplier W_head N_final(x)``.  No bias but the
  convolution's.
- ``Mamba(h)``: ``[z | x | B | C] = (W_in (ssm_in_multiplier h)) * m``
  and ``dt = (W_dt (ssm_in_multiplier h)) * m_dt``, ``m`` constant on
  each part (``ssm_multipliers``, in that order); ``[x | B | C]``
  through a causal depthwise convolution of ``mamba_d_conv`` taps, its
  bias and SiLU; per head ``i`` of group ``g = i // (H / G)``: ``D_t =
  softplus(dt + dt_bias)``, ``a = exp(-D_t exp(A_log))``, ``S <- a S +
  D_t x (x) B_g``, ``y = S C_g + D x``; ``W_out(w * RMSNorm over each
  group's channels of (y * SiLU(z)))``: the gate first
  (``mamba_norm_before_gate`` false).
- ``Attn(u)``: ``q = W_q u``, ``k = key_multiplier W_k u``, ``v = W_v
  u``; rotate-half rotary over the whole head (``rope_theta``, no
  scaling); causal ``softmax(q k^T / sqrt(head_dim)) v``, a key-value
  head serving ``heads / kv_heads`` neighbouring query heads; ``W_o``.
- ``MLP(u) = mlp_multipliers[1] W_down(W_up u * SiLU(mlp_multipliers[0]
  W_gate u))``.

Departures from the modeling code, as the configuration's file lists
them:

- the checkpoint's ``in_proj`` is held as ``in_weight`` (rows ``[z | x |
  B | C]``) and ``dt_weight`` (its last ``mamba_n_heads`` rows): a split
  of the rows of a matrix that is random here;
- the modeling code multiplies a branch's rounded bfloat16 output by its
  multiplier; here, in float32, the order makes no difference;
- the step ``softplus(dt + dt_bias)`` is not clipped (the modeling
  code's ``time_step_limit`` default is ``(0, inf)``).

So that a 10,240-wide sequence fits beside 10.5 GB of bfloat16 weights,
a weight is taken to float32 where it is used, the feed-forward and the
attention run over blocks of rows, and logits too large for what the
weights leave of the device (``[10240, 261120]`` float32 is 10.7 GB)
are computed in blocks of rows that are put in the host's memory as
they are made: a placement, no arithmetic (:func:`head`).

``mode`` selects the arithmetic.  ``float32`` is the reference; the
lower ones exist for the control of "How correct is decided":

    float32   float32 storage, products at HIGHEST
    bfloat16  bfloat16 storage and products (float32 accumulation); the
              softmax, the norm statistics, the step, the decay and the
              recurrent state in float32: what the configuration states
    float8    bfloat16 storage; both operands of every product rounded
              to float8_e4m3fn first, those of the recurrence's products
              with its state too (one precision under the stated)

Two more controls, each a fault a server can have: ``lost_at``, a
position at which every layer's state-space state (the recurrence's and
the convolution's rows) is set to zero before the token there is taken,
as a server does that loses a prompt's state between its prefill and
its first decode step; and ``attention=False``, the attention branch
dropped from every layer (``attention_out_multiplier`` 0), as a server
does that answers from the recurrent state alone.
"""

import jax
import jax.numpy as jnp

MODES = ("float32", "bfloat16", "float8")
ROW_BLOCK = 128         # rows of queries an attention block
FF_BLOCK = 1024         # rows a feed-forward block
HEAD_BLOCK = 512        # rows a block of logits that goes to the host
#: logits larger than this are made block by block into the host's
#: memory
DEVICE_LOGITS_BYTES = 4 * 2 ** 30


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

    def dot(self, spec, a, b, factor=None, keep_float32=False):
        """The product, float32 sums; ``factor`` (a published
        multiplier) on the sum before it is rounded to the storage
        dtype."""
        out = jnp.einsum(spec, self.rnd(a), self.rnd(b), precision=self.prec,
                         preferred_element_type=jnp.float32)
        if factor is not None:
            out = out * factor
        return out if keep_float32 else out.astype(self.store)


def _rms_norm(x, gain, eps, store, factor=1.0):
    x = x.astype(jnp.float32)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32) * factor).astype(store)


def _blocks_of(t, block):
    """``block`` where it divides ``t``, else ``t`` (a test's short
    sequence runs whole)."""
    return block if t % block == 0 else t


# ----------------------------------------------------------------------
# rotary grouped-query attention


def _rotate(x, theta):
    """Rotate-half rotary over the whole last axis of ``x [T, H, D]``,
    token ``t`` at position ``t``: the halves ``(j, j + D / 2)`` turn by
    ``t / theta^(2j / D)``."""
    t, _, dim = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :dim // 2].astype(jnp.float32), \
        x[..., dim // 2:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _attention(cfg, w, u, ar):
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, store = cfg["head_dim"], ar.store
    t = u.shape[0]
    theta = float(cfg["rope_theta"])
    q = ar.dot("tc,fc->tf", u, w["q_weight"]).reshape(t, heads, dim)
    k = ar.dot("tc,fc->tf", u, w["k_weight"],
               factor=cfg["key_multiplier"]).reshape(t, groups, dim)
    v = ar.dot("tc,fc->tf", u, w["v_weight"]).reshape(t, groups, dim)
    q, k = _rotate(q, theta), _rotate(k, theta)
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

    rb = _blocks_of(t, ROW_BLOCK)
    blocks = q.transpose(1, 0, 2).reshape(heads, t // rb, rb, dim)
    o = jax.lax.map(row_block, (blocks.transpose(1, 0, 2, 3),
                                rows.reshape(t // rb, rb)))
    o = o.transpose(1, 0, 2, 3).reshape(heads, t, dim)
    o = o.transpose(1, 0, 2).reshape(t, heads * dim)
    return ar.dot("tc,fc->tf", o, w["o_weight"],
                  factor=cfg["attention_out_multiplier"], keep_float32=True)


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


def _mamba(cfg, w, u, ar, lost_at=None):
    """``ssm_out_multiplier Mamba(h)`` of ``u = ssm_in_multiplier h``,
    float32."""
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inner, bc = heads * p, groups * n
    taps, store = cfg["mamba_d_conv"], ar.store
    m_z, m_x, m_b, m_c, m_dt = cfg["ssm_multipliers"]
    t = u.shape[0]
    # the parts of in_weight's rows one at a time, each under its factor
    z = ar.dot("tc,fc->tf", u, w["in_weight"][:inner], factor=m_z)
    into = jnp.concatenate([
        ar.dot("tc,fc->tf", u, w["in_weight"][lo:hi], factor=m)
        for lo, hi, m in ((inner, 2 * inner, m_x),
                          (2 * inner, 2 * inner + bc, m_b),
                          (2 * inner + bc, 2 * inner + 2 * bc, m_c))],
        axis=-1)
    dt = ar.dot("tc,fc->tf", u, w["dt_weight"], factor=m_dt,
                keep_float32=True)

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
        + cfg["rms_norm_eps"])
    normed = by_group.reshape(t, inner) \
        * w["ssm_norm_gamma"].astype(jnp.float32)
    out = ar.dot("tc,fc->tf", normed.astype(store), w["out_weight"])
    return cfg["ssm_out_multiplier"] * out.astype(jnp.float32)


# ----------------------------------------------------------------------
# the gated feed-forward


def _feed_forward(cfg, w, u, ar):
    gate_factor, down_factor = cfg["mlp_multipliers"]

    def rows(block):
        gate = ar.dot("tc,fc->tf", block, w["gate_weight"],
                      factor=gate_factor, keep_float32=True)
        up = ar.dot("tc,fc->tf", block, w["up_weight"], keep_float32=True)
        hidden = (up * jax.nn.silu(gate)).astype(ar.store)
        return ar.dot("tf,cf->tc", hidden, w["down_weight"],
                      factor=down_factor)

    t = u.shape[0]
    fb = _blocks_of(t, FF_BLOCK)
    return jax.lax.map(rows, u.reshape(t // fb, fb, -1)).reshape(t, -1)


def _layer_weights(params, i):
    prefix = "l%d_" % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def layer(cfg, w, x, ar, lost_at=None, attention=True, parts=None):
    """One layer of the residual stream ``x [T, d]``.  ``parts`` (a
    dict) is handed the three branches' updates, for the tests."""
    eps, store = cfg["rms_norm_eps"], ar.store
    mamba = _mamba(cfg, w, _rms_norm(x, w["norm_gamma"], eps, store,
                                     cfg["ssm_in_multiplier"]), ar, lost_at)
    update = mamba
    if attention:
        attn = _attention(cfg, w, _rms_norm(
            x, w["norm_gamma"], eps, store, cfg["attention_in_multiplier"]),
            ar)
        update = update + attn
    mixed = x + update.astype(store)
    ff = _feed_forward(cfg, w, _rms_norm(mixed, w["ff_norm_gamma"], eps,
                                         store), ar)
    if parts is not None:
        parts.update(residual=x, mamba=mamba,
                     attention=attn if attention else None, mixed=mixed,
                     feed_forward=ff)
    return mixed + ff


def hidden(cfg, params, tokens, mode="float32", lost_at=None,
           attention=True, parts=None):
    """Final-norm activations ``[T, d]`` of ``tokens`` ``[T]``.
    ``parts`` (a list) is handed every layer's branches, for the
    tests."""
    ar = _Math(mode)
    x = (params["embed_weight"][tokens].astype(jnp.float32)
         * cfg["embedding_multiplier"]).astype(ar.store)
    for i in range(cfg["num_hidden_layers"]):
        seen = None if parts is None else {}
        x = layer(cfg, _layer_weights(params, i), x, ar, lost_at, attention,
                  seen)
        if parts is not None:
            parts.append(seen)
    return _rms_norm(x, params["final_norm_gamma"], cfg["rms_norm_eps"],
                     ar.store)


def head(cfg, params, h, mode="float32"):
    """float32 logits ``[T, V]`` of final-norm activations ``h [T,
    d]``: ``lm_head_multiplier W_head h``.  Logits over
    :data:`DEVICE_LOGITS_BYTES` are made :data:`HEAD_BLOCK` rows at a
    time and put in the host's memory, where the caller reads them
    from."""
    ar = _Math(mode)
    w, factor = params["pred_weight"], cfg["lm_head_multiplier"]
    t = h.shape[0]
    if t * w.shape[0] * 4 <= DEVICE_LOGITS_BYTES or t % HEAD_BLOCK:
        return ar.dot("tc,vc->tv", h, w, factor=factor, keep_float32=True)

    def rows(_, block):
        out = ar.dot("tc,vc->tv", block, w, factor=factor, keep_float32=True)
        return None, jax.device_put(out, jax.memory.Space.Host)

    _, out = jax.lax.scan(rows, None, h.reshape(t // HEAD_BLOCK, HEAD_BLOCK,
                                                -1))
    return out.reshape(t, -1)


def logits(cfg, params, tokens, mode="float32", lost_at=None,
           attention=True):
    """float32 logits ``[B, T, V]`` of ``tokens`` int32 ``[B, T]``."""
    return jnp.stack([
        head(cfg, params, hidden(cfg, params, row, mode, lost_at, attention),
             mode)
        for row in tokens])
