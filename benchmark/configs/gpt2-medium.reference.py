"""Plain reference of the ``gpt2-medium`` configuration.

A GPT-2 block language model written straight from its equations
(Radford et al. 2019; pre-norm blocks, learned positions, tanh GELU) in
``jax.numpy``: float32 with every product at ``HIGHEST`` precision, no
kernels, no cache, no batching tricks.  It imports nothing of the
program and takes nothing the program made: the weights are the
benchmark's own (``benchmark/models/gpt2.py`` makes them from the seed)
under the checkpoint names of the configuration file.

Departures from the published model, as the configuration's ``assumed``
lists them: the output head is not tied to the embedding and has a
bias, and the attention projections have no bias.

``mode`` selects the arithmetic.  ``float32`` is the reference.  The
lower ones exist for the control of "How correct is decided": the same
equations in the precision a later PR would be tempted by.

    float32   float32 storage, products at HIGHEST
    bfloat16  bfloat16 storage and products (float32 accumulation),
              LayerNorm and softmax statistics in float32
    float8    bfloat16 storage; both operands of every product rounded
              to float8_e4m3fn first
"""

import jax
import jax.numpy as jnp

LN_EPS = 1e-5

MODES = ("float32", "bfloat16", "float8")


def _arith(mode):
    """(storage dtype, operand rounding, product precision) of a mode."""
    if mode == "float32":
        return jnp.float32, (lambda a: a), jax.lax.Precision.HIGHEST
    if mode == "bfloat16":
        return jnp.bfloat16, (lambda a: a.astype(jnp.bfloat16)), None
    if mode == "float8":
        return (jnp.bfloat16,
                lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16),
                None)
    raise ValueError("unknown mode %r (one of %s)" % (mode, ", ".join(MODES)))


def _layer_norm(x, gamma, beta, store):
    x = x.astype(jnp.float32)
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + LN_EPS)
    return (y * gamma + beta).astype(store)


def _layer_names(i):
    p = "l%d_" % i
    return {"ln1_g": p + "ln1_gamma", "ln1_b": p + "ln1_beta",
            "qkv": p + "attn_qkv_weight", "out": p + "attn_out_weight",
            "ln2_g": p + "ln2_gamma", "ln2_b": p + "ln2_beta",
            "w1": p + "ffn1_weight", "b1": p + "ffn1_bias",
            "w2": p + "ffn2_weight", "b2": p + "ffn2_bias"}


def hidden(cfg, params, tokens, mode="float32"):
    """Final-LayerNorm activations ``[B, T, C]`` of ``tokens`` ``[B, T]``."""
    store, rnd, prec = _arith(mode)
    heads = cfg["n_head"]
    b, t = tokens.shape

    def dot(spec, x, w):
        return jnp.einsum(spec, rnd(x), rnd(w), precision=prec,
                          preferred_element_type=jnp.float32).astype(store)

    x = (params["embed_weight"][tokens]
         + params["pos_embed_weight"][0, :t]).astype(store)
    mask = jnp.tril(jnp.ones((t, t), bool))
    layers = [_layer_names(i) for i in range(cfg["n_layer"])]
    stacked = {k: jnp.stack([params[names[k]] for names in layers])
               for k in layers[0]}

    def block(x, w):
        h = _layer_norm(x, w["ln1_g"], w["ln1_b"], store)
        qkv = dot("btc,fc->btf", h, w["qkv"])
        q, k, v = [a.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)
                   for a in jnp.split(qkv, 3, axis=-1)]
        s = dot("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) \
            / jnp.sqrt(jnp.float32(q.shape[-1]))
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        a = dot("bhqk,bhkd->bhqd", p.astype(store), v)
        a = a.transpose(0, 2, 1, 3).reshape(b, t, -1)
        x = x + dot("btc,fc->btf", a, w["out"])
        h = _layer_norm(x, w["ln2_g"], w["ln2_b"], store)
        h = dot("btc,fc->btf", h, w["w1"]) + w["b1"].astype(store)
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True)
        x = x + dot("btf,cf->btc", h.astype(store), w["w2"]) \
            + w["b2"].astype(store)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(block), x, stacked)
    return _layer_norm(x, params["final_ln_gamma"], params["final_ln_beta"],
                       store)


def logits(cfg, params, tokens, mode="float32"):
    """float32 logits ``[B, T, V]`` of ``tokens`` int32 ``[B, T]``."""
    _, rnd, prec = _arith(mode)
    x = hidden(cfg, params, tokens, mode)
    return jnp.einsum("btc,vc->btv", rnd(x), rnd(params["pred_weight"]),
                      precision=prec, preferred_element_type=jnp.float32) \
        + params["pred_bias"]


def loss_sum(cfg, params, batch, mode="float32"):
    """Sum over the rows of ``batch`` of the cross-entropy of every
    position (``data`` int tokens ``[B, T]``, ``softmax_label`` ``[B, T]``
    the token to predict there).  The mean loss of a step is this over
    ``B * T``."""
    lg = logits(cfg, params, batch["data"], mode)
    logp = jax.nn.log_softmax(lg, axis=-1)
    label = batch["softmax_label"].astype(jnp.int32)
    return -jnp.take_along_axis(logp, label[..., None], axis=-1).sum()


def loss_units(cfg, batch):
    """How many terms ``loss_sum`` adds up for ``batch``."""
    b, t = batch["data"].shape
    return b * t
