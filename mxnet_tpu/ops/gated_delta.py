"""The gated delta rule (Gated DeltaNet's linear attention) in its two
forms.

Per value head, with a state ``S`` of shape ``[key, value]``, token
``t`` does

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
    o_t = S^T q_t

(``g_t <= 0`` the log of the decay, ``beta_t`` in (0, 1) the write
strength).  A decode step runs that once a sequence
(:func:`gated_delta_step`): every product with the state is an
elementwise multiply and a sum in float32, so nothing of the state is
rounded.  The serving path runs it over a pool of states
(:func:`gated_delta_update`): each row's state is read from one row of
the pool and written to another, once each; on a TPU a Pallas kernel
does that with the pool left where it lies (one program a row, the
state's rows named by scalar-prefetched indices, the pool aliased to the
output: :mod:`~mxnet_tpu.ops.state_pool`), elsewhere XLA gathers, steps
and scatters.  A prefill runs it over
chunks of :data:`CHUNK` tokens (:func:`gated_delta_chunked`): inside a
chunk the rule is solved as one triangular system (the WY form of the
chunk's rank-one updates), and only the chunk-to-chunk carry of the
state is sequential, ``T / 64`` dependent steps a layer where the plain
scan has ``T``.  Both forms keep the state in float32 and take a
carried-in state; ``tests/test_gated_delta_moe.py`` holds one to the
other and to the plain scan of the benchmark's reference.

A token with ``beta = 0`` and ``g = 0`` leaves the state as it was: that
is how a prefill bucket's pad positions are passed through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import platform as _platform
from .fused.parity import case_rng, register_parity
from .state_pool import rows_through_pool

__all__ = ["CHUNK", "gated_delta_step", "gated_delta_update",
           "gated_delta_chunked"]

#: tokens a chunk of the prefill form
CHUNK = 64

_HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row.  ``q``/``k`` ``[B, H, dk]``, ``v`` ``[B, H,
    dv]``, ``g``/``beta`` ``[B, H]``, ``state`` float32 ``[B, H, dk,
    dv]``.  Returns ``(o float32 [B, H, dv], state)``."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    state = state * jnp.exp(g.astype(f32))[..., None, None]
    seen = jnp.sum(state * k[..., :, None], axis=-2)
    delta = beta.astype(f32)[..., None] * (v - seen)
    state = state + k[..., :, None] * delta[..., None, :]
    return jnp.sum(state * q[..., :, None], axis=-2), state


def gated_delta_update(q, k, v, g, beta, pool, read, write):
    """One token a row over a pool of states.  ``q``/``k``/``v``/``g``/
    ``beta`` as :func:`gated_delta_step` takes them; ``pool`` float32
    ``[rows, H, dk, dv]``; row ``i`` of the batch reads its state from
    ``pool[read[i]]`` and writes it, advanced, to ``pool[write[i]]``
    (``int32 [B]``, both in range: a pad row names a row of the pool no
    sequence owns).  No row of the pool is both read and written by
    rows that matter.  Returns ``(o float32 [B, H, dv], pool)``; with
    ``pool`` donated by the caller it is updated where it lies.

    On a TPU, with states that are whole tiles (``dv`` a multiple of
    128 lanes, ``dk`` of 8 sublanes), the kernel; elsewhere XLA gathers
    the rows, steps them and scatters them back."""
    mode = _platform.pallas_mode()
    if mode and pool.shape[-1] % 128 == 0 and pool.shape[-2] % 8 == 0:
        return _update_pallas(q, k, v, g, beta, pool, read, write,
                              mode == "interpret")
    with jax.named_scope("gated_delta_decode"):
        return _update_xla(q, k, v, g, beta, pool, read, write)


def _update_xla(q, k, v, g, beta, pool, read, write):
    o, state = gated_delta_step(q, k, v, g, beta, pool[read])
    return o, pool.at[write].set(state)


def _update_kernel(read_ref, write_ref, decay_ref, beta_ref, q_ref, k_ref,
                   v_ref, pool_ref, o_ref, out_ref, *, heads):
    """One row of the batch: ``q_ref``/``k_ref`` ``[1, dk, H]`` (the key
    dimension on sublanes, so that a head's column spreads over the
    lanes of its state), ``v_ref`` ``[1, H, dv]``, the state ``[1, H,
    dk, dv]`` as the index maps chose it; ``decay`` and ``beta`` are
    scalars in SMEM, ``[B * H]``."""
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    for h in range(heads):
        k_col = k_ref[0][:, h:h + 1]                        # [dk, 1]
        q_col = q_ref[0][:, h:h + 1]
        state = pool_ref[0, h] * decay_ref[b * heads + h]   # [dk, dv]
        seen = jnp.sum(state * k_col, axis=0, keepdims=True)
        delta = beta_ref[b * heads + h] * (v_ref[0, h:h + 1, :] - seen)
        state = state + k_col * delta
        out_ref[0, h] = state
        o_ref[0, h:h + 1, :] = jnp.sum(state * q_col, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_pallas(q, k, v, g, beta, pool, read, write, interpret=False):
    """The update with the pool left in place
    (:func:`~mxnet_tpu.ops.state_pool.rows_through_pool`: one program a
    row, its state fetched from ``pool[read[i]]`` and stored to
    ``pool[write[i]]``, the pool aliased to the output).  Jitted so
    that a model's layers share one trace and one lowering of the
    kernel."""
    f32 = jnp.float32
    bsz, heads, _ = q.shape
    dv = v.shape[-1]
    decay = jnp.exp(g.astype(f32)).reshape(-1)
    q_t = q.astype(f32).transpose(0, 2, 1)                  # [B, dk, H]
    k_t = k.astype(f32).transpose(0, 2, 1)
    o, pool = rows_through_pool(
        functools.partial(_update_kernel, heads=heads),
        [decay, beta.astype(f32).reshape(-1)], [q_t, k_t, v.astype(f32)],
        pool, read, write, [jax.ShapeDtypeStruct((bsz, heads, dv), f32)],
        scope="gated_delta_decode", interpret=interpret)
    return o, pool


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def gated_delta_chunked(q, k, v, g, beta, state=None, chunk=CHUNK):
    """One sequence.  ``q``/``k`` ``[T, H, dk]``, ``v`` ``[T, H, dv]``,
    ``g``/``beta`` ``[T, H]``, ``state`` float32 ``[H, dk, dv]`` carried
    in (zeros if None).  Returns ``(o float32 [T, H, dv], state)``.
    ``T`` need not be a multiple of ``chunk``: the tail is padded with
    tokens that leave the state alone."""
    with jax.named_scope("gated_delta_chunked"):
        return _chunked(q, k, v, g, beta, state, int(chunk))


def _chunked(q, k, v, g, beta, state, c):
    f32 = jnp.float32
    t, heads, dk = q.shape
    dv = v.shape[-1]
    n = -(-t // c)
    pad = n * c - t

    def chunks(x):
        """``[T, H, ...]`` -> ``[H, N, C, ...]``, float32."""
        x = jnp.pad(x.astype(f32), [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        x = x.reshape((n, c) + x.shape[1:])
        return jnp.moveaxis(x, 2, 0)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    g = jnp.cumsum(g, axis=-1)                          # [H, N, C]
    at = jnp.arange(c)
    lower = at[:, None] >= at[None, :]
    # exp(g_i - g_j) for j <= i; the difference is masked before the
    # exponential, so nothing above the diagonal can overflow
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    k_beta = k * beta[..., None]
    strict = (at[:, None] > at[None, :]).astype(f32)
    system = _dot("hnid,hnjd->hnij", k_beta, k) * decay * strict \
        + jnp.eye(c, dtype=f32)
    # the chunk's writes: (I + L) u = beta v, (I + L) w = beta exp(g) k
    rhs = jnp.concatenate([v * beta[..., None],
                           k_beta * jnp.exp(g)[..., None]], axis=-1)
    solved = jax.lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, w = solved[..., :dv], solved[..., dv:]
    inside = _dot("hnid,hnjd->hnij", q, k) * decay
    q_decayed = q * jnp.exp(g)[..., None]
    last = g[..., -1]                                   # [H, N]
    k_rest = k * jnp.exp(last[..., None] - g)[..., None]

    def carry(s, x):
        u_n, w_n, inside_n, q_n, k_n, last_n = x
        v_new = u_n - _dot("hck,hkv->hcv", w_n, s)
        o = _dot("hck,hkv->hcv", q_n, s) + _dot("hij,hjv->hiv", inside_n,
                                                v_new)
        s = s * jnp.exp(last_n)[:, None, None] \
            + _dot("hck,hcv->hkv", k_n, v_new)
        return s, o

    if state is None:
        state = jnp.zeros((heads, dk, dv), f32)
    per_chunk = tuple(jnp.moveaxis(x, 1, 0) for x in
                      (u, w, inside, q_decayed, k_rest, last))
    state, o = jax.lax.scan(carry, state.astype(f32), per_chunk)
    # [N, H, C, dv] -> [T, H, dv]
    return o.transpose(0, 2, 1, 3).reshape(n * c, heads, dv)[:t], state


# ----------------------------------------------------------------------
# parity: the kernel against XLA's gather, step and scatter


def _update_case(case):
    bsz, heads, dk, dv, rows = case
    rng = case_rng(case)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    k = rand(bsz, heads, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    order = rng.permutation(rows)
    args = (rand(bsz, heads, dk), k, rand(bsz, heads, dv),
            -jnp.abs(rand(bsz, heads)), jax.nn.sigmoid(rand(bsz, heads)),
            rand(rows, heads, dk, dv),
            jnp.asarray(order[:bsz], jnp.int32),
            jnp.asarray(order[bsz:2 * bsz], jnp.int32))
    return (_update_xla,
            functools.partial(_update_pallas,
                              interpret=_platform.pallas_mode() != "chip"),
            args, (1e-5, 1e-5))


register_parity(
    "gated_delta_decode", _update_case, parity="tolerance",
    grid=(
        (3, 4, 8, 128, 7),
        (2, 8, 16, 128, 4),
        # the served state (32 value heads of 128 x 128) in a small pool
        (4, 32, 128, 128, 9),
    ))
