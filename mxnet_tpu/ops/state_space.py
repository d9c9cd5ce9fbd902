"""The selective state-space recurrence (Mamba-2's, "SSD") in its two
forms.

Per head ``i`` of ``H``, with a state ``S_i`` of shape ``[P, N]`` (``P``
the head's channels, ``N`` the state's size), token ``t`` does

    S_i <- a_i S_i + dt_i x_i (x) B_g;    y_i = S_i C_g + D_i x_i

with ``dt_i >= 0`` the head's step, ``a_i = exp(dt_i A_i)`` its decay
(``A_i < 0``: a scalar a head), ``B_g`` and ``C_g`` ``[N]`` shared by
the ``H / G`` neighbouring heads of group ``g``.  Every channel ``(i,
p)`` so keeps ``N`` numbers, and that is how the state lies here:
float32 ``[G, N, W]`` with ``W = H P / G`` the group's channels on the
lanes and ``N`` on the sublanes (:func:`state_shape`), so that ``S C``
is a sum over sublanes and a head's decay a row.

A decode step runs the rule once a sequence (:func:`ssm_step`), in
float32, elementwise: nothing of the state is rounded.  The serving
path runs it over a pool of states (:func:`ssm_update`): on a TPU a
Pallas kernel reads each row's state from one row of the pool and
writes it to another with the pool left where it lies
(:mod:`~mxnet_tpu.ops.state_pool`), elsewhere XLA gathers, steps and
scatters.  A prefill runs it over chunks of :data:`CHUNK` tokens
(:func:`ssm_chunked`): inside a chunk the outputs are one masked
product (the rule has no correction term: the chunk's ``C_t . B_s``
scores times the decay between ``s`` and ``t``), and only the
chunk-to-chunk carry of the state is sequential.  On a TPU a chunk's
whole rule is one program of a Pallas kernel whose grid walks a group's
chunks with the group's state in VMEM; elsewhere XLA scans
:data:`BLOCK` chunks an iteration.  Both forms keep the state in
float32 and take a carried-in state.

A token with ``dt = 0`` leaves the state as it was (``a = 1``, nothing
added): that is how a prefill bucket's pad positions are passed
through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..observability import metrics as _metrics
from . import platform as _platform
from .fused.parity import case_rng, register_parity
from .state_pool import rows_through_pool

__all__ = ["CHUNK", "BLOCK", "state_shape", "ssm_step", "ssm_update",
           "scan_form", "ssm_chunked"]

#: tokens a chunk of the prefill form (Mamba-2's published chunk_size)
CHUNK = 128
#: chunks an iteration of the carry in XLA's body of the prefill form:
#: their products are batched, their states passed on one after another
#: inside it
BLOCK = 4

#: the largest state a row of the kernel holds (in and out, each twice:
#: the next row's is fetched while one is computed)
KERNEL_STATE_BYTES = 4 * 2 ** 20

_HIGHEST = jax.lax.Precision.HIGHEST

_M_CHUNK = _metrics.gauge(
    "ssm_prefill_chunk_tokens",
    "Tokens a chunk of the state-space prefill scan traced last, by the "
    "scanned positions (the bucket) and the body traced (the kernel or "
    "XLA's)", ["tokens", "form"])


def state_shape(heads, head_dim, groups, state_size):
    """``(G, N, W)``: how one sequence's state of one layer lies."""
    return (groups, state_size, heads * head_dim // groups)


def _lanes(v, p, groups):
    """A value a head ``[.., H]`` over the head's ``P`` channels, by
    group: ``[.., G, W]``."""
    v = jnp.repeat(v, p, axis=-1)
    return v.reshape(v.shape[:-1] + (groups, -1))


def _by_channel(x, dt, a_rate, groups):
    """One token's ``x [B, H, P]`` in float32, and by channel ``[B, G,
    W]`` its head's decay ``exp(dt A)`` and its ``dt x``."""
    xf, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    decay = _lanes(jnp.exp(dt * a_rate.astype(jnp.float32)), x.shape[-1],
                   groups)
    return xf, decay, (dt[..., None] * xf).reshape(decay.shape)


def ssm_step(x, dt, a_rate, b, c, d_skip, state):
    """One token a row.  ``x`` ``[B, H, P]``, ``dt`` ``[B, H]``,
    ``a_rate``/``d_skip`` ``[H]``, ``b``/``c`` ``[B, G, N]``, ``state``
    float32 ``[B, G, N, W]``.  Returns ``(y float32 [B, H, P],
    state)``."""
    f32 = jnp.float32
    xf, decay, dx = _by_channel(x, dt, a_rate, b.shape[1])
    state = state * decay[:, :, None, :] \
        + b.astype(f32)[..., None] * dx[:, :, None, :]
    y = jnp.sum(state * c.astype(f32)[..., None], axis=2)     # [B, G, W]
    return y.reshape(xf.shape) + d_skip.astype(f32)[:, None] * xf, state


def ssm_update(x, dt, a_rate, b, c, d_skip, pool, read, write):
    """One token a row over a pool of states.  ``x``/``dt``/``a_rate``/
    ``b``/``c``/``d_skip`` as :func:`ssm_step` takes them; ``pool``
    float32 ``[rows, G, N, W]``; row ``i`` of the batch reads its state
    from ``pool[read[i]]`` and writes it, advanced, to
    ``pool[write[i]]`` (``int32 [B]``, both in range: a pad row names a
    row of the pool no sequence owns).  No row of the pool is both read
    and written by rows that matter.  Returns ``(y float32 [B, H, P],
    pool)``; with ``pool`` donated by the caller it is updated where it
    lies.

    On a TPU, with states of whole tiles (``W`` a multiple of 128 lanes,
    ``N`` of 8 sublanes) no larger than :data:`KERNEL_STATE_BYTES`, the
    kernel; elsewhere XLA gathers the rows, steps them and scatters
    them back."""
    mode = _platform.pallas_mode()
    _, groups, n, w = pool.shape
    if mode and w % 128 == 0 and n % 8 == 0 \
            and groups * n * w * 4 <= KERNEL_STATE_BYTES:
        return _update_pallas(x, dt, a_rate, b, c, d_skip, pool, read,
                              write, mode == "interpret")
    with jax.named_scope("ssm_decode"):
        return _update_xla(x, dt, a_rate, b, c, d_skip, pool, read, write)


def _update_xla(x, dt, a_rate, b, c, d_skip, pool, read, write):
    y, state = ssm_step(x, dt, a_rate, b, c, d_skip, pool[read])
    return y, pool.at[write].set(state)


def _update_kernel(read_ref, write_ref, decay_ref, dx_ref, b_ref, c_ref,
                   pool_ref, y_ref, out_ref, *, groups):
    """One row of the batch: ``decay_ref``/``dx_ref`` ``[1, G, W]`` (a
    channel's decay and its ``dt x`` on the lanes), ``b_ref``/``c_ref``
    ``[1, N, G]`` (the state's dimension on sublanes, so that a group's
    column spreads over the lanes of its state), the state ``[1, G, N,
    W]`` as the index maps chose it."""
    del read_ref, write_ref             # the index maps read them
    for g in range(groups):
        state = pool_ref[0, g] * decay_ref[0, g:g + 1, :] \
            + b_ref[0][:, g:g + 1] * dx_ref[0, g:g + 1, :]       # [N, W]
        out_ref[0, g] = state
        y_ref[0, g:g + 1, :] = jnp.sum(state * c_ref[0][:, g:g + 1],
                                       axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_pallas(x, dt, a_rate, b, c, d_skip, pool, read, write,
                   interpret=False):
    """The update with the pool left in place
    (:func:`~mxnet_tpu.ops.state_pool.rows_through_pool`).  Jitted so
    that a model's layers share one trace and one lowering of the
    kernel."""
    f32 = jnp.float32
    _, groups, n, w = pool.shape
    xf, decay, dx = _by_channel(x, dt, a_rate, groups)
    y, pool = rows_through_pool(
        functools.partial(_update_kernel, groups=groups), [],
        [decay, dx, b.astype(f32).transpose(0, 2, 1),
         c.astype(f32).transpose(0, 2, 1)],
        pool, read, write, [jax.ShapeDtypeStruct(decay.shape, f32)],
        scope="ssm_decode", interpret=interpret,
        vmem_limit_bytes=6 * groups * n * w * 4 + 4 * 2 ** 20)
    return y.reshape(xf.shape) + d_skip.astype(f32)[:, None] * xf, pool


def _dot(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def scan_form(heads, head_dim, groups, state_size, chunk=CHUNK):
    """Which body :func:`ssm_chunked` runs here on such shapes:
    ``"kernel"`` on a TPU where the kernel's tiles are whole (a group's
    channels ``W``, the state's size ``N`` and the chunk multiples of
    128, so that each is whole lanes where it lies on the lanes, and a
    head's channels a divisor or a multiple of 128), else ``"xla"``."""
    whole = heads * head_dim // groups % 128 == 0 \
        and state_size % 128 == 0 and chunk % 128 == 0 \
        and (128 % head_dim == 0 or head_dim % 128 == 0)
    return "kernel" if _platform.pallas_mode() and whole else "xla"


def ssm_chunked(x, dt, a_rate, b, c, d_skip, state=None, length=None,
                chunk=CHUNK, block=BLOCK):
    """One sequence.  ``x`` ``[T, H, P]``, ``dt`` ``[T, H]``,
    ``a_rate``/``d_skip`` ``[H]``, ``b``/``c`` ``[T, G, N]``, ``state``
    float32 ``[G, N, W]`` carried in (zeros if None); positions ``>=
    length`` (all ``T`` count if None) are passed with ``dt = 0``.
    Returns ``(y [T, H, P] in x's dtype, state)``, the state as the
    token before ``length`` left it.  ``T`` need not be a multiple of
    ``chunk``: the tail is padded with tokens that leave the state
    alone.

    Where :func:`scan_form` says so, the kernel: a chunk's scores,
    decays and products in VMEM, the state carried from chunk to chunk
    in VMEM, and a chunk that lies wholly past ``length`` runs no
    product (its rows of ``y``, which nothing reads, are ``D x`` there
    and the state's term besides in XLA's body).  Both bodies compute
    every product as ``Precision.HIGHEST`` does: a bfloat16 ``x``,
    ``b`` or ``c`` goes to the array as it lies, a float32 one, like
    the decays and the state, as three bfloat16 terms.  ``block`` is
    XLA's body's alone."""
    form = scan_form(x.shape[1], x.shape[2], b.shape[1], b.shape[2], chunk)
    _M_CHUNK.labels(str(x.shape[0]), form).set(int(chunk))
    if form == "kernel":
        if state is None:
            state = jnp.zeros(state_shape(x.shape[1], x.shape[2],
                                          *b.shape[1:]), jnp.float32)
        return _chunked_pallas(
            x, dt, a_rate, b, c, d_skip, state,
            x.shape[0] if length is None else length, size=int(chunk),
            interpret=_platform.pallas_mode() == "interpret")
    with jax.named_scope("ssm_prefill"):
        return _chunked(x, dt, a_rate, b, c, d_skip, state, length,
                        int(chunk), int(block))


def _terms(v):
    """``v`` as the bfloat16 terms that sum to it: itself where it is
    bfloat16, else the three (24 bits) of a float32 value."""
    if v.dtype == jnp.bfloat16:
        return [v]
    v = v.astype(jnp.float32)
    out = []
    for _ in range(3):
        out.append(v.astype(jnp.bfloat16))
        v = v - out[-1].astype(jnp.float32)
    return out


def _product(a, b, contract=((1,), (0,))):
    """``a . b`` in float32 from bfloat16 passes, as
    ``Precision.HIGHEST`` makes it: the products of the operands' terms
    whose orders sum to under three (one pass where both are bfloat16,
    three where one is, six where neither)."""
    a, b = _terms(a), _terms(b)
    return sum(jax.lax.dot_general(a[i], b[j], (contract, ((), ())),
                                   preferred_element_type=jnp.float32)
               for i in range(len(a)) for j in range(len(b)) if i + j < 3)


def _chunk_kernel(length_ref, x_ref, b_ref, c_ref, dt_ref, dt_across_ref,
                  rate_ref, rate_across_ref, skip_ref, entered_ref, y_ref,
                  state_ref, *, size, p):
    """One (group, chunk) program.  ``x_ref``/``y_ref`` ``[L, W]`` (the
    group's heads of ``p`` channels on the lanes), ``b_ref``/``c_ref``
    ``[L, N]``, the step twice: ``dt_ref`` ``[L, heads]`` (tokens on
    sublanes: a head's column scales rows) and ``dt_across_ref``
    ``[heads, L]`` (tokens on lanes: a head's row scales columns), the
    heads' rate ``A`` likewise ``[1, heads]`` and ``[heads, 1]``,
    ``skip_ref`` ``D`` by channel ``[1, W]``.  ``state_ref`` ``[N, W]``
    is the group's output block, the same for all its chunks: the state
    lives in it from the first chunk, which copies ``entered_ref`` in,
    to the last, after which the pipeline writes it out."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    chunk = pl.program_id(1)
    holds_a_token = chunk * size < length_ref[0]
    together = max(1, 128 // p)         # heads that share 128 lanes

    @pl.when(chunk == 0)
    def _enter():
        state_ref[...] = entered_ref[...]

    @pl.when(jnp.logical_not(holds_a_token))
    def _pad():
        y_ref[...] = (skip_ref[...] * x_ref[...].astype(f32)
                      ).astype(y_ref.dtype)

    @pl.when(holds_a_token)
    def _run():
        w = x_ref.shape[1]
        at = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
        to = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
        lower = at >= to
        dt, dt_across = dt_ref[...], dt_across_ref[...]
        # the running sum of dt A down the rows and along the lanes: a
        # product with the triangle of ones, which is exact in bfloat16
        cum = _product(lower.astype(jnp.bfloat16), dt * rate_ref[...])
        cum_across = _product(dt_across * rate_across_ref[...],
                              (at <= to).astype(jnp.bfloat16))
        last = cum[size - 1:size, :]                        # [1, heads]
        grown = jnp.exp(cum)        # from the chunk's start to a token
        rest = dt * jnp.exp(last - cum)     # dt, decayed to its end
        shrink = jnp.exp(last)
        b_k, c_k = b_ref[...], c_ref[...]
        scores = _product(c_k, b_k, ((1,), (1,)))           # [L, L]
        b_turned = b_k.T
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

        def spread(v, first):
            """The columns of ``v [rows, heads]`` of the heads that lie
            in 128 lanes from head ``first`` on, each over its lanes."""
            out = v[:, first:first + 1]
            if together == 1:
                # a head fills the lanes: its column goes over them
                # here, by a select the compiler cannot fold away (a
                # ``[1, 1]`` value spread over sublanes and lanes at
                # once it refuses: "Broadcast in both sublanes and
                # lanes")
                return jnp.where(lane >= 0, out, 0.0)
            for k in range(1, together):
                out = jnp.where(lane >= k * p,
                                v[:, first + k:first + k + 1], out)
            return out

        for left in range(0, w, 128):       # 128 lanes of channels
            lanes = slice(left, left + 128)
            first = left // p
            x_k = x_ref[:, lanes]
            xf = x_k.astype(f32)
            entered = state_ref[:, lanes]                   # [N, 128]
            inside = None
            for k in range(together):
                h = first + k
                # exp(cum_l - cum_s) for s <= l; the difference is
                # masked before the exponential, so nothing above the
                # diagonal can overflow
                decay = jnp.exp(jnp.where(
                    lower, cum[:, h:h + 1] - cum_across[h:h + 1, :],
                    -jnp.inf))
                part = _product(decay * scores * dt_across[h:h + 1, :],
                                x_k)
                inside = part if inside is None \
                    else jnp.where(lane >= k * p, part, inside)
            y = inside + _product(c_k, entered) * spread(grown, first) \
                + skip_ref[:, lanes] * xf
            y_ref[:, lanes] = y.astype(y_ref.dtype)
            # what the chunk adds to the state, decayed to its end
            state_ref[:, lanes] = entered * spread(shrink, first) \
                + _product(b_turned, xf * spread(rest, first))


@functools.partial(jax.jit, static_argnames=("size", "interpret"))
def _chunked_pallas(x, dt, a_rate, b, c, d_skip, state, length, size,
                    interpret=False):
    """The scan as one kernel over (group, chunk), a group's chunks in
    turn.  Jitted so that a model's layers and stretches share one trace
    and one lowering of the kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    t, heads, p = x.shape
    groups, n = b.shape[1:]
    per, w = heads // groups, heads * p // groups
    dt = jnp.where(jnp.arange(t)[:, None] < length, dt.astype(f32), 0.0)
    pad = -t % size

    def chunks(v):
        """``[T, ...]`` -> ``[chunks * L, rest]``."""
        v = jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
        return v.reshape(t + pad, -1)

    dt = chunks(dt).reshape(-1, groups, per)
    rate = a_rate.astype(f32).reshape(groups, 1, per)
    rows = [chunks(x), chunks(b), chunks(c), dt.transpose(1, 0, 2),
            dt.transpose(1, 2, 0), rate, rate.transpose(0, 2, 1),
            _lanes(d_skip.astype(f32), p, groups)[:, None, :],
            state.astype(f32)]

    def by_chunk(width):
        return pl.BlockSpec((size, width), lambda g, k, _: (k, g))

    def by_group(*block):
        return pl.BlockSpec((None,) + block, lambda g, k, _: (g, 0, 0))

    in_specs = [
        by_chunk(w), by_chunk(n), by_chunk(n),
        pl.BlockSpec((None, size, per), lambda g, k, _: (g, k, 0)),
        pl.BlockSpec((None, per, size), lambda g, k, _: (g, 0, k)),
        by_group(1, per), by_group(per, 1), by_group(1, w), by_group(n, w)]
    kwargs = {}
    if not interpret:
        # every block twice (the next chunk's in flight), a step's lanes
        # padded to a tile, and room for a chunk's float32 temporaries
        blocks = 2 * size * (2 * w * x.dtype.itemsize
                             + 2 * n * b.dtype.itemsize + 2 * 128 * 4) \
            + 4 * n * w * 4
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=blocks + 8 * 2 ** 20)
    # the scope names the kernel in a trace; it has to be the innermost
    with jax.named_scope("ssm_prefill"):
        y, state = pl.pallas_call(
            functools.partial(_chunk_kernel, size=size, p=p),
            out_shape=[jax.ShapeDtypeStruct((t + pad, groups * w), x.dtype),
                       jax.ShapeDtypeStruct((groups, n, w), f32)],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(groups, (t + pad) // size),
                in_specs=in_specs,
                out_specs=[by_chunk(w), by_group(n, w)]),
            interpret=interpret, **kwargs)(
            jnp.reshape(length, (1,)).astype(jnp.int32), *rows)
    return y[:t].reshape(t, heads, p), state


def _chunked(x, dt, a_rate, b, c, d_skip, state, length, size, block):
    f32 = jnp.float32
    t, heads, p = x.shape
    groups, n = b.shape[1:]
    per, w = heads // groups, heads * p // groups
    dt = dt.astype(f32)
    if length is not None:
        dt = jnp.where(jnp.arange(t)[:, None] < length, dt, 0.0)
    block = max(1, min(block, -(-t // size)))
    step = size * block
    pad = -t % step

    def blocks(v):
        """``[T, ...]`` -> ``[iterations, block, chunk, ...]``."""
        v = jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
        return v.reshape((-1, block, size) + v.shape[1:])

    at = jnp.arange(size)
    lower = at[:, None] >= at[None, :]
    a_rate, d_skip = a_rate.astype(f32), d_skip.astype(f32)

    def carry(s, inputs):
        x_k, dt_k, b_k, c_k = inputs        # [k, L, ...] of this block
        xf = x_k.astype(f32)
        cum = jnp.cumsum(dt_k * a_rate, axis=1)             # [k, L, H]
        cum_h = cum.transpose(0, 2, 1)                      # [k, H, L]
        # exp(cum_l - cum_s) for s <= l; the difference is masked before
        # the exponential, so nothing above the diagonal can overflow
        decay = jnp.exp(jnp.where(
            lower, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
        scores = _dot("klgn,ksgn->kgls", c_k, b_k)          # [k, G, L, L]
        weights = decay.reshape((block, groups, per, size, size)) \
            * scores[:, :, None]
        xdt = (xf * dt_k[..., None]).reshape(block, size, groups, per, p)
        inside = _dot("kgils,ksgip->klgip", weights, xdt)
        # what a chunk adds to the state, decayed to the chunk's end
        last = cum[:, -1]                                   # [k, H]
        rest = jnp.exp(last[:, None, :] - cum)              # [k, L, H]
        added = _dot("ksgn,ksgw->kgnw", b_k,
                     (xdt * rest.reshape(block, size, groups, per, 1)
                      ).reshape(block, size, groups, w))
        shrink = _lanes(jnp.exp(last), p, groups)           # [k, G, W]
        entered = []
        for j in range(block):          # the only sequential part
            entered.append(s)
            s = s * shrink[j][:, None, :] + added[j]
        before = _dot("klgn,kgnw->klgw", c_k, jnp.stack(entered))
        y = inside.reshape(block, size, heads, p) \
            + before.reshape(block, size, heads, p) \
            * jnp.exp(cum)[..., None] + d_skip[:, None] * xf
        return s, y.astype(x.dtype)

    if state is None:
        state = jnp.zeros((groups, n, w), f32)
    state, y = jax.lax.scan(carry, state.astype(f32),
                            tuple(blocks(v) for v in (x, dt, b, c)))
    return y.reshape((-1, heads, p))[:t], state


# ----------------------------------------------------------------------
# parity: the kernels against XLA's bodies


def _update_case(case):
    bsz, heads, p, groups, n, rows = case
    rng = case_rng(case)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    order = rng.permutation(rows)
    args = (rand(bsz, heads, p), jax.nn.softplus(rand(bsz, heads)),
            -jnp.exp(rand(heads)), rand(bsz, groups, n),
            rand(bsz, groups, n), rand(heads),
            rand(rows, *state_shape(heads, p, groups, n)),
            jnp.asarray(order[:bsz], jnp.int32),
            jnp.asarray(order[bsz:2 * bsz], jnp.int32))
    return (_update_xla,
            functools.partial(_update_pallas,
                              interpret=_platform.pallas_mode() != "chip"),
            args, (1e-5, 1e-5))


register_parity(
    "ssm_decode", _update_case, parity="tolerance",
    grid=(
        (3, 4, 32, 1, 8, 7),
        (2, 16, 16, 2, 16, 4),
        # the served state (128 heads of 64 x 128 in 8 groups) in a
        # small pool
        (4, 128, 64, 8, 128, 9),
        # the parallel hybrid's (32 heads of 128 x 256 in 2 groups):
        # exactly KERNEL_STATE_BYTES a row
        (3, 32, 128, 2, 256, 7),
    ))


def _chunked_case(case):
    t, length, heads, p, groups, n, dtype = case
    rng = case_rng(case)

    def rand(*shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(scale * rng.standard_normal(shape),
                           jnp.float32).astype(dtype)

    # B and C a tenth as wide: a sum over a state of 128 stays of size
    # one, where the float32 class's tolerance means something
    args = (rand(t, heads, p, dtype=dtype), jax.nn.softplus(rand(t, heads)),
            -jnp.exp(rand(heads)), rand(t, groups, n, scale=0.1, dtype=dtype),
            rand(t, groups, n, scale=0.1, dtype=dtype), rand(heads),
            rand(*state_shape(heads, p, groups, n)))

    def read(out):      # the pad's rows differ, and nothing reads them
        return out[0][:length], out[1]

    return (lambda *a: read(_chunked(*a, length, CHUNK, BLOCK)),
            lambda *a: read(_chunked_pallas(
                *a, length, size=CHUNK,
                interpret=_platform.pallas_mode() != "chip")),
            args)


register_parity(
    "ssm_prefill", _chunked_case, parity="tolerance",
    grid=(
        # two heads a 128 lanes, a ragged tail, float32 operands
        (200, 200, 4, 64, 2, 128, jnp.float32),
        # four heads a 128 lanes, a chunk wholly in the pad
        (300, 120, 8, 32, 1, 128, jnp.bfloat16),
        # the served layer (128 heads of 64 in 8 groups, a state of 128)
        (256, 256, 128, 64, 8, 128, jnp.bfloat16),
        # the parallel hybrid's layer (32 heads of 128 in 2 groups, a
        # state of 256): a head fills 128 lanes by itself
        (256, 200, 32, 128, 2, 256, jnp.bfloat16),
    ))
