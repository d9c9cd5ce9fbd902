"""Probe: can a Pallas MXU matmul with fused epilogues beat XLA's 1x1-conv
chains at ResNet-50 bottleneck shapes?  (VERDICT r4 #1 — the PERF.md claim
"cotangent-sum fusion into conv epilogues ... not reachable from
graph-level JAX" is now a testable hypothesis.)

Three head-to-heads per shape, fwd-only timing, best-of-3:
  A. forward 1x1 conv + BN-affine + ReLU (+ residual add)
     XLA:    relu(scale * (x @ w) + bias [+ res])
     Pallas: one kernel, epilogue fused into the matmul tiles
  B. backward cotangent path: dx = dy @ w^T + dres (the add_any fusion)
     XLA:    (dy @ w^T) + dres        (separate add pass, as in the model)
     Pallas: add fused into the dgrad matmul epilogue
  C. forward with BN-stat side outputs: y = x @ w, plus per-channel
     sum(y), sum(y^2) (the training-BN stats read)
     XLA:    y = x @ w; stats = fused reduce over y (one extra read)
     Pallas: per-M-block partial stats accumulated in the matmul epilogue

Shapes: the four bottleneck stages of ResNet-50 at the bench config
(batch 128, NHWC, bf16): M = B*H*W rows, widths (Cin -> Cmid -> Cout).

Run on the chip:  python tools/bottleneck_probe.py
"""

import functools
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x):
    import jax.tree_util as tu

    return np.asarray(jnp.ravel(tu.tree_leaves(x)[0])[0])


def _time(fn, args, steps=30, couple=1):
    """Per-step ms with `steps` iterations chained inside ONE jit (a
    host loop is floored by per-call dispatch).  Iterations couple through args[couple] (pick a SMALL
    operand, e.g. the weight): a data dependence on the previous step's
    output defeats loop-invariant hoisting at negligible added cost."""
    from jax import lax

    def runner(n):
        def run(*a):
            def body(i, c):
                ai = list(a)
                ai[couple] = ai[couple] + c.astype(ai[couple].dtype)
                out = fn(*ai)
                import jax.tree_util as tu

                leaf = jnp.ravel(tu.tree_leaves(out)[0])
                # DYNAMIC index: a static [0] lets XLA narrow the whole
                # computation to one output element (measured: a conv
                # dgrad "ran" in 3 us); a loop-varying index defeats the
                # slice push-through while reading only one element
                pick = (i * 997) % leaf.shape[0]
                return lax.dynamic_index_in_dim(
                    leaf, pick, keepdims=False).astype(jnp.float32) * 1e-20
            return lax.fori_loop(0, n, body, jnp.float32(0))
        return jax.jit(run)

    # a blocking fetch has a fixed cost regardless of the computation;
    # measure two step counts and difference it out
    lo, hi = runner(steps), runner(3 * steps)

    def once(jrun):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            _sync(jrun(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    _sync(lo(*args)), _sync(hi(*args))  # compile
    return (once(hi) - once(lo)) / (2 * steps) * 1e3


# ---------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------

def _mm_epi_kernel(x_ref, w_ref, scale_ref, bias_ref, res_ref, y_ref, *,
                   relu, add_res):
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)
    y = acc * scale_ref[...] + bias_ref[...]
    if add_res:
        y = y + res_ref[...].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)


def _pick_bm(M, bm):
    """Largest block <= bm that divides M (grid truncation would silently
    skip the tail rows — measured-garbage hazard)."""
    while M % bm:
        bm //= 2
        if bm < 8:
            raise ValueError("no block size divides M=%d" % M)
    return bm


def mm_epilogue(x, w, scale, bias, res=None, relu=True, bm=512):
    """relu(scale * (x @ w) + bias [+ res]) as ONE Pallas kernel."""
    import jax.experimental.pallas as pl

    M, K = x.shape
    N = w.shape[1]
    bm = _pick_bm(M, bm)
    grid = (M // bm,)
    in_specs = [
        pl.BlockSpec((bm, K), lambda i: (i, 0)),
        pl.BlockSpec((K, N), lambda i: (0, 0)),
        pl.BlockSpec((1, N), lambda i: (0, 0)),
        pl.BlockSpec((1, N), lambda i: (0, 0)),
    ]
    args = [x, w, scale.reshape(1, N), bias.reshape(1, N)]
    if res is not None:
        in_specs.append(pl.BlockSpec((bm, N), lambda i: (i, 0)))
        args.append(res)
    else:
        in_specs.append(pl.BlockSpec((1, N), lambda i: (0, 0)))
        args.append(jnp.zeros((1, N), x.dtype))
    kern = functools.partial(_mm_epi_kernel, relu=relu,
                             add_res=res is not None)
    return pl.pallas_call(
        kern, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype))(*args)


def _mm_stats_kernel(x_ref, w_ref, y_ref, s1_ref, s2_ref):
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)
    y_ref[...] = acc.astype(y_ref.dtype)
    # partials land in an (8, N) sublane-aligned block; every row holds
    # the same value and the caller divides by 8 after the final reduce
    s1_ref[...] = jnp.broadcast_to(
        jnp.sum(acc, axis=0, keepdims=True), s1_ref.shape)
    s2_ref[...] = jnp.broadcast_to(
        jnp.sum(acc * acc, axis=0, keepdims=True), s2_ref.shape)


def mm_with_stats(x, w, bm=512):
    """y = x @ w plus per-M-block partial (sum, sum^2) side outputs; the
    tiny [n_blocks*8, N] partials reduce in XLA afterwards (negligible)."""
    import jax.experimental.pallas as pl

    M, K = x.shape
    N = w.shape[1]
    bm = _pick_bm(M, bm)
    nb = M // bm
    y, s1, s2 = pl.pallas_call(
        _mm_stats_kernel, grid=(nb,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                  pl.BlockSpec((K, N), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0)),
                   pl.BlockSpec((8, N), lambda i: (i, 0)),
                   pl.BlockSpec((8, N), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, N), x.dtype),
                   jax.ShapeDtypeStruct((nb * 8, N), jnp.float32),
                   jax.ShapeDtypeStruct((nb * 8, N), jnp.float32)])(x, w)
    return y, jnp.sum(s1, axis=0) / 8.0, jnp.sum(s2, axis=0) / 8.0


# ---------------------------------------------------------------------
# probes
# ---------------------------------------------------------------------

def probe_shape(M, K, N, steps):
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(M, K), jnp.bfloat16)
    w = jnp.asarray(rs.randn(K, N) * 0.05, jnp.bfloat16)
    scale = jnp.asarray(rs.rand(N) + 0.5, jnp.float32)
    bias = jnp.asarray(rs.randn(N), jnp.float32)
    res = jnp.asarray(rs.randn(M, N), jnp.bfloat16)

    rows = {}

    # A: fwd conv+bn+relu+res
    def xla_a(x, w, scale, bias, res):
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return jnp.maximum(y * scale + bias + res.astype(jnp.float32),
                           0.0).astype(jnp.bfloat16)

    rows["A_xla"] = _time(jax.jit(xla_a), (x, w, scale, bias, res), steps)
    rows["A_pallas"] = _time(
        jax.jit(lambda *a: mm_epilogue(*a, relu=True)),
        (x, w, scale, bias, res), steps)

    # B: bwd cotangent dx = dy @ w^T + dres
    dy = jnp.asarray(rs.randn(M, N), jnp.bfloat16)
    dres = jnp.asarray(rs.randn(M, K), jnp.bfloat16)
    wT = jnp.asarray(np.asarray(w).T)  # [N, K]
    ones = jnp.ones((K,), jnp.float32)
    zeros = jnp.zeros((K,), jnp.float32)

    def xla_b(dy, wT, dres):
        dx = jnp.dot(dy, wT, preferred_element_type=jnp.float32)
        return (dx + dres.astype(jnp.float32)).astype(jnp.bfloat16)

    rows["B_xla"] = _time(jax.jit(xla_b), (dy, wT, dres), steps)
    rows["B_pallas"] = _time(
        jax.jit(lambda dy, wT, dres: mm_epilogue(
            dy, wT, ones, zeros, res=dres, relu=False)),
        (dy, wT, dres), steps)

    # C: fwd matmul + BN stats
    def xla_c(x, w):
        y = jnp.dot(x, w, preferred_element_type=jnp.float32) \
            .astype(jnp.bfloat16)
        yf = y.astype(jnp.float32)
        return y, jnp.sum(yf, axis=0), jnp.sum(yf * yf, axis=0)

    rows["C_xla"] = _time(jax.jit(xla_c), (x, w), steps)
    rows["C_pallas"] = _time(jax.jit(mm_with_stats), (x, w), steps)
    return rows


def main():
    assert jax.default_backend() == "tpu", "probe the chip, not the host"
    # (M, K, N): the 1x1 convs of each ResNet-50 stage at batch 128
    shapes = [
        ("stage2_reduce", 401408, 256, 64),
        ("stage2_expand", 401408, 64, 256),
        ("stage3_expand", 100352, 128, 512),
        ("stage4_expand", 25088, 256, 1024),
        ("stage5_expand", 6272, 512, 2048),
    ]
    steps = int(os.environ.get("PROBE_STEPS", "100"))
    print("%-16s %10s %10s %10s %10s %10s %10s" % (
        "shape", "A_xla", "A_pallas", "B_xla", "B_pallas", "C_xla",
        "C_pallas"))
    for name, M, K, N in shapes:
        r = probe_shape(M, K, N, steps)
        print("%-16s %9.3f %9.3f %9.3f %9.3f %9.3f %9.3f  (ms)" % (
            name, r["A_xla"], r["A_pallas"], r["B_xla"], r["B_pallas"],
            r["C_xla"], r["C_pallas"]))


if __name__ == "__main__":
    main()
