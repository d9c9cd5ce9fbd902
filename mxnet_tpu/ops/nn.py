"""Neural-net layer operators.

Parity targets: the reference's legacy ``OperatorProperty`` layers
(``src/operator/*-inl.h``: FullyConnected, Convolution, Pooling, BatchNorm,
Activation, Dropout, SoftmaxOutput, ...).  Where the reference dispatches to
cuDNN fast paths (``src/operator/cudnn_*-inl.h``), here the same layer lowers
to XLA ops (``lax.conv_general_dilated``, ``lax.reduce_window``) that hit the
TPU MXU/VPU directly — the compiler plays cuDNN's role.

Loss layers (SoftmaxOutput, regression outputs, MakeLoss) replicate the
reference's semantics that ``backward()`` needs no head gradient: they are
``jax.custom_vjp`` rules that *ignore* the incoming cotangent, exactly as the
reference's loss-layer Backward ignores ``out_grad``
(``src/operator/softmax_output-inl.h``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from . import platform as _platform
from .registry import ParamSpec as P
from .registry import register

# ----------------------------------------------------------------------
# FullyConnected (reference src/operator/fully_connected-inl.h:76-84:
# out = dot(data, W.T) + b) — lowers to a single MXU matmul.
# ----------------------------------------------------------------------


def _fc_input_names(attrs):
    return ["data", "weight"] if attrs.get("no_bias") else ["data", "weight", "bias"]


@register(
    "FullyConnected",
    arg_names=["data", "weight", "bias"],
    input_names_fn=_fc_input_names,
    params={
        "num_hidden": P("int", 0, required=True),
        "no_bias": P("bool", False),
        "flatten": P("bool", True),
    },
)
def _fully_connected(attrs, data, weight, bias=None):
    if attrs["flatten"] and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    # mixed precision: fp32 master weights cast to the activation dtype at
    # use; the MXU accumulates fp32 (preferred_element_type)
    weight = weight.astype(data.dtype)
    # NB: no preferred_element_type — the TPU MXU accumulates fp32 for bf16
    # operands anyway, and a widened primal output breaks the conv/dot
    # transpose (f32 cotangent vs bf16 operand) under vjp.  fp16 (whose
    # accumulation is NOT guaranteed fp32 on all backends) computes in fp32.
    if data.dtype == jnp.float16:
        out = jax.lax.dot_general(
            data.astype(jnp.float32), weight.astype(jnp.float32),
            (((data.ndim - 1,), (1,)), ((), ()))).astype(jnp.float16)
    else:
        out = jax.lax.dot_general(
            data, weight, (((data.ndim - 1,), (1,)), ((), ())))
    if not attrs["no_bias"]:
        out = out + bias.astype(data.dtype)
    return out


# ----------------------------------------------------------------------
# Convolution / Deconvolution (reference convolution-inl.h, cudnn_convolution)
# ----------------------------------------------------------------------


def _conv_dims(kernel):
    return len(kernel)


def _conv_dnums(nd):
    # NC[DHW] activations, OI[DHW] weights
    spatial = "DHW"[-nd:] if nd <= 3 else None
    lhs = "NC" + spatial
    rhs = "OI" + spatial
    return (lhs, rhs, lhs)


def _conv1x1_pick_bm(M):
    for bm in (4096, 2048, 1024, 512, 256, 128):
        if M % bm == 0:
            return bm
    return None


def _conv1x1_dgrad_pallas(dy2, wio, out_dtype, bm):
    """dx = dy @ w as one Pallas MXU pass over row blocks: dy2 [M, O]
    times wio [O, I] -> [M, I].  The r5 roofline probe
    (tools/bottleneck_probe.py) measured XLA's 1x1 transposed-conv dgrad
    at ~2-3x the stream floor at ResNet bottleneck shapes; this matmul
    formulation is the experiment's positive arm."""
    import jax.experimental.pallas as pl

    M, O = dy2.shape
    I = wio.shape[1]

    def kern(dy_ref, w_ref, o_ref):
        acc = jnp.dot(dy_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)

    return pl.pallas_call(
        kern, grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, O), lambda i: (i, 0)),
                  pl.BlockSpec((O, I), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, I), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, I), out_dtype),
        interpret=_platform.pallas_mode() != "chip")(dy2, wio)


@jax.custom_vjp
def _conv1x1_nhwc(x, w):
    """1x1 stride-1 NHWC conv with a hand-rolled backward (experiment
    surface for the ResNet roofline attack; MXTPU_CONV1X1 selects the
    backward implementation: 'dot' = dot_general dgrad+wgrad,
    'pallas' = Pallas dgrad + dot wgrad; forward stays XLA's conv,
    which already fuses its BN/ReLU/residual epilogue consumers)."""
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID",
        dimension_numbers=("NHWC", "OHWI", "NHWC"))


def _conv1x1_fwd(x, w):
    return _conv1x1_nhwc(x, w), (x, w)


def _conv1x1_bwd(res, dy):
    import os

    x, w = res
    mode = os.environ.get("MXTPU_CONV1X1", "dot")
    if mode not in ("dot", "pallas"):
        from ..base import MXNetError

        raise MXNetError(
            "MXTPU_CONV1X1=%r is not a backward mode (valid: 'default' "
            "or unset = XLA conv, 'dot', 'pallas'); refusing to guess — "
            "a silent fallback would mislabel a benchmark" % mode)
    B, H, W_, I = x.shape
    O = w.shape[0]
    M = B * H * W_
    wio = w.reshape(O, I)  # OHWI, 1x1 kernel
    # wgrad: dw[o, i] = sum_m dy[m, o] * x[m, i] — a single MXU matmul
    # contracting the whole batch*spatial axis (the transposed-conv
    # formulation XLA uses pays layout copies instead)
    dw = jax.lax.dot_general(
        dy.reshape(M, O), x.reshape(M, I),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(w.dtype)
    bm = _conv1x1_pick_bm(M)
    if mode == "pallas" and bm is not None:
        dx2 = _conv1x1_dgrad_pallas(dy.reshape(M, O), wio, x.dtype, bm)
        dx = dx2.reshape(B, H, W_, I)
    else:
        dx = jax.lax.dot_general(
            dy, wio, (((3,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
    return dx, dw.reshape(w.shape)


_conv1x1_nhwc.defvjp(_conv1x1_fwd, _conv1x1_bwd)


def _conv1x1_eligible(attrs, out_dtype, nd, stride, dilate, pad, nhwc):
    """NOTE: the env var is read at TRACE time — a jitted step keeps the
    mode it was traced with regardless of later env changes (jit caches
    don't key on env).  Benchmark each mode in a fresh process, as
    docs/PERF.md's round-5 table did."""
    import os

    if os.environ.get("MXTPU_CONV1X1", "") in ("", "default"):
        return False
    # out_dtype is the ORIGINAL dtype (fp16 is cast to f32 before this
    # runs; gate on what the user ran, not the upcast)
    return (nhwc and nd == 2 and tuple(attrs["kernel"]) == (1, 1)
            and tuple(stride) == (1, 1) and tuple(dilate) == (1, 1)
            and tuple(pad) == (0, 0) and attrs["num_group"] == 1
            and out_dtype in (jnp.bfloat16, jnp.float32))


@register(
    "Convolution",
    aliases=["Convolution_v1"],  # legacy pre-NNVM registration, same math
    arg_names=["data", "weight", "bias"],
    input_names_fn=_fc_input_names,
    params={
        "kernel": P("shape", None, required=True),
        "stride": P("shape", None),
        "dilate": P("shape", None),
        "pad": P("shape", None),
        "num_filter": P("int", 0, required=True),
        "num_group": P("int", 1),
        "workspace": P("int", 1024),
        "no_bias": P("bool", False),
        "cudnn_tune": P("str", None),
        "cudnn_off": P("bool", False),
        "layout": P("str", None, enum=["NCHW", "NCW", "NCDHW", "NHWC"]),
    },
)
def _convolution(attrs, data, weight, bias=None):
    nd = _conv_dims(attrs["kernel"])
    stride = attrs["stride"] or (1,) * nd
    dilate = attrs["dilate"] or (1,) * nd
    pad = attrs["pad"] or (0,) * nd
    nhwc = attrs.get("layout") == "NHWC" and nd == 2
    # mixed precision: fp32 master weights cast to the activation dtype;
    # bf16 accumulates fp32 on the MXU implicitly; fp16 (no such guarantee
    # on all backends) computes in fp32 and casts back — see the FC note
    out_dtype = data.dtype
    weight = weight.astype(out_dtype)
    if out_dtype == jnp.float16:
        data = data.astype(jnp.float32)
        weight = weight.astype(jnp.float32)
    if _conv1x1_eligible(attrs, out_dtype, nd, stride, dilate, pad, nhwc):
        out = _conv1x1_nhwc(data, weight).astype(out_dtype)
    else:
        out = jax.lax.conv_general_dilated(
            data,
            weight,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            # NHWC: channels-last activations + OHWI weights — the
            # TPU-preferred layout (no relayout copies around each conv)
            dimension_numbers=("NHWC", "OHWI", "NHWC") if nhwc
            else _conv_dnums(nd),
            feature_group_count=attrs["num_group"],
        ).astype(out_dtype)
    if not attrs["no_bias"]:
        bias = bias.astype(out_dtype)
        out = out + (bias if nhwc else bias.reshape((1, -1) + (1,) * nd))
    return out


@register(
    "Deconvolution",
    arg_names=["data", "weight", "bias"],
    input_names_fn=_fc_input_names,
    params={
        "kernel": P("shape", None, required=True),
        "stride": P("shape", None),
        "dilate": P("shape", None),
        "pad": P("shape", None),
        "adj": P("shape", None),
        "target_shape": P("shape", None),
        "num_filter": P("int", 0, required=True),
        "num_group": P("int", 1),
        "workspace": P("int", 512),
        "no_bias": P("bool", True),
        "cudnn_tune": P("str", None),
        "cudnn_off": P("bool", False),
        "layout": P("str", None, enum=["NCHW", "NCW", "NCDHW", "NHWC"]),
    },
)
def _deconvolution(attrs, data, weight, bias=None):
    nd = _conv_dims(attrs["kernel"])
    stride = attrs["stride"] or (1,) * nd
    pad = attrs["pad"] or (0,) * nd
    adj = attrs["adj"] or (0,) * nd
    # transposed conv = gradient of conv wrt its input: lhs-dilated conv with
    # flipped IO[DHW]->OI[DHW] kernel
    k = attrs["kernel"]
    padding = [
        (k[i] - 1 - pad[i], k[i] - 1 - pad[i] + adj[i]) for i in range(nd)
    ]
    out_dtype = data.dtype
    weight = weight.astype(out_dtype)
    if out_dtype == jnp.float16:  # see the FC fp16 note
        data = data.astype(jnp.float32)
        weight = weight.astype(jnp.float32)
    w = jnp.swapaxes(weight, 0, 1)  # (in, out/g, *k) -> (out/g, in, *k)... see below
    # weight layout for Deconvolution in the reference is (in_ch, out_ch/g, *k)
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    out = jax.lax.conv_general_dilated(
        data,
        w,
        window_strides=(1,) * nd,
        padding=padding,
        lhs_dilation=stride,
        dimension_numbers=_conv_dnums(nd),
        feature_group_count=attrs["num_group"],
    ).astype(out_dtype)
    if not attrs["no_bias"] and bias is not None:
        out = out + bias.astype(out_dtype).reshape((1, -1) + (1,) * nd)
    return out


# ----------------------------------------------------------------------
# Pooling (reference pooling-inl.h) → lax.reduce_window
# ----------------------------------------------------------------------


@register(
    "Pooling",
    aliases=["Pooling_v1"],  # legacy pre-NNVM registration, same math
    params={
        "kernel": P("shape", None, required=True),
        "pool_type": P("str", "max", enum=["max", "avg", "sum"]),
        "global_pool": P("bool", False),
        "pooling_convention": P("str", "valid", enum=["valid", "full"]),
        "stride": P("shape", None),
        "pad": P("shape", None),
        "cudnn_off": P("bool", False),
        "layout": P("str", None, enum=["NCHW", "NCW", "NCDHW", "NHWC"]),
    },
)
def _pooling(attrs, data):
    nd = data.ndim - 2
    nhwc = attrs.get("layout") == "NHWC" and nd == 2
    spatial0 = 1 if nhwc else 2  # first spatial dim index
    if attrs["global_pool"]:
        axes = tuple(range(spatial0, spatial0 + nd))
        if attrs["pool_type"] == "max":
            out = jnp.max(data, axis=axes, keepdims=True)
        elif attrs["pool_type"] == "sum":
            out = jnp.sum(data, axis=axes, keepdims=True)
        else:
            out = jnp.mean(data, axis=axes, keepdims=True)
        return out
    kernel = attrs["kernel"]
    stride = attrs["stride"] or (1,) * nd
    pad = attrs["pad"] or (0,) * nd
    pads = []
    for i in range(nd):
        x, k, s, p = data.shape[spatial0 + i], kernel[i], stride[i], pad[i]
        if attrs["pooling_convention"] == "full":
            out_sz = int(_np.ceil((x + 2 * p - k) / s)) + 1
        else:
            out_sz = (x + 2 * p - k) // s + 1
        need = max((out_sz - 1) * s + k - x - p, p)
        pads.append((p, need))
    if nhwc:
        window = (1,) + tuple(kernel) + (1,)
        strides = (1,) + tuple(stride) + (1,)
        padding = ((0, 0),) + tuple(pads) + ((0, 0),)
    else:
        window = (1, 1) + tuple(kernel)
        strides = (1, 1) + tuple(stride)
        padding = ((0, 0), (0, 0)) + tuple(pads)
    pt = attrs["pool_type"]
    if pt == "max":
        # scalar (not Array) init value so jax dispatches to the monoid
        # reduce_window_max primitive, which has a linearization rule
        init = -_np.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else _np.iinfo(data.dtype).min
        return jax.lax.reduce_window(
            data, init, jax.lax.max, window, strides, padding
        )
    summed = jax.lax.reduce_window(
        data, 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0,
        jax.lax.add, window, strides, padding
    )
    if pt == "sum":
        return summed
    # avg: reference divides by full kernel volume (padding included)
    return summed / _np.prod(kernel)


# ----------------------------------------------------------------------
# Activation / LeakyReLU / Dropout
# ----------------------------------------------------------------------


@register(
    "Activation",
    params={
        "act_type": P(
            "str", "relu",
            enum=["relu", "sigmoid", "tanh", "softrelu", "softsign", "gelu"]
        )
    },
)
def _activation(attrs, x):
    t = attrs["act_type"]
    if t == "relu":
        return jax.nn.relu(x)
    if t == "gelu":  # transformer capability layer (absent in 2017 reference)
        return jax.nn.gelu(x)
    if t == "sigmoid":
        return jax.nn.sigmoid(x)
    if t == "tanh":
        return jnp.tanh(x)
    if t == "softrelu":
        return jax.nn.softplus(x)
    return jax.nn.soft_sign(x)


def _leaky_args(attrs):
    return ["data", "gamma"] if attrs.get("act_type") == "prelu" else ["data"]


@register(
    "LeakyReLU",
    arg_names=["data"],
    input_names_fn=_leaky_args,
    params={
        "act_type": P("str", "leaky", enum=["elu", "leaky", "prelu", "rrelu"]),
        "slope": P("float", 0.25),
        "lower_bound": P("float", 0.125),
        "upper_bound": P("float", 0.334),
    },
    needs_mode=True,
    needs_rng=True,
)
def _leaky_relu(attrs, x, gamma=None, is_train=False, rng=None):
    t = attrs["act_type"]
    if t == "leaky":
        return jnp.where(x > 0, x, attrs["slope"] * x)
    if t == "elu":
        return jnp.where(x > 0, x, attrs["slope"] * jnp.expm1(x))
    if t == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 else gamma
        return jnp.where(x > 0, x, g * x)
    # rrelu
    if is_train and rng is not None:
        slope = jax.random.uniform(
            rng, x.shape, minval=attrs["lower_bound"], maxval=attrs["upper_bound"]
        ).astype(x.dtype)
    else:
        slope = (attrs["lower_bound"] + attrs["upper_bound"]) / 2.0
    return jnp.where(x > 0, x, slope * x)


@register(
    "Dropout",
    params={"p": P("float", 0.5), "mode": P("str", "training")},
    needs_mode=True,
    needs_rng=True,
)
def _dropout(attrs, x, is_train=False, rng=None):
    p = attrs["p"]
    if not is_train or p <= 0.0 or rng is None:
        return x
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


# ----------------------------------------------------------------------
# BatchNorm (reference batch_norm-inl.h).  args: data,gamma,beta;
# aux: moving_mean,moving_var (updated by training forward).
# ----------------------------------------------------------------------


@register(
    "BatchNorm",
    arg_names=["data", "gamma", "beta"],
    aux_names=["moving_mean", "moving_var"],
    params={
        "eps": P("float", 1e-3),
        "momentum": P("float", 0.9),
        "fix_gamma": P("bool", True),
        "use_global_stats": P("bool", False),
        "output_mean_var": P("bool", False),
        "cudnn_off": P("bool", False),
        "axis": P("int", 1),
    },
    needs_mode=True,
)
def _batch_norm(attrs, data, gamma, beta, moving_mean, moving_var, is_train=False):
    eps = attrs["eps"]
    mom = attrs["momentum"]
    # channel axis: 1 (NCHW default) or -1/ndim-1 for channels-last graphs
    ch = attrs.get("axis", 1) % data.ndim
    axes = tuple(i for i in range(data.ndim) if i != ch)
    bshape = tuple(-1 if i == ch else 1 for i in range(data.ndim))
    if attrs["fix_gamma"]:
        gamma = jax.lax.stop_gradient(jnp.ones_like(gamma))
    use_batch = is_train and not attrs["use_global_stats"]
    if use_batch:
        # SHIFTED single-pass stats: reduce E[x-s] and E[(x-s)^2] in ONE
        # fused read of the activation (XLA fuses sibling reductions over
        # the same operand), halving BN-stat HBM traffic vs the two-pass
        # mean->var form.  The shift s = running mean (free, per-channel,
        # tracks the true mean after warm-up) bounds the catastrophic
        # cancellation E[x^2]-E[x]^2 suffers when mean^2 >> var — e.g.
        # un-centered uint8-range inputs.  s is stop_gradient'd and exact
        # algebra: mean = s + E[x-s], var = E[(x-s)^2] - E[x-s]^2.
        shift = jax.lax.stop_gradient(moving_mean.astype(jnp.float32))
        xs = data.astype(jnp.float32) - shift.reshape(bshape)
        n = 1.0
        for i in axes:
            n *= data.shape[i]
        d_mean = jnp.sum(xs, axis=axes) / n
        mean = shift + d_mean
        var = jnp.sum(jnp.square(xs), axis=axes) / n - jnp.square(d_mean)
        var = jnp.maximum(var, 0.0)
        new_mm = mom * moving_mean + (1 - mom) * jax.lax.stop_gradient(mean)
        new_mv = mom * moving_var + (1 - mom) * jax.lax.stop_gradient(var)
    else:
        mean, var = moving_mean, moving_var
        new_mm, new_mv = moving_mean, moving_var
    inv = jax.lax.rsqrt(var + eps)
    out = (data - mean.reshape(bshape).astype(data.dtype)) * (
        inv.reshape(bshape).astype(data.dtype)
    ) * gamma.reshape(bshape).astype(data.dtype) \
        + beta.reshape(bshape).astype(data.dtype)
    return out, new_mm, new_mv


# ----------------------------------------------------------------------
# Normalization cousins
# ----------------------------------------------------------------------


@register(
    "InstanceNorm",
    arg_names=["data", "gamma", "beta"],
    params={"eps": P("float", 1e-3)},
)
def _instance_norm(attrs, x, gamma, beta):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    return (x - mean) * jax.lax.rsqrt(var + attrs["eps"]) * gamma.reshape(
        bshape
    ) + beta.reshape(bshape)


@register(
    "L2Normalization",
    params={
        "eps": P("float", 1e-10),
        "mode": P("str", "instance", enum=["instance", "channel", "spatial"]),
    },
)
def _l2_normalization(attrs, x):
    mode = attrs["mode"]
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
    elif mode == "channel":
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, x.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + attrs["eps"])
    return x / norm


@register(
    "LRN",
    params={
        "alpha": P("float", 1e-4),
        "beta": P("float", 0.75),
        "knorm": P("float", 2.0),
        "nsize": P("int", 5, required=True),
    },
)
def _lrn(attrs, x):
    n = attrs["nsize"]
    sq = jnp.square(x)
    # sum over a window of n channels centered at each channel
    pad = n // 2
    sq_pad = jnp.pad(sq, [(0, 0), (pad, pad)] + [(0, 0)] * (x.ndim - 2))
    acc = jnp.zeros_like(x)
    for i in range(n):
        acc = acc + jax.lax.dynamic_slice_in_dim(sq_pad, i, x.shape[1], axis=1)
    scale = attrs["knorm"] + (attrs["alpha"] / n) * acc
    return x / jnp.power(scale, attrs["beta"])


# ----------------------------------------------------------------------
# Loss layers — custom_vjp, head-grad independent (reference softmax_output-inl.h,
# regression_output-inl.h, make_loss-inl.h, svm_output-inl.h)
# ----------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _softmax_output_rule(grad_scale, ignore_label, multi_output, use_ignore,
                         preserve_shape, normalization, out_grad):
    @jax.custom_vjp
    def f(data, label):
        return _softmax_fwd(data)

    def _softmax_fwd(data):
        if multi_output:
            return jax.nn.softmax(data, axis=1)
        return jax.nn.softmax(
            data.reshape(data.shape[0], -1) if not preserve_shape else data, axis=-1
        ).reshape(data.shape)

    def fwd(data, label):
        out = _softmax_fwd(data)
        return out, (out, label)

    def bwd(res, g):
        out, label = res
        if multi_output:
            # data (n, c, *rest); label (n, *rest)
            lab = label.astype(jnp.int32)
            onehot = jnp.moveaxis(
                jax.nn.one_hot(lab, out.shape[1], dtype=out.dtype), -1, 1
            )
            grad = out - onehot
            valid = jnp.ones(lab.shape, dtype=out.dtype)
            if use_ignore:
                valid = (lab != int(ignore_label)).astype(out.dtype)
                grad = grad * valid[:, None]
        else:
            lab = label.reshape(-1).astype(jnp.int32)
            flat = out.reshape(out.shape[0], -1)
            onehot = jax.nn.one_hot(lab, flat.shape[1], dtype=out.dtype)
            grad = (flat - onehot).reshape(out.shape)
            valid = jnp.ones((out.shape[0],), dtype=out.dtype)
            if use_ignore:
                valid = (lab != int(ignore_label)).astype(out.dtype)
                grad = grad * valid.reshape((-1,) + (1,) * (out.ndim - 1))
        if normalization == "batch":
            grad = grad / out.shape[0]
        elif normalization == "valid":
            grad = grad / jnp.maximum(jnp.sum(valid), 1.0)
        grad = grad * grad_scale
        if out_grad:
            grad = grad * g
        return grad, jnp.zeros_like(label)

    f.defvjp(fwd, bwd)
    return f


@register(
    "SoftmaxOutput",
    aliases=["Softmax"],
    arg_names=["data", "label"],
    params={
        "grad_scale": P("float", 1.0),
        "ignore_label": P("float", -1.0),
        "multi_output": P("bool", False),
        "use_ignore": P("bool", False),
        "preserve_shape": P("bool", False),
        "normalization": P("str", "null", enum=["null", "batch", "valid"]),
        "out_grad": P("bool", False),
        "smooth_alpha": P("float", 0.0),
    },
)
def _softmax_output(attrs, data, label):
    rule = _softmax_output_rule(
        attrs["grad_scale"],
        attrs["ignore_label"],
        attrs["multi_output"],
        attrs["use_ignore"],
        attrs["preserve_shape"],
        attrs["normalization"],
        attrs["out_grad"],
    )
    return rule(data, label.astype(data.dtype))


@functools.lru_cache(maxsize=None)
def _fused_lm_head_rule(chunk):
    """Chunked fused linear + softmax cross-entropy (beyond-parity: the
    2017 reference predates LM heads; this is the long-context enabler).

    Computes per-token CE of ``logits = x @ W.T`` WITHOUT materializing
    the [T, V] logits: both passes stream T in ``chunk``-row slices via
    ``lax.scan``, so peak memory is O(chunk*V + d*V) instead of O(T*V)
    — at T=32k, V=32k that is the difference between 130 MB and 4.2 GB.
    Custom vjp (so the recompute is explicit, like the flash-attention
    backward): bwd recomputes each chunk's softmax and accumulates dW in
    fp32.  Matmuls run in the input dtype with fp32 accumulation
    (``preferred_element_type`` is safe here — no XLA transpose is ever
    taken of this op)."""

    @jax.custom_vjp
    def f(x, w, lab):
        return _loss(x, w, lab)

    def _dot_f32(a, b, dims):
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)

    def _loss(x, w, lab):
        T, d = x.shape
        n = T // chunk
        xs = x.reshape(n, chunk, d)
        labs = lab.reshape(n, chunk).astype(jnp.int32)
        wl = w.astype(x.dtype)

        def body(_, xl):
            xc, lc = xl
            # [chunk, V] fp32, live only inside this scan step
            logits = _dot_f32(xc, wl, (((1,), (1,)), ((), ())))
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
            return None, jnp.where(lc >= 0, lse - ll, 0.0)

        _, losses = jax.lax.scan(body, None, (xs, labs))
        return losses.reshape(T)

    def fwd(x, w, lab):
        return _loss(x, w, lab), (x, w, lab)

    def bwd(res, g):
        x, w, lab = res
        T, d = x.shape
        n = T // chunk
        xs = x.reshape(n, chunk, d)
        labs = lab.reshape(n, chunk).astype(jnp.int32)
        gs = g.reshape(n, chunk)
        wl = w.astype(x.dtype)

        def body(dw, xlg):
            xc, lc, gc = xlg
            logits = _dot_f32(xc, wl, (((1,), (1,)), ((), ())))
            p = jax.nn.softmax(logits, axis=-1)
            onehot = jax.nn.one_hot(lc, p.shape[-1], dtype=p.dtype)
            mask = (lc >= 0).astype(p.dtype)
            dl = ((p - onehot) * (gc * mask)[:, None]).astype(xc.dtype)
            dxc = dl @ wl  # [chunk, d]
            dw = dw + _dot_f32(dl, xc, (((0,), (0,)), ((), ())))
            return dw, dxc

        dw0 = jnp.zeros(w.shape, jnp.float32)
        dw, dxs = jax.lax.scan(body, dw0, (xs, labs, gs))
        return dxs.reshape(T, d), dw.astype(w.dtype), jnp.zeros_like(lab)

    f.defvjp(fwd, bwd)
    return f


@register(
    "_contrib_fused_lm_head",
    arg_names=["data", "weight", "label"],
    params={"chunk": P("int", 2048)},
)
def _fused_lm_head(attrs, data, weight, label):
    """Per-token softmax cross-entropy of ``data @ weight.T`` against
    integer ``label`` rows, streamed in chunks (see
    :func:`_fused_lm_head_rule`).  ``weight`` uses the FullyConnected
    [num_classes, d] layout so an LM checkpoint's ``pred_weight`` drops
    in unchanged; labels < 0 are ignored (zero loss and gradient).
    Output: [T] fp32 losses."""
    T = data.shape[0]
    chunk = min(int(attrs["chunk"]), T)
    pad = (-T) % chunk
    x = data.reshape(T, -1)
    lab = label.reshape(T)
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
        lab = jnp.concatenate(
            [lab, jnp.full((pad,), -1.0, lab.dtype)], axis=0)
    out = _fused_lm_head_rule(chunk)(x, weight, lab)
    return out[:T] if pad else out


@register("SoftmaxActivation", params={"mode": P("str", "instance", enum=["instance", "channel"])})
def _softmax_activation(attrs, x):
    if attrs["mode"] == "channel":
        return jax.nn.softmax(x, axis=1)
    return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)


def _regression_rule(grad_fn):
    @functools.lru_cache(maxsize=None)
    def make(grad_scale):
        @jax.custom_vjp
        def f(data, label):
            return grad_fn.fwd(data)

        def fwd(data, label):
            out = grad_fn.fwd(data)
            return out, (out, label)

        def bwd(res, g):
            out, label = res
            # reference scales by grad_scale only; batch normalization of the
            # loss is the optimizer's rescale_grad job
            grad = grad_fn.bwd(out, label.reshape(out.shape)) * grad_scale
            return grad, jnp.zeros_like(label)

        f.defvjp(fwd, bwd)
        return f

    return make


class _LinearReg:
    fwd = staticmethod(lambda d: d)
    bwd = staticmethod(lambda o, l: o - l)


class _LogisticReg:
    fwd = staticmethod(jax.nn.sigmoid)
    bwd = staticmethod(lambda o, l: o - l)


class _MAEReg:
    fwd = staticmethod(lambda d: d)
    bwd = staticmethod(lambda o, l: jnp.sign(o - l))


_linear_reg = _regression_rule(_LinearReg)
_logistic_reg = _regression_rule(_LogisticReg)
_mae_reg = _regression_rule(_MAEReg)


@register(
    "LinearRegressionOutput",
    arg_names=["data", "label"],
    params={"grad_scale": P("float", 1.0)},
)
def _linear_regression_output(attrs, data, label):
    return _linear_reg(attrs["grad_scale"])(data, label.astype(data.dtype))


@register(
    "LogisticRegressionOutput",
    arg_names=["data", "label"],
    params={"grad_scale": P("float", 1.0)},
)
def _logistic_regression_output(attrs, data, label):
    return _logistic_reg(attrs["grad_scale"])(data, label.astype(data.dtype))


@register(
    "MAERegressionOutput",
    arg_names=["data", "label"],
    params={"grad_scale": P("float", 1.0)},
)
def _mae_regression_output(attrs, data, label):
    return _mae_reg(attrs["grad_scale"])(data, label.astype(data.dtype))


@functools.lru_cache(maxsize=None)
def _svm_rule(margin, regularization_coefficient, use_linear):
    @jax.custom_vjp
    def f(data, label):
        return data

    def fwd(data, label):
        return data, (data, label)

    def bwd(res, g):
        data, label = res
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, data.shape[1], dtype=data.dtype)
        sign = 2.0 * onehot - 1.0  # +1 at true class, -1 elsewhere
        viol = (margin - sign * data) > 0
        if use_linear:
            grad = jnp.where(viol, -sign, 0.0)
        else:
            grad = jnp.where(viol, -2.0 * sign * (margin - sign * data), 0.0)
        return grad * regularization_coefficient, jnp.zeros_like(label)

    f.defvjp(fwd, bwd)
    return f


@register(
    "SVMOutput",
    arg_names=["data", "label"],
    params={
        "margin": P("float", 1.0),
        "regularization_coefficient": P("float", 1.0),
        "use_linear": P("bool", False),
    },
)
def _svm_output(attrs, data, label):
    return _svm_rule(
        attrs["margin"], attrs["regularization_coefficient"], attrs["use_linear"]
    )(data, label.astype(data.dtype))


@functools.lru_cache(maxsize=None)
def _make_loss_rule(grad_scale, normalization):
    @jax.custom_vjp
    def f(data):
        return data

    def fwd(data):
        return data, data.shape

    def bwd(shape, g):
        grad = jnp.full(shape, grad_scale)
        if normalization == "batch":
            grad = grad / shape[0]
        return (grad,)

    f.defvjp(fwd, bwd)
    return f


@register(
    "MakeLoss",
    aliases=["make_loss"],
    params={
        "grad_scale": P("float", 1.0),
        "valid_thresh": P("float", 0.0),
        "normalization": P("str", "null", enum=["null", "batch", "valid"]),
    },
)
def _make_loss(attrs, data):
    return _make_loss_rule(attrs["grad_scale"], attrs["normalization"])(data)


@register("BlockGrad", aliases=["stop_gradient"])
def _block_grad(attrs, x):
    return jax.lax.stop_gradient(x)


# ----------------------------------------------------------------------
# Spatial ops
# ----------------------------------------------------------------------


@register(
    "UpSampling",
    variable_args=True,
    params={
        "scale": P("int", 1, required=True),
        "num_filter": P("int", 0),
        "sample_type": P("str", "nearest", enum=["nearest", "bilinear"]),
        "multi_input_mode": P("str", "concat", enum=["concat", "sum"]),
        "num_args": P("int", 1),
        "workspace": P("int", 512),
    },
)
def _upsampling(attrs, *xs):
    s = attrs["scale"]
    outs = []
    for x in xs:
        if attrs["sample_type"] == "nearest":
            up = jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
        else:
            up = jax.image.resize(
                x, x.shape[:2] + (x.shape[2] * s, x.shape[3] * s), method="bilinear"
            )
        outs.append(up)
    if len(outs) == 1:
        return outs[0]
    if attrs["multi_input_mode"] == "sum":
        out = outs[0]
        for o in outs[1:]:
            out = out + o
        return out
    return jnp.concatenate(outs, axis=1)


@register(
    "Pad",
    aliases=["pad"],
    params={
        "mode": P("str", "constant", enum=["constant", "edge", "reflect"]),
        "pad_width": P("shape", None, required=True),
        "constant_value": P("float", 0.0),
    },
)
def _pad(attrs, x):
    pw = attrs["pad_width"]
    pairs = [(pw[2 * i], pw[2 * i + 1]) for i in range(len(pw) // 2)]
    mode = attrs["mode"]
    if mode == "constant":
        return jnp.pad(x, pairs, mode="constant", constant_values=attrs["constant_value"])
    return jnp.pad(x, pairs, mode="edge" if mode == "edge" else "reflect")


@register(
    "Crop",
    variable_args=True,
    params={
        "num_args": P("int", 1),
        "offset": P("shape", (0, 0)),
        "h_w": P("shape", (0, 0)),
        "center_crop": P("bool", False),
    },
)
def _crop(attrs, *xs):
    x = xs[0]
    if len(xs) == 2:
        th, tw = xs[1].shape[2], xs[1].shape[3]
    else:
        th, tw = attrs["h_w"]
    if attrs["center_crop"]:
        oy = (x.shape[2] - th) // 2
        ox = (x.shape[3] - tw) // 2
    else:
        oy, ox = attrs["offset"]
    return x[:, :, oy : oy + th, ox : ox + tw]


# ----------------------------------------------------------------------
# Sequence ops (reference sequence_last/mask/reverse-inl.h).
# Layout matches the reference: (seq_len, batch, ...) by default.
# ----------------------------------------------------------------------


def _seq_args(attrs):
    return (
        ["data", "sequence_length"] if attrs.get("use_sequence_length") else ["data"]
    )


@register(
    "SequenceLast",
    arg_names=["data", "sequence_length"],
    input_names_fn=_seq_args,
    params={"use_sequence_length": P("bool", False)},
)
def _sequence_last(attrs, data, seq_len=None):
    if not attrs["use_sequence_length"] or seq_len is None:
        return data[-1]
    idx = jnp.maximum(seq_len.astype(jnp.int32) - 1, 0)  # (batch,)
    return jnp.take_along_axis(
        data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0
    )[0]


def _seq_last_args(attrs):
    return ["data", "sequence_length"] if attrs.get("use_sequence_length") else ["data"]


@register(
    "SequenceMask",
    arg_names=["data", "sequence_length"],
    input_names_fn=_seq_args,
    params={"use_sequence_length": P("bool", False), "value": P("float", 0.0)},
)
def _sequence_mask(attrs, data, seq_len=None):
    if not attrs["use_sequence_length"] or seq_len is None:
        return data
    steps = jnp.arange(data.shape[0]).reshape((-1, 1))
    mask = steps < seq_len.astype(jnp.int32).reshape((1, -1))
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(attrs["value"], data.dtype))


@register(
    "SequenceReverse",
    arg_names=["data", "sequence_length"],
    input_names_fn=_seq_args,
    params={"use_sequence_length": P("bool", False)},
)
def _sequence_reverse(attrs, data, seq_len=None):
    if not attrs["use_sequence_length"] or seq_len is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    steps = jnp.arange(T).reshape((-1, 1))
    L = seq_len.astype(jnp.int32).reshape((1, -1))
    rev_idx = jnp.where(steps < L, L - 1 - steps, steps)  # (T, batch)
    return jnp.take_along_axis(
        data, rev_idx.reshape((T, -1) + (1,) * (data.ndim - 2)), axis=0
    )


# ----------------------------------------------------------------------
# ROIPooling / BilinearSampler / GridGenerator / SpatialTransformer
# ----------------------------------------------------------------------


@register(
    "ROIPooling",
    arg_names=["data", "rois"],
    params={
        "pooled_size": P("shape", None, required=True),
        "spatial_scale": P("float", 1.0, required=True),
    },
)
def _roi_pooling(attrs, data, rois):
    ph, pw = attrs["pooled_size"]
    scale = attrs["spatial_scale"]
    H, W = data.shape[2], data.shape[3]

    def pool_one(roi):
        batch_idx = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * scale).astype(jnp.int32)
        h = jnp.maximum(y2 - y1 + 1, 1)
        w = jnp.maximum(x2 - x1 + 1, 1)
        img = data[batch_idx]  # (C, H, W)
        ys = jnp.arange(H).reshape(1, -1, 1)
        xs = jnp.arange(W).reshape(1, 1, -1)

        def cell(iy, ix):
            hstart = y1 + (iy * h) // ph
            hend = y1 + ((iy + 1) * h + ph - 1) // ph
            wstart = x1 + (ix * w) // pw
            wend = x1 + ((ix + 1) * w + pw - 1) // pw
            mask = (ys >= hstart) & (ys < hend) & (xs >= wstart) & (xs < wend)
            # empty cells (degenerate/clipped rois) are 0 like the
            # reference (roi_pooling-inl.h is_empty), NOT -inf — an -inf
            # output NaNs the backward and poisons the whole step
            mx_val = jnp.max(jnp.where(mask, img, -jnp.inf), axis=(1, 2))
            return jnp.where(jnp.isfinite(mx_val), mx_val, 0.0)

        cells = [[cell(iy, ix) for ix in range(pw)] for iy in range(ph)]
        out = jnp.stack([jnp.stack(r, axis=-1) for r in cells], axis=-2)
        return out  # (C, ph, pw)

    return jax.vmap(pool_one)(rois)


@register("GridGenerator", arg_names=["data"], params={
    "transform_type": P("str", "affine", enum=["affine", "warp"]),
    "target_shape": P("shape", (0, 0)),
})
def _grid_generator(attrs, data):
    if attrs["transform_type"] == "affine":
        h, w = attrs["target_shape"]
        n = data.shape[0]
        ys = jnp.linspace(-1, 1, h)
        xs = jnp.linspace(-1, 1, w)
        gx, gy = jnp.meshgrid(xs, ys)
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=0).reshape(3, -1)  # (3, h*w)
        theta = data.reshape(n, 2, 3)
        grid = jnp.einsum("nij,jk->nik", theta, base)  # (n, 2, h*w)
        return grid.reshape(n, 2, h, w)
    # warp: data is flow (n, 2, h, w)
    n, _, h, w = data.shape
    ys = jnp.arange(h, dtype=data.dtype)
    xs = jnp.arange(w, dtype=data.dtype)
    gx, gy = jnp.meshgrid(xs, ys)
    fx = (gx + data[:, 0]) * 2.0 / jnp.maximum(w - 1, 1) - 1.0
    fy = (gy + data[:, 1]) * 2.0 / jnp.maximum(h - 1, 1) - 1.0
    return jnp.stack([fx, fy], axis=1)


def _bilinear_sample(data, grid):
    """data (n,c,H,W), grid (n,2,h,w) in [-1,1] -> (n,c,h,w)."""
    n, c, H, W = data.shape
    gx = (grid[:, 0] + 1.0) * (W - 1) / 2.0
    gy = (grid[:, 1] + 1.0) * (H - 1) / 2.0
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def gather(img, yy, xx):
        # img (c,H,W); yy/xx (h,w) int32
        valid = (yy >= 0) & (yy < H) & (xx >= 0) & (xx < W)
        yc = jnp.clip(yy, 0, H - 1)
        xc = jnp.clip(xx, 0, W - 1)
        vals = img[:, yc, xc]  # (c,h,w)
        return jnp.where(valid, vals, 0.0)

    def sample_one(img, x0_, y0_, wx_, wy_):
        x0i = x0_.astype(jnp.int32)
        y0i = y0_.astype(jnp.int32)
        v00 = gather(img, y0i, x0i)
        v01 = gather(img, y0i, x0i + 1)
        v10 = gather(img, y0i + 1, x0i)
        v11 = gather(img, y0i + 1, x0i + 1)
        return (
            v00 * (1 - wy_) * (1 - wx_)
            + v01 * (1 - wy_) * wx_
            + v10 * wy_ * (1 - wx_)
            + v11 * wy_ * wx_
        )

    return jax.vmap(sample_one)(data, x0, y0, wx, wy)


@register("BilinearSampler", arg_names=["data", "grid"])
def _bilinear_sampler(attrs, data, grid):
    return _bilinear_sample(data, grid)


@register(
    "SpatialTransformer",
    arg_names=["data", "loc"],
    params={
        "target_shape": P("shape", (0, 0)),
        "transform_type": P("str", "affine", enum=["affine"]),
        "sampler_type": P("str", "bilinear", enum=["bilinear"]),
    },
)
def _spatial_transformer(attrs, data, loc):
    grid = _grid_generator(
        {"transform_type": "affine", "target_shape": attrs["target_shape"]}, loc
    )
    return _bilinear_sample(data, grid)


# ----------------------------------------------------------------------
# Correlation (reference src/operator/correlation-inl.h — FlowNet-style
# patch correlation between two feature maps)
# ----------------------------------------------------------------------


@register(
    "Correlation",
    arg_names=["data1", "data2"],
    params={
        "kernel_size": P("int", 1),
        "max_displacement": P("int", 1),
        "stride1": P("int", 1),
        "stride2": P("int", 1),
        "pad_size": P("int", 0),
        "is_multiply": P("bool", True),
    },
)
def _correlation(attrs, data1, data2):
    """Correlation volume: for every displacement d in a (2m+1)^2 grid,
    the K*K*C-normalized patch product (or abs-difference) of data1 and
    shifted data2.  Output (B, D*D, H', W').  Vectorized as a static
    python loop over displacements (the grid is small) with XLA window
    sums — no im2col scratch like the reference's CUDA kernel."""
    K = attrs["kernel_size"]
    md = attrs["max_displacement"]
    s1, s2 = attrs["stride1"], attrs["stride2"]
    pad = attrs["pad_size"]
    B, C, H, W = data1.shape
    rad = (K - 1) // 2
    border = md + rad
    grid_rad = md // s2
    D = 2 * grid_rad + 1
    p1 = jnp.pad(data1, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    p2 = jnp.pad(data2, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    Hp, Wp = H + 2 * pad, W + 2 * pad
    out_h = int(_np.ceil((Hp - border * 2) / float(s1)))
    out_w = int(_np.ceil((Wp - border * 2) / float(s1)))
    norm = float(K * K * C)
    window = (1, 1, K, K)
    strides = (1, 1, 1, 1)
    maps = []
    for di in range(-grid_rad, grid_rad + 1):
        for dj in range(-grid_rad, grid_rad + 1):
            dy, dx = di * s2, dj * s2
            shifted = jnp.roll(p2, shift=(-dy, -dx), axis=(2, 3))
            if attrs["is_multiply"]:
                prod = jnp.sum(p1 * shifted, axis=1, keepdims=True)
            else:
                prod = jnp.sum(jnp.abs(p1 - shifted), axis=1, keepdims=True)
            acc = jax.lax.reduce_window(
                prod, 0.0, jax.lax.add, window, strides,
                [(0, 0), (0, 0), (rad, rad), (rad, rad)])
            sl = acc[:, 0, border:border + out_h * s1:s1,
                     border:border + out_w * s1:s1]
            maps.append(sl / norm)
    return jnp.stack(maps, axis=1)


# ----------------------------------------------------------------------
# IdentityAttachKLSparseReg (reference
# src/operator/identity_attach_KL_sparse_reg-inl.h — identity forward,
# KL sparsity penalty injected into backward; moving-average activation)
# ----------------------------------------------------------------------


@register(
    "IdentityAttachKLSparseReg",
    arg_names=["data"],
    aux_names=["moving_avg"],
    params={
        "sparseness_target": P("float", 0.1),
        "penalty": P("float", 0.001),
        "momentum": P("float", 0.9),
    },
    needs_mode=True,
)
def _identity_kl_sparse(attrs, data, moving_avg, is_train=False):
    rho = attrs["sparseness_target"]
    penalty = attrs["penalty"]
    mom = attrs["momentum"]

    @jax.custom_vjp
    def ident(x, avg):
        return x

    def fwd(x, avg):
        return x, (x, avg)

    def bwd(res, dy):
        x, avg = res
        # KL'(rho || rho_hat) per unit, broadcast over the batch
        rho_hat = jnp.clip(avg, 1e-6, 1.0 - 1e-6)
        kl_grad = penalty * (-rho / rho_hat + (1 - rho) / (1 - rho_hat))
        return dy + jnp.broadcast_to(kl_grad, x.shape), None

    ident.defvjp(fwd, bwd)

    batch_mean = jnp.mean(data, axis=0)
    new_avg = jnp.where(
        is_train, mom * moving_avg + (1 - mom) * batch_mean, moving_avg)
    return ident(data, jax.lax.stop_gradient(new_avg)), new_avg
