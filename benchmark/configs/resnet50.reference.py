"""Plain reference of the ``resnet50`` configuration.

ResNet-50 (He et al., arXiv:1512.03385, Table 1: 3-4-6-3 bottleneck
units of 256-512-1024-2048 channels, 224x224 input, 1000 classes) in the
pre-activation arrangement of the source's
``example/image-classification/symbols/resnet.py``: BatchNorm on the
input, BatchNorm-ReLU before every convolution, a projection shortcut
taken after the first activation of a stage's first unit.  Written in
``jax.numpy`` and ``jax.lax``: float32, every product at ``HIGHEST``
precision, channels last, batch statistics over the whole batch, no
kernels.  It imports nothing of the program; the weights are the
benchmark's own (``benchmark/models/resnet.py``).

Departure, listed under the configuration's ``assumed``: the 7x7/2 stem
is held as the equal 4x4/1 convolution over 2x2 space-to-depth blocks
(the zero taps of the padded 8x8 kernel are weights and learn).

``mode`` as in the GPT-2 reference: ``float32`` is the reference,
``bfloat16`` and ``float8`` are the lower precisions of the control.
"""

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 2e-5
UNITS = {50: (3, 4, 6, 3)}

MODES = ("float32", "bfloat16", "float8")


def _arith(mode):
    if mode == "float32":
        return jnp.float32, (lambda a: a), jax.lax.Precision.HIGHEST
    if mode == "bfloat16":
        return jnp.bfloat16, (lambda a: a.astype(jnp.bfloat16)), None
    if mode == "float8":
        return (jnp.bfloat16,
                lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16),
                None)
    raise ValueError("unknown mode %r (one of %s)" % (mode, ", ".join(MODES)))


def logits(cfg, params, images, mode="float32"):
    """float32 class scores ``[N, classes]`` of ``images`` ``[N, 3, H, W]``
    in training mode (batch statistics)."""
    store, rnd, prec = _arith(mode)

    def conv(x, name, stride, pad):
        w = params[name + "_weight"]                     # [out, kh, kw, in]
        return jax.lax.conv_general_dilated(
            rnd(x), rnd(w), (stride, stride), [pad, pad],
            dimension_numbers=("NHWC", "OHWI", "NHWC"),
            precision=prec).astype(store)

    def bn(x, name, fixed_gamma=False):
        x32 = x.astype(jnp.float32)
        mean = x32.mean((0, 1, 2))
        var = ((x32 - mean) ** 2).mean((0, 1, 2))
        gamma = 1.0 if fixed_gamma else params[name + "_gamma"]
        y = (x32 - mean) / jnp.sqrt(var + BN_EPS) * gamma \
            + params[name + "_beta"]
        return y.astype(store)

    def unit(x, name, stride, dim_match):
        act1 = jax.nn.relu(bn(x, name + "_bn1"))
        y = conv(act1, name + "_conv1", 1, (0, 0))
        y = jax.nn.relu(bn(y, name + "_bn2"))
        y = conv(y, name + "_conv2", stride, (1, 1))
        y = jax.nn.relu(bn(y, name + "_bn3"))
        y = conv(y, name + "_conv3", 1, (0, 0))
        short = x if dim_match else conv(act1, name + "_sc", stride, (0, 0))
        return y + short

    x = jnp.transpose(images, (0, 2, 3, 1)).astype(store)
    x = bn(x, "bn_data", fixed_gamma=True)
    n, h, w, c = x.shape
    x = x.reshape(n, h // 2, 2, w // 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(n, h // 2, w // 2, 4 * c)
    x = conv(x, "conv0", 1, (2, 1))
    x = jax.nn.relu(bn(x, "bn0"))
    x = jax.lax.reduce_window(
        x, np.array(-np.inf, x.dtype), jax.lax.max, (1, 3, 3, 1),
        (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    for stage, count in enumerate(UNITS[cfg["num_layers"]]):
        for j in range(count):
            step = jax.checkpoint(
                lambda x, name="stage%d_unit%d" % (stage + 1, j + 1),
                stride=2 if (stage > 0 and j == 0) else 1,
                dim_match=j > 0: unit(x, name, stride, dim_match))
            x = step(x)
    x = jax.nn.relu(bn(x, "bn1")).astype(jnp.float32).mean((1, 2))
    return jnp.dot(rnd(x.astype(store)), rnd(params["fc1_weight"]).T,
                   precision=prec, preferred_element_type=jnp.float32) \
        + params["fc1_bias"]


def loss_sum(cfg, params, batch, mode="float32"):
    """Sum over the batch's images of the cross-entropy of the label.
    BatchNorm ties the rows together: the batch is never split."""
    lg = logits(cfg, params, batch["data"], mode)
    logp = jax.nn.log_softmax(lg, axis=-1)
    label = batch["softmax_label"].astype(jnp.int32)
    return -jnp.take_along_axis(logp, label[:, None], axis=-1).sum()


def loss_units(cfg, batch):
    return batch["data"].shape[0]
