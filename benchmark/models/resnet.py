"""The ResNet family through the program: ``models.resnet`` trained by
``ShardedTrainer``, bf16 NHWC with the space-to-depth stem, as
``bench.py``'s default lane and ``chip_smoke.py`` build it.

The weights are the benchmark's input, made on the device from the seed
in one jitted call under the program's checkpoint names.
"""

import numpy as np

UNITS = {50: (3, 4, 6, 3)}
FILTERS = (64, 256, 512, 1024, 2048)


def weight_shapes(cfg):
    classes = cfg["num_classes"]
    shapes = {"bn_data_gamma": (3,), "bn_data_beta": (3,),
              "conv0_weight": (FILTERS[0], 4, 4, 12),
              "bn0_gamma": (FILTERS[0],), "bn0_beta": (FILTERS[0],)}
    in_ch = FILTERS[0]
    for stage, count in enumerate(UNITS[cfg["num_layers"]]):
        filters = FILTERS[stage + 1]
        width = filters // 4
        for j in range(count):
            p = "stage%d_unit%d_" % (stage + 1, j + 1)
            shapes.update({
                p + "bn1_gamma": (in_ch,), p + "bn1_beta": (in_ch,),
                p + "conv1_weight": (width, 1, 1, in_ch),
                p + "bn2_gamma": (width,), p + "bn2_beta": (width,),
                p + "conv2_weight": (width, 3, 3, width),
                p + "bn3_gamma": (width,), p + "bn3_beta": (width,),
                p + "conv3_weight": (filters, 1, 1, width)})
            if j == 0:
                shapes[p + "sc_weight"] = (filters, 1, 1, in_ch)
            in_ch = filters
    shapes.update({"bn1_gamma": (in_ch,), "bn1_beta": (in_ch,),
                   "fc1_weight": (classes, in_ch), "fc1_bias": (classes,)})
    return shapes


def weight_maker(cfg):
    """float32 weights from the seed, on the device, as a function of the
    key (made inside whatever program needs them):
    convolutions normal with variance 2 / fan-in (He et al.), the
    classifier normal(0, 0.01), BatchNorm gains 1, every bias 0."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if name.endswith("_gamma"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith(("_beta", "_bias")):
                out[name] = jnp.zeros(shape, jnp.float32)
            elif name == "fc1_weight":
                out[name] = 0.01 * jax.random.normal(k, shape, jnp.float32)
            else:
                fan_in = shape[1] * shape[2] * shape[3]
                out[name] = (2.0 / fan_in) ** 0.5 * jax.random.normal(
                    k, shape, jnp.float32)
        return out

    return make


def make_weights(cfg, seed, shardings=None):
    """The seed's weights on the device, one jitted call."""
    import jax

    return jax.jit(weight_maker(cfg), out_shardings=shardings)(
        weight_key(seed))


def weight_key(seed):
    import jax

    return jax.random.PRNGKey(seed % (2 ** 31))


def build_trainer(cfg, train, mesh):
    from mxnet_tpu.models import resnet
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    batch, size = train["batch"], cfg["image_size"]
    sym = resnet.get_symbol(
        num_classes=cfg["num_classes"], num_layers=cfg["num_layers"],
        image_shape=(3, size, size), dtype=train["dtype"],
        layout="NHWC", stem="s2d")
    opt = train["optimizer"]
    trainer = ShardedTrainer(
        sym, mesh, data_shapes={"data": (batch, 3, size, size)},
        label_shapes={"softmax_label": (batch,)},
        learning_rate=opt["learning_rate"], momentum=opt["momentum"],
        wd=opt.get("wd", 0.0), rescale_grad=1.0 / batch)
    return trainer, {"items_per_step": batch, "label": "softmax_label"}


def weights_for_training(cfg, train):
    return cfg


def batch_maker(cfg, train, seed):
    """A new seeded host batch at every call.  The pixels of a batch are
    a window, at an offset drawn from the seed, into a pool of random
    images made once before the window (two batches' worth): the bytes
    differ from step to step, and making them costs nothing in the
    loop.  Labels are drawn fresh."""
    rng = np.random.RandomState(seed % (2 ** 32))
    batch, size = train["batch"], cfg["image_size"]
    pool = rng.random_sample((2 * batch, 3, size, size)).astype(np.float32)
    pool = pool * 2.0 - 1.0
    classes = cfg["num_classes"]

    def make():
        at = rng.randint(0, batch + 1)
        return {"data": pool[at:at + batch],
                "softmax_label": rng.randint(0, classes, (batch,))
                .astype(np.float32)}

    return make


def train_flops_per_item(cfg, train):
    from benchmark import flops

    return flops.resnet_train_flops_per_image(cfg)
