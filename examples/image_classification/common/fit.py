"""Shared training harness (behavioral parity: reference
``example/image-classification/common/fit.py:45-89`` — same CLI surface with
``--tpus`` in place of ``--gpus``, kvstore creation, lr schedule from epoch
steps, checkpointing, top-k metrics, Speedometer logging)."""

import argparse
import logging
import os
import time

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))))
import mxnet_tpu as mx


def _get_lr_scheduler(args, kv):
    if not args.lr_factor or args.lr_factor >= 1:
        return (args.lr, None)
    epoch_size = _get_epoch_size(args, kv)
    begin_epoch = args.load_epoch if args.load_epoch else 0
    step_epochs = [int(l) for l in args.lr_step_epochs.split(",")]
    lr = args.lr
    for s in step_epochs:
        if begin_epoch >= s:
            lr *= args.lr_factor
    if lr != args.lr:
        logging.info("Adjust learning rate to %e for epoch %d", lr, begin_epoch)
    steps = [
        epoch_size * (x - begin_epoch)
        for x in step_epochs
        if x - begin_epoch > 0
    ]
    return (lr, mx.lr_scheduler.MultiFactorScheduler(step=steps,
                                                     factor=args.lr_factor))


def _get_epoch_size(args, kv):
    return int(args.num_examples / args.batch_size / kv.num_workers)


def _load_model(args, rank=0):
    if args.load_epoch is None or args.model_prefix is None:
        return (None, None, None)
    model_prefix = args.model_prefix
    if rank > 0 and os.path.exists("%s-%d-symbol.json" % (model_prefix, rank)):
        model_prefix += "-%d" % rank
    sym, arg_params, aux_params = mx.model.load_checkpoint(
        model_prefix, args.load_epoch)
    logging.info("Loaded model %s_%04d.params", model_prefix, args.load_epoch)
    return (sym, arg_params, aux_params)


def _save_model(args, rank=0):
    if args.model_prefix is None:
        return None
    dst_dir = os.path.dirname(args.model_prefix)
    if dst_dir and not os.path.isdir(dst_dir):
        os.makedirs(dst_dir, exist_ok=True)
    return mx.callback.do_checkpoint(
        args.model_prefix if rank == 0 else "%s-%d" % (args.model_prefix, rank))


def add_fit_args(parser):
    """Add training CLI args (reference ``fit.py:add_fit_args`` surface)."""
    train = parser.add_argument_group("Training", "model training")
    train.add_argument("--network", type=str, help="the neural network to use")
    train.add_argument("--num-layers", type=int,
                       help="number of layers in the neural network")
    train.add_argument("--tpus", type=str, default=None,
                       help="list of tpus to run, e.g. 0 or 0,2,5. empty means"
                            " using first device (cpu fallback off-tpu)")
    train.add_argument("--kv-store", type=str, default="device",
                       help="key-value store type")
    train.add_argument("--num-epochs", type=int, default=100,
                       help="max num of epochs")
    train.add_argument("--lr", type=float, default=0.1, help="initial lr")
    train.add_argument("--lr-factor", type=float, default=0.1,
                       help="the ratio to reduce lr on each step")
    train.add_argument("--lr-step-epochs", type=str, default="30,60",
                       help="the epochs to reduce the lr, e.g. 30,60")
    train.add_argument("--optimizer", type=str, default="sgd", help="optimizer")
    train.add_argument("--mom", type=float, default=0.9, help="momentum")
    train.add_argument("--wd", type=float, default=0.0001, help="weight decay")
    train.add_argument("--batch-size", type=int, default=128, help="batch size")
    train.add_argument("--disp-batches", type=int, default=20,
                       help="show progress for every n batches")
    train.add_argument("--model-prefix", type=str,
                       help="model prefix for checkpoints")
    train.add_argument("--monitor", dest="monitor", type=int, default=0,
                       help="log network parameters every N iters if larger than 0")
    train.add_argument("--load-epoch", type=int,
                       help="load the model on an epoch using the model-prefix")
    train.add_argument("--top-k", type=int, default=0,
                       help="report the top-k accuracy. 0 means no report")
    train.add_argument("--test-io", type=int, default=0,
                       help="1 means test reading speed without training")
    train.add_argument("--dtype", type=str, default="float32",
                       help="float32 or bfloat16")
    return train


def get_devices(args):
    """``--tpus`` -> context list (the reference's ``--gpus`` mapping)."""
    return mx.context.devices_from_arg(args.tpus)


def fit(args, network, data_loader, **kwargs):
    """Train ``network`` on data from ``data_loader(args, kv)``."""
    mx.compile_cache.enable()
    kv = mx.kvstore.create(args.kv_store)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)-15s Node[" + str(kv.rank) + "] %(message)s")
    logging.info("start with arguments %s", args)

    (train, val) = data_loader(args, kv)
    if args.test_io:
        tic = time.time()
        for i, batch in enumerate(train):
            for j in batch.data:
                j.wait_to_read()
            if (i + 1) % args.disp_batches == 0:
                logging.info("Batch [%d]\tSpeed: %.2f samples/sec", i,
                             args.disp_batches * args.batch_size / (time.time() - tic))
                tic = time.time()
        return

    sym, arg_params, aux_params = _load_model(args, kv.rank)
    if sym is not None:
        assert sym.tojson() == network.tojson()

    devs = get_devices(args)
    lr, lr_scheduler = _get_lr_scheduler(args, kv)

    model = mx.mod.Module(context=devs, symbol=network)

    optimizer_params = {
        "learning_rate": lr,
        "wd": args.wd,
        "lr_scheduler": lr_scheduler,
    }
    if args.optimizer in ("sgd", "nag", "dcasgd", "ccsgd", "sgld"):
        optimizer_params["momentum"] = args.mom

    monitor = mx.mon.Monitor(args.monitor, pattern=".*") if args.monitor > 0 else None

    initializer = mx.initializer.Xavier(rnd_type="gaussian",
                                        factor_type="in", magnitude=2)

    eval_metrics = ["accuracy"]
    if args.top_k > 0:
        eval_metrics.append(mx.metric.create("top_k_accuracy", top_k=args.top_k))

    batch_end_callbacks = [mx.callback.Speedometer(args.batch_size,
                                                   args.disp_batches)]
    if "batch_end_callback" in kwargs:
        cbs = kwargs.pop("batch_end_callback")
        batch_end_callbacks += cbs if isinstance(cbs, list) else [cbs]

    model.fit(
        train,
        begin_epoch=args.load_epoch if args.load_epoch else 0,
        num_epoch=args.num_epochs,
        eval_data=val,
        eval_metric=eval_metrics,
        kvstore=kv,
        optimizer=args.optimizer,
        optimizer_params=optimizer_params,
        initializer=initializer,
        arg_params=arg_params,
        aux_params=aux_params,
        batch_end_callback=batch_end_callbacks,
        epoch_end_callback=_save_model(args, kv.rank),
        allow_missing=True,
        monitor=monitor,
        **kwargs,
    )
    return model
