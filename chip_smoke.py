"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once through the entry points a user calls,
at the full width of the models the repo trains and serves, in ONE
process (a chip belongs to one process at a time):

  train_lm      models.transformer.get_symbol 12L d1024 h16 T2048 b8
                vocab 32000 bf16 through ShardedTrainer on a 1-device
                mesh, as bench.py's transformer lane builds it
  train_resnet  ResNet-50 b128 bf16 NHWC s2d through ShardedTrainer, as
                bench.py's default lane builds it
  serve_lm      LMBackend at the same LM width behind GenerationScheduler
                and the HTTP front end; four concurrent /v1/generate
                requests checked against a plain full forward
  fit           the README's first code block: Module.fit on mx.tpu(0)
                and a checkpoint round trip

``--chips 4`` runs instead, and only: the train_lm configuration at 4
layers on a data=2 x model=2 mesh of four chips, and the same seed on one
chip, and compares the losses.

Every phase prints one JSON line: the device it ran on, compile seconds
against step seconds, the compile cache's hits and misses and which
native library loaded; a last row says which hot paths lower to their
Pallas kernel on this platform.  The script exits non-zero if a phase
fails, if the platform is not ``tpu`` or if one of them lowers without
its kernel there.  On success, and
only then, the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The timings printed are smoke timings on the named device, not
benchmark results.  Weights and data are random, made from ``--seed``.
"""

import argparse
import gc
import http.client
import json
import sys
import tempfile
import threading
import time

import numpy as np

#: The widths the phases run at.  Depth may be cut, widths never.
FULL = {
    "steps": 4,
    "lm": dict(layers=12, d_model=1024, heads=16, seq=2048, batch=8,
               vocab=32000, dtype="bfloat16"),
    "lm_sharded_layers": 4,
    "resnet": dict(layers=50, image=224, batch=128, classes=1000,
                   layout="NHWC", stem="s2d"),
    "serve": dict(layers=12, d_model=1024, heads=16, vocab=32000,
                  seq_len=2048, prompts=(512, 512, 1536, 1536),
                  new_tokens=32, block_size=16),
    "fit": dict(epochs=10, context="tpu"),
}

#: Test-only sizes (tests/test_chip_smoke.py hands them to ``main``):
#: the same phases and checks in seconds on a CPU.
TINY = {
    "steps": 3,
    "lm": dict(layers=2, d_model=64, heads=2, seq=64, batch=4,
               vocab=256, dtype="float32"),
    "lm_sharded_layers": 1,
    "resnet": dict(layers=8, image=28, batch=4, classes=16,
                   layout="NCHW", stem="conv7"),
    "serve": dict(layers=2, d_model=64, heads=2, vocab=256, seq_len=64,
                  prompts=(8, 8, 24, 24), new_tokens=4, block_size=8),
    "fit": dict(epochs=10, context="cpu"),
}

#: Stated tolerances.  Serving runs fp32 at the chip's default matmul
#: precision (bf16 passes) and its prefill is the flash kernel, so its
#: logits (std ~0.6 at this init) are held to a HIGHEST-precision plain
#: forward within LOGIT_ATOL (0.023 was observed on a v5e).  The sharded
#: run reorders bf16 reductions across chips; its loss (~11.2) is held to
#: the one-chip run within SHARDED_LOSS_RTOL (8e-6 was observed).
LOGIT_ATOL = 0.05
SHARDED_LOSS_RTOL = 1e-3


class SmokeFailure(Exception):
    """A phase ran and its result is wrong."""


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class _CacheCounter(object):
    """Counts what JAX's persistent compilation cache was asked
    (``requests``), found (``hits``) and wrote (``misses``: a compile
    under a second is looked up but never written)."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax

        self.counts = dict.fromkeys(self._EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        name = self._EVENTS.get(event)
        if name is not None:
            self.counts[name] += 1

    def since(self, before):
        return {k: v - before[k] for k, v in self.counts.items()}


def _device():
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _kernels_chosen():
    """Whether each hot path with a Pallas kernel lowers to it on this
    platform, at the widths the phases above ran (lowering only: a
    fraction of a second, nothing runs)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention, paged_attention

    def lowered_with_kernel(fn, *shapes):
        args = [jax.ShapeDtypeStruct(shape, dtype)
                for shape, dtype in shapes]
        return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()

    f32, i32 = jnp.float32, jnp.int32
    qkv = ((1, 16, 1536, 64), f32)
    step, pool = ((4, 16, 64), f32), ((64, 16, 16, 64), f32)
    return {
        "flash_attention": lowered_with_kernel(
            lambda q, k, v: attention.flash_attention(q, k, v, causal=True),
            *[((8, 16, 2048, 64), jnp.bfloat16)] * 3),
        "stable_causal_attention": lowered_with_kernel(
            attention.stable_causal_attention, qkv, qkv, qkv),
        "paged_decode_attention": lowered_with_kernel(
            paged_attention.paged_decode_attention, step, step, step,
            pool, pool, ((4, 8), i32), ((4,), i32)),
    }


def _run_phase(name, fn, cache):
    """Run one phase, print its line, return whether it passed.  A
    failure is printed with its traceback and fails the run; it is
    never turned into a pass."""
    import traceback

    from mxnet_tpu import _native

    before = dict(cache.counts)
    t0 = time.perf_counter()
    row = {"phase": name}
    try:
        row.update(fn())
        row["passed"] = True
    except Exception as exc:  # noqa: BLE001 — reported, and the run fails
        traceback.print_exc()
        row["passed"] = False
        row["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:300])
    row.update({
        "device": _device(), "wall_s": round(time.perf_counter() - t0, 2),
        "compile_cache": cache.since(before),
        "native": _native.status(),
    })
    print(json.dumps(row), flush=True)
    gc.collect()
    return row["passed"]


# ----------------------------------------------------------------------
# training phases


def _xent():
    """Mean cross-entropy of SoftmaxOutput's probabilities, on device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loss(p, y):
        picked = jnp.take_along_axis(
            p, y.reshape(-1, 1).astype(jnp.int32), axis=1)
        return -jnp.mean(jnp.log(picked.astype(jnp.float32)))

    return loss


def _train(trainer, host_batch, label_name, steps, seed):
    """init, place_batch, ``steps`` steps on a repeated batch, each read
    back with block_until_ready.  Returns the row and the placed state
    (for the sharding checks)."""
    import jax

    params, moms, aux = trainer.init(seed=seed)
    arrays = trainer.place_batch(host_batch)
    step = trainer.step_fn()
    key = jax.random.PRNGKey(seed)
    xent = _xent()
    losses, secs = [], []
    fetch_after_block = None
    for _ in range(steps):
        t0 = time.perf_counter()
        outs, params, moms, aux = step(params, moms, aux, arrays, key)
        jax.block_until_ready(outs)
        secs.append(time.perf_counter() - t0)
        # if block_until_ready really blocked, nothing is left to wait
        # for and a one-element fetch costs a round trip, not a step
        t0 = time.perf_counter()
        np.asarray(outs[0][(0,) * outs[0].ndim])
        fetch_after_block = time.perf_counter() - t0
        losses.append(float(xent(outs[0], arrays[label_name])))
    _check(all(np.isfinite(losses)), "loss not finite: %r" % (losses,))
    _check(losses[-1] < losses[0],
           "loss did not fall on a repeated batch: %r" % (losses,))
    step_s = float(np.median(secs[1:]))
    _check(fetch_after_block < max(0.5 * step_s, 0.05),
           "block_until_ready returned early: a fetch after it took "
           "%.3fs of a %.3fs step" % (fetch_after_block, step_s))
    row = {"losses": [round(l, 6) for l in losses],
           "first_step_s": round(secs[0], 2),        # compile + one step
           "step_s": round(step_s, 4),
           "compile_s": round(secs[0] - step_s, 2),
           "fetch_after_block_s": round(fetch_after_block, 5)}
    return row, params, arrays


def _lm_trainer(cfg, mesh, layers=None):
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    batch, seq = cfg["batch"], cfg["seq"]
    sym = transformer.get_symbol(
        num_classes=cfg["vocab"], seq_len=seq, num_embed=cfg["d_model"],
        num_heads=cfg["heads"], num_layers=layers or cfg["layers"],
        dtype=cfg["dtype"])
    return ShardedTrainer(
        sym, mesh, data_shapes={"data": (batch, seq)},
        label_shapes={"softmax_label": (batch, seq)},
        type_dict={"data": "int32"}, learning_rate=1e-3, momentum=0.9,
        rescale_grad=1.0 / (batch * seq))


def _lm_batch(cfg, seed):
    rng = np.random.RandomState(seed)
    shape = (cfg["batch"], cfg["seq"])
    return {"data": rng.randint(0, cfg["vocab"], shape).astype(np.int32),
            "softmax_label": rng.randint(0, cfg["vocab"], shape)
            .astype(np.float32)}


def phase_train_lm(sizes, seed):
    import jax
    from jax.sharding import Mesh

    cfg = sizes["lm"]
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))
    row, _, _ = _train(_lm_trainer(cfg, mesh), _lm_batch(cfg, seed),
                       "softmax_label", sizes["steps"], seed)
    row["config"] = cfg
    return row


def phase_train_resnet(sizes, seed):
    import jax
    from jax.sharding import Mesh

    from mxnet_tpu.models import resnet
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    cfg = sizes["resnet"]
    batch, image = cfg["batch"], cfg["image"]
    sym = resnet.get_symbol(
        num_classes=cfg["classes"], num_layers=cfg["layers"],
        image_shape=(3, image, image), dtype="bfloat16",
        layout=cfg["layout"], stem=cfg["stem"])
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    trainer = ShardedTrainer(
        sym, mesh, data_shapes={"data": (batch, 3, image, image)},
        label_shapes={"softmax_label": (batch,)},
        momentum=0.9, learning_rate=0.1, wd=1e-4, rescale_grad=1.0 / batch)
    rng = np.random.RandomState(seed)
    host = {"data": rng.uniform(-1, 1, (batch, 3, image, image))
            .astype(np.float32),
            "softmax_label": rng.randint(0, cfg["classes"], (batch,))
            .astype(np.float32)}
    row, _, _ = _train(trainer, host, "softmax_label", sizes["steps"], seed)
    row["config"] = cfg
    return row


# ----------------------------------------------------------------------
# four chips: the sharded LM step against the same seed on one chip


def _spread(tree, n_devices):
    """How a placed tree lies on the mesh, from ``addressable_shards``:
    the devices every leaf touches, and how many leaves hold only a
    slice of themselves on each device."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    devices = set()
    sliced = 0
    for leaf in leaves:
        shards = leaf.addressable_shards
        here = {s.device for s in shards}
        _check(len(here) == n_devices,
               "an array lies on %d device(s), not %d" % (len(here),
                                                         n_devices))
        devices |= here
        sliced += any(s.data.shape != leaf.shape for s in shards)
    return {"arrays": len(leaves), "devices": len(devices),
            "sliced": sliced}


def phase_sharded_lm(sizes, seed):
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    _check(len(devs) >= 4, "--chips 4 needs four devices, JAX found %d"
           % len(devs))
    cfg, layers = sizes["lm"], sizes["lm_sharded_layers"]
    batch = _lm_batch(cfg, seed)
    mesh4 = Mesh(np.array(devs[:4]).reshape(2, 2), ("data", "model"))
    row4, params, arrays = _train(_lm_trainer(cfg, mesh4, layers), batch,
                                  "softmax_label", 3, seed)
    p_spread = _spread(params, 4)
    b_spread = _spread(arrays, 4)
    _check(p_spread["sliced"] > 0,
           "no parameter is sliced over the model axis")
    _check(b_spread["sliced"] == b_spread["arrays"],
           "the batch is not sliced over the data axis")
    del params, arrays
    gc.collect()
    mesh1 = Mesh(np.array(devs[:1]).reshape(1, 1), ("data", "model"))
    row1, _, _ = _train(_lm_trainer(cfg, mesh1, layers), batch,
                        "softmax_label", 3, seed)
    for a, b in zip(row4["losses"], row1["losses"]):
        _check(abs(a - b) <= SHARDED_LOSS_RTOL * abs(b),
               "sharded losses %r differ from one-chip losses %r by more "
               "than rtol %g" % (row4["losses"], row1["losses"],
                                 SHARDED_LOSS_RTOL))
    return {"mesh": {"data": 2, "model": 2}, "four_chips": row4, "one_chip": row1,
            "params_spread": p_spread, "batch_spread": b_spread,
            "loss_rtol": SHARDED_LOSS_RTOL,
            "config": dict(cfg, layers=layers)}


# ----------------------------------------------------------------------
# serving phase


def _plain_forward(cfg):
    """A straightforward fp32 forward of the LM at HIGHEST matmul
    precision, written against the checkpoint names only — the
    reference the served logits are held to.  Returns the jitted
    ``(params, tokens [T]) -> logits [T, V]``."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    heads = cfg["num_heads"]

    def ln(params, x, name):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return ((x - mean) / jnp.sqrt(var + 1e-5)
                * params[name + "_gamma"] + params[name + "_beta"])

    def forward(params, tokens):
        t = tokens.shape[0]
        x = params["embed_weight"][tokens] + params["pos_embed_weight"][0, :t]
        mask = jnp.tril(jnp.ones((t, t), bool))
        for i in range(cfg["num_layers"]):
            p = "l%d_" % i
            qkv = jnp.dot(ln(params, x, p + "ln1"),
                          params[p + "attn_qkv_weight"].T, precision=hi)
            q, k, v = [a.reshape(t, heads, -1).transpose(1, 0, 2)
                       for a in jnp.split(qkv, 3, axis=-1)]
            s = jnp.einsum("hqd,hkd->hqk", q, k, precision=hi) \
                / np.sqrt(q.shape[-1])
            w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            a = jnp.einsum("hqk,hkd->hqd", w, v, precision=hi)
            a = a.transpose(1, 0, 2).reshape(t, -1)
            x = x + jnp.dot(a, params[p + "attn_out_weight"].T, precision=hi)
            h = jnp.dot(ln(params, x, p + "ln2"),
                        params[p + "ffn1_weight"].T,
                        precision=hi) + params[p + "ffn1_bias"]
            x = x + jnp.dot(jax.nn.gelu(h), params[p + "ffn2_weight"].T,
                            precision=hi) + params[p + "ffn2_bias"]
        x = ln(params, x, "final_ln")
        return jnp.dot(x, params["pred_weight"].T, precision=hi) \
            + params["pred_bias"]

    return jax.jit(forward)


def _post_generate(port, model, prompt, new_tokens, out, idx):
    """One client: POST /v1/generate, read the token stream."""
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        conn.request("POST", "/v1/generate",
                     json.dumps({"model": model, "prompt": prompt,
                                 "max_new_tokens": new_tokens}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        streamed, tail = [], None
        for raw in resp:
            line = json.loads(raw)
            if line.get("done"):
                tail = line
                break
            streamed.append(line["token"])
        out[idx] = (resp.status, streamed, tail)
        conn.close()
    except Exception as exc:  # noqa: BLE001 — reported by the caller
        out[idx] = exc


def phase_serve_lm(sizes, seed):
    from mxnet_tpu import observability as obs
    from mxnet_tpu import serving
    from mxnet_tpu.models import transformer as tfm

    cfg_s = sizes["serve"]
    model = "smoke_lm"
    cfg = tfm.lm_config(num_classes=cfg_s["vocab"], seq_len=cfg_s["seq_len"],
                        num_embed=cfg_s["d_model"],
                        num_heads=cfg_s["heads"],
                        num_layers=cfg_s["layers"])
    params = tfm.init_lm_params(cfg, seed=seed)
    prompts_len, new = cfg_s["prompts"], cfg_s["new_tokens"]
    blk = cfg_s["block_size"]
    num_blocks = sum(-(-(n + new) // blk) for n in prompts_len) + 8

    class Recording(serving.LMBackend):
        """The served backend, unchanged, keeping the logits it
        returns so they can be held to the reference afterwards."""

        def __init__(self, *args, **kwargs):
            serving.LMBackend.__init__(self, *args, **kwargs)
            self.prefills, self.decodes = [], []

        def prefill(self, tokens, length):
            out = serving.LMBackend.prefill(self, tokens, length)
            self.prefills.append((np.array(tokens[:length]), out[0]))
            return out

        def decode(self, tokens, positions, block_tables, context_lens):
            out = serving.LMBackend.decode(self, tokens, positions,
                                           block_tables, context_lens)
            self.decodes.append((np.array(tokens), np.array(positions),
                                 out[0]))
            return out

    backend = Recording(params, cfg, block_size=blk, num_blocks=num_blocks,
                        model=model)
    sched = serving.GenerationScheduler(name="smoke")
    sched.register(model, backend, decode_buckets=[1, 2, 4],
                   prefill_buckets=sorted(set(prompts_len)))
    t0 = time.perf_counter()
    warm_shapes = sched.warmup(model)
    warm_s = time.perf_counter() - t0
    backend.prefills, backend.decodes = [], []      # warmup's zeros
    compiles = obs.REGISTRY.get("generation_compiles_total").labels(model)
    warm_count = compiles.value
    fe = serving.start_frontend(sched, timeout=300.0)
    try:
        rng = np.random.RandomState(seed + 1)
        prompts = [rng.randint(0, cfg_s["vocab"], n).tolist()
                   for n in prompts_len]
        results = [None] * len(prompts)
        clients = [threading.Thread(
            target=_post_generate,
            args=(fe.port, model, p, new, results, i))
            for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=600)
        serve_s = time.perf_counter() - t0
        _check(not any(c.is_alive() for c in clients),
               "a client is still waiting after 600s")
        stats = sched.stats(model)
        recompiles = int(compiles.value - warm_count)
    finally:
        fe.close()
        sched.close()

    for i, res in enumerate(results):
        _check(not isinstance(res, Exception),
               "request %d failed: %r" % (i, res))
        status, streamed, tail = res
        _check(status == 200 and tail is not None
               and tail.get("finish_reason") == "length"
               and tail["tokens"] == streamed and len(streamed) == new,
               "request %d: status %s, %d tokens, tail %r"
               % (i, status, len(streamed), tail))
    _check(recompiles == 0,
           "%d compile(s) after warmup" % recompiles)

    # the reference: a plain forward over prompt + generated tokens
    # gives, in one pass, the logits behind every token of a request;
    # row j of refs[i] is what produced request i's j-th new token
    plain = _plain_forward(cfg)
    refs = [np.asarray(plain(params, np.asarray(p + res[1][:-1], np.int32))
                       )[len(p) - 1:]
            for p, res in zip(prompts, results)]
    _check(len(backend.prefills) == len(prompts),
           "%d prefills for %d requests" % (len(backend.prefills),
                                            len(prompts)))
    first_exact, prefill_err = 0, 0.0
    for toks, logits in backend.prefills:
        i = _index(prompts, toks)
        row, first = refs[i][0], results[i][1][0]
        prefill_err = max(prefill_err, float(np.abs(logits - row).max()))
        first_exact += int(first == int(row.argmax()))
        _check(row[first] >= row.max() - LOGIT_ATOL,
               "first token %d is not the reference's argmax within %g "
               "(its logit %.4f, the maximum %.4f)"
               % (first, LOGIT_ATOL, row[first], row.max()))
    _check(prefill_err <= LOGIT_ATOL,
           "prefill logits differ from the plain forward by %.4f > %g"
           % (prefill_err, LOGIT_ATOL))

    # a decode row belongs to the (request, step) whose position and
    # consumed token it carries — the nearest one where two requests
    # agree on both; the pad rows of a bucket match nothing
    want = {}
    for i, (p, res) in enumerate(zip(prompts, results)):
        for j, tok in enumerate(res[1][:-1]):
            want.setdefault((len(p) + j, tok), []).append((i, j + 1))
    got = [{} for _ in prompts]
    decode_err = 0.0
    for tokens, positions, logits in backend.decodes:
        for r in range(len(tokens)):
            cands = want.get((int(positions[r]), int(tokens[r])), ())
            errs = [(float(np.abs(logits[r] - refs[i][j]).max()), i, j)
                    for i, j in cands]
            if errs:
                err, i, j = min(errs)
                got[i][j] = logits[r]
                decode_err = max(decode_err, err)
    _check(all(len(g) == new - 1 for g in got),
           "decode rows matched per request: %r, expected %d each"
           % ([len(g) for g in got], new - 1))
    _check(decode_err <= LOGIT_ATOL,
           "decode logits differ from the plain forward by %.4f > %g"
           % (decode_err, LOGIT_ATOL))

    # ROADMAP D2: is a decode step's logits row bit-equal to the same
    # row of the system's own full forward on this device?
    p0, g0 = prompts[0], results[0][1]
    own = backend.infer(
        {"data": np.asarray(p0 + g0[:-1], np.int32)[None]})[0][0][0]
    own = own[len(p0) - 1:]
    own_err = max(float(np.abs(got[0][j] - own[j]).max())
                  for j in got[0])
    bitwise = all(bool((got[0][j] == own[j]).all()) for j in got[0])
    return {
        "requests": len(prompts), "prompt_tokens": list(prompts_len),
        "new_tokens": new, "warmup_shapes": warm_shapes,
        "compile_s": round(warm_s, 2), "serve_s": round(serve_s, 2),
        "decode_steps": stats["steps"],
        "max_step_rows": stats["max_step_rows"],
        "recompiles_after_warmup": recompiles,
        "first_token_exact": "%d/%d" % (first_exact, len(prompts)),
        "prefill_logit_err": round(prefill_err, 5),
        "decode_logit_err": round(decode_err, 5),
        "logit_atol": LOGIT_ATOL,
        "decode_bitwise_vs_own_forward": bitwise,
        "decode_vs_own_forward_err": round(own_err, 8),
        "config": {k: v for k, v in cfg_s.items()},
    }


def _index(prompts, tokens):
    """Which request a recorded prefill belongs to (prompts differ)."""
    for i, p in enumerate(prompts):
        if len(p) == len(tokens) and p == tokens.tolist():
            return i
    raise SmokeFailure("a prefill ran on tokens no request sent")


# ----------------------------------------------------------------------
# the library user's path


def phase_fit(sizes, seed):
    import mxnet_tpu as mx

    ctx = mx.Context(sizes["fit"]["context"], 0)
    rng = np.random.RandomState(seed)
    centers = rng.randn(4, 10) * 3.0
    labels = rng.randint(0, 4, 400)
    data = (centers[labels] + rng.randn(400, 10)).astype(np.float32)
    labels = labels.astype(np.float32)
    train = mx.io.NDArrayIter(data, labels, batch_size=40, shuffle=True)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=32,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(net, num_hidden=4, name="fc2"),
        name="softmax")
    t0 = time.perf_counter()
    mod = mx.mod.Module(net, context=ctx)
    mod.fit(train, num_epoch=sizes["fit"]["epochs"], optimizer="sgd",
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
            initializer=mx.initializer.Xavier())
    fit_s = time.perf_counter() - t0

    def score(module):
        it = mx.io.NDArrayIter(data, labels, batch_size=40)
        return module.score(it, "acc")[0][1]

    acc = score(mod)
    _check(acc > 0.95, "Module.fit reached accuracy %.3f" % acc)
    with tempfile.TemporaryDirectory() as tmp:
        prefix = tmp + "/smoke"
        mod.save_checkpoint(prefix, 1)
        sym2, args2, aux2 = mx.model.load_checkpoint(prefix, 1)
    mod2 = mx.mod.Module(sym2, context=ctx)
    mod2.bind(data_shapes=[("data", (40, 10))],
              label_shapes=[("softmax_label", (40,))], for_training=False)
    mod2.set_params(args2, aux2)
    acc2 = score(mod2)
    _check(abs(acc2 - acc) < 1e-6,
           "checkpoint round trip changed accuracy %.6f -> %.6f"
           % (acc, acc2))
    return {"context": str(ctx), "accuracy": round(float(acc), 4),
            "fit_s": round(fit_s, 2)}


# ----------------------------------------------------------------------


def main(argv=None, sizes=None):
    """Run the smoke; returns the exit code.  ``sizes`` is for the
    tests: with it the phases run at that size on whatever platform JAX
    finds, so a CPU can rehearse them — the platform check still fails
    the run.  Without it a missing chip fails before any phase."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded LM step on a 2x2 mesh and "
                         "its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    ns = ap.parse_args(argv)

    import jax

    from mxnet_tpu import compile_cache

    device = _device()
    on_chip = device["platform"] == "tpu"
    if not on_chip and sizes is None:
        print("chip_smoke: JAX found no TPU (platform %r): nothing ran"
              % device["platform"], file=sys.stderr)
        return 2
    sizes = sizes or FULL
    print(json.dumps({"device": device, "jax": jax.__version__,
                      "compile_cache_dir": compile_cache.enable(),
                      "chips": ns.chips, "seed": ns.seed}), flush=True)
    cache = _CacheCounter()
    if ns.chips == 4:
        phases = [("sharded_lm", phase_sharded_lm)]
    else:
        phases = [("train_lm", phase_train_lm),
                  ("train_resnet", phase_train_resnet),
                  ("serve_lm", phase_serve_lm),
                  ("fit", phase_fit)]
    failed = [name for name, fn in phases
              if not _run_phase(name, lambda f=fn: f(sizes, ns.seed), cache)]
    kernels = _kernels_chosen()
    print(json.dumps({"kernels_chosen": kernels}), flush=True)
    without = sorted(k for k, chosen in kernels.items() if not chosen)
    if failed or not on_chip or without:
        print("chip_smoke: FAILED (phases failed: %s; lowered without "
              "their kernel: %s; platform: %s)"
              % (failed or "none", without or "none", device["platform"]),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
