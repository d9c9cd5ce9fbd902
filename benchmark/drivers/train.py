"""Traffic of kind ``train``: optimizer steps on fresh seeded batches,
timed in whole segments.

Set-up builds one object — the compiled step with its state — drives it
from the seed through its first steps (the ones the reference follows),
warms it for whole segments, and hands that same object to the window.
A segment is ``segment_steps`` dispatched steps, each fed by its own
``place_batch`` as in ``fit``, ended by one ``block_until_ready``; the
window keeps starting segments until ``--seconds`` has passed and then
runs from the start of its first segment to the end of its last: the
run's reading is every step over all of that time.
"""

import gc
import time

import numpy as np

from benchmark import compare, segments


def build_mesh(devices, axes):
    from jax.sharding import Mesh

    names = tuple(axes)
    shape = tuple(axes[n] for n in names)
    if int(np.prod(shape)) != len(devices):
        raise ValueError("mesh %r needs %d device(s), the cell has %d"
                         % (axes, int(np.prod(shape)), len(devices)))
    return Mesh(np.array(devices).reshape(shape), names)


class Program(object):
    """The compiled step with its state: what set-up builds, checks and
    warms, and the window then drives."""

    def __init__(self, job, model, cfg, train):
        import jax
        import jax.numpy as jnp

        self.job, self.model, self.cfg, self.train = job, model, cfg, train
        self.mesh = build_mesh(job.devices, train["mesh"])
        self.trainer, self.info = model.build_trainer(cfg, train, self.mesh)
        self.wcfg = model.weights_for_training(cfg, train)
        self.weight_maker = model.weight_maker(self.wcfg)
        self.weight_key = model.weight_key(job.seed)
        self.pshard, mshard, ashard, _ = self.trainer._step_shardings()
        # the state, made on the device as the trainer shards it: the
        # seed's weights, zero momentum, BatchNorm's moving statistics
        self.params = self.weights()
        unknown = set(self.trainer.param_names) ^ set(self.params)
        if unknown:
            raise ValueError("weights and trainer disagree on: %s"
                             % sorted(unknown))
        self.moms = jax.jit(
            lambda: {n: jnp.zeros(s.shape, s.dtype) for n, s in
                     self.trainer.opt_state_struct().items()},
            out_shardings=mshard)()
        self.aux = jax.jit(
            lambda: {n: (jnp.ones if n.endswith("_var") else jnp.zeros)(
                shape, jnp.float32)
                for n, shape in self.trainer.aux_shapes.items()},
            out_shardings=ashard)()
        self.step = self.trainer.step_fn()
        self.key = jax.random.PRNGKey(job.seed % (2 ** 31))
        self.make_batch = model.batch_maker(cfg, train, job.seed)
        self.outs = self.arrays = None

        def xent(p, y):
            picked = jnp.take_along_axis(
                p, y.reshape(-1, 1).astype(jnp.int32), axis=1)
            return -jnp.mean(jnp.log(picked.astype(jnp.float32)))

        self._xent = jax.jit(xent)

    def weights(self):
        """The seed's weights, placed as the trainer shards them."""
        return self.model.make_weights(self.wcfg, self.job.seed,
                                       shardings=self.pshard)

    def one_step(self, batch=None):
        """place_batch + the step, as ``fit`` does for every batch."""
        spans = self.job.spans
        with spans.span("place_batch"):
            self.arrays = self.trainer.place_batch(
                batch if batch is not None else self.make_batch())
        self.outs = None          # the last step's outputs are not kept
        with spans.span("dispatch"):
            self.outs, self.params, self.moms, self.aux = self.step(
                self.params, self.moms, self.aux, self.arrays, self.key)

    def sync(self):
        import jax

        with self.job.spans.span("sync"):
            jax.block_until_ready((self.outs, self.params))

    def loss(self):
        return float(self._xent(self.outs[0], self.arrays[self.info["label"]]))

    def segment(self, steps):
        t0 = time.perf_counter()
        for _ in range(steps):
            self.one_step()
        self.sync()
        return time.perf_counter() - t0

    def free(self):
        self.params = self.moms = self.aux = self.outs = self.arrays = None
        self.step = self.trainer = None
        gc.collect()


def first_steps(prog, count):
    """Drive the program through its first ``count`` steps on rows that
    all differ; keep what the reference will be held against."""
    opt = prog.train["optimizer"]
    batches, losses, secs = [], [], []
    grad_norms = None
    for i in range(count):
        batch = prog.make_batch()
        batches.append({k: np.array(v) for k, v in batch.items()})
        t0 = time.perf_counter()
        prog.one_step(batch)
        prog.sync()
        secs.append(time.perf_counter() - t0)
        losses.append(prog.loss())
        if i == 0:
            names = list(prog.params)
            grad_norms = compare.first_gradient_norms(
                {n: prog.moms[n] for n in names}, prog.weight_maker,
                prog.weight_key, opt)
            grad_norms = {k: float(v) for k, v in grad_norms.items()}
    change = compare.change_norms(prog.params, prog.weight_maker,
                                  prog.weight_key)
    return batches, secs, {
        "losses": losses, "grad_norms": grad_norms,
        "change_norms": {k: float(v) for k, v in change.items()}}


def run(job):
    cfg, traffic = job.config, job.traffic
    model = job.spec.model(cfg["family"])
    train = cfg["deployment"]["train"]
    steps_per_segment = train["segment_steps"]

    prog = Program(job, model, cfg, train)
    batches, first_secs, program_side = first_steps(
        prog, traffic["checked_steps"])
    compile_s = max(0.0, first_secs[0] - float(np.median(first_secs[1:])))
    for _ in range(traffic["warm_segments"]):
        prog.segment(steps_per_segment)

    # ---- the window: whole segments only, from the start of the first
    # to the end of the last
    before = job.counters.snapshot()
    job.window_opens()
    t_open = time.perf_counter()
    seconds, where, traced, profiler_s = [], [], 0, 0.0
    while time.perf_counter() - t_open - profiler_s < job.seconds:
        if job.trace and traced == 0:
            t0 = time.perf_counter()
            job.profiler.start()
            profiler_s += time.perf_counter() - t0
        marks = dict(job.spans.seconds)
        seconds.append(prog.segment(steps_per_segment))
        where.append({name: job.spans.seconds[name] - marks.get(name, 0.0)
                      for name in ("place_batch", "dispatch", "sync")})
        if job.trace:
            traced += 1
            if traced == traffic["traced_segments"]:
                t0 = time.perf_counter()
                job.profiler.stop()
                profiler_s += time.perf_counter() - t0
    # starting and stopping the profiler (a traced run only) is not the
    # program's time
    window_s = time.perf_counter() - t_open - profiler_s
    if job.trace:
        job.profiler.reduce()
    in_window = job.counters.since(before)
    final_loss = prog.loss()
    peak = job.memory_peak()
    items = prog.info["items_per_step"]
    unit = traffic["item"][cfg["family"]]
    typical = float(np.median(seconds))
    for i, s in enumerate(seconds):
        line = "segment %d: %d steps in %.6f s = %.3f %s/s" % (
            i, steps_per_segment, s, steps_per_segment * items / s, unit)
        if s > 1.05 * typical:       # a slow one: where the host waited
            line += "  SLOW (" + ", ".join(
                "%s %.3f s" % kv for kv in sorted(where[i].items())) + ")"
        print(line, flush=True)
    print("window %.6f s, %d segments: %.3f %s/s over the whole window, "
          "%.3f in the median segment, %.4f%% of the window lost against it"
          % (window_s, len(seconds),
             items * segments.window_rate(seconds, steps_per_segment,
                                          window_s), unit,
             items * segments.median_rate(seconds, steps_per_segment),
             100 * segments.stall_share(seconds, steps_per_segment,
                                        window_s)), flush=True)

    # ---- the reference, after the program's state is freed
    flops_per_item = model.train_flops_per_item(cfg, train)
    attention = (model.attention_calls(cfg, train, len(job.devices))
                 if hasattr(model, "attention_calls") else None)
    prog.free()
    weights = prog.weights()
    reference, ref_path = job.spec.reference(cfg["name"])
    t0 = time.perf_counter()
    ref_side = compare.follow_steps(
        reference, cfg, weights, batches, train["optimizer"],
        block_rows=train.get("reference_block_rows"))
    del weights
    print("reference followed %d steps in %.2f s"
          % (len(batches), time.perf_counter() - t0), flush=True)
    rows = compare.training_rows(program_side, ref_side, job.limits)
    correct = compare.report(cfg["name"], job.relative(ref_path), rows)
    finite = bool(np.isfinite(final_loss))
    if not finite:
        print("the loss after the window is not finite: %r" % final_loss,
              flush=True)

    steps = len(seconds) * steps_per_segment
    return {
        "correct": correct and finite, "attempted": steps,
        "failed": 0 if finite else steps, "memory_peak_bytes": peak,
        "readings": {
            "kind": "train", "segment_seconds": seconds,
            "window_s": window_s,
            "steps_per_segment": steps_per_segment,
            "items_per_step": items, "chips": len(job.devices),
            "flops_per_item": flops_per_item, "attention": attention,
            "compile_s": compile_s, "compiles_in_window": in_window,
            "dtype": train["dtype"]}}
