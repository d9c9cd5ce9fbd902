"""Benchmark: ResNet-50 training throughput on one chip.

Baseline: the reference's published ResNet-50 training speed, batch 32 on
1x P100 = 181.53 img/s (reference docs/how_to/perf.md:181-188; BASELINE.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
additive keys, among them the device it ran on (``platform``,
``device_kind``, ``device_count``).  Runs in this process, on whatever
device JAX finds, and exits non-zero when the run fails.  With no
accelerator it fails; only ``JAX_PLATFORMS=cpu``, asked for, selects the
CPU smoke sizes and their ``*_cpu_smoke_*`` metric names.

Config is the TPU-idiomatic equivalent of the reference's benchmark_score.py
training loop: bf16 activations with fp32 MXU accumulation, fused
fwd+bwd+SGD-momentum step, synthetic data (the reference benchmark also uses
synthetic data).
"""

import json
import os
import time

import numpy as np

BASELINE_IMG_S = 181.53  # ResNet-50 train, batch 32, 1x P100


def _sync(tree):
    """Wait for the device: ``block_until_ready`` blocks on the attached
    chip (chip_smoke.py checks it on every run: a fetch after it costs
    a round trip, not a step)."""
    import jax

    return jax.block_until_ready(tree)


def _step_percentiles(run_step, sync, reps, per_call_steps=1):
    """step_ms p50/p99 from a short per-step-synced loop.

    The headline loop stays fetch-free between steps (per-step syncing
    would serialize the very dispatch overlap being measured), so the
    latency distribution comes from this separate, smaller loop:
    ``run_step()`` dispatches one step (or one K-step flush; pass
    ``per_call_steps=K``) and ``sync`` forces its result."""
    lat = []
    for _ in range(reps):
        t0 = time.perf_counter()
        outs = run_step()
        sync(outs)
        lat.append((time.perf_counter() - t0) / per_call_steps)
    p50, p99 = np.percentile(np.asarray(lat), [50, 99])
    return round(float(p50) * 1e3, 3), round(float(p99) * 1e3, 3)


def _obs_counters():
    """Additive observability keys for the one-line JSON contract:
    chaos injections fired and trace spans lost to ring-buffer eviction
    during the run (both 0 on a clean bench — nonzero values flag that
    the headline number was taken under fault injection or with a
    truncated trace)."""
    from mxnet_tpu import observability as obs

    fired = obs.REGISTRY.get("chaos_fired_total")
    dropped = obs.REGISTRY.get("spans_dropped_total")
    return {
        "chaos_fired_total": int(fired.total()) if fired else 0,
        "spans_dropped_total": int(dropped.total()) if dropped else 0,
    }


# bump when the emitted keys change shape (keys are only ever ADDED —
# consumers keying on schema_version never break on older rows).
# v4: mfu / goodput_ratio / model_flops_per_step from the efficiency
# accounting plane (cost-analysis FLOPs + goodput ledger)
# v5: requests_per_sec / request_ms_p50 / request_ms_p99 /
# batch_occupancy from the BENCH_SERVING=1 continuous-batching loop
# v6: reserved (ROADMAP: LM serving lane — tokens/sec/user, inter-token
# p99)
# v7: resize_cutover_ms / autoscale_actions_total from the
# BENCH_ELASTIC=1 live-resize loop
# v8: request_trace_overhead_pct (serving throughput with the metrics
# plane on vs MXNET_TPU_METRICS=0) / slo_availability from the
# per-request observability plane
# v9: stream_mb_per_sec / data_wait_pct / swap_downtime_ms from the
# BENCH_CONTINUOUS=1 continuous-training lane (streamed recordio fit
# on the prefetch feeder + one hot-swap under a client hammer)
# v10: tokens_per_sec / tokens_per_sec_per_user / inter_token_ms_p99 /
# prefill_ms_p50 / kv_cache_occupancy (+ tokens_per_sec_naive, the
# re-prefill-per-token baseline, and positions_per_token[_naive], the
# work each path is made to do, which the ≥2x acceptance ratio is
# taken on) from the BENCH_GENERATE=1 autoregressive generation lane —
# the v6 reservation, filled
# v11: kv_bytes_per_step / kv_header_overhead_pct / kv_codec_ms_share /
# kv_rpcs_per_flush_p50 from the BENCH_WIRE=1 wire-bandwidth lane (a
# 2-shard replicated in-process kvstore fit under the PR-15 byte
# books) — the measured baseline the binary-wire lane must beat
# v12: fairness_p99_ratio (innocent tenant's p99 with a saturating
# tenant present / alone — 1.0 is perfect isolation, down-is-good) /
# quota_shed_rate (quota 429s over the saturating tenant's offered
# load) / kv_affinity_hit_ratio (sessions landing on their KV blocks)
# from the BENCH_FAIRNESS=1 multi-tenant robustness lane (PR-16)
# v13: kv_compress_ratio (dense gradient bytes in / compressed bytes
# out under MXNET_TPU_KV_COMPRESS) / kv_coalesce_rpcs_saved (RPCs the
# fused push_pull path avoided) on the BENCH_WIRE=1 lane, which now
# runs the PR-17 binary wire by default
# v14: snapshot_save_ms / snapshot_restore_ms / snapshot_frozen_ms from
# the BENCH_SNAPSHOT=1 durability lane (PR-18): a consistent cut of a
# live 2-shard PS under push load, then a cold restore onto a 3-shard
# fleet — frozen_ms is the only window where pushes block, so it is the
# number the trend gate must keep flat
# v15: fused_parity_ok / attn_prefill_ms / paged_decode_tokens_per_sec /
# fused_opt_step_ms / stock_opt_step_ms / variant_compile_flops from
# the BENCH_KERNELS=1 fused-kernel lane (PR-19): the quick parity grid
# is the gate; attention numbers ride the public dispatch seam (stock
# on CPU — Pallas wins are asserted only on TPU); the optimizer pair is
# the one measured CPU claim (one jitted fused tree step vs the eager
# per-param updater dispatch)
# v16: kv_cache_occupancy_pct / memory_headroom_ratio /
# memory_ledger_reconciles from the BENCH_MEMORY=1 capacity lane
# (PR-20): the pool ledger must reconcile against jax.live_arrays()
# truth on a live generation workload (the gate — an empty ledger
# fails), occupancy is read with sessions still resident, and the
# headroom ratio rides the synthetic MXNET_TPU_MEMORY_BUDGET_BYTES
# device budget on CPU (real memory_stats() limits on TPU)
_SCHEMA_VERSION = 16


def _cpu_smoke():
    """CPU sizes, and the ``*_cpu_smoke_*`` metric names that go with
    them, are chosen only when ``JAX_PLATFORMS=cpu`` was asked for.  A
    run that merely finds no accelerator fails in :func:`main`; it never
    shrinks the model and prints a row."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def _bench_peak():
    """MFU denominator: ``BENCH_PEAK_TFLOPS`` (the historical bench
    knob) wins when set, else the efficiency module's per-device-kind
    table (which itself honors ``MXNET_TPU_DEVICE_PEAK_FLOPS``)."""
    from mxnet_tpu.observability import efficiency as eff

    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env) * 1e12
    return eff.peak_flops()


def _efficiency_keys(led, wall_s, n_steps, seconds):
    """Additive schema-4 keys from the efficiency accounting plane.

    ``mfu`` is the MEASURED ``model_flops_utilization`` gauge —
    cost-analysis FLOPs of the compiled step times the headline-loop
    step rate over the device peak — null when the backend supports no
    cost analysis or metrics are disabled (the documented fallback);
    ``goodput_ratio`` comes from closing the bench's ledger over the
    whole warmup+measure wall; ``model_flops_per_step`` is the raw
    numerator so consumers can re-derive MFU under a different peak."""
    from mxnet_tpu.observability import efficiency as eff

    eff.record_step_rate(n_steps, seconds, peak=_bench_peak())
    summary = led.close(wall_s) or {}
    mfps = eff.model_flops_per_step()
    _, rows = eff.efficiency_table()
    mfu = dict(rows).get("mfu")
    ratio = summary.get("goodput_ratio")
    return {
        "mfu": None if mfu is None else round(float(mfu), 6),
        "goodput_ratio": None if ratio is None else round(float(ratio), 4),
        "model_flops_per_step": None if mfps is None else float(mfps),
    }


def _provenance():
    """Additive provenance keys: the JSON schema revision, the git
    commit the number was measured at and the device JAX ran it on —
    the fields a regression tracker needs to pin 'which code produced
    this row, where'.  ``BENCH_GIT_SHA``
    overrides (CI passes the exact sha); outside a work tree the sha is
    ``"unknown"``, never an error."""
    sha = os.environ.get("BENCH_GIT_SHA")
    if not sha:
        import subprocess
        try:
            sha = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True, text=True, timeout=10,
            ).stdout.strip() or "unknown"
        except (OSError, subprocess.SubprocessError):
            sha = "unknown"
    import jax

    dev = jax.devices()[0]
    return {"schema_version": _SCHEMA_VERSION, "git_sha": sha,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def transformer_main():
    """Transformer-LM training throughput (the Pallas flash-attention
    path) + MFU.  Select with BENCH_MODEL=transformer; prints the same
    one-line JSON contract."""
    import time

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    full = not _cpu_smoke()
    batch = int(os.environ.get("BENCH_BATCH", "8" if full else "2"))
    seq = int(os.environ.get("BENCH_SEQ", "2048" if full else "128"))
    d_model = int(os.environ.get("BENCH_DMODEL", "1024" if full else "64"))
    layers = int(os.environ.get("BENCH_LAYERS", "12" if full else "2"))
    heads = d_model // 64
    vocab = 32000 if full else 256
    steps = int(os.environ.get("BENCH_STEPS", "30" if full else "3"))

    # BENCH_HEAD=fused_ce selects the chunked fused linear+softmax-CE head
    # (the long-context configuration: T=32768 b1 fits one chip with it —
    # docs/PERF.md "Long context on one chip")
    head = os.environ.get("BENCH_HEAD", "softmax")
    # BENCH_REMAT=block enables per-block __remat__ checkpoint regions
    # (docs/PERF.md "Per-block rematerialization")
    remat = os.environ.get("BENCH_REMAT", "none")
    # BENCH_FFN=moe swaps dense FFNs for MoELayer (BENCH_EXPERTS experts,
    # top-BENCH_TOPK routing) — the single-chip MoE row: experts fold to
    # one device but routing/capacity/dispatch execute for real
    ffn = os.environ.get("BENCH_FFN", "dense")
    n_experts = int(os.environ.get("BENCH_EXPERTS", "8"))
    moe_top_k = int(os.environ.get("BENCH_TOPK", "1"))
    sym = transformer.get_symbol(
        num_classes=vocab, seq_len=seq, num_embed=d_model,
        num_heads=heads, num_layers=layers, dtype="bfloat16" if full
        else "float32", head=head, remat=remat,
        ce_chunk=int(os.environ.get("BENCH_CE_CHUNK", "4096")),
        ffn=ffn, num_experts=n_experts, moe_top_k=moe_top_k)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))
    # BENCH_OPT=adam benches the sharded-Adam path (2 extra state tensors
    # per param + bias correction); default stays sgd+momentum
    opt = os.environ.get("BENCH_OPT", "sgd")
    tr = ShardedTrainer(
        sym, mesh, data_shapes={"data": (batch, seq)},
        label_shapes={"softmax_label": (batch, seq)},
        type_dict={"data": "int32"}, learning_rate=1e-3,
        momentum=0.9 if opt == "sgd" else 0.0, optimizer=opt,
        rescale_grad=1.0 / (batch * seq))
    params, moms, aux = tr.init(seed=0)
    rng = np.random.RandomState(0)
    arrays = tr.place_batch({
        "data": rng.randint(0, vocab, (batch, seq)).astype(np.int32),
        "softmax_label": rng.randint(0, vocab, (batch, seq))
        .astype(np.float32),
    })
    step = tr.step_fn()
    key = jax.random.PRNGKey(0)
    from mxnet_tpu.observability import efficiency as _eff

    led = _eff.ledger()
    t_bench = time.perf_counter()

    outs, params, moms, aux = step(params, moms, aux, arrays, key)
    _sync(outs)
    led.step(time.perf_counter() - t_bench)
    t0 = time.perf_counter()
    for _ in range(steps):
        outs, params, moms, aux = step(params, moms, aux, arrays, key)
    _sync(outs)
    dt = time.perf_counter() - t0
    led.step(dt)

    tokens_s = batch * seq * steps / dt

    def _one_step():
        nonlocal params, moms, aux
        outs, params, moms, aux = step(params, moms, aux, arrays, key)
        return outs

    t_pct = time.perf_counter()
    p50_ms, p99_ms = _step_percentiles(_one_step, _sync,
                                       min(steps, 10))
    led.step(time.perf_counter() - t_pct)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    # PaLM-appendix accounting: train FLOPs/token = 6N + 12*L*T*d_model
    # (the attention quadratic term), N = parameter count.  MoE: a token
    # runs top_k experts, not all BENCH_EXPERTS — count ACTIVE params
    # (total minus the unvisited experts' FFN weights) or the "MFU"
    # overcounts by ~E/top_k on the FFN share
    n_active = n_params
    if ffn == "moe":
        # derive the expert share from the REAL param tree (no mirror of
        # the hidden_size wiring to drift): a token visits top_k of the
        # n_experts expert FFNs
        expert_params = sum(
            int(np.prod(p.shape)) for n, p in params.items()
            if "_moe_w1_weight" in n or "_moe_w2_weight" in n)
        n_active -= int(expert_params * (n_experts - moe_top_k)
                        / max(n_experts, 1))
    flops_per_token = 6.0 * n_active + 12.0 * layers * seq * d_model
    # no peak on the CPU (efficiency.peak_flops), so no utilization
    peak = _bench_peak()
    mfu_formula = (round(tokens_s * flops_per_token / peak, 4)
                   if peak else None)
    # measured MFU (compiled-program FLOPs) wins when the backend gives
    # cost analysis; the PaLM-appendix formula stays as mfu_formula and
    # is the documented fallback for "mfu" when it does not
    eff_keys = _efficiency_keys(led, time.perf_counter() - t_bench,
                                steps, dt)
    if eff_keys["mfu"] is None:
        eff_keys["mfu"] = mfu_formula
    print(json.dumps({
        "metric": "transformer_lm_train_throughput" if full
                  else "transformer_lm_cpu_smoke_throughput",
        "value": round(tokens_s, 1), "unit": "tokens/s",
        "vs_baseline": 0.0,  # the 2017 reference has no transformer
        "step_ms_p50": p50_ms, "step_ms_p99": p99_ms,
        "tokens_per_sec": round(tokens_s, 1),
        **_obs_counters(),
        **_provenance(),
        **eff_keys,
        "mfu_formula": mfu_formula, "n_params": n_params,
        **({"n_params_active": n_active} if ffn == "moe" else {}),
        "config": {"batch": batch, "seq": seq, "d_model": d_model,
                   "layers": layers, "head": head, "ffn": ffn,
                   **({"experts": n_experts, "top_k": moe_top_k}
                      if ffn == "moe" else {})},
    }))


def serving_main():
    """Serving-tier throughput: the continuous-batching scheduler vs a
    batch-1 sequential ``forward()`` loop over the SAME model and
    shapes.  Select with BENCH_SERVING=1; prints the same one-line JSON
    contract with the schema-5 additive keys (``requests_per_sec``,
    ``request_ms_p50``/``p99``, ``batch_occupancy``) plus
    ``requests_per_sec_sequential`` (the per-request-dispatch baseline
    the ≥2× acceptance ratio is taken against) and
    ``recompiles_after_warmup`` (0 is the steady-state contract).
    Schema-8 adds ``request_trace_overhead_pct`` (the same warm
    scheduler re-measured under ``MXNET_TPU_METRICS=0`` — the
    per-request observability tax as a percentage of throughput) and
    ``slo_availability`` (good/(good+bad) from the availability error
    budget the run just accrued)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu import observability as obs
    from mxnet_tpu import predict, serving

    n_requests = int(os.environ.get("BENCH_REQUESTS", "256"))
    feat = int(os.environ.get("BENCH_FEATURES", "32"))
    hidden = int(os.environ.get("BENCH_HIDDEN", "64"))
    # a geometric ladder (not a dense one): deep windows amortize the
    # per-dispatch tax hardest, and each bucket is one compiled
    # executor — 4 shapes cover 1..64 within 4x padding waste
    buckets = [1, 4, 16, 64]

    net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = net.infer_shape(data=(1, feat))
    rs = np.random.RandomState(0)
    params = {"arg:%s" % n: nd.array(rs.randn(*s).astype(np.float32)
                                     * 0.1)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n != "data" and not n.endswith("label")}

    def _pred():
        return predict.Predictor(net.tojson(), dict(params),
                                 input_shapes={"data": (1, feat)})

    rows = rs.randn(n_requests, feat).astype(np.float32)

    # baseline: one device dispatch per request (batch 1, warm executor)
    seq_pred = _pred()
    seq_pred.forward(data=rows[:1])
    seq_pred.get_output(0)
    t0 = time.perf_counter()
    for i in range(n_requests):
        seq_pred.forward(data=rows[i:i + 1])
        seq_pred.get_output(0)
    rps_sequential = n_requests / (time.perf_counter() - t0)

    # continuous batching over the same shapes: pre-bound buckets, all
    # requests in flight, the dispatch loop packs them into windows
    sched = serving.Scheduler(name="bench")
    sched.register("bench_mlp", _pred(), buckets=buckets,
                   max_queue=n_requests + len(buckets))
    sched.warmup("bench_mlp")
    compiles = obs.REGISTRY.get("serving_compiles_total")
    warm_compiles = int(compiles.total()) if compiles else 0
    t0 = time.perf_counter()
    reqs = [sched.submit("bench_mlp", {"data": rows[i]})
            for i in range(n_requests)]
    for r in reqs:
        r.result(timeout=120)
    dt = time.perf_counter() - t0
    rps = n_requests / dt
    lat_ms = np.asarray([r.latency_s for r in reqs]) * 1e3
    p50, p99 = np.percentile(lat_ms, [50, 99])
    stats = sched.stats("bench_mlp")
    recompiles = (int(compiles.total()) if compiles else 0) \
        - warm_compiles

    # schema-8: the per-request observability tax — the same warm
    # scheduler re-measured with the metrics plane off.  The env var is
    # re-read lazily on every hot-path call, so flipping it here turns
    # every counter/histogram/event/exemplar into a constant-time no-op.
    prior = os.environ.get("MXNET_TPU_METRICS")
    os.environ["MXNET_TPU_METRICS"] = "0"
    try:
        t0 = time.perf_counter()
        bare = [sched.submit("bench_mlp", {"data": rows[i]})
                for i in range(n_requests)]
        for r in bare:
            r.result(timeout=120)
        rps_off = n_requests / (time.perf_counter() - t0)
    finally:
        if prior is None:
            os.environ.pop("MXNET_TPU_METRICS", None)
        else:
            os.environ["MXNET_TPU_METRICS"] = prior
    overhead_pct = ((1.0 - rps / rps_off) * 100.0) if rps_off > 0 else 0.0
    sched.close()

    # the availability budget the instrumented pass just accrued (the
    # METRICS=0 pass recorded nothing, by construction)
    from mxnet_tpu.observability import slo as _slo

    arow = next((r for r in _slo.report().get("slos", ())
                 if r["slo"] == "availability"), None)
    slo_availability = (
        None if arow is None or not (arow["good"] + arow["bad"])
        else round(arow["good"] / float(arow["good"] + arow["bad"]), 6))

    print(json.dumps({
        "metric": "serving_throughput" if not _cpu_smoke()
                  else "serving_cpu_smoke_throughput",
        "value": round(rps, 2), "unit": "req/s",
        "vs_baseline": 0.0,  # the 2017 reference has no serving tier
        "requests_per_sec": round(rps, 2),
        "request_ms_p50": round(float(p50), 3),
        "request_ms_p99": round(float(p99), 3),
        "batch_occupancy": round(stats["occupancy"], 4),
        "requests_per_sec_sequential": round(rps_sequential, 2),
        "recompiles_after_warmup": recompiles,
        "request_trace_overhead_pct": round(overhead_pct, 2),
        "slo_availability": slo_availability,
        **_obs_counters(),
        **_provenance(),
        "config": {"requests": n_requests, "features": feat,
                   "hidden": hidden, "buckets": buckets},
    }))


def fairness_main():
    """Multi-tenant robustness lane (BENCH_FAIRNESS=1, PR-16).

    Three measurements on the real serving stack, numpy-backed so the
    lane is seconds on CPU:

    - ``fairness_p99_ratio`` — the innocent tenant's p99 with a
      quota-limited saturating tenant hammering the same lane, divided
      by its p99 alone.  1.0 is perfect isolation; the WFQ + quota
      contract is that a heavy tail costs the innocent tenant a
      bounded factor, not a meltdown.
    - ``quota_shed_rate`` — the saturating tenant's typed-429 fraction
      (sheds / offered): the quota actually biting.
    - ``kv_affinity_hit_ratio`` — sticky generation sessions landing
      on the replica that already holds their KV blocks, from the
      :class:`~mxnet_tpu.serving.KVAffinityRouter` gauge.
    """
    import threading

    import jax

    from mxnet_tpu import serving
    from mxnet_tpu import observability as obs

    n_requests = int(os.environ.get("BENCH_FAIR_REQUESTS", "96"))

    class _SlowEcho(serving.Backend):
        input_shapes = {"data": (4,)}

        def infer(self, batch):
            time.sleep(0.002)
            return [batch["data"] * 2.0], False

    def _drive(sched, plan):
        """Submit (tenant, count) bursts on threads; returns
        ({tenant: [latency_s]}, {tenant: sheds})."""
        lat, sheds = {}, {}
        lock = threading.Lock()
        row = {"data": np.ones(4, np.float32)}

        def one(tenant):
            try:
                req = sched.submit("mlp", row, tenant=tenant)
                req.result(timeout=60.0)
            except (serving.QuotaExceededError,
                    serving.ServerOverloadedError):
                with lock:
                    sheds[tenant] = sheds.get(tenant, 0) + 1
                return
            with lock:
                lat.setdefault(tenant, []).append(req.latency_s)

        threads = []
        for tenant, count in plan:
            for _ in range(count):
                th = threading.Thread(target=one, args=(tenant,))
                th.start()
                threads.append(th)
        for th in threads:
            th.join(timeout=120.0)
        return lat, sheds

    def _p99(xs):
        return float(np.percentile(np.asarray(xs) * 1e3, 99))

    # innocent tenant alone: the isolation baseline
    sched = serving.Scheduler(name="bench-fair")
    sched.register("mlp", _SlowEcho(), buckets=[1, 2, 4, 8],
                   max_queue=16 * n_requests,
                   tenant_weights={"gold": 3.0})
    sched.tenants.set_quota("bulk", rps=50.0)
    lat, _ = _drive(sched, [("gold", n_requests)])
    p99_alone = _p99(lat["gold"])

    # the heavy tail: the saturating tenant offers 8x the innocent load
    t0 = time.perf_counter()
    lat, sheds = _drive(sched, [("bulk", 8 * n_requests),
                                ("gold", n_requests)])
    dt = time.perf_counter() - t0
    sched.close()
    p99_mixed = _p99(lat["gold"])
    ratio = p99_mixed / p99_alone if p99_alone > 0 else 0.0
    shed_rate = sheds.get("bulk", 0) / float(8 * n_requests)
    rps_gold = len(lat["gold"]) / dt

    # sticky sessions over a 2-replica generation group: the affinity
    # hit ratio the router gauge accrues (3 sessions x 4 visits)
    from mxnet_tpu.models import transformer as tfm

    cfg = tfm.lm_config(num_classes=64, seq_len=48, num_embed=16,
                        num_heads=2, num_layers=2)
    params = tfm.init_lm_params(cfg, seed=0)
    group = serving.ReplicaGroup(
        replicas=2, group="bench-gen",
        scheduler_cls=serving.GenerationScheduler)
    group.register("lm", lambda: serving.LMBackend(
        params, cfg, block_size=4, num_blocks=64))
    router = serving.KVAffinityRouter(group)
    prompt = np.arange(1, 9, dtype=np.int32)
    for i in range(12):
        router.generate("lm", prompt, max_new_tokens=4,
                        session="s%d" % (i % 3), timeout=120)
    group.close()
    hit_gauge = obs.REGISTRY.get("kv_affinity_hit_ratio")
    hit_ratio = float(hit_gauge.labels("bench-gen").value)

    print(json.dumps({
        "metric": "fairness_throughput" if not _cpu_smoke()
                  else "fairness_cpu_smoke_throughput",
        "value": round(rps_gold, 2), "unit": "req/s",
        "vs_baseline": 0.0,  # the 2017 reference has no serving tier
        "fairness_p99_ratio": round(ratio, 3),
        "quota_shed_rate": round(shed_rate, 4),
        "kv_affinity_hit_ratio": round(hit_ratio, 4),
        **_obs_counters(),
        **_provenance(),
        "config": {"requests": n_requests, "skew": 8,
                   "p99_alone_ms": round(p99_alone, 3),
                   "p99_contended_ms": round(p99_mixed, 3)},
    }))


def elastic_main():
    """Elastic-scale lane (BENCH_ELASTIC=1): a live 2→4→2 PS-shard
    resize under a concurrent push load, driven end-to-end by the
    autoscaler (a firing watchdog rule scales up; sustained quiet
    scales back down).  Emits the schema-7 additive keys:
    ``resize_cutover_ms`` (max routing-frozen window across the two
    cutovers) and ``autoscale_actions_total`` (actions the policy
    engine took — 2 on a clean run)."""
    import threading

    import mxnet_tpu  # noqa: F401 — env bootstrap
    from mxnet_tpu import elastic
    from mxnet_tpu import kvstore_async as ka
    from mxnet_tpu import observability as obs
    from mxnet_tpu.observability import Autoscaler, Rule, Watchdog

    n_keys = int(os.environ.get("BENCH_ELASTIC_KEYS", "24"))
    n_push = int(os.environ.get("BENCH_ELASTIC_PUSHES", "400"))
    servers = [ka.AsyncServer(secret="bench", server_id=i).start()
               for i in range(4)]
    group = ka.ServerGroup([servers[0].address, servers[1].address],
                           rank=0, heartbeat=False, secret="bench")
    group._bound = 1 << 10  # stripe the big keys across the fleet
    rs = np.random.RandomState(0)
    keys = [("k%02d" % i,
             (4096,) if i % 4 == 0 else (64,)) for i in range(n_keys)]
    group.init([(k, rs.randn(*s).astype(np.float32)) for k, s in keys])
    import pickle

    from mxnet_tpu import optimizer as mx_opt

    # pushes go through the server-side optimizer, like a real fit
    group.set_optimizer(pickle.dumps(mx_opt.SGD(learning_rate=0.01)))

    pushed = [0]
    stop = threading.Event()

    def pound():
        while not stop.is_set():
            k, s = keys[pushed[0] % n_keys]
            group.push([(k, np.ones(s, np.float32))])
            pushed[0] += 1
            if pushed[0] >= n_push:
                break

    # the alert loop, closed: a saturation gauge trips the watchdog
    # rule, the autoscaler's sustained-alert policy resizes the fleet
    sat = obs.gauge("serving_queue_saturation",
                    "Queue depth / max_queue per model lane "
                    "(1.0 = shedding)", ["model"]).labels("bench")
    dog = Watchdog([Rule("queue_saturation", "serving_queue_saturation",
                         stat="max", op=">=", threshold=0.9,
                         description="bench: synthetic saturation")])
    cutovers = []

    def up(action):
        res = elastic.ResizePlan(
            group, [s.address for s in servers], keys,
            secret="bench")
        res.run()
        cutovers.append(res.cutover_ms)
        return {"epoch": group.topology_epoch}

    def down(action):
        res = elastic.ResizePlan(
            group, [servers[0].address, servers[1].address], keys,
            secret="bench")
        res.run()
        cutovers.append(res.cutover_ms)
        return {"epoch": group.topology_epoch}

    asc = Autoscaler(dog, scale_up=up, scale_down=down,
                     size=lambda: len(group._specs),
                     sustain_s=0.0, cooldown_s=0.0, idle_s=0.05,
                     min_size=2, max_size=4)
    pusher = threading.Thread(target=pound)
    t0 = time.perf_counter()
    pusher.start()
    while pushed[0] < 8 and time.perf_counter() - t0 < 5:
        time.sleep(0.002)               # resize under real push load
    sat.set(1.0)                        # load spike → scale-up
    act_up = asc.evaluate()
    sat.set(0.0)                        # quiet → drain-and-shrink
    deadline = time.perf_counter() + 30
    act_down = None
    while act_down is None and time.perf_counter() < deadline:
        act_down = asc.evaluate()
        time.sleep(0.01)
    stop.set()
    pusher.join()
    dt = time.perf_counter() - t0
    ok = (act_up is not None and act_up.ok
          and act_down is not None and act_down.ok
          and len(group._specs) == 2)
    # every key must survive both restripes at full value (the pusher's
    # in-flight increments make exact totals racy; presence + shape is
    # the bench contract, tests assert exactness)
    out = group.pull([k for k, _ in keys])
    survived = all(v.shape == tuple(s) for v, (_, s) in zip(out, keys))
    group.shutdown()
    for s in servers:
        s.stop()
    actions = obs.REGISTRY.get("cluster_autoscale_actions_total")
    print(json.dumps({
        "metric": "elastic_resize_cutover",
        "value": round(max(cutovers), 3) if cutovers else None,
        "unit": "ms",
        "vs_baseline": 0.0,  # the 2017 reference cannot resize at all
        "resize_cutover_ms": round(max(cutovers), 3) if cutovers
                             else None,
        "autoscale_actions_total": int(actions.total()) if actions
                                   else 0,
        "scale_cycle_ok": bool(ok and survived),
        "pushes_during_resize": pushed[0],
        "elapsed_s": round(dt, 3),
        **_obs_counters(),
        **_provenance(),
        "config": {"keys": n_keys, "pushes": n_push},
    }))


def snapshot_main():
    """Durability lane (BENCH_SNAPSHOT=1, PR-18): time a coordinated
    snapshot of a live 2-shard striped PS while a pusher thread keeps
    updates flowing, then a cold restore onto a DIFFERENT (3-shard)
    fleet.  Emits the schema-14 additive keys: ``snapshot_save_ms``
    (end-to-end commit including fsync discipline),
    ``snapshot_frozen_ms`` (the routing-frozen delta cut — the only
    window where training blocks) and ``snapshot_restore_ms``
    (verify + reassemble + re-stripe + install)."""
    import pickle
    import shutil
    import tempfile
    import threading

    import mxnet_tpu  # noqa: F401 — env bootstrap
    from mxnet_tpu import kvstore_async as ka
    from mxnet_tpu import optimizer as mx_opt
    from mxnet_tpu import snapshot

    n_keys = int(os.environ.get("BENCH_SNAPSHOT_KEYS", "24"))
    n_push = int(os.environ.get("BENCH_SNAPSHOT_PUSHES", "400"))
    servers = [ka.AsyncServer(secret="bench", server_id=i).start()
               for i in range(5)]
    group = ka.ServerGroup([servers[0].address, servers[1].address],
                           rank=0, heartbeat=False, secret="bench")
    group._bound = 1 << 10  # stripe the big keys across the fleet
    rs = np.random.RandomState(0)
    keys = [("k%02d" % i,
             (4096,) if i % 4 == 0 else (64,)) for i in range(n_keys)]
    group.init([(k, rs.randn(*s).astype(np.float32)) for k, s in keys])
    group.set_optimizer(pickle.dumps(mx_opt.SGD(learning_rate=0.01)))

    pushed = [0]
    stop = threading.Event()

    def pound():
        while not stop.is_set() and pushed[0] < n_push:
            k, s = keys[pushed[0] % n_keys]
            group.push([(k, np.ones(s, np.float32))])
            pushed[0] += 1

    snap_dir = tempfile.mkdtemp(prefix="mxtpu_bench_snap_")
    t0 = time.perf_counter()
    pusher = threading.Thread(target=pound)
    pusher.start()
    while pushed[0] < 8 and time.perf_counter() - t0 < 5:
        time.sleep(0.002)               # cut under real push load
    saved = snapshot.save(group, snap_dir, keys, step=1, secret="bench")
    stop.set()
    pusher.join()
    group.shutdown()

    # cold restore onto a different topology: 3 fresh shards
    group2 = ka.ServerGroup([s.address for s in servers[2:]], rank=0,
                            heartbeat=False, secret="bench")
    group2._bound = 1 << 10
    restored = snapshot.restore_latest(snap_dir, group2, secret="bench")
    out = group2.pull([k for k, _ in keys])
    survived = all(v.shape == tuple(s) for v, (_, s) in zip(out, keys))
    group2.shutdown()
    for s in servers:
        s.stop()
    shutil.rmtree(snap_dir, ignore_errors=True)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "snapshot_save",
        "value": round(saved["save_ms"], 3),
        "unit": "ms",
        "vs_baseline": 0.0,  # the 2017 reference has no live PS snapshot
        "snapshot_save_ms": round(saved["save_ms"], 3),
        "snapshot_frozen_ms": round(saved["frozen_ms"], 3),
        "snapshot_restore_ms": round(restored["restore_ms"], 3),
        "snapshot_restripe_ok": bool(
            survived and restored["restored_shards"] == 3),
        "pushes_during_save": pushed[0],
        "elapsed_s": round(dt, 3),
        **_obs_counters(),
        **_provenance(),
        "config": {"keys": n_keys, "pushes": n_push},
    }))


def kernels_main():
    """Fused-kernel lane (BENCH_KERNELS=1, PR-19): the parity gate plus
    kernel-level timings on the operator-variant seam.

    Emits the schema-15 additive keys.  ``fused_parity_ok`` is the gate
    everything else rides on: the quick parity grid (2 cases per
    variant) must be green or the lane's headline value is 0 and
    ``make kernels`` exits nonzero.  ``attn_prefill_ms`` and
    ``paged_decode_tokens_per_sec`` time the PUBLIC functions —
    whatever body the platform and the shape choose, which on CPU is the
    reference body, so off TPU they are a baseline and never a kernel
    claim (the Pallas kernels gate on parity + their
    ``trainer_compile_flops`` rows).
    ``fused_opt_step_ms`` vs ``stock_opt_step_ms`` is the one measured
    CPU claim: one jitted fused optimizer tree step against the eager
    per-param updater dispatch (the imperative ``model._update_params``
    shape the fused tree replaces)."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401 — env bootstrap
    from mxnet_tpu import observability as obs
    from mxnet_tpu.observability import efficiency as eff
    from mxnet_tpu.ops import attention as oatt
    from mxnet_tpu.ops import paged_attention as opaged
    from mxnet_tpu.ops.fused import parity as fpar
    from mxnet_tpu.parallel import trainer as ptr

    t_start = time.perf_counter()
    reps = int(os.environ.get("BENCH_KERNEL_REPS", "15"))
    parity_rows = fpar.run_parity(quick=True)
    parity_ok = bool(parity_rows) and all(r["ok"] for r in parity_rows)

    def _med_ms(fn, *args):
        jax.block_until_ready(fn(*args))       # warmup / compile
        lat = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            lat.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(np.asarray(lat)))

    rs = np.random.RandomState(0)

    # prefill attention through the seam (jitted, like every call site)
    b, h, t, d = 2, 4, 128, 32
    q = jnp.asarray(rs.randn(b, h, t, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, h, t, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, h, t, d).astype(np.float32))
    attn_prefill_ms = _med_ms(jax.jit(oatt.stable_causal_attention),
                              q, k, v)

    # paged decode through the seam: one token per live sequence
    bsz, heads, dim, blk, max_blocks = 4, 4, 32, 16, 4
    n_pages = bsz * max_blocks + 1
    k_pages = jnp.asarray(
        rs.randn(n_pages, blk, heads, dim).astype(np.float32))
    v_pages = jnp.asarray(
        rs.randn(n_pages, blk, heads, dim).astype(np.float32))
    ctx = [37, 12, 64, 5][:bsz]
    bt = np.zeros((bsz, max_blocks), np.int32)
    nxt = 1
    for i, c in enumerate(ctx):
        for jj in range(-(-c // blk)):
            bt[i, jj] = nxt
            nxt += 1
    dq = jnp.asarray(rs.randn(bsz, heads, dim).astype(np.float32))
    k_step = jnp.asarray(rs.randn(bsz, heads, dim).astype(np.float32))
    v_step = jnp.asarray(rs.randn(bsz, heads, dim).astype(np.float32))
    dargs = (dq, k_step, v_step, k_pages, v_pages, jnp.asarray(bt),
             jnp.asarray(ctx, dtype=jnp.int32))
    decode_ms = _med_ms(jax.jit(opaged.paged_decode_attention), *dargs)
    paged_decode_tokens_per_sec = bsz / (decode_ms / 1e3)

    # the optimizer-tree fusion's measured CPU win: eager per-param
    # dispatch (stock updater shape) vs ONE jitted fused tree step
    attrs = {"lr": 0.05, "wd": 1e-4, "momentum": 0.9,
             "rescale_grad": 1.0, "clip_gradient": -1.0}
    shapes = [(256, 64), (64,), (128, 128), (128,), (512, 32), (32,)]
    shapes = shapes * 4                         # 24 params, mixed sizes
    params = {"p%02d" % i: jnp.asarray(rs.randn(*s).astype(np.float32))
              for i, s in enumerate(shapes)}
    grads = {n: jnp.asarray(rs.randn(*w.shape).astype(np.float32))
             for n, w in params.items()}
    moms = {n: jnp.zeros_like(w) for n, w in params.items()}
    stock_opt_step_ms = _med_ms(
        lambda: ptr.sgd_mom_tree_stock(attrs, params, grads, moms))
    fused_tree = jax.jit(
        lambda p, g, m: ptr.fused_sgd_mom_tree(attrs, p, g, m))
    fused_opt_step_ms = _med_ms(fused_tree, params, grads, moms)

    # per-kernel compile cost: the trainer_compile_flops{cache} rows the
    # attention kernels gate on (analysis only, nothing executes), each
    # kernel and its reference body at its first parity-grid case
    regs = fpar.parity_registrations()
    sides = []
    for kernel in ("flash_prefill_attention", "paged_decode_attention"):
        reference_fn, kernel_fn, args = regs[kernel].builder(
            regs[kernel].grid[0])[:3]
        for side, fn in (("reference", reference_fn), ("kernel", kernel_fn)):
            eff.record_variant_compile(kernel, side, fn, *args)
            sides.append("variant:%s:%s" % (kernel, side))
    flops_fam = obs.REGISTRY.get("trainer_compile_flops")
    variant_flops = {}
    if flops_fam is not None:
        for cache in sides:
            val = flops_fam.labels(cache).value
            if val:
                variant_flops[cache] = float(val)

    dt = time.perf_counter() - t_start
    print(json.dumps({
        "metric": "kernels_parity",
        "value": 1.0 if parity_ok else 0.0,
        "unit": "ok",
        "vs_baseline": 0.0,  # the gate is parity, not a 2017 number
        "fused_parity_ok": parity_ok,
        "fused_parity_cases": len(parity_rows),
        "attn_prefill_ms": round(attn_prefill_ms, 3),
        "paged_decode_tokens_per_sec": round(
            paged_decode_tokens_per_sec, 2),
        "fused_opt_step_ms": round(fused_opt_step_ms, 3),
        "stock_opt_step_ms": round(stock_opt_step_ms, 3),
        "variant_compile_flops": variant_flops,
        "elapsed_s": round(dt, 3),
        **_obs_counters(),
        **_provenance(),
        "config": {"reps": reps, "opt_params": len(shapes)},
    }))
    if not parity_ok:
        raise SystemExit(1)


def memory_main():
    """Memory/capacity lane (BENCH_MEMORY=1, PR-20): the reconciled
    pool ledger measured on a live generation workload.

    Emits the schema-16 additive keys.  ``memory_ledger_reconciles``
    is the gate everything rides on: the named pool books must explain
    the ``jax.live_arrays()`` truth within the ledger tolerance or the
    lane exits nonzero — and an empty ledger fails by contract, the
    same falsifiability shape as the wire lane's reconcile.
    ``kv_cache_occupancy_pct`` is read with sessions still resident
    (peak hold, not the drained pool), and ``memory_headroom_ratio``
    is computed against the synthetic ``MXNET_TPU_MEMORY_BUDGET_BYTES``
    device budget on CPU (real ``memory_stats()`` limits on TPU)."""
    import jax

    import mxnet_tpu  # noqa: F401 — env bootstrap
    from mxnet_tpu import serving
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.observability import memory as omem
    from mxnet_tpu.observability import metrics as om

    t_start = time.perf_counter()
    om.reset_metrics()
    cfg = tfm.lm_config(num_classes=128, seq_len=64, num_embed=64,
                        num_heads=4, num_layers=2)
    # commit the weight tree to the device: the ledger books jax.Array
    # leaves only, and host-numpy weights would leave both the books
    # and the live-array truth empty (a vacuous, failing gate)
    params = jax.device_put(tfm.init_lm_params(cfg, seed=0))
    sched = serving.GenerationScheduler()
    be = serving.LMBackend(params, cfg, block_size=8, num_blocks=32)
    sched.register("lm", be, decode_buckets=[1, 2],
                   prefill_buckets=[8, 16])
    sched.warmup("lm")
    for seed in range(3):
        toks = sched.generate("lm", list(range(1 + seed, 9 + seed)),
                              max_new_tokens=8)
        assert toks, "generation produced no tokens"
    # hold a few sessions resident so occupancy is read at peak — the
    # generate() free path would otherwise drain the pool back to zero
    held = ("bench-a", "bench-b", "bench-c")
    for sid in held:
        be.cache.allocate(sid, 24)
    occ_fam = om.REGISTRY.get("serving_kv_cache_occupancy")
    occupancy = float(occ_fam.labels("lm").value) if occ_fam else 0.0
    budget_preset = os.environ.get("MXNET_TPU_MEMORY_BUDGET_BYTES")
    try:
        if not budget_preset:
            # CPU memory_stats() carries no bytes_limit: pin the
            # synthetic budget at 2x the live total so the headroom
            # ratio is deterministic (~0.5) instead of absent
            live = omem.sample() or 0
            os.environ["MXNET_TPU_MEMORY_BUDGET_BYTES"] = str(
                int(max(live, 1) * 2))
        omem.sample()
        ok, booked, truth = omem.memory_reconciles()
        head_fam = om.REGISTRY.get("memory_headroom_ratio")
        headroom = (float(head_fam.labels("all").value)
                    if head_fam else 0.0)
        rep = omem.memory_report()
    finally:
        for sid in held:
            be.cache.free(sid)
        if not budget_preset:
            del os.environ["MXNET_TPU_MEMORY_BUDGET_BYTES"]
    sched.close()
    dt = time.perf_counter() - t_start
    print(json.dumps({
        "metric": "memory_ledger",
        "value": 1.0 if ok else 0.0,
        "unit": "ok",
        "vs_baseline": 0.0,  # the gate is the reconcile, not a 2017 number
        "memory_ledger_reconciles": bool(ok),
        "memory_booked_bytes": int(booked),
        "memory_live_bytes": int(truth),
        "memory_other_bytes": int(rep["other_bytes"]),
        "kv_cache_occupancy_pct": round(occupancy * 100.0, 2),
        "memory_headroom_ratio": round(headroom, 4),
        "elapsed_s": round(dt, 3),
        **_obs_counters(),
        **_provenance(),
        "config": {"num_blocks": 32, "block_size": 8,
                   "held_sessions": len(held)},
    }))
    if not ok:
        raise SystemExit(1)


def wire_main():
    """Wire-bandwidth lane (BENCH_WIRE=1): a 2-shard replicated
    in-process kvstore fit (sync replication, followers attached via
    live state transfer) with the PR-15 byte books on.  Emits the
    schema-11 additive keys — ``kv_bytes_per_step``,
    ``kv_header_overhead_pct``, ``kv_codec_ms_share``,
    ``kv_rpcs_per_flush_p50`` — the schema-13 additions —
    ``kv_compress_ratio``, ``kv_coalesce_rpcs_saved`` — plus
    ``wire_reconciles``: whether the per-op byte books matched the
    socket-level truth within 1% (the same falsifiability gate
    ``make wire`` exits nonzero on)."""
    import jax
    from jax.sharding import Mesh

    import mxnet_tpu as mx
    from mxnet_tpu import kvstore_async as ka
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.observability import wire as owire
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    os.environ["MXNET_TPU_KV_REPL_SYNC"] = "1"
    os.environ.setdefault("MXNET_TPU_PS_SECRET", "bench")
    # the lane measures the full PR-17 stack by default (binary wire +
    # int8 push compression + coalescing); export the knobs to compare
    os.environ.setdefault("MXNET_TPU_KV_COMPRESS", "int8")
    secret = os.environ["MXNET_TPU_PS_SECRET"]
    servers, addrs = [], []
    for shard in range(2):
        pri = ka.AsyncServer(server_id=shard * 2, secret=secret).start()
        fol = ka.AsyncServer(server_id=shard * 2 + 1,
                             secret=secret).start()
        fol.rejoin(pri.address)
        servers += [pri, fol]
        addrs.append("%s|%s" % (pri.address, fol.address))
    os.environ["MXNET_TPU_ASYNC_PS_ADDRS"] = ",".join(addrs)
    ka.reset_membership()

    B = int(os.environ.get("BENCH_BATCH", "8"))
    D = 6
    steps = max(int(os.environ.get("BENCH_STEPS", "4")), 2)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rs = np.random.RandomState(3)
    X = rs.randn(steps * B, D).astype(np.float32)
    Y = rs.randint(0, 8, (steps * B,)).astype(np.float32)
    kv = mx.kv.create("dist_async")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1,
                                      rescale_grad=1.0 / B, wd=0.0))
    it = NDArrayIter({"data": X}, {"softmax_label": Y}, batch_size=B)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tr = ShardedTrainer(net, mesh, data_shapes={"data": (B, D)},
                        label_shapes={"softmax_label": (B,)},
                        rescale_grad=1.0 / B)
    t0 = time.perf_counter()
    tr.fit(it, num_epoch=2, seed=5, log_every=0, kvstore=kv)
    dt = time.perf_counter() - t0
    for s in servers:
        s.stop()
    rep = owire.wire_report()
    ok, _wire_b, _sock_b = owire.wire_reconciles()
    codec_ok, _ck, _kp = owire.codec_reconciles()
    print(json.dumps({
        "metric": "kv_wire_bytes_per_step",
        "value": round(rep["bytes_per_step"], 1),
        "unit": "B/step",
        "vs_baseline": 0.0,  # the 2017 reference has no byte books
        "kv_bytes_per_step": round(rep["bytes_per_step"], 1),
        "kv_header_overhead_pct": round(rep["header_overhead_pct"], 2),
        "kv_codec_ms_share": round(
            100.0 * rep["codec_share_of_step"], 4),
        "kv_rpcs_per_flush_p50": round(rep["rpcs_per_flush_p50"], 1),
        "kv_compress_ratio": round(rep["compress_ratio"], 2),
        "kv_coalesce_rpcs_saved": int(rep["coalesce_rpcs_saved"]),
        "wire_reconciles": bool(ok),
        "codec_reconciles": bool(codec_ok),
        "elapsed_s": round(dt, 3),
        **_obs_counters(),
        **_provenance(),
        "config": {"batch": B, "steps": steps, "shards": 2,
                   "replicas": 2},
    }))


def continuous_main():
    """Continuous-training lane (BENCH_CONTINUOUS=1): a streamed
    recordio fit on the pipelined prefetch feeder, then one gated
    hot-swap under a hammering client.  Emits the schema-9 additive
    keys: ``stream_mb_per_sec`` (recordio bytes decoded per fit
    second), ``data_wait_pct`` (data-wait badput share of the fit
    wall — the stall the background decode is supposed to overlap
    away) and ``swap_downtime_ms`` (longest gap between answered
    requests across the ``ModelRegistry.swap``)."""
    import tempfile
    import threading

    import jax
    from jax.sharding import Mesh

    import mxnet_tpu as mx
    from mxnet_tpu import observability as obs
    from mxnet_tpu import serving, stream
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    batch = int(os.environ.get("BENCH_STREAM_BATCH", "32"))
    dim = int(os.environ.get("BENCH_STREAM_DIM", "256"))
    hidden = int(os.environ.get("BENCH_STREAM_HIDDEN", "512"))
    n = int(os.environ.get("BENCH_STREAM_RECORDS", str(48 * batch)))

    rs = np.random.RandomState(0)
    rec = os.path.join(tempfile.mkdtemp(prefix="mxtpu_bench_stream_"),
                       "train.rec")
    stream.write_ndarray_records(
        rec, rs.randn(n, dim).astype(np.float32),
        (np.arange(n) % 8).astype(np.float32))

    net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tr = ShardedTrainer(net, mesh, data_shapes={"data": (batch, dim)},
                        label_shapes={"softmax_label": (batch,)},
                        optimizer="sgd",
                        optimizer_params={"lr": 0.1,
                                          "rescale_grad": 1.0 / batch},
                        pipeline_steps=4)

    def _counter(name, label=None):
        fam = obs.REGISTRY.get(name)
        if fam is None:
            return 0.0
        return fam.labels(label).value if label else fam.total()

    wait0 = _counter("badput_seconds_total", "data_wait")
    bytes0 = _counter("stream_bytes_read_total")
    t0 = time.perf_counter()
    (params, _, _), _ = tr.fit(
        stream.StreamDataIter([rec], (dim,), batch, seed=7),
        num_epoch=2, seed=5, log_every=0)
    wall = time.perf_counter() - t0
    mb_s = (_counter("stream_bytes_read_total") - bytes0) / wall / 2**20
    wait_pct = 100.0 * (_counter("badput_seconds_total", "data_wait")
                        - wait0) / wall

    # one hot-swap under live single-row traffic: downtime = longest
    # answer gap a hammering client saw across the swap window
    class _NpBackend(serving.Backend):
        def __init__(self, p):
            self.p = {k: np.asarray(v) for k, v in p.items()}
            self.input_shapes = {"data": (dim,)}

        def infer(self, b):
            h = np.maximum(np.asarray(b["data"], np.float64)
                           @ self.p["fc1_weight"].T + self.p["fc1_bias"],
                           0)
            return [h @ self.p["fc2_weight"].T + self.p["fc2_bias"]], \
                False

    sched = serving.Scheduler()
    sched.register("mlp", _NpBackend(params), buckets=[1, 4])
    row = {"data": rs.randn(dim).astype(np.float32)}
    stamps = []
    stop = threading.Event()

    def pound():
        while not stop.is_set():
            sched.request("mlp", dict(row), timeout=10)
            stamps.append(time.perf_counter())

    client = threading.Thread(target=pound)
    client.start()
    time.sleep(0.1)
    sched.swap("mlp", _NpBackend(params))
    time.sleep(0.1)
    stop.set()
    client.join()
    gaps = np.diff(np.asarray(stamps)) if len(stamps) > 1 else [0.0]
    swap_ms = float(np.max(gaps)) * 1e3

    print(json.dumps({
        "metric": "stream_throughput",
        "value": round(mb_s, 3),
        "unit": "MB/s",
        "vs_baseline": 0.0,  # the 2017 reference has no streamed lane
        "stream_mb_per_sec": round(mb_s, 3),
        "data_wait_pct": round(wait_pct, 3),
        "swap_downtime_ms": round(swap_ms, 3),
        "requests_across_swap": len(stamps),
        "elapsed_s": round(wall, 3),
        **_obs_counters(),
        **_provenance(),
        "config": {"batch": batch, "dim": dim, "hidden": hidden,
                   "records": n},
    }))


def _book_positions(backend):
    """Book every token-position ``backend`` is handed from here on
    (prefill rows and decode rows, padding included): what a path is
    made to compute, whatever the clock says of it."""
    book = {"positions": 0}

    def booked(fn):
        def call(tokens, *rest):
            book["positions"] += len(tokens)
            return fn(tokens, *rest)
        return call

    backend.prefill = booked(backend.prefill)
    backend.decode = booked(backend.decode)
    return book


def generate_main():
    """Autoregressive generation lane (BENCH_GENERATE=1): the
    prefill/decode split with the paged KV cache vs the naive
    re-prefill-per-token baseline (one full-sequence forward per
    generated token, at a FIXED padded shape so the baseline pays no
    recompiles either).  The ≥2x acceptance ratio is taken on what
    the two paths are made to compute, ``positions_per_token_naive``
    over ``positions_per_token`` (token-positions pushed through the
    model for each generated token, padding included); the wall-clock
    ``speedup_vs_naive`` is printed beside it and gates nothing — on a
    shared CPU it read 1.24-2.26x over three runs of one tree.
    Schema-10 additive keys:
    ``tokens_per_sec`` (aggregate across concurrent users),
    ``tokens_per_sec_per_user``, ``inter_token_ms_p99`` (client-side,
    measured off the chunked token stream the way a user would),
    ``prefill_ms_p50`` (admission to first token), and
    ``kv_cache_occupancy`` (used/total blocks at full load)."""
    import threading as _threading

    import jax

    from mxnet_tpu import observability as obs
    from mxnet_tpu import serving
    from mxnet_tpu.models import transformer as tfm

    users = int(os.environ.get("BENCH_GEN_USERS", "4"))
    prompt_len = int(os.environ.get("BENCH_GEN_PROMPT", "8"))
    new_tokens = int(os.environ.get("BENCH_GEN_TOKENS", "32"))
    embed = int(os.environ.get("BENCH_GEN_EMBED",
                               "64" if _cpu_smoke() else "256"))
    layers = int(os.environ.get("BENCH_GEN_LAYERS", "2"))
    vocab = int(os.environ.get("BENCH_GEN_VOCAB", "512"))
    seq_len = prompt_len + new_tokens

    cfg = tfm.lm_config(num_classes=vocab, seq_len=seq_len,
                        num_embed=embed, num_heads=4, num_layers=layers)
    params = tfm.init_lm_params(cfg, seed=0)
    rs = np.random.RandomState(0)
    prompts = rs.randint(0, vocab, size=(users, prompt_len)).astype(
        np.int32)

    # naive baseline: every token re-runs the FULL forward over the
    # whole context (what serving looks like without a KV cache) —
    # one warm fixed-shape executor, one dispatch per token
    naive = serving.LMBackend(params, cfg, num_blocks=4)
    toks = list(prompts[0])
    naive.prefill(np.pad(prompts[0], (0, seq_len - prompt_len)),
                  prompt_len)                      # warm the executor
    naive_book = _book_positions(naive)
    t0 = time.perf_counter()
    for _ in range(new_tokens):
        padded = np.zeros(seq_len, np.int32)
        padded[:len(toks)] = toks
        logits, _, _, _ = naive.prefill(padded, len(toks))
        toks.append(int(np.argmax(logits)))
    tps_naive = new_tokens / (time.perf_counter() - t0)

    # the generation lane: paged cache, iteration-level batching
    blocks_needed = users * -(-seq_len // 16) + 4
    be = serving.LMBackend(params, cfg, block_size=16,
                           num_blocks=blocks_needed, model="bench_lm")
    sched = serving.GenerationScheduler(name="bench")
    decode_buckets = sorted({1, max(1, users // 2), users})
    sched.register("bench_lm", be, decode_buckets=decode_buckets,
                   prefill_buckets=[prompt_len])
    sched.warmup("bench_lm")
    book = _book_positions(be)
    compiles = obs.REGISTRY.get("generation_compiles_total")
    warm_compiles = int(compiles.total()) if compiles else 0

    arrivals = [[] for _ in range(users)]
    peak_occ = [0.0]

    def _consume(i, req):
        for _ in req.tokens(timeout=120):
            arrivals[i].append(time.perf_counter())
            peak_occ[0] = max(peak_occ[0],
                              be.cache.stats()["occupancy"])

    t0 = time.perf_counter()
    reqs = [sched.submit("bench_lm", prompts[i],
                         max_new_tokens=new_tokens)
            for i in range(users)]
    consumers = [_threading.Thread(target=_consume, args=(i, r))
                 for i, r in enumerate(reqs)]
    for c in consumers:
        c.start()
    for c in consumers:
        c.join(timeout=300)
    wall = time.perf_counter() - t0
    total_tokens = sum(len(r.generated) for r in reqs)
    tps = total_tokens / wall
    itl_ms = np.concatenate(
        [np.diff(np.asarray(a)) for a in arrivals if len(a) > 1]) * 1e3
    prefill_ms = np.asarray(
        [r.first_token_s for r in reqs if r.first_token_s]) * 1e3
    recompiles = (int(compiles.total()) if compiles else 0) \
        - warm_compiles
    sched.close()

    print(json.dumps({
        "metric": "generation_throughput" if not _cpu_smoke()
                  else "generation_cpu_smoke_throughput",
        "value": round(tps, 2), "unit": "tokens/s",
        "vs_baseline": 0.0,  # the 2017 reference has no generation lane
        "tokens_per_sec": round(tps, 2),
        "tokens_per_sec_per_user": round(tps / users, 2),
        "inter_token_ms_p99": round(
            float(np.percentile(itl_ms, 99)) if itl_ms.size else 0.0, 3),
        "prefill_ms_p50": round(
            float(np.percentile(prefill_ms, 50))
            if prefill_ms.size else 0.0, 3),
        "kv_cache_occupancy": round(peak_occ[0], 4),
        "tokens_per_sec_naive": round(tps_naive, 2),
        "speedup_vs_naive": round(tps / tps_naive, 2)
        if tps_naive > 0 else None,
        "positions_per_token": round(
            book["positions"] / float(total_tokens), 3),
        "positions_per_token_naive": round(
            naive_book["positions"] / float(new_tokens), 3),
        "recompiles_after_warmup": recompiles,
        **_obs_counters(),
        **_provenance(),
        "config": {"users": users, "prompt_len": prompt_len,
                   "new_tokens": new_tokens, "embed": embed,
                   "layers": layers, "vocab": vocab,
                   "decode_buckets": decode_buckets},
    }))


def main():
    import jax
    import mxnet_tpu  # noqa: F401
    from jax.sharding import Mesh
    from mxnet_tpu import compile_cache
    from mxnet_tpu.models import resnet
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    if jax.devices()[0].platform == "cpu" and not _cpu_smoke():
        raise SystemExit(
            "bench.py: JAX found no accelerator.  It does not shrink the "
            "model and print a CPU number: ask for the CPU smoke sizes "
            "with JAX_PLATFORMS=cpu")
    compile_cache.enable()
    if os.environ.get("BENCH_MEMORY") == "1":
        memory_main()
        return
    if os.environ.get("BENCH_KERNELS") == "1":
        kernels_main()
        return
    if os.environ.get("BENCH_FAIRNESS") == "1":
        fairness_main()
        return
    if os.environ.get("BENCH_WIRE") == "1":
        wire_main()
        return
    if os.environ.get("BENCH_SNAPSHOT") == "1":
        snapshot_main()
        return
    if os.environ.get("BENCH_GENERATE") == "1":
        generate_main()
        return
    if os.environ.get("BENCH_CONTINUOUS") == "1":
        continuous_main()
        return
    if os.environ.get("BENCH_ELASTIC") == "1":
        elastic_main()
        return
    if os.environ.get("BENCH_SERVING") == "1":
        serving_main()
        return
    if os.environ.get("BENCH_MODEL") == "transformer":
        transformer_main()
        return

    full = not _cpu_smoke()
    batch = int(os.environ.get("BENCH_BATCH", "128" if full else "8"))
    image = 224 if full else 28
    layers = 50 if full else 8
    steps = int(os.environ.get("BENCH_STEPS", "50" if full else "3"))

    layout = os.environ.get("BENCH_LAYOUT", "NHWC" if full else "NCHW")
    # space-to-depth stem measured faster on the real chip (2872.76 vs
    # 2755.92 img/s, 2026-07-31 driver-era A/B) — default for the TPU
    # path; the CPU smoke uses the 28px cifar-style stem where s2d does
    # not apply
    stem = os.environ.get(
        "BENCH_STEM",
        "s2d" if full and layout == "NHWC" else "conv7")
    # BENCH_PIPELINE=K fuses K optimizer steps into ONE dispatch
    # (ShardedTrainer.pipeline_steps): the per-call dispatch cost is
    # paid once per K steps — docs/PERF.md "Pipelined training"
    pipeline = int(os.environ.get("BENCH_PIPELINE", "1"))
    sym = resnet.get_symbol(num_classes=1000, num_layers=layers,
                            image_shape=(3, image, image), dtype="bfloat16",
                            layout=layout, stem=stem)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tr = ShardedTrainer(
        sym, mesh,
        data_shapes={"data": (batch, 3, image, image)},
        label_shapes={"softmax_label": (batch,)},
        momentum=0.9, learning_rate=0.1, wd=1e-4, rescale_grad=1.0 / batch,
        pipeline_steps=pipeline,
    )
    params, moms, aux = tr.init(seed=0)
    host = {
        "data": np.random.uniform(-1, 1, (batch, 3, image, image)).astype(np.float32),
        "softmax_label": np.random.randint(0, 1000, (batch,)).astype(np.float32),
    }
    key = jax.random.PRNGKey(0)
    from mxnet_tpu.observability import efficiency as _eff

    # goodput ledger over the whole warmup+measure window: the warmup
    # dispatch books as a step whose compile seconds settle out as
    # cause="recompile", the timed loops book as productive wall
    led = _eff.ledger()
    t_bench = time.perf_counter()

    if pipeline > 1:
        sb = tr.place_superbatch([host] * pipeline)
        pipe = tr.pipeline_fn(pipeline)
        outs, params, moms, aux = pipe(params, moms, aux, sb, key,
                                       np.int32(0))
        _sync(outs)
        led.step(time.perf_counter() - t_bench)
        t0 = time.perf_counter()
        for i in range(steps):
            outs, params, moms, aux = pipe(
                params, moms, aux, sb, key, np.int32((i + 1) * pipeline))
        _sync(outs)
        dt = time.perf_counter() - t0
        led.step(dt)
        img_s = batch * steps * pipeline / dt

        def _one_flush():
            nonlocal params, moms, aux
            outs, params, moms, aux = pipe(
                params, moms, aux, sb, key, np.int32(0))
            return outs

        t_pct = time.perf_counter()
        p50_ms, p99_ms = _step_percentiles(_one_flush, _sync,
                                           min(steps, 10),
                                           per_call_steps=pipeline)
        led.step(time.perf_counter() - t_pct)
    else:
        data = tr.place_batch(host)
        step = tr.step_fn()
        outs, params, moms, aux = step(params, moms, aux, data, key)
        _sync(outs)
        led.step(time.perf_counter() - t_bench)
        t0 = time.perf_counter()
        for i in range(steps):
            outs, params, moms, aux = step(params, moms, aux, data, key)
        _sync(outs)
        dt = time.perf_counter() - t0
        led.step(dt)
        img_s = batch * steps / dt

        def _one_step():
            nonlocal params, moms, aux
            outs, params, moms, aux = step(params, moms, aux, data, key)
            return outs

        t_pct = time.perf_counter()
        p50_ms, p99_ms = _step_percentiles(_one_step, _sync,
                                           min(steps, 10))
        led.step(time.perf_counter() - t_pct)

    eff_keys = _efficiency_keys(led, time.perf_counter() - t_bench,
                                steps * pipeline, dt)
    print(json.dumps({
        "metric": "resnet50_train_throughput" if full
                  else "resnet8_cpu_smoke_throughput",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        # additive contract keys: per-step latency distribution from the
        # synced percentile loop; tokens == samples for the image bench
        "step_ms_p50": p50_ms, "step_ms_p99": p99_ms,
        "tokens_per_sec": round(img_s, 2),
        **_obs_counters(),
        **_provenance(),
        **eff_keys,
        **({"pipeline_steps": pipeline} if pipeline > 1 else {}),
    }))


if __name__ == "__main__":
    main()
