"""Traffic of kind ``serve-closed``: callers over the HTTP front end,
each sending its next ``/v1/generate`` when its last one ended.

The requests are a fixed list of (prompt length, new tokens) pairs — the
mid-quantiles of the mix's two lognormals, dealt into rounds of one
request per caller so that every round is a stratified sample of the
mix — that every seed sends alike, in the same order; the seed draws
the weights and the prompts' token ids.  So the seed changes which
bytes are served, not how much work a window holds: a 45 s window sees
about two rounds, and with an order of its own each seed would see
other requests (read so on the chip, PR 23: 2.6% spread of tokens/s
across seeds against 0.3% between two runs of one seed).  The traffic
file says which rounds the window holds.  The callers are threads of
this process (one process uses the chip).  Set-up warms every prefill
and decode bucket, starts the callers staggered and waits until each is
mid-stream.  The window opens at the end of a decode call and closes at
the end of the first decode call ``--seconds`` later, so it holds whole
decode steps; tokens and first tokens are counted by when they reached
their caller.
"""

import http.client
import json
import statistics
import threading
import time

import numpy as np

from benchmark import compare

MODEL = "bench_lm"


def lengths(dist, count):
    """``count`` whole numbers at the mid-quantiles of a clipped
    lognormal, ascending."""
    if dist["dist"] != "lognormal":
        raise ValueError("unknown distribution %r" % dist["dist"])
    normal = statistics.NormalDist()
    out = []
    for i in range(count):
        z = normal.inv_cdf((i + 0.5) / count)
        value = int(round(dist["median"] * np.exp(dist["sigma"] * z)))
        out.append(min(dist["max"], max(dist["min"], value)))
    return out


def dealt(values, rounds, rng):
    """``values`` (ascending) dealt into ``rounds`` rounds, each taking
    one value from every run of ``rounds`` neighbours: every round is a
    stratified sample of the whole list."""
    per_round = len(values) // rounds
    picks = [rng.permutation(rounds) for _ in range(per_round)]
    return [[values[j * rounds + picks[j][b]] for j in range(per_round)]
            for b in range(rounds)]


def request_set(traffic, vocab, seed):
    """The run's requests, in the order they are sent: rounds of one
    request per caller, every round a stratified sample of the mix's two
    lognormals (so whichever rounds a window holds, it holds the mix and
    not a corner of it).  Lengths, pairs and order are fixed numbers of
    the traffic file (``deal_seed``); the seed draws the token ids."""
    count, n = traffic["requests"], traffic["clients"]
    if count % n:
        raise ValueError("%d requests do not make whole rounds of %d"
                         % (count, n))
    rng = np.random.RandomState(traffic["deal_seed"])
    prompts = dealt(lengths(traffic["prompt_tokens"], count), count // n, rng)
    news = dealt(lengths(traffic["new_tokens"], count), count // n, rng)
    pairs = []
    for round_prompts, round_news in zip(prompts, news):
        order = rng.permutation(n)
        match = rng.permutation(n)
        pairs += [(round_prompts[order[i]], round_news[match[i]])
                  for i in range(n)]
    for p, m in pairs:
        if p + m > traffic["max_total_tokens"]:
            raise ValueError("a request of %d + %d tokens passes %d"
                             % (p, m, traffic["max_total_tokens"]))
    tokens = np.random.RandomState(seed % (2 ** 32))
    return [{"prompt": tokens.randint(0, vocab, p).tolist(),
             "max_new_tokens": m} for p, m in pairs]


class Caller(threading.Thread):
    """One caller: sends its requests one after another and notes when
    each token reached it."""

    def __init__(self, port, requests, stop, timeout, first_share):
        threading.Thread.__init__(self, daemon=True)
        self.port, self.stop_flag = port, stop
        # the caller's first request, sent during warm-up, asks for only
        # ``first_share`` of its tokens: the callers then enter the
        # window at different points of their requests, as callers that
        # have been at it for a long time are, and not all at the start
        first = dict(requests[0])
        first["max_new_tokens"] = max(
            2, int(round(first["max_new_tokens"] * first_share)))
        self.requests = [first] + list(requests[1:])
        self.timeout = timeout
        self.records = []
        self.streaming = threading.Event()

    def run(self):
        i = 0
        while not self.stop_flag.is_set():
            # the cut first request is sent once; later rounds are whole
            req = self.requests[i] if i < len(self.requests) \
                else self.requests[1 + (i - 1) % (len(self.requests) - 1)]
            i += 1
            rec = {"prompt": req["prompt"], "want": req["max_new_tokens"],
                   "sent": time.perf_counter(), "arrivals": [],
                   "tokens": [], "status": None, "done": False,
                   "error": None}
            self.records.append(rec)
            try:
                self._one(req, rec)
            except Exception as exc:  # noqa: BLE001 — counted as failed
                if not self.stop_flag.is_set():
                    rec["error"] = "%s: %s" % (type(exc).__name__, exc)

    def _one(self, req, rec):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=self.timeout)
        try:
            conn.request(
                "POST", "/v1/generate",
                json.dumps({"model": MODEL, "prompt": req["prompt"],
                            "max_new_tokens": req["max_new_tokens"]}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = resp.read()[:200].decode("utf-8", "replace")
                return
            for raw in resp:
                if self.stop_flag.is_set():
                    return
                line = json.loads(raw)
                if line.get("done"):
                    rec["done"] = (line.get("finish_reason") == "length"
                                   and line.get("tokens") == rec["tokens"])
                    if not rec["done"]:
                        rec["error"] = "tail %r" % (line,)
                    return
                rec["arrivals"].append(time.perf_counter())
                rec["tokens"].append(line["token"])
                self.streaming.set()
        finally:
            conn.close()


def measured_backend(job, stats):
    """Subclass ``LMBackend`` with spans around ``prefill`` and
    ``decode`` and a count of the host bytes each decode call hands to
    the device."""

    def wrap(base):
        class Measured(base):
            def prefill(self, tokens, length):
                with job.spans.span("prefill_call", keep=True):
                    out = base.prefill(self, tokens, length)
                stats["prefill_logits"][tuple(
                    int(t) for t in tokens[:length])] = out[0]
                return out

            def decode(self, tokens, positions, block_tables,
                       context_lens):
                staged = sum(a.nbytes for a in list(self.params.values())
                             + [self.cache.k_pages, self.cache.v_pages]
                             if isinstance(a, np.ndarray))
                with job.spans.span("decode_call", keep=True):
                    out = base.decode(self, tokens, positions,
                                      block_tables, context_lens)
                stats["decode_logits"].append(
                    (np.array(tokens), np.array(positions), out[0]))
                stats["decode_ends"].append(time.perf_counter())
                stats["staged_bytes"].append(staged)
                stats["rows"].append(len(tokens))
                stats["occupancy"].append(
                    self.cache.stats()["occupancy"])
                return out

        return Measured

    return wrap


def run(job):
    from mxnet_tpu import serving

    cfg, traffic = job.config, job.traffic
    model = job.spec.model(cfg["family"])
    serve = cfg["deployment"]["serve"]
    stats = {"decode_ends": [], "staged_bytes": [], "rows": [],
             "occupancy": [], "prefill_logits": {}, "decode_logits": []}

    weights = model.make_weights(cfg, job.seed)
    backend = model.build_backend(cfg, serve, weights, MODEL,
                                  measured_backend(job, stats))
    del weights
    sched = serving.GenerationScheduler(name="bench")
    sched.register(MODEL, backend,
                   decode_buckets=traffic["decode_buckets"],
                   prefill_buckets=traffic["prefill_buckets"])
    t0 = time.perf_counter()
    warm_shapes = sched.warmup(MODEL)
    compile_s = time.perf_counter() - t0
    fe = serving.start_frontend(sched, timeout=traffic["request_timeout_s"])
    stop = threading.Event()
    requests = request_set(traffic, cfg["vocab_size"], job.seed)
    n = traffic["clients"]
    shares = np.random.RandomState(traffic["deal_seed"] + 1).permutation(n)
    callers = [Caller(fe.port, requests[i::n], stop,
                      traffic["request_timeout_s"],
                      first_share=(shares[i] + 0.5) / n) for i in range(n)]
    try:
        for c in callers:
            c.start()
            time.sleep(traffic["stagger_s"])
        for c in callers:
            if not c.streaming.wait(timeout=traffic["request_timeout_s"]):
                raise RuntimeError("a caller got no token in %d s"
                                   % traffic["request_timeout_s"])

        # ---- the window: from the end of one decode call to the end of
        # the first one --seconds later
        before = job.counters.snapshot()
        job.window_opens()
        first = len(stats["decode_ends"])
        while len(stats["decode_ends"]) <= first:
            time.sleep(0.005)
        t_a = stats["decode_ends"][first]
        if job.trace:
            job.profiler.start()
            time.sleep(min(traffic["traced_seconds"], job.seconds))
            job.profiler.stop()
        while stats["decode_ends"][-1] < t_a + job.seconds:
            time.sleep(0.01)
        ends = stats["decode_ends"]
        last = next(i for i in range(first, len(ends))
                    if ends[i] >= t_a + job.seconds)
        t_b = ends[last]
        in_window = job.counters.since(before)
        peak = job.memory_peak()
    finally:
        stop.set()
        fe.close()
        sched.close()
        for c in callers:
            c.join(timeout=30)
    if job.trace:
        job.profiler.reduce()

    records = [r for c in callers for r in c.records]

    def inside(t):
        return t_a < t <= t_b

    tokens = sum(inside(t) for r in records for t in r["arrivals"])
    started = [r for r in records
               if (r["arrivals"] and inside(r["arrivals"][0]))
               or (r["error"] and not r["arrivals"] and inside(r["sent"]))]
    # a request that failed before its first token waited the longest a
    # caller waits
    ttft = [(r["arrivals"][0] - r["sent"]) if r["arrivals"]
            else float(traffic["request_timeout_s"]) for r in started]
    gaps = [b - a for r in records
            for a, b in zip(r["arrivals"], r["arrivals"][1:]) if inside(b)]
    finished = [r for r in records if r["done"] and inside(r["arrivals"][-1])]
    failed = [r for r in records if r["error"]]
    for r in failed:
        print("request failed: status %s: %s" % (r["status"], r["error"]),
              flush=True)
    print("window %.3f s, %d decode steps, %d tokens, %d first tokens, "
          "%d requests finished, %d failed"
          % (t_b - t_a, last - first, tokens, len(started), len(finished),
             len(failed)), flush=True)
    print("first tokens, ms after their request was sent: %s"
          % " ".join("%.1f" % (1e3 * t) for t in sorted(ttft)), flush=True)
    if gaps:
        ordered = sorted(gaps)
        print("gaps between tokens at the callers: %d, ms mean %.2f p50 %.2f "
              "p95 %.2f p99 %.2f max %.2f"
              % (len(gaps), 1e3 * statistics.fmean(gaps),
                 *(1e3 * ordered[min(len(gaps) - 1, int(q * len(gaps)))]
                   for q in (0.5, 0.95, 0.99)), 1e3 * ordered[-1]),
              flush=True)

    for name in ("decode_call", "prefill_call"):
        took = sorted(dt for end, dt in job.spans.samples.get(name, ())
                      if inside(end))
        if took:
            print("%s: %d in the window, seconds min %.3f median %.3f "
                  "max %.3f" % (name, len(took), took[0],
                                took[len(took) // 2], took[-1]), flush=True)

    # ---- the reference, on a sample of what the window finished
    del backend, sched
    rows = check_served(job, model, cfg, finished, traffic, stats)
    reference_path = job.relative(job.spec.reference(cfg["name"])[1])
    correct = compare.report(cfg["name"], reference_path, rows) \
        and bool(finished)

    def window_spans(name):
        return [dt for end, dt in job.spans.samples.get(name, ())
                if inside(end)]

    return {
        "correct": correct, "attempted": len(started),
        "failed": len(failed), "memory_peak_bytes": peak,
        "readings": {
            "kind": "serve", "window_s": t_b - t_a, "tokens": tokens,
            "ttft_s": ttft, "itl_s": gaps, "finished": len(finished),
            "decode_steps": last - first,
            "decode_rows": stats["rows"][first + 1:last + 1],
            "staged_bytes": stats["staged_bytes"][first + 1:last + 1],
            "occupancy": stats["occupancy"][first + 1:last + 1],
            "prefill_s": window_spans("prefill_call"),
            "decode_s": window_spans("decode_call"),
            "compile_s": compile_s, "warm_shapes": warm_shapes,
            "compiles_in_window": in_window, "chips": len(job.devices),
            "counter_tokens": in_window.get("generation_tokens_total"),
            "counter_steps": in_window.get("generation_decode_steps_total"),
            "dtype": serve["dtype"]}}


def served_logits(stats):
    """The logits the timed path produced, findable by what produced
    them: the first token's by its prompt, a later token's by the
    (position, token consumed) of its decode row."""
    rows = {}
    for tokens, positions, logits in stats["decode_logits"]:
        for i in range(len(tokens)):
            rows.setdefault((int(positions[i]), int(tokens[i])),
                            []).append(logits[i])
    return stats["prefill_logits"], rows


def program_logit_error(request, ref_logits, prefill_logits, decode_rows):
    """Largest |program - reference| over the logits behind the
    request's served tokens, and how many of them were found.  Where two
    requests agree on position and token, the nearer row is the
    request's."""
    prompt, served = request["prompt"], request["tokens"]
    at = len(prompt) - 1
    worst, found = 0.0, 0
    first = prefill_logits.get(tuple(prompt))
    if first is not None:
        worst = float(np.abs(first - ref_logits[at]).max())
        found = 1
    for j in range(1, len(served)):
        errs = [float(np.abs(row - ref_logits[at + j]).max())
                for row in decode_rows.get((at + j, served[j - 1]), ())]
        if errs:
            worst = max(worst, min(errs))
            found += 1
    return worst, found


def check_served(job, model, cfg, finished, traffic, stats, mode="float32"):
    """(name, value, limit) over a seeded sample of the finished
    requests with the longest among them: the widest gap by which a
    served token's reference logit lies below the reference's best, and
    the largest difference between the logits the timed path produced
    for those tokens (prefill, then decode through the cache) and the
    reference's full forward."""
    import jax
    import jax.numpy as jnp

    reference, _ = job.spec.reference(cfg["name"])
    rng = np.random.RandomState((job.seed + 1) % (2 ** 32))
    sample = compare.sample_finished(finished, rng,
                                     traffic["checked_requests"])
    weights = model.make_weights(cfg, job.seed)
    width = cfg["n_positions"]
    forward = jax.jit(lambda p, t: reference.logits(cfg, p, t, mode)[0])
    prefill_logits, decode_rows = served_logits(stats)
    worst_gap, worst_err, served, found = 0.0, 0.0, 0, 0
    t0 = time.perf_counter()
    for r in sample:
        seq = np.zeros((1, width), np.int32)
        toks = r["prompt"] + r["tokens"]
        seq[0, :len(toks)] = toks
        logits = np.asarray(forward(weights, jnp.asarray(seq)))
        gaps = compare.served_token_gaps(logits, len(r["prompt"]),
                                         r["tokens"])
        worst_gap = max(worst_gap, float(gaps.max()))
        err, n = program_logit_error(r, logits, prefill_logits, decode_rows)
        worst_err, found = max(worst_err, err), found + n
        served += len(r["tokens"])
    print("reference ran over %d requests, %d served tokens (%d of their "
          "logits found), in %.2f s"
          % (len(sample), served, found, time.perf_counter() - t0),
          flush=True)
    if found < served:             # a served token nothing produced
        worst_err = float("inf")
    return [("served_token_logit_gap[%d tokens]" % served, worst_gap,
             job.limits["served_token_logit_gap"]),
            ("served_logit_abs_err[%d logits]" % found, worst_err,
             job.limits["served_logit_abs_err"])]
