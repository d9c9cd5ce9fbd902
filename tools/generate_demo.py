"""``make generate`` / ``python tools/generate_demo.py``: the
autoregressive generation lane, end to end; a few seconds with
``JAX_PLATFORMS=cpu``, which the ``make`` target asks for (the bitwise
check below is a CPU contract; on a chip ``chip_smoke.py`` holds the
same lane to a tolerance).

Builds a tiny randomly-initialized transformer LM, registers it on a
:class:`~mxnet_tpu.serving.GenerationScheduler` (paged KV cache,
prefill/decode split), starts the HTTP front-end, and streams tokens
over ``POST /v1/generate`` with chunked transfer encoding — printing
each token AS IT ARRIVES, the way a chat client would.  Then it
verifies the contracts the round-14 issue names:

- the streamed tokens equal a naive re-prefill-per-token full-forward
  chain BITWISE (the KV cache changed nothing but the cost);
- steady-state generation compiled nothing after warmup;
- concurrent prompts share decode steps (iteration-level batching).

Exits non-zero on any miss.  No checkpoint.
"""

import json
import http.client
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_TPU_METRICS", "1")

import numpy as np  # noqa: E402

from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.models import transformer as tfm  # noqa: E402


def main():
    vocab, seq_len = 256, 64
    cfg = tfm.lm_config(num_classes=vocab, seq_len=seq_len,
                        num_embed=64, num_heads=4, num_layers=2)
    params = tfm.init_lm_params(cfg, seed=7)
    backend = serving.LMBackend(params, cfg, block_size=16,
                                num_blocks=32, model="demo_lm")
    sched = serving.GenerationScheduler(name="demo")
    sched.register("demo_lm", backend, decode_buckets=[1, 2, 4],
                   prefill_buckets=[8, 16])
    print("warmup: %d shapes compiled" % sched.warmup("demo_lm"))
    compiles = sched._fam["compiles"].labels("demo_lm")
    warm = compiles.value

    fe = serving.start_frontend(sched)
    print("serving %s/v1/generate" % fe.url)

    prompt = [3, 141, 59, 26, 53, 58]
    conn = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=60)
    conn.request("POST", "/v1/generate",
                 json.dumps({"model": "demo_lm", "prompt": prompt,
                             "max_new_tokens": 24}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200, resp.status
    print("prompt %r ->" % (prompt,))
    streamed, tail = [], None
    t0 = time.perf_counter()
    for raw in resp:                       # chunk-decoded line iterator
        line = json.loads(raw)
        if line.get("done"):
            tail = line
            break
        streamed.append(line["token"])
        print("  +%6.1fms  token %d"
              % ((time.perf_counter() - t0) * 1e3, line["token"]))
    assert tail and tail["tokens"] == streamed, "stream/summary mismatch"
    print("finish_reason=%s (%d tokens)"
          % (tail["finish_reason"], len(streamed)))

    # parity vs the naive chain: re-run the full forward per token
    toks = list(prompt)
    for _ in range(24):
        logits, _, _ = tfm.lm_prefill(
            params, np.asarray(toks, np.int32)[None], cfg)
        toks.append(int(np.argmax(np.asarray(logits)[0, len(toks) - 1])))
    assert toks[len(prompt):] == streamed, \
        "paged-cache decode diverged from the full forward"
    print("parity: streamed tokens == full-forward chain")

    # concurrent prompts: iteration-level batching shares decode steps
    reqs = [sched.submit("demo_lm",
                         np.asarray(p, np.int32), max_new_tokens=16)
            for p in ([5, 9, 2], [100, 3], [42, 77, 18, 6])]
    for r in reqs:
        r.result(timeout=60)
    stats = sched.stats("demo_lm")
    assert stats["max_step_rows"] >= 2, "no decode step was shared"
    print("iteration-level batching: up to %d sequences per decode "
          "step, occupancy %.2f"
          % (stats["max_step_rows"], stats["occupancy"]))

    assert compiles.value == warm, "steady-state generation recompiled"
    print("zero steady-state recompiles after warmup")

    fe.close()
    sched.close()
    print("generation demo: OK")


if __name__ == "__main__":
    main()
