"""``make fairness`` / ``python tools/loadgen.py``: the multi-tenant
robustness drill.

A self-contained synthetic load generator proving the PR-16 fairness
contract the repo's way — drive the real stack, assert on the real
metrics, exit non-zero on any miss.  Four acts, a few seconds with
``JAX_PLATFORMS=cpu`` (what the ``make`` target asks for):

1. **Fairness under heavy-tailed skew.**  Three tenants hammer one
   numpy-backed replica group — ``bulk`` sends ~8× the load of
   ``gold`` and ``silver`` (the heavy tail) and holds a tight
   requests/s quota.  Assert: ``bulk`` is shed with typed per-tenant
   429s (``QuotaExceededError``, ``serving_rejected_total{reason=
   "quota",tenant="bulk"}``) while ``gold``'s p99 stays inside its SLO
   — overload degrades per tenant, never globally.
2. **Zero dropped accepted work across elastic scale.**  One
   ``grow(1)`` and one ``shrink(1)`` land mid-load; every request the
   group *accepted* must answer (the PR-8/PR-11 brownout contract,
   now under multi-tenant queues).
3. **KV-affinity routing.**  A tiny LM replica group behind
   :class:`~mxnet_tpu.serving.KVAffinityRouter`, with a seeded
   ``serving.route`` chaos rule knocking candidates out of rotation:
   assert ``kv_affinity_hit_ratio`` ends > 0, and that a session
   forced off its home replica re-prefills to a **bitwise-identical**
   token stream (a spill costs latency, never correctness).
4. **Per-tenant budgets federate.**  Run the SLO report over the
   process registry, then a :class:`~mxnet_tpu.observability.
   federation` pass, and assert ``slo_error_budget_remaining{slo,
   tenant}`` rows ride the federated exposition.

Knobs (env): ``LOADGEN_REQUESTS`` (default 240 fairness requests),
``LOADGEN_SEED`` (chaos + skew seed, default 16).
"""

import os
import random
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_TPU_METRICS", "1")

import numpy as np                                    # noqa: E402

from mxnet_tpu import chaos, serving                  # noqa: E402
from mxnet_tpu import observability as obs            # noqa: E402
from mxnet_tpu.observability import metrics as omet   # noqa: E402
from mxnet_tpu.observability import slo as oslo       # noqa: E402

FAILURES = []


def check(ok, what):
    tag = "ok  " if ok else "FAIL"
    print("  [%s] %s" % (tag, what))
    if not ok:
        FAILURES.append(what)


class _SlowEcho(serving.Backend):
    """Numpy backend with a tiny fixed service time, so queues actually
    form and fairness is observable."""

    input_shapes = {"data": (4,)}

    def __init__(self, delay_s=0.002):
        self.delay_s = delay_s

    def infer(self, batch):
        time.sleep(self.delay_s)
        return [batch["data"] * 2.0], False


def _fairness_and_scale(n_requests, seed):
    print("== fairness under heavy-tailed skew + elastic scale ==")
    group = serving.ReplicaGroup(replicas=2, group="fairpool")
    group.register("mlp", lambda: _SlowEcho(), buckets=[1, 2, 4, 8])
    group.tenant_policy.set_weight("gold", 3.0)
    group.tenant_policy.set_weight("silver", 1.0)
    # the saturating tenant: weight 1 AND a tight request budget
    group.tenant_policy.set_quota("bulk", rps=20.0)
    router = serving.ServingRouter(group)

    rng = random.Random(seed)
    # heavy tail: bulk is ~80% of offered load
    tenants = ["bulk"] * 8 + ["gold", "silver"]
    lat = {"gold": [], "silver": [], "bulk": []}
    sheds = {"bulk": 0, "gold": 0, "silver": 0}
    dropped = []           # accepted-but-unanswered: must stay empty
    lock = threading.Lock()
    row = {"data": np.ones(4, np.float32)}

    def one(tenant):
        t0 = time.monotonic()
        try:
            router.request("mlp", row, tenant=tenant, timeout=30.0)
        except serving.QuotaExceededError as exc:
            with lock:
                sheds[tenant] += 1
            assert exc.http_status == 429
            return
        except serving.ServerOverloadedError:
            with lock:
                sheds[tenant] += 1
            return
        except Exception as exc:       # accepted work must never die
            with lock:
                dropped.append("%s: %r" % (tenant, exc))
            return
        with lock:
            lat[tenant].append(time.monotonic() - t0)

    threads = []
    grew = shrunk = False
    for i in range(n_requests):
        tenant = tenants[rng.randrange(len(tenants))]
        th = threading.Thread(target=one, args=(tenant,))
        th.start()
        threads.append(th)
        if i == n_requests // 3 and not grew:
            grow = group.grow(1)
            grew = True
            print("  grow mid-load:", grow)
        if i == (2 * n_requests) // 3 and not shrunk:
            shrink = group.shrink(1, timeout=30.0)
            shrunk = True
            print("  shrink mid-load:", shrink)
        time.sleep(0.001)
    for th in threads:
        th.join(timeout=60.0)

    def p99(xs):
        if not xs:
            return float("nan")
        return sorted(xs)[min(len(xs) - 1, int(0.99 * len(xs)))]

    gold_p99 = p99(lat["gold"])
    slo_s = float(os.environ.get("LOADGEN_SLO_S", "0.5"))
    print("  answered: gold=%d silver=%d bulk=%d; quota sheds bulk=%d"
          % (len(lat["gold"]), len(lat["silver"]), len(lat["bulk"]),
             sheds["bulk"]))
    print("  gold p99 = %.1f ms (SLO %.0f ms)"
          % (gold_p99 * 1e3, slo_s * 1e3))
    check(grew and shrunk, "one grow and one shrink landed mid-load")
    check(not dropped, "zero accepted requests dropped across scale "
                       "events%s" % ("" if not dropped
                                     else ": " + "; ".join(dropped[:3])))
    check(sheds["bulk"] > 0,
          "saturating tenant shed with typed per-tenant 429s "
          "(%d quota sheds)" % sheds["bulk"])
    check(len(lat["gold"]) > 0 and gold_p99 <= slo_s,
          "innocent tenant p99 inside SLO under saturation")
    rej = omet.REGISTRY.get("serving_rejected_total")
    check(rej.labels("mlp", "quota", "bulk").value > 0,
          "sheds booked in serving_rejected_total{reason=quota,"
          "tenant=bulk}")
    group.close()
    return sheds, lat


def _affinity(seed):
    print("== KV-affinity routing under seeded serving.route chaos ==")
    from mxnet_tpu.models import transformer as tfm

    cfg = tfm.lm_config(num_classes=64, seq_len=48, num_embed=16,
                        num_heads=2, num_layers=2)
    params = tfm.init_lm_params(cfg, seed=0)
    group = serving.ReplicaGroup(
        replicas=2, group="genpool",
        scheduler_cls=serving.GenerationScheduler)
    group.register("lm", lambda: serving.LMBackend(
        params, cfg, block_size=4, num_blocks=64))
    router = serving.KVAffinityRouter(group)
    prompt = np.arange(1, 9, dtype=np.int32)

    # the cold reference: a sessionless one-shot generation
    cold = router.generate("lm", prompt, max_new_tokens=6, timeout=120)

    # seeded chaos: every ~3rd routing candidate is unroutable — the
    # drill for spill / re-home without ever dropping work
    chaos.clear()
    chaos.inject("serving.route", "drop", prob=0.34, seed=seed)
    streams = []
    for i in range(12):
        session = "s%d" % (i % 3)       # 3 sticky sessions, revisited
        streams.append(router.generate("lm", prompt, max_new_tokens=6,
                                       session=session, tenant="gold",
                                       timeout=120))
    chaos.clear()
    check(all(s == cold for s in streams),
          "12/12 chaos-routed generations bitwise-equal to the cold "
          "session (re-prefill spill is correctness-free)")
    ratio = omet.REGISTRY.get("kv_affinity_hit_ratio")
    val = ratio.labels("genpool").value
    print("  kv_affinity_hit_ratio = %.3f (hits %d / lookups %d)"
          % (val, router._hits, router._lookups))
    check(val > 0, "kv_affinity_hit_ratio > 0 with affinity on")
    route = omet.REGISTRY.get("serving_route_total")
    outcomes = {o: route.labels("genpool", o).value
                for o in ("hit", "miss", "spill", "dead", "failover")}
    print("  serving_route_total:", outcomes)
    group.close()
    return outcomes


def _federated_budgets():
    print("== per-tenant error budgets federate ==")
    report = oslo.report()           # sets the {slo, tenant} gauges
    avail = [r for r in report["slos"]
             if r["slo"] == "availability"][0]
    check("tenants" in avail and "bulk" in avail["tenants"],
          "/slo report carries per-tenant availability rows")
    out = obs.federate([{"shard": 0, "role": "serving", "epoch": 1,
                         "registry": omet.REGISTRY}])
    rows = [l for l in out.splitlines()
            if l.startswith("slo_error_budget_remaining{")]
    per_tenant = [l for l in rows
                  if 'tenant="all"' not in l and "tenant=" in l]
    for l in rows[:6]:
        print("  " + l)
    check(any('tenant="all"' in l for l in rows),
          "aggregate budget row federates")
    check(len(per_tenant) > 0,
          "per-tenant slo_error_budget_remaining rows federate")


def main():
    n = int(os.environ.get("LOADGEN_REQUESTS", "240"))
    seed = int(os.environ.get("LOADGEN_SEED", "16"))
    t0 = time.monotonic()
    _fairness_and_scale(n, seed)
    _affinity(seed)
    _federated_budgets()
    dt = time.monotonic() - t0
    if FAILURES:
        print("\nFAIL (%d): %s  [%.1fs]" % (len(FAILURES),
                                            "; ".join(FAILURES), dt))
        return 1
    print("\nfairness drill PASS  [%.1fs]" % dt)
    return 0


if __name__ == "__main__":
    sys.exit(main())
