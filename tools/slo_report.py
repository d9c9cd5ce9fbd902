"""``make slo`` / ``python tools/slo_report.py``: SLO error budgets.

Prints one row per SLO — objective, good/bad totals, error rate, and
the fraction of error budget remaining — from a metrics exposition:

    python tools/slo_report.py                      # self-contained demo
    python tools/slo_report.py --url http://host:9100/metrics
    python tools/slo_report.py --file metrics.prom

Exit status is the contract: **nonzero when any budget is exhausted**,
so the report slots into CI and release gates as-is.  The default mode
is a self-contained demo — a tiny numpy-backed model behind the
continuous-batching scheduler answers a burst of requests, then the
budgets are read back from the metrics the serving tier emitted
(``--breach`` sheds traffic against a drained replica first, proving
the nonzero-exit path).
"""

import argparse
import json
import os
import sys
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_TPU_METRICS", "1")


def format_slo_table(rows):
    """The report as an aligned text table (one row per SLO)."""
    head = ("slo", "kind", "objective", "good", "bad", "error_rate",
            "burn", "budget_left", "state")
    table = [head]
    for r in rows:
        table.append((
            r["slo"], r["kind"], "%.4f" % r["objective"],
            "%d" % r["good"], "%d" % r["bad"],
            "%.5f" % r["error_rate"], "%.2fx" % r["budget_consumed"],
            "%.4f" % r["budget_remaining"],
            "EXHAUSTED" if r["exhausted"] else "ok"))
    widths = [max(len(row[i]) for row in table)
              for i in range(len(head))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(row, widths))
                     for row in table)


def _demo_source(breach):
    """Drive a tiny serving stack so the registry has something to
    report on; with ``breach`` the replica drains first and traffic is
    shed, exhausting the availability budget."""
    import numpy as np

    from mxnet_tpu import serving

    class _SumBackend(serving.Backend):
        # pure-numpy backend: no compile, no accelerator — the point is
        # the metrics, not the model
        input_shapes = {"data": (4,)}
        buckets = None

        def infer(self, batch):
            return [batch["data"].sum(axis=1, keepdims=True)], False

    sched = serving.Scheduler(name="slo-demo")
    sched.register("demo", _SumBackend(), buckets=[1, 4])
    row = np.ones(4, dtype=np.float32)
    for _ in range(32):
        sched.request("demo", {"data": row})
    if breach:
        sched.drain()
        for _ in range(8):
            try:
                sched.submit("demo", {"data": row})
            except serving.ServingError:
                pass
    sched.close()
    return None      # report() reads the process-global registry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", default=None,
                    help="scrape this /metrics endpoint")
    ap.add_argument("--file", default=None,
                    help="read exposition text from this file")
    ap.add_argument("--json", action="store_true",
                    help="emit the raw /slo JSON instead of the table")
    ap.add_argument("--breach", action="store_true",
                    help="demo mode only: shed traffic first so the "
                         "availability budget exhausts (exit 1)")
    args = ap.parse_args(argv)

    from mxnet_tpu.observability import slo as _slo

    if args.url:
        with urllib.request.urlopen(args.url, timeout=10) as resp:
            source = resp.read().decode("utf-8")
    elif args.file:
        with open(args.file, encoding="utf-8") as f:
            source = f.read()
    else:
        source = _demo_source(args.breach)

    report = _slo.report(source)
    if report.get("disabled"):
        print("metrics are disabled (MXNET_TPU_METRICS=0): no budgets "
              "to report")
        return 0
    rows = report["slos"]
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(format_slo_table(rows))
    exhausted = [r["slo"] for r in rows if r["exhausted"]]
    if exhausted:
        print("error budget EXHAUSTED: %s" % ", ".join(exhausted))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
