"""Generate the API reference from the live registry and library surface.

The reference ships a sphinx docs subsystem whose per-op API pages are
reflected out of the C++ registry's ``describe()`` strings
(``docs/api/python/``, the autogen docstrings in ``python/mxnet/
ndarray.py``, ``docs/sphinx_util.py``).  Same contract here, one source of
truth: walk ``OP_REGISTRY`` (+ ``opdocs.describe``) and the live Python
classes, and emit

  docs/api/ops.md     — every registered operator: description, inputs,
                        auxiliary states, outputs, attribute table, aliases
  docs/api/python.md  — the library surfaces (Module, KVStore, IO,
                        optimizer, metric, ...) reflected from class
                        docstrings and signatures

Both files are checked in; CI regenerates and diffs them
(``tests/test_docs.py``), so a new op or method cannot land undocumented
or with stale docs.

Run:  python tools/gen_docs.py [--check]
"""

import argparse
import inspect
import io
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

OUT_OPS = os.path.join(ROOT, "docs", "api", "ops.md")
OUT_PY = os.path.join(ROOT, "docs", "api", "python.md")


# ---------------------------------------------------------------------
# ops.md
# ---------------------------------------------------------------------

def _op_category(name, op):
    mod = op.fn.__module__.rsplit(".", 1)[-1]
    if mod == "nn":
        return "Neural-network layers"
    if mod == "contrib_op":
        return "Contrib operators"
    if mod == "attention":
        return "Attention and long-context"
    if mod == "rnn_op":
        return "Fused RNN"
    if mod == "torch_bridge":
        return "Interop (torch bridge)"
    if mod == "operator":
        return "Custom operators"
    # the tensor module, partitioned the way the reference's op docs are
    if name.endswith("_update"):
        return "Fused optimizer updates"
    if name.startswith(("_random_", "_sample_")):
        return "Random samplers"
    if name in ("_zeros", "_ones", "_full", "_arange", "one_hot",
                "ones_like", "zeros_like"):
        return "Array creation"
    if name.startswith("broadcast_"):
        return "Broadcast operations"
    if name.startswith("elemwise_") or (
            name.startswith("_") and not name.startswith("_contrib")):
        return "Element-wise and scalar arithmetic"
    return "Tensor manipulation and math"


_CATEGORY_ORDER = [
    "Neural-network layers",
    "Attention and long-context",
    "Fused RNN",
    "Tensor manipulation and math",
    "Broadcast operations",
    "Element-wise and scalar arithmetic",
    "Array creation",
    "Random samplers",
    "Fused optimizer updates",
    "Contrib operators",
    "Custom operators",
    "Interop (torch bridge)",
]


def gen_ops_md():
    from mxnet_tpu.ops.registry import OP_REGISTRY, _ALIAS
    from mxnet_tpu.ops.opdocs import describe, op_io_summary

    rev = {}
    for a, t in _ALIAS.items():
        rev.setdefault(t, []).append(a)

    cats = {}
    for name, op in OP_REGISTRY.items():
        cats.setdefault(_op_category(name, op), []).append(name)

    buf = io.StringIO()
    w = buf.write
    w("# Operator reference\n\n")
    w("GENERATED by `tools/gen_docs.py` from the live op registry — do "
      "not edit.\nEvery op is callable as `mx.nd.<op>` (imperative) and "
      "`mx.sym.<op>`\n(symbolic); leading-underscore ops are also exposed "
      "without the\nunderscore where unambiguous.  Optional tensor inputs "
      "left out at call\nsites auto-create Variables (symbolic) — the "
      "reference's parameter-input\nrule.\n\n")
    w("%d operators, %d aliases.\n\n" % (len(OP_REGISTRY), len(_ALIAS)))
    w("Contents: " + " · ".join(
        "[%s](#%s)" % (c, c.lower().replace(" ", "-").replace("(", "")
                       .replace(")", ""))
        for c in _CATEGORY_ORDER if c in cats) + "\n")

    for cat in _CATEGORY_ORDER:
        if cat not in cats:
            continue
        w("\n## %s\n" % cat)
        for name in sorted(cats[cat]):
            op = OP_REGISTRY[name]
            w("\n### `%s`\n\n" % name)
            w(describe(op).rstrip() + "\n\n")
            osum = op_io_summary(op)
            if osum["inputs"] == "<variable>":
                w("**Inputs**: variable arity (`num_args` tensors).\n")
            elif osum["inputs"] == "<none>":
                w("**Inputs**: none (creation op).\n")
            else:
                w("**Inputs**: %s.%s\n" % (
                    ", ".join("`%s`" % a for a in osum["inputs"]),
                    " (%s)" % osum["inputs_note"]
                    if osum["inputs_note"] else ""))
            if osum["aux"]:
                w("**Auxiliary states**: %s.\n" % ", ".join(
                    "`%s`" % a for a in osum["aux"]))
            if osum["outputs"] == "<attr-dependent>":
                w("**Outputs**: attr-dependent count.\n")
            elif osum["outputs"] is not None:
                w("**Outputs**: %s.\n" % (
                    ", ".join("`%s`" % o for o in osum["outputs"])
                    if isinstance(osum["outputs"], list)
                    else "%d arrays" % osum["outputs"]))
            if op.params:
                w("\n| attribute | type | default |\n|---|---|---|\n")
                for pname in sorted(op.params):
                    spec = op.params[pname]
                    if spec.required:
                        dflt = "*required*"
                    else:
                        dflt = "`%r`" % (spec.default,)
                    typ = spec.type
                    if spec.enum:
                        typ = "{%s}" % ", ".join(spec.enum)
                    w("| `%s` | %s | %s |\n" % (pname, typ, dflt))
            if rev.get(name):
                w("\n*Aliases*: %s.\n" % ", ".join(
                    "`%s`" % a for a in sorted(rev[name])))
    return buf.getvalue()


# ---------------------------------------------------------------------
# python.md — library surfaces reflected from the live classes
# ---------------------------------------------------------------------

def _first_para(doc):
    doc = inspect.cleandoc(doc or "")
    return doc.split("\n\n", 1)[0].replace("\n", " ").strip()


def _sig(obj):
    try:
        s = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    return s if len(s) <= 88 else "(...)"


def _doc_class(w, cls, skip=()):
    w("\n### `%s`\n\n" % cls.__name__)
    p = _first_para(cls.__doc__)
    if p:
        w(p + "\n")
    meths = []
    for name, m in sorted(vars(cls).items()):
        if name.startswith("_") or name in skip:
            continue
        if callable(m) or isinstance(m, (staticmethod, classmethod)):
            fn = m.__func__ if isinstance(m, (staticmethod, classmethod)) \
                else m
            meths.append((name, fn))
        elif isinstance(m, property):
            meths.append((name, m.fget))
    if meths:
        w("\n")
        for name, fn in meths:
            brief = _first_para(getattr(fn, "__doc__", ""))
            w("- `%s%s`%s\n" % (name, _sig(fn),
                                " — " + brief if brief else ""))


def _doc_functions(w, mod, names):
    for name in names:
        fn = getattr(mod, name)
        brief = _first_para(fn.__doc__)
        w("- `%s%s`%s\n" % (name, _sig(fn),
                            " — " + brief if brief else ""))


def gen_python_md():
    import mxnet_tpu as mx
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu.parallel.pipeline import PipelinedTrainer
    from mxnet_tpu.parallel import checkpoint as sharded_ckpt

    buf = io.StringIO()
    w = buf.write
    w("# Python API reference (library surfaces)\n\n")
    w("GENERATED by `tools/gen_docs.py` from the live classes — do not "
      "edit.\nPer-operator docs live in [ops.md](ops.md) and on the "
      "functions\nthemselves (`help(mx.nd.Convolution)`).\n")

    w("\n## NDArray (`mx.nd`)\n")
    _doc_class(w, mx.nd.NDArray)
    w("\nModule-level helpers:\n")
    _doc_functions(w, mx.nd, ["array", "zeros", "ones", "full", "arange",
                              "concatenate", "load", "save", "waitall"])

    w("\n## Symbol (`mx.sym`)\n")
    _doc_class(w, mx.sym.Symbol)
    w("\nModule-level helpers:\n")
    _doc_functions(w, mx.sym, ["Variable", "Group", "load", "load_json"])

    w("\n## Executor\n")
    _doc_class(w, mx.executor.Executor)

    w("\n## Module (`mx.mod`)\n")
    for cls in (mx.mod.Module, mx.mod.BucketingModule,
                mx.mod.SequentialModule, mx.mod.PythonModule):
        _doc_class(w, cls)

    w("\n## Legacy estimator (`mx.model`)\n")
    _doc_class(w, mx.model.FeedForward)
    _doc_functions(w, mx.model, ["save_checkpoint", "load_checkpoint"])

    w("\n## Optimizers (`mx.optimizer`)\n")
    w(_first_para(mx.optimizer.__doc__) + "\n\n")
    opts = sorted(
        n for n, c in vars(mx.optimizer).items()
        if inspect.isclass(c) and issubclass(c, mx.optimizer.Optimizer)
        and c is not mx.optimizer.Optimizer)
    w("Classes: %s.\n" % ", ".join("`%s`" % n for n in opts))
    _doc_class(w, mx.optimizer.Optimizer)
    _doc_functions(w, mx.optimizer, ["create", "get_updater"])

    w("\n## LR schedules (`mx.lr_scheduler`)\n")
    for n in ("FactorScheduler", "MultiFactorScheduler", "PolyScheduler"):
        _doc_class(w, getattr(mx.lr_scheduler, n))

    w("\n## Initializers (`mx.initializer`)\n")
    inits = sorted(
        n for n, c in vars(mx.initializer).items()
        if inspect.isclass(c)
        and issubclass(c, mx.initializer.Initializer)
        and c is not mx.initializer.Initializer)
    w("Classes: %s.\n" % ", ".join("`%s`" % n for n in inits))

    w("\n## Metrics (`mx.metric`)\n")
    mets = sorted(
        n for n, c in vars(mx.metric).items()
        if inspect.isclass(c) and issubclass(c, mx.metric.EvalMetric)
        and c is not mx.metric.EvalMetric)
    w("Classes: %s.\n" % ", ".join("`%s`" % n for n in mets))
    _doc_class(w, mx.metric.EvalMetric)

    w("\n## Data IO (`mx.io`)\n")
    for n in ("NDArrayIter", "ImageRecordIter", "CSVIter", "MNISTIter",
              "PrefetchingIter", "ResizeIter"):
        if hasattr(mx.io, n):
            _doc_class(w, getattr(mx.io, n))

    w("\n## KVStore (`mx.kv`)\n")
    _doc_class(w, mx.kv.KVStore)
    _doc_functions(w, mx.kv, ["create"])

    w("\n## Errors (`mxnet_tpu.base`)\n")
    from mxnet_tpu import base as _base
    w("The typed hierarchy the kvstore retry ladder and the serving "
      "tier\ndispatch on (enforced by the `typed-errors` graftcheck "
      "rule —\nsee docs/how_to/static_analysis.md):\n\n")
    for n in ("MXNetError", "ServerDeadError", "ShardFailedError",
              "StaleEpochError", "TruncatedMessageError",
              "CorruptMessageError", "CheckpointCorruptError"):
        cls = getattr(_base, n)
        bases = ", ".join(b.__name__ for b in cls.__bases__)
        w("- `%s(%s)` — %s\n" % (n, bases, _first_para(cls.__doc__)))

    w("\n## Callbacks (`mx.callback`)\n")
    _doc_functions(w, mx.callback, ["Speedometer", "ProgressBar",
                                    "do_checkpoint", "module_checkpoint",
                                    "log_train_metric"])

    w("\n## RNN cells (`mx.rnn`)\n")
    cells = sorted(
        n for n, c in vars(mx.rnn).items()
        if inspect.isclass(c) and n.endswith("Cell"))
    w("Classes: %s.\n" % ", ".join("`%s`" % n for n in cells))
    _doc_class(w, mx.rnn.LSTMCell)

    w("\n## Autograd (`mx.contrib.autograd`)\n")
    from mxnet_tpu.contrib import autograd as ag
    _doc_functions(w, ag, ["set_is_training", "train_section",
                           "compute_gradient", "grad_and_loss", "grad"])

    w("\n## Mesh-scale training (`mxnet_tpu.parallel`)\n")
    _doc_class(w, ShardedTrainer)
    _doc_class(w, PipelinedTrainer)
    w("\nSharded checkpointing:\n")
    _doc_functions(w, sharded_ckpt, ["save_sharded", "restore_sharded",
                                     "latest_step", "verify_checkpoint"])

    w("\n## Streaming data plane (`mxnet_tpu.stream`)\n")
    _doc_class(w, mx.stream.StreamDataIter)
    _doc_functions(w, mx.stream, ["write_ndarray_records"])

    w("\n## Deployment daemon (`mxnet_tpu.deployd`)\n")
    _doc_class(w, mx.deployd.DeployDaemon)

    w("\n## Durable cluster snapshots (`mxnet_tpu.snapshot`)\n")
    from mxnet_tpu import snapshot as _snap
    w(_first_para(_snap.__doc__) + "\n")
    _doc_class(w, _snap.SnapshotPlan)
    _doc_functions(w, _snap, ["save", "restore_latest", "restore_path",
                              "list_snapshots", "verify",
                              "quarantine_snapshot", "gc"])

    w("\n## Durable writes (`mxnet_tpu.durable`)\n")
    from mxnet_tpu import durable as _dur
    w(_first_para(_dur.__doc__) + "\n")
    _doc_functions(w, _dur, ["atomic_write_bytes", "atomic_write_json",
                             "file_sha256", "checksummed_json_bytes",
                             "verify_checksummed_json",
                             "load_checksummed_json", "quarantine"])

    w("\n## Random (`mx.random`)\n")
    _doc_functions(w, mx.random, ["seed"])

    w("\n## Kernels and their parity harness (`mxnet_tpu.ops.fused`)\n")
    from mxnet_tpu.ops import fused as _fused
    from mxnet_tpu.ops import platform as _opplat
    from mxnet_tpu.ops.fused import parity as _fpar
    w(_first_para(_fused.__doc__) + "\n\nThe platform test "
      "(`mxnet_tpu.ops.platform`):\n")
    _doc_functions(w, _opplat, ["pallas_mode"])
    w("\nParity harness (`mxnet_tpu.ops.fused.parity`):\n")
    _doc_functions(w, _fpar, ["register_parity", "run_parity",
                              "parity_registrations"])
    w("\nSee [the kernels how-to](../how_to/kernels.md) for the rule a "
      "hot path\nstates in its own body, the parity classes and the "
      "compile-only guards.\n")

    w("\n## Observability (`mxnet_tpu.observability`)\n")
    obs = mx.observability
    w(_first_para(obs.__doc__) + "\n\nMetrics:\n")
    _doc_functions(w, obs, ["counter", "gauge", "histogram",
                            "dump_metrics", "reset_metrics",
                            "metrics_enabled"])
    _doc_class(w, obs.Registry)
    w("\nTracing:\n")
    _doc_functions(w, obs, ["enable_tracing", "disable_tracing",
                            "tracing_enabled", "capture_context", "spans",
                            "clear_spans", "capture_wire_context"])
    _doc_class(w, obs.span)
    _doc_class(w, obs.attach_context)
    _doc_class(w, obs.attach_wire_context)
    w("\nExporters:\n")
    _doc_functions(w, obs, ["render_prometheus", "start_metrics_server",
                            "export_chrome_trace", "merge_chrome_traces"])
    w("\nFederation:\n")
    _doc_functions(w, obs, ["federate"])
    _doc_class(w, obs.FederatedCollector)
    w("\nFlight recorder:\n")
    _doc_functions(w, obs, ["record_failure", "flight_enabled"])
    w("\nStep-time attribution:\n")
    _doc_functions(w, obs, ["attributor", "sample_memory",
                            "attribution_table", "format_attribution"])
    _doc_class(w, obs.StepAttribution)
    w("\nMemory ledger:\n")
    _doc_functions(w, obs, ["memory_tag", "memory_tag_tree",
                            "memory_untag", "memory_sample",
                            "top_buffers", "memory_report",
                            "format_memory_report",
                            "memory_reconciles"])
    w("\nWatchdog:\n")
    _doc_functions(w, obs, ["default_rules"])
    _doc_class(w, obs.Watchdog)
    _doc_class(w, obs.Rule)
    _doc_class(w, obs.Alert)
    w("\nEfficiency accounting:\n")
    _doc_functions(w, obs, ["peak_flops", "record_compile",
                            "record_variant_compile",
                            "record_step_rate", "model_flops_per_step",
                            "ledger", "efficiency_table",
                            "format_efficiency", "goodput_table",
                            "format_goodput", "goodput_reconciles",
                            "capture_profile"])
    _doc_class(w, obs.GoodputLedger)

    w("\n## Serving (`mxnet_tpu.serving`)\n")
    srv = mx.serving
    w(_first_para(srv.__doc__) + "\n\nScheduling and registry:\n")
    _doc_class(w, srv.Scheduler)
    _doc_class(w, srv.ModelRegistry)
    _doc_class(w, srv.InferenceRequest)
    w("\nBackends:\n")
    _doc_class(w, srv.PredictorBackend)
    _doc_class(w, srv.ExportedBackend)
    _doc_functions(w, srv, ["as_backend", "default_buckets"])
    w("\nAdmission control:\n")
    _doc_class(w, srv.AdmissionController)
    _doc_functions(w, srv, ["default_deadline_ms", "deadline_from_ms"])
    w("\nReplication and routing:\n")
    _doc_class(w, srv.ReplicaGroup)
    _doc_class(w, srv.ServingRouter)
    w("\nHTTP front-end:\n")
    _doc_functions(w, srv, ["start_frontend"])
    _doc_class(w, srv.ServingFrontend)
    w("\nAutoregressive generation "
      "(`docs/how_to/llm_serving.md`):\n")
    _doc_class(w, srv.GenerationScheduler)
    _doc_class(w, srv.GenerationRequest)
    _doc_class(w, srv.LMBackend)
    from mxnet_tpu.ops import kv_cache as _kvc

    _doc_class(w, _kvc.PagedKVCache)
    _doc_functions(w, srv.generation,
                   ["default_prefill_buckets", "default_decode_buckets",
                    "default_max_new_tokens"])

    w("\n## Profiler / RTC / misc\n")
    w("- `mx.profiler` — façade over `mxnet_tpu.observability`: "
      "chrome-trace + xplane profiling "
      "(`profiler_set_config`, `profiler_set_state`, `dump_profile`, "
      "`scope`)\n")
    w("- `mx.rtc` — runtime-compiled JAX/Pallas source modules\n")
    w("- `mx.test_utils` — numeric-gradient checks, data download, "
      "`assert_almost_equal`\n")
    return buf.getvalue()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="diff against checked-in files; exit 1 on drift")
    args = ap.parse_args()
    outputs = {OUT_OPS: gen_ops_md(), OUT_PY: gen_python_md()}
    if args.check:
        stale = []
        for path, text in outputs.items():
            on_disk = (open(path, encoding="utf-8").read()
                       if os.path.exists(path) else "")
            if on_disk != text:
                stale.append(path)
        if stale:
            print("STALE (rerun tools/gen_docs.py): %s" % stale)
            return 1
        print("docs in sync (%d files)" % len(outputs))
        return 0
    os.makedirs(os.path.dirname(OUT_OPS), exist_ok=True)
    for path, text in outputs.items():
        # the content is non-ASCII; don't depend on the locale encoding
        with open(path, "w", encoding="utf-8") as f:
            f.write(text)
        print("wrote %s (%d lines)" % (path, text.count("\n")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
