"""Docs subsystem gates (the reference's sphinx/docstring-reflection
pipeline, SURVEY aux rows): every registered op must be documented, the
generated API reference must be in sync with the registry, and the
frontend docstrings must reflect the registry (not the old one-liners)."""

import os
import subprocess
import sys

import mxnet_tpu as mx
from mxnet_tpu.ops import opdocs
from mxnet_tpu.ops.registry import OP_REGISTRY, _ALIAS

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_every_op_documented():
    """A newly registered op cannot land without documentation: either a
    docstring on the compute fn or an opdocs entry."""
    missing, thin = [], []
    for name, op in sorted(OP_REGISTRY.items()):
        try:
            desc = opdocs.describe(op)
        except KeyError:
            missing.append(name)
            continue
        if len(desc.strip()) < 20:
            thin.append((name, desc))
    assert not missing, "undocumented ops: %s" % missing
    assert not thin, "one-word docs are not docs: %s" % thin


def test_every_alias_resolves_to_documented_op():
    for alias, target in _ALIAS.items():
        assert target in OP_REGISTRY, (alias, target)
        opdocs.describe(OP_REGISTRY[target])  # KeyError = fail


def test_frontend_docstrings_reflect_registry():
    """help(mx.nd.X) shows the real description + attribute table, both
    frontends, including alias-named functions."""
    for fn in (mx.nd.Convolution, mx.sym.Convolution):
        doc = fn.__doc__
        assert "N-D convolution" in doc
        assert "num_filter" in doc and "required" in doc
    # attr-less op, alias name, aux-state op
    assert "stops the gradient" in mx.nd.stop_gradient.__doc__.lower()
    assert "moving_mean" in mx.sym.BatchNorm.__doc__
    # multi-output op declares its outputs
    assert "Outputs" in mx.nd.adam_update.__doc__


def test_generated_docs_in_sync():
    """Regenerate the API reference and diff against the checked-in files
    (the gen_cpp_ops-style drift gate)."""
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "gen_docs.py"),
         "--check"], capture_output=True, text=True, cwd=_REPO,
        timeout=120)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])


def test_ops_md_covers_registry():
    """The checked-in ops.md mentions every op and every alias."""
    text = open(os.path.join(_REPO, "docs", "api", "ops.md"),
                encoding="utf-8").read()
    missing = [n for n in OP_REGISTRY if "### `%s`" % n not in text]
    assert not missing, missing
    missing_alias = [a for a in _ALIAS if "`%s`" % a not in text]
    assert not missing_alias, missing_alias


def test_how_tos_present():
    """The load-bearing how_tos exist and document their subject (the
    reference's docs/how_to tree: bucketing, multi-device, env vars)."""
    docs = os.path.join(_REPO, "docs")
    buck = open(os.path.join(docs, "how_to", "bucketing.md"),
                encoding="utf-8").read()
    assert "sym_gen" in buck and "BucketingModule" in buck
    multi = open(os.path.join(docs, "how_to", "multi_devices.md"),
                 encoding="utf-8").read()
    assert "context=" in multi and "dist_sync" in multi
    env = open(os.path.join(docs, "env_vars.md"),
               encoding="utf-8").read()
    assert "MXTPU_ENGINE_TYPE" in env


def test_how_to_and_architecture_trees_complete():
    """Round 5: the full how_to tree (reference docs/how_to analog) and
    the architecture notes exist with their subjects covered."""
    docs = os.path.join(_REPO, "docs")
    expect = {
        ("how_to", "new_op.md"): ["CustomOp", "ParamSpec", "pallas_call"],
        ("how_to", "recordio.md"): ["IRHeader", "im2rec", "preprocess_threads"],
        ("how_to", "torch.md"): ["mx.th.call", "TorchModule", "pure_callback"],
        ("how_to", "model_parallel_lstm.md"): ["ctx_group", "ShardedTrainer"],
        ("how_to", "visualize_graph.md"): ["plot_network", "print_summary"],
        ("how_to", "faq.md"): ["BucketingModule", "bf16"],
        ("how_to", "perf.md"): ["chip_smoke.py", "PERF.md"],
        ("how_to", "index.md"): ["new_op.md", "faq.md"],
        ("architecture", "index.md"): ["overview.md", "note_engine.md"],
        ("architecture", "overview.md"): ["Layer map", "C ABI"],
        ("architecture", "note_engine.md"): ["FnProperty", "comm lane"],
        ("architecture", "note_memory.md"): ["jax.checkpoint", "Donation"],
        ("architecture", "note_data_loading.md"): ["reorder buffer",
                                                   "InputSplit"],
        ("architecture", "program_model.md"): ["registry", "imperative"],
        ("architecture", "read_code.md"): ["registry.py", "executor.py"],
    }
    for (sub, fname), needles in expect.items():
        path = os.path.join(docs, sub, fname)
        assert os.path.exists(path), path
        text = open(path, encoding="utf-8").read()
        for needle in needles:
            assert needle in text, (path, needle)


def test_docs_relative_links_resolve():
    """Every relative markdown link under docs/ points at a file that
    exists (the docs tree cannot silently rot)."""
    import re

    docs = os.path.join(_REPO, "docs")
    bad = []
    for root, _dirs, files in os.walk(docs):
        for fname in files:
            if not fname.endswith(".md"):
                continue
            path = os.path.join(root, fname)
            text = open(path, encoding="utf-8").read()
            for m in re.finditer(r"\]\(([^)#\s]+)(#[^)]*)?\)", text):
                target = m.group(1)
                if target.startswith(("http://", "https://", "mailto:")):
                    continue
                resolved = os.path.normpath(os.path.join(root, target))
                if not os.path.exists(resolved):
                    bad.append((os.path.relpath(path, _REPO), target))
    assert not bad, bad


def test_suite_fits_its_clock():
    """Tier-1 runs ``-n 6 --dist loadfile`` inside 1470 s: a file is the
    unit of distribution and one hung child must not eat the clock.  So
    every ``subprocess.run(`` / ``_run_example(`` under tests/ passes a
    ``timeout``, no ``timeout=<number>`` anywhere under tests/ (a call's
    or a helper's default) is over 600 s, no ``_run_example`` gate's is
    over 300 s (the longest gate takes 60-80 s: ``_run_example``), and no
    file holds more than eight ``_run_example`` gates."""
    import ast
    import re

    bad = []
    for root, _dirs, files in os.walk(os.path.join(_REPO, "tests")):
        for fname in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, _REPO)
            src = open(path, encoding="utf-8").read()
            bad += ["%s: timeout=%s is over 600 s" % (rel, n)
                    for n in re.findall(r"timeout=(\d+)", src)
                    if int(n) > 600]
            tree = ast.parse(src)
            bad += ["%s:%d %s( passes no timeout"
                    % (rel, c.lineno, ast.unparse(c.func))
                    for c in ast.walk(tree) if isinstance(c, ast.Call)
                    and ast.unparse(c.func) in ("subprocess.run",
                                                "_run_example")
                    and not any(kw.arg == "timeout" for kw in c.keywords)]
            bad += ["%s:%d _run_example(timeout=%s) is over 300 s"
                    % (rel, c.lineno, ast.unparse(kw.value))
                    for c in ast.walk(tree) if isinstance(c, ast.Call)
                    and ast.unparse(c.func) == "_run_example"
                    for kw in c.keywords if kw.arg == "timeout"
                    and not (isinstance(kw.value, ast.Constant)
                             and kw.value.value <= 300)]
            gates = [f.name for f in ast.walk(tree)
                     if isinstance(f, ast.FunctionDef)
                     and f.name.startswith("test_")
                     and any(isinstance(c, ast.Call)
                             and ast.unparse(c.func) == "_run_example"
                             for c in ast.walk(f))]
            if len(gates) > 8:
                bad.append("%s holds %d _run_example gates, over eight"
                           % (rel, len(gates)))
    assert not bad, "\n".join(bad)
