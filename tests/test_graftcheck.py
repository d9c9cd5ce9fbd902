"""graftcheck static-analysis suite: per-rule fixture pairs (bad code
flagged at the right line / good code clean / pragma suppresses), the
JSON reporter schema, the baseline lifecycle, CLI exit codes, and the
tier-1 gate: the real repo must come back with zero unbaselined
findings.

Fixtures are synthetic mini-repos in ``tmp_path`` — ``Project`` takes a
root, so each test builds exactly the tree shape its rule reads
(``docs/env_vars.md`` for the env registry, ``mxnet_tpu/chaos.py`` for
``SITES``, hot-path file names for the metrics rule).
"""

import io
import json
import os
import textwrap
import time

from tools.graftcheck import ALL_RULES, Project, run_rules
from tools.graftcheck.__main__ import main as graftcheck_main
from tools.graftcheck.core import (apply_baseline, load_baseline,
                                   report_json, save_baseline)

# -- mini-repo helpers ------------------------------------------------------

CHAOS_PY = """\
SITES = frozenset({
    "engine.op",
    "kvstore.send",
})


def visit(site, payload=None, **meta):
    return payload
"""

ENV_DOC = """\
# Environment variables

| Variable | Default | Meaning |
|---|---|---|
| `MXNET_TPU_GOOD` | unset | a documented tunable |
"""

# keeps the base doc row alive so the dead-row check stays quiet in
# fixtures that are about something else
BASE_CFG = """\
import os

GOOD = os.environ.get("MXNET_TPU_GOOD", "0")
"""


def _mini(tmp_path, files):
    base = {"mxnet_tpu/chaos.py": CHAOS_PY, "docs/env_vars.md": ENV_DOC,
            "mxnet_tpu/_basecfg.py": BASE_CFG}
    base.update(files)
    for rel, text in base.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return str(tmp_path)


def _run(root, rule):
    project = Project(root)
    return run_rules(project, {rule: ALL_RULES[rule]})


# -- env-var-registry -------------------------------------------------------

def test_envvar_undocumented_read_flagged_at_line(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/cfg.py": """\
        import os

        def knob():
            return os.environ.get("MXNET_TPU_UNDOCUMENTED", "0")
        """})
    findings = _run(root, "env-var-registry")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("mxnet_tpu", "cfg.py"), 4)]
    assert "MXNET_TPU_UNDOCUMENTED" in findings[0].message


def test_envvar_documented_read_clean(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/cfg.py": """\
        import os

        def knob():
            return os.environ.get("MXNET_TPU_GOOD", "0")
        """})
    assert _run(root, "env-var-registry") == []


def test_envvar_dead_doc_row_flagged(tmp_path):
    # removing the last read of a documented var (or renaming it in
    # code) must fail the suite at the now-dead doc row
    root = _mini(tmp_path, {"docs/env_vars.md": ENV_DOC + (
        "| `MXNET_TPU_DEAD` | unset | nothing reads this anymore |\n")})
    findings = _run(root, "env-var-registry")
    assert len(findings) == 1
    assert findings[0].path == os.path.join("docs", "env_vars.md")
    assert findings[0].line == 6          # the MXNET_TPU_DEAD table row
    assert "MXNET_TPU_DEAD" in findings[0].message
    assert "dead row" in findings[0].message


def test_envvar_pragma_suppresses(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/cfg.py": """\
        import os

        def knob():
            # launcher-internal, deliberately undocumented
            # graftcheck: disable-next=env-var-registry
            return os.environ.get("MXNET_TPU_UNDOCUMENTED")
        """})
    assert _run(root, "env-var-registry") == []


def test_envvar_test_files_exempt_but_count_as_usage(tmp_path):
    root = _mini(tmp_path, {"tests/test_x.py": """\
        import os

        def test_knob(monkeypatch):
            monkeypatch.setenv("MXNET_TPU_GOOD", "1")
            assert os.environ.get("MXNET_TPU_NOT_A_RUNTIME_READ") is None
        """})
    # reads in tests/ are not flagged, and the mention of the
    # documented name keeps its row alive
    assert _run(root, "env-var-registry") == []


# -- chaos-site -------------------------------------------------------------

def test_chaos_unknown_site_flagged_at_line(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/net.py": """\
        from . import chaos as _chaos

        def send(payload):
            return _chaos.visit("kvstore.sendd", payload)
        """})
    findings = _run(root, "chaos-site")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("mxnet_tpu", "net.py"), 4)]
    assert "kvstore.sendd" in findings[0].message


def test_chaos_known_site_clean(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/net.py": """\
        from . import chaos as _chaos

        def send(payload):
            return _chaos.visit("kvstore.send", payload)
        """})
    assert _run(root, "chaos-site") == []


def test_chaos_renamed_site_breaks_call_sites(tmp_path):
    # the acceptance scenario: rename a site in chaos.SITES and every
    # caller still using the old name goes red
    root = _mini(tmp_path, {
        "mxnet_tpu/chaos.py": CHAOS_PY.replace(
            '"kvstore.send"', '"kvstore.tx"'),
        "mxnet_tpu/net.py": """\
        from . import chaos as _chaos

        def send(payload):
            return _chaos.visit("kvstore.send", payload)
        """})
    findings = _run(root, "chaos-site")
    assert len(findings) == 1
    assert findings[0].path == os.path.join("mxnet_tpu", "net.py")


def test_chaos_spec_string_in_test_flagged(tmp_path):
    root = _mini(tmp_path, {"tests/test_chaos_use.py": """\
        def test_inject(monkeypatch):
            monkeypatch.setenv(
                "MXNET_TPU_CHAOS", "kvstore.sned:drop@0.5")
        """})
    findings = _run(root, "chaos-site")
    assert len(findings) == 1
    assert "kvstore.sned" in findings[0].message


def test_chaos_docs_code_block_flagged(tmp_path):
    root = _mini(tmp_path, {"docs/how_to/chaos.md": """\
        # Chaos

        ```python
        chaos.visit("engine.opp")
        ```
        """})
    findings = _run(root, "chaos-site")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("docs", "how_to", "chaos.md"), 4)]


# -- metrics-hot-path -------------------------------------------------------

def test_metrics_lookup_in_dispatch_loop_flagged(tmp_path):
    # the acceptance scenario: move a label resolution into the
    # scheduler dispatch loop
    root = _mini(tmp_path, {"mxnet_tpu/serving/scheduler.py": """\
        class Scheduler:
            def _dispatch(self, lane, batch):
                self._m_batch.labels(lane.name).observe(len(batch))
        """})
    findings = _run(root, "metrics-hot-path")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("mxnet_tpu", "serving", "scheduler.py"), 3)]
    assert ".labels(" in findings[0].message


def test_metrics_preresolved_handle_clean(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/serving/scheduler.py": """\
        class Scheduler:
            def _dispatch(self, lane, batch):
                lane.m_batch.observe(len(batch))
        """})
    assert _run(root, "metrics-hot-path") == []


def test_metrics_registration_in_engine_push_flagged(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/engine.py": """\
        from .observability.metrics import counter

        def push(fn, ctx):
            counter("engine_push_total", "pushes").inc()
        """})
    findings = _run(root, "metrics-hot-path")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("mxnet_tpu", "engine.py"), 4)]


def test_metrics_invalid_name_and_conflict_flagged(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/obs.py": """\
        from .observability.metrics import counter, gauge

        M_BAD = counter("engine-push-total", "invalid char")
        M_A = counter("dup_total", "first", ["op"])
        M_B = gauge("dup_total", "second", ["op"])
        """})
    findings = _run(root, "metrics-hot-path")
    msgs = [(f.line, f.message) for f in findings]
    assert any(line == 3 and "not Prometheus-valid" in m
               for line, m in msgs)
    assert any(line == 5 and "re-registered" in m for line, m in msgs)
    assert len(findings) == 2


def test_metrics_pragma_suppresses(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/serving/scheduler.py": """\
        class Scheduler:
            def _dispatch(self, lane, batch):
                # cold slow-path branch, hit once per model load
                self._m.labels(lane.name).inc()  # graftcheck: disable=metrics-hot-path
        """})
    assert _run(root, "metrics-hot-path") == []


# -- typed-errors -----------------------------------------------------------

def test_typed_errors_bare_runtimeerror_flagged(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/serving/frontend.py": """\
        def admit(req):
            if req is None:
                raise RuntimeError("bad request")
        """})
    findings = _run(root, "typed-errors")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("mxnet_tpu", "serving", "frontend.py"), 3)]
    assert "RuntimeError" in findings[0].message


def test_typed_errors_valueerror_in_wire_fn_flagged(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/kvstore_wire.py": """\
        def _recv_msg(sock):
            raise ValueError("truncated")
        """})
    findings = _run(root, "typed-errors")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("mxnet_tpu", "kvstore_wire.py"), 2)]


def test_typed_errors_good_cases_clean(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/kvstore_wire.py": """\
        from .base import TruncatedMessageError

        def _recv_msg(sock):
            raise TruncatedMessageError("peer died mid-frame")

        def __init__(self, addrs):
            # constructor validation is NOT wire-path: ValueError ok
            if not addrs:
                raise ValueError("need at least one address")
        """})
    assert _run(root, "typed-errors") == []


def test_typed_errors_out_of_scope_module_clean(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/visualization.py": """\
        def plot(g):
            raise RuntimeError("no display")
        """})
    assert _run(root, "typed-errors") == []


def test_typed_errors_pragma_suppresses(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/serving/frontend.py": """\
        def admit(req):
            # stdlib http.server contract requires a bare error here
            raise RuntimeError("x")  # graftcheck: disable=typed-errors
        """})
    assert _run(root, "typed-errors") == []


# -- lock-discipline --------------------------------------------------------

THREADED_BAD = """\
import threading


class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.state = 0

    def start(self):
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        self.state = 1

    def poke(self):
        self.state = 2
"""


def test_lock_discipline_unguarded_writes_flagged(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/w.py": THREADED_BAD})
    findings = _run(root, "lock-discipline")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("mxnet_tpu", "w.py"), 13),
        (os.path.join("mxnet_tpu", "w.py"), 16)]
    assert all("state" in f.message for f in findings)


def test_lock_discipline_guarded_writes_clean(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/w.py": """\
        import threading


        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.state = 0

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                with self._lock:
                    self.state = 1

            def poke(self):
                with self._lock:
                    self.state = 2
        """})
    assert _run(root, "lock-discipline") == []


def test_lock_discipline_locked_suffix_exempt(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/w.py": """\
        import threading


        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.state = 0

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                with self._lock:
                    self._advance_locked()

            def _advance_locked(self):
                # caller holds self._lock (the *_locked convention)
                self.state = 1
        """})
    assert _run(root, "lock-discipline") == []


def test_lock_discipline_non_threaded_class_clean(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/w.py": """\
        class Plain:
            def __init__(self):
                self.state = 0

            def poke(self):
                self.state = 2
        """})
    assert _run(root, "lock-discipline") == []


def test_lock_discipline_pragma_suppresses(tmp_path):
    bad = THREADED_BAD.replace(
        "        self.state = 1",
        "        self.state = 1  # graftcheck: disable=lock-discipline"
    ).replace(
        "        self.state = 2",
        "        self.state = 2  # graftcheck: disable=lock-discipline")
    root = _mini(tmp_path, {"mxnet_tpu/w.py": bad})
    assert _run(root, "lock-discipline") == []


# -- jit-purity -------------------------------------------------------------

def test_jit_purity_time_call_flagged(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/step.py": """\
        import time

        import jax


        def step(x):
            t0 = time.time()
            return x + t0


        step_fn = jax.jit(step)
        """})
    findings = _run(root, "jit-purity")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("mxnet_tpu", "step.py"), 7)]
    assert "time.time" in findings[0].message


def test_jit_purity_pure_fn_clean(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/step.py": """\
        import jax


        def step(x):
            return x * 2


        step_fn = jax.jit(step)
        """})
    assert _run(root, "jit-purity") == []


def test_jit_purity_scan_lambda_print_flagged(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/scan.py": """\
        from jax import lax


        def run(xs):
            return lax.scan(lambda c, x: (c, print(x)), 0, xs)
        """})
    findings = _run(root, "jit-purity")
    assert len(findings) == 1
    assert findings[0].line == 5
    assert "print()" in findings[0].message


def test_jit_purity_impure_outside_traced_fn_clean(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/step.py": """\
        import time

        import jax


        def step(x):
            return x * 2


        t0 = time.time()
        step_fn = jax.jit(step)
        """})
    assert _run(root, "jit-purity") == []


def test_jit_purity_pragma_suppresses(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/step.py": """\
        import os

        import jax


        def step(x):
            # debug-only trace knob, read once at trace time on purpose
            flag = os.environ.get("DEBUG")  # graftcheck: disable=jit-purity
            return x


        step_fn = jax.jit(step)
        """})
    assert _run(root, "jit-purity") == []


# -- golden-metrics ---------------------------------------------------------

def test_golden_unregistered_family_flagged(tmp_path):
    root = _mini(tmp_path, {"tests/golden/expo.txt": """\
        # TYPE engine_push_total counter
        engine_push_total 3
        """})
    findings = _run(root, "golden-metrics")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("tests", "golden", "expo.txt"), 1)]
    assert "engine_push_total" in findings[0].message


def test_golden_registered_family_clean(tmp_path):
    root = _mini(tmp_path, {
        "mxnet_tpu/obs.py": """\
        from .observability.metrics import counter

        M_PUSH = counter("engine_push_total", "pushes")
        """,
        "tests/golden/expo.txt": """\
        # TYPE engine_push_total counter
        engine_push_total 3
        """})
    assert _run(root, "golden-metrics") == []


def test_golden_demo_prefix_exempt_and_stray_series_flagged(tmp_path):
    root = _mini(tmp_path, {"tests/golden/expo.txt": """\
        # TYPE demo_requests_total counter
        demo_requests_total{code="200"} 7
        stray_series_total 1
        """})
    findings = _run(root, "golden-metrics")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("tests", "golden", "expo.txt"), 3)]
    assert "stray_series_total" in findings[0].message


# -- pragma forms -----------------------------------------------------------

def test_pragma_disable_next_and_file(tmp_path):
    root = _mini(tmp_path, {
        "mxnet_tpu/a.py": """\
        import os

        # graftcheck: disable-next=env-var-registry
        V = os.environ.get("MXNET_TPU_NOT_DOCUMENTED")
        """,
        "mxnet_tpu/b.py": """\
        # graftcheck: disable-file=env-var-registry
        import os

        V = os.environ.get("MXNET_TPU_ALSO_NOT_DOCUMENTED")
        """})
    assert _run(root, "env-var-registry") == []


def test_pragma_other_rule_does_not_suppress(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/a.py": """\
        import os

        V = os.environ.get("MXNET_TPU_X")  # graftcheck: disable=chaos-site
        """})
    assert len(_run(root, "env-var-registry")) == 1


# -- parse errors surface, never hide --------------------------------------

def test_syntax_error_yields_parse_finding(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/broken.py": "def f(:\n"})
    findings = _run(root, "env-var-registry")
    assert [(f.rule, f.path) for f in findings] == [
        ("parse", os.path.join("mxnet_tpu", "broken.py"))]


# -- baseline lifecycle -----------------------------------------------------

def test_baseline_grandfathers_and_reports_stale(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/cfg.py": """\
        import os

        V = os.environ.get("MXNET_TPU_LEGACY")
        """})
    findings = _run(root, "env-var-registry")
    assert len(findings) == 1

    baseline_path = str(tmp_path / "baseline.txt")
    save_baseline(baseline_path, findings)
    baseline = load_baseline(baseline_path)
    fresh, grandfathered, stale = apply_baseline(findings, baseline)
    assert fresh == [] and len(grandfathered) == 1 and stale == []

    # line moves do not resurrect a grandfathered finding
    moved = [type(f)(f.path, f.line + 40, f.rule, f.message)
             for f in findings]
    fresh, grandfathered, _ = apply_baseline(moved, baseline)
    assert fresh == [] and len(grandfathered) == 1

    # a fixed finding leaves a stale entry the report calls out
    fresh, grandfathered, stale = apply_baseline([], baseline)
    assert stale and stale[0][0] == "env-var-registry"


# -- JSON reporter ----------------------------------------------------------

def test_json_report_schema(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/cfg.py": """\
        import os

        V = os.environ.get("MXNET_TPU_MYSTERY")
        """})
    findings = _run(root, "env-var-registry")
    buf = io.StringIO()
    report_json(findings, [], [], {"env-var-registry": None}, buf)
    doc = json.loads(buf.getvalue())
    assert doc["version"] == 1
    assert doc["rules"] == ["env-var-registry"]
    assert doc["counts"] == {"total": 1, "unbaselined": 1, "baselined": 0}
    (f,) = doc["findings"]
    assert set(f) == {"path", "line", "rule", "message", "baselined"}
    assert f["rule"] == "env-var-registry" and f["baselined"] is False


# -- CLI --------------------------------------------------------------------

def test_cli_exit_codes_and_update_baseline(tmp_path, capsys):
    root = _mini(tmp_path, {"mxnet_tpu/cfg.py": """\
        import os

        V = os.environ.get("MXNET_TPU_MYSTERY")
        """})
    baseline = str(tmp_path / "baseline.txt")

    assert graftcheck_main(
        ["--root", root, "--baseline", baseline]) == 1
    out = capsys.readouterr().out
    assert "mxnet_tpu%scfg.py:3" % os.sep in out

    assert graftcheck_main(
        ["--root", root, "--baseline", baseline,
         "--update-baseline"]) == 0
    capsys.readouterr()
    assert graftcheck_main(
        ["--root", root, "--baseline", baseline]) == 0
    assert "1 baselined finding(s) suppressed" in capsys.readouterr().out

    assert graftcheck_main(["--rule", "no-such-rule"]) == 2


def test_cli_json_output(tmp_path, capsys):
    root = _mini(tmp_path, {"mxnet_tpu/cfg.py": "X = 1\n"})
    rc = graftcheck_main(
        ["--root", root, "--baseline", str(tmp_path / "b.txt"),
         "--rule", "chaos-site", "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["rules"] == ["chaos-site"] and doc["findings"] == []


# -- atomic-write -----------------------------------------------------------

def test_atomic_write_durable_module_flagged(tmp_path):
    """ANY write-mode open in a durable-state module is flagged; reads
    and the atomic helpers' own tmp writes are exempt."""
    root = _mini(tmp_path, {"mxnet_tpu/snapshot.py": """\
        def save(path, data):
            with open(path, "wb") as f:
                f.write(data)

        def load(path):
            with open(path, "rb") as f:
                return f.read()

        def atomic_write_bytes(path, data):
            with open(path + ".tmp", "wb") as f:
                f.write(data)
        """})
    findings = _run(root, "atomic-write")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("mxnet_tpu", "snapshot.py"), 2)]
    assert "durable-state module" in findings[0].message


def test_atomic_write_token_path_flagged_elsewhere(tmp_path):
    """Outside the durable modules, only writes whose path expression
    names durable-state tokens are flagged."""
    root = _mini(tmp_path, {"mxnet_tpu/other.py": """\
        def dump(d, log_path):
            with open(d + "/manifest.json", "w") as f:
                f.write("{}")
            with open(log_path, "w") as f:
                f.write("scratch log, not durable state")
        """})
    findings = _run(root, "atomic-write")
    assert [(f.path, f.line) for f in findings] == [
        (os.path.join("mxnet_tpu", "other.py"), 2)]


def test_atomic_write_pragma_suppresses(tmp_path):
    root = _mini(tmp_path, {"mxnet_tpu/other.py": """\
        def dump(d):
            # staged into a .tmp dir; one rename commits the bundle
            with open(d + "/manifest.json", "w") as f:  # graftcheck: disable=atomic-write
                f.write("{}")
        """})
    assert _run(root, "atomic-write") == []


# -- the tier-1 gate: this repo stays clean ---------------------------------

def test_whole_repo_zero_unbaselined(capsys):
    """The actual repo passes its own analyzer with no unbaselined
    findings, within the interactive budget the Makefile relies on."""
    t0 = time.monotonic()
    rc = graftcheck_main([])
    elapsed = time.monotonic() - t0
    out = capsys.readouterr().out
    assert rc == 0, "unbaselined graftcheck findings:\n%s" % out
    assert elapsed < 30.0, "graftcheck exceeded its 30s budget"
