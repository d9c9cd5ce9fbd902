"""What a run is made of, found by name.

``BENCHMARK.json`` at the root of the checkout lists the cells and the
metrics.  Everything that belongs to one configuration, one traffic mix,
one cell's limits or one per-layer metric is a file of its own under the
benchmark's directory, and this module finds it by the name the JSON
gives.  A later PR adds a cell by adding files and entries; nothing here
names a cell, a configuration or a metric.

    configs/<config>.json            sizes, source, reduced, assumed,
                                     family, deployment
    configs/<config>.reference.py    the configuration's plain reference
    traffic/<traffic>.json           kind and parameters of the mix
    limits/<cell>.json               the limits `correct` is decided by
    metrics/<metric>.json            reader, its parameters, what it is
    readers/<reader>.py              read(ctx, params) -> number or None
    models/<family>.py               builds the program for a family
    drivers/<kind>.py                drives one kind of traffic
    peaks.json                       device peaks by device_kind
"""

import importlib.util
import json
import re
from pathlib import Path

CODE_DIR = Path(__file__).resolve().parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(Exception):
    """The benchmark's files do not describe what was asked for."""


def check_name(name, what="name"):
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError("%s %r has a character outside letters, digits, "
                        "'_', '.', '-' (or is empty or over 64 long)"
                        % (what, name))
    return name


def check_unit(unit, metric):
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError("unit %r of metric %r has a character outside "
                        "letters, digits, '_', '/', '%%', '.', '-'"
                        % (unit, metric))
    return unit


def load_module(path, name):
    """Import one file by path (names here may hold '-' and '.')."""
    path = Path(path)
    if not path.is_file():
        raise SpecError("no file %s" % path)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Spec(object):
    """The benchmark as its files describe it.  ``root`` holds
    ``BENCHMARK.json`` (a test hands over its own as ``doc``); data files are looked for under the first of its
    ``paths`` and then beside this module (so a test's tiny benchmark
    brings only what differs)."""

    def __init__(self, root, doc=None):
        self.root = Path(root).resolve()
        if doc is None:
            path = self.root / "BENCHMARK.json"
            if not path.is_file():
                raise SpecError("no BENCHMARK.json in %s" % self.root)
            doc = json.loads(path.read_text())
        self.doc = doc
        self.data_dir = (self.root / self.doc["paths"][0]).resolve()
        self.cells = {}
        for cell in self.doc["workloads"]:
            for key in ("name", "config", "traffic"):
                check_name(cell[key], "workload " + key)
            self.cells[cell["name"]] = cell
        self.configs = {check_name(c["name"], "config"): c
                        for c in self.doc["configs"]}
        self.metrics = {}
        for group in ("end_to_end", "per_layer"):
            for m in self.doc[group]:
                check_name(m["name"], "metric")
                check_unit(m["unit"], m["name"])
                if m["source"] not in SOURCES:
                    raise SpecError("metric %r: source %r is none of %s"
                                    % (m["name"], m["source"], SOURCES))
                self.metrics[m["name"]] = dict(m, group=group)

    # -- files by name --------------------------------------------------

    def find(self, *parts):
        for base in (self.data_dir, CODE_DIR):
            path = base.joinpath(*parts)
            if path.is_file():
                return path
        raise SpecError("no file %s under %s or %s"
                        % ("/".join(parts), self.data_dir, CODE_DIR))

    def _json(self, *parts):
        return json.loads(self.find(*parts).read_text())

    def cell(self, name):
        if name not in self.cells:
            raise SpecError("unknown workload %r (BENCHMARK.json has: %s)"
                            % (name, ", ".join(sorted(self.cells))))
        return self.cells[name]

    def config(self, name):
        if name not in self.configs:
            raise SpecError("unknown config %r" % name)
        entry = self.configs[name]
        cfg = json.loads((self.root / entry["file"]).read_text())
        cfg["name"] = name
        return cfg

    def reference(self, config_name):
        """The plain reference beside the configuration's file."""
        entry = self.configs[config_name]
        path = (self.root / entry["file"]).with_suffix(".reference.py")
        return load_module(path, "reference_" + re.sub(r"\W", "_",
                                                       config_name)), path

    def traffic(self, name):
        traffic = self._json("traffic", name + ".json")
        traffic["name"] = name
        return traffic

    def limits(self, cell_name):
        return self._json("limits", cell_name + ".json")

    def peaks(self, device_kind):
        table = self._json("peaks.json")["devices"]
        if device_kind not in table:
            raise SpecError("device kind %r is not in peaks.json (known: "
                            "%s): add it with its source, there is no "
                            "default" % (device_kind, ", ".join(sorted(table))))
        return table[device_kind]

    def model(self, family):
        check_name(family, "family")
        return load_module(self.find("models", family + ".py"),
                           "bench_model_" + family)

    def driver(self, kind):
        check_name(kind, "traffic kind")
        return load_module(self.find("drivers", kind + ".py"),
                           "bench_driver_" + re.sub(r"\W", "_", kind))

    # -- metrics ----------------------------------------------------------

    def cell_metrics(self, cell_name, group):
        """The metrics of ``group`` this cell may report: those without a
        ``workloads`` key in every cell that reports what they move, the
        others where the key lists the cell."""
        reported = {m["name"] for m in self.doc["end_to_end"]
                    if "workloads" not in m or cell_name in m["workloads"]}
        out = []
        for m in self.doc[group]:
            if "workloads" in m:
                if cell_name in m["workloads"]:
                    out.append(m)
            elif group == "end_to_end" or m["moves"] in reported:
                out.append(m)
        return out

    def metric_file(self, name):
        """``metrics/<name>.json``: the metric's reader, the reader's
        parameters and what the number is.  Unit, source, layer and
        ``moves`` are BENCHMARK.json's to say, and are said only there."""
        return self._json("metrics", name + ".json")

    def reader(self, name):
        check_name(name, "reader")
        try:
            path = self.find("readers", name + ".py")
        except SpecError:
            raise SpecError("unknown reader %r: no readers/%s.py"
                            % (name, name))
        module = load_module(path, "bench_reader_" + re.sub(r"\W", "_", name))
        if not hasattr(module, "read"):
            raise SpecError("reader %r has no read(ctx, params)" % name)
        return module.read

    def read_metrics(self, cell_name, group, ctx):
        """Run each metric's reader; a reader with nothing to read
        returns None and the metric is left out."""
        out = {}
        for m in self.cell_metrics(cell_name, group):
            doc = self.metric_file(m["name"])
            value = self.reader(doc["reader"])(ctx, doc.get("params", {}))
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out
