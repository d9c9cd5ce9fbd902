"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration, traffic mix and
limits by name, builds the program through the configuration's family,
lets the traffic kind's driver warm it up and measure for ``--seconds``,
holds what the timed path produced against the configuration's plain
reference, and prints as the last line of stdout one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``).  With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones.

Exits non-zero, with no result line, when JAX finds no TPU or fewer
chips than the cell asks for, and for a cell, reader or name it does not
know.
"""

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.spec import Spec, SpecError  # noqa: E402


class Job(object):
    """What a driver is handed."""

    def __init__(self, spec, cell, seed, seconds, trace, devices,
                 probe=False):
        self.spec, self.cell = spec, cell
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.devices = devices
        self.config = spec.config(cell["config"])
        self.traffic = spec.traffic(cell["traffic"])
        self.limits = spec.limits(cell["name"])
        self.spans = harness.Spans()
        self.counters = harness.Counters()
        self.profiler = harness.Profiler(self.spans, spec.root)
        self.setup_s = None
        self.probe = harness.Probe(devices) if probe else None
        self.host = harness.HostWatch()

    def window_opens(self):
        """Set-up ends here: everything since the process started."""
        self.setup_s = time.perf_counter() - T_PROCESS
        self.host.open()
        if self.probe:
            self.probe.start()

    def memory_peak(self):
        """Read by the driver as its window closes."""
        self.host.close()
        if self.probe:
            self.probe.report()
        return harness.memory_peak_bytes(self.devices)

    def relative(self, path):
        return os.path.relpath(str(path), str(self.spec.root))


def dump_trace(job, directory):
    """Write the traced stretch in the neutral form (for a look by hand,
    and to cut a recorded sample from)."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "trace_%s_%d.json"
                        % (job.cell["name"], job.seed))
    with open(path, "w") as out:
        json.dump(job.profiler.trace, out)
    return path


def run_cell(spec, name, seed, seconds, trace, require_chip=True, dump=None,
             probe=False):
    """Drive one run; returns the result object of the last line."""
    cell = spec.cell(name)
    devices = harness.take_devices(cell["chips"], require_chip)
    if require_chip:          # a rehearsal on the CPU keeps no cache
        harness.enable_compile_cache()
    job = Job(spec, cell, seed, seconds, trace, devices, probe)
    driver = spec.driver(job.traffic["kind"])
    out = driver.run(job)

    device = harness.device_info()
    device["count"] = len(devices)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    ctx = dict(out["readings"])
    ctx.update({
        "setup_s": job.setup_s, "seconds": seconds, "spans": job.spans,
        "peaks": spec.peaks(device["kind"]),
        "memory_peak_bytes": out["memory_peak_bytes"],
        "trace": job.profiler.trace, "reduced": job.profiler.reduced})
    if dump and job.profiler.trace is not None:
        print("trace written to %s" % dump_trace(job, dump), flush=True)
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if trace:
        reduced = job.profiler.reduced
        if require_chip and (reduced is None or reduced["busy_s"] <= 0):
            raise RuntimeError("the traced stretch shows no operation on "
                               "the device")
        result["metrics"] = spec.read_metrics(name, "per_layer", ctx)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = spec.read_metrics(name, "end_to_end", ctx)
    result["device"] = device
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None,
                    help="with --trace 1: also write the traced stretch, "
                         "in the reduction's neutral form, to this directory")
    ap.add_argument("--probe", action="store_true",
                    help="for a look by hand: a thread that watches the "
                         "window for freezes of this process and samples "
                         "device memory; its run is not a measurement")
    ns = ap.parse_args(argv)
    try:
        spec = Spec(ROOT)
        seconds = ns.seconds if ns.seconds is not None \
            else spec.doc["run_seconds"]
        result = run_cell(spec, ns.workload, ns.seed, seconds, ns.trace,
                          dump=ns.dump, probe=ns.probe)
    except harness.NoChip as exc:
        print("benchmark: %s: nothing ran" % exc, file=sys.stderr)
        return 2
    except SpecError as exc:
        print("benchmark: %s" % exc, file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
