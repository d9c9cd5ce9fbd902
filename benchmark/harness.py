"""What every driver needs from the machine: the device, the compile
cache, spans, the profiler, the peak of device memory."""

import contextlib
import gc
import os
import shutil
import threading
import time

from . import trace_reduce


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks."""


def device_info():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def take_devices(chips, require_chip=True):
    """The ``chips`` devices the cell runs on.  Without a TPU, or with
    fewer than asked, the run fails: there is no CPU path."""
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip("JAX found no TPU (platform %r)" % devs[0].platform)
    if len(devs) < chips:
        raise NoChip("the cell needs %d chip(s), JAX found %d"
                     % (chips, len(devs)))
    return devs[:chips]


def enable_compile_cache():
    """The program's own switch (``JAX_COMPILATION_CACHE_DIR`` if the
    machine sets it, else ``<checkout>/.jax_cache``), and every program
    kept, however quick its compile."""
    import jax

    from mxnet_tpu import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_parts(devices):
    """(peak_bytes_in_use, peak_bytes_reserved, bytes_reserved now) of
    the fullest chip; zeros where the backend does not say."""
    parts = (0, 0, 0)
    for dev in devices:
        stats = dev.memory_stats() or {}
        mine = tuple(int(stats.get(key, 0)) for key in (
            "peak_bytes_in_use", "peak_bytes_reserved", "bytes_reserved"))
        if sum(mine[:2]) > sum(parts[:2]):
            parts = mine
    return parts


def memory_peak_bytes(devices):
    """Peak bytes on the fullest chip: the arrays in use at their peak
    plus what XLA reserves for its loaded programs' temporaries.  On a
    TPU the two are separate pools of the same memory
    (``bytes_reservable_limit`` = ``bytes_limit`` - ``peak_bytes_in_use``)
    and ``peak_bytes_in_use`` leaves the second out: a training step
    that needs 10 GB of temporaries reads 5 GB without it.  The
    reservation is made when a program is loaded, so through a window
    whose programs were all warmed before it, it stands at its peak and
    the sum is the high-water mark (``--probe`` samples both together;
    PERF.md has the readings).  Every run prints the parts."""
    in_use, reserved, now = memory_parts(devices)
    print("memory: peak_bytes_in_use %d + peak_bytes_reserved %d = %d "
          "(bytes_reserved now %d)" % (in_use, reserved, in_use + reserved,
                                      now), flush=True)
    return in_use + reserved


def _proc_numbers(path, skip=0):
    """The whole numbers of the first line of a /proc file, after
    ``skip`` words; [] where it is not there."""
    try:
        with open(path) as f:
            return [int(w) for w in f.readline().split()[skip:]]
    except (OSError, ValueError):
        return []


class HostWatch(object):
    """What the host did to the window, on two printed lines of every
    run.  A rate over a whole window carries every moment in which the
    host did not feed the device, so a run that reads low says here
    whether the host was at fault and how: the collector's pauses
    (every thread of the process stands still through one), the CPU
    seconds the process took, how long its threads stood runnable
    waiting for a core (``/proc/self/task/*/schedstat``), the time the
    hypervisor gave to others (``steal`` in ``/proc/stat``: a one-chip
    machine shares its host's cores), and the seconds some task of the
    machine was stalled for want of CPU, I/O or memory
    (``/proc/pressure``).  It changes nothing and costs two reads of
    /proc."""

    def __init__(self):
        self.pauses, self._t, self.t0 = [], None, None

    def _collected(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif self._t is not None:
            self.pauses.append((info["generation"], now - self._t,
                                self._t - self.t0))

    def _now(self):
        mine = _proc_numbers("/proc/self/schedstat")
        threads = 0
        try:
            for task in os.listdir("/proc/self/task"):
                row = _proc_numbers("/proc/self/task/%s/schedstat" % task)
                threads += row[1] if len(row) > 1 else 0
        except OSError:
            pass
        stat = _proc_numbers("/proc/stat", skip=1)
        out = {"cpu": sum(os.times()[:2]),
               "main waited": (mine[1] if len(mine) > 1 else 0) / 1e9,
               "threads waited": threads / 1e9,
               "stolen": stat[7] / float(os.sysconf("SC_CLK_TCK"))
               if len(stat) > 7 else 0.0}
        for what in ("cpu", "io", "memory"):
            try:
                with open("/proc/pressure/" + what) as f:
                    out["pressure " + what] = int(
                        f.readline().rsplit("total=", 1)[1]) / 1e6
            except (OSError, IndexError, ValueError):
                pass
        return out

    def open(self):
        self.t0 = time.perf_counter()
        self.before = self._now()
        gc.callbacks.append(self._collected)

    def close(self):
        if self.t0 is None:
            return
        gc.callbacks.remove(self._collected)
        wall = time.perf_counter() - self.t0
        after = self._now()
        full = [p for p in self.pauses if p[0] == 2]
        longest = max(self.pauses, key=lambda p: p[1], default=None)
        print("collector: %d collections held the process %.3f s of %.1f s,"
              " %d of them full for %.3f s; the longest %s"
              % (len(self.pauses), sum(p[1] for p in self.pauses), wall,
                 len(full), sum(p[1] for p in full),
                 "%.3f s (generation %d) at %.1f s"
                 % (longest[1], longest[0], longest[2]) if longest
                 else "none"), flush=True)
        took = {k: after[k] - self.before.get(k, 0.0) for k in after}
        print("machine: the process took %.1f CPU s in %.1f s (%.2f cores); "
              "its main thread stood %.3f s runnable without a core, all "
              "its threads %.3f s; %.2f s of the machine's CPU time were "
              "stolen; some task of the machine stalled %s"
              % (took["cpu"], wall, took["cpu"] / wall, took["main waited"],
                 took["threads waited"], took["stolen"],
                 ", ".join("%.3f s for %s" % (took[k], k.split()[1])
                           for k in sorted(took) if k.startswith("pressure"))
                 or "(no /proc/pressure)"), flush=True)
        self.t0 = None


class Probe(threading.Thread):
    """``--probe``, for a look by hand and never in a measured run: a
    thread that, while the window is open, wakes every few milliseconds
    and notes how late it woke (a gap of seconds means this process's
    threads did not run: the machine, not the device) and reads
    ``bytes_in_use`` + ``bytes_reserved`` together (the largest sum seen
    is a lower bound on the true high-water mark, to set beside the sum
    of the two peaks that ``memory_peak_bytes`` reports)."""

    def __init__(self, devices, every=0.005):
        threading.Thread.__init__(self, daemon=True)
        self.devices, self.every = devices, every
        self.done = threading.Event()
        self.gaps, self.sums, self.reserved = [], [], set()

    def run(self):
        t0 = last = time.perf_counter()
        n = 0
        while not self.done.is_set():
            time.sleep(self.every)
            now = time.perf_counter()
            if now - last > 0.1:
                self.gaps.append((last - t0, now - last))
            last = now
            n += 1
            if n % 4 == 0:
                for dev in self.devices:
                    stats = dev.memory_stats() or {}
                    self.sums.append(int(stats.get("bytes_in_use", 0))
                                     + int(stats.get("bytes_reserved", 0)))
                    self.reserved.add(int(stats.get("bytes_reserved", 0)))
                last = time.perf_counter()

    def report(self):
        self.done.set()
        self.join()
        print("probe: %d memory samples, largest bytes_in_use + "
              "bytes_reserved seen together %d; bytes_reserved took the "
              "values %s" % (len(self.sums), max(self.sums or [0]),
                             sorted(self.reserved)), flush=True)
        print("probe: the ticker woke over 0.1 s late %d times: %s"
              % (len(self.gaps), ", ".join(
                  "%.2f s at %.1f s" % (gap, at)
                  for at, gap in self.gaps) or "never"), flush=True)


class Spans(object):
    """Host spans of the benchmark's own, around its calls into the
    program.  With the profiler on they go into its trace (prefix
    ``bench:``); always, their seconds are summed by name."""

    def __init__(self):
        self.tracing = False
        self.seconds = {}
        self.samples = {}

    @contextlib.contextmanager
    def span(self, name, keep=False):
        import jax

        ctx = (jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)
               if self.tracing else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ctx:
            yield
        dt = time.perf_counter() - t0
        self.seconds[name] = self.seconds.get(name, 0.0) + dt
        if keep:                          # (when it ended, how long it took)
            self.samples.setdefault(name, []).append((t0 + dt, dt))


class Profiler(object):
    """One traced stretch of a run, reduced when it stops.  The trace is
    written inside the checkout and removed once read."""

    def __init__(self, spans, root):
        self.spans = spans
        self.dir = os.path.join(str(root), ".bench_trace")
        self.reduced = None
        self.trace = None
        self._window = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.spans.tracing = True
        self._window = jax.profiler.TraceAnnotation(
            trace_reduce.SPAN_PREFIX + "window")
        self._window.__enter__()

    def stop(self):
        """End the traced stretch.  The trace is read by :meth:`reduce`,
        which the driver calls once the window has closed: reading it
        holds the interpreter, and a served window must not wait."""
        import jax

        self._window.__exit__(None, None, None)
        self.spans.tracing = False
        jax.profiler.stop_trace()
        self._window = None

    def reduce(self):
        if self._window is not None:
            self.stop()
        if self.trace is None and os.path.isdir(self.dir):
            self.trace = trace_reduce.load_xplane(
                trace_reduce.find_xplane(self.dir))
            self.reduced = trace_reduce.reduce(self.trace)
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.reduced


class Counters(object):
    """The program's counters (its metrics registry, read through its
    Prometheus rendering) and JAX's own count of compile requests,
    as deltas over a stretch of the run."""

    JAX_EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax

        self._jax_compiles = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == self.JAX_EVENT:
            self._jax_compiles += 1

    def snapshot(self):
        from mxnet_tpu import observability as obs

        out = {"jax_compile_requests": float(self._jax_compiles)}
        for line in obs.REGISTRY.render().splitlines():
            if line.startswith("#") or not line.strip():
                continue
            series, _, value = line.rpartition(" ")
            name = series.split("{", 1)[0]
            if name.endswith(("_total", "_count", "_sum")):
                try:
                    out[name] = out.get(name, 0.0) + float(value)
                except ValueError:
                    pass
        return out

    def since(self, before):
        now = self.snapshot()
        return {k: v - before.get(k, 0.0) for k, v in now.items()}
