"""From a profiler trace to numbers: the yardstick's own reduction.

A trace is held in a neutral form, so that the arithmetic can be checked
on a small recorded one (``data/trace_sample.json``,
``tests/test_trace_reduce.py``) and so that a change of the profiler's
format touches only :func:`load_xplane`:

    {"window_ns": [start, end],
     "devices": {"<device id>": [[name, start_ns, dur_ns], ...]},
     "host": [[name, start_ns, dur_ns], ...]}

``devices`` holds the operations that ran on each chip (the profiler's
"XLA Ops" line of a ``/device:TPU:n`` plane).  ``host`` holds the
benchmark's own spans (``jax.profiler.TraceAnnotation`` with the prefix
``bench:``, the prefix stripped), on the same clock.
"""

import glob
import os
import re

SPAN_PREFIX = "bench:"
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I)


# ----------------------------------------------------------------------
# names: on a TPU an operation's trace name is its HLO text,
# "%fusion.7 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[8,128]{...} %p0), ..."

_LAYOUT_RE = re.compile(r"\{[^{}]*\}")
_OPERAND_NAME_RE = re.compile(r"\s*%[\w.\-]+")


def hlo_parts(name):
    """(opcode, output shapes, operand text) of an HLO-text name; a name
    in another form is its own opcode."""
    head = re.match(r"%?[\w.\-]+ = ", name)
    if not head:
        return name, "", ""
    rest = name[head.end():]
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    opcode, _, operands = rest.partition("(")
    return opcode, shape, operands


def hlo_opcode(name):
    return hlo_parts(name)[0]


def signature(name):
    """What kind of operation a name is, without instruction numbers
    and layouts: ``custom-call (bf16[128,1024,64], f32[128,1024,128]) <-
    bf16[128,1024,64], ...``.  The layers of a model share one."""
    opcode, shape, operands = hlo_parts(name)
    if not shape:
        return name
    def clean(text):                 # layouts nest once: {1,0:T(8,128)}
        return _OPERAND_NAME_RE.sub(
            "", _LAYOUT_RE.sub("", _LAYOUT_RE.sub("", text)))

    operands = clean(operands.split("), ")[0].rstrip(")"))
    return "%s %s <- %s" % (opcode, clean(shape), operands)


# ----------------------------------------------------------------------
# intervals


def _clip(events, window):
    lo, hi = window
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals):
    """Merged, sorted [a, b) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(merged):
    return sum(b - a for a, b in merged)


def busy_ns(events, window):
    """Nanoseconds of the window in which some operation ran."""
    return _length(_union((a, b) for _, a, b in _clip(events, window)))


def idle_gaps(events, window):
    """The [a, b) stretches of the window in which nothing ran."""
    merged = _union((a, b) for _, a, b in _clip(events, window))
    gaps, at = [], window[0]
    for a, b in merged:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if window[1] > at:
        gaps.append((at, window[1]))
    return gaps


def attribute_gaps(gaps, host_spans, window):
    """Seconds of idle time by what the host was doing in it.  Each
    stretch of a gap goes to the host span that covers it — where spans
    nest, to the one that started last — and to ``unattributed`` where
    none does."""
    spans = sorted(_clip(host_spans, window), key=lambda s: (s[1], -s[2]))
    out = {}
    for ga, gb in gaps:
        cuts = sorted({ga, gb} | {t for _, a, b in spans for t in (a, b)
                                  if ga < t < gb})
        for a, b in zip(cuts, cuts[1:]):
            owner = "unattributed"
            for name, sa, sb in spans:           # later start wins
                if sa <= a and sb >= b:
                    owner = name
            out[owner] = out.get(owner, 0) + (b - a)
    return {k: v / 1e9 for k, v in out.items()}


def op_seconds(events, window, key=signature):
    """Seconds by kind of operation (``key`` of the name)."""
    out, keys = {}, {}
    for name, a, b in _clip(events, window):
        if name not in keys:
            keys[name] = key(name)
        out[keys[name]] = out.get(keys[name], 0) + (b - a)
    return {k: v / 1e9 for k, v in out.items()}


def matching_seconds(events, window, match):
    """Seconds of the operations whose name ``match`` accepts (a
    function, or a pattern to search for), and how many there were."""
    if isinstance(match, str):
        match = re.compile(match).search
    verdict, total, count = {}, 0, 0
    for name, a, b in _clip(events, window):
        if name not in verdict:
            verdict[name] = bool(match(name))
        if verdict[name]:
            total += b - a
            count += 1
    return total / 1e9, count


def exposed_collective_ns(events, window):
    """Nanoseconds in which a collective ran on this device and no other
    operation did."""
    clipped = _clip(events, window)
    coll = _union((a, b) for n, a, b in clipped if COLLECTIVE_RE.search(n))
    comp = _union((a, b) for n, a, b in clipped
                  if not COLLECTIVE_RE.search(n))
    exposed = _length(coll)
    i = 0
    for a, b in coll:
        while i < len(comp) and comp[i][1] <= a:
            i += 1
        j = i
        while j < len(comp) and comp[j][0] < b:
            exposed -= min(b, comp[j][1]) - max(a, comp[j][0])
            j += 1
    return exposed


# ----------------------------------------------------------------------
# the reduction a run reports


def reduce(trace, top=10):
    """busy and window seconds averaged over the chips, the top device
    operations, the idle gaps by host span, the exposed collective
    share."""
    window = tuple(trace["window_ns"])
    width = (window[1] - window[0]) / 1e9
    devices = trace["devices"]
    if not devices:
        return None
    busy = [busy_ns(ev, window) / 1e9 for ev in devices.values()]
    ops, gaps, exposed = {}, {}, []
    for ev in devices.values():
        for name, s in op_seconds(ev, window).items():
            ops[name] = ops.get(name, 0) + s / len(devices)
        for name, s in attribute_gaps(idle_gaps(ev, window), trace["host"],
                                      window).items():
            gaps[name] = gaps.get(name, 0) + s / len(devices)
        exposed.append(exposed_collective_ns(ev, window) / 1e9)

    def ranked(table):
        return [[k[:64], v] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": sum(busy) / len(busy), "window_s": width,
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps),
            "exposed_collective_s": sum(exposed) / len(exposed)}


# ----------------------------------------------------------------------
# the profiler's file


def find_xplane(logdir):
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % logdir)
    return paths[-1]


def load_xplane(path, window_ns=None):
    """Read a ``.xplane.pb`` into the neutral form.  The window is the
    span ``bench:window`` if the run recorded one, else the extent of
    the device operations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name.rsplit(":", 1)[1]] = [
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append([ev.name[len(SPAN_PREFIX):],
                                     int(ev.start_ns), int(ev.duration_ns)])
    if window_ns is None:
        marks = [(s, s + d) for n, s, d in host if n == "window"]
        if marks:
            window_ns = [min(a for a, _ in marks), max(b for _, b in marks)]
        else:
            spans = [(s, s + d) for ev in devices.values() for _, s, d in ev]
            window_ns = [min(a for a, _ in spans),
                         max(b for _, b in spans)] if spans else [0, 0]
    return {"window_ns": [int(window_ns[0]), int(window_ns[1])],
            "devices": devices,
            "host": [h for h in host if h[0] != "window"]}
