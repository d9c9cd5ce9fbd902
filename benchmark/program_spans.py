"""The program's own spans of a traced stretch, on the trace's clock.

The program records its spans (``mxnet_tpu.observability.tracing``)
whenever a profiler session is live, so after a ``--trace 1`` run its
ring holds the traced stretch and nothing else.  The ring is stamped
CLOCK_MONOTONIC; the device's operations are on the profiler's clock.
Both clocks already share one thing: every ``decode_call`` of the
benchmark is in ``ctx["spans"].samples`` by ``time.perf_counter`` (on
Linux the same clock as ``time.monotonic``) and in
``ctx["trace"]["host"]`` by the profiler's.  The offset between the two
is the median, over the traced calls, of the difference of their ends.

Nothing here names a cell.  Where the program records no such spans (a
parent of the PR that added them), where the ring lost some, or where
the calls do not match, there is nothing to read and the readers built
on this return ``None``.
"""

import numpy as np

from benchmark import trace_reduce

MATCH_NS = 50000          # ends further apart after the shift do not match
MATCHED_SHARE = 0.9       # of the traced calls have to
SAMPLES = "decode_call"
ROOTS = ("generation.iterate", "generation.idle")
STEP = "generation.decode"
_CACHE = "_program_spans"


def clock_offset_ns(sample_ends_ns, trace_ends_ns, near=None):
    """``trace - monotonic`` in ns, from the ends of the same calls on
    both clocks: ``trace_ends_ns`` are the calls of the traced stretch,
    ``sample_ends_ns`` those of the whole run (``near``: a
    ``(lo, hi)`` on the samples' clock outside which none is tried).
    The traced calls are a run of consecutive samples; of all such runs
    the one whose ends, shifted by their median difference, lie closest
    is taken, and it has to bring nine in ten within ``MATCH_NS``.
    ``None`` where no run does."""
    trace = np.sort(np.asarray(trace_ends_ns, dtype=np.int64))
    samples = np.sort(np.asarray(
        [s for s in sample_ends_ns
         if near is None or near[0] <= s <= near[1]], dtype=np.int64))
    n = len(trace)
    if n < 2 or len(samples) < n:
        return None
    best = None
    for k in range(len(samples) - n + 1):
        diffs = trace - samples[k:k + n]
        offset = int(np.median(diffs))
        apart = np.abs(diffs - offset)
        middle = float(np.median(apart))
        if best is None or middle < best[0]:
            best = (middle, offset, apart)
    _, offset, apart = best
    matched = int((apart <= MATCH_NS).sum())
    return offset if matched >= MATCHED_SHARE * n else None


def loop_spans(spans):
    """The spans of the serving loop's own thread: its roots
    (``ROOTS``) and whatever was opened under them on that thread.  A
    span recorded there with another parent (a request's queue wait,
    under the request's root) is not the loop's work."""
    part = {}
    for s in sorted(spans, key=lambda s: s["id"]):
        parent = part.get(s["parent"])
        if (s["name"] in ROOTS and not parent) or (
                parent is not None and parent["tid"] == s["tid"]):
            part[s["id"]] = s
    return list(part.values())


def innermost(spans):
    """Disjoint, sorted ``(a, b, name)``: each stretch of time with the
    innermost of the (properly nested) ``spans`` that covers it."""
    out, stack, at = [], [], 0

    def close(until):
        nonlocal at
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for s in sorted(spans, key=lambda s: (s["a"], -s["b"], s["id"])):
        close(s["a"])
        if stack and s["a"] > at:
            out.append((at, s["a"], stack[-1][1]))
        at = max(at, s["a"])
        stack.append((s["b"], s["name"]))
    close(float("inf"))
    return out


def covered(gaps, segments):
    """Nanoseconds of the ``gaps`` (disjoint, sorted) by the name of the
    segment (``innermost``) that covers them; ``""`` where none does."""
    out, i = {}, 0
    for ga, gb in gaps:
        at = ga
        while i < len(segments) and segments[i][1] <= ga:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < gb:
            a, b, name = segments[j]
            a, b = max(a, at), min(b, gb)
            if a > at:
                out[""] = out.get("", 0) + (a - at)
            if b > a:
                out[name] = out.get(name, 0) + (b - a)
                at = b
            j += 1
        if gb > at:
            out[""] = out.get("", 0) + (gb - at)
    return out


def _ring():
    """The program's ring as rows."""
    from mxnet_tpu.observability import tracing

    return [{"name": s.name, "a": int(s.start_us) * 1000,
             "b": int(s.end_us) * 1000, "tid": s.tid, "id": s.span_id,
             "parent": s.parent_id} for s in tracing.spans()]


def load(ctx):
    """``{"spans": rows on the trace's clock, "offset_ns": …}`` of the
    traced stretch, or ``None`` (see the module's docstring).  Computed
    once a run."""
    if _CACHE not in ctx:
        ctx[_CACHE] = _load(ctx)
    return ctx[_CACHE]


def _load(ctx):
    trace, harness_spans = ctx.get("trace"), ctx.get("spans")
    if not trace or harness_spans is None:
        return None
    if (ctx.get("compiles_in_window") or {}).get("spans_dropped_total"):
        return None
    spans = _ring()
    mine = loop_spans(spans)
    if not mine:
        return None
    lo = min(s["a"] for s in mine) - 10 ** 9
    hi = max(s["b"] for s in mine) + 10 ** 9
    offset = clock_offset_ns(
        [int(end * 1e9) for end, _ in harness_spans.samples.get(SAMPLES, ())],
        [start + dur for name, start, dur in trace["host"]
         if name == SAMPLES], near=(lo, hi))
    if offset is None:
        return None
    for s in spans:
        s["a"] += offset
        s["b"] += offset
    return {"spans": spans, "loop": mine, "offset_ns": offset}


def gap_shares(ctx):
    """``(seconds by name, window seconds)``: the device's idle time in
    the traced window by the loop's innermost span over it (``""``:
    none), averaged over the chips."""
    loaded = load(ctx)
    devices = (ctx.get("trace") or {}).get("devices")
    if loaded is None or not devices:
        return None
    if "gaps" not in loaded:
        window = tuple(ctx["trace"]["window_ns"])
        segments = innermost(loaded["loop"])
        total = {}
        for events in devices.values():
            gaps = trace_reduce.idle_gaps(events, window)
            for name, ns in covered(gaps, segments).items():
                total[name] = total.get(name, 0.0) + ns / 1e9 / len(devices)
        loaded["gaps"] = (total, (window[1] - window[0]) / 1e9)
    return loaded["gaps"]
