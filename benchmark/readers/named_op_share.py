"""Device time of the operations whose name in the trace (their HLO
text: a kernel under ``jax.named_scope`` carries the scope as its
instruction name, an XLA fusion only its shapes) matches one of
``match``, over the traced window, in percent, averaged over the
chips.  Nothing where no operation matches."""
import re

from benchmark import trace_reduce


def matcher(patterns):
    return re.compile("|".join("(?:%s)" % p for p in patterns)).search


def read(ctx, params):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    window = tuple(trace["window_ns"])
    width = (window[1] - window[0]) / 1e9
    total, count = 0.0, 0
    for events in trace["devices"].values():
        s, n = trace_reduce.matching_seconds(events, window,
                                             matcher(params["match"]))
        total, count = total + s, count + n
    if not count or width <= 0:
        return None
    return 100.0 * total / len(trace["devices"]) / width
