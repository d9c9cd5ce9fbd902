"""Device time of the Pallas kernels over the traced window, in percent,
averaged over the chips.  ``which`` = "attention" keeps the kernels with
the shapes of the cell's attention calls, "other" the rest."""
from benchmark import trace_reduce
from benchmark.readers_common import is_attention, is_kernel


def read(ctx, params):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    window = tuple(trace["window_ns"])
    width = (window[1] - window[0]) / 1e9
    attention = ctx.get("attention")
    want_attention = params["which"] == "attention"

    def match(name):
        return is_kernel(name) \
            and is_attention(name, attention) == want_attention

    total = sum(trace_reduce.matching_seconds(events, window, match)[0]
                for events in trace["devices"].values())
    return 100.0 * total / len(trace["devices"]) / width
