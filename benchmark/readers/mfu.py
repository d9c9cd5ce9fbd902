"""Model FLOP/s utilization: the yardstick's operations per item times
the median segment's items per second (the steady rate: the traced run's
first segments carry the profiler), over chips times the peak of the
step's dtype."""
from benchmark import segments


def read(ctx, params):
    if "segment_seconds" not in ctx:
        return None
    items_per_s = ctx["items_per_step"] * segments.median_rate(
        ctx["segment_seconds"], ctx["steps_per_segment"])
    peak = ctx["peaks"]["flops_per_s"][ctx["dtype"]] * ctx["chips"]
    return 100.0 * ctx["flops_per_item"] * items_per_s / peak
