"""Items per second of the median segment."""
from benchmark import segments


def read(ctx, params):
    if "segment_seconds" not in ctx:
        return None
    return ctx["items_per_step"] * segments.median_rate(
        ctx["segment_seconds"], ctx["steps_per_segment"])
