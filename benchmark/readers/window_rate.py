"""Items per second over the whole window: every step of every segment
over the time from the first segment's start to the last one's end."""
from benchmark import segments


def read(ctx, params):
    if "segment_seconds" not in ctx:
        return None
    return ctx["items_per_step"] * segments.window_rate(
        ctx["segment_seconds"], ctx["steps_per_segment"],
        ctx.get("window_s"))
