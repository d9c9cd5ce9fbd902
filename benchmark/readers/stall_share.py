"""1 - the whole window's rate / the median segment's rate, in percent."""
from benchmark import segments


def read(ctx, params):
    if "segment_seconds" not in ctx:
        return None
    return 100.0 * segments.stall_share(
        ctx["segment_seconds"], ctx["steps_per_segment"],
        ctx.get("window_s"))
