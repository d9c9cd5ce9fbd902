"""Mean, median or max of a series the driver kept."""
import statistics

STATS = {"mean": statistics.fmean, "median": statistics.median, "max": max}


def read(ctx, params):
    samples = ctx.get(params["samples"])
    if not samples:
        return None
    return params.get("scale", 1.0) * STATS[params["stat"]](samples)
