"""A percentile of a list of samples (nearest rank on the sorted list,
so the largest sample, a failed request at the caller's timeout, counts as the worst)."""
import math


def read(ctx, params):
    samples = ctx.get(params["samples"])
    if not samples:
        return None
    ordered = sorted(samples)
    rank = max(1, math.ceil(params["q"] / 100.0 * len(ordered)))
    return params.get("scale", 1.0) * ordered[rank - 1]
