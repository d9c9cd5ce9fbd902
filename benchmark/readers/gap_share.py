"""Device idle time inside one of the benchmark's host spans, over the
traced window, in percent."""


def read(ctx, params):
    reduced = ctx.get("reduced")
    if not reduced or reduced["window_s"] <= 0:
        return None
    gaps = dict(reduced["idle_gaps"])
    return 100.0 * gaps.get(params["span"], 0.0) / reduced["window_s"]
