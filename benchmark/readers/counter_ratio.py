"""One of the program's counters over another, as deltas over the
window: ``scale * num / (den * den_scale)``.  Nothing where the program
has no such counter."""


def read(ctx, params):
    deltas = ctx.get("compiles_in_window") or {}
    num, den = deltas.get(params["num"]), deltas.get(params["den"])
    if num is None or not den:
        return None
    return params.get("scale", 1.0) * num \
        / (den * params.get("den_scale", 1.0))
