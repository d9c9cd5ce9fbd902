"""The largest delta over the window among the named counters."""


def read(ctx, params):
    deltas = ctx.get("compiles_in_window")
    if deltas is None:
        return None
    return max([deltas.get(name, 0.0) for name in params["counters"]] + [0.0])
