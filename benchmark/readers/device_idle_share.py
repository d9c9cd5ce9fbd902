"""1 - busy over window of the traced stretch, in percent."""


def read(ctx, params):
    reduced = ctx.get("reduced")
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
