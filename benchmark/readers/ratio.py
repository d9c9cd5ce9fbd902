"""One reading over another, scaled."""


def read(ctx, params):
    num, den = ctx.get(params["num"]), ctx.get(params["den"])
    if num is None or not den:
        return None
    return params.get("scale", 1.0) * num / den
