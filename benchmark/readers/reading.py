"""One reading as it is, scaled."""


def read(ctx, params):
    value = ctx.get(params["key"])
    if value is None:
        return None
    return params.get("scale", 1.0) * value
