"""A gauge of the program's metrics registry as it stands when the
readers run (after the window): ``scale`` times the value of the series
``name`` whose labels hold every ``labels`` pair.  For what the program
keeps as a level and not as a count (a high-water mark).  Nothing where
the program has no such series."""


def read(ctx, params):
    from mxnet_tpu import observability as obs

    want = ['%s="%s"' % kv for kv in sorted(params.get("labels",
                                                       {}).items())]
    for line in obs.REGISTRY.render().splitlines():
        series, _, value = line.rpartition(" ")
        if series.split("{", 1)[0] == params["name"] \
                and all(part in series for part in want):
            try:
                return params.get("scale", 1.0) * float(value)
            except ValueError:
                return None
    return None
