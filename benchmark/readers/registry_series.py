"""Several series of one family of the program's metrics registry as it
stands when the readers run (after the window), as one number.  The
series of the family ``name`` that carry every label of ``labels`` (a
value, or a list of which one is to match) and none of ``without`` are
summed by the value of the label ``by`` (all into one sum without it);
the ``stat`` (``sum``, the default, or ``max``) of those sums is scaled
by ``scale``.  With ``share_of``, a list of family names, the number is
that over the sum of the same selection in those families.  For what the
program books as it starts up: its scopes are entered at start-up only,
so their level after the window is start-up's own.  0 where the program
has the family and no such series; nothing where it has no such family."""

import re

LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')
STATS = {"sum": sum, "max": max}


def carries(labels, pairs):
    """Which of ``pairs`` (label: a value or a list of values) the
    series' ``labels`` match."""
    return [labels.get(key) in (want if isinstance(want, list) else [want])
            for key, want in pairs.items()]


def sums(text, name, params):
    """``{value of the label by: sum of the chosen series}`` of the
    family ``name`` in the rendering ``text``; None where the rendering
    does not declare the family."""
    if "# TYPE %s " % name not in text:
        return None
    out = {}
    for line in text.splitlines():
        series, _, value = line.rpartition(" ")
        if series.split("{", 1)[0] != name:
            continue
        labels = dict(LABEL.findall(series))
        if all(carries(labels, params.get("labels", {}))) \
                and not any(carries(labels, params.get("without", {}))):
            key = labels.get(params.get("by"))
            out[key] = out.get(key, 0.0) + float(value)
    return out


def read(ctx, params):
    from mxnet_tpu import observability as obs

    text = obs.REGISTRY.render()
    mine = sums(text, params["name"], params)
    if mine is None:
        return None
    value = STATS[params.get("stat", "sum")](list(mine.values()) or [0.0])
    if "share_of" in params:
        whole = sum(sum((sums(text, name, params) or {}).values())
                    for name in params["share_of"])
        if not whole:
            return None
        value /= whole
    return params.get("scale", 1.0) * value
