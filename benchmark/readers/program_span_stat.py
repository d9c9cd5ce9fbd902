"""A statistic of the program's own spans of the traced stretch
(``benchmark/program_spans.py``), in seconds times ``scale``:

``p50`` / ``p90``   that percentile of the named spans' durations
                    (nearest rank, as ``readers/percentile.py``)
``per_step``        their summed duration over the number of decode
                    steps (``generation.decode`` spans)
``self_per_step``   the same of their self time: a span's duration less
                    what its child spans cover

Nothing where the program recorded no such span or the ring cannot be
trusted."""
import math

from benchmark import program_spans


def read(ctx, params):
    loaded = program_spans.load(ctx)
    if loaded is None:
        return None
    names, stat = set(params["spans"]), params["stat"]
    spans = loaded["spans"]
    took = [(s["b"] - s["a"]) / 1e9 for s in spans if s["name"] in names]
    if not took:
        return None
    scale = params.get("scale", 1.0)
    if stat in ("p50", "p90"):
        rank = max(1, math.ceil(int(stat[1:]) / 100.0 * len(took)))
        return scale * sorted(took)[rank - 1]
    steps = sum(s["name"] == program_spans.STEP for s in spans)
    if not steps:
        return None
    if stat == "self_per_step":
        ids = {s["id"] for s in spans if s["name"] in names}
        took.append(-sum((s["b"] - s["a"]) / 1e9 for s in spans
                         if s["parent"] in ids))
    elif stat != "per_step":
        raise ValueError("unknown stat %r" % stat)
    return scale * sum(took) / steps
