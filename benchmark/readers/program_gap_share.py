"""Device idle time of the traced window while the innermost span of
the serving loop's thread was one of ``spans``, over the window, in
percent.  ``spans: []`` reads the idle time no span of the loop covers.
The spans are the program's own (``benchmark/program_spans.py``);
nothing where the program recorded none or they cannot be put on the
trace's clock."""

from benchmark import program_spans


def read(ctx, params):
    shares = program_spans.gap_shares(ctx)
    if shares is None:
        return None
    seconds, window_s = shares
    if window_s <= 0:
        return None
    names = params["spans"] or [""]
    return 100.0 * sum(seconds.get(n, 0.0) for n in names) / window_s
