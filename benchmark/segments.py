"""The arithmetic of a training window made of whole segments.

A segment is ``k`` dispatched steps ended by one ``block_until_ready``.
The window is the segments that were started before ``--seconds`` had
passed, all of them whole: it runs from the start of the first to the
end of the last, never to a deadline.  The run's reading is all its
steps over all that time (``window_rate``).  The median segment's rate
stands beside it as the steady statistic, and ``stall_share`` is how far
the window falls short of it: the share of the window that stalls,
slow segments and the time between segments took.
"""

import statistics


def rates(seconds, steps_per_segment):
    return [steps_per_segment / s for s in seconds]


def window_rate(seconds, steps_per_segment, window_s=None):
    """Steps per second over the whole window: every step of every
    segment over the time from the first segment's start to the last
    one's end (the segments' own seconds where that was not taken)."""
    if not seconds:
        raise ValueError("no segment was measured")
    if window_s is None:
        window_s = sum(seconds)
    return steps_per_segment * len(seconds) / window_s


def median_rate(seconds, steps_per_segment):
    """Steps per second of the median segment."""
    if not seconds:
        raise ValueError("no segment was measured")
    return statistics.median(rates(seconds, steps_per_segment))


def stall_share(seconds, steps_per_segment, window_s=None):
    """1 - window rate / median segment's rate, as a share (0.01 = 1%)."""
    return 1.0 - window_rate(seconds, steps_per_segment, window_s) \
        / median_rate(seconds, steps_per_segment)
