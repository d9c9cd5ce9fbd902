"""The flash kernel's share of its roofline over a sliding window's
band: the least time the chip could take for the window layers' prefill
attention calls that ran (from each call's prompt length, read off the
kernel's first operand ``[heads, tokens, head_dim]``: the products of
the pairs inside the band, and queries, keys, values and outputs moved
once: the larger of operations over peak and bytes over HBM bandwidth)
over the device time they took, in percent.  Nothing where the trace
has no such kernel.  Prints the calls by length."""
import re

from benchmark import flops, trace_reduce, window_moe_costs

_SHAPE = re.compile(r"\[(\d+),(\d+),(\d+)\]")


def prompt_tokens(name, match):
    """The ``tokens`` of the kernel's first operand, or None for a name
    that is not such a kernel."""
    if not match(name):
        return None
    shape = _SHAPE.search(trace_reduce.hlo_parts(name)[2])
    return int(shape.group(2)) if shape else None


def read(ctx, params):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    window = tuple(trace["window_ns"])
    match = re.compile(params["match"]).search
    cfg = window_moe_costs.configuration(params["config"])
    seconds, least, calls = 0.0, 0.0, {}
    for events in trace["devices"].values():
        # a first pass that matches nothing and notes the lengths there
        # are; then each length's kernels, which cost alike
        lengths = set()
        trace_reduce.matching_seconds(
            events, window,
            lambda name: lengths.add(prompt_tokens(name, match)))
        for tokens in sorted(lengths - {None}):
            s, n = trace_reduce.matching_seconds(
                events, window,
                lambda name, t=tokens: prompt_tokens(name, match) == t)
            ops, moved = window_moe_costs.band_prefill_cost(cfg, tokens)
            seconds += s
            least += n * flops.roofline_seconds(ops, moved, ctx["peaks"])[0]
            calls[tokens] = calls.get(tokens, 0) + n
    if not seconds:
        return None
    print("band prefill roofline: kernels by prompt length %s, %.4f s "
          "measured, %.4f s least" % (sorted(calls.items()), seconds, least),
          flush=True)
    return 100.0 * least / seconds
