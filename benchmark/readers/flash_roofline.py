"""The flash kernels' share of their roofline: the least time the chip
could take for the attention calls that ran (forward and backward, from
their shapes: the larger of operations over peak and bytes over HBM
bandwidth) over the device time they took, in percent.  Prints which
peak bounds each."""
from benchmark import flops, trace_reduce
from benchmark.readers_common import is_attention


def read(ctx, params):
    trace, att = ctx.get("trace"), ctx.get("attention")
    if not trace or not trace["devices"] or not att:
        return None
    window = tuple(trace["window_ns"])
    seconds, calls = 0.0, 0
    for events in trace["devices"].values():
        s, n = trace_reduce.matching_seconds(
            events, window, lambda name: is_attention(name, att))
        seconds, calls = seconds + s, calls + n
    if not seconds:
        return None
    shape = (att["batch"] // att.get("chips", 1), att["heads"],
             att["seq_len"], att["head_dim"])
    least_fwd, by_fwd = flops.roofline_seconds(
        flops.flash_attention_flops(*shape),
        flops.flash_attention_bytes(*shape), ctx["peaks"])
    least_bwd, by_bwd = flops.roofline_seconds(
        flops.flash_attention_flops(*shape, backward=True),
        flops.flash_attention_bytes(*shape, backward=True), ctx["peaks"])
    # one layer's attention is kernels_per_layer kernels: one forward
    # and the backward's (dK/dV and dQ)
    layers = calls / float(params["kernels_per_layer"])
    least = layers * (least_fwd + least_bwd)
    print("flash roofline: forward bound by %s, backward by %s; %d "
          "kernels, %.4f s measured, %.4f s least"
          % (by_fwd, by_bwd, calls, seconds, least), flush=True)
    return 100.0 * least / seconds
