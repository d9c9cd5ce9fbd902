"""The grouped-query paged decode attention's share of its roofline: the
least time the chip could take for it (every live cached key row and
value row read once, every query head's products with them: the larger
of operations over peak and bytes over HBM bandwidth) over the device
time of its operations, in percent.  The program counts the live
context and the rows of every decode step over the whole window; the
traced stretch gets its share of them by the decode steps it holds
(``count`` matches an operation that runs once a full-attention layer
and step).  Prints which peak bounds."""
from benchmark import flops, gated_delta_costs
from benchmark.readers.state_decode_roofline import traced_kernel


def read(ctx, params):
    deltas = ctx.get("compiles_in_window") or {}
    context = deltas.get("generation_decode_context_tokens_total")
    steps = deltas.get("generation_decode_steps_total")
    found = traced_kernel(ctx, params)
    if not context or not steps or not found:
        return None
    seconds, layer_steps = found
    cfg = gated_delta_costs.configuration(params["config"])
    per_step = 1.0 / steps
    ops, moved = gated_delta_costs.gqa_decode_cost(
        cfg, layer_steps * per_step * context,
        layer_steps * per_step * deltas["generation_tokens_total"])
    least, by = flops.roofline_seconds(ops, moved, ctx["peaks"])
    print("gqa decode roofline: bound by %s; %d (layer, step) pairs, "
          "%.4f s measured, %.4f s least" % (by, layer_steps, seconds,
                                             least), flush=True)
    return 100.0 * least / seconds
