"""A sliding-window layer's paged decode attention's share of its
roofline: the least time the chip could take for it (the keys and
values the window lets every row see, read once a window layer, and
every query head's products with them: the larger of operations over
peak and bytes over HBM bandwidth) over the device time of the walk
over the ring, in percent.  The program counts ``min(context, window)``
of every row and decode step over the whole window
(``params["context_counter"]``); the traced stretch gets its share by
the decode steps it holds (``count`` matches an operation that runs
once a window layer and step).  Nothing where the program has no such
counter or the trace no such kernel.  Prints which peak bounds."""
from benchmark import flops, window_moe_costs
from benchmark.readers.state_decode_roofline import traced_kernel


def read(ctx, params):
    deltas = ctx.get("compiles_in_window") or {}
    context = deltas.get(params["context_counter"])
    steps = deltas.get("generation_decode_steps_total")
    found = traced_kernel(ctx, params)
    if not context or not steps or not found:
        return None
    seconds, layer_steps = found
    cfg = window_moe_costs.configuration(params["config"])
    per_step = 1.0 / steps
    ops, moved = window_moe_costs.window_decode_cost(
        cfg, layer_steps * per_step * context,
        layer_steps * per_step * deltas["generation_tokens_total"])
    least, by = flops.roofline_seconds(ops, moved, ctx["peaks"])
    print("window decode roofline: bound by %s; %d (layer, step) pairs, "
          "%.4f s measured, %.4f s least" % (by, layer_steps, seconds,
                                             least), flush=True)
    return 100.0 * least / seconds
