"""The absorbed decode attention's share of its roofline: the least
time the chip could take for it (every live cached row read once, every
head's products with it: the larger of operations over peak and bytes
over HBM bandwidth) over the device time of its operations, in percent.
The program counts the live context and the rows of every decode step
over the whole window; the traced stretch gets its share of them by the
decode steps it holds (``count`` matches an operation that runs once a
layer and step).  Prints which peak bounds."""
from benchmark import flops, latent_moe_costs, trace_reduce
from benchmark.readers.named_op_share import matcher


def read(ctx, params):
    trace = ctx.get("trace")
    deltas = ctx.get("compiles_in_window") or {}
    context = deltas.get("generation_decode_context_tokens_total")
    steps = deltas.get("generation_decode_steps_total")
    if not trace or not trace["devices"] or not context or not steps:
        return None
    window = tuple(trace["window_ns"])
    seconds, layer_steps = 0.0, 0
    for events in trace["devices"].values():
        seconds += trace_reduce.matching_seconds(
            events, window, matcher(params["match"]))[0]
        layer_steps += trace_reduce.matching_seconds(
            events, window, matcher([params["count"]]))[1]
    if not seconds or not layer_steps:
        return None
    cfg = latent_moe_costs.configuration(params["config"])
    # per layer: the window's totals scaled to the traced steps, then
    # one such cost for every (layer, step) the trace holds
    per_step = 1.0 / steps
    ops, moved = latent_moe_costs.latent_decode_cost(
        cfg, layer_steps * per_step * context,
        layer_steps * per_step * deltas["generation_tokens_total"])
    least, by = flops.roofline_seconds(ops, moved, ctx["peaks"])
    print("latent decode roofline: bound by %s; %d (layer, step) pairs, "
          "%.4f s measured, %.4f s least" % (by, layer_steps, seconds,
                                             least), flush=True)
    return 100.0 * least / seconds
