"""The routed experts' products' share of their roofline: the least
time the chip could take for them (the weights of the held experts that
got a token read once a layer and call, plus the passes of the tokens
through them: the larger of operations over peak and bytes over HBM
bandwidth) over the device time of the grouped-product kernels, in
percent.  The program counts experts hit, pairs and expert layers run
over the whole window; the traced stretch gets its share of them by the
grouped-product kernels it holds (``kernels_per_layer`` a layer and
call).  Prints which peak bounds."""
from benchmark import flops, latent_moe_costs, trace_reduce
from benchmark.readers.named_op_share import matcher


def read(ctx, params):
    trace = ctx.get("trace")
    deltas = ctx.get("compiles_in_window") or {}
    layer_calls = deltas.get("moe_layer_steps_total")
    if not trace or not trace["devices"] or not layer_calls:
        return None
    window = tuple(trace["window_ns"])
    seconds, kernels = 0.0, 0
    for events in trace["devices"].values():
        s, n = trace_reduce.matching_seconds(events, window,
                                             matcher(params["match"]))
        seconds, kernels = seconds + s, kernels + n
    if not seconds:
        return None
    cfg = latent_moe_costs.configuration(params["config"])
    traced = kernels / float(params["kernels_per_layer"]) / layer_calls
    ops, moved = latent_moe_costs.routed_experts_cost(
        cfg, traced * deltas["moe_local_experts_hit_total"],
        traced * deltas["moe_local_assignments_total"])
    least, by = flops.roofline_seconds(ops, moved, ctx["peaks"])
    print("expert roofline: bound by %s; %d kernels (%.1f%% of the "
          "window's expert layers), %.4f s measured, %.4f s least"
          % (by, kernels, 100.0 * traced, seconds, least), flush=True)
    return 100.0 * least / seconds
