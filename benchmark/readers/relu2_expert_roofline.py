"""``moe_expert_roofline_groups`` for routed experts of two matrices in
a latent (``relu2_expert_costs``): ``groups`` is a list of ``{"match":
[...], "kernels_per_layer": n}``, one a kind of call (a prefill's two
grouped kernels a run; a decode step's batched products).  The traced
stretch's expert layers are the sum over the groups of the operations
found over their ``kernels_per_layer``; the window's counters are
scaled to them; the least time is the weights of the held experts that
got a token read once a layer and call plus the passes of the tokens
through them (the larger of operations over peak and bytes over HBM
bandwidth), over the device time of all the groups' operations, in
percent.  Prints which peak bounds."""
from benchmark import flops, relu2_expert_costs, trace_reduce
from benchmark.readers.named_op_share import matcher


def read(ctx, params):
    trace = ctx.get("trace")
    deltas = ctx.get("compiles_in_window") or {}
    layer_calls = deltas.get("moe_layer_steps_total")
    if not trace or not trace["devices"] or not layer_calls:
        return None
    window = tuple(trace["window_ns"])
    seconds, kernels, layers = 0.0, 0, 0.0
    for group in params["groups"]:
        for events in trace["devices"].values():
            s, n = trace_reduce.matching_seconds(events, window,
                                                 matcher(group["match"]))
            seconds, kernels = seconds + s, kernels + n
            layers += n / float(group["kernels_per_layer"])
    if not seconds:
        return None
    cfg = relu2_expert_costs.configuration(params["config"])
    traced = layers / layer_calls
    ops, moved = relu2_expert_costs.routed_experts_cost(
        cfg, traced * deltas["moe_local_experts_hit_total"],
        traced * deltas["moe_local_assignments_total"])
    least, by = flops.roofline_seconds(ops, moved, ctx["peaks"])
    print("expert roofline: bound by %s; %d kernels, %.1f expert layers "
          "(%.1f%% of the window's), %.4f s measured, %.4f s least"
          % (by, kernels, layers, 100.0 * traced, seconds, least), flush=True)
    return 100.0 * least / seconds
