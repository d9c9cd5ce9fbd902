"""``moe_expert_roofline_groups`` where the compiler moves part of an
expert's weights outside the operations the trace can name: a group may
carry ``weight_share``, the share of an expert's weight bytes that the
group's own operations read (default 1).  A decode step of
``smallthinker-21b-ep4`` computes every held expert over every row in
two fusions a layer, and the compiler brings the first one's operand,
the gate matrices ``bf16[16,2560,768]``, into fast memory ahead of time
by asynchronous slices that it starts under earlier operations
(``slice-start`` / ``slice-done`` and a ``ConcatBitcast``): that read
is in no operation's time, so it is left out of the least time too
(``weight_share`` 2/3: the up and down matrices, which the second
fusion reads itself).  Counted whole, the share read 104% on the chip
(my chip run, PR 41): the time left out part of the work.  Everything
else is ``moe_expert_roofline_groups``: the traced stretch's expert
layers are the operations found over their ``kernels_per_layer``, the
window's counters are scaled to them, the least time is the larger of
operations over peak and bytes over HBM bandwidth, in percent of the
operations' device time.  Prints which peak bounds."""
from benchmark import flops, latent_moe_costs, trace_reduce
from benchmark.readers.named_op_share import matcher


def read(ctx, params):
    trace = ctx.get("trace")
    deltas = ctx.get("compiles_in_window") or {}
    layer_calls = deltas.get("moe_layer_steps_total")
    if not trace or not trace["devices"] or not layer_calls:
        return None
    window = tuple(trace["window_ns"])
    cfg = latent_moe_costs.configuration(params["config"])
    hit = deltas["moe_local_experts_hit_total"] / layer_calls
    pairs = deltas["moe_local_assignments_total"] / layer_calls
    seconds, kernels, ops, moved = 0.0, 0, 0.0, 0.0
    for group in params["groups"]:
        layers = 0.0
        for events in trace["devices"].values():
            s, n = trace_reduce.matching_seconds(events, window,
                                                 matcher(group["match"]))
            seconds, kernels = seconds + s, kernels + n
            layers += n / float(group["kernels_per_layer"])
        # a layer's products at the window's mean: experts hit, pairs
        layer_ops, whole = latent_moe_costs.routed_experts_cost(
            cfg, hit, pairs)
        weights = hit * latent_moe_costs.expert_weight_bytes(cfg)
        ops += layers * layer_ops
        moved += layers * (whole - weights
                           + group.get("weight_share", 1.0) * weights)
    if not seconds:
        return None
    least, by = flops.roofline_seconds(ops, moved, ctx["peaks"])
    print("expert roofline: bound by %s; %d kernels, %.4f s measured, "
          "%.4f s least" % (by, kernels, seconds, least), flush=True)
    return 100.0 * least / seconds
