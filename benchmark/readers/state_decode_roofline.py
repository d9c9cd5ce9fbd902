"""The one-step gated delta update's share of its roofline: the least
time the chip could take for it (every live row's state read once and
written once a DeltaNet layer, and the update's operations: the larger
of operations over peak and bytes over HBM bandwidth) over the device
time of its operations, in percent.  The program counts the state bytes
its decode steps read and wrote over the whole window
(``generation_state_bytes_total``); the traced stretch gets its share of
them by the decode steps it holds (``count`` matches an operation that
runs once a DeltaNet layer and step).  Prints which peak bounds."""
from benchmark import flops, gated_delta_costs, trace_reduce
from benchmark.readers.named_op_share import matcher


def traced_kernel(ctx, params):
    """(device seconds of the operations ``match`` names, how many
    ``count`` names: one a layer and step) over the traced stretch;
    ``None`` where there is no trace or nothing matches."""
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    window = tuple(trace["window_ns"])
    seconds, layer_steps = 0.0, 0
    for events in trace["devices"].values():
        seconds += trace_reduce.matching_seconds(
            events, window, matcher(params["match"]))[0]
        layer_steps += trace_reduce.matching_seconds(
            events, window, matcher([params["count"]]))[1]
    return (seconds, layer_steps) if seconds and layer_steps else None


def read(ctx, params):
    deltas = ctx.get("compiles_in_window") or {}
    moved = deltas.get("generation_state_bytes_total")
    steps = deltas.get("generation_decode_steps_total")
    found = traced_kernel(ctx, params)
    if not moved or not steps or not found:
        return None
    seconds, layer_steps = found
    cfg = gated_delta_costs.configuration(params["config"])
    layers = gated_delta_costs.linear_layers(cfg)
    # rows a step, from the bytes the program booked: every live row's
    # state over all its layers, once each way
    rows_a_step = moved / steps / (2.0 * gated_delta_costs.state_bytes(cfg))
    ops, least_bytes = gated_delta_costs.delta_decode_cost(
        cfg, layer_steps * rows_a_step)
    least, by = flops.roofline_seconds(ops, least_bytes, ctx["peaks"])
    print("state decode roofline: bound by %s; %d (layer, step) pairs of "
          "%d layers, %.1f rows a step, %.4f s measured, %.4f s least"
          % (by, layer_steps, layers, rows_a_step, seconds, least),
          flush=True)
    return 100.0 * least / seconds
