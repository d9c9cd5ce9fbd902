"""The one-step state-space update's share of its roofline: the least
time the chip could take for it (every live row's float32 state read
once and written once a Mamba-2 layer, and the update's operations: the
larger of operations over peak and bytes over HBM bandwidth) over the
device time of its kernel, in percent.  The program counts the state
bytes its decode steps read and wrote over the whole window
(``generation_state_bytes_total``); the traced stretch gets its share of
them by the decode steps it holds (``count`` matches an operation that
runs once a state-space layer and step).  Prints which peak bounds."""
from benchmark import flops, ssm_costs
from benchmark.readers.state_decode_roofline import traced_kernel


def read(ctx, params):
    deltas = ctx.get("compiles_in_window") or {}
    moved = deltas.get("generation_state_bytes_total")
    steps = deltas.get("generation_decode_steps_total")
    found = traced_kernel(ctx, params)
    if not moved or not steps or not found:
        return None
    seconds, layer_steps = found
    cfg = ssm_costs.configuration(params["config"])
    # rows a step, from the bytes the program booked: every live row's
    # state over all its layers, once each way
    rows_a_step = moved / steps / (2.0 * ssm_costs.state_bytes(cfg))
    ops, least_bytes = ssm_costs.ssm_decode_cost(
        cfg, layer_steps * rows_a_step)
    least, by = flops.roofline_seconds(ops, least_bytes, ctx["peaks"])
    print("ssm decode roofline: bound by %s; %d (layer, step) pairs of %d "
          "layers, %.1f rows a step, %.4f s measured, %.4f s least"
          % (by, layer_steps, ssm_costs.state_layers(cfg), rows_a_step,
             seconds, least), flush=True)
    return 100.0 * least / seconds
