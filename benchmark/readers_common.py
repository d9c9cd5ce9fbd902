"""What several readers share: telling the kernels apart by the HLO text
that is their name in the trace."""

from benchmark import trace_reduce


def is_kernel(name):
    """A Pallas (Mosaic) kernel: an HLO custom call that takes time.
    XLA's own zero-cost custom calls (buffer joins) have the opcode too;
    they add nothing to a sum of durations."""
    return trace_reduce.hlo_opcode(name) == "custom-call"


def is_attention(name, attention):
    """A kernel whose first operand has the ``[batch*heads, seq,
    head_dim]`` shape of the cell's attention calls on one chip."""
    if not attention or not is_kernel(name):
        return False
    rows = attention["batch"] * attention["heads"] // attention.get(
        "chips", 1)
    shape = "[%d,%d,%d]" % (rows, attention["seq_len"],
                            attention["head_dim"])
    operands = trace_reduce.hlo_parts(name)[2]
    return operands.split("{")[0].split(" ")[0].endswith(shape)
