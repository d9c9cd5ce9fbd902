"""Operations and bytes an algorithm needs, computed from its shapes.

These are the yardstick's own counts (a multiply-add is two operations;
recomputed and fused-away work is not counted), so that a utilization
means the same thing before and after a change to the program.  Checked
against hand-worked numbers in ``tests/test_flops.py``.
"""

RESNET_UNITS = {50: (3, 4, 6, 3)}
RESNET_FILTERS = (64, 256, 512, 1024, 2048)


def gpt2_forward_flops_per_token(cfg, seq_len):
    """Forward operations per token of a GPT-2 block model at sequence
    length ``seq_len``: the four projections and two FFN products of
    every layer, causal attention (each query meets half the keys on
    average: ``(seq_len + 1) / 2``), and the vocabulary head.
    Embedding lookups, LayerNorm, GELU and softmax are not counted."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    ffn = cfg.get("n_inner") or 4 * d
    per_layer = 2 * (3 * d * d + d * d + 2 * d * ffn)
    attention = 2 * 2 * d * (seq_len + 1) / 2.0      # QK^T and PV
    return layers * (per_layer + attention) + 2 * d * cfg["vocab_size"]


def gpt2_train_flops_per_token(cfg, seq_len):
    """Forward plus backward (twice the forward), no recomputation."""
    return 3 * gpt2_forward_flops_per_token(cfg, seq_len)


def flash_attention_flops(batch, heads, seq_len, head_dim, causal=True,
                          backward=False):
    """Operations of one attention call.  Forward: QK^T and PV.  Backward
    as the flash algorithm needs it: QK^T again, dV, dP, dQ and dK —
    five products for the forward's two."""
    pairs = seq_len * (seq_len + 1) / 2.0 if causal else float(seq_len) ** 2
    products = 5 if backward else 2
    return 2.0 * products * batch * heads * pairs * head_dim


def flash_attention_bytes(batch, heads, seq_len, head_dim, itemsize=2,
                          backward=False):
    """Bytes one attention call must move at least: forward reads Q, K, V
    and writes O (and the float32 row statistics); backward reads Q, K,
    V, O, dO and the statistics, and writes dQ, dK, dV."""
    tensor = batch * heads * seq_len * head_dim * itemsize
    stats = batch * heads * seq_len * 4
    if backward:
        return 8 * tensor + 2 * stats
    return 4 * tensor + stats


def roofline_seconds(flops, nbytes, peaks, dtype="bfloat16"):
    """The least time the chip could take and which peak sets it."""
    t_flops = flops / peaks["flops_per_s"][dtype]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes
                                   else "memory")


def resnet_forward_flops_per_image(cfg):
    """Forward operations per image of the pre-activation bottleneck
    ResNet: every convolution and the classifier (2 per multiply-add);
    BatchNorm, ReLU and pooling are not counted.  The stem is counted
    as the published 7x7/2 convolution on 3 channels (the 4x4 form over
    space-to-depth blocks multiplies zero taps, which do not count)."""
    size = cfg["image_size"]
    units = RESNET_UNITS[cfg["num_layers"]]
    out = size // 2
    total = 2.0 * out * out * 7 * 7 * 3 * RESNET_FILTERS[0]
    hw = out // 2                                       # after max pooling
    in_ch = RESNET_FILTERS[0]
    for stage, count in enumerate(units):
        filters = RESNET_FILTERS[stage + 1]
        width = filters // 4
        for j in range(count):
            stride = 2 if (stage > 0 and j == 0) else 1
            out_hw = hw // stride
            total += 2.0 * hw * hw * in_ch * width                 # 1x1
            total += 2.0 * out_hw * out_hw * 9 * width * width     # 3x3
            total += 2.0 * out_hw * out_hw * width * filters       # 1x1
            if j == 0:                                  # projection shortcut
                total += 2.0 * out_hw * out_hw * in_ch * filters
            hw, in_ch = out_hw, filters
    return total + 2.0 * in_ch * cfg["num_classes"]


def resnet_train_flops_per_image(cfg):
    return 3 * resnet_forward_flops_per_image(cfg)
