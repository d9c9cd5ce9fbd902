"""Sweep: device time of a prefill's Mamba-2 scan alone
(``ops/state_space.py``: ``ssm_chunked``'s two bodies) at the shapes
``nemotron3-super-serve-docs64`` serves: one layer's 128 heads of 64 in 8
groups over a state of 128, bfloat16 ``x``, ``B`` and ``C``, a stretch of
1,024 / 3,072 / 4,096 tokens from an empty or a carried-in state, every
position a token or the last fifth a bucket's pad.

What the choice of the kernel over XLA's body rests on (PERF.md §6 holds
the table this printed on the attached v5e).  Every case runs ``--iters``
times inside one profiler session (``tools/flash_sweep.py``'s reduction):
``scan`` is the device time of the operations the scan is told by in a
trace (the kernel's one custom call ``%ssm_prefill``; of XLA's body the
loop's ``%while``, which encloses its iterations), ``all`` every device
operation of the call, with what lays the operands out for either body.
Both bodies run on the same inputs and the largest difference of their
outputs is printed beside the times.

    python tools/scan_sweep.py                      # both bodies
    python tools/scan_sweep.py --root .pr47/parent  # another checkout's
    python tools/scan_sweep.py --tokens 4096 --iters 20
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

# nemotron3-super-ep4: mamba_num_heads, mamba_head_dim, n_groups,
# ssm_state_size, chunk_size (benchmark/configs/nemotron3-super-ep4.json)
HEADS, HEAD_DIM, GROUPS, STATE, CHUNK = 128, 64, 8, 128, 128
# the stretches a bucket's Mamba-2 layers run in
# (models/state_space_moe.py:_segment)
TOKENS = (1024, 3072, 4096)


def _inputs(jnp, np, tokens, seed=0):
    rng = np.random.default_rng(seed)

    def rand(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(
            dtype)

    bf16 = jnp.bfloat16
    # a step of softplus(-2 +- 1) and a rate of -exp(+-1): a decay of
    # ~0.85 a token, as the configuration's weights give
    step = jnp.log1p(jnp.exp(rand(tokens, HEADS) - 2.0))
    return (rand(tokens, HEADS, HEAD_DIM, dtype=bf16), step,
            -jnp.exp(rand(HEADS)), rand(tokens, GROUPS, STATE, dtype=bf16),
            rand(tokens, GROUPS, STATE, dtype=bf16), rand(HEADS)), \
        rand(GROUPS, STATE, HEADS * HEAD_DIM // GROUPS)


def _scan_kind(name):
    if name.startswith("%ssm_prefill") or name.startswith("%while"):
        return "scan"
    return None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(HERE),
                    help="the checkout whose mxnet_tpu is swept")
    ap.add_argument("--tokens", nargs="*", type=int, default=list(TOKENS))
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.abspath(args.root), HERE]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import state_space as ss
    import flash_sweep

    dev = jax.devices()[0]
    print("device: %s %s; jax %s; scan of %s" % (
        dev.platform, dev.device_kind, jax.__version__,
        os.path.relpath(ss.__file__)))
    bodies = {"xla": jax.jit(lambda *a: ss._chunked(*a, CHUNK, ss.BLOCK))}
    if hasattr(ss, "_chunked_pallas"):      # a parent's checkout has none
        bodies["kernel"] = jax.jit(lambda *a: ss._chunked_pallas(
            *a, size=CHUNK, interpret=flash_sweep.OFF_CHIP))
    print("%-7s %-8s %-7s %-7s %10s %10s  %s" % (
        "tokens", "state", "length", "body", "scan us", "all us",
        "largest difference from xla's (y, state)"))
    for tokens in args.tokens:
        ops, carried = _inputs(jnp, np, tokens, seed=tokens)
        cases, outs = [], {}
        for state in ("empty", "carried"):
            for length in (tokens, tokens * 4 // 5):
                start = carried if state == "carried" \
                    else jnp.zeros_like(carried)
                for body, fn in bodies.items():
                    label = (state, length, body)
                    call = ops + (start, jnp.int32(length))
                    cases.append((label, fn, call))
                    outs[label] = fn(*call)
        got = flash_sweep.run(cases, args.iters, kind=_scan_kind)
        for (state, length, body), _, _ in cases:
            row = got[(state, length, body)]
            y, s = outs[(state, length, body)]
            want_y, want_s = outs[(state, length, "xla")]
            gap = "" if body == "xla" else "%.3g (of %.3g), %.3g (of %.3g)" % (
                float(jnp.abs(y[:length].astype(jnp.float32)
                              - want_y[:length].astype(jnp.float32)).max()),
                float(jnp.abs(want_y[:length].astype(jnp.float32)).max()),
                float(jnp.abs(s - want_s).max()),
                float(jnp.abs(want_s).max()))
            scan = row.get("scan", 0.0)
            # a loop's event encloses its iterations' own
            rest = row["all"] - (scan if body == "xla" else 0.0)
            print("%-7d %-8s %-7d %-7s %10.1f %10.1f  %s" % (
                tokens, state, length, body, scan, rest, gap), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
