"""Both forms of the dropless expert layer (``parallel/moe.py``: every
held expert over every row, or grouped products over the sorted pairs)
at a decode step's shapes, on the chip it is started on: wall time of
one layer, the median of 60 calls and the pace of 20 queued calls, for
8 to 128 rows over 32 experts of LFM2's widths, 4 a token; with
``smallthinker`` as its argument, for 16 to 128 rows over the 16 held of
SmallThinker's 64 ReGLU experts (2560 x 768), 6 a token, of which a
quarter falls on the held ones.

    chiprun --chips 1 -- python3 tools/expert_forms.py [smallthinker]

Writes ``chiprun_out/expert_forms[_smallthinker].json``; says for each
shape what ``few_rows_hit_most`` would choose (PERF.md section 6: PR 39
against the compiler's 512-row tile, PR 40 against
``moe.grouped_tiling``'s, PR 41 at 48 rows of the new model)."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(model="lfm2"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel import moe

    # widths, held experts, choices a token, the router's width, the
    # gate's activation, rows
    d, h, n, k, wide, act, sizes = {
        "lfm2": (2048, 1792, 32, 4, 32, "silu", (8, 16, 32, 64, 128)),
        "smallthinker": (2560, 768, 16, 6, 64, "relu", (16, 48, 96, 128)),
    }[model]
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    w_gate = (0.02 * jax.random.normal(ks[0], (n, d, h), jnp.float32)).astype(jnp.bfloat16)
    w_up = (0.02 * jax.random.normal(ks[1], (n, d, h), jnp.float32)).astype(jnp.bfloat16)
    w_down = (0.02 * jax.random.normal(ks[2], (n, h, d), jnp.float32)).astype(jnp.bfloat16)
    router = 0.02 * jax.random.normal(ks[3], (wide, d), jnp.float32)
    bias = 0.01 * jax.random.normal(ks[4], (wide,), jnp.float32)
    out = {}
    for rows in sizes:
        x = jax.random.normal(ks[5], (rows, d), jnp.float32).astype(jnp.bfloat16)
        for every in (False, True):
            def layer(x, w_gate, w_up, w_down):
                logits = jnp.einsum("nc,ec->ne", x.astype(jnp.float32), router)
                if model == "lfm2":
                    chosen, gates = moe.route_group_limited(logits, bias, top_k=k, eps=1e-6)
                else:
                    chosen, gates = moe.route_softmax_topk(logits, top_k=k)
                return moe.dropless_experts(x, chosen, gates, w_gate, w_up, w_down, (0, n), every_row=every,
                                            n_experts=wide, activation=act)
            fn = jax.jit(layer)
            y, counts = fn(x, w_gate, w_up, w_down); y.block_until_ready()
            times = []
            for _ in range(60):
                t0 = time.perf_counter()
                y, counts = fn(x, w_gate, w_up, w_down); y.block_until_ready()
                times.append(time.perf_counter() - t0)
            # twenty calls queued, one wait: the device's own pace
            t0 = time.perf_counter()
            for _ in range(20):
                y, counts = fn(x, w_gate, w_up, w_down)
            y.block_until_ready()
            queued = (time.perf_counter() - t0) / 20
            name = "%d_rows_%s" % (rows, "every_row" if every else "grouped")
            out[name] = {"median_ms": 1e3 * float(np.median(times)), "queued_ms": 1e3 * queued,
                         "hit": int(counts[2]), "rule": bool(moe.few_rows_hit_most(rows, k, wide))}
            print(name, out[name], flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    name = "expert_forms.json" if model == "lfm2" \
        else "expert_forms_%s.json" % model
    with open(os.path.join("chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:2])
