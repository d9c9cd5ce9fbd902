"""Both forms of the dropless expert layer (``parallel/moe.py``: every
held expert over every row, or grouped products over the sorted pairs)
at a decode step's shapes, on the chip it is started on: wall time of
one layer, the median of 60 calls and the pace of 20 queued calls, for
8 to 128 rows over 32 experts of LFM2's widths, 4 a token; with
``smallthinker`` as its argument, for 16 to 128 rows over the 16 held of
SmallThinker's 64 ReGLU experts (2560 x 768), 6 a token, of which a
quarter falls on the held ones (there the grouped form keeps
``moe.grouped_kept_rows`` of a call's sorted pairs a run: ``kept_rows``
and ``extra_runs`` in a row of the output).

    chiprun --chips 1 -- python3 tools/expert_forms.py [smallthinker]

Writes ``chiprun_out/expert_forms[_smallthinker].json``; says for each
shape what ``few_rows_hit_most`` would choose (PERF.md section 6: PR 39
against the compiler's 512-row tile, PR 40 against
``moe.grouped_tiling``'s, PR 41 at 48 rows of the new model).

With ``prefill`` as its argument, the grouped form alone at prefill
buckets of the five expert cells (a softmax router over the cell's
width, even but for its draw; then with a tenth of the rows forced onto
the held experts, an overflow): the pace of 20 queued calls, the rows a
run keeps, the runs beyond the first, the compile's seconds and
temporaries.  A second argument names another checkout to import the
package from (the parent, in a process of its own):

    chiprun --chips 1 -- python3 tools/expert_forms.py prefill [ROOT]

Appends to ``chiprun_out/expert_forms_prefill.jsonl`` (PERF.md section
6, PR 43)."""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the prefill buckets timed, by cell of ``tools/grouped_tiles.py``'s
# table (which has the widths); SmallThinker's experts are ReGLU
PREFILL = {
    "dots-vlm1-serve-chat64": (1024, 2048),
    "longcat-serve-agent64": (2048, 6144),
    "smallthinker-serve-mixed48": (4096, 12288),
    "qwen3next-serve-reason128": (4096,),
    "lfm2-serve-chat64": (1024,),
}


def prefill(root=HERE):
    """One prefill expert layer a (cell, bucket, forced share)."""
    from grouped_tiles import CELLS     # puts this checkout on the path
    sys.path.insert(0, os.path.abspath(root))   # and ``root`` before it
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe

    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(HERE, "chiprun_out",
                            "expert_forms_prefill.jsonl"), "a")
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    for cell, buckets in PREFILL.items():
        _, n, wide, k, d, h = CELLS[cell]
        act = "relu" if cell.startswith("smallthinker") else "silu"
        w_gate, w_up, w_down = (
            (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(
                jnp.bfloat16)
            for key, shape in zip(ks, ((n, d, h), (n, d, h), (n, h, d))))

        def layer(x, chosen, gates, w_gate, w_up, w_down):
            return moe.dropless_experts(x, chosen, gates, w_gate, w_up,
                                        w_down, (0, n), n_experts=wide,
                                        activation=act)
        for tokens in buckets:
            x = jax.random.normal(ks[3], (tokens, d), jnp.float32).astype(
                jnp.bfloat16)
            logits = jax.random.normal(ks[4], (tokens, wide), jnp.float32)
            for forced in (0.0, 0.1):
                skewed = logits.at[:int(forced * tokens), :n].add(30.0)
                chosen, gates = moe.route_softmax_topk(skewed, top_k=k)
                args = (x, chosen, gates, w_gate, w_up, w_down)
                t0 = time.perf_counter()
                fn = jax.jit(layer).lower(*args).compile()
                compile_s = time.perf_counter() - t0
                y, counts = fn(*args)
                y.block_until_ready()
                best = None
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(20):
                        y, counts = fn(*args)
                    y.block_until_ready()
                    took = (time.perf_counter() - t0) / 20
                    best = took if best is None else min(best, took)
                kept = getattr(moe, "grouped_kept_rows", None)
                row = {"root": os.path.abspath(root), "cell": cell,
                       "tokens": tokens, "forced": forced,
                       "queued_ms": 1e3 * best, "compile_s": compile_s,
                       "temp_mb": fn.memory_analysis().temp_size_in_bytes
                       / 2 ** 20, "counts": [int(c) for c in counts],
                       "kept_rows": kept and kept(tokens * k, n, wide,
                                                  d * 2)}
                print(json.dumps(row), flush=True)
                out.write(json.dumps(row) + "\n")
                out.flush()



def main(model="lfm2"):
    sys.path.insert(0, HERE)
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
            # what a run of the grouped form keeps of the call's pairs
            # (all of them where every expert is held), and the runs
            # the held pairs took beyond the first
            out[name] = {"median_ms": 1e3 * float(np.median(times)), "queued_ms": 1e3 * queued,
                         "hit": int(counts[2]), "rule": bool(moe.few_rows_hit_most(rows, k, wide)),
                         "kept_rows": moe.grouped_kept_rows(rows * k, n, wide, d * 2),
                         "extra_runs": int(counts[4])}
            print(name, out[name], flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    name = "expert_forms.json" if model == "lfm2" \
        else "expert_forms_%s.json" % model
    with open(os.path.join("chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    if sys.argv[1:2] == ["prefill"]:
        prefill(*sys.argv[2:3])
    else:
        main(*sys.argv[1:2])
