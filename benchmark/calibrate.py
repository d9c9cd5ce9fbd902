"""Read what the limits of ``correct`` are set from, on the chip, in one
process: for a training cell the numbers that sound runs of the program
give over many seeds, and the numbers its control gives — the plain
reference put in the program's place and computed in the nearest
precision below the configuration's (float8 products for a bfloat16
step).  No measured window: training's readings need none.

    python3 benchmark/calibrate.py --workload gpt2m-train --seeds 12 --control-seeds 3

For a serving cell the control needs the tokens a window served, so it
rides a run: ``--workload gpt2m-serve-chat --seeds 3 --seconds 45``
drives that many whole runs (new weights, server and callers per seed,
one process) and reads, beside each run's own number, the gap of the
token the bfloat16 reference puts first at every checked position.

Every line of ``chiprun_out/calibrate_<cell>.jsonl`` is one seed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, harness, run  # noqa: E402
from benchmark.spec import Spec  # noqa: E402


def rows_to_dict(rows):
    return {name.split("[")[0]: value for name, value, _ in rows}


def training(spec, cell, seeds, control_seeds, control_mode, out):
    train_driver = spec.driver("train")
    devices = harness.take_devices(cell["chips"])
    harness.enable_compile_cache()
    for i, seed in enumerate(seeds):
        job = run.Job(spec, cell, seed, 0, 0, devices)
        cfg = job.config
        model = spec.model(cfg["family"])
        train = cfg["deployment"]["train"]
        prog = train_driver.Program(job, model, cfg, train)
        batches, secs, program_side = train_driver.first_steps(
            prog, job.traffic["checked_steps"])
        weights = prog.weights()
        prog.free()
        reference, _ = spec.reference(cfg["name"])
        block = train.get("reference_block_rows")
        ref = compare.follow_steps(reference, cfg, weights, batches,
                                   train["optimizer"], block_rows=block)
        leaves = sorted(ref["grad_norms"])
        line = {"seed": seed, "step_seconds": secs,
                "leaves": leaves if i == 0 else None,
                "ref_grad_norms": [ref["grad_norms"][k] for k in leaves],
                "ref_change_norms": [ref["change_norms"][k] for k in leaves],
                "program_grad_norms": [program_side["grad_norms"][k]
                                       for k in leaves],
                "program_change_norms": [program_side["change_norms"][k]
                                         for k in leaves],
                "program": rows_to_dict(compare.training_rows(
                    program_side, ref, job.limits)),
                "losses": [program_side["losses"], ref["losses"]]}
        if i < control_seeds:
            ctl = compare.follow_steps(reference, cfg, weights, batches,
                                       train["optimizer"],
                                       mode=control_mode, block_rows=block)
            line["control"] = rows_to_dict(compare.training_rows(
                ctl, ref, job.limits))
            line["control_mode"] = control_mode
            line["control_grad_norms"] = [ctl["grad_norms"][k]
                                          for k in leaves]
            line["control_change_norms"] = [ctl["change_norms"][k]
                                            for k in leaves]
            # the fault the parameters' change is there to catch
            line["state_unchanged_gap"] = compare.worst_leaf_gap(
                {k: 0.0 for k in ref["change_norms"]},
                ref["change_norms"])[0]
        del weights
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()


def serving(spec, cell, seeds, seconds, control_mode, out):
    driver = spec.driver("serve-closed")
    devices = harness.take_devices(cell["chips"])
    harness.enable_compile_cache()
    original = driver.check_served

    for seed in seeds:
        line = {"seed": seed}

        def both(job, model, cfg, finished, traffic, stats):
            rows = original(job, model, cfg, finished, traffic, stats)
            line["program"] = rows_to_dict(rows)
            line["control"] = control_gap(job, model, cfg, finished,
                                          traffic, control_mode)
            line["control_mode"] = control_mode
            return rows

        driver.check_served = both
        job = run.Job(spec, cell, seed, seconds, 0, devices)
        result = driver.run(job)
        line["correct"] = result["correct"]
        line["tokens_per_s"] = result["readings"]["tokens"] \
            / result["readings"]["window_s"]
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()
    driver.check_served = original


def control_gap(job, model, cfg, finished, traffic, mode):
    """Over the same sample of requests, the lower-precision reference
    in the program's place: at each checked position, how far below the
    float32 reference's best lies the token it puts first (the widest),
    and how far its logits lie from the float32 reference's (the
    largest)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference, _ = job.spec.reference(cfg["name"])
    rng = np.random.RandomState((job.seed + 1) % (2 ** 32))
    sample = compare.sample_finished(finished, rng,
                                     traffic["checked_requests"])
    weights = model.make_weights(cfg, job.seed)
    width = cfg["n_positions"]
    exact = jax.jit(lambda p, t: reference.logits(cfg, p, t, "float32")[0])
    lower = jax.jit(lambda p, t: reference.logits(cfg, p, t, mode)[0])
    worst, worst_err, gaps_all = 0.0, 0.0, []
    for r in sample:
        seq = np.zeros((1, width), np.int32)
        toks = r["prompt"] + r["tokens"]
        seq[0, :len(toks)] = toks
        ref_logits = np.asarray(exact(weights, jnp.asarray(seq)))
        low_logits = np.asarray(lower(weights, jnp.asarray(seq)))
        at = np.arange(len(r["prompt"]) - 1,
                       len(r["prompt"]) - 1 + len(r["tokens"]))
        worst_err = max(worst_err, float(
            np.abs(low_logits[at] - ref_logits[at]).max()))
        gaps = compare.served_token_gaps(
            ref_logits, len(r["prompt"]),
            low_logits[at].argmax(axis=-1).tolist())
        gaps_all.extend(gaps.tolist())
        worst = max(worst, float(gaps.max()))
    return {"served_token_logit_gap": worst,
            "served_logit_abs_err": worst_err,
            "positions": len(gaps_all),
            "positions_off_best": int(sum(g > 0 for g in gaps_all))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2100000011)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--control-mode", default=None)
    ap.add_argument("--seconds", type=float, default=45)
    ns = ap.parse_args(argv)
    spec = Spec(ROOT)
    cell = spec.cell(ns.workload)
    kind = spec.traffic(cell["traffic"])["kind"]
    seeds = [ns.first_seed + 7919 * i for i in range(ns.seeds)]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        "calibrate_%s.jsonl" % ns.workload)
    with open(path, "a") as out:
        if kind == "train":
            training(spec, cell, seeds, ns.control_seeds,
                     ns.control_mode or "float8", out)
        else:
            serving(spec, cell, seeds, ns.seconds,
                    ns.control_mode or "bfloat16", out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
