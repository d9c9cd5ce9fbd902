"""The comparison that decides ``correct``.

Training: the reference follows the program's first optimizer steps on
the same weights and batches, and three kinds of number are compared:
each step's loss, the norm of the first gradient as the optimizer got it
(worked out from the program's momentum after one step), and the norm of
the parameters' change after the steps.  Norms are compared leaf by
leaf, as the gap between the program's norm and the reference's over the
reference's norm of that leaf or of the median leaf, whichever is
larger; the worst leaf is the number.

Serving: over a sample of the requests the window finished, the widest
gap by which a served (greedy) token's reference logit lies below the
reference's best at that position.

Every number is printed beside its limit (``report``).  The limits are
data: ``limits/<cell>.json``.
"""

import statistics

import numpy as np


def report(config, reference_path, rows):
    """Print each number beside its limit; return whether all hold."""
    ok = True
    for name, value, limit in rows:
        holds = bool(np.isfinite(value)) and value <= limit
        ok = ok and holds
        print("compare config=%s reference=%s %s=%.6g limit=%.6g %s"
              % (config, reference_path, name, value, limit,
                 "ok" if holds else "OVER"), flush=True)
    return ok


# ----------------------------------------------------------------------
# training


def first_gradient_norms(moms, make_weights0, key, opt):
    """Norm per leaf of the gradient of the mean loss as the optimizer
    got it in the first step, from the momentum after that step:
    ``m1 = -lr * (g + wd * w0)`` with the momentum zero before it.  The
    starting weights are made again from the key inside the program, so
    no second copy of them is held on the device."""
    import jax
    import jax.numpy as jnp

    lr, wd = opt["learning_rate"], opt.get("wd", 0.0)

    def norms(moms, key):
        w0 = make_weights0(key) if wd else None
        out = {}
        for k, m in moms.items():
            g = -m.astype(jnp.float32) / lr
            if wd:
                g = g - wd * w0[k].astype(jnp.float32)
            out[k] = jnp.sqrt(jnp.sum(jnp.square(g)))
        return out

    return jax.jit(norms)(moms, key)


def change_norms(params, make_weights0, key):
    """Norm per leaf of ``params`` minus the weights the key makes."""
    import jax
    import jax.numpy as jnp

    def norms(params, key):
        w0 = make_weights0(key)
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            p.astype(jnp.float32) - w0[k].astype(jnp.float32))))
            for k, p in params.items()}

    return jax.jit(norms)(params, key)


def leaf_gaps(program, reference):
    """Per leaf: |program - reference| / max(reference of that leaf,
    reference of the median leaf)."""
    ref = {k: float(v) for k, v in reference.items()}
    floor = statistics.median(ref.values())
    out = {}
    for k, r in ref.items():
        scale = max(r, floor)
        gap = abs(float(program[k]) - r)
        out[k] = gap / scale if scale > 0 else gap
        if not np.isfinite(out[k]):
            out[k] = float("inf")
    return out


def worst_leaf_gap(program, reference, suffix=""):
    """The largest of :func:`leaf_gaps` over the leaves whose name ends
    in ``suffix``, and the leaf it is at."""
    gaps = {k: g for k, g in leaf_gaps(program, reference).items()
            if k.endswith(suffix)}
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def median_leaf_gap(program, reference):
    return statistics.median(leaf_gaps(program, reference).values())


def follow_steps(reference, cfg, weights, batches, opt, mode="float32",
                 block_rows=None):
    """The reference's side of a training comparison: ``len(batches)``
    steps of SGD with momentum from ``weights``, the loss summed over
    blocks of ``block_rows`` rows (None: the batch whole).  Returns the
    mean loss of each step, the norm per leaf of the first step's
    gradient of the mean loss, and the norm per leaf of the change of
    the parameters."""
    import jax
    import jax.numpy as jnp

    lr, mu = opt["learning_rate"], opt["momentum"]
    wd = opt.get("wd", 0.0)

    grad_block = jax.jit(jax.value_and_grad(
        lambda p, b: reference.loss_sum(cfg, p, b, mode)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))

    def update(params, moms, grads, units):
        new_p, new_m = {}, {}
        for k in params:
            g = grads[k].astype(jnp.float32) / units
            new_m[k] = mu * moms[k] - lr * (g + wd * params[k])
            new_p[k] = params[k] + new_m[k]
        return new_p, new_m

    update = jax.jit(update, donate_argnums=(0, 1, 2))
    scale_norms = jax.jit(lambda g, units: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) / units
        for k, v in g.items()})

    params = {k: jnp.array(v, jnp.float32, copy=True)
              for k, v in weights.items()}
    moms = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, grad_norms = [], None
    for batch in batches:
        rows = next(iter(batch.values())).shape[0]
        step = block_rows or rows
        total, grads = 0.0, None
        for at in range(0, rows, step):
            block = {k: jnp.asarray(v[at:at + step])
                     for k, v in batch.items()}
            loss, g = grad_block(params, block)
            total += float(loss)
            grads = g if grads is None else add(grads, g)
        units = float(reference.loss_units(cfg, batch))
        losses.append(total / units)
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in
                          scale_norms(grads, units).items()}
        params, moms = update(params, moms, grads, units)
    deltas = jax.jit(lambda p, w0: {
        k: jnp.sqrt(jnp.sum(jnp.square(p[k] - w0[k].astype(jnp.float32))))
        for k in p})(params, weights)
    deltas = {k: float(v) for k, v in deltas.items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": deltas}


def training_rows(program, ref, limits):
    """(name, value, limit) of a training comparison.  ``program`` and
    ``ref`` hold ``losses``, ``grad_norms`` and ``change_norms``.  The
    worst-leaf numbers run over the leaves whose name ends in the
    limits' ``worst_leaf_suffix`` (all, if it is not given); a
    ``first_grad_norm_median_gap`` limit adds the median leaf's gap
    over all leaves."""
    suffix = limits.get("worst_leaf_suffix", "")
    rows = []
    for i, (a, b) in enumerate(zip(program["losses"], ref["losses"])):
        rows.append(("loss_step%d_rel_gap" % i, abs(a - b) / abs(b),
                     limits["loss_rel_gap"]))
    gap, leaf = worst_leaf_gap(program["grad_norms"], ref["grad_norms"],
                               suffix)
    rows.append(("first_grad_norm_worst_leaf_gap[%s]" % leaf, gap,
                 limits["first_grad_norm_gap"]))
    if "first_grad_norm_median_gap" in limits:
        rows.append(("first_grad_norm_median_leaf_gap",
                     median_leaf_gap(program["grad_norms"],
                                     ref["grad_norms"]),
                     limits["first_grad_norm_median_gap"]))
    gap, leaf = worst_leaf_gap(program["change_norms"], ref["change_norms"],
                               suffix)
    rows.append(("param_change_norm_worst_leaf_gap[%s]" % leaf, gap,
                 limits["param_change_norm_gap"]))
    return rows


# ----------------------------------------------------------------------
# serving


def served_token_gaps(ref_logits, prompt_len, served):
    """For each served token, how far its reference logit lies below the
    reference's best at the position that produced it.  ``ref_logits``
    is ``[T, V]`` over prompt + served tokens."""
    ref_logits = np.asarray(ref_logits)
    at = np.arange(prompt_len - 1, prompt_len - 1 + len(served))
    rows = ref_logits[at]
    return rows.max(axis=1) - rows[np.arange(len(served)),
                                   np.asarray(served)]


def sample_finished(finished, rng, count):
    """``count`` of the finished requests, the longest always among
    them, the rest drawn by ``rng``."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i]["prompt"])
                                   + len(finished[i]["tokens"])))
    rest = order[1:]
    rng.shuffle(rest)
    return [finished[i] for i in [order[0]] + rest[:max(0, count - 1)]]
