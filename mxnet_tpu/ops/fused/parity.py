"""Parity harness: every kernel against the reference body it replaces.

A kernel ships only with a registered comparison against the plain
body its function falls back to: that is how it is falsified off the
chip, before a compile for the described chip
(``tests/test_chip_compile.py``) and a chip run say anything about it.

* ``register_parity(name, builder, grid, parity=...)`` — declares a
  kernel by its name.  ``builder(case)`` returns ``(reference_fn,
  kernel_fn, args)`` for one grid case (optionally ``(..., (rtol,
  atol))`` to override the tolerance class, e.g. low-precision
  inputs).  ``kernel_fn`` is the kernel itself, not the public
  function that chooses it: off the chip it runs under
  ``interpret=True``.  Both callables run under ``jax.jit`` because
  every call site is jitted — bitwise parity is pinned under the
  production condition.  (Eager XLA:CPU takes different fusion/FMA
  decisions than jit and differs from BOTH jitted paths by a few ULP,
  so eager-vs-jit is not the contract anywhere in this repo.)
* ``parity`` is the comparison class: ``bitwise`` asserts byte-equal
  outputs (the PR-14 decode-parity precedent — dtype, shape, and every
  bit), ``tolerance`` asserts a dtype-classed ``allclose`` (reduction
  reorder allowed, e.g. an online softmax).
* ``pallas`` says whether the kernel is a Pallas kernel a TPU compiles:
  ``tests/test_chip_compile.py`` compiles each of those for the
  described chip at its widest grid case.

Grid cases deliberately include ragged tails (sequence lengths and
feature dims that are not multiples of any block size) because padding
bugs live there.

CLI: ``JAX_PLATFORMS=cpu python -m mxnet_tpu.ops.fused.parity`` (the
``make kernels`` lane) prints one row per (kernel, case) and exits
nonzero on any failure.  ``MXNET_TPU_OPS_PARITY_GRID=quick`` trims each
kernel to its first two grid cases (the bench smoke setting); ``full``
(default) runs everything.
"""

from __future__ import annotations

import os
from typing import Dict

import numpy as np

__all__ = ["register_parity", "parity_registrations", "run_parity",
           "case_rng", "main"]

#: kernel name -> _ParityReg, in registration order.
_PARITY: Dict[str, "_ParityReg"] = {}

#: tolerance class per result dtype name: (rtol, atol), compared in fp32.
_TOL = {
    "float32": (2e-5, 2e-5),
    "float16": (2e-3, 2e-3),
    "bfloat16": (2e-2, 2e-2),
}


class _ParityReg:
    __slots__ = ("name", "builder", "grid", "parity", "pallas")

    def __init__(self, name, builder, grid, parity, pallas):
        self.name = name
        self.builder = builder
        self.grid = tuple(grid)
        self.parity = parity
        self.pallas = pallas


def register_parity(name, builder=None, grid=(), parity="bitwise",
                    pallas=True):
    """Declare the kernel ``name`` and its comparison.

    ``builder(case)`` -> ``(reference_fn, kernel_fn, args)``; each is
    called as ``fn(*args)`` and may return an array or a tuple of
    arrays.  ``grid`` is the tuple of case descriptors (opaque to the
    harness — printed in reports, passed to ``builder``).  Usable
    directly or as a decorator on the builder.
    """
    if parity not in ("bitwise", "tolerance"):
        raise ValueError("register_parity(%r): parity must be 'bitwise' "
                         "or 'tolerance', got %r" % (name, parity))

    def deco(f):
        if not grid:
            raise ValueError(
                "register_parity(%r): empty grid — parity needs at "
                "least one case" % (name,))
        _PARITY[name] = _ParityReg(name, f, grid, parity, pallas)
        return f

    if builder is not None:
        return deco(builder)
    return deco


def case_rng(case):
    """A numpy generator seeded by the grid case: a builder's data."""
    import zlib

    return np.random.default_rng(zlib.adler32(repr(case).encode()))


def parity_registrations():
    """Snapshot {kernel name: registration} (``builder``, ``grid``,
    ``parity``, ``pallas``) for tests and tooling."""
    return dict(_PARITY)


def _leaves(out):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(out)]


def _compare(parity, ref, got, tol=None):
    """One case's verdict: (ok, detail str)."""
    ref_leaves, got_leaves = _leaves(ref), _leaves(got)
    if len(ref_leaves) != len(got_leaves):
        return False, "output arity %d != reference %d" % (
            len(got_leaves), len(ref_leaves))
    for i, (r, g) in enumerate(zip(ref_leaves, got_leaves)):
        if r.shape != g.shape:
            return False, "out[%d] shape %s != reference %s" % (
                i, g.shape, r.shape)
        if r.dtype != g.dtype:
            return False, "out[%d] dtype %s != reference %s" % (
                i, g.dtype, r.dtype)
        if parity == "bitwise":
            if g.tobytes() != r.tobytes():
                delta = np.abs(g.astype(np.float64)
                               - r.astype(np.float64))
                return False, "out[%d] bits differ (max abs err %.3e)" \
                    % (i, float(delta.max()))
        else:
            rtol, atol = tol or _TOL.get(str(r.dtype), _TOL["float32"])
            rf = r.astype(np.float32)
            gf = g.astype(np.float32)
            if not np.allclose(rf, gf, rtol=rtol, atol=atol):
                delta = np.abs(rf.astype(np.float64)
                               - gf.astype(np.float64))
                return False, "out[%d] exceeds tol(%g, %g): max abs " \
                    "err %.3e" % (i, rtol, atol, float(delta.max()))
    return True, ""


def run_parity(quick=None):
    """Run the whole grid; returns a list of result rows.

    Each row: ``{"kernel", "case", "parity", "ok", "detail"}``.
    ``quick`` trims each grid to 2 cases; default comes from
    ``MXNET_TPU_OPS_PARITY_GRID``.
    """
    import jax

    if quick is None:
        quick = os.environ.get(
            "MXNET_TPU_OPS_PARITY_GRID", "full").strip().lower() == "quick"
    rows = []
    for reg in list(_PARITY.values()):
        for case in (reg.grid[:2] if quick else reg.grid):
            row = {"kernel": reg.name, "case": repr(case),
                   "parity": reg.parity}
            try:
                built = reg.builder(case)
                tol = built[3] if len(built) > 3 else None
                reference_fn, kernel_fn, args = built[:3]
                ok, detail = _compare(
                    reg.parity, jax.jit(reference_fn)(*args),
                    jax.jit(kernel_fn)(*args), tol=tol)
            except Exception as exc:  # noqa: BLE001 — reported as a row
                ok, detail = False, "%s: %s" % (type(exc).__name__,
                                                str(exc)[:200])
            row["ok"] = ok
            row["detail"] = detail
            rows.append(row)
    return rows


def main(argv=None):
    """CLI entry: print the parity table, exit 1 on any failure."""
    import argparse

    ap = argparse.ArgumentParser(
        description="kernel parity harness (reference body vs kernel)")
    ap.add_argument("--quick", action="store_true",
                    help="2 cases per kernel (bench smoke setting)")
    ns = ap.parse_args(argv)
    rows = run_parity(quick=True if ns.quick else None)
    bad = [r for r in rows if not r["ok"]]
    for r in rows:
        mark = "ok " if r["ok"] else "FAIL"
        line = "%s  %-28s %-9s %s" % (
            mark, r["kernel"], r["parity"], r["case"])
        if r["detail"]:
            line += "  -- " + r["detail"]
        print(line)
    print("parity: %d cases, %d failed, %d kernels" % (
        len(rows), len(bad), len({r["kernel"] for r in rows})))
    return 1 if bad else 0


if __name__ == "__main__":  # pragma: no cover - exercised via make kernels
    # ``python -m`` executes this file as a SECOND module instance with
    # its own empty registry; delegate to the canonical one, which
    # importing the package populates (a kernel's module registers it).
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.ops.fused import parity as _canonical

    raise SystemExit(_canonical.main())
