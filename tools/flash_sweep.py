"""Sweep: device time of the two flash kernels over (shape, block, run,
chunk), and of the einsum body beside the kernel at the 1024 crossover.

What ``ops/attention.py``'s block rules rest on (PERF.md §6 holds the
tables this printed on the attached v5e).  Every case runs ``--iters``
times inside ONE profiler session, a marker op between cases; the times
are the device durations of the trace's events, never the host clock:
``fwd`` / ``bwd`` are the Pallas custom calls (told apart by their
outputs), ``all`` is every device op of the case (the einsum body, or
the backward's delta fusion too).

    python tools/flash_sweep.py                  # the LM training shape
    python tools/flash_sweep.py --set serve      # the serving prefills, forward only
    python tools/flash_sweep.py --set crossover  # D16: einsum body vs kernel, T 512..1024

A block set is ``block_q,block_k,rows,keys`` (``ops/attention.py:
_flash_fwd_pallas``: runs of ``rows`` query rows over chunks of ``keys``
keys; the backward takes that tile, runs of ``keys`` keys over chunks
of ``rows`` query rows);
``rule`` is what the kernels choose by themselves.
"""

import argparse
import glob
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import attention as att

MARK = (3, 5, 7)       # the marker op's shape: no model has it
# off the chip the kernels run under the interpreter, up to the trace's
# reading, which finds no device and stops: a rehearsal of the calls
OFF_CHIP = jax.default_backend() != "tpu"

# (batch, heads, T, D, Dv)
TRAIN = [(8, 16, 1024, 64, 64)]
SERVE = [(1, 128, 2048, 192, 128), (1, 128, 4096, 192, 128),
         (1, 64, 2048, 192, 128), (1, 64, 6144, 192, 128),
         (1, 16, 4096, 256, 256)]
CROSSOVER = [(b, 16, t, 64, 64) for b in (8, 1) for t in (512, 768, 1024)]
GRID = {"train": ["1024,1024,%d,%d" % (r, c) for r in (128, 256, 512)
                  for c in (128, 256, 512)] + ["1024,1024,1024,1024"],
        "serve": ["1024,1024,256,256", "1024,1024,256,512",
                  "1024,1024,512,256", "1024,1024,512,512",
                  "1024,2048,256,256", "1024,2048,512,512",
                  "512,1024,256,256", "2048,2048,256,256"],
        "crossover": []}


def _inputs(shape, seed=0):
    b, h, t, d, dv = shape
    rng = np.random.default_rng(seed)

    def mk(width):
        return jnp.asarray(rng.standard_normal((b, h, t, width)),
                           jnp.float32).astype(jnp.bfloat16)

    return mk(d), mk(d), mk(dv), mk(dv)


def _cases(shape, blocks, backward, einsum):
    """``[(label, jitted fn, args)]`` of one shape: forward with lse
    (and the backward) per block set, or the einsum body."""
    q, k, v, do = _inputs(shape)
    scale = 1.0 / float(shape[3]) ** 0.5
    out = []
    if einsum:
        fn = jax.jit(lambda q, k, v: att._attention_fwd_ref(
            q, k, v, True, scale, return_lse=True))
        out.append(("einsum", fn, (q, k, v)))
    o, lse = jax.jit(lambda q, k, v: att._attention_fwd_ref(
        q, k, v, True, scale, return_lse=True))(q, k, v) \
        if backward else (None, None)
    for text in blocks:
        kw, bkw = {}, {}
        if text != "rule":
            bq, bk, run, chunk = (int(x) for x in text.split(","))
            kw = {"blocks": (bq, bk, run, chunk)}
            bkw = {"blocks": (bq, min(bk, 1024), (run, chunk))}
        fwd = jax.jit(lambda q, k, v, kw=kw: att._flash_fwd_pallas(
            q, k, v, True, scale, return_lse=True, interpret=OFF_CHIP,
            **kw))
        out.append((text + " fwd", fwd, (q, k, v)))
        if backward:
            bwd = jax.jit(lambda q, k, v, o, lse, do, kw=bkw:
                          att._flash_bwd_pallas(q, k, v, o, lse, do, True,
                                                scale, interpret=OFF_CHIP,
                                                **kw))
            out.append((text + " bwd", bwd, (q, k, v, o, lse, do)))
    return out


def _kind(name):
    """fwd / bwd for a Pallas custom call's event, by its outputs (with
    lse: a bf16 and an f32; dq, dk and dv: three bf16)."""
    head = re.match(r"%?[\w.\-]+ = ", name)
    shape, call, _ = name[head.end() if head else 0:].partition(
        " custom-call(")
    if not call:
        return None
    return "fwd" if "f32[" in shape else "bwd"


def _device_events(logdir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    return sorted((int(e.start_ns), e.name,
                                   int(e.duration_ns)) for e in line.events)
    raise SystemExit("no TPU device plane in the trace: this sweep "
                     "measures on the chip and nowhere else")


def run(cases, iters, kind=_kind):
    """Device microseconds a call of every case: ``{label: {kind: us}}``,
    ``kind(name)`` a device event's (None: counted under ``all`` alone)."""
    mark = jax.jit(lambda x: x + 1.0)
    flag = jnp.zeros(MARK, jnp.float32)
    sound = []
    for case in cases:              # compile and warm outside the trace
        try:
            jax.block_until_ready(case[1](*case[2]))
            sound.append(case)
        except Exception as exc:    # noqa: BLE001 — the compiler's refusal
            print("refused: %s: %s" % (case[0], str(exc)[:160].replace(
                "\n", " ")), flush=True)
    cases = sound
    jax.block_until_ready(mark(flag))
    logdir = tempfile.mkdtemp(prefix="flash_sweep_")
    with jax.profiler.trace(logdir):
        for _, fn, args in cases:
            jax.block_until_ready(mark(flag))
            for _ in range(iters):
                res = fn(*args)
            jax.block_until_ready(res)
        jax.block_until_ready(mark(flag))
    groups, shape = [], "f32[%d,%d,%d]" % MARK
    events = _device_events(logdir)
    for _, name, dur in events:
        if shape in name:
            groups.append({})
        elif groups:
            for key in (kind(name), "all"):
                if key:
                    groups[-1][key] = groups[-1].get(key, 0) + dur
    if len(groups) != len(cases) + 1:
        raise SystemExit("%d markers for %d cases; the trace's names: %s" % (
            len(groups), len(cases), sorted({n for _, n, _ in events})[:20]))
    return {label: {k: ns / iters / 1e3 for k, ns in got.items()}
            for (label, _, _), got in zip(cases, groups)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--set", default="train",
                    choices=["train", "serve", "crossover"])
    ap.add_argument("--blocks", nargs="*", default=None,
                    help="block sets (default: the grid), 'rule' for "
                         "the kernels' own choice")
    ap.add_argument("--shape", default=None,
                    help="one shape b,h,T,D,Dv in place of the set's")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    print("device: %s %s; jax %s" % (dev.platform, dev.device_kind,
                                     jax.__version__))
    blocks = ["rule"] + (GRID[args.set] if args.blocks is None
                         else args.blocks)
    shapes = {"train": TRAIN, "serve": SERVE,
              "crossover": CROSSOVER}[args.set]
    if args.shape:
        shapes = [tuple(int(x) for x in args.shape.split(","))]
    print("%-28s %-22s %9s %9s %9s  walked" % (
        "shape (b,h,T,D,Dv) causal", "blocks", "fwd us", "bwd us",
        "all us"))
    for shape in shapes:
        cases = _cases(shape, blocks, backward=args.set == "train",
                       einsum=args.set == "crossover")
        got = run(cases, args.iters)
        for text in (["einsum"] if args.set == "crossover" else []) + blocks:
            row = dict(got.get(text, {}))
            for part in ("fwd", "bwd"):
                for k, us in got.get(text + " " + part, {}).items():
                    row[k] = row.get(k, 0) + us
            share = ""
            if text not in ("rule", "einsum"):
                _, _, run_, chunk = (int(x) for x in text.split(","))
                walked, masked, pairs = att.causal_walk(
                    shape[2], shape[2], min(run_, shape[2]),
                    min(chunk, shape[2]))
                share = "%d/%d, %d masked" % (walked, pairs, masked)
            if row:
                print("%-28s %-22s %9s %9s %9.1f  %s" % (
                    ",".join(str(x) for x in shape), text,
                    *("%.1f" % row[k] if k in row else "-"
                      for k in ("fwd", "bwd")), row["all"], share),
                    flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
