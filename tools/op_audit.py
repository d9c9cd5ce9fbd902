"""Machine audit of the operator surface vs the reference registrations.

Scans every operator registration in the reference tree
(`MXNET_REGISTER_OP_PROPERTY`, `NNVM_REGISTER_OP`,
`MXNET_OPERATOR_REGISTER_*` invocations under ``<ref>/src/operator/``,
macro-definition lines excluded) and diffs the public names against
``mxnet_tpu.ops.registry`` (``OP_REGISTRY`` + its alias map) plus the
documented structural-equivalence lists below.

Exit 0 iff every reference op is registered, aliased, or explicitly
accounted for.  Run:  python tools/op_audit.py [--ref PATH] [-v]
"""

import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# multisample macro: MXNET_OPERATOR_REGISTER_SAMPLING[12](distr, ...) expands
# to NNVM_REGISTER_OP(sample_##distr)
_SAMPLING_PREFIX = "sample_"

# reference ops whose job is done by a different mechanism here, each with
# the reason on record (audited, not forgotten)
STRUCTURAL = {
    "_CrossDeviceCopy": "device placement is GSPMD sharding / executor "
                        "_place; no graph copy node (executor.py)",
    "_Native": "legacy python-callback host -> mxnet_tpu/operator.py "
               "NumpyOp/CustomOp",
    "_NDArray": "legacy python-callback host -> mxnet_tpu/operator.py",
    "_broadcast_backward": "gradient node; jax.vjp derives backwards",
    "_identity_with_attr_like_rhs": "autodiff-internal identity; jax.vjp",
    "_grad_add": "gradient accumulation; XLA add_any via jax.vjp",
    "CuDNNBatchNorm": "cudnn fast path of BatchNorm; XLA lowers BatchNorm",
    "CaffeOp": "caffe plugin omitted (no caffe in env; COVERAGE.md)",
    "CaffeLoss": "caffe plugin omitted (no caffe in env; COVERAGE.md)",
    "_imdecode": "image.imdecode (PIL-based; image.py)",
    "_crop_assign": "registered as _slice_assign alias",
}

_MACRO_RE = re.compile(
    r"(?:MXNET_REGISTER_OP_PROPERTY|NNVM_REGISTER_OP|"
    r"MXNET_OPERATOR_REGISTER_[A-Z_0-9]+)\s*\(\s*([A-Za-z0-9_]+)")


def reference_ops(ref):
    srcdir = os.path.join(ref, "src", "operator")
    names = set()
    for dirpath, _dirs, files in os.walk(srcdir):
        for fn in files:
            if not fn.endswith((".cc", ".cu")):
                continue
            text = open(os.path.join(dirpath, fn), errors="replace").read()
            # drop macro DEFINITIONS (keep invocations): a #define line and
            # its continuation lines
            kept, skipping = [], False
            for line in text.splitlines():
                if skipping or line.lstrip().startswith("#define"):
                    skipping = line.rstrip().endswith("\\")
                    continue
                kept.append(line)
            text = "\n".join(kept)
            for m in _MACRO_RE.finditer(text):
                name = m.group(1)
                if "SAMPLING" in text[max(0, m.start() - 40):m.start()] \
                        or re.search(r"MXNET_OPERATOR_REGISTER_SAMPLING\d*"
                                     r"\s*\(\s*" + re.escape(name), text):
                    name = _SAMPLING_PREFIX + name
                names.add(name)
    return names


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ref", default="/root/reference")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args()

    # static audit, no device work: force the CPU platform so the audit
    # never takes the chip from a process that needs it
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    from mxnet_tpu.ops import registry

    ours = set(registry.OP_REGISTRY) | set(registry._ALIAS)
    ref = reference_ops(args.ref)
    backward = {n for n in ref if n.startswith("_backward_")}
    ref_public = ref - backward

    missing, structural = [], 0
    for name in sorted(ref_public):
        if name in ours:
            continue
        if name in STRUCTURAL:
            structural += 1
            if args.verbose:
                print("structural: %-30s %s" % (name, STRUCTURAL[name]))
        else:
            missing.append(name)

    beyond = sorted(n for n in set(registry.OP_REGISTRY) if n not in ref)
    print("reference public ops : %d  (+%d _backward_ nodes subsumed by "
          "jax.vjp)" % (len(ref_public), len(backward)))
    print("registry ops          : %d  (+%d aliases)"
          % (len(registry.OP_REGISTRY), len(registry._ALIAS)))
    print("covered by name/alias : %d" % (len(ref_public) - structural
                                          - len(missing)))
    print("structural equivalents: %d (documented in tools/op_audit.py)"
          % structural)
    print("beyond-reference ops  : %d" % len(beyond))
    if args.verbose:
        print("  " + " ".join(beyond))
    if missing:
        print("MISSING (%d):" % len(missing))
        for n in missing:
            print("  ", n)
        return 1
    print("OK: zero unexplained misses")
    return 0


if __name__ == "__main__":
    sys.exit(main())
