"""Post-training quantization passes (model-level PTQ).

The 2017 reference ships the quantize/dequantize contrib ops
(``src/operator/contrib/quantize.cc``) but no model pass; its later
versions grew ``contrib.quantization.quantize_model`` (BN fold +
calibrate + graph rewrite).  This module is that subsystem, TPU-native:
eligible Convolution/FullyConnected nodes are rewritten to the int8 MXU
compute ops (``_contrib_quantized_conv`` / ``_contrib_quantized_fully_
connected``), weights are quantized offline, activation ranges come
from a calibration pass, and BatchNorm folds into the preceding conv
first (inference-only, the standard PTQ step).

Calibration is SYMMETRIC (min = -max): the quantized compute ops'
zero-point cross terms vanish, leaving the pure int8xint8->int32 MXU
path (docs/PERF.md "int8 on the MXU").

    from mxnet_tpu.contrib import quantization as q
    qsym, qargs, qauxs = q.quantize_model(
        sym, arg_params, aux_params, ctx=mx.tpu(),
        calib_data=iter_of_batches, excluded_sym_names=["conv0"])

Driven end-to-end (train -> PTQ -> accuracy gate -> chip throughput) by
``examples/quantize_resnet.py``.
"""

from __future__ import annotations

import json

import numpy as _np

from ..base import MXNetError

__all__ = ["fold_bn", "quantize_symbol", "calibrate_ranges",
           "quantize_model", "quantize_aware_symbol", "quantize_model_qat",
           "quantize_weight_int8"]


def quantize_weight_int8(w):
    """Symmetric max-abs int8/127 grid for ONE weight array.

    The same grid :func:`quantize_symbol` deploys, exposed as an
    array-level helper so other subsystems (the generation lane's
    opt-in int8 vocab head) stage int8 weights without a graph rewrite.
    Returns ``(w_q int8, scale fp32)`` with ``w ≈ w_q * scale``.
    """
    w = _np.asarray(w)
    wmax = float(_np.abs(w).max()) or 1e-8
    wq = _np.clip(_np.round(w / wmax * 127.0), -127, 127).astype(_np.int8)
    return wq, _np.float32(wmax / 127.0)


# ---------------------------------------------------------------------
# JSON graph surgery helpers: object-linked nodes + topo re-emit
# ---------------------------------------------------------------------

def _load_graph(sym):
    g = json.loads(sym.tojson())
    nodes = []
    for jn in g["nodes"]:
        nodes.append({
            "op": jn["op"], "name": jn["name"],
            "attr": dict(jn.get("attr", {})),
            "inputs": [],  # filled below with (node, out_idx)
        })
    for node, jn in zip(nodes, g["nodes"]):
        node["inputs"] = [(nodes[e[0]], e[1]) for e in jn["inputs"]]
    heads = [(nodes[h[0]], h[1]) for h in g["heads"]]
    return nodes, heads


def _emit_graph(heads):
    """Topo-sort reachable nodes from heads and rebuild a Symbol —
    orphans (folded BN subtrees, replaced fp32 weights) drop out here."""
    from .. import symbol as _sym

    order, seen = [], set()

    def visit(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for src, _ in node["inputs"]:
            visit(src)
        order.append(node)

    for h, _ in heads:
        visit(h)
    idx = {id(n): i for i, n in enumerate(order)}
    jnodes = []
    for n in order:
        jn = {"op": n["op"], "name": n["name"],
              "inputs": [[idx[id(s)], oi, 0] for s, oi in n["inputs"]]}
        if n["attr"]:
            jn["attr"] = n["attr"]
        jnodes.append(jn)
    g = {"nodes": jnodes,
         "arg_nodes": [i for i, n in enumerate(order) if n["op"] == "null"],
         "node_row_ptr": list(range(len(order) + 1)),
         "heads": [[idx[id(h)], oi, 0] for h, oi in heads],
         "attrs": {"mxnet_version": ["int", 905]}}
    return _sym.load_json(json.dumps(g))


def _consumers(nodes):
    out = {id(n): [] for n in nodes}
    for n in nodes:
        for src, _ in n["inputs"]:
            out[id(src)].append(n)
    return out


def _null(name, shape=None, dtype=None):
    """Param node with shape/dtype hints so the rewritten graph still
    shape-infers without an explicit type_dict (the quantized compute
    ops have no backward shape rules, unlike Convolution/FC)."""
    attr = {}
    if shape is not None:
        attr["__shape__"] = str(tuple(shape))
    if dtype is not None:
        attr["__dtype__"] = str(_np.dtype(dtype))
    return {"op": "null", "name": name, "attr": attr, "inputs": []}


def _rewire(nodes, heads, old, new):
    """Point every consumer of ``old``'s output 0 (and heads) at ``new``
    — a node (its output 0) or an explicit ``(node, out_idx)`` entry."""
    entry = new if isinstance(new, tuple) else (new, 0)
    for n in nodes:
        n["inputs"] = [(entry if s is old and oi == 0 else (s, oi))
                       for s, oi in n["inputs"]]
    return [(entry if h is old and oi == 0 else (h, oi))
            for h, oi in heads]


# ---------------------------------------------------------------------
# pass 1: fold BatchNorm into the preceding Convolution/FullyConnected
# ---------------------------------------------------------------------

def fold_bn(sym, arg_params, aux_params):
    """Inference-only BN fold: for every ``conv/FC -> BatchNorm`` pair
    where the conv output feeds only the BN, scale the conv weight by
    ``gamma/sqrt(var+eps)`` per out-channel and fold mean/beta into a
    bias; the BN node (and its four params) disappear.

    Returns ``(folded_sym, folded_args, remaining_auxs)``.  Weight
    layouts OIHW/OHWI/FC all carry out-channels on axis 0, so one
    reshape rule covers them.
    """
    nodes, heads = _load_graph(sym)
    cons = _consumers(nodes)
    args = dict(arg_params)
    auxs = dict(aux_params)

    for bn in [n for n in nodes if n["op"] == "BatchNorm"]:
        src, oi = bn["inputs"][0]
        if oi != 0 or src["op"] not in ("Convolution", "FullyConnected"):
            continue
        if len(cons[id(src)]) != 1:
            continue  # conv output also used elsewhere: unsafe to fold
        gname, bname = bn["inputs"][1][0]["name"], bn["inputs"][2][0]["name"]
        mname, vname = bn["inputs"][3][0]["name"], bn["inputs"][4][0]["name"]
        eps = float(bn["attr"].get("eps", 1e-3))
        fix_gamma = bn["attr"].get("fix_gamma", "True") == "True"
        gamma = (_np.ones_like(_asnp(auxs[mname])) if fix_gamma
                 else _asnp(args[gname]))
        beta = _asnp(args[bname])
        mean, var = _asnp(auxs[mname]), _asnp(auxs[vname])
        inv = gamma / _np.sqrt(var + eps)

        wname = src["inputs"][1][0]["name"]
        w = _asnp(args[wname])
        args[wname] = w * inv.reshape((-1,) + (1,) * (w.ndim - 1))
        had_bias = src["attr"].get("no_bias", "False") == "False" \
            and len(src["inputs"]) > 2
        old_b = _asnp(args[src["inputs"][2][0]["name"]]) if had_bias else 0.0
        new_b = beta + (old_b - mean) * inv
        if had_bias:
            bias_node = src["inputs"][2][0]
        else:
            bias_node = _null(src["name"] + "_bias")
            nodes.append(bias_node)
            src["inputs"] = src["inputs"] + [(bias_node, 0)]
            src["attr"]["no_bias"] = "False"
        args[bias_node["name"]] = new_b.astype(w.dtype)
        for nm in (gname, bname):
            args.pop(nm, None)
        for nm in (mname, vname):
            auxs.pop(nm, None)
        heads = _rewire(nodes, heads, bn, src)

    return _emit_graph(heads), _wrap_nd(args), _wrap_nd(auxs)


# ---------------------------------------------------------------------
# pass 2: calibration (symmetric max-abs over calibration batches)
# ---------------------------------------------------------------------

def _quantizable(node):
    a = node["attr"]
    if node["op"] == "Convolution":
        return (a.get("num_group", "1") == "1"
                and a.get("dilate") in (None, "(1, 1)", "(1,1)")
                and len(node["inputs"]) >= 2)
    return node["op"] == "FullyConnected" and len(node["inputs"]) >= 2


def calibrate_ranges(sym, arg_params, aux_params, calib_data, ctx,
                     excluded_sym_names=()):
    """Max-|x| of every quantizable node's DATA input over the
    calibration batches.  Returns {node_name: amax}.  ``calib_data``
    iterates dicts of input arrays (host numpy)."""
    from .. import symbol as _sym  # noqa: F401  (Symbol methods used)

    nodes, _ = _load_graph(sym)
    targets = [n for n in nodes if _quantizable(n)
               and n["name"] not in excluded_sym_names]
    internals = sym.get_internals()
    out_names = internals.list_outputs()

    def internal_name(src_name, oi):
        """Internal-output name for (node, output idx), matching the
        Symbol naming rules: '<n>_output' (single), '<n>_output<i>'
        (multi), '<n>_<outname>' (declared output names — resolved
        positionally among the node's outputs)."""
        cands = (["%s_output" % src_name] if oi == 0 else []) \
            + ["%s_output%d" % (src_name, oi)]
        for c in cands:
            if c in out_names:
                return c
        named = [n for n in out_names
                 if n.startswith(src_name + "_")]
        if len(named) > oi:
            return named[oi]
        raise MXNetError(
            "calibration: no internal output for %r[%d] (outputs: %s)"
            % (src_name, oi, named or "none"))

    # internal output feeding each target's data input ("data" variables
    # calibrate from the batch itself)
    want = {}
    for n in targets:
        src, oi = n["inputs"][0]
        if src["op"] == "null":
            want[n["name"]] = ("var", src["name"])
        else:
            want[n["name"]] = ("out", internal_name(src["name"], oi))

    pick = sorted({spec[1] for spec in want.values() if spec[0] == "out"})
    # reduce max|x| INSIDE the calibration graph: one compile, scalar
    # outputs.  (Eager per-output nd.max(nd.abs(...)) costs one jit
    # compile per distinct activation shape — ~50 compiles.)
    group = _sym.Group([_sym.max(_sym.abs(internals[p]))
                        for p in pick]) if pick else None

    amax = {k: 0.0 for k in want}
    batches = list(calib_data)
    if not batches:
        raise MXNetError("calibration needs at least one batch")
    exe = None
    for batch in batches:
        if group is not None:
            if exe is None:
                shapes = {k: tuple(v.shape) for k, v in batch.items()}
                exe = group.simple_bind(ctx, grad_req="null", **shapes)
                # host-numpy assignment keeps the executor's placement
                # (an NDArray source re-binds the dest to ITS device —
                # a silent all-CPU calibration on a TPU ctx)
                for k, v in arg_params.items():
                    if k in exe.arg_dict:
                        exe.arg_dict[k][:] = _asnp(v)
                for k, v in aux_params.items():
                    if k in exe.aux_dict:
                        exe.aux_dict[k][:] = _asnp(v)
            for k, v in batch.items():
                if k in exe.arg_dict:
                    exe.arg_dict[k][:] = _asnp(v)
            outs = exe.forward(is_train=False)
            vals = {p: o for p, o in zip(pick, outs)}
        else:
            vals = {}
        for name, spec in want.items():
            if spec[0] == "var":
                a = float(_np.abs(_np.asarray(batch[spec[1]])).max())
            else:
                a = float(vals[spec[1]].asnumpy())  # scalar: in-graph max
            amax[name] = max(amax[name], a)
    return amax


# ---------------------------------------------------------------------
# pass 3: graph rewrite to int8 compute ops
# ---------------------------------------------------------------------

def quantize_symbol(sym, arg_params, act_ranges, excluded_sym_names=(),
                    out_dtype="float32"):
    """Rewrite quantizable nodes to int8 MXU ops.

    Each target conv/FC becomes: ``_contrib_quantize(data)`` (symmetric
    int8, calibrated range params) -> quantized compute op with the
    offline-quantized int8 weight -> float32 out (+ bias broadcast_add
    when the conv carries one).  Returns ``(qsym, qarg_params)``.
    """
    nodes, heads = _load_graph(sym)
    args = {k: _asnp(v) for k, v in arg_params.items()}
    quantized_w = {}  # weight name -> wmax (tied weights quantize ONCE)
    q_cache = {}      # (id(src), out_idx) -> shared _contrib_quantize

    targets = [n for n in nodes if _quantizable(n)
               and n["name"] not in excluded_sym_names
               and n["name"] in act_ranges]
    # a weight consumed by BOTH a to-be-quantized node and anything else
    # (an excluded node, a non-quantizable op) would be rewritten to raw
    # int8 codes under the float consumer's feet — refuse loudly
    cons = _consumers(nodes)
    target_ids = {id(n) for n in targets}
    for node in targets:
        wnode = node["inputs"][1][0]
        outside = [c["name"] for c in cons[id(wnode)]
                   if id(c) not in target_ids]
        if outside:
            raise MXNetError(
                "weight %r is shared between quantized node %r and "
                "non-quantized consumer(s) %s; exclude all of its "
                "consumers or none" % (wnode["name"], node["name"],
                                       outside))

    for node in targets:
        name = node["name"]
        a = node["attr"]
        is_fc = node["op"] == "FullyConnected"
        data_src = node["inputs"][0]
        wnode = node["inputs"][1][0]
        wname = wnode["name"]
        had_bias = a.get("no_bias", "False") == "False" \
            and len(node["inputs"]) > 2

        # offline weight quantization (symmetric int8, max-abs).  A tied
        # weight shared by several nodes quantizes once — re-quantizing
        # the already-int8 array would record wmax=127 and silently wreck
        # the second node's dequant scale
        if wname in quantized_w:
            wmax = quantized_w[wname]
        else:
            w = args[wname]
            wmax = float(_np.abs(w).max()) or 1e-8
            args[wname] = _np.clip(
                _np.round(w / wmax * 127.0), -127, 127).astype(_np.int8)
            wnode["attr"]["__shape__"] = str(tuple(w.shape))
            wnode["attr"]["__dtype__"] = "int8"
            quantized_w[wname] = wmax
        args["%s_weight_min" % name] = _np.full((1,), -wmax, _np.float32)
        args["%s_weight_max" % name] = _np.full((1,), wmax, _np.float32)
        wmin_n = _null("%s_weight_min" % name, (1,))
        wmax_n = _null("%s_weight_max" % name, (1,))

        data_in = data_src
        if is_fc and a.get("flatten", "True") == "True":
            flat = {"op": "Flatten", "name": "%s_qflatten" % name,
                    "attr": {}, "inputs": [data_in]}
            nodes.append(flat)
            data_in = (flat, 0)
        # one quantize per SOURCE tensor: consumers sharing an input
        # (e.g. a ResNet downsample block's shortcut + main-path convs)
        # reuse the same int8 activation — same calibrated range by
        # construction (max-|x| of the same tensor), and distinct nodes
        # would defeat XLA CSE on the memory-bound quantize pass
        qkey = (id(data_in[0]), data_in[1])
        if qkey in q_cache:
            q = q_cache[qkey]
        else:
            amax = float(act_ranges[name]) or 1e-8
            args["%s_data_min" % name] = _np.full((1,), -amax, _np.float32)
            args["%s_data_max" % name] = _np.full((1,), amax, _np.float32)
            dmin_n = _null("%s_data_min" % name, (1,))
            dmax_n = _null("%s_data_max" % name, (1,))
            q = {"op": "_contrib_quantize", "name": "%s_qdata" % name,
                 "attr": {"out_type": "int8"},
                 "inputs": [data_in, (dmin_n, 0), (dmax_n, 0)]}
            nodes.extend([dmin_n, dmax_n, q])
            q_cache[qkey] = q
        nodes.extend([wmin_n, wmax_n])

        if is_fc:
            qop = {"op": "_contrib_quantized_fully_connected",
                   "name": name,
                   "attr": {"num_hidden": a["num_hidden"],
                            "symmetric": "True",
                            "out_type": out_dtype},
                   "inputs": [(q, 0), (wnode, 0), (q, 1), (q, 2),
                              (wmin_n, 0), (wmax_n, 0)]}
        else:
            qattr = {"kernel": a["kernel"],
                     "num_filter": a["num_filter"],
                     "layout": a.get("layout") or "NCHW",
                     "symmetric": "True",  # calib IS min=-max
                     "out_type": out_dtype}
            for k in ("stride", "pad"):
                if a.get(k):
                    qattr[k] = a[k]
            qop = {"op": "_contrib_quantized_conv", "name": name,
                   "attr": qattr,
                   "inputs": [(q, 0), (wnode, 0), (q, 1), (q, 2),
                              (wmin_n, 0), (wmax_n, 0)]}
        nodes.append(qop)

        tail = qop
        if had_bias:
            bnode = node["inputs"][2][0]
            import ml_dtypes  # numpy has no bf16; jax ships ml_dtypes

            b = args[bnode["name"]].astype(
                ml_dtypes.bfloat16 if out_dtype == "bfloat16"
                else _np.float32)
            if not is_fc:  # pre-shape for rank-4 broadcast
                nhwc = (a.get("layout") == "NHWC")
                b = b.reshape((1, 1, 1, -1) if nhwc else (1, -1, 1, 1))
            args[bnode["name"]] = b
            bnode["attr"]["__shape__"] = str(tuple(b.shape))
            tail = {"op": "broadcast_add", "name": "%s_bias_add" % name,
                    "attr": {}, "inputs": [(qop, 0), (bnode, 0)]}
            nodes.append(tail)

        # the original node keeps its name on the quantized op; rewire
        # consumers to the (bias-added) float output
        node["name"] = "%s_fp32_dead" % name
        heads = _rewire(nodes, heads, node, tail)

    return _emit_graph(heads), _wrap_nd(args)


def quantize_model(sym, arg_params, aux_params, calib_data, ctx,
                   excluded_sym_names=(), out_dtype="float32"):
    """The full PTQ pipeline (the reference's later-version
    ``contrib.quantization.quantize_model`` role): BN fold -> symmetric
    calibration -> int8 graph rewrite.  Returns
    ``(qsym, qarg_params, qaux_params)`` — aux is empty after the fold
    unless non-BN aux states exist."""
    batches = list(calib_data)
    fsym, fargs, fauxs = fold_bn(sym, arg_params, aux_params)
    ranges = calibrate_ranges(fsym, fargs, fauxs, batches, ctx,
                              excluded_sym_names=excluded_sym_names)
    qsym, qargs = quantize_symbol(fsym, fargs, ranges,
                                  excluded_sym_names=excluded_sym_names,
                                  out_dtype=out_dtype)
    return qsym, qargs, fauxs


# ---------------------------------------------------------------------
# QAT: fake-quant insertion (training) + export to the int8 graph
# ---------------------------------------------------------------------

def quantize_aware_symbol(sym, excluded_sym_names=(), ema_momentum=0.99,
                          num_bits=8, quantize_weights=True):
    """Insert fake-quant nodes for quantization-aware training.

    Every quantizable Convolution/FullyConnected gets its DATA input
    routed through a ``_contrib_fake_quant`` observer (EMA-tracked amax
    auxiliary state, straight-through-estimator backward) and — when
    ``quantize_weights`` — its weight through the stateless
    ``_contrib_fake_quant_dynamic``, so training sees the same symmetric
    int8 grids ``quantize_symbol`` will deploy.  Consumers sharing a data
    tensor share one observer (mirroring ``quantize_symbol``'s shared
    ``_contrib_quantize`` node).

    Recommended flow for BN models (the standard QAT pipeline): train
    fp32 -> :func:`fold_bn` -> ``quantize_aware_symbol`` -> finetune via
    Module (observers update like BN moving stats) ->
    :func:`quantize_model_qat`.  Returns the QAT training symbol; the
    new ``*_fq_amax`` aux states initialize to zero ("empty"; the first
    training batch seeds them — Initializer routes the suffix to zeros).
    """
    nodes, heads = _load_graph(sym)
    targets = [n for n in nodes if _quantizable(n)
               and n["name"] not in excluded_sym_names]
    # keyed by role too: a tensor consumed both as someone's data and as
    # someone's weight needs BOTH observer types (EMA-stateful for the
    # data edge, dynamic for the weight edge), not whichever was built
    # first
    fq_cache = {}  # (id(src node), out_idx, role) -> fake-quant node (shared)
    for n in targets:
        src, oi = n["inputs"][0]
        key = (id(src), oi, "data")
        if key not in fq_cache:
            base = src["name"] if oi == 0 else "%s%d" % (src["name"], oi)
            amax = _null("%s_fq_amax" % base, (1,))
            fq_cache[key] = {
                "op": "_contrib_fake_quant", "name": "%s_fq" % base,
                "attr": {"ema_momentum": str(ema_momentum),
                         "num_bits": str(num_bits)},
                "inputs": [(src, oi), (amax, 0)]}
        n["inputs"][0] = (fq_cache[key], 0)
        if quantize_weights:
            wsrc, woi = n["inputs"][1]
            wkey = (id(wsrc), woi, "weight")
            if wkey not in fq_cache:
                # "_fqw" keeps the name distinct from a data observer on
                # the same tensor; dynamic nodes own no params/aux, so no
                # stored name depends on this
                fq_cache[wkey] = {
                    "op": "_contrib_fake_quant_dynamic",
                    "name": "%s_fqw" % wsrc["name"],
                    "attr": {"num_bits": str(num_bits)},
                    "inputs": [(wsrc, woi)]}
            n["inputs"][1] = (fq_cache[wkey], 0)
    return _emit_graph(heads)


def quantize_model_qat(qat_sym, arg_params, aux_params,
                       excluded_sym_names=(), out_dtype="float32"):
    """Export a QAT-finetuned graph to the deployable int8 graph.

    Reads each conv/FC's activation range out of its observer's
    ``*_fq_amax`` aux state, strips every fake-quant node, and hands the
    plain graph + ranges to :func:`quantize_symbol` — so deployment uses
    exactly the ranges training simulated (no separate calibration pass).
    The graph must have been trained with ``num_bits=8``: the deployed
    grid (:func:`quantize_symbol`) is hard int8/127, so exporting a
    different trained width would silently change the simulated
    quantization — that raises :class:`MXNetError` instead.
    Returns ``(qsym, qarg_params, qaux_params)`` with the observer states
    dropped from aux."""
    import logging

    nodes, heads = _load_graph(qat_sym)
    act_ranges = {}
    for n in nodes:
        if not (_quantizable(n) and n["name"] not in excluded_sym_names):
            continue
        src, _oi = n["inputs"][0]
        if src["op"] != "_contrib_fake_quant":
            # a quantizable node this export will int8-convert, but whose
            # data edge was never observed during training — usually an
            # excluded_sym_names mismatch between insertion and export;
            # quantize_symbol will fall back to skipping it, silently
            # shipping a float node the user believes is quantized
            logging.warning(
                "QAT export: quantizable node %r has no fake-quant "
                "observer on its data input (trained with it in "
                "excluded_sym_names?); it will stay float in the "
                "exported graph", n["name"])
            continue
        amax_name = src["inputs"][1][0]["name"]
        if amax_name not in aux_params:
            raise MXNetError("QAT export: observer state %r missing from "
                             "aux_params" % amax_name)
        a = float(_asnp(aux_params[amax_name]).max())
        if a <= 0.0:
            raise MXNetError(
                "QAT observer %r is empty (amax=0); run at least one "
                "training batch before export" % amax_name)
        act_ranges[n["name"]] = a
    for fq in nodes:
        if fq["op"] not in ("_contrib_fake_quant",
                            "_contrib_fake_quant_dynamic"):
            continue
        bits = int(fq.get("attr", {}).get("num_bits", 8))
        if bits != 8:
            # quantize_symbol deploys a hard int8/127 grid; exporting a
            # graph trained at another width would quantize differently
            # than training simulated
            raise MXNetError(
                "QAT export: %r was trained with num_bits=%d but the "
                "deployable graph uses the int8 (127-step) grid; retrain "
                "with num_bits=8 or exclude the node" % (fq["name"], bits))
        heads = _rewire(nodes, heads, fq, fq["inputs"][0])
    stripped = _emit_graph(heads)
    qsym, qargs = quantize_symbol(stripped, arg_params, act_ranges,
                                  excluded_sym_names=excluded_sym_names,
                                  out_dtype=out_dtype)
    qauxs = {k: v for k, v in aux_params.items()
             if not k.endswith("_fq_amax")}
    return qsym, qargs, qauxs


# ---------------------------------------------------------------------

def _asnp(v):
    return v.asnumpy() if hasattr(v, "asnumpy") else _np.asarray(v)


def _wrap_nd(d):
    from .. import ndarray as nd

    return {k: (v if hasattr(v, "asnumpy") else nd.array(_np.asarray(v)))
            for k, v in d.items()}
