"""Deployment predict API (parity: reference ``include/mxnet/c_predict_api.h``
+ ``src/c_api/c_predict_api.cc`` — ``MXPredCreate/SetInput/Forward/
GetOutput/Reshape``, the amalgamation-friendly inference-only surface).

TPU framing: a ``Predictor`` is one AOT-jitted forward executable per input
shape (the ``MXNET_PREDICT_ONLY`` bind of the reference becomes an XLA
compile), with an executable cache keyed by shape so ``reshape`` is cheap
after first compile — the bucketing executors' trick applied to serving.
"""

from __future__ import annotations

import numpy as _np

from .base import MXNetError

__all__ = ["Predictor", "load"]


class Predictor(object):
    """Forward-only model loaded from checkpoint artifacts.

    Parameters
    ----------
    symbol_json : str — Symbol JSON (contents, not path).
    param_bytes : bytes or dict — serialized params (``nd.save`` format) or
        an in-memory ``{'arg:name'/'aux:name' -> NDArray}`` dict.
    ctx : Context
    input_shapes : dict name -> shape
    """

    def __init__(self, symbol_json, param_bytes, ctx=None, input_shapes=None,
                 output_index=None):
        from . import context, ndarray, symbol

        self._ctx = ctx or context.current_context()
        self.symbol = symbol.load_json(symbol_json)
        if isinstance(param_bytes, dict):
            saved = param_bytes
        else:
            saved = ndarray.load_frombuffer(param_bytes)
        self._arg_params, self._aux_params = {}, {}
        for k, v in saved.items():
            if k.startswith("arg:"):
                self._arg_params[k[4:]] = v
            elif k.startswith("aux:"):
                self._aux_params[k[4:]] = v
            else:
                self._arg_params[k] = v
        if not input_shapes:
            raise MXNetError("input_shapes required")
        self._input_shapes = dict(input_shapes)
        self._exec_cache = {}
        self._pipe_cache = {}  # jitted device-loop traces, per (shapes, N)
        self._inputs = {n: None for n in self._input_shapes}
        self._output_index = output_index
        self._bind()

    # -- executor cache ------------------------------------------------
    def _bind(self):
        from . import ndarray

        key = tuple(sorted((n, tuple(s))
                           for n, s in self._input_shapes.items()))
        if key not in self._exec_cache:
            # place loaded params on the serving device (checkpoint loads
            # land on host; every array must live on self._ctx before bind)
            args = {n: v.as_in_context(self._ctx)
                    for n, v in self._arg_params.items()}
            aux = {n: v.as_in_context(self._ctx)
                   for n, v in self._aux_params.items()}
            for n, s in self._input_shapes.items():
                args[n] = ndarray.zeros(s, ctx=self._ctx)
            # loss-layer label args have no saved params: zero-fill at their
            # inferred shapes (the reference's predict-only bind does the
            # same — labels are dead inputs in inference)
            missing = [n for n in self.symbol.list_arguments()
                       if n not in args]
            if missing:
                arg_shapes, _, _ = self.symbol.infer_shape(
                    **{n: tuple(s) for n, s in self._input_shapes.items()})
                shape_map = dict(zip(self.symbol.list_arguments(),
                                     arg_shapes))
                for n in missing:
                    if shape_map.get(n) is None:
                        raise MXNetError(
                            "missing param %r with uninferrable shape" % n)
                    args[n] = ndarray.zeros(shape_map[n], ctx=self._ctx)
            self._exec_cache[key] = self.symbol.bind(
                self._ctx, args, aux_states=aux, grad_req="null")
        self._exec = self._exec_cache[key]

    def reshape(self, input_shapes):
        """Rebind for new input shapes (parity: ``MXPredReshape``); cached
        per shape like bucketing executors."""
        self._input_shapes = dict(input_shapes)
        self._bind()

    # -- the MXPred* surface -------------------------------------------
    def set_input(self, name, value):
        """(parity: ``MXPredSetInput``)"""
        from . import ndarray

        if name not in self._input_shapes:
            raise MXNetError("unknown input %r" % name)
        value = _np.asarray(value, dtype=_np.float32)
        if tuple(value.shape) != tuple(self._input_shapes[name]):
            self.reshape({**self._input_shapes, name: value.shape})
        self._exec.arg_dict[name][:] = ndarray.array(value, ctx=self._ctx)

    def forward(self, **inputs):
        """(parity: ``MXPredForward``); optional inputs by kwarg."""
        for n, v in inputs.items():
            self.set_input(n, v)
        self._exec.forward(is_train=False)
        return self

    def forward_pipeline(self, batches):
        """Run N batches in ONE device dispatch — serving's version of the
        trainer's ``pipeline_steps``: a jitted ``lax.scan`` over stacked
        ``[N, ...]`` inputs pays the host→device dispatch (docs/PERF.md
        "Batch-32 inference") once per window instead of once per batch.

        ``batches`` is a list of ``{input: array}`` dicts, each matching
        ``input_shapes``, or a dict of pre-stacked ``[N, ...]`` arrays.
        Returns the outputs as a list of ``[N, ...]``-stacked numpy arrays
        (scoped to a single output when the Predictor was built with
        ``output_index``, like ``get_output``).  The scan trace is cached
        per ``(input shapes, N)``, so serving at a fixed window size
        compiles once."""
        import jax

        if isinstance(batches, dict):
            if not batches:
                raise MXNetError("forward_pipeline needs >= 1 batch")
            stacked = {n: _np.asarray(v) for n, v in batches.items()}
        else:
            if not batches:
                raise MXNetError("forward_pipeline needs >= 1 batch")
            stacked = {n: _np.stack([_np.asarray(b[n]) for b in batches])
                       for n in batches[0]}
        missing = set(self._input_shapes) - set(stacked)
        if missing:
            raise MXNetError("forward_pipeline missing inputs %r"
                             % sorted(missing))
        for n, v in stacked.items():
            if n not in self._input_shapes:
                raise MXNetError("unknown input %r" % n)
            if tuple(v.shape[1:]) != tuple(self._input_shapes[n]):
                raise MXNetError(
                    "input %r batches have shape %r, declared %r"
                    % (n, tuple(v.shape[1:]),
                       tuple(self._input_shapes[n])))
        depths = {v.shape[0] for v in stacked.values()}
        if len(depths) != 1:
            raise MXNetError(
                "inputs disagree on pipeline depth: %r" % sorted(depths))
        depth = depths.pop()
        if depth == 0:
            # a pre-stacked {n: empty [0, ...]} dict would compile a
            # degenerate scan and silently return empty outputs
            raise MXNetError("forward_pipeline needs >= 1 batch")
        ex = self._exec
        stacked = {n: v.astype(ex.arg_dict[n].dtype, copy=False)
                   for n, v in stacked.items()}
        shape_key = tuple(sorted((n, tuple(s))
                                 for n, s in self._input_shapes.items()))
        fn = self._pipe_cache.get((shape_key, depth))
        if fn is None:
            run = ex._run

            def pipe(params, aux, stacked):
                def body(key, batch):
                    args = dict(params)
                    args.update(batch)
                    outs, _ = run(args, aux, key, False)
                    return key, outs

                _, outs = jax.lax.scan(body, jax.random.PRNGKey(0), stacked)
                return outs

            fn = jax.jit(pipe)
            self._pipe_cache[(shape_key, depth)] = fn
        params = {k: v._data for k, v in ex.arg_dict.items()
                  if k not in self._input_shapes}
        aux = {k: v._data for k, v in ex.aux_dict.items()}
        outs = fn(params, aux, stacked)
        if self._output_index is not None:
            outs = [outs[self._output_index]]
        return [_np.asarray(o) for o in outs]

    def get_output(self, index=0):
        """(parity: ``MXPredGetOutput``) → numpy array.  When the Predictor
        was built with ``output_index``, the view is scoped to that single
        output (``MXPredCreatePartialOut`` semantics)."""
        if self._output_index is not None:
            assert index == 0, "output_index-scoped predictor has 1 output"
            index = self._output_index
        return self._exec.outputs[index].asnumpy()

    @property
    def num_outputs(self):
        if self._output_index is not None:
            return 1
        return len(self._exec.outputs)


def load(prefix, epoch, ctx=None, input_shapes=None):
    """Build a Predictor straight from ``save_checkpoint`` artifacts
    (``prefix-symbol.json`` + ``prefix-%04d.params``)."""
    from . import model as _model

    with open("%s-symbol.json" % prefix) as f:
        symbol_json = f.read()
    param_name = "%s-%04d.params" % (prefix, epoch)
    # checkpoint writes are async engine ops: order this read after them
    _model.wait_for_checkpoint(param_name)
    with open(param_name, "rb") as f:
        param_bytes = f.read()
    return Predictor(symbol_json, param_bytes, ctx=ctx,
                     input_shapes=input_shapes)
