"""Standalone inference predictor.

Reference: ``include/mxnet/c_predict_api.h`` + ``src/c_api/c_predict_api.cc``
— the deployment-facing minimal API (create from symbol JSON + param bytes,
set input, forward, get output) that the amalgamation build ships.  Same
surface here, jit-compiled underneath.
"""
from __future__ import annotations

import logging

import numpy as np

from .base import MXNetError
from . import ndarray as nd
from . import symbol as sym_mod
from .context import cpu

__all__ = ["Predictor", "pad_batch"]


def pad_batch(value, batch):
    """Zero-pad ``value`` along axis 0 to ``batch`` rows.

    The shared pad half of the predict path's pad-and-slice contract:
    :meth:`Predictor.forward` pads partial batches up to its bound
    shape (so the compiled program's avals never change — zero
    retraces) and :meth:`Predictor.get_output` slices the pad rows
    back off; the serving batch ladder
    (:mod:`mxnet_tpu.serving.ladder`) uses the same helper to fill the
    tail of a coalesced batch up to the selected rung.  Padding is
    zeros: inference graphs are row-independent, so pad rows cost
    compute but never leak into real rows' outputs."""
    arr = np.asarray(value)
    if arr.ndim == 0:
        raise MXNetError("pad_batch needs a batched array, got a scalar")
    rows = arr.shape[0]
    if rows == batch:
        return arr
    if rows > batch:
        raise MXNetError("pad_batch: %d rows exceed the target batch %d"
                         % (rows, batch))
    pad = np.zeros((batch - rows,) + arr.shape[1:], dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


class Predictor:
    """Reference MXPredCreate / MXPredForward / MXPredGetOutput."""

    def __init__(self, symbol_json, param_bytes_or_dict, input_shapes,
                 ctx=None, output_names=None):
        """
        symbol_json: JSON string or path of the network (``*-symbol.json``).
        param_bytes_or_dict: path to ``*.params``, or {name: NDArray}.
        input_shapes: dict name -> shape.
        """
        if symbol_json.strip().startswith("{"):
            symbol = sym_mod.load_json(symbol_json)
        else:
            symbol = sym_mod.load(symbol_json)
        if output_names:
            internals = symbol.get_internals()
            outs = [internals[n if n.endswith("_output") else n + "_output"]
                    for n in output_names]
            symbol = sym_mod.Group(outs)
        self._symbol = symbol
        ctx = ctx or cpu()

        if isinstance(param_bytes_or_dict, str):
            loaded = nd.load(param_bytes_or_dict)
        elif isinstance(param_bytes_or_dict, (bytes, bytearray)):
            # raw .params content — the C predict API path
            # (MXPredCreate receives the file as a buffer)
            loaded = nd.load_buffer(bytes(param_bytes_or_dict))
        else:
            loaded = None
        if loaded is not None:
            if not isinstance(loaded, dict):
                raise MXNetError(
                    "params were saved as an unnamed list; the predictor "
                    "needs the name->array dict form (save with a dict)")
            params = {}
            for k, v in loaded.items():
                if ":" in k:
                    k = k.split(":", 1)[1]
                params[k] = v
        else:
            params = dict(param_bytes_or_dict)

        arg_shapes, _, aux_shapes = symbol.infer_shape(**input_shapes)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        args = {}
        for name, shape in zip(arg_names, arg_shapes):
            if name in input_shapes:
                args[name] = nd.zeros(shape, ctx=ctx)
            elif name in params:
                args[name] = params[name].as_in_context(ctx)
            elif name.endswith("label"):
                # deployment symbols keep their loss heads; label inputs
                # are inert at inference.  NOTE: the reference
                # c_predict_api.cc:182-188 silently zero-fills EVERY
                # missing arg; restricting the fallback to label-named
                # args (and warning) keeps missing real weights a loud
                # error instead of silent garbage.
                logging.warning("Predictor: zero-filling inference-inert "
                                "input %r", name)
                args[name] = nd.zeros(shape, ctx=ctx)
            else:
                raise MXNetError("missing parameter %r" % name)
        aux = {}
        for name, shape in zip(aux_names, aux_shapes):
            if name in params:
                aux[name] = params[name].as_in_context(ctx)
            else:
                aux[name] = nd.zeros(shape, ctx=ctx)
        self._input_names = list(input_shapes)
        self._partial_rows = {}
        self._executor = symbol.bind(ctx, args, grad_req="null",
                                     aux_states=aux)

    def set_input(self, name, value):
        """Stage one named input.  A value whose batch dim (axis 0) is
        SMALLER than the bound shape is zero-padded up to it
        (:func:`pad_batch`) and the pad rows are sliced off every
        output by :meth:`get_output` — the compiled program keeps its
        bound avals, so partial batches never retrace or recompile (the
        executor dispatches through the AOT executable
        ``telemetry.memory.planned_executable`` cached on first use).
        A LARGER batch is a loud error pointing at :meth:`reshaped` /
        the serving batch ladder instead of a silent per-shape
        recompile."""
        if name not in self._input_names:
            raise MXNetError("unknown input %r" % name)
        arr = self._executor.arg_dict[name]
        bound = tuple(arr.shape)
        value = np.asarray(value)
        if value.ndim == len(bound) and value.shape != bound:
            if value.shape[1:] != bound[1:]:
                raise MXNetError(
                    "input %r: non-batch dims %r do not match the bound "
                    "shape %r — reshape the predictor (reshaped()) for "
                    "a different feature shape" % (name, value.shape,
                                                   bound))
            rows, cap = value.shape[0], bound[0]
            if rows > cap:
                raise MXNetError(
                    "input %r: batch %d exceeds the bound batch %d; a "
                    "bigger batch needs its own executable — use "
                    "reshaped({%r: %r}) for a second handle, or the "
                    "serving batch ladder (mxnet_tpu.serving) which "
                    "AOT-compiles a rung per batch size"
                    % (name, rows, cap, name, (rows,) + bound[1:]))
            value = pad_batch(value, cap)
            self._partial_rows[name] = rows
        else:
            # a full-shape restage clears the input's partial marker, so
            # slicing state can never leak across forwards
            self._partial_rows.pop(name, None)
        arr[:] = value

    def forward(self, **inputs):
        for k, v in inputs.items():
            self.set_input(k, v)
        self._executor.forward(is_train=False)
        return self

    def get_output(self, index=0):
        """Fetch one output; pad rows staged by a partial-batch
        :meth:`set_input` are sliced off (the slice half of
        pad-and-slice)."""
        out = self._executor.outputs[index].asnumpy()
        partial = getattr(self, "_partial_rows", None)
        rows = min(partial.values()) if partial else None
        if rows is not None and out.ndim and out.shape[0] >= rows:
            out = out[:rows]
        return out

    def reshape(self, input_shapes):
        # the C predict API reallocates freely on reshape
        # (c_predict_api.cc MXPredReshape), so growing inputs is
        # allowed; partial_shaping covers implied changes (an inert
        # label head's batch dim follows the data input)
        self._partial_rows = {}
        self._executor = self._executor.reshape(allow_up_sizing=True,
                                                partial_shaping=True,
                                                **input_shapes)
        return self

    def reshaped(self, input_shapes):
        """Return a NEW Predictor bound at ``input_shapes``, leaving this
        one untouched.

        Reference MXPredReshape (c_predict_api.cc:228-270) hands the caller
        a fresh handle backed by a new executor while the original handle
        keeps working at its original shapes (weights are shared); this is
        the method the native ABI calls so one handle per batch size works.
        """
        clone = object.__new__(Predictor)
        clone._symbol = self._symbol
        clone._partial_rows = {}
        # partial reshape keeps the full input set (reference allows
        # reshaping a subset of inputs; the others keep their shapes)
        clone._input_names = list(self._input_names)
        clone._executor = self._executor.reshape(allow_up_sizing=True,
                                                 partial_shaping=True,
                                                 **input_shapes)
        return clone
