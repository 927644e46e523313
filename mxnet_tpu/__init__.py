"""mxnet_tpu: a TPU-native deep-learning framework with the capability
surface of Apache MXNet 0.10 (reference: daiab/mxnet @ v0.10.1), built on
JAX/XLA/Pallas/pjit.

Import convention mirrors the reference's ``import mxnet as mx``::

    import mxnet_tpu as mx
    x = mx.nd.zeros((2, 3), ctx=mx.tpu(0))
"""
from __future__ import annotations

import sys as _sys
import time as _time

_IMPORT_T0 = _time.perf_counter()   # the ``mxnet_tpu.import`` span's start
# what the process had done before this line, for ``process.before_import``
_JAX_IMPORTED = "jax" in _sys.modules
_BACKEND_UP = _JAX_IMPORTED and \
    _sys.modules["jax"]._src.xla_bridge.backends_are_initialized()


def _process_start():
    """The process's start on the ``perf_counter`` clock: its start time
    in ``/proc/self/stat`` (clock ticks after boot) against
    ``CLOCK_BOOTTIME``.  None where that cannot be read."""
    import os
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = _time.clock_gettime(_time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, AttributeError, IndexError, ValueError):
        return None
    return _time.perf_counter() - age

__version__ = "0.1.0"

from .base import MXNetError
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import base
from . import ndarray
from . import ndarray as nd
from . import random
from . import autograd
from . import ops
from . import operator  # registers the "Custom" op before codegen below
from . import name
from .attribute import AttrScope
from . import symbol
from . import symbol as sym
from .executor import Executor

# generate mx.nd.<op> functions from the registry (reference:
# python/mxnet/ndarray.py:2281-2423 codegen over the C op registry)
ndarray._register_op_functions(ops.generate_nd_functions())

# training stack (imported after op injection: optimizer uses nd.sgd_update
# et al., which only exist once the codegen above has run)
from . import registry
from . import initializer
from . import initializer as init  # reference alias (python/mxnet/__init__.py)
from .initializer import InitDesc
from . import lr_scheduler
from . import optimizer
from . import metric
from . import io
from . import io_resume
from . import callback
from . import kvstore
from . import kvstore as kv
from . import model
from . import module
from . import module as mod  # reference alias (python/mxnet/__init__.py)
from .module import Module
from . import rnn
from . import profiler
from . import telemetry
from . import monitor
from . import monitor as mon  # reference alias (python/mxnet/__init__.py)
from .monitor import Monitor
from . import recordio
from . import resilience
from . import visualization
from . import visualization as viz
from . import test_utils
from . import analysis
from . import autotune
from . import contrib
from . import config
from . import predictor
from .predictor import Predictor
from . import serving

# optional: image pipelines need PIL
try:
    from . import image
    from . import image_det
except ImportError:  # pragma: no cover
    image = None
    image_det = None

from . import rtc

# optional: torch interop (plugin/torch + python/mxnet/torch.py parity)
try:
    from . import torch as th
    sym.TorchModule = th.torch_module_symbol
    sym.TorchCriterion = th.torch_criterion_symbol
except ImportError:  # pragma: no cover
    th = None

# first to last line of this file, as a span record (telemetry.spans):
# a benchmark's ``import_s``; before it the process up to the first line
# (the interpreter, the script's own imports, ``import jax`` and the
# runtime's bring-up where they came first)
_PROCESS_T0 = _process_start()
if _PROCESS_T0 is not None and _PROCESS_T0 <= _IMPORT_T0:
    telemetry.spans.record("process.before_import", _PROCESS_T0, _IMPORT_T0,
                           jax_imported=_JAX_IMPORTED, backend_up=_BACKEND_UP)
telemetry.spans.record("mxnet_tpu.import", _IMPORT_T0, _time.perf_counter())
