"""Weight initializers.

Reference: ``python/mxnet/initializer.py`` (660 L) — registry of initializers
dispatched by parameter-name pattern; ``InitDesc`` carries the name + attrs
(``__init__`` override per variable).

One dispatch, two places to draw.  ``init(desc, arr)`` fills a host array
(``Module``, the executor): numpy's global generator, float64 cast to the
array's dtype, the reference's numbers.  ``rule_for(init, desc)`` names the
rule without running it, and ``draw(rule, desc, shape, key)`` runs a rule
marked ``traceable`` on a ``jax.random`` key instead, float32 and under a
trace: ``ShardedTrainer`` draws its initial state that way, on the device.
"""
from __future__ import annotations

import json
import logging
import re

import numpy as np

from .base import MXNetError
from . import ndarray as nd
from .registry import get_register_func, get_create_func, get_alias_func

__all__ = ["InitDesc", "Initializer", "Uniform", "Normal", "Zero", "One",
           "Constant", "Orthogonal", "Xavier", "MSRAPrelu", "Bilinear",
           "LSTMBias", "Load", "Mixed", "LogUniform",
           "InverseSoftplusLogUniform", "register"]


class InitDesc(str):
    """Name + attrs descriptor (reference initializer.py InitDesc)."""
    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


def traceable(rule):
    """Mark a slot rule as one that can be drawn under a trace: it
    assigns ``arr[:]`` once, from constants and from ``_rng(arr)``, and
    reads nothing of ``arr`` but its shape and nothing of ``desc`` (one
    trace serves every parameter of that rule and shape).  ``draw``
    runs such a rule on a jax key; a rule without the mark (a user's
    ``_init_weight`` written against ``np.random``, an SVD, a loop
    over elements) only ever fills a host array."""
    rule.traceable = True
    return rule


class _Traced:
    """What a ``traceable`` rule fills inside ``draw``: a shape, the
    float32 value the rule assigned, and ``np.random``'s ``uniform`` /
    ``normal`` over one ``jax.random`` key (a rule draws once, so the
    key is used once)."""

    def __init__(self, shape, key):
        self.shape = tuple(shape)
        self._key = key
        self.value = None

    def __setitem__(self, index, value):
        import jax.numpy as jnp
        if index != slice(None):
            raise MXNetError("a traceable rule assigns arr[:] whole")
        self.value = jnp.broadcast_to(
            jnp.asarray(value, jnp.float32), self.shape)

    def uniform(self, low, high, size):
        import jax
        return jax.random.uniform(self._key, size, "float32",
                                  float(low), float(high))

    def normal(self, loc, scale, size):
        import jax
        return float(loc) + float(scale) * jax.random.normal(
            self._key, size, "float32")


def _rng(arr):
    """Where a rule draws for ``arr``: the traced value's own key, else
    numpy's global generator (float64, as the reference)."""
    return arr if isinstance(arr, _Traced) else np.random


def rule_for(init, desc):
    """The callable ``(desc, arr)`` that ``init(desc, arr)`` runs for
    this name, without running it: an ``Initializer``'s slot rule, the
    rule of the ``Mixed`` pattern that matches, or ``init`` itself
    where its call is its own code (``Load``, a subclass that
    overrides ``__call__``, any callable)."""
    if type(init).__call__ in (Initializer.__call__, Mixed.__call__):
        return init.rule(desc)
    return init


def draw(rule, desc, shape, key):
    """The float32 value a ``traceable`` ``rule`` gives a parameter of
    ``shape``, as a function of a ``jax.random`` key: the same rule
    that fills a host array, traced."""
    arr = _Traced(shape, key)
    rule(desc, arr)
    return arr.value


class Initializer:
    """Base initializer; callable on (InitDesc/name, NDArray)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs

    def dumps(self):
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, desc, arr):
        self.rule(desc)(desc, arr)

    def rule(self, desc):
        """The slot rule this name gets (a bound method taking
        ``(desc, arr)``): the one place the suffix rules live."""
        if not isinstance(desc, str):
            raise TypeError("desc must be a string or InitDesc")
        if isinstance(desc, InitDesc) and desc.attrs.get("__init__"):
            return create(desc.attrs["__init__"])._init_weight
        name = desc.lower()
        # name-pattern dispatch, matching the reference's suffix rules
        if name.endswith("upsampling"):
            return self._init_bilinear
        if name.endswith("bias"):
            return self._init_bias
        if name.endswith("gamma"):
            return self._init_gamma
        if name.endswith("beta"):
            return self._init_beta
        if name.endswith("weight"):
            return self._init_weight
        if name.endswith(("moving_var", "running_var")):
            return self._init_one
        if name.endswith(("moving_mean", "running_mean", "moving_inv_var",
                          "moving_avg",
                          # an expert layer's load statistics
                          # (_contrib_TopKMoE's aux)
                          "_load")):
            return self._init_zero
        return self._init_default

    # ---- slot initializers
    def _init_bilinear(self, _, arr):
        shape = arr.shape
        weight = np.zeros(int(np.prod(shape)), dtype="float32")
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        arr[:] = weight.reshape(shape)

    @traceable
    def _init_zero(self, _, arr):
        arr[:] = 0.0

    @traceable
    def _init_one(self, _, arr):
        arr[:] = 1.0

    @traceable
    def _init_bias(self, _, arr):
        arr[:] = 0.0

    @traceable
    def _init_gamma(self, _, arr):
        arr[:] = 1.0

    @traceable
    def _init_beta(self, _, arr):
        arr[:] = 0.0

    def _init_weight(self, name, arr):
        raise NotImplementedError("must override _init_weight")

    def _init_default(self, name, arr):
        raise MXNetError(
            "Unknown initialization pattern for %s. Default initialization "
            "is now limited to \"weight\", \"bias\", \"gamma\" and \"beta\"; "
            "use mx.sym.Variable(init=...) for other names" % name)


register = get_register_func(Initializer, "initializer")
create = get_create_func(Initializer, "initializer")
alias = get_alias_func(Initializer, "initializer")


@register
class Load:
    """Init from an existing param dict, falling back to ``default_init``."""

    def __init__(self, param, default_init=None, verbose=False):
        self.param = {
            (k[4:] if k.startswith("arg:") or k.startswith("aux:") else k): v
            for k, v in param.items()}
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr):
        if name in self.param:
            src = self.param[name]
            if tuple(src.shape) != tuple(arr.shape):
                raise MXNetError(
                    "Parameter %s cannot be initialized from loading, "
                    "shape mismatch %s vs %s" % (name, src.shape, arr.shape))
            arr[:] = src
            if self.verbose:
                logging.info("Initialized %s by loading", name)
        else:
            if self.default_init is None:
                raise MXNetError(
                    "Cannot Initialize %s. Not found in loaded param and no "
                    "default Initializer is provided." % name)
            self.default_init(name, arr)
            if self.verbose:
                logging.info("Initialized %s by default", name)


Load = Load  # registered as 'load'


@register
class Mixed:
    """Pattern-matched list of initializers (reference Mixed)."""

    def __init__(self, patterns, initializers):
        if len(patterns) != len(initializers):
            raise MXNetError("patterns and initializers must be same length")
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        self.rule(name)(name, arr)

    def rule(self, name):
        """The rule of the first pattern that matches ``name``."""
        for prog, init in self.map:
            if prog.match(name):
                return rule_for(init, name)
        raise MXNetError(
            "Parameter name %s did not match any pattern. Consider adding a "
            "\".*\" pattern at the end with default Initializer." % name)


@register
class Zero(Initializer):
    @traceable
    def _init_weight(self, _, arr):
        arr[:] = 0.0


alias("zeros")(Zero)


@register
class One(Initializer):
    @traceable
    def _init_weight(self, _, arr):
        arr[:] = 1.0


alias("ones")(One)


@register
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    @traceable
    def _init_weight(self, _, arr):
        arr[:] = self.value


@register
class Uniform(Initializer):
    """U(-scale, scale).  Reference initializer.py Uniform."""

    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    @traceable
    def _init_weight(self, _, arr):
        arr[:] = _rng(arr).uniform(-self.scale, self.scale, arr.shape)


@register
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    @traceable
    def _init_weight(self, _, arr):
        arr[:] = _rng(arr).normal(0, self.sigma, arr.shape)


def _math(arr):
    """``numpy``'s elementwise functions for what ``_rng(arr)`` drew:
    ``jax.numpy`` under a trace."""
    if isinstance(arr, _Traced):
        import jax.numpy as jnp
        return jnp
    return np


@register
class LogUniform(Initializer):
    """``log(U(low, high))``: the log of a rate drawn evenly between two
    bounds (a gated delta-rule layer's ``A_log``: ``fla.layers.kda``)."""

    def __init__(self, low=1.0, high=16.0):
        super().__init__(low=low, high=high)
        self.low, self.high = float(low), float(high)

    @traceable
    def _init_weight(self, _, arr):
        arr[:] = _math(arr).log(
            _rng(arr).uniform(self.low, self.high, arr.shape))


@register
class InverseSoftplusLogUniform(Initializer):
    """``softplus^-1(exp(U(log low, log high)))``: a bias under a softplus
    whose output starts log-uniform between ``low`` and ``high`` (a
    gated delta-rule layer's ``dt_bias``: ``fla.layers.kda``)."""

    def __init__(self, low=0.001, high=0.1):
        super().__init__(low=low, high=high)
        self.low, self.high = float(low), float(high)

    @traceable
    def _init_weight(self, _, arr):
        xp = _math(arr)
        dt = xp.exp(_rng(arr).uniform(np.log(self.low), np.log(self.high),
                                      arr.shape))
        arr[:] = dt + xp.log(-xp.expm1(-dt))


@register
class Orthogonal(Initializer):
    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, _, arr):
        nout = arr.shape[0]
        nin = int(np.prod(arr.shape[1:]))
        if self.rand_type == "uniform":
            tmp = np.random.uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = np.random.normal(0.0, 1.0, (nout, nin))
        u, _, v = np.linalg.svd(tmp, full_matrices=False)
        res = u if u.shape == tmp.shape else v
        arr[:] = (self.scale * res).reshape(arr.shape)


@register
class Xavier(Initializer):
    """Reference initializer.py Xavier (gaussian/uniform × avg/in/out)."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    @traceable
    def _init_weight(self, name, arr):
        shape = arr.shape
        hw_scale = 1.0
        if len(shape) < 2:
            raise MXNetError(
                "Xavier initializer cannot be applied to vector %s. It "
                "requires at least 2D." % name)
        if len(shape) > 2:
            hw_scale = np.prod(shape[2:])
        fan_in, fan_out = shape[1] * hw_scale, shape[0] * hw_scale
        factor = 1.0
        if self.factor_type == "avg":
            factor = (fan_in + fan_out) / 2.0
        elif self.factor_type == "in":
            factor = fan_in
        elif self.factor_type == "out":
            factor = fan_out
        else:
            raise ValueError("Incorrect factor type")
        scale = np.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            arr[:] = _rng(arr).uniform(-scale, scale, arr.shape)
        elif self.rnd_type == "gaussian":
            arr[:] = _rng(arr).normal(0, scale, arr.shape)
        else:
            raise ValueError("Unknown random type")


@register
class MSRAPrelu(Xavier):
    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    def _init_weight(self, _, arr):
        Initializer._init_bilinear(self, _, arr)


@register
class LSTMBias(Initializer):
    """Forget-gate bias init (reference LSTMBias; cuDNN gate order i,f,g,o)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, name, arr):
        b = np.zeros(arr.shape, dtype="float32")
        num_hidden = int(b.shape[0] / 4)
        b[num_hidden:2 * num_hidden] = self.forget_bias
        arr[:] = b


class FusedRNN(Initializer):
    """Initialize a fused RNN parameter vector by unpacking into per-gate
    matrices, applying ``init``, and repacking (reference FusedRNN)."""

    def __init__(self, init, num_hidden, num_layers, mode,
                 bidirectional=False, forget_bias=1.0):
        if isinstance(init, str):
            klass, kwargs = json.loads(init)
            init = create(klass, **kwargs)
        super().__init__(init=init.dumps() if init else None,
                         num_hidden=num_hidden, num_layers=num_layers,
                         mode=mode, bidirectional=bidirectional,
                         forget_bias=forget_bias)
        self._init = init
        self._num_hidden = num_hidden
        self._num_layers = num_layers
        self._mode = mode
        self._bidirectional = bidirectional
        self._forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        from .rnn.rnn_cell import FusedRNNCell
        cell = FusedRNNCell(self._num_hidden, self._num_layers,
                            self._mode, self._bidirectional,
                            forget_bias=self._forget_bias)
        args = cell.unpack_weights({cell._parameter_name(): arr})
        for name, a in args.items():
            desc_i = InitDesc(name, getattr(desc, "attrs", {}))
            if self._init is None:
                if isinstance(desc, InitDesc) and desc.global_init:
                    desc.global_init(desc_i, a)
            else:
                self._init(desc_i, a)
        arr[:] = cell.pack_weights(args)[cell._parameter_name()]


register(FusedRNN)
