"""Registry-wide finite-difference gradient sweep.

Reference: tests/python/unittest/test_operator.py (3119 L) checks each
operator's backward against central differences via
check_numeric_gradient.  This sweep walks the ENTIRE op registry: every
registered op must either have a gradient case here or an explicit skip
entry with a reason — `test_registry_fully_classified` fails when a new
op lands unclassified.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import registry
from mxnet_tpu.test_utils import check_numeric_gradient

_RNG = np.random.RandomState(11)


def _x(*shape):
    """Well-separated values away from kinks/ties/integers."""
    n = int(np.prod(shape))
    base = np.linspace(-1.7, 1.9, n) + _RNG.uniform(0.011, 0.019, n)
    return _RNG.permutation(base).astype("float64").reshape(shape)


def _pos(*shape):
    return np.abs(_x(*shape)) + 0.3


def _unit(*shape):
    return np.tanh(_x(*shape)) * 0.8


# op -> (input arrays, attrs[, kwargs for check_numeric_gradient])
CASES = {
    # elementwise unary
    "abs": ([_x(2, 5)], {}),
    "arccos": ([_unit(2, 5)], {}),
    "arccosh": ([_pos(2, 5) + 1.2], {}),
    "arcsin": ([_unit(2, 5)], {}),
    "arcsinh": ([_x(2, 5)], {}),
    "arctan": ([_x(2, 5)], {}),
    "arctanh": ([_unit(2, 5)], {}),
    "cbrt": ([_pos(2, 5)], {}),
    "cos": ([_x(2, 5)], {}),
    "cosh": ([_x(2, 5)], {}),
    "degrees": ([_x(2, 5)], {}),
    "erf": ([_x(2, 5)], {}),
    "exp": ([_x(2, 5) * 0.5], {}),
    "expm1": ([_x(2, 5) * 0.5], {}),
    "gamma": ([_pos(2, 5) + 0.5], {}),
    "gammaln": ([_pos(2, 5) + 0.5], {}),
    "log": ([_pos(2, 5)], {}),
    "log10": ([_pos(2, 5)], {}),
    "log1p": ([_pos(2, 5)], {}),
    "log2": ([_pos(2, 5)], {}),
    "negative": ([_x(2, 5)], {}),
    "radians": ([_x(2, 5)], {}),
    "rcbrt": ([_pos(2, 5)], {}),
    "reciprocal": ([_pos(2, 5)], {}),
    "relu": ([_x(2, 5)], {}),
    "rsqrt": ([_pos(2, 5)], {}),
    "sigmoid": ([_x(2, 5)], {}),
    "sin": ([_x(2, 5)], {}),
    "sinh": ([_x(2, 5)], {}),
    "softsign": ([_x(2, 5)], {}),
    "sqrt": ([_pos(2, 5)], {}),
    "square": ([_x(2, 5)], {}),
    "tan": ([_unit(2, 5)], {}),
    "tanh": ([_x(2, 5)], {}),
    "smooth_l1": ([_x(2, 5)], {}),
    "identity": ([_x(2, 5)], {}),
    "Cast": ([_x(2, 5)], {"dtype": "float32"}),
    "clip": ([_x(2, 5)], {"a_min": -1.0, "a_max": 1.0}),
    # piecewise-constant (zero gradient a.e. — both sides must agree)
    "sign": ([_x(2, 5)], {}),
    "floor": ([_x(2, 5)], {}),
    "ceil": ([_x(2, 5)], {}),
    "round": ([_x(2, 5)], {}),
    "rint": ([_x(2, 5)], {}),
    "fix": ([_x(2, 5)], {}),
    "trunc": ([_x(2, 5)], {}),
    # binary / scalar arithmetic
    "elemwise_add": ([_x(2, 5), _x(2, 5)], {}),
    "elemwise_sub": ([_x(2, 5), _x(2, 5)], {}),
    "elemwise_mul": ([_x(2, 5), _x(2, 5)], {}),
    "elemwise_div": ([_x(2, 5), _pos(2, 5)], {}),
    "_maximum": ([_x(2, 5), _x(2, 5) + 0.11], {}),
    "_minimum": ([_x(2, 5), _x(2, 5) + 0.11], {}),
    "_hypot": ([_pos(2, 5), _pos(2, 5)], {}),
    "_power": ([_pos(2, 5), _x(2, 5)], {}),
    "_plus_scalar": ([_x(2, 5)], {"scalar": 1.5}),
    "_minus_scalar": ([_x(2, 5)], {"scalar": 1.5}),
    "_rminus_scalar": ([_x(2, 5)], {"scalar": 1.5}),
    "_mul_scalar": ([_x(2, 5)], {"scalar": -2.5}),
    "_div_scalar": ([_x(2, 5)], {"scalar": 2.5}),
    "_rdiv_scalar": ([_pos(2, 5)], {"scalar": 2.5}),
    "_power_scalar": ([_pos(2, 5)], {"scalar": 2.0}),
    "_rpower_scalar": ([_x(2, 5) * 0.5], {"scalar": 2.0}),
    "_maximum_scalar": ([_x(2, 5)], {"scalar": 0.13}),
    "_minimum_scalar": ([_x(2, 5)], {"scalar": 0.13}),
    "broadcast_add": ([_x(2, 5), _x(1, 5)], {}),
    "broadcast_sub": ([_x(2, 5), _x(1, 5)], {}),
    "broadcast_mul": ([_x(2, 5), _x(1, 5)], {}),
    "broadcast_div": ([_x(2, 5), _pos(1, 5)], {}),
    "broadcast_maximum": ([_x(2, 5), _x(1, 5) + 0.11], {}),
    "broadcast_minimum": ([_x(2, 5), _x(1, 5) + 0.11], {}),
    "broadcast_hypot": ([_pos(2, 5), _pos(1, 5)], {}),
    "broadcast_power": ([_pos(2, 5), _x(1, 5)], {}),
    "add_n": ([_x(2, 5), _x(2, 5), _x(2, 5)], {}),
    # reductions
    "sum": ([_x(2, 6)], {"axis": 1}),
    "mean": ([_x(2, 6)], {"axis": 1}),
    "max": ([_x(2, 6)], {"axis": 1}),
    "min": ([_x(2, 6)], {"axis": 1}),
    "prod": ([_pos(2, 4)], {"axis": 1}),
    "nansum": ([_x(2, 6)], {"axis": 1}),
    "nanprod": ([_pos(2, 4)], {"axis": 1}),
    "norm": ([_x(2, 6)], {}),
    # shape / layout
    "transpose": ([_x(2, 5)], {}),
    "Reshape": ([_x(2, 6)], {"shape": (3, 4)}),
    "Flatten": ([_x(2, 3, 2)], {}),
    "expand_dims": ([_x(2, 5)], {"axis": 1}),
    "slice": ([_x(3, 5)], {"begin": (0, 1), "end": (2, 4)}),
    "slice_axis": ([_x(3, 5)], {"axis": 1, "begin": 1, "end": 4}),
    "flip": ([_x(2, 5)], {"axis": 1}),
    "repeat": ([_x(2, 3)], {"repeats": 2, "axis": 1}),
    "tile": ([_x(2, 3)], {"reps": (1, 2)}),
    "stack": ([_x(2, 3), _x(2, 3)], {}),
    "Concat": ([_x(2, 3), _x(2, 3)], {"num_args": 2}),
    "SliceChannel": ([_x(2, 6)], {"num_outputs": 2}),
    "broadcast_to": ([_x(1, 5)], {"shape": (3, 5)}),
    "broadcast_axis": ([_x(1, 5)], {"axis": 0, "size": 3}),
    "SwapAxis": ([_x(2, 3, 2)], {"dim1": 1, "dim2": 2}),
    "Pad": ([_x(1, 2, 4, 4)],
            {"mode": "constant", "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)}),
    "Crop": ([_x(1, 2, 5, 5)], {"h_w": (3, 3), "center_crop": True}),
    "where": ([(np.asarray(_x(2, 5)) > 0).astype("float64"),
               _x(2, 5), _x(2, 5)], {}),
    "sort": ([_x(2, 5)], {"axis": 1}),
    # indexing / gather
    "take": ([_x(5, 3), np.array([0., 2., 4.])], {}, {"wrt": (0,)}),
    "batch_take": ([_x(3, 4), np.array([0., 2., 1.])], {},
                   {"wrt": (0,)}),
    "pick": ([_x(3, 4), np.array([0., 2., 1.])], {"axis": 1},
             {"wrt": (0,)}),
    "gather_nd": ([_x(4, 3), np.array([[0., 2.], [1., 0.]])], {},
                  {"wrt": (0,)}),
    "scatter_nd": ([_x(2,), np.array([[1., 3.]])], {"shape": (5,)},
                   {"wrt": (0,)}),
    "Embedding": ([np.array([[0., 2.], [1., 3.]]), _x(4, 3)],
                  {"input_dim": 4, "output_dim": 3}, {"wrt": (1,)}),
    "ones_like": ([_x(2, 5)], {}),
    "zeros_like": ([_x(2, 5)], {}),
    # matmul
    "dot": ([_x(3, 4), _x(4, 2)], {}),
    "batch_dot": ([_x(2, 3, 4), _x(2, 4, 2)], {}),
    # softmax family
    "softmax": ([_x(2, 5)], {}),
    "log_softmax": ([_x(2, 5)], {}),
    "SoftmaxActivation": ([_x(2, 5)], {}),
    "softmax_cross_entropy": ([_x(3, 4), np.array([0., 2., 1.])], {},
                              {"wrt": (0,)}),
    "_contrib_TokenCrossEntropy": ([_x(3, 4), np.array([0., 2., 1.])], {},
                                   {"wrt": (0,)}),
    # neural layers
    "Activation": ([_x(2, 5)], {"act_type": "relu"}),
    "LeakyReLU": ([_x(2, 5)], {"act_type": "leaky", "slope": 0.1}),
    "FullyConnected": ([_x(3, 4), _x(2, 4), _x(2)], {"num_hidden": 2}),
    "Convolution": ([_x(1, 2, 5, 5), _x(2, 2, 3, 3) * 0.3],
                    {"kernel": (3, 3), "num_filter": 2, "no_bias": True}),
    "Deconvolution": ([_x(1, 2, 4, 4), _x(2, 2, 3, 3) * 0.3],
                     {"kernel": (3, 3), "num_filter": 2, "no_bias": True}),
    "Pooling": ([_x(1, 2, 4, 4)],
                {"kernel": (2, 2), "stride": (2, 2), "pool_type": "avg"}),
    "LayerNorm": ([_x(2, 6), _pos(6), _x(6)], {}),
    # weighted: under a plain sum loss the instance-norm data/gamma
    # gradients are IDENTICALLY zero (mean subtraction), so the plain
    # check compares f32 forward noise to ~0 at the tolerance boundary
    "InstanceNorm": ([_x(1, 2, 4, 4), _pos(2), _x(2)], {},
                     {"weighted": True}),
    "L2Normalization": ([_x(2, 6)], {}),
    "LRN": ([_x(1, 3, 4, 4)], {"nsize": 3}),
    "UpSampling": ([_x(1, 2, 3, 3)],
                   {"scale": 2, "sample_type": "nearest", "num_args": 1}),
    "MakeLoss": ([_pos(2, 3)], {}),
    "SequenceReverse": ([_x(3, 2, 4)], {}),
    "SequenceLast": ([_x(3, 2, 4)], {}),
    "SequenceMask": ([_x(3, 2, 4)], {}),
    "ROIPooling": ([_x(1, 2, 6, 6), np.array([[0., 0., 0., 3., 3.]])],
                   {"pooled_size": (2, 2), "spatial_scale": 1.0},
                   {"wrt": (0,)}),
    # spatial / attention
    "GridGenerator": ([_unit(1, 6) * 0.5],
                      {"transform_type": "affine", "target_shape": (4, 4)}),
    "BilinearSampler": ([_x(1, 2, 5, 5), _unit(1, 2, 4, 4) * 0.7], {}),
    "SpatialTransformer": ([_x(1, 2, 5, 5), _unit(1, 6) * 0.5],
                           {"transform_type": "affine",
                            "sampler_type": "bilinear",
                            "target_shape": (4, 4)}),
    "Correlation": ([_x(1, 2, 5, 5), _x(1, 2, 5, 5)],
                    {"kernel_size": 1, "max_displacement": 1,
                     "stride1": 1, "stride2": 1, "pad_size": 1}),
    "_contrib_FlashAttention": ([_x(1, 4, 2, 3), _x(1, 4, 2, 3),
                                 _x(1, 4, 2, 3)], {}),
    "_contrib_RingAttention": ([_x(1, 4, 2, 3), _x(1, 4, 2, 3),
                                _x(1, 4, 2, 3)], {}),
    "_contrib_count_sketch": ([_x(2, 6), np.array([0., 3., 1., 2., 5., 4.]),
                               np.array([1., -1., 1., 1., -1., 1.])],
                              {"out_dim": 4}, {"wrt": (0,)}),
    # appended entries (keep them LAST: the _x/_pos/_unit helpers share
    # one RNG stream in dict-literal order, so inserting mid-dict would
    # silently reroll every later case's data)
    "squeeze": ([_x(2, 1, 5)], {"axis": 1}),
    "RMSNorm": ([_x(2, 6), _pos(6)], {}),
    "_contrib_CausalConv1D": ([_x(2, 5, 4), _x(4, 3)], {"kernel": 3}),
    "_contrib_RotaryEmbedding": ([_x(1, 4, 2, 6)], {"base": 100.0}),
    "_contrib_GatedRMSNorm": ([_x(2, 3, 2, 4), _x(2, 3, 2, 4), _pos(4)], {}),
    "_contrib_KDAGate": ([_x(2, 3, 8), _x(2), _x(8)], {"num_heads": 2}),
}

# every other registered op must appear here, with the reason it has no
# finite-difference case
SKIP = {
    # loss heads: backward is the reference-defined rule ((p - label),
    # sign, margin...), intentionally NOT the derivative of the forward
    "SoftmaxOutput": "custom head grad (p - onehot), not d(forward)",
    "LinearRegressionOutput": "custom head grad (pred - label)",
    "MAERegressionOutput": "custom head grad sign(pred - label)",
    "LogisticRegressionOutput": "custom head grad (sigmoid - label)",
    "SVMOutput": "custom head grad (margin rule)",
    "LSoftmax": "custom head grad (margin-scaled rows)",
    "_contrib_CTCLoss": "grad is the CTC beta recursion; covered by "
                        "tests/test_ctc_example.py numeric check",
    # stochastic / constant / integer-valued
    "Dropout": "stochastic mask",
    "_random_exponential": "stochastic", "_random_gamma": "stochastic",
    "_random_generalized_negative_binomial": "stochastic",
    "_random_negative_binomial": "stochastic",
    "_random_normal": "stochastic", "_random_poisson": "stochastic",
    "_random_uniform": "stochastic",
    "_arange": "no inputs", "_ones": "no inputs", "_zeros": "no inputs",
    "one_hot": "its only input is an index array (wrt would be empty)",
    "_full": "no inputs",
    "argmax": "integer output", "argmin": "integer output",
    "argsort": "integer output", "argmax_channel": "integer output",
    "topk": "integer (index) output",
    "_equal": "boolean output", "_not_equal": "boolean output",
    "_greater": "boolean output", "_greater_equal": "boolean output",
    "_lesser": "boolean output", "_lesser_equal": "boolean output",
    "_equal_scalar": "boolean output",
    "_not_equal_scalar": "boolean output",
    "_greater_scalar": "boolean output",
    "_greater_equal_scalar": "boolean output",
    "_lesser_scalar": "boolean output",
    "_lesser_equal_scalar": "boolean output",
    "broadcast_equal": "boolean output",
    "broadcast_not_equal": "boolean output",
    "broadcast_greater": "boolean output",
    "broadcast_greater_equal": "boolean output",
    "broadcast_lesser": "boolean output",
    "broadcast_lesser_equal": "boolean output",
    "broadcast_mod": "discontinuous in denominator",
    "_mod_scalar": "discontinuous at wrap points",
    # optimizer kernels are in-place update rules, not graph ops
    "sgd_update": "optimizer kernel", "sgd_mom_update": "optimizer kernel",
    "adam_update": "optimizer kernel", "rmsprop_update": "optimizer kernel",
    "rmspropalex_update": "optimizer kernel",
    # composite/stateful ops with dedicated gradient tests elsewhere
    "BatchNorm": "train-mode stats backward covered exhaustively by "
                 "tests/test_batchnorm_grad.py",
    "RNN": "fused cell backward covered by tests/test_rnn.py parity",
    "_contrib_SwitchMoE": "router+dispatch grads covered by "
                          "tests/test_moe.py sharded-parity",
    "_contrib_TopKMoE": "discrete top-k routing with an aux state; values "
                        "and gradients against the plain reference in "
                        "tests/test_lfm2_moe.py",
    "_contrib_GatedDeltaRule": "a scan with a custom backward; values and all "
                               "five gradients against the token-by-token "
                               "recurrence in tests/test_kimi_linear.py",
    "_contrib_SSDScan": "a scan with a custom backward; values and all six "
                        "gradients against the token-by-token recurrence in "
                        "tests/test_nemotron_h.py",
    "Custom": "user-defined python op",
    "BlockGrad": "gradient blocked by definition (backward is zero, "
                 "forward is identity)",
    "IdentityAttachKLSparseReg": "backward attaches the KL sparsity "
                                 "penalty grad, not d(forward=identity)",
    # non-differentiable detection/quantization pipelines
    "_contrib_MultiBoxDetection": "NMS pipeline (discrete)",
    "_contrib_MultiBoxPrior": "constant prior boxes",
    "_contrib_MultiBoxTarget": "matching pipeline (discrete)",
    "_contrib_Proposal": "NMS pipeline (discrete)",
    "_contrib_quantize": "discrete quantization",
    "_contrib_dequantize": "inverse of discrete quantization",
    "_contrib_fft": "complex-interleaved output; forward-only parity op",
    "_contrib_ifft": "complex-interleaved input; forward-only parity op",
}


def test_registry_fully_classified():
    """Every registered op has a gradient case or an explicit skip."""
    # sibling suites register `_test_*` probe ops into the process-wide
    # registry (test_analysis duplicate/shape-rule probes) and leave
    # them behind; they are not product ops, and counting them made
    # this sweep fail run-order-dependently in the full tier-1 run
    ops = {o for o in registry.list_ops() if not o.startswith("_test_")}
    classified = set(CASES) | set(SKIP)
    missing = ops - classified
    stale = classified - ops
    assert not missing, "unclassified ops (add a CASE or SKIP): %s" \
        % sorted(missing)
    assert not stale, "stale entries for unregistered ops: %s" \
        % sorted(stale)
    assert not (set(CASES) & set(SKIP))


@pytest.mark.parametrize("op_name", sorted(CASES))
def test_numeric_gradient(op_name):
    case = CASES[op_name]
    arrays, attrs = case[0], case[1]
    kwargs = case[2] if len(case) > 2 else {}
    check_numeric_gradient(op_name, [np.array(a, "float64", copy=True)
                                     for a in arrays],
                           attrs=attrs, **kwargs)
