"""Nemotron-H (PR 38): the Mamba-2 scan against the token-by-token
recurrence, the two ops that grew (a convolution's bias, the gated norm's
second form), ungated experts against a masked loop with the shares adding
up, the streamed flash backward at 16 query heads a key/value head, and the
toy model through ``ShardedTrainer`` against the plain reference
(``benchmark/references/nemotron-3-nano-30b-a3b.py``), all at toy size on
the CPU.
"""
import functools
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import nemotron_h
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops import ssd
from mxnet_tpu.parallel import moe
from mxnet_tpu.parallel import mesh as pmesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
#: the benchmark's harness (``benchmark/run.py``) and the plain reference,
#: set for this file's tests by ``_benchmark_modules``
run = REF = None


@pytest.fixture(scope="module", autouse=True)
def _benchmark_modules():
    """The benchmark's modules, importable while this file's tests run and
    gone after them (``tests/test_lfm2_moe.py`` has why)."""
    global run, REF
    path, before = list(sys.path), dict(sys.modules)
    shadowed = {name: sys.modules.pop(name) for name in ("common", "run")
                if name in sys.modules}
    sys.path[:0] = [BENCH, os.path.join(BENCH, "references")]
    import run as harness
    run = harness
    REF = harness.load_module("references", "nemotron-3-nano-30b-a3b")
    yield
    sys.path[:] = path
    for name, mod in list(sys.modules.items()):
        if name not in before and \
                (getattr(mod, "__file__", None) or "").startswith(BENCH):
            del sys.modules[name]
    sys.modules.update(shadowed)


def _rand(*shape, seed=0, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


# --------------------------------------------------- the state-space scan
def _recurrence(x, dt, b, c, a_log, d):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
    x_t``, one position after another, a head's own copy of its group."""
    heads = x.shape[2]
    bh, ch = (jnp.repeat(m, heads // m.shape[2], axis=2) for m in (b, c))
    a = -jnp.exp(a_log)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) \
            + d[:, None] * x_t

    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[3:], jnp.float32)
    _, y = jax.lax.scan(step, zero,
                        [jnp.moveaxis(v, 1, 0) for v in (x, dt, bh, ch)])
    return jnp.moveaxis(y, 0, 1)


#: positions, heads, groups, chunk, the largest step: 1, 2 and 5 chunks;
#: 1, 2 and 8 groups; 8, 2 and 1 heads a group; chunks of 128 and 64.  A
#: step is at most 0.1 here, as the model's initialisation draws it
SCANS = {"1chunk_8heads_a_group": (128, 8, 1, 128, 0.1),
         "2chunks_1head_a_group": (256, 8, 8, 128, 0.1),
         "5chunks_2groups": (640, 4, 2, 128, 0.1),
         "5chunks_of_64": (320, 8, 1, 64, 0.1),
         # a chunk's decay passes exp(-100): steps up to 3 under rates up
         # to 16
         "strong_decay": (256, 4, 2, 128, 3.0),
         # whole stretches of positions with a step of exactly 0
         "zero_steps": (256, 4, 2, 128, 0.0)}
INPUTS = ("x", "dt", "B", "C", "A_log", "D")


def _scan_inputs(t, heads, groups, high, dtype=jnp.float32, batch=2):
    rng = np.random.RandomState(t + heads)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    if high:
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(high), (batch, t, heads)))
    else:
        dt = rng.uniform(0.0, 0.1, (batch, t, heads))
        dt[:, 40:170] = 0.0
    return (f(batch, t, heads, 8).astype(dtype), jnp.asarray(dt, jnp.float32),
            f(batch, t, groups, 16).astype(dtype),
            f(batch, t, groups, 16).astype(dtype),
            jnp.log(jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)),
            f(heads))


@functools.lru_cache(maxsize=None)
def _scan_both(name):
    """(chunked, recurrence), each ``(y, gradients of sum(y * w))``."""
    t, heads, groups, chunk, high = SCANS[name]
    args = _scan_inputs(t, heads, groups, high)
    w = _rand(*args[0].shape, seed=5)
    out = []
    for fn in (functools.partial(ssd.ssd_scan, chunk=chunk), _recurrence):
        y, pull = jax.vjp(fn, *args)
        out.append((y,) + pull(w))
    return out


@pytest.mark.parametrize("what", ("y",) + INPUTS)
@pytest.mark.parametrize("name", sorted(SCANS))
def test_ssd_scan_matches_the_recurrence(name, what):
    """Float32, against the largest entry: 1e-5 at the steps the model draws
    (3e-5 for ``dt`` and ``A_log``, whose gradients sum cancelling terms over
    every position of a head: the recurrence's own float32 sum moves by as
    much when its blocking does).  Under the strong decay the running sums
    inside a chunk reach 6000, where a float32 holds 5e-4: the differences
    under the mask are that exact and no more (the recurrence multiplies its
    decays one by one and is); what the test holds there is that everything
    is finite and equal to 1e-3."""
    got, want = (side[(("y",) + INPUTS).index(what)]
                 for side in _scan_both(name))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.isfinite(np.asarray(got)).all()
    scale = float(jnp.max(jnp.abs(want)))
    tol = 1e-3 if name == "strong_decay" else \
        3e-5 if what in ("dt", "A_log") else 1e-5
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


def test_ssd_scan_strong_decay_is_what_the_case_says():
    t, heads, groups, chunk, high = SCANS["strong_decay"]
    _x, dt, _b, _c, a_log, _d = _scan_inputs(t, heads, groups, high)
    log_decay = jnp.sum((dt * -jnp.exp(a_log)).reshape(2, -1, chunk, heads),
                        axis=2)
    assert float(jnp.min(log_decay)) < -100.0


def test_ssd_scan_zero_steps_pass_the_state_unchanged():
    """With a step of 0 nothing is written and nothing decays: the output
    there is the kept state read by ``C_t``, plus ``D x_t``."""
    t, heads, groups, chunk, high = SCANS["zero_steps"]
    x, dt, b, c, a_log, d = _scan_inputs(t, heads, groups, high)
    y = ssd.ssd_scan(x, dt, b, c, a_log, d, chunk=chunk)
    # the state after position 39, by the recurrence
    a = -jnp.exp(a_log)
    state = jnp.zeros((2, heads, 8, 16))
    bh = jnp.repeat(b, heads // groups, axis=2)
    for i in range(40):
        state = jnp.exp(dt[:, i] * a)[..., None, None] * state \
            + (dt[:, i, :, None] * x[:, i])[..., None] * bh[:, i, :, None, :]
    ch = jnp.repeat(c, heads // groups, axis=2)
    want = jnp.einsum("bhpn,bthn->bthp", state, ch[:, 40:170]) \
        + d[:, None] * x[:, 40:170]
    np.testing.assert_allclose(y[:, 40:170], want, rtol=1e-5, atol=1e-5)


def test_ssd_scan_in_bfloat16_follows_the_float32_result():
    """Operands of the four products in bfloat16 (8 bits of mantissa), decay
    and state float32: within 2% of the largest entry of the float32 result,
    forward and every gradient."""
    t, heads, groups, chunk, high = SCANS["5chunks_2groups"]
    args = _scan_inputs(t, heads, groups, high)
    low = _scan_inputs(t, heads, groups, high, jnp.bfloat16)
    w = _rand(*args[0].shape, seed=5)
    fn = functools.partial(ssd.ssd_scan, chunk=chunk)
    y32, pull32 = jax.vjp(fn, *args)
    y16, pull16 = jax.vjp(fn, *low)
    assert y16.dtype == jnp.bfloat16
    for got, want in zip((y16,) + pull16(w.astype(jnp.bfloat16)),
                         (y32,) + pull32(w)):
        assert got.shape == want.shape
        gap = jnp.max(jnp.abs(got.astype(jnp.float32) - want))
        assert float(gap) <= 0.02 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("t,chunk,states", [(1024, 128, 2), (640, 128, 5),
                                            (512, 64, 1), (128, 128, 1)])
def test_ssd_scan_keeps_one_state_a_head_and_group_of_chunks(t, chunk, states):
    """The backward's residuals are the inputs and the state each group of
    chunks was handed; the plan says their bytes."""
    x, dt, b, c, a_log, d = _scan_inputs(t, 4, 2, 0.1, batch=1)
    with ssd.plan_recording():
        res = jax.eval_shape(
            lambda *a: jax.vjp(functools.partial(ssd.ssd_scan, chunk=chunk),
                               *a)[1], x, dt, b, c, a_log, d)
    plan = ssd.last_plan_summary()
    assert plan["chunked_layers"] == 1
    layer = plan["layers"][0]
    assert (layer["heads"], layer["head_dim"], layer["state"],
            layer["groups"], layer["positions"],
            layer["chunk"]) == (4, 8, 16, 2, t, chunk)
    assert layer["group"] == t // states <= max(ssd.GROUP, chunk)
    assert plan["state_bytes"] == layer["state_bytes"] \
        == 4 * 4 * 8 * 16 * states
    kept = [leaf for leaf in jax.tree.leaves(res) if leaf.ndim == 6]
    assert [leaf.shape for leaf in kept] == [(states, 1, 2, 2, 8, 16)]
    assert sum(leaf.size * 4 for leaf in kept) == plan["state_bytes"]
    # nothing of (chunk, chunk) a head, no state a chunk
    assert max(leaf.size for leaf in jax.tree.leaves(res)) \
        <= max(x.size, kept[0].size)


def test_ssd_scan_refuses_a_ragged_sequence_and_uneven_groups():
    x, dt, b, c, a_log, d = _scan_inputs(100, 4, 2, 0.1)
    with pytest.raises(ValueError, match="100 positions are not a whole "
                                         "number of chunks of 64"):
        ssd.ssd_scan(x, dt, b, c, a_log, d, chunk=64)
    with pytest.raises(MXNetError, match="whole number of chunks"):
        mx.nd._contrib_SSDScan(*(mx.nd.array(np.asarray(v))
                                 for v in (x, dt, b, c, a_log, d, d)),
                               chunk_size=64)
    with pytest.raises(MXNetError, match="heads a multiple of groups"):
        mx.nd._contrib_SSDScan(*(mx.nd.array(np.asarray(v)) for v in (
            x[:, :64, :3], dt[:, :64, :3], b[:, :64], c[:, :64], a_log[:3],
            d[:3], d[:3])), chunk_size=64)


def test_ssd_scan_op_takes_the_bias_under_the_softplus():
    x, dt, b, c, a_log, d = _scan_inputs(64, 4, 2, 0.1)
    bias = _rand(4, seed=9)
    nd = lambda v: mx.nd.array(np.asarray(v))               # noqa: E731
    got = mx.nd._contrib_SSDScan(nd(x), nd(dt), nd(b), nd(c), nd(a_log),
                                 nd(d), nd(bias), chunk_size=32).asnumpy()
    want = _recurrence(x, jax.nn.softplus(dt + bias), b, c, a_log, d)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ssd_scan_shards_over_batch_and_heads_or_refuses():
    """Under a mesh of more than one device the scan runs inside a
    ``shard_map`` over (batch, groups of heads) and gives what it gives
    alone; a mesh that divides neither is refused, not replicated."""
    x, dt, b, c, a_log, d = _scan_inputs(128, 4, 2, 0.1)
    w = _rand(*x.shape, seed=5)
    fn = functools.partial(ssd.ssd_scan, chunk=64)
    y, pull = jax.vjp(fn, x, dt, b, c, a_log, d)
    mesh = pmesh.build_mesh(devices=jax.devices()[:4], tp=2)
    with pmesh.kernel_mesh(mesh):
        y_m, pull_m = jax.vjp(fn, x, dt, b, c, a_log, d)
        text = str(jax.make_jaxpr(fn)(x, dt, b, c, a_log, d))
        for got, want in zip((y_m,) + pull_m(w), (y,) + pull(w)):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert "shard_map" in text
        with pytest.raises(ValueError, match="divides neither the batch of 1 "
                                             "nor the 1 groups"):
            fn(x[:1], dt[:1], b[:1, :, :1], c[:1, :, :1], a_log, d)


# --------------------------------------------------- the two ops that grew
def test_causal_conv_default_is_todays_and_its_bias_is_added():
    x, w, bias = _rand(2, 24, 6, seed=1), _rand(6, 4, seed=2), _rand(6, seed=3)


    def taps(x, w):         # the op's body as the parent commit had it
        xp = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        return sum(xp[:, j:j + 24, :].astype(jnp.float32)
                   * w.astype(jnp.float32)[:, j] for j in range(4))

    nd = lambda v: mx.nd.array(np.asarray(v))               # noqa: E731
    for act, f in (("", lambda v: v), ("silu", jax.nn.silu)):
        got = mx.nd._contrib_CausalConv1D(nd(x), nd(w), kernel=4,
                                          act_type=act).asnumpy()
        want = jax.jit(lambda x, w: f(taps(x, w)).astype(x.dtype))(x, w)
        assert (got == np.asarray(want)).all()               # bit for bit
        got = mx.nd._contrib_CausalConv1D(nd(x), nd(w), nd(bias), kernel=4,
                                          act_type=act, no_bias=False).asnumpy()
        np.testing.assert_allclose(got, f(taps(x, w) + bias), rtol=1e-6,
                                   atol=1e-6)
    net = mx.sym._contrib_CausalConv1D(mx.sym.Variable("data"), kernel=4,
                                       name="conv")
    assert net.list_arguments() == ["data", "conv_weight"]
    net = mx.sym._contrib_CausalConv1D(mx.sym.Variable("data"), kernel=4,
                                       no_bias=False, name="conv")
    assert net.list_arguments() == ["data", "conv_weight", "conv_bias"]
    assert net.infer_shape(data=(2, 24, 6))[0] == [(2, 24, 6), (6, 4), (6,)]
    with pytest.raises(MXNetError, match=r"\(channels,\) bias"):
        mx.nd._contrib_CausalConv1D(nd(x), nd(w), nd(bias[:5]), kernel=4,
                                    no_bias=False)


def test_gated_norm_default_is_todays_and_its_second_form_is_mamba2s():
    x, gate = _rand(2, 5, 4, 8, seed=1), _rand(2, 5, 4, 8, seed=2)
    rms = lambda v: v * jax.lax.rsqrt(                       # noqa: E731
        jnp.mean(jnp.square(v), axis=-1, keepdims=True) + 1e-5)
    nd = lambda v: mx.nd.array(np.asarray(v))               # noqa: E731
    gamma = _rand(8, seed=3)
    got = mx.nd._contrib_GatedRMSNorm(nd(x), nd(gate), nd(gamma)).asnumpy()
    assert (got == np.asarray(rms(x) * gamma * jax.nn.sigmoid(gate))).all()
    wide = _rand(4, 8, seed=4)
    got = mx.nd._contrib_GatedRMSNorm(
        nd(x), nd(gate), nd(wide), gate_act="silu", gate_first=True,
        gamma_axes=2).asnumpy()
    np.testing.assert_allclose(got, rms(x * jax.nn.silu(gate)) * wide,
                               rtol=1e-6, atol=1e-6)
    got = mx.nd._contrib_GatedRMSNorm(nd(x), nd(gate), nd(gamma),
                                      gate_act="silu").asnumpy()
    np.testing.assert_allclose(got, rms(x) * gamma * jax.nn.silu(gate),
                               rtol=1e-6, atol=1e-6)
    net = mx.sym._contrib_GatedRMSNorm(
        mx.sym.Variable("data"), mx.sym.Variable("gate"), gamma_axes=2,
        name="n")
    assert net.infer_shape(data=(2, 5, 4, 8), gate=(2, 5, 4, 8))[0][2] == (4, 8)
    with pytest.raises(MXNetError, match="last two axes"):
        mx.nd._contrib_GatedRMSNorm(nd(x), nd(gate), nd(gamma), gamma_axes=2)
    with pytest.raises(MXNetError, match="neither sigmoid nor silu"):
        mx.nd._contrib_GatedRMSNorm(nd(x), nd(gate), nd(gamma), gate_act="tanh")


def test_activation_knows_relu2():
    x = _rand(3, 7, seed=1)
    got = mx.nd.Activation(mx.nd.array(np.asarray(x)), act_type="relu2")
    assert (got.asnumpy() == np.asarray(jnp.square(jax.nn.relu(x)))).all()


# ------------------------------------------------------- ungated experts
E, K, D_MODEL, FF = 16, 3, 16, 24


def _layer_params(seed=0):
    return {"moe_router_weight": _rand(E, D_MODEL, seed=seed + 1, scale=0.5),
            "moe_expert_bias": _rand(E, seed=seed + 2, scale=0.1),
            "moe_w1_weight": _rand(E, FF, D_MODEL, seed=seed + 3, scale=0.3),
            "moe_w2_weight": _rand(E, FF, D_MODEL, seed=seed + 4, scale=0.3),
            "shared_w1_weight": _rand(2 * FF, D_MODEL, seed=seed + 5, scale=0.3),
            "shared_w2_weight": _rand(D_MODEL, 2 * FF, seed=seed + 6, scale=0.3)}


def _share_cfg(held, offset=0):
    return {"num_experts_per_tok": K, "n_routed_experts": held,
            "expert_offset": offset, "router_num_experts": E,
            "router_trained": True, "norm_topk_prob": True,
            "routed_scaling_factor": 2.5, "n_shared_experts": 1}


def _program_share(x, p, held, offset=0):
    return moe.topk_moe(
        x, p["moe_router_weight"], p["moe_expert_bias"],
        p["moe_w1_weight"][offset:offset + held], None,
        p["moe_w2_weight"][offset:offset + held], K, expert_offset=offset,
        routed_scaling_factor=2.5)[0]


@pytest.mark.parametrize("what", ["values", "x", "w1", "w2"])
@pytest.mark.parametrize("held", [16, 4, 1], ids=["all", "a_quarter", "1_of_16"])
def test_relu2_experts_match_the_masked_loop(held, what):
    """``topk_moe`` with ``w3=None`` against the reference's loop over the
    held experts with a mask: outputs and gradients, with every expert, a
    quarter (the whole buffer) and one of sixteen held (the bounded buffer:
    four times the even load, which one expert of this routing stays under)."""
    x, p = _rand(48, D_MODEL, seed=7), _layer_params()
    cot = _rand(48, D_MODEL, seed=8)

    def program(x, w1, w2):
        return moe.topk_moe(x, p["moe_router_weight"], p["moe_expert_bias"],
                            w1, None, w2, K, routed_scaling_factor=2.5)[0]

    def reference(x, w1, w2):
        return REF.expert_layer(
            x, dict(p, moe_w1_weight=w1, moe_w2_weight=w2), _share_cfg(held))

    args = (x, p["moe_w1_weight"][:held], p["moe_w2_weight"][:held])
    if what == "values":
        got, want = program(*args), reference(*args)
    else:
        i = ("x", "w1", "w2").index(what)
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * cot), argnums=i)(*args)
                     for f in (program, reference))
    assert moe.buffer_rows(48, K, held, E) == (48 * K if held > 1 else 40)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "silu_gated"])
def test_experts_width_is_padded_to_whole_tiles_for_the_products(gated):
    """An ungated width over one tile of 256 that is no whole number of them
    (264 here, 1856 in the cell) is zero-padded for the grouped products:
    values and gradients are what the unpadded formula gives; 256 and 24 are
    left alone, and so is every width of gated experts."""
    d, ff, held = 16, 264, 3
    xs, cot = _rand(24, d, seed=1), _rand(24, d, seed=2)
    w1 = _rand(held, ff, d, seed=3, scale=0.3)
    w3 = _rand(held, d, ff, seed=4, scale=0.3) if gated else None
    w2 = _rand(held, ff, d, seed=5, scale=0.3)
    sizes = jnp.asarray([10, 6, 8], jnp.int32)
    first = jnp.swapaxes(w1, 1, 2)

    def plain(xs, w2):
        up = jax.lax.ragged_dot(xs, first, sizes)
        h = jax.nn.silu(up) * jax.lax.ragged_dot(xs, w3, sizes) if gated \
            else jnp.square(jax.nn.relu(up))
        return jax.lax.ragged_dot(h, w2, sizes)

    def padded(xs, w2):
        return moe._experts(xs, first if gated else w1, w3, w2, sizes)

    np.testing.assert_allclose(padded(xs, w2), plain(xs, w2), rtol=1e-5, atol=1e-5)
    for i in (0, 1):
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * cot), argnums=i)(xs, w2)
                     for f in (padded, plain))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if gated:
        assert " pad[" not in str(jax.make_jaxpr(padded)(xs, w2))
        return
    text = lambda ff: str(jax.make_jaxpr(lambda xs: moe._experts(  # noqa: E731
        xs, _rand(held, ff, d), None, _rand(held, ff, d), sizes))(xs))
    assert "f32[24,512]" in text(264) and " pad[" in text(264)
    assert all(" pad[" not in text(ff) and "f32[24,%d]" % ff in text(ff)
               for ff in (256, 24))


def test_relu2_layer_says_six_products_and_gated_nine():
    x, p = _rand(48, D_MODEL, seed=7), _layer_params()
    with moe.plan_recording():
        _program_share(x, p, 4)
        moe.topk_moe(x, p["moe_router_weight"], None,
                     jnp.swapaxes(p["moe_w1_weight"], 1, 2),
                     jnp.swapaxes(p["moe_w1_weight"], 1, 2),
                     p["moe_w2_weight"], K)
    plan = moe.last_plan_summary()
    assert [x["products_trained"] for x in plan["layers"]] == [6, 9]
    # the first holds 4 of 16: two sizes, and the compiled text holds both
    # branches' products, twice what a step runs; the second holds them all
    assert [x["small_rows"] is None for x in plan["layers"]] == [False, True]

    class Compiled:
        def __init__(self, n):
            self.n = n

        def as_text(self):
            return "\n".join("  %%ragged-dot-none.%d = f32[8] custom-call(%%x)"
                             % i for i in range(self.n))

    for products, layers in ((21, 2), (20, 1), (12, 1), (11, 0)):
        moe.note_compiled(Compiled(products))
        assert (moe.last_plan_summary()["grouped_products"],
                moe.last_plan_summary()["grouped_layers"]) == (products, layers)


@pytest.mark.parametrize("what", ["values", "input_gradients"])
def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(what):
    """``model-configs`` section 4's one test: the parts that all 16 shares
    of one expert each give, with the shared expert (which every chip computes
    alike) counted once, sum to the uncut reference's result for the layer."""
    x, p = _rand(40, D_MODEL, seed=9), _layer_params(seed=20)

    def shares(x):
        return sum(_program_share(x, p, 1, off) for off in range(E)) \
            + REF.shared_expert(x, p)

    def uncut(x):
        return REF._experts(x, p, _share_cfg(E), None)

    if what == "values":
        got, want = shares(x), uncut(x)
    else:
        cot = _rand(40, D_MODEL, seed=11)
        got, want = (jax.grad(lambda x: jnp.sum(f(x) * cot))(x)
                     for f in (shares, uncut))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_topk_moe_op_takes_relu2_experts_without_a_third_matrix():
    net = mx.sym._contrib_TopKMoE(
        mx.sym.Variable("data"), num_experts=E, experts_held=4,
        num_experts_per_tok=K, hidden_size=FF, expert_act="relu2", name="moe")
    assert net.list_arguments() == ["data", "moe_router_weight",
                                    "moe_expert_bias", "moe_w1_weight",
                                    "moe_w2_weight"]
    shapes = net.infer_shape(data=(2, 8, D_MODEL))[0]
    assert shapes[3:] == [(4, FF, D_MODEL), (4, FF, D_MODEL)]
    gated = mx.sym._contrib_TopKMoE(
        mx.sym.Variable("data"), num_experts=E, experts_held=4,
        num_experts_per_tok=K, hidden_size=FF, name="moe")
    assert gated.list_arguments()[3:] == ["moe_w1_weight", "moe_w3_weight",
                                          "moe_w2_weight"]
    assert gated.infer_shape(data=(2, 8, D_MODEL))[0][3:] == [
        (4, D_MODEL, FF), (4, D_MODEL, FF), (4, FF, D_MODEL)]
    with pytest.raises(MXNetError, match="neither silu_gated nor relu2"):
        mx.sym._contrib_TopKMoE(
            mx.sym.Variable("data"), num_experts=E, num_experts_per_tok=K,
            hidden_size=FF, expert_act="gelu", name="moe").infer_shape(
                data=(2, 8, D_MODEL))


# --------------------------------- 16 query heads a key/value head, backward
T = 64
#: (block_q, block_k): four K/V tiles streamed
STREAM = (8, 16)


@pytest.mark.parametrize("parts", [1, 2, 4])
@pytest.mark.parametrize("heads", [(32, 2), (16, 1)], ids=["32over2", "16over1"])
def test_streamed_backward_in_parts_matches_the_plain_formula(monkeypatch,
                                                              heads, parts):
    """The streamed backward (interpret mode) at 16 query heads a key/value
    head, the group run whole and in 2 and 4 equal parts whose float32 dK /
    dV are summed: all three gradients against ``_attention_jnp``."""
    hq, hk = heads
    q, k, v, g = (_rand(2, T, hq, 16, seed=1), _rand(2, T, hk, 16, seed=2),
                  _rand(2, T, hk, 16, seed=3), _rand(2, T, hq, 16, seed=4))
    monkeypatch.setattr(pk, "_group_parts", lambda *a, **kw: parts)
    o, lse = pk._flash_attention_fwd_pallas(q, k, v, True, True, blocks=STREAM)
    with pk.causal_plan_recording():
        got = pk._flash_attention_bwd_pallas(q, k, v, o, lse, g, True, True,
                                             blocks=STREAM)
    assert pk.last_causal_plan()["kernels"][0]["group_parts"] == parts
    want, pull = jax.vjp(lambda q, k, v: pk._attention_jnp(q, k, v, True),
                         q, k, v)
    np.testing.assert_allclose(o, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(got, pull(g)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)


#: (t, d, group) -> parts: the cells' groupings run whole; 16 heads of 128 at
#: 8192 positions need 74 MiB and would ask for 111 of the 100 a kernel may
PARTS = [((8192, 128, 16), 2), ((8192, 128, 8), 1), ((8192, 64, 4), 1),
         ((8192, 192, 1), 1), ((2048, 128, 16), 1), ((8192, 128, 32), 4),
         ((16384, 128, 8), 2)]


@pytest.mark.parametrize("shape,parts", PARTS, ids=str)
def test_group_parts_are_the_fewest_whose_accumulator_fits(shape, parts):
    assert pk._group_parts(*shape) == parts
    t, d, group = shape
    fits = lambda n: pk._vmem_request(pk._vmem_need(        # noqa: E731
        d, *pk._blocks(t), group // n * t)) <= pk._VMEM_MAX
    if t > pk._blocks(t)[1]:
        assert fits(parts) and (parts == 1 or not fits(parts // 2))


#: (batch, seq, query heads, key/value heads, head width) -> (Q block, K/V
#: tile), the backward's VMEM request: LFM2's and Trinity-Mini's calls as
#: they were recorded on the parent commit (830a6fc), and Nemotron-H's
RECORDED = [((1, 8192, 32, 8, 64), (512, 2048), 51904512, 1, 1),
            ((1, 8192, 32, 4, 128), (512, 2048), 77070336, 1, 1),
            ((1, 8192, 32, 2, 128), (512, 2048), 77070336, 2, 2)]


@pytest.mark.parametrize("shape,blocks,vmem,parts,calls", RECORDED, ids=str)
def test_grouped_calls_take_the_plan_recorded_before_this_pr(
        monkeypatch, shape, blocks, vmem, parts, calls):
    """Groups of 4 and 8 take the path, blocks, VMEM request and kernel names
    they took; the group of 16 is the group of 8's kernel, called twice."""
    from mxnet_tpu import context
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    b, t, hq, hk, d = shape
    loss = lambda q, k, v: pk.flash_attention(               # noqa: E731
        q, k, v, True).astype(jnp.float32).sum()
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for s in ((b, t, hq, d), (b, t, hk, d), (b, t, hk, d))]
    with pk.causal_plan_recording():
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args))
    kernels = pk.last_causal_plan()["kernels"]
    assert [x["kernel"] for x in kernels] == ["flash_attention_fwd",
                                              "flash_attention_bwd"]
    assert {(x["block_q"], x["block_k"]) for x in kernels} == {blocks}
    assert [x["group_parts"] for x in kernels] == [1, parts]
    assert {x["scores_computed_pct"] for x in kernels} == {53.125}
    assert re.findall(r"name=(mxtpu_flash_\w+)", text) == \
        ["mxtpu_flash_fwd_stream"] + ["mxtpu_flash_bwd_stream"] * calls
    assert re.findall(r"vmem_limit_bytes=(\d+)", text) == [str(vmem)] * calls


# --------------------------------------------------------- the whole model
def _toy_bench():
    bench = dict(run.load_json(run.ROOT, "BENCHMARK.json"))
    bench["configs"] = [{"name": "smoke-nemotron",
                         "file": "benchmark/configs/smoke-nemotron.json"}]
    bench["workloads"] = [{"name": "smoke-nemotron", "config": "smoke-nemotron",
                           "traffic": "smoke-s64-b1-chain2", "chips": 1}]
    return bench


SEED = 2 ** 31 + 38


@pytest.fixture(scope="module")
def toy_cell():
    return run.Cell("smoke-nemotron", _toy_bench())


@pytest.fixture(scope="module")
def both_sides(toy_cell, _benchmark_modules):
    """One run of the toy cell through the harness (``run.run_cell`` on the
    CPU: the reference's and the program's first 1 + chain steps of the five
    layers ``MEM*E`` at width 64, sequence 64, float32, 4 of 16 experts held,
    from the same seeded weights, then a short window), with what the harness
    compared kept.  The reference's attention rows and the recurrence's blocks
    are cut so that its blocking is exercised."""
    import check
    from mxnet_tpu.telemetry import spans
    cell, kept = toy_cell, {}
    compare = check.compare

    def keeping(prog, ref, limits, say=print):
        kept.update(prog=prog, ref=ref)
        return compare(prog, ref, limits, say)

    sizes = cell.refmod.ATTENTION_ROWS, cell.refmod.RECURRENCE_BLOCK
    cell.refmod.ATTENTION_ROWS, cell.refmod.RECURRENCE_BLOCK = 16, 8
    check.compare = keeping
    try:
        result = run.run_cell(cell, seed=SEED, seconds=0.3, trace=0,
                              on_chip=False)
    finally:
        check.compare = compare
        cell.refmod.ATTENTION_ROWS, cell.refmod.RECURRENCE_BLOCK = sizes
    built = [r.attrs for r in spans.records("model.build")]
    return (kept["ref"], kept["prog"], moe.last_plan_summary(),
            ssd.last_plan_summary(), result, built)


def test_model_symbol_is_built_from_the_configuration(toy_cell):
    net, data, label = toy_cell.cfgmod.build(toy_cell.cfg, toy_cell.mix, 1)
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(**data, **label)[0]))
    del shapes["data"], shapes["softmax_label"]
    assert shapes == toy_cell.refmod.param_shapes(toy_cell.cfg)
    assert all(name.endswith(("_weight", "_gamma", "_bias")) for name in shapes)
    assert net.list_auxiliary_states() == ["layer1_moe_load", "layer4_moe_load"]
    nodes = json.loads(net.tojson())["nodes"]
    ops = [n["op"] for n in nodes]
    assert (ops.count("_contrib_SSDScan"), ops.count("_contrib_TopKMoE"),
            ops.count("_contrib_FlashAttention")) == (2, 2, 1)
    assert ops.count("RMSNorm") == 5 + 1            # one a layer, and the last
    assert ops.count("_contrib_RotaryEmbedding") == 0
    assert [n["attrs"]["expert_act"] for n in nodes
            if n["op"] == "_contrib_TopKMoE"] == ["relu2", "relu2"]
    # Module binds such a Symbol too (the mixer alone, for the compile's sake)
    net = toy_cell.cfgmod.build(dict(toy_cell.cfg, num_hidden_layers=1),
                                toy_cell.mix, 1)[0]
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", data["data"])],
             label_shapes=[("softmax_label", label["softmax_label"])])
    mod.init_params(mx.init.Normal(0.02))
    mod.forward(mx.io.DataBatch([mx.nd.zeros(data["data"])],
                                [mx.nd.zeros(label["softmax_label"])]),
                is_train=False)
    assert mod.get_outputs()[0].shape == (64, toy_cell.cfg["vocab_size"])


@pytest.mark.parametrize("change,says", [
    ({"hybrid_override_pattern": "MEX*E"}, "holds 'X'; a layer is one of"),
    ({"hybrid_override_pattern": "ME-*E"}, "holds '-'"),
    ({"num_hidden_layers": 6}, "6 layers, hybrid_override_pattern 'MEM\\*E' "
                               "names 5"),
    ({"mlp_bias": True}, "mlp_bias is not built"),
    ({"mlp_hidden_act": "silu"}, "only relu2 experts"),
    ({"n_groups": 3}, "8 heads over 3 groups")])
def test_model_refuses_what_it_does_not_build(toy_cell, change, says):
    with pytest.raises(MXNetError, match=says):
        nemotron_h.get_symbol(dict(toy_cell.cfg, **change), 64)


def test_model_builds_the_layers_its_pattern_names(toy_cell):
    """A pattern longer than ``num_hidden_layers`` is cut to it, as the cell's
    nine of 52 are."""
    net = nemotron_h.get_symbol(
        dict(toy_cell.cfg, hybrid_override_pattern="*EMEM", num_hidden_layers=3),
        64)
    names = net.list_arguments()
    assert "layer0_q_weight" in names and "layer1_moe_w1_weight" in names \
        and "layer2_in_proj_weight" in names
    assert not any(n.startswith("layer3_") for n in names)


@pytest.mark.parametrize("number,tolerance", [
    ("loss_gap", 1e-4), ("grad_sample_err", 1e-4), ("grad_norm_gap", 1e-4),
    ("delta_norm_gap", 1e-4)])
def test_model_through_sharded_trainer_follows_the_reference(both_sides, number,
                                                            tolerance):
    """Float32 on both sides: three losses, the first gradient element by
    element and by leaf, and the parameters' change after two more steps."""
    import check
    ref, prog = both_sides[:2]
    assert len(ref["losses"]) == len(prog["losses"]) == 3
    values = {n: v for n, v, _ in check.numbers(prog, ref)}
    assert values[number] <= tolerance, values
    worst = max(check.leaf_gaps(prog["grad_norms"], ref["grad_norms"]).values())
    assert worst <= 1e-3, worst


def _first_steps(cell, cfg):
    import traffic
    hb = traffic.host_batch(cell.cfg, cell.mix, 1, SEED)
    session = cell.runner.open(
        cfg, cell.cfgmod, cell.mix, jax.devices()[:1], SEED,
        lambda key: cell.refmod.init_params(cell.cfg, key), run.seed_key(SEED), hb)
    prog = session.first_steps()
    session.close()
    return prog


def test_model_in_bfloat16_stays_inside_a_band_the_fp8_control_leaves(
        toy_cell, both_sides):
    """Under ``dtype="bfloat16"`` the toy model reads 0.0004 on the loss,
    0.008 on the gradient's elements and 0.0003 on the norms (my CPU run, PR
    38): inside 0.005 / 0.03 / 0.003 / 0.003.  The fp8 control (the reference
    with every matmul operand and the recurrence's x, B, C rounded to float8)
    is outside the elements' band at the toy size too."""
    import check
    ref = both_sides[0]
    low = dict(toy_cell.cfg, trainer=dict(toy_cell.cfg["trainer"],
                                          dtype="bfloat16"))
    band = {"loss_gap": 0.005, "grad_sample_err": 0.03,
            "grad_norm_gap": 0.003, "delta_norm_gap": 0.003}
    values = {n: v for n, v, _ in check.numbers(_first_steps(toy_cell, low), ref)}
    assert all(values[n] <= band[n] for n in band), values
    import traffic
    hb = traffic.host_batch(toy_cell.cfg, toy_cell.mix, 1, SEED)
    control = run.reference_first_steps(toy_cell, SEED, hb, 3, jax.devices()[:1],
                                        quant="fp8")
    values = {n: v for n, v, _ in check.numbers(control, ref)}
    assert values["grad_sample_err"] > band["grad_sample_err"], values
    limits = {n: {"limit": v} for n, v in band.items()}
    assert not check.compare(control, ref, limits, say=lambda *_: None)


def test_a_model_with_gated_norm_after_the_statistics_does_not_follow(
        toy_cell, both_sides, monkeypatch):
    """The comparison sees the mechanism: with the gate applied after the
    norm's statistics (the other form of the op) the program is outside what
    float noise explains."""
    import check
    from mxnet_tpu import symbol as sym
    real = sym._contrib_GatedRMSNorm
    monkeypatch.setattr(
        sym, "_contrib_GatedRMSNorm",
        lambda *a, **kw: real(*a, **dict(kw, gate_first=False)))
    values = {n: v for n, v, _ in
              check.numbers(_first_steps(toy_cell, toy_cell.cfg), both_sides[0])}
    assert values["grad_sample_err"] > 1e-2, values


def test_trainer_records_the_plans_and_the_build_span(both_sides):
    experts, scans, built = both_sides[2], both_sides[3], both_sides[5]
    assert experts["expert_layers"] == 2
    assert {(x["buffer_rows"], x["even_rows"], x["products_trained"])
            for x in experts["layers"]} == {(64 * 3, 48.0, 6)}
    assert scans["chunked_layers"] == 2
    assert scans["state_bytes"] == 2 * 4 * 8 * 8 * 16
    assert {"model": "nemotron_h"} in [{"model": b.get("model")} for b in built]


def test_toy_cell_runs_through_the_harness(both_sides):
    result = both_sides[4]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0 and result["device"]["count"] == 1


def test_new_readers_read_the_plans_and_none_without_them(monkeypatch, toy_cell):
    readers = {name: run.load_module("layer_metrics", name) for name in (
        "ssd_chunked_layers", "ssd_state_saved_gb", "moe_products_per_layer",
        "moe_grouped_layers")}
    monkeypatch.setattr(ssd, "last_plan_summary", lambda:
                        {"chunked_layers": 4, "state_bytes": 134217728})
    monkeypatch.setattr(moe, "last_plan_summary", lambda: {
        "expert_layers": 2, "grouped_layers": 2,
        "layers": [{"products_trained": 6}, {"products_trained": 9}]})
    assert readers["ssd_chunked_layers"].read({}) == 4
    assert readers["ssd_state_saved_gb"].read({}) == pytest.approx(0.134217728)
    assert readers["moe_products_per_layer"].read({}) == 9
    assert readers["moe_grouped_layers"].read({}) == 2
    # a program that traced no such layer, and an older one whose plan has no
    # such field: nothing is reported, nothing raises
    monkeypatch.setattr(ssd, "last_plan_summary", lambda: None)
    monkeypatch.setattr(moe, "last_plan_summary", lambda:
                        {"expert_layers": 1, "layers": [{"buffer_rows": 8}]})
    assert readers["ssd_chunked_layers"].read({}) is None
    assert readers["ssd_state_saved_gb"].read({}) is None
    assert readers["moe_products_per_layer"].read({}) is None
    monkeypatch.setitem(sys.modules, "mxnet_tpu.ops.ssd", None)
    assert readers["ssd_chunked_layers"].read({}) is None
    assert readers["ssd_state_saved_gb"].read({}) is None


#: sha256 of the three neighbours' toy Symbols' arguments and auxiliary states
#: as the parent commit (830a6fc) listed them
PARENT_NAMES = {"smoke-lfm2": (37, "968a58ca29b2c60f"),
                "smoke-kimi": (119, "87155419be2a505d"),
                "smoke-trinity": (99, "f366b575983c1c3e")}


@pytest.mark.parametrize("name", sorted(PARENT_NAMES))
def test_neighbours_list_the_parameters_they_listed(name):
    cfg = run.load_json(run.ROOT, "benchmark/configs/%s.json" % name)
    mix = run.load_json(run.HERE, "traffic", "smoke-s64-b1-chain2.json")
    net = run.load_module("configs", cfg["code"]).build(cfg, mix, 1)[0]
    names = net.list_arguments() + net.list_auxiliary_states()
    assert (len(names), hashlib.sha256("\n".join(names).encode())
            .hexdigest()[:16]) == PARENT_NAMES[name]


def test_cell_configuration_keeps_every_published_width():
    cfg = run.load_json(run.ROOT, "benchmark/configs/nemotron-3-nano-30b-a3b.json")
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (9, 8, 16384)
    assert cfg["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128,
                                "vocab_size": 131072}
    assert cfg["hybrid_override_pattern"][:9] == "MEMEM*EME"
    published = {"hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
                 "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
                 "chunk_size": 128, "num_attention_heads": 32,
                 "num_key_value_heads": 2, "head_dim": 128,
                 "moe_intermediate_size": 1856,
                 "moe_shared_expert_intermediate_size": 3712,
                 "num_experts_per_tok": 6, "router_num_experts": 128,
                 "routed_scaling_factor": 2.5}
    assert {k: cfg[k] for k in published} == published
    shapes = REF.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 666963456
    cell = next(w for w in bench["workloads"]
                if w["name"] == "nemotron3nano-fused-s8192")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        cfg["name"], "s8192-b1-chain2", 1)
    cfgmod = run.load_module("configs", "nemotron-3-nano-30b-a3b")
    mix = run.load_json(run.HERE, "traffic", "s8192-b1-chain2.json")
    flops = cfgmod.step_flops(cfg, mix, 1)
    assert 17.4e12 < flops < 17.7e12
    costs = cfgmod.kernel_costs(cfg, mix)
    assert costs["ragged-dot"]["calls"] == 4 * 6
    assert costs["mxtpu.block.ssd"]["calls"] == 4
    assert costs["mxtpu.block.ssd"]["flops"] == pytest.approx(
        3 * 4 * 8192 * 64 * 43008)
