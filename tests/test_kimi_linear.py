"""Kimi Linear (PR 31): the gated delta rule as a chunked scan against the
token-by-token recurrence, flash attention whose value heads have a width of
their own, the expert layer's share-sized buffer, the shared expert, and the
five-layer model through ``ShardedTrainer`` against the plain reference
(``benchmark/references/kimi-linear-48b-a3b.py``), all at toy size on the CPU.
"""
import functools
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import delta_rule
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel import moe

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
    run, REF = harness, harness.load_module("references", "kimi-linear-48b-a3b")
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


def _fcompute(name, attrs, *arrays):
    """An op's lowering itself (differentiable)."""
    from mxnet_tpu.ops.registry import OpContext, get_op
    op = get_op(name)
    return op.fcompute(op.parse_attrs(attrs), OpContext(is_train=True),
                       *arrays)


# ------------------------------------------------- the gated delta rule
def _recurrent(q, k, v, g, beta):
    """The oracle: the reference's token-by-token recurrence, a sequence at
    a time (``q`` scaled, ``q`` and ``k`` normalised by the caller)."""
    return jax.vmap(REF.delta_rule)(q, k, v, g, beta)


def _kda_inputs(t, strongest, beta, repeat=False, heads=3, dk=32, dv=16):
    """Unit keys and queries; log-decays uniform in ``(-strongest, 0)`` with
    four channels of every head held at ``-strongest``; ``beta`` ``"0"``,
    ``"1"`` or sigmoid of noise; ``repeat``: head 0's keys are all the first."""
    b = 2
    q, k = _rand(b, t, heads, dk, seed=1), _rand(b, t, heads, dk, seed=2)
    if repeat:
        k = k.at[:, :, 0].set(k[:, :1, 0])
    v = _rand(b, t, heads, dv, seed=3)
    g = -strongest * jnp.asarray(
        np.random.RandomState(4).rand(b, t, heads, dk), jnp.float32)
    g = g.at[..., :4].set(-strongest)
    be = {"0": jnp.zeros((b, t, heads)), "1": jnp.ones((b, t, heads))}.get(
        beta, jax.nn.sigmoid(_rand(b, t, heads, seed=5)))
    return q, k, v, g, be


def _normed(q, k):
    return REF._l2(q) * q.shape[-1] ** -0.5, REF._l2(k)


KDA_CASES = {
    # name: (positions, chunk, strongest decay a position, beta, keys repeat)
    "one_chunk_mild": (64, 64, 0.05, "noise", False),
    "three_chunks_strongest": (192, 64, 1.6, "noise", False),
    "repeated_keys_beta_one": (256, 64, 1.6, "1", True),
    "chunk_not_dividing": (200, 64, 0.5, "noise", False),
    "beta_zero_writes_nothing": (256, 32, 1.6, "0", False),
    "beyond_the_assumed_decay": (250, 64, 8.0, "noise", False),
}


LOWERINGS = ["xla", "pallas"]


@functools.lru_cache(maxsize=None)
def _oracle(name):
    """The recurrence's values and five gradients (the cotangent of seed 9)
    for one case of ``KDA_CASES``: made once, whatever the group and the
    lowering it is held against."""
    t, _chunk, strongest, beta, repeat = KDA_CASES[name]
    args = _kda_inputs(t, strongest, beta, repeat)
    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(
            lambda q, k, v, g, be: _recurrent(*_normed(q, k), v, g, be), *args)
        return want, pull(_rand(*args[2].shape, seed=9))


@pytest.fixture
def lowered_as(monkeypatch):
    """``lowered_as("pallas")``: the scan's two kernels under Pallas's
    interpreter, whatever the widths (the module itself chooses them on a
    TPU alone); ``"xla"``: the ``jax.numpy`` form, as the CPU takes it."""
    def choose(lowering):
        if lowering == "pallas":
            monkeypatch.setattr(delta_rule, "_lowering_for",
                                lambda *_: "interpret")
    return choose


@pytest.mark.parametrize("lowering", LOWERINGS)
@pytest.mark.parametrize("group", [1024, 128], ids=["one_group", "groups"])
@pytest.mark.parametrize("name", sorted(KDA_CASES))
def test_chunked_scan_matches_the_recurrence(name, group, lowering, lowered_as):
    """Values and all five gradients, float32, to 1e-4 of the largest
    gradient: chunks that divide the sequence and do not, decays from mild to
    the assumed initialisation's strongest (1.6 a position on some channels:
    102 inside a chunk, past what ``exp`` holds in float32 when factored round
    the chunk's start) and 5 times beyond, ``beta`` at 0 and 1, a head whose
    keys repeat; one group of chunks and several (the backward's kept states);
    as the ``jax.numpy`` form and as the two kernels."""
    lowered_as(lowering)
    t, chunk, strongest, beta, repeat = KDA_CASES[name]
    args = _kda_inputs(t, strongest, beta, repeat)
    cot = _rand(*args[2].shape, seed=9)

    def chunked(q, k, v, g, be):
        return delta_rule.gated_delta_rule(
            q, k, v, g, be, chunk=chunk, group=group, qk_l2norm=True,
            scale=q.shape[-1] ** -0.5)

    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(chunked, *args)     # one trace: values and gradients
        g_got = pull(cot)
    want, g_want = _oracle(name)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for a, b in zip(g_got, g_want):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(b).max()) + 1e-7)
    if beta == "0":
        assert float(jnp.abs(got).max()) == 0.0


@pytest.mark.parametrize("lowering", LOWERINGS)
def test_chunked_scan_in_bfloat16_with_a_float32_state(lowering, lowered_as):
    """bfloat16 ``q, k, v`` with float32 decay and state against the float32
    recurrence on the same (rounded) inputs: the products' operands are
    rounded to 8 bits, so 2% of the result's scale."""
    lowered_as(lowering)
    q, k, v, g, be = _kda_inputs(256, 1.6, "noise")
    lo = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    got = delta_rule.gated_delta_rule(*lo, g, be.astype(jnp.bfloat16),
                                      qk_l2norm=True, scale=32 ** -0.5)
    assert got.dtype == jnp.bfloat16 and bool(jnp.isfinite(got).all())
    qn, kn = _normed(*[x.astype(jnp.float32) for x in lo[:2]])
    want = _recurrent(qn, kn, lo[2].astype(jnp.float32), g,
                      be.astype(jnp.bfloat16).astype(jnp.float32))
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert err <= 0.02 * float(jnp.abs(want).max()), err


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("l2norm", [True, False], ids=["l2norm", "raw"])
def test_kernels_match_the_jax_numpy_form(l2norm, dtype, lowered_as):
    """The two lowerings of one algebra against each other, values and the
    five gradients, ``q`` and ``k`` normalised inside the op and raw:
    float32 to float noise; bfloat16 operands (the state float32 in both) to
    the operands' rounding, and every gradient in its input's dtype."""
    q, k, v, g, be = _kda_inputs(200, 1.6, "noise")
    if not l2norm:
        q, k = _normed(q, k)
    args = [x.astype(dtype) for x in (q, k, v)] + [g, be.astype(dtype)]
    cot = _rand(*v.shape, seed=9).astype(dtype)

    def both():
        f = lambda *a: delta_rule.gated_delta_rule(      # noqa: E731
            *a, group=128, qk_l2norm=l2norm, scale=0.5)
        out, pull = jax.vjp(f, *args)
        return (out,) + pull(cot)

    want = both()
    lowered_as("pallas")
    got = both()
    tol = 1e-5 if dtype == "float32" else 3e-2
    for a, b, like in zip(got, want, [args[2]] + args):
        assert a.dtype == b.dtype == like.dtype
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert bool(jnp.isfinite(a).all())
        assert float(jnp.abs(a - b).max()) <= tol * float(jnp.abs(b).max()) + 1e-7


@pytest.mark.parametrize("chunk,sub,largest", [
    (64, 16, 0.9), (64, 64, 0.9), (32, 16, 0.9), (64, 16, 4.0)],
    ids=["64_by_16", "64_by_64", "32_by_16", "a_strong_system"])
def test_inverse_rule_is_autodiff_of_the_jax_numpy_inverse(chunk, sub, largest):
    """The kernels' inverse carries its cotangent as a rule, ``-X^T X_bar
    X^T``, with the ``X`` the forward kernel made and kept; the ``jax.numpy``
    form's stays under autodiff and is what the rule is held against: random
    strictly-lower tiles, float32, to 1e-5 of the largest cotangent, also
    where entries of up to 4 make ``X`` grow past 1e20.  The rule's is zero
    on and above the diagonal, where ``L`` has no entry (autodiff's is not:
    the substitution reads the zeros there), and the kept ``X`` takes none."""
    rng = np.random.RandomState(chunk + sub)
    under = np.tril(np.ones((chunk, chunk), bool), -1)
    m = jnp.asarray(np.where(under, rng.uniform(-largest, largest,
                                                 (8, chunk, chunk)), 0.0),
                    jnp.float32)
    cot = _rand(8, chunk, chunk, seed=9)
    kept = delta_rule._tiles_inverse(m, chunk, sub)
    x, pull = jax.vjp(delta_rule._kept_inverse, m, kept)
    assert x is kept
    want_x, want_pull = jax.vjp(
        lambda a: delta_rule._unit_lower_inverse(a, sub), m)
    np.testing.assert_allclose(x, want_x, rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(want_x).max()))
    got, to_kept = pull(cot)
    got, want = np.asarray(got), np.asarray(want_pull(cot)[0])
    assert np.isfinite(got).all() and not np.asarray(to_kept).any()
    assert np.abs(np.where(under, 0.0, got)).max() == 0.0
    size = np.abs(np.where(under, want, 0.0)).max()
    assert size > (1e20 if largest > 1 else 10.0)
    assert np.abs(np.where(under, got - want, 0.0)).max() <= 1e-5 * size


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(inner)


def _made_again(rule):
    """What stands in for ``_kept_inverse`` in a backward body that makes the
    inverse again, as the bodies before the forward kept it did: under the
    rule (PR 37's body) or under autodiff's transpose (the one before)."""
    if not rule:
        return lambda m, _kept: delta_rule._tiles_inverse(m, 64, 16)
    made = jax.custom_vjp(lambda m: delta_rule._tiles_inverse(m, 64, 16))
    made.defvjp(lambda m: (made(m),) * 2,
                lambda x, d_x: delta_rule._kept_inverse_bwd(x, d_x)[:1])
    return lambda m, _kept: made(m)


#: the backward body's inverse -> (the head width that keeps the case's trace
#: its own: the kernel's call is traced once a signature; the body's
#: highest-precision products)
BODIES = {"the_inverse_kept": (32, 4), "the_rule_alone": (48, 8),
          "autodiffs_transpose": (80, 14)}


def _backward_body(monkeypatch, body):
    """``(the layer's plan, the jaxpr of the backward kernel's body)``, traced
    under the interpreter."""
    monkeypatch.setattr(delta_rule, "_lowering_for", lambda *_: "interpret")
    monkeypatch.setattr(delta_rule, "_BWD_HI_PRODUCTS", {})
    if body != "the_inverse_kept":
        monkeypatch.setattr(delta_rule, "_kept_inverse",
                            _made_again(body == "the_rule_alone"))
    args = _kda_inputs(64, 0.5, "noise", heads=1, dk=BODIES[body][0])
    with delta_rule.plan_recording():
        traced = jax.make_jaxpr(lambda *a: jax.vjp(
            delta_rule.gated_delta_rule, *a)[1](a[2]))(*args)
    backward = [e for e in _eqns(traced.jaxpr)
                if e.primitive.name == "pallas_call"
                and e.params["name"] == delta_rule.KDA_BWD]
    assert len(backward) == 1
    return delta_rule.last_plan_summary(), backward[0].params["jaxpr"]


@pytest.mark.parametrize("body", sorted(BODIES))
def test_backward_body_holds_fewer_highest_precision_products(monkeypatch, body):
    """The plan's count is read from the traced body.  With the forward's
    inverse kept: the running sums' product and its transpose and the
    inverse's rule's 2.  Made again under the rule, 4 more (the block
    formula's); under autodiff's transpose, the rule's 2 are 8.  The reader
    returns the count; a forward-only trace and the ``jax.numpy`` form have no
    such body."""
    plan, _ = _backward_body(monkeypatch, body)
    want = BODIES[body][1]
    assert plan["layers"][0]["bwd_hi_products"] == plan["bwd_hi_products"] == want
    read = run.load_module("layer_metrics", "kda_bwd_hi_products").read
    assert read({}) == want
    monkeypatch.setattr(delta_rule, "_BWD_HI_PRODUCTS", {})
    args = _kda_inputs(64, 0.5, "noise", heads=1)
    with delta_rule.plan_recording():
        # a function of its own a case: a trace made before is not made again
        jax.make_jaxpr(lambda *a: delta_rule.gated_delta_rule(*a))(*args)
    assert "bwd_hi_products" not in delta_rule.last_plan_summary()
    assert "bwd_hi_products" not in delta_rule.last_plan_summary()["layers"][0]
    assert read({}) is None


def test_backward_body_holds_no_substitution(monkeypatch):
    """Handed the inverse, the backward body makes none: against the body
    that makes it again under the same rule, the block formula's four
    highest-precision products are gone and every row of the substitution
    (each cuts the tiles into ``(chunks, blocks, sub, chunk)`` once, and
    nothing else does); the other products
    are the same.  The six products of ``strict_k`` stay: ``beta``'s cotangent
    is the inverse's times ``strict_k``, row by row."""
    def census(jaxpr):
        dots = [e for e in _eqns(jaxpr) if e.primitive.name == "dot_general"]
        hi = delta_rule._hi_products(jaxpr)
        rows = sum(1 for e in _eqns(jaxpr) for v in e.outvars
                   if getattr(v.aval, "shape", ()) == (1, 4, 16, 64)
                   and e.primitive.name == "reshape")
        return hi, len(dots) - hi, rows

    kept = census(_backward_body(monkeypatch, "the_inverse_kept")[1])
    again = census(_backward_body(monkeypatch, "the_rule_alone")[1])
    assert again[2] == 15 and kept[2] == 0
    assert (again[0] - kept[0], again[1] - kept[1]) == (4, 0)
    assert kept[0] == 4


#: the inputs' kind -> what the largest entry of ``X`` reaches at least
FORWARD_SYSTEMS = {
    "noise": 1.0,
    # keys of a few small whole numbers, no decay, a write strength of one:
    # the system's entries are the same whole numbers in both forms, and its
    # inverse grows past 1e20
    "a_strong_system": 1e20,
}


@pytest.mark.parametrize("name", sorted(FORWARD_SYSTEMS))
def test_forward_keeps_the_inverse_of_the_jax_numpy_form(name):
    """What the forward kernel keeps a chunk, a group's chunks side by side
    along the lanes, is ``(I + Diag(beta) tril(A, -1))^-1`` in float32:
    against ``_unit_lower_inverse`` on the ``jax.numpy`` form's ``A`` of the
    same inputs, to 1e-6 of its largest entry."""
    t, chunk, sub, group = 256, 64, 16, 128
    q, k, v, g, be = _kda_inputs(t, 0.5, "noise", heads=2)
    l2norm = name == "noise"
    if not l2norm:
        rng = np.random.RandomState(3)
        k = jnp.asarray(rng.randint(-2, 3, k.shape)
                        * (rng.uniform(size=k.shape) < 0.5), jnp.float32)
        g, be = jnp.zeros_like(g), jnp.ones_like(be)
    args = [delta_rule._head_major(x, t) for x in (q, k, v, g, be)]
    how = (chunk, sub, group, l2norm, 0.5, "interpret")
    _o, starts, kept = delta_rule._forward_kernel(*args, how=how)
    b, h = args[0].shape[:2]
    assert starts.shape == (t // group, b, h, v.shape[-1], k.shape[-1])
    assert kept.shape == (b, h, t // group, chunk, group)
    assert kept.dtype == jnp.float32
    # (B, H, groups, chunk, chunks of a group, chunk) -> a tile a chunk
    got = jnp.moveaxis(kept.reshape(b, h, t // group, chunk, group // chunk,
                                    chunk), 4, 3).reshape(b, h, -1, chunk, chunk)
    kc, gc, bc = (jnp.moveaxis(delta_rule._chunks(x, chunk), 0, 2)
                  for x in args[1:2] + args[3:])
    k32 = delta_rule._l2(kc) if l2norm else kc
    a = delta_rule._pairs(jnp.stack([k32, k32]), k32,
                          jnp.cumsum(gc, axis=-2), jnp.float32)[0][0]
    want = delta_rule._unit_lower_inverse(bc[..., :, None] * a, sub)
    size = float(jnp.abs(want).max())
    assert size >= FORWARD_SYSTEMS[name]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * size)


@pytest.mark.parametrize("why,kwargs,patched", [
    ("the_cpu", dict(dk=128, dv=128), False),
    ("a_width_that_is_no_lane_tile", dict(dk=96, dv=128), True),
    ("a_value_width_that_is_no_lane_tile", dict(dk=128, dv=64), True),
    ("a_chunk_of_32", dict(dk=128, dv=128, chunk=32), True)])
def test_what_the_kernels_do_not_take_runs_the_jax_numpy_form(
        why, kwargs, patched, monkeypatch):
    """The choice is made from the backend and the shapes alone, and the
    plan says what was chosen."""
    from mxnet_tpu import context
    if patched:
        monkeypatch.setattr(context, "on_tpu", lambda: True)
    chunk = kwargs.pop("chunk", 64)
    args = _kda_inputs(64, 0.5, "noise", heads=1, **kwargs)
    with delta_rule.plan_recording():
        text = str(jax.make_jaxpr(lambda *a: delta_rule.gated_delta_rule(
            *a, chunk=chunk))(*args))
    assert "pallas_call" not in text
    plan = delta_rule.last_plan_summary()
    assert plan["kernel_layers"] == 0 and plan["chunked_layers"] == 1
    assert plan["layers"][0]["lowering"] == "xla"
    # the same shapes at whole lane tiles, a chunk of 64 and a TPU: kernels
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    assert delta_rule._lowering_for(64, 128, 256) == "pallas"


@pytest.mark.parametrize("shape", [(4, 1), (2, 2)], ids=["dp4", "dp2_tp2"])
def test_kernels_under_a_mesh_equal_the_unsharded_call(shape, lowered_as):
    """Four virtual CPU devices: the ``shard_map``-wrapped kernels (batch
    over the data axis, heads over ``model``) against one device's."""
    from mxnet_tpu.parallel import build_mesh
    from mxnet_tpu.parallel.mesh import kernel_mesh
    lowered_as("pallas")
    q, k, v, g, be = _kda_inputs(128, 0.5, "noise", heads=2)
    args = [jnp.concatenate([x, x[::-1]]) for x in (q, k, v, g, be)]  # batch 4
    cot = _rand(*args[2].shape, seed=9)

    def both():
        # a function of its own a trace: the mesh is no argument jax sees
        def run(*a):
            out, pull = jax.vjp(lambda *b: delta_rule.gated_delta_rule(
                *b, group=64, qk_l2norm=True, scale=0.5), *a)
            return (out,) + pull(cot)
        return run

    want = jax.jit(both())(*args)
    mesh = build_mesh(devices=jax.devices()[:4], tp=shape[1])
    with kernel_mesh(mesh):
        text = str(jax.make_jaxpr(both())(*args))
        got = jax.jit(both())(*args)
    assert text.count("shard_map") == text.count("pallas_call") == 2
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("lowering", LOWERINGS)
def test_scan_keeps_a_state_a_group_not_a_token(lowering, lowered_as):
    """What the backward is handed: the inputs, one float32 state a head
    and group of chunks and, from the kernels, one ``(chunk, chunk)`` float32
    inverse a head and chunk; and the plan counts what is kept beyond the
    inputs."""
    lowered_as(lowering)
    q, k, v, g, be = _kda_inputs(256, 0.5, "noise")
    with delta_rule.plan_recording():
        _, res = jax.vjp(lambda *a: delta_rule.gated_delta_rule(
            *a, chunk=64, group=128), q, k, v, g, be)
    kept = [x for x in jax.tree_util.tree_leaves(res) if hasattr(x, "shape")]
    beyond = [x for x in kept if x.ndim == 5]
    states = (2, 2, 3, 32, 16) if lowering == "xla" else (2, 2, 3, 16, 32)
    inverses = [] if lowering == "xla" else [(2, 3, 2, 64, 128)]
    assert [x.shape for x in beyond] == [states] + inverses
    assert {x.dtype for x in beyond} == {jnp.dtype("float32")}
    assert len(kept) == 5 + len(beyond)
    assert max(x.size for x in kept) <= max(q.size, *(x.size for x in beyond))
    plan = delta_rule.last_plan_summary()
    assert plan["chunked_layers"] == 1
    held = sum(x.size * 4 for x in beyond)
    assert plan["layers"][0] == dict(
        {"heads": 3, "dk": 32, "dv": 16, "positions": 256, "chunk": 64,
         "group": 128, "form": "chunked", "state_bytes": held},
        **({"lowering": "xla"} if lowering == "xla"
           else {"lowering": "pallas", "bwd_hi_products": 4}))
    assert plan["state_bytes"] == held
    assert plan["kernel_layers"] == (lowering != "xla")


# ------------------------------------------- the small ops beside it
OPS = {
    "conv_then_silu": (
        lambda x, w: _fcompute("_contrib_CausalConv1D",
                               {"kernel": 4, "act_type": "silu"}, x, w),
        lambda x, w: jax.vmap(lambda s: REF.conv_silu(s, w))(x),
        lambda: (_rand(2, 11, 6), _rand(6, 4, seed=2))),
    "gated_rms_norm": (
        lambda x, z, g: _fcompute("_contrib_GatedRMSNorm", {"eps": 1e-5}, x, z, g),
        lambda x, z, g: REF.gated_norm(x, z, g, 1e-5),
        lambda: (_rand(2, 5, 3, 8), _rand(2, 5, 3, 8, seed=1),
                 1 + _rand(8, seed=2, scale=0.3))),
    "log_decay_softplus": (
        lambda f, a, b: _fcompute("_contrib_KDAGate", {"num_heads": 3}, f, a, b),
        lambda f, a, b: jax.vmap(lambda s: REF.log_decay(s, a, b, 3))(f),
        lambda: (_rand(2, 7, 12), _rand(3, seed=1), _rand(12, seed=2))),
    "softplus": (
        lambda x: _fcompute("Activation", {"act_type": "softrelu"}, x),
        jax.nn.softplus, lambda: (_rand(3, 7, scale=3.0),)),
    "l2_norm_a_head": (
        lambda q, k: delta_rule._state_free(
            q, k, k, jnp.zeros_like(q), jnp.ones(q.shape[:-1]), 4, True, 0.5)[0],
        lambda q, k: REF._l2(q) * 0.5,
        lambda: (_rand(2, 3, 8, 16), _rand(2, 3, 8, 16, seed=1))),
}


@pytest.mark.parametrize("name", sorted(OPS))
def test_new_op_matches_its_lines_of_the_reference(name):
    op, ref, make = OPS[name]
    args = make()
    np.testing.assert_allclose(op(*args), ref(*args), rtol=2e-6, atol=2e-6)
    cot = _rand(*ref(*args).shape, seed=9)
    got = jax.grad(lambda *a: jnp.sum(op(*a) * cot), range(len(args)))(*args)
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * cot), range(len(args)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)


def test_log_decay_is_float32_whatever_the_input():
    g = _fcompute("_contrib_KDAGate", {"num_heads": 3},
                  _rand(1, 4, 12).astype(jnp.bfloat16),
                  _rand(3).astype(jnp.bfloat16), _rand(12).astype(jnp.bfloat16))
    assert g.dtype == jnp.float32 and g.shape == (1, 4, 3, 4)
    assert float(g.max()) <= 0.0


@pytest.mark.parametrize("op,shapes,attrs,says", [
    ("_contrib_GatedDeltaRule",
     [(1, 8, 2, 4), (1, 8, 2, 4), (1, 8, 2, 6), (1, 8, 2, 4), (1, 8, 3)], {},
     ["beta (batch, seq, heads)", "(1, 8, 3)"]),
    ("_contrib_KDAGate", [(1, 8, 12), (4,), (12,)], {"num_heads": 3},
     ["a_log (num_heads,)", "(4,)", "num_heads=3"]),
    ("_contrib_GatedRMSNorm", [(1, 8, 2, 4), (1, 8, 2, 4), (8,)], {},
     ["gamma of its last axis", "(8,)"]),
    ("_contrib_FlashAttention", [(1, 8, 4, 6), (1, 8, 4, 4), (1, 8, 4, 4)], {},
     ["query heads are 6 wide", "key heads 4"]),
    ("_contrib_FlashAttention", [(1, 8, 4, 6), (1, 8, 2, 6), (1, 8, 4, 4)], {},
     ["keys and values differ in more than their head width"]),
    ("_contrib_FlashAttention", [(1, 8, 6, 4), (1, 8, 4, 4), (1, 8, 4, 8)], {},
     ["6 query heads", "4 key/value heads"]),
])
def test_errors_name_what_is_at_fault(op, shapes, attrs, says):
    with pytest.raises(MXNetError) as e:
        getattr(mx.nd, op)(*[mx.nd.array(np.asarray(_rand(*s))) for s in shapes],
                           **attrs)
    for text in says:
        assert text in str(e.value), (text, str(e.value))


@pytest.mark.parametrize("name,want", [
    ("loguniform", lambda x: x.min() >= 0.0 and x.max() <= np.log(16.0) + 1e-6
     and x.std() > 0.5),
    ("inversesoftpluslogunifom", None)])
def test_decay_initialisers_draw_on_the_host_and_under_a_trace(name, want):
    """``A_log`` and ``dt_bias`` get rules that ``ShardedTrainer`` can trace:
    the same law from numpy's generator and from a jax key."""
    from mxnet_tpu import initializer as init
    rule_of = {"loguniform": init.LogUniform(1.0, 16.0),
               "inversesoftpluslogunifom":
                   init.InverseSoftplusLogUniform(0.001, 0.1)}[name]
    # as the model's variables carry it: Variable(init=...) over the name's suffix
    rule = init.rule_for(init.Normal(0.02), init.InitDesc(
        "layer0_a_log_bias", attrs={"__init__": rule_of.dumps()}))
    assert getattr(rule, "traceable", False)
    host = mx.nd.zeros((4096,))
    np.random.seed(3)
    rule("x", host)
    traced = np.asarray(jax.jit(lambda key: init.draw(
        rule, "x", (4096,), key))(jax.random.PRNGKey(3)))
    for x in (host.asnumpy(), traced):
        if want is not None:
            assert want(x)
        else:       # softplus of the bias is the rate drawn
            dt = np.log1p(np.exp(x))
            assert dt.min() >= 0.001 * 0.999 and dt.max() <= 0.1 * 1.001
    # the same law: quartiles agree between the two generators
    np.testing.assert_allclose(np.quantile(host.asnumpy(), [.25, .5, .75]),
                               np.quantile(traced, [.25, .5, .75]), atol=0.12)


# --------------------------- attention with a value width of its own
ROUTES = {"panel": (16, 64), "stream": (16, 32)}


def _mla_inputs(hq, hk, dk, dv, t=64):
    return (_rand(2, t, hq, dk, seed=1), _rand(2, t, hk, dk, seed=2),
            _rand(2, t, hk, dv, seed=3), _rand(2, t, hq, dv, seed=4))


@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("heads", [(4, 4), (4, 2)], ids=["plain", "grouped"])
@pytest.mark.parametrize("widths", [(192, 128), (24, 16)], ids=str)
def test_flash_kernels_take_values_of_their_own_width(route, heads, widths):
    """Interpret mode, forward and backward, both routes, with and without
    grouped queries, against the plain formula generalised alike."""
    q, k, v, g = _mla_inputs(*heads, *widths)
    blocks = ROUTES[route]
    o, lse = pk._flash_attention_fwd_pallas(q, k, v, True, True, blocks=blocks)
    want, pull = jax.vjp(lambda q, k, v: pk._attention_jnp(q, k, v, True),
                         q, k, v)
    assert o.shape == q.shape[:3] + (widths[1],)
    np.testing.assert_allclose(o, want, rtol=2e-5, atol=2e-5)
    grads = pk._flash_attention_bwd_pallas(q, k, v, o, lse, g, True, True,
                                           blocks=blocks)
    for got, ref, like in zip(grads, pull(g), (q, k, v)):
        assert got.shape == like.shape
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_plain_attention_scales_by_the_query_width():
    q, k, v, _ = _mla_inputs(2, 2, 24, 16, t=8)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 24 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((8, 8), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(pk._attention_jnp(q, k, v, True), want,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        jax.vmap(REF.attention)(q, k, v), want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_equal_widths_are_the_kernels_of_before(route):
    """``dk == dv``: bit-equal to the kernels given values padded to nothing
    (the very calls: same grid, blocks, names, no compiler parameters), and
    the causal plan's numbers are what they were; entries gain the widths."""
    q, k, v, g = _mla_inputs(4, 4, 32, 32)
    blocks = ROUTES[route]

    def both(q, k, v, g):
        o, lse = pk._flash_attention_fwd_pallas(q, k, v, True, True,
                                                blocks=blocks)
        return (o,) + pk._flash_attention_bwd_pallas(q, k, v, o, lse, g, True,
                                                     True, blocks=blocks)

    with pk.causal_plan_recording():
        text = str(jax.make_jaxpr(both)(q, k, v, g))
    assert "vmem_limit" not in text
    assert text.count("name=mxtpu_flash_fwd_%s" % route) == 1
    assert text.count("name=mxtpu_flash_bwd_%s" % route) == 1
    plan = pk.last_causal_plan()
    assert [(e["kernel"], e["dk"], e["dv"], e["block_q"], e["block_k"])
            for e in plan["kernels"]] == [
        ("flash_attention_fwd", 32, 32) + blocks,
        ("flash_attention_bwd", 32, 32) + blocks]
    # the numbers OPT's and LFM2's cells report come from the same rule
    assert plan["scores_computed_pct"] == pk._scores_computed_pct(
        64, *blocks, pk._causal_plan(*blocks))
    # a wider value that is all zeros past 32 gives the same first 32
    wide = jnp.concatenate([v, jnp.zeros_like(v)], axis=-1)
    o = both(q, k, v, g)[0]
    o2, _ = pk._flash_attention_fwd_pallas(q, k, wide, True, True, blocks=blocks)
    np.testing.assert_array_equal(o, o2[..., :32])
    # a 128-wide head at 8192 positions asks for nothing, as it always did;
    # a 192-wide one takes two lane tiles a row, and that call asks
    assert pk._vmem_params(pk._vmem_need(128, 128, 2048, 8192)) == {}
    assert "compiler_params" in pk._vmem_params(
        pk._vmem_need(192, 128, 2048, 8192))


def test_op_takes_the_latent_widths_and_carries_the_scope():
    q, k, v, _ = _mla_inputs(4, 4, 24, 16, t=16)
    out = mx.nd._contrib_FlashAttention(*[mx.nd.array(np.asarray(a))
                                          for a in (q, k, v)], causal=True)
    np.testing.assert_allclose(out.asnumpy(), pk._attention_jnp(q, k, v, True),
                               rtol=1e-5, atol=1e-6)
    text = jax.jit(lambda *a: _fcompute("_contrib_FlashAttention",
                                        {"causal": True}, *a)).lower(
        q, k, v).as_text(debug_info=True)
    assert pk.SCOPE_MLA in text
    same = jax.jit(lambda *a: _fcompute("_contrib_FlashAttention",
                                        {"causal": True}, *a)).lower(
        q, k, k).as_text(debug_info=True)
    assert pk.SCOPE_MLA not in same


# ------------------------------------------ the expert layer's buffer
@pytest.mark.parametrize("held,experts,rows", [
    (8, 32, 8192 * 8), (32, 32, 8192 * 8), (8, 256, 8192), (1, 256, 1024)])
def test_buffer_rows_follow_the_share(held, experts, rows):
    """``min(T k, 4 T k held / E)``: every assignment from a quarter up
    (LFM2's 8 of 32, a layer that holds everything), four times the even load
    below it."""
    assert moe.buffer_rows(8192, 8, held, experts) == rows
    assert moe.buffer_rows(8192, 8, held, experts) % moe.ROW_TILE == 0


def test_buffer_rows_round_up_to_the_row_tile():
    assert moe.buffer_rows(10, 3, 1, 64) == 8       # 4 * 30 / 64 = 1.9
    assert moe.buffer_rows(10, 3, 16, 64) == 30     # a quarter: all of them


KIMI_MOE = dict(num_experts=8, router_num_experts=256, num_experts_per_token=8,
                expert_offset=0, moe_renormalize=True, routed_scaling_factor=2.446,
                router_trained=True)


def _moe_params(d=16, ff=24, e=256, held=8, push=0.0):
    """``push``: added to the selection bias of held expert 0."""
    bias = _rand(e, seed=3, scale=0.1).at[0].add(push)
    return {"moe_router_weight": _rand(e, d, seed=1, scale=0.5),
            "moe_expert_bias": bias,
            "moe_w1_weight": _rand(held, d, ff, seed=4, scale=0.2),
            "moe_w3_weight": _rand(held, d, ff, seed=5, scale=0.2),
            "moe_w2_weight": _rand(held, ff, d, seed=6, scale=0.2)}


def _layer(x, p, cfg=KIMI_MOE):
    return moe.topk_moe(
        x, p["moe_router_weight"], p["moe_expert_bias"], p["moe_w1_weight"],
        p["moe_w3_weight"], p["moe_w2_weight"], cfg["num_experts_per_token"],
        expert_offset=cfg["expert_offset"], norm_topk_prob=True,
        routed_scaling_factor=cfg["routed_scaling_factor"])


def test_share_sized_buffer_matches_the_reference_under_even_routing():
    """8 of 256 held: a buffer of ``T`` rows, an eighth of the assignments;
    values and gradients are the bufferless reference's."""
    x, p = _rand(64, 16), _moe_params()
    (y, load), want = _layer(x, p), REF.expert_layer(x, p, KIMI_MOE)
    assert float(load[:-1].sum()) <= 64
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    got = jax.grad(lambda x, p: jnp.sum(_layer(x, p)[0] ** 2), (0, 1))(x, p)
    ref = jax.grad(lambda x, p: jnp.sum(REF.expert_layer(x, p, KIMI_MOE) ** 2),
                   (0, 1))(x, p)
    np.testing.assert_allclose(got[0], ref[0], rtol=1e-4, atol=1e-5)
    for n in p:
        np.testing.assert_allclose(got[1][n], ref[1][n], rtol=1e-4, atol=1e-5)


def _routing(x, p, cfg):
    """``(expert of each assignment, its gate)`` as the reference routes."""
    s = jax.nn.sigmoid(x @ p["moe_router_weight"].T)
    _, idx = jax.lax.top_k(s + p["moe_expert_bias"], cfg["num_experts_per_token"])
    gates = jnp.take_along_axis(s, idx, axis=1)
    gates = gates / (gates.sum(axis=1, keepdims=True) + 1e-6)
    return np.asarray(idx), np.asarray(gates * cfg["routed_scaling_factor"])


def test_past_the_buffer_exactly_the_last_assignments_are_left_out(monkeypatch):
    """Routing forced past the buffer (every token takes held expert 1 and,
    by the sign of its first feature, held expert 0 or 2: 8 times the even
    load, twice the buffer): the result is the reference's with exactly the assignments
    past the buffer left out, the last in (expert, token) order; ``load``
    still reports what was assigned, and the benchmark's readers count it."""
    t, k = 64, 8
    x, p = _rand(t, 16), _moe_params()
    p["moe_expert_bias"] = p["moe_expert_bias"].at[1].add(10.0).at[
        jnp.array([0, 2])].add(0.5)
    p["moe_router_weight"] = p["moe_router_weight"].at[0].set(
        200.0 * jnp.eye(16)[0]).at[2].set(-200.0 * jnp.eye(16)[0])
    rows = moe.buffer_rows(t, k, 8, 256)
    assert rows == t
    with moe.plan_recording():
        y, load = _layer(x, p)
    counts = np.asarray(load[:-1])
    assert counts[1] == t and 16 < counts[0] < 48 and counts[0] + counts[2] == t
    assert counts.sum() >= 8 * 16
    # the oracle: the bufferless reference minus the assignments whose place
    # in the sorted order is past the buffer
    idx, gates = _routing(x, p, KIMI_MOE)
    held = [(e, tok, gates[tok, j]) for tok in range(t) for j in range(k)
            for e in [int(idx[tok, j])] if e < 8]
    held.sort(key=lambda a: (a[0], a[1]))
    assert len(held) == counts.sum()
    want = np.array(REF.expert_layer(x, p, KIMI_MOE))
    for e, tok, gate in held[rows:]:
        one = REF._gated(x[tok:tok + 1], p["moe_w1_weight"][e],
                         p["moe_w3_weight"][e], p["moe_w2_weight"][e], None)
        want[tok] -= gate * np.asarray(one[0])
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert float(np.abs(np.asarray(y) - np.asarray(
        REF.expert_layer(x, p, KIMI_MOE))).max()) > 1e-3
    assert float(load[-1]) == 0.0          # every token has a held expert
    plan = moe.last_plan_summary()["layers"][0]
    assert plan["buffer_rows"] == rows and plan["even_rows"] == t * k * 8 / 256
    # the readers: assignments beyond the buffer, from the published loads
    sample = {"l": {"assignments": [float(c) for c in counts],
                    "tokens_unrouted": 0.0}}
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [(1.0, sample)])
    ctx = {"samples": [(0.0, 0.1, 2.0, [1.0])]}
    dropped = run.load_module("layer_metrics", "moe_dropped_tokens").read(ctx)
    assert dropped == counts.sum() - rows == len(held) - rows
    pct = run.load_module("layer_metrics", "moe_buffer_rows_pct").read(ctx)
    assert pct == 100.0 * rows / (t * k) == 12.5


def test_lfm2_toy_step_lowers_to_the_parents_text():
    """The buffer rule, the shared helpers and the generalised kernels leave
    LFM2's step as it was: the lowered text of the toy configuration's 2-step
    chain, hashed on the parent of PR 31 (``git archive`` of 762f2db, the same
    script).  A change that means to move LFM2's lowering refreshes the digest
    and says so; nothing else may."""
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    cfg = run.load_json(BENCH, "configs", "smoke-lfm2.json")
    mix = run.load_json(BENCH, "traffic", "smoke-s64-b1-chain2.json")
    net, data, label = run.load_module("configs", cfg["code"]).build(cfg, mix, 1)
    opt = dict(cfg["optimizer"])
    trainer = ShardedTrainer(
        net, build_mesh(devices=jax.devices()[:1], tp=1), data_shapes=data,
        label_shapes=label, optimizer=opt.pop("optimizer"), seed=1, **opt,
        **cfg["trainer"])
    batch = trainer.put_batch({k: np.zeros(v, np.float32)
                               for k, v in {**data, **label}.items()})
    fn, args = trainer._prepare_run_steps(batch, 2)
    text = fn.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "364a9ef0eb68e8ae50b5ce91fc4275e9e2b0a23f082ac769d7e265df9915b73f"


# --------------------------------------------- the shares add up
WHOLE = dict(num_experts=16, router_num_experts=16, num_experts_per_token=4,
             expert_offset=0, moe_renormalize=True, routed_scaling_factor=2.446,
             router_trained=True, num_shared_experts=1)


def _whole_params(d=16, ff=24):
    p = _moe_params(d, ff, e=16, held=16)
    p.update(shared_w1_weight=_rand(ff, d, seed=7, scale=0.2),
             shared_w3_weight=_rand(ff, d, seed=8, scale=0.2),
             shared_w2_weight=_rand(d, ff, seed=9, scale=0.2))
    return p


def _shares_sum(x, p):
    """4 shares of 4 experts, each without the shared expert, summed, plus the
    shared expert once (every chip computes it alike)."""
    y = 0.0
    for off in range(0, 16, 4):
        share = {n: (v[off:off + 4] if n.startswith("moe_w") else v)
                 for n, v in p.items()}
        y = y + _layer(x, share, dict(WHOLE, num_experts=4, expert_offset=off))[0]
    shared = jax.nn.silu(x @ p["shared_w1_weight"].T) * (x @ p["shared_w3_weight"].T)
    return y + shared @ p["shared_w2_weight"].T


def _uncut(x, p):
    return REF.expert_layer(x, p, WHOLE) + REF.shared_expert(x, p)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    x, p = _rand(40, 16), _whole_params()
    np.testing.assert_allclose(_shares_sum(x, p), _uncut(x, p),
                               rtol=1e-5, atol=1e-6)


def test_the_shares_input_gradients_add_up_too():
    x, p = _rand(40, 16), _whole_params()
    cot = _rand(40, 16, seed=11)
    got = jax.grad(lambda x: jnp.sum(_shares_sum(x, p) * cot))(x)
    want = jax.grad(lambda x: jnp.sum(_uncut(x, p) * cot))(x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------- the whole model
def _toy_bench():
    bench = dict(run.load_json(run.ROOT, "BENCHMARK.json"))
    bench["configs"] = [{"name": "smoke-kimi",
                         "file": "benchmark/configs/smoke-kimi.json"}]
    bench["workloads"] = [{"name": "smoke-kimi", "config": "smoke-kimi",
                           "traffic": "smoke-s64-b1-chain2", "chips": 1}]
    return bench


SEED = 2 ** 31 + 31


@pytest.fixture(scope="module")
def toy_cell():
    return run.Cell("smoke-kimi", _toy_bench())


@pytest.fixture(scope="module")
def both_sides(toy_cell, _benchmark_modules):
    """One run of the toy cell through the harness (``run.run_cell`` on the
    CPU: the reference's and the program's first 1 + chain steps of 5 layers,
    KDA-dense, KDA, KDA, MLA, KDA at width 64, float32, 4 of 16 experts held,
    from the same seeded weights, then a short window), with what the
    harness compared kept.  The reference's attention rows and recurrence
    blocks are cut so that both kinds of blocking are exercised."""
    import check
    from mxnet_tpu.telemetry import spans
    cell, kept = toy_cell, {}
    compare = check.compare

    def keeping(prog, ref, limits, say=print):
        kept.update(prog=prog, ref=ref)
        return compare(prog, ref, limits, say)

    rows, cell.refmod.ATTENTION_ROWS = cell.refmod.ATTENTION_ROWS, 16
    block, cell.refmod.RECURRENCE_BLOCK = cell.refmod.RECURRENCE_BLOCK, 16
    check.compare = keeping
    try:
        result = run.run_cell(cell, seed=SEED, seconds=0.3, trace=0,
                              on_chip=False)
    finally:
        check.compare = compare
        cell.refmod.ATTENTION_ROWS, cell.refmod.RECURRENCE_BLOCK = rows, block
    plans = (delta_rule.last_plan_summary(), moe.last_plan_summary(),
             pk.last_causal_plan())
    init = spans.records("trainer.build.init_params")[-1].attrs
    return kept["ref"], kept["prog"], plans, result, init


def test_model_symbol_is_built_from_the_configuration(toy_cell):
    net, data, label = toy_cell.cfgmod.build(toy_cell.cfg, toy_cell.mix, 1)
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(**data, **label)[0]))
    del shapes["data"], shapes["softmax_label"]
    assert shapes == toy_cell.refmod.param_shapes(toy_cell.cfg)
    assert net.list_auxiliary_states() == ["layer%d_moe_load" % i
                                           for i in (1, 2, 3, 4)]
    ops = [n["op"] for n in json.loads(net.tojson())["nodes"]]
    assert ops.count("_contrib_GatedDeltaRule") == 4
    assert ops.count("_contrib_FlashAttention") == 1
    assert ops.count("_contrib_TopKMoE") == 4
    # Module binds such a Symbol too (one layer of it, for the compile's sake)
    net = toy_cell.cfgmod.build(dict(toy_cell.cfg, num_hidden_layers=1),
                                toy_cell.mix, 1)[0]
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", data["data"])],
             label_shapes=[("softmax_label", label["softmax_label"])])
    mod.init_params(mx.init.Normal(0.02))
    a_log = mod.get_params()[0]["layer0_a_log_bias"].asnumpy()
    assert a_log.min() >= 0 and a_log.max() <= np.log(16.0) + 1e-6 and a_log.std() > 0
    mod.forward(mx.io.DataBatch([mx.nd.zeros(data["data"])],
                                [mx.nd.zeros(label["softmax_label"])]),
                is_train=False)
    assert mod.get_outputs()[0].shape == (64, toy_cell.cfg["vocab_size"])


@pytest.mark.parametrize("number,tolerance", [
    ("loss_gap", 2e-5), ("grad_sample_err", 2e-5), ("grad_norm_gap", 2e-5),
    ("delta_norm_gap", 2e-4)])
def test_model_through_sharded_trainer_follows_the_reference(both_sides, number,
                                                            tolerance):
    """Float32 on both sides: three losses, the first gradient element by
    element and by leaf, and the parameters' change agree to float noise (Adam
    divides by the square root of a tiny second moment: its change is looser)."""
    import check
    ref, prog = both_sides[:2]
    assert len(ref["losses"]) == len(prog["losses"]) == 3
    values = {n: v for n, v, _ in check.numbers(prog, ref)}
    assert values[number] <= tolerance, values
    worst = max(check.leaf_gaps(prog["grad_norms"], ref["grad_norms"]).values())
    assert worst <= 1e-3, worst


def test_trainer_records_the_three_plans(both_sides):
    kda, experts, causal = both_sides[2]
    assert kda["chunked_layers"] == 4 and len(kda["layers"]) == 4
    assert {(x["heads"], x["dk"], x["dv"], x["chunk"], x["form"], x["lowering"])
            for x in kda["layers"]} == {(4, 16, 16, 64, "chunked", "xla")}
    assert kda["kernel_layers"] == 0
    assert kda["state_bytes"] == 4 * 4 * 4 * 16 * 16
    assert experts["expert_layers"] == 4
    assert {(x["buffer_rows"], x["even_rows"]) for x in experts["layers"]} \
        == {(64 * 4, 64.0)}
    # no causal flash kernel on the CPU: the plain formula ran
    assert causal is None or all(k["dk"] != k["dv"] or k["dk"] != 24
                                 for k in causal["kernels"])


def test_toy_step_lowered_for_the_tpu_holds_the_two_kernels_a_layer(
        toy_cell, monkeypatch):
    """The toy model's two leading layers (both KDA) at one head of 128, the
    platform probe patched true, the step lowered for the TPU from here: two
    ``tpu_custom_call``s a layer, named for the trace, forward under
    ``mxtpu.fwd`` and backward under ``mxtpu.bwd``, and the plan says
    ``pallas``."""
    from mxnet_tpu import context
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    cfg = dict(toy_cell.cfg, num_hidden_layers=2, linear_attn_config=dict(
        toy_cell.cfg["linear_attn_config"], head_dim=128, num_heads=1))
    net, data, label = toy_cell.cfgmod.build(cfg, toy_cell.mix, 1)
    opt = dict(cfg["optimizer"])
    t = ShardedTrainer(
        net, build_mesh(devices=jax.devices()[:1], tp=1), data_shapes=data,
        label_shapes=label, optimizer=opt.pop("optimizer"), seed=1, **opt,
        **cfg["trainer"])
    spec = lambda tree: jax.tree.map(                       # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    args = (spec(t.params), spec(t.opt_state), spec(t.aux),
            {k: jax.ShapeDtypeStruct(v, jnp.float32)
             for k, v in {**data, **label}.items()},
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32))
    text = jax.jit(t._py_step).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert (delta_rule.KDA_FWD, delta_rule.KDA_BWD) == \
        ("mxtpu_kda_fwd", "mxtpu_kda_bwd")
    assert text.count("tpu_custom_call") == 4
    assert text.count('kernel_name = "mxtpu_kda_fwd"') == 2
    assert text.count('kernel_name = "mxtpu_kda_bwd"') == 2
    assert "mxtpu.fwd/jvp(mxtpu.block.kda)/mxtpu_kda_fwd/pallas_call" in text
    assert "jvp(mxtpu.block.kda)/mxtpu_kda_bwd/pallas_call" in text
    assert "mxtpu.bwd/" in text
    plan = delta_rule.last_plan_summary()
    assert plan["kernel_layers"] == plan["chunked_layers"] == 2
    assert {x["lowering"] for x in plan["layers"]} == {"pallas"}
    # the trainer's recording spans the forward trace alone: the backward
    # body's count is there all the same, one trace for both layers
    assert [x["bwd_hi_products"] for x in plan["layers"]] == [4, 4]
    assert plan["bwd_hi_products"] == 4


def test_every_leaf_of_the_model_is_drawn_on_the_device(both_sides):
    """``init_on_host_pct`` 0: each parameter's rule is traceable, the new
    leaves' (``A_log``, ``dt_bias``: ``Variable(init=...)``) among them."""
    init = both_sides[4]
    assert init["host_bytes"] == 0 and init["device_bytes"] > 0


def test_toy_cell_runs_through_the_harness(both_sides):
    result = both_sides[3]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0 and result["device"]["count"] == 1


def test_new_readers_read_the_plans_and_none_without_them(monkeypatch, toy_cell):
    def read(name):
        return run.load_module("layer_metrics", name).read({"cell": toy_cell})

    monkeypatch.setattr(delta_rule, "last_plan_summary", lambda: {
        "layers": [{}] * 4, "chunked_layers": 4, "kernel_layers": 3,
        "state_bytes": 67108864})
    layer = {"buffer_rows": 8192, "even_rows": 2048.0, "experts_held": 8,
             "num_experts": 256}
    monkeypatch.setattr(moe, "last_plan_summary", lambda: {
        "layers": [layer, dict(layer, buffer_rows=4096)]})
    assert read("kda_chunked_layers") == 4
    assert read("kda_kernel_layers") == 3
    assert read("kda_state_saved_gb") == 67108864 / 1e9
    assert read("moe_buffer_rows_pct") == 12.5
    # LFM2's quarter: every assignment has a row
    monkeypatch.setattr(moe, "last_plan_summary", lambda: {"layers": [dict(
        buffer_rows=32768, even_rows=8192.0, experts_held=8, num_experts=32)]})
    assert read("moe_buffer_rows_pct") == 100.0
    # a plan of the parent's (no even_rows): nothing to read
    monkeypatch.setattr(moe, "last_plan_summary", lambda: {"layers": [{"buffer_rows": 64}]})
    assert read("moe_buffer_rows_pct") is None
    # the parent's plan (no lowering recorded): the new reader has nothing
    monkeypatch.setattr(delta_rule, "last_plan_summary", lambda: {
        "layers": [{}] * 4, "chunked_layers": 4, "state_bytes": 67108864})
    assert read("kda_kernel_layers") is None
    # a plan without the backward body's count (the parent's, the CPU's)
    assert read("kda_bwd_hi_products") is None
    monkeypatch.setattr(delta_rule, "last_plan_summary", lambda: {
        "layers": [{}] * 4, "chunked_layers": 4, "kernel_layers": 4,
        "state_bytes": 67108864, "bwd_hi_products": 8})
    assert read("kda_bwd_hi_products") == 8
    # a program without the records (the parent of this change): None, no raise
    monkeypatch.setattr(delta_rule, "last_plan_summary", lambda: None)
    monkeypatch.delattr(moe, "last_plan_summary")
    for name in ("kda_chunked_layers", "kda_kernel_layers",
                 "kda_state_saved_gb", "moe_buffer_rows_pct",
                 "kda_bwd_hi_products"):
        assert read(name) is None


def test_cell_configuration_keeps_every_published_width():
    """``benchmark/configs/kimi-linear-48b-a3b.json`` against the catalog's
    ``config`` (``model-configs``' ``architectures.jsonl``, quoted here): only
    the three reduced keys differ, each with its published value beside it."""
    cfg = run.load_json(BENCH, "configs", "kimi-linear-48b-a3b.json")
    published = dict(
        first_k_dense_replace=1, head_dim=72, hidden_act="silu",
        hidden_size=2304, intermediate_size=9216, kv_lora_rank=512,
        mla_use_nope=True, model_max_length=1048576, model_type="kimi_linear",
        moe_intermediate_size=1024, moe_layer_freq=1, moe_renormalize=True,
        moe_router_activation_func="sigmoid", num_attention_heads=32,
        num_expert_group=1, num_experts=256, num_experts_per_token=8,
        num_hidden_layers=27, num_key_value_heads=32,
        num_nextn_predict_layers=0, num_shared_experts=1, q_lora_rank=None,
        qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-5,
        rope_scaling=None, rope_theta=10000, routed_scaling_factor=2.446,
        tie_word_embeddings=False, topk_group=1, use_grouped_topk=True,
        v_head_dim=128, vocab_size=163840)
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "num_experts", "vocab_size"}
    assert {k: published[k] for k in changed} == cfg["published"]
    assert cfg["linear_attn_config"] == {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["router_num_experts"], cfg["num_experts_per_tok"]) == \
        (5, 8, 20480, 256, 8)
    cfgmod = run.load_module("configs", "kimi-linear-48b-a3b")
    mix = run.load_json(BENCH, "traffic", "s8192-b1-chain2.json")
    shapes = REF.param_shapes(cfg)
    # ISSUE 31's count: 602.4M parameters, 39.51M a KDA mixer, 29.11M the MLA's
    assert sum(int(np.prod(s)) for s in shapes.values()) == 602434432
    mixer = lambda i: sum(int(np.prod(s)) for n, s in shapes.items()  # noqa: E731
                          if n.startswith("layer%d_" % i)
                          and not n.startswith(("layer%d_moe" % i, "layer%d_shared" % i,
                                                "layer%d_ffn" % i, "layer%d_op_norm" % i,
                                                "layer%d_w" % i)))
    assert round(mixer(1) / 1e6, 2) == 39.51 and round(mixer(3) / 1e6, 2) == 29.11
    assert round(cfgmod.matmul_params_per_token(cfg) / 1e6) == 333
    costs = cfgmod.kernel_costs(cfg, mix)
    assert set(costs) == {"mxtpu_flash_fwd_stream", "mxtpu_flash_bwd_stream",
                          "mxtpu.block.kda", "ragged-dot"}
    assert costs["ragged-dot"]["calls"] == 36 and costs["mxtpu.block.kda"]["calls"] == 4
    # MLA's two kernels: 2.06 TFLOP of the step's 19.0
    flash = sum(costs[k]["flops"] for k in costs if k.startswith("mxtpu_flash"))
    assert abs(flash / 1e12 - 2.06) < 0.01
    assert abs(cfgmod.step_flops(cfg, mix, 1) / 1e12 - 19.0) < 0.1
