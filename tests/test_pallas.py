"""Pallas flash-attention kernel vs the jnp oracle (interpret mode on the
CPU test mesh; the same kernel compiles for the MXU on TPU)."""
import numpy as np
import pytest

import mxnet_tpu as mx


def _qkv(B=2, T=128, H=2, D=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.normal(0, 1, (B, T, H, D)).astype(np.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_interpret(causal):
    from mxnet_tpu.ops.pallas_kernels import (flash_attention,
                                              _attention_jnp)
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, True)  # interpret=True
    ref = _attention_jnp(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_backward_kernel(causal):
    """The Pallas flash backward (Q-block streaming, dK/dV accumulation
    over the grid, P reconstituted from the saved log-sum-exp) must
    match the dense jnp attention vjp."""
    import jax
    from mxnet_tpu.ops.pallas_kernels import (flash_attention,
                                              _attention_jnp)
    q, k, v = _qkv(T=256)
    rng = np.random.RandomState(7)
    g = rng.normal(0, 1, q.shape).astype(np.float32)

    _o, vjp = jax.vjp(lambda q, k, v:
                      flash_attention(q, k, v, causal, True), q, k, v)
    _r, ref_vjp = jax.vjp(lambda q, k, v:
                          _attention_jnp(q, k, v, causal), q, k, v)
    for got, want in zip(vjp(g), ref_vjp(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_op_fallback():
    q, k, v = _qkv(T=32)
    out = mx.nd._contrib_FlashAttention(mx.nd.array(q), mx.nd.array(k),
                                        mx.nd.array(v))
    from mxnet_tpu.ops.pallas_kernels import _attention_jnp
    ref = _attention_jnp(q, k, v, False)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_streaming_path(causal):
    """T > _BLOCK_K takes the K/V-streaming kernels (online-softmax
    forward scratch, full-sequence dQ accumulator backward, causal
    tile skip) — the path that lifts the old panel kernels' VMEM wall
    at S>=4096 (VERDICT r4 #2).  Exercised here at a shrunk _BLOCK_K
    so interpret mode stays fast while covering the real code path."""
    import jax
    from mxnet_tpu.ops import pallas_kernels as pk
    old_bk = pk._BLOCK_K
    pk._BLOCK_K = 256          # T=512 -> 2 K blocks: streaming engaged
    try:
        q, k, v = _qkv(B=1, T=512, H=2, D=32)
        rng = np.random.RandomState(7)
        g = rng.normal(0, 1, q.shape).astype(np.float32)
        out, vjp = jax.vjp(lambda q, k, v:
                           pk.flash_attention(q, k, v, causal, True),
                           q, k, v)
        ref, ref_vjp = jax.vjp(lambda q, k, v:
                               pk._attention_jnp(q, k, v, causal),
                               q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        for got, want in zip(vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       rtol=2e-4, atol=3e-5)
    finally:
        pk._BLOCK_K = old_bk


def test_block_choice_cliff_shapes():
    """ADVICE r5 perf cliff: a seq length that is not a _BLOCK_K
    multiple used to collapse straight to 128-wide K blocks (3200 ->
    25 tiny streams).  _blocks must now pick the largest block_q-
    multiple divisor of t that still fits the VMEM budget."""
    from mxnet_tpu.ops.pallas_kernels import (_BLOCK_K, _BLOCK_Q, _blocks,
                                              _flash_blocks)

    # multiples of _BLOCK_K stream the full panel
    assert _blocks(2048) == (128, 2048)
    assert _blocks(4096) == (128, 2048)
    # short sequences keep the single-panel fast path
    assert _blocks(512) == (128, 512)
    # the cliff shapes: largest 128-multiple divisor <= _BLOCK_K
    assert _blocks(3200) == (128, 640)    # 5 K blocks (was 25)
    assert _blocks(2304) == (128, 1152)   # 2 K blocks (was 18)
    assert _blocks(6144) == (128, 2048)   # 3 K blocks (was 48)
    # 2176 = 128 * 17: no larger divisor exists, 128 is genuinely best
    assert _blocks(2176) == (128, 128)

    # invariants across every Q-tileable length: the K block always
    # divides t (the grid is exact), is a block_q multiple (MXU
    # tileable), and never exceeds the VMEM budget; a causal call's Q
    # block (_flash_blocks) is 128 rows or more, divides t and the K
    # block, and leaves the K block what it was
    for t in range(128, 8193, 128):
        bq, bk = _blocks(t)
        assert bq == min(_BLOCK_Q, t)
        assert t % bk == 0, t
        assert bk % bq == 0, t
        assert bk <= max(_BLOCK_K, bq), t
        assert _flash_blocks(t, 64) == (bq, bk)
        cq, ck = _flash_blocks(t, 64, causal=True)
        assert ck == bk and cq in (128, 256, 512), t
        assert t % cq == 0 and bk % cq == 0, t
        assert cq == 128 or bk // cq >= 4, t


def test_attention_step_names_its_kernels(monkeypatch):
    """A tiny attention train step, lowered for the TPU from here: the
    flash kernels carry their own names into the program (``name=`` of
    the ``pallas_call``), forward under ``mxtpu.fwd`` and backward
    under ``mxtpu.bwd`` — what a device trace's "XLA Ops" line shows
    where it used to show ``transpose_jvp___``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import context
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    B, T, H, D = 2, 128, 2, 64
    x = mx.sym.Variable("data")
    qkv = mx.sym.FullyConnected(x, num_hidden=3 * H * D, flatten=False,
                                name="qkv")
    qkv = mx.sym.Reshape(qkv, shape=(0, 0, 3, H, -1))
    q, k, v = (mx.sym.Reshape(
        mx.sym.slice_axis(qkv, axis=2, begin=i, end=i + 1),
        shape=(0, 0, -3, -2)) for i in range(3))
    att = mx.sym._contrib_FlashAttention(q, k, v, causal=True, name="attn")
    att = mx.sym.Reshape(att, shape=(-1, H * D))
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(att, num_hidden=16, name="head"),
        label=mx.sym.Reshape(mx.sym.Variable("softmax_label"),
                             shape=(-1,)), name="softmax")
    t = ShardedTrainer(net, build_mesh(n_devices=1),
                       data_shapes={"data": (B, T, 32)},
                       label_shapes={"softmax_label": (B, T)},
                       optimizer="adam", learning_rate=1e-4)
    spec = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    args = (spec(t.params), spec(t.opt_state), spec(t.aux),
            {"data": jax.ShapeDtypeStruct((B, T, 32), jnp.float32),
             "softmax_label": jax.ShapeDtypeStruct((B, T), jnp.float32)},
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32))
    text = jax.jit(t._py_step).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("tpu_custom_call") >= 2
    assert (pk.FLASH_FWD_PANEL, pk.FLASH_BWD_PANEL) == \
        ("mxtpu_flash_fwd_panel", "mxtpu_flash_bwd_panel")
    assert "mxtpu.fwd/jvp(mxtpu_flash_fwd_panel)/pallas_call" in text
    assert "jvp(mxtpu_flash_bwd_panel)/pallas_call" in text
    assert "mxtpu.bwd/" in text and "mxtpu.opt/" in text
    # the streaming kernels' names are constants of their call sites too
    assert (pk.FLASH_FWD_STREAM, pk.FLASH_BWD_STREAM) == \
        ("mxtpu_flash_fwd_stream", "mxtpu_flash_bwd_stream")
    # the trainer's trace leaves the step's causal kernels, forward and
    # backward, for last_causal_plan(): one Q block a head here, so the
    # one diagonal tile is computed whole
    plan = pk.last_causal_plan()
    assert [(k["kernel"], k["shape"]) for k in plan["kernels"]] == \
        [("flash_attention_fwd", (B, T, H, D)),
         ("flash_attention_bwd", (B, T, H, D))]
    assert (plan["causal_ranges"], plan["scores_computed_pct"]) == (1, 100.0)


# ------------------------------------------- causal prefix ranges (PR 27)
# (t, block_q, block_k): six Q blocks on a K/V tile's diagonal, which 4
# ranges do not divide evenly; one tile (panel) and two (stream)
CAUSAL_ROUTES = {"panel": (384, 64, 384), "stream": (768, 64, 384)}
ONE_A_BLOCK = 6
_single_range = {}


def _causal_inputs(route, group):
    t = CAUSAL_ROUTES[route][0]
    rng = np.random.RandomState(3)
    mk = lambda h: rng.normal(0, 1, (1, t, h, 32)).astype(np.float32)
    return mk(4), mk(4 // group), mk(4 // group), mk(4)


def _causal_run(route, group, ranges, inputs=None):
    """(o, lse, dq, dk, dv) of the kernels at ``ranges`` static ranges."""
    from mxnet_tpu.ops import pallas_kernels as pk
    q, k, v, g = inputs or _causal_inputs(route, group)
    blocks = CAUSAL_ROUTES[route][1:]
    o, lse = pk._flash_attention_fwd_pallas(q, k, v, True, True,
                                            blocks=blocks, ranges=ranges)
    return (o, lse) + tuple(pk._flash_attention_bwd_pallas(
        q, k, v, o, lse, g, True, True, blocks=blocks, ranges=ranges))


@pytest.mark.parametrize("ranges", [1, 2, 4, ONE_A_BLOCK])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("route", sorted(CAUSAL_ROUTES))
def test_causal_ranges_change_no_number(route, group, ranges):
    """A Q block that multiplies only the K/V prefix its range can see
    gives what the whole tile gives: the columns left out enter every
    sum as exp(-inf) = 0.  Against the single-range kernels to 1e-6
    and against the dense float32 attention to this file's tolerance."""
    import jax
    from mxnet_tpu.ops import pallas_kernels as pk
    t, block_q, block_k = CAUSAL_ROUTES[route]
    m, plan = pk._causal_plan(block_q, block_k, ranges)
    assert (m, len(plan)) == (ONE_A_BLOCK, ranges)
    assert [hi - lo for lo, hi, _c in plan] == \
        {1: [6], 2: [3, 3], 4: [2, 1, 2, 1], 6: [1] * 6}[ranges]
    assert all(cols == hi * block_q for _lo, hi, cols in plan)
    key = (route, group)
    if key not in _single_range:
        _single_range[key] = _causal_run(route, group, 1)
    got = _causal_run(route, group, ranges)
    for a, b in zip(got, _single_range[key]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-6)
    q, k, v, g = _causal_inputs(route, group)
    want, vjp = jax.vjp(lambda *a: pk._attention_jnp(*a, True), q, k, v)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    for a, b in zip(got[2:], vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=3e-5)


@pytest.mark.parametrize("route", sorted(CAUSAL_ROUTES))
def test_first_q_block_of_each_grouped_head_reads_block_q_columns(route):
    """With one range a Q block, the first Q block of EVERY query head
    of a group (its place in its own head, not on the kernel's Q axis)
    reads the first ``block_q`` rows of K/V and no more: NaNs in V
    below them do not reach it, and they reach the whole-tile kernel
    (0 * NaN), so the test can tell."""
    t, block_q, _bk = CAUSAL_ROUTES[route]
    q, k, v, g = _causal_inputs(route, 4)
    bad = v.copy()
    bad[:, block_q:] = np.nan
    clean = _causal_run(route, 4, ONE_A_BLOCK)
    got = _causal_run(route, 4, ONE_A_BLOCK, (q, k, bad, g))
    whole = _causal_run(route, 4, 1, (q, k, bad, g))
    for name, i in (("o", 0), ("dq", 2)):
        first = np.asarray(got[i])[:, :block_q]
        assert np.isfinite(first).all(), name
        np.testing.assert_array_equal(first,
                                      np.asarray(clean[i])[:, :block_q])
        assert np.isnan(np.asarray(whole[i])[:, :block_q]).any(), name
        assert np.isnan(np.asarray(got[i])[:, block_q:]).any(), name


# sha256[:16] of the jaxpr text of forward + backward at causal=False on
# the commit before the ranges came (2fa84db), q (2, 256, 4, 32) float32
NONCAUSAL_JAXPR = {("panel", 4): "3385563da8120ad7",
                   ("panel", 1): "f392d7a587c082be",
                   ("stream", 4): "1d115c3aaeaf854e",
                   ("stream", 1): "75d30303bea0f05e"}


@pytest.mark.parametrize("route,kv_heads", sorted(NONCAUSAL_JAXPR))
def test_noncausal_calls_are_the_parents(route, kv_heads):
    """``causal=False`` traces to the calls it always did, kernel bodies
    included, with as many key/value heads as query heads and with
    fewer: ring attention's blocks and every bidirectional model."""
    import hashlib
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    blocks = {"panel": (128, 256), "stream": (64, 64)}[route]
    q = jax.ShapeDtypeStruct((2, 256, 4, 32), jnp.float32)
    kv = jax.ShapeDtypeStruct((2, 256, kv_heads, 32), jnp.float32)

    def f(q, k, v, g):
        o, lse = pk._flash_attention_fwd_pallas(q, k, v, False, True,
                                                blocks=blocks)
        return pk._flash_attention_bwd_pallas(q, k, v, o, lse, g, False,
                                              True, blocks=blocks)

    text = str(jax.make_jaxpr(f)(q, kv, kv, q))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        NONCAUSAL_JAXPR[route, kv_heads]


@pytest.mark.parametrize("t,block_k,ranges,want", [
    # panel: S even ranges compute (S + 1) / 2S of the square
    (2048, 2048, 1, 100.0), (2048, 2048, 2, 75.0), (2048, 2048, 4, 62.5),
    (2048, 2048, 8, 56.25), (2048, 2048, 16, 53.125),
    # stream, 4 x 4 tiles: 6 below the diagonal whole, 4 on it by ranges
    (8192, 2048, 1, 100.0 * (6 + 4) / 16),
    (8192, 2048, 4, 100.0 * (6 + 4 * 5 / 8) / 16),
    (8192, 2048, 16, 100.0 * (6 + 4 * 17 / 32) / 16)])
def test_last_causal_plan_counts_the_scores_computed(t, block_k, ranges,
                                                     want):
    """The kernels' records and ``last_causal_plan()`` say into how many
    ranges a diagonal tile is split and which share of a head's t * t
    scores is computed; only tracing happens here."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.telemetry import costdb
    telemetry.reset()
    q = jax.ShapeDtypeStruct((1, t, 2, 64), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((2, t, 1), jnp.float32)

    # sixteen places on a tile's diagonal, for up to sixteen ranges: Q
    # blocks of 128 rows (the rule's 512 leave four)
    kw = dict(ranges=ranges, blocks=(128, block_k))

    def f(q, k, v, o, lse, g):
        pk._flash_attention_fwd_pallas(q, k, v, True, True, **kw)
        # a kernel that is not causal joins no plan
        pk._flash_attention_fwd_pallas(q, k, v, False, True, **kw)
        return pk._flash_attention_bwd_pallas(q, k, v, o, lse, g, True, True,
                                              **kw)

    with pk.causal_plan_recording():
        jax.make_jaxpr(f)(q, q, q, q, lse, q)
    plan = pk.last_causal_plan()
    assert [k["kernel"] for k in plan["kernels"]] == \
        ["flash_attention_fwd", "flash_attention_bwd"]
    assert plan["causal_ranges"] == ranges
    assert plan["scores_computed_pct"] == pytest.approx(want)
    for kern in plan["kernels"]:
        assert (kern["block_q"], kern["block_k"]) == (128, block_k)
        assert kern["shape"] == (1, t, 2, 64)
        assert kern["scores_computed_pct"] == pytest.approx(want)
    with costdb.DB._lock:
        configs = [s["block_config"] for s in costdb.DB._pending]
    assert sorted((c["causal"], c["causal_ranges"],
                   round(c["scores_computed_pct"], 3)) for c in configs) == \
        sorted([(False, 1, 100.0)] + [(True, ranges, round(want, 3))] * 2)


def test_default_causal_ranges_follow_the_blocks():
    """The rule reads the blocks it is given: a tuned pair from the cache
    gets ranges of its own Q blocks, and a ``block_k`` that ``block_q``
    does not divide keeps whole diagonal tiles."""
    from mxnet_tpu.ops import pallas_kernels as pk
    s = pk._CAUSAL_RANGES
    m, plan = pk._causal_plan(128, 2048)
    assert m == 16 and len(plan) == min(s, 16)
    assert plan[-1] == (plan[-1][0], 16, 2048)
    assert pk._causal_plan(256, 2048)[0] == 8
    assert pk._causal_plan(128, 128) == (1, ((0, 1, 128),))
    assert pk._causal_plan(128, 192) == (1, ((0, 1, 192),))
    # every Q block's place lies in exactly one range, which sees it whole
    for block_q, block_k in ((128, 2048), (64, 384), (128, 640), (128, 1152)):
        for ranges in (None, 1, 2, 3, 4, 5, 100):
            m, plan = pk._causal_plan(block_q, block_k, ranges)
            assert [lo for lo, _h, _c in plan] == \
                [0] + [hi for _l, hi, _c in plan[:-1]]
            assert plan[-1][1] == m
            assert all(cols >= hi * block_q and cols <= block_k
                       for _lo, hi, cols in plan)


# ------------------------------------------- the Q block from the shape
def _shape_chosen_case(block_q, route, group, widths):
    """(``_BLOCK_K``, t, q heads, k/v heads, dk, dv) at which
    ``_flash_blocks`` gives ``block_q`` rows on ``route``: a diagonal of
    four places, one K/V tile (panel) or two (stream)."""
    block_k = 4 * block_q
    return (block_k, block_k * (2 if route == "stream" else 1), group, 1) \
        + widths


@pytest.mark.parametrize("widths", [(16, 16), (24, 16)],
                         ids=["dk_eq_dv", "dk_192_dv_128_scaled"])
@pytest.mark.parametrize("group", [1, 2], ids=["one_head", "grouped"])
@pytest.mark.parametrize("route", ["panel", "stream"])
@pytest.mark.parametrize("block_q", [256, 512])
def test_causal_call_at_the_shape_chosen_q_block(monkeypatch, block_q, route,
                                                 group, widths):
    """Forward and backward in interpret mode at Q blocks of 256 and 512
    rows, chosen by the rule itself (a smaller ``_BLOCK_K`` where 256 is
    the largest that leaves four places), on both routes, one query head a
    key/value head and two, equal widths and latent attention's 192 / 128
    scaled down, against the plain formula."""
    import jax
    from mxnet_tpu.ops import pallas_kernels as pk
    block_k, t, hq, hk, dk, dv = _shape_chosen_case(block_q, route, group,
                                                    widths)
    monkeypatch.setattr(pk, "_BLOCK_K", block_k)
    assert pk._flash_blocks(t, dk, dv, hq // hk, True) == (block_q, block_k)
    rng = np.random.RandomState(block_q + t + hq + dk)
    mk = lambda h, d: rng.normal(0, 1, (1, t, h, d)).astype(np.float32)
    q, k, v, g = mk(hq, dk), mk(hk, dk), mk(hk, dv), mk(hq, dv)
    with pk.causal_plan_recording():
        out, vjp = jax.vjp(lambda q, k, v:
                           pk.flash_attention(q, k, v, True, True), q, k, v)
        grads = vjp(g)
    plan = pk.last_causal_plan()
    assert plan["q_block_rows"] == block_q and plan["causal_ranges"] == 4
    assert [(e["kernel"], e["block_q"], e["block_k"])
            for e in plan["kernels"]] == [
        ("flash_attention_fwd", block_q, block_k),
        ("flash_attention_bwd", block_q, block_k)]
    ref, ref_vjp = jax.vjp(lambda q, k, v:
                           pk._attention_jnp(q, k, v, True), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    for got, want in zip(grads, ref_vjp(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=3e-5)


@pytest.mark.parametrize("t,pct", [(2048, 62.5), (8192, 53.125)])
def test_rule_keeps_four_ranges_and_the_share_computed(t, pct):
    """OPT's panel and the 8192-token cells' streamed tiles: Q blocks of
    512 rows leave a tile of 2048 columns four places on its diagonal, so
    the plan keeps four ranges and computes what 128 rows computed."""
    from mxnet_tpu.ops import pallas_kernels as pk
    for dk, dv, group in ((64, 64, 1), (64, 64, 4), (192, 128, 1),
                          (128, 128, 8)):
        blocks = pk._flash_blocks(t, dk, dv, group, True)
        assert blocks == (512, 2048)
        m, ranges = plan = pk._causal_plan(*blocks)
        assert (m, len(ranges)) == (4, pk._CAUSAL_RANGES)
        assert pk._scores_computed_pct(t, *blocks, plan) == pct == \
            pk._scores_computed_pct(t, 128, 2048, pk._causal_plan(128, 2048))


@pytest.mark.parametrize("t,causal,group,d,blocks,why", [
    (1024, True, 1, 64, (256, 1024), "512 rows leave a panel of 1024 two places"),
    (1536, True, 1, 64, (256, 1536), "512 rows leave 1536 columns three places"),
    (2560, True, 1, 64, (256, 1280), "512 does not divide the K/V tile of 1280"),
    (3200, True, 1, 64, (128, 640), "256 does not divide the K/V tile of 640"),
    (1152, True, 1, 64, (128, 1152), "256 does not divide t"),
    (512, True, 1, 64, (128, 512), "a panel of 512 has two places at 256"),
    (8192, False, 8, 128, (128, 2048), "not causal"),
    (16384, True, 8, 128, (512, 2048), "the group's dQ rows (64 MiB) leave no "
     "VMEM: the backward runs as two calls of four heads, whose rows do"),
    (16384, True, 2, 128, (512, 2048), "two heads' dQ rows do")])
def test_rule_falls_back_where_512_rows_do_not_suit(t, causal, group, d,
                                                    blocks, why):
    from mxnet_tpu.ops import pallas_kernels as pk
    assert pk._flash_blocks(t, d, d, group, causal) == blocks, why
    assert t % blocks[0] == 0 and t % blocks[1] == 0
    # the op's own test of a length keeps its granularity of 128 rows
    assert blocks[0] >= min(pk._BLOCK_Q, t)


def test_windowed_call_keeps_its_measured_blocks(monkeypatch):
    """A sliding window's blocks are ``_WINDOW_BLOCKS`` still, and the plan
    says the smallest Q block over causal and windowed kernels."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    q = jax.ShapeDtypeStruct((1, 4096, 2, 32), jnp.float32)

    def both(q, k, v):
        return (pk._flash_attention_fwd_pallas(q, k, v, True, True,
                                               window=1024)[0],
                pk._flash_attention_fwd_pallas(q, k, v, True, True)[0])

    with pk.causal_plan_recording():
        jax.make_jaxpr(both)(q, q, q)
    plan = pk.last_causal_plan()
    assert [(e["kernel"], e["block_q"], e["block_k"])
            for e in plan["kernels"]] == [
        ("flash_attention_fwd_window", 512, 1024),
        ("flash_attention_fwd", 512, 2048)]
    assert plan["q_block_rows"] == 512
    assert pk._window_blocks(4096) == pk._WINDOW_BLOCKS == (512, 1024)
    # one kernel on the old constant and the summary says so
    with pk.causal_plan_recording():
        jax.make_jaxpr(lambda q, k, v: (
            both(q, k, v), pk._flash_attention_fwd_pallas(
                q, k, v, True, True, blocks=(128, 2048))))(q, q, q)
    assert pk.last_causal_plan()["q_block_rows"] == 128


@pytest.mark.parametrize("shape,blocks", [
    ((1, 2048, 2, 2, 64), (128, 2048)), ((1, 2048, 2, 2, 64), (256, 2048)),
    ((1, 2048, 2, 2, 64), (512, 2048)), ((1, 8192, 4, 1, 64), (128, 2048)),
    ((1, 8192, 4, 1, 64), (512, 2048)), ((1, 4096, 1, 1, 192), (512, 2048)),
    ((1, 512, 2, 2, 64), (128, 512)), ((1, 4096, 1, 1, 64), (128, 128))],
    ids=str)
def test_vmem_is_asked_for_exactly_when_the_need_passes(shape, blocks):
    """A backward kernel asks for ``vmem_limit_bytes`` when, and only when,
    the need reckoned from its blocks passes the scoped default: the
    streamed backward's dQ accumulator under grouped queries does, the
    panel kernel at 512 x 2048 in bfloat16 does not (reckoned 15 MiB, the
    compiler's least limit there); no forward asks."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    b, t, hq, hk, d = shape
    q = jax.ShapeDtypeStruct((b, t, hq, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, t, hk, d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((b * hq, t, 1), jnp.float32)
    dq_rows = hq // hk * t if t > blocks[1] else 0
    fwd = str(jax.make_jaxpr(lambda q, k, v: pk._flash_attention_fwd_pallas(
        q, k, v, True, True, blocks=blocks))(q, kv, kv))
    bwd = str(jax.make_jaxpr(
        lambda q, k, v, o, lse, g: pk._flash_attention_bwd_pallas(
            q, k, v, o, lse, g, True, True, blocks=blocks))(
                q, kv, kv, q, lse, q))
    assert "vmem_limit" not in fwd
    need = pk._vmem_need(d, *blocks, dq_rows)
    asked = need > pk._VMEM_DEFAULT
    assert ("vmem_limit_bytes=%d" % min(need * 3 // 2, pk._VMEM_MAX)
            in bwd) == asked, need
    assert ("vmem_limit" in bwd) == asked
    assert (pk._vmem_params(need) != {}) == asked
    # OPT's sixteen panel calls ask for nothing: 15 of the default's 16 MiB
    assert pk._vmem_need(64, 512, 2048) == 15 * 2 ** 20
    # what the rule's Q block adds to a call of one query head a key/value
    # head at 8192 positions is a request; at 128 rows, without a mask or
    # under a window (512 x 1024), that call asks for nothing, as always
    assert "compiler_params" in pk._vmem_params(
        pk._vmem_need(128, 512, 2048, 8192))
    for d_, blocks_ in ((64, (128, 2048)), (128, (128, 2048)),
                        (128, pk._WINDOW_BLOCKS)):
        assert pk._vmem_params(pk._vmem_need(d_, *blocks_, 8192)) == {}
