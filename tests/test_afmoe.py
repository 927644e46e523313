"""Trinity-Mini (PR 33): a sliding window in the flash kernels against the
plain formula, the band's tile arithmetic against a brute-force count of the
mask, the grouped-query attention builder two models share, the shares of
the expert layer with the shared expert counted once, and the five-layer
model through ``ShardedTrainer`` against the plain reference
(``benchmark/references/trinity-mini.py``), all at toy size on the CPU.
"""
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
from mxnet_tpu.models import afmoe, decoder_blocks
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
    run, REF = harness, harness.load_module("references", "trinity-mini")
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


# ------------------------------------------- the window in the kernels
T, BLOCK_Q = 64, 8
#: (block_q, block_k): one K/V panel, and four K/V tiles streamed
ROUTES = {"panel": (BLOCK_Q, 64), "stream": (BLOCK_Q, 16)}
#: 1, less than block_q, block_q, a whole streamed block_k, block_k +
#: block_q, a value no block divides, T, above T
WINDOWS = [1, 5, 8, 16, 24, 27, 64, 100]


def _inputs(hq, hk, dk, dv, t=T):
    return (_rand(1, t, hq, dk, seed=1), _rand(1, t, hk, dk, seed=2),
            _rand(1, t, hk, dv, seed=3), _rand(1, t, hq, dv, seed=4))


def _both(q, k, v, g, blocks, window):
    o, lse = pk._flash_attention_fwd_pallas(q, k, v, True, True,
                                            blocks=blocks, window=window)
    return (o,) + pk._flash_attention_bwd_pallas(
        q, k, v, o, lse, g, True, True, blocks=blocks, window=window)


def _check_against_the_formula(route, window, heads, widths):
    q, k, v, g = _inputs(*heads, *widths)
    got = _both(q, k, v, g, ROUTES[route], window)
    want, pull = jax.vjp(
        lambda q, k, v: pk._attention_jnp(q, k, v, True, window), q, k, v)
    for a, b in zip(got, (want,) + pull(g)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("heads", [(4, 4), (4, 1), (8, 1)],
                         ids=["group1", "group4", "group8"])
@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_windowed_kernels_match_the_plain_formula(route, window, heads):
    """Interpret mode, float32, forward and the three gradients."""
    _check_against_the_formula(route, window, heads, (16, 16))


@pytest.mark.parametrize("heads", [(2, 2), (4, 1)], ids=["group1", "group4"])
@pytest.mark.parametrize("window", [5, 24])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_windowed_kernels_take_values_of_their_own_width(route, window, heads):
    _check_against_the_formula(route, window, heads, (192, 128))


def test_plain_formula_is_the_inequality():
    """``_attention_jnp``'s window and the reference's are ``0 <= t - j <
    window``, written out here once more."""
    q, k, v, _ = _inputs(2, 2, 16, 16, t=16)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 16 ** -0.5
    back = np.arange(16)[:, None] - np.arange(16)[None, :]
    s = jnp.where((back >= 0) & (back < 5), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(pk._attention_jnp(q, k, v, True, 5), want,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        jax.vmap(lambda q, k, v: REF.softmax_attention(q, k, v, 5))(q, k, v),
        want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("window", [0, T, T + 36], ids=["none", "T", "above_T"])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_window_that_reaches_the_prefix_is_the_causal_call(route, window):
    """Bit-equal to the causal call: the same jaxpr (names, grids, bodies),
    the same ``last_causal_plan()``, no windowed kernel."""
    q, k, v, g = _inputs(8, 2, 16, 16)
    blocks = ROUTES[route]
    causal = lambda q, k, v, g: _both(q, k, v, g, blocks, 0)       # noqa: E731
    windowed = lambda q, k, v, g: _both(q, k, v, g, blocks, window)  # noqa: E731
    with pk.causal_plan_recording():
        want_text = str(jax.make_jaxpr(causal)(q, k, v, g))
    want_plan = pk.last_causal_plan()
    with pk.causal_plan_recording():
        text = str(jax.make_jaxpr(windowed)(q, k, v, g))
    assert text == want_text and pk.last_causal_plan() == want_plan
    assert "window" not in text and want_plan["window_layers"] == 0
    assert want_plan["window_scores_computed_pct"] is None
    assert {(e["window"], e["tiles_per_q_block"])
            for e in want_plan["kernels"]} == {(0, T // blocks[1])}
    for a, b in zip(windowed(q, k, v, g), causal(q, k, v, g)):
        np.testing.assert_array_equal(a, b)


def test_windowed_stream_call_carries_its_own_names_and_plan():
    q, k, v, g = _inputs(8, 1, 16, 16)
    with pk.causal_plan_recording():
        text = str(jax.make_jaxpr(
            lambda *a: _both(*a, ROUTES["stream"], 24))(q, k, v, g))
    assert (pk.FLASH_FWD_WINDOW, pk.FLASH_BWD_WINDOW) == \
        ("mxtpu_flash_fwd_window", "mxtpu_flash_bwd_window")
    assert text.count("name=mxtpu_flash_fwd_window") == 1
    assert text.count("name=mxtpu_flash_bwd_window") == 1
    assert "_stream" not in text
    plan = pk.last_causal_plan()
    assert [e["kernel"] for e in plan["kernels"]] == [
        "flash_attention_fwd_window", "flash_attention_bwd_window"]
    want = pk._scores_computed_pct(T, 8, 16, pk._causal_plan(8, 16), 24)
    assert plan["window_layers"] == 1
    assert plan["window_scores_computed_pct"] == want < 100 * 40 / 64
    assert {(e["window"], e["tiles_per_q_block"])
            for e in plan["kernels"]} == {(24, 3)}
    # the panel route masks and skips nothing: the causal kernels' names
    with pk.causal_plan_recording():
        text = str(jax.make_jaxpr(
            lambda *a: _both(*a, ROUTES["panel"], 24))(q, k, v, g))
    assert text.count("name=mxtpu_flash_fwd_panel") == 1
    assert text.count("name=mxtpu_flash_bwd_panel") == 1
    plan = pk.last_causal_plan()
    assert plan["window_layers"] == 0
    assert {e["window"] for e in plan["kernels"]} == {0}


# ------------------------------------------------ the band's arithmetic
BANDS = [(8192, 2048, 128, 2048), (8192, 2048, 128, 512),
         (8192, 2048, 512, 1024), (64, 5, 8, 16), (64, 24, 8, 16),
         (64, 16, 8, 16), (96, 10, 8, 12), (4096, 1000, 128, 1024)]


@pytest.mark.parametrize("t,window,block_q,block_k", BANDS, ids=str)
def test_band_tiles_against_a_brute_force_count_of_the_mask(t, window,
                                                            block_q, block_k):
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = ((back >= 0) & (back < window)).reshape(
        t // block_q, block_q, t // block_k, block_k)
    live = seen.any(axis=(1, 3))
    above = (back < 0).reshape(seen.shape).any(axis=(1, 3))
    below = (back >= window).reshape(seen.shape).any(axis=(1, 3))
    plan = pk._causal_plan(block_q, block_k)
    m, ranges = plan
    done, most = 0, 0
    for qpos in range(t // block_q):
        tiles = np.flatnonzero(live[qpos])
        first, last = pk._live_k_tiles(qpos, block_q, block_k, window)
        # every tile between the first and the last is live: the backward
        # starts a Q block's dQ rows on the first and emits them on the last
        assert list(tiles) == list(range(first, last + 1))
        most = max(most, len(tiles))
        for ki in tiles:
            assert pk._band_tile(qpos, ki, block_q, block_k, window) == \
                (above[qpos, ki], below[qpos, ki])
            diagonal = above[qpos, ki] and not below[qpos, ki]
            done += next(c for lo, hi, c in ranges
                         if lo <= qpos % m < hi) if diagonal else block_k
    assert pk._window_tiles_per_q_block(t, block_q, block_k, window) == most
    pct = pk._scores_computed_pct(t, block_q, block_k, plan, window)
    assert pct == 100.0 * done * block_q / (t * t)
    assert pct >= 100.0 * seen.sum() / (t * t)
    # the band seen from a K/V tile
    per_tile = 0
    for ki in range(t // block_k):
        blocks = np.flatnonzero(live[:, ki])
        first, last = pk._live_q_blocks(ki, block_q, block_k, window,
                                        t // block_q)
        assert list(blocks) == list(range(first, last + 1))
        per_tile = max(per_tile, len(blocks))
    assert pk._window_q_blocks_per_tile(t, block_q, block_k, window) == per_tile


def test_the_cells_band_in_numbers():
    """8192 positions under a window of 2048: the band is 21.9% of a head's
    square; at 128 rows a Q block whole tiles of 2048 compute 34.375%, of
    512 25%; the blocks a windowed call takes 29.6875%; the causal kernels
    53.125%."""
    plan = pk._causal_plan(128, 2048)
    assert pk._scores_computed_pct(8192, 128, 2048, plan) == 53.125
    assert pk._scores_computed_pct(8192, 128, 2048, plan, 2048) == 34.375
    assert pk._scores_computed_pct(
        8192, 128, 512, pk._causal_plan(128, 512), 2048) == 25.0
    assert pk._window_tiles_per_q_block(8192, 128, 2048, 2048) == 2
    assert pk._window_q_blocks_per_tile(8192, 128, 2048, 2048) == 32
    blocks = pk._window_blocks(8192)
    assert blocks == (512, 1024)
    assert pk._scores_computed_pct(
        8192, *blocks, pk._causal_plan(*blocks), 2048) == 29.6875
    assert pk._window_tiles_per_q_block(8192, *blocks, 2048) == 3
    assert pk._window_q_blocks_per_tile(8192, *blocks, 2048) == 6


@pytest.mark.parametrize("t,blocks", [
    (8192, (512, 1024)), (4096, (512, 1024)), (3072, (512, 1024)),
    (2048, (128, 2048)), (512, (128, 512)), (64, (64, 64)),
    (3200, (128, 640))])
def test_windowed_call_takes_the_measured_blocks_on_the_streamed_route(t, blocks):
    """Up to one K/V panel, and for a length that tiles of 1024 do not
    divide, the heuristic's."""
    assert pk._window_blocks(t) == blocks
    assert t % blocks[0] == 0 and t % blocks[1] == 0


# ------------------------------------------------------------ the op
@pytest.mark.parametrize("attrs,says", [
    ({"causal": False, "window": 4}, "needs causal"),
    ({"causal": True, "window": -1}, "positive count"),
])
def test_op_refuses_a_window_it_cannot_honour(attrs, says):
    q = mx.nd.zeros((1, 8, 2, 4))
    with pytest.raises(MXNetError, match=says):
        mx.nd._contrib_FlashAttention(q, q, q, **attrs)


def test_ring_attention_refuses_a_window():
    q = mx.nd.zeros((1, 8, 2, 4))
    with pytest.raises(MXNetError, match="takes no sliding window"):
        mx.nd._contrib_RingAttention(q, q, q, causal=True, window=4)
    assert mx.nd._contrib_RingAttention(q, q, q, causal=True).shape == q.shape


def test_op_honours_the_window_and_carries_the_scope():
    from mxnet_tpu.ops.registry import OpContext, get_op
    q, k, v, _ = _inputs(4, 2, 16, 16, t=16)
    out = mx.nd._contrib_FlashAttention(
        *[mx.nd.array(np.asarray(a)) for a in (q, k, v)], causal=True, window=5)
    np.testing.assert_allclose(out.asnumpy(),
                               pk._attention_jnp(q, k, v, True, 5),
                               rtol=1e-5, atol=1e-6)
    op = get_op("_contrib_FlashAttention")

    def lowered(**attrs):
        return jax.jit(lambda *a: op.fcompute(
            op.parse_attrs(dict(causal=True, **attrs)),
            OpContext(is_train=True), *a)).lower(q, k, v).as_text(
                debug_info=True)

    assert pk.SCOPE_SWA == "mxtpu.block.swa" and pk.SCOPE_SWA in lowered(window=5)
    assert pk.SCOPE_SWA not in lowered()
    assert pk.SCOPE_SWA not in lowered(window=16)      # the whole prefix


# ------------------------------------------------ the attention builder
PARENT_DIGESTS = {
    # the lowered text of each toy configuration's 2-step chain, hashed on the
    # parent of PR 33 (``git archive`` of b8e6a7f, the same script)
    "smoke-lfm2": ("smoke-s64-b1-chain2",
                   "364a9ef0eb68e8ae50b5ce91fc4275e9e2b0a23f082ac769d7e265df9915b73f"),
    # refreshed by PR 39, which means to move it: the toy holds 4 of 16
    # experts, a layer with two sizes, so its step holds ``topk_moe``'s
    # ``cond`` (7528ea78... on the parent of PR 33); the two others hold half
    # or all of their experts and stay the parent's
    "smoke-kimi": ("smoke-s64-b1-chain2",
                   "2d4fa983b93f4193eb06a6276843ceb1669cf5e053760be84be1f5b43ba6ac63"),
    "smoke-opt": ("smoke-s32-b2-chain2",
                  "6c71f808a100828fbf2ad7368361a4237734a6cdd0e8fa7adb679093d03aa53a"),
}


def _toy_trainer(cfg, mix):
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    net, data, label = run.load_module("configs", cfg["code"]).build(cfg, mix, 1)
    opt = dict(cfg["optimizer"])
    trainer = ShardedTrainer(
        net, build_mesh(devices=jax.devices()[:1], tp=1), data_shapes=data,
        label_shapes=label, optimizer=opt.pop("optimizer"), seed=1, **opt,
        **cfg["trainer"])
    return trainer, data, label


@pytest.mark.parametrize("name", sorted(PARENT_DIGESTS))
def test_neighbours_toy_steps_lower_to_the_parents_text(name):
    """The shared attention builder, the shared expert's helper and the
    window in the kernels' file leave the three neighbours' steps as they
    were.  A change that means to move one refreshes its digest and says so."""
    mix, digest = PARENT_DIGESTS[name]
    cfg = run.load_json(BENCH, "configs", name + ".json")
    trainer, data, label = _toy_trainer(
        cfg, run.load_json(BENCH, "traffic", mix + ".json"))
    batch = trainer.put_batch({k: np.zeros(v, np.float32)
                               for k, v in {**data, **label}.items()})
    fn, args = trainer._prepare_run_steps(batch, 2)
    assert hashlib.sha256(fn.lower(*args).as_text().encode()).hexdigest() \
        == digest


LAYER = dict(hidden_size=32, num_attention_heads=8, num_key_value_heads=2,
             head_dim=8, rms_norm_eps=1e-5, rope_theta=10000, sliding_window=6)


def _attention_params():
    d, hq, hk, hd = 32, 8, 2, 8
    return {"q_weight": _rand(hq * hd, d, seed=1, scale=0.3),
            "k_weight": _rand(hk * hd, d, seed=2, scale=0.3),
            "v_weight": _rand(hk * hd, d, seed=3, scale=0.3),
            "g_weight": _rand(hq * hd, d, seed=4, scale=0.3),
            "o_weight": _rand(d, hq * hd, seed=5, scale=0.3),
            "q_norm_gamma": 1 + _rand(hd, seed=6, scale=0.1),
            "k_norm_gamma": 1 + _rand(hd, seed=7, scale=0.1)}


@pytest.mark.parametrize("kind", [afmoe.SLIDING, afmoe.FULL])
def test_attention_layer_matches_its_lines_of_the_reference(kind):
    """Values and every gradient of one attention sub-layer of each type:
    rotary embedding and the window on the sliding one, neither on the full
    one, the gate before ``W_o`` on both."""
    x, p = _rand(2, 16, 32, seed=9), _attention_params()
    cot = _rand(2, 16, 32, seed=10)
    net = afmoe._attention(mx.sym.Variable("x"), LAYER, kind, "l_")
    nodes = json.loads(net.tojson())["nodes"]
    ops = [n["op"] for n in nodes]
    sliding = kind == afmoe.SLIDING
    assert ops.count("_contrib_RotaryEmbedding") == (2 if sliding else 0)
    attn = next(n for n in nodes if n["op"] == "_contrib_FlashAttention")
    assert attn["attrs"]["window"] == ("6" if sliding else "0")
    # the gate multiplies the heads' outputs, and W_o takes the product
    assert nodes[-1]["name"] == "l_o"
    assert nodes[nodes[-1]["inputs"][0][0]]["op"] == "elemwise_mul"
    exe = net.simple_bind(mx.cpu(), grad_req="write", x=x.shape)
    exe.arg_dict["x"][:] = np.asarray(x)
    for n, w in p.items():
        exe.arg_dict["l_" + n][:] = np.asarray(w)
    exe.forward(is_train=True)
    exe.backward([mx.nd.array(np.asarray(cot))])

    def ref(x, p):
        return jax.vmap(lambda x: REF.attention_layer(x, p, sliding, LAYER))(x)

    want, pull = jax.vjp(ref, x, p)
    np.testing.assert_allclose(exe.outputs[0].asnumpy(), want,
                               rtol=2e-5, atol=2e-6)
    gx, gp = pull(cot)
    np.testing.assert_allclose(exe.grad_dict["x"].asnumpy(), gx,
                               rtol=2e-4, atol=2e-5)
    for n in p:
        np.testing.assert_allclose(exe.grad_dict["l_" + n].asnumpy(), gp[n],
                                   rtol=2e-4, atol=2e-5, err_msg=n)
    # the other type's mask or rotation is not this one's
    other = jax.vmap(lambda x: REF.attention_layer(x, p, not sliding, LAYER))(x)
    assert float(jnp.abs(other - want).max()) > 1e-2


def test_lfm2_builds_its_attention_from_the_shared_builder():
    """No window, no gate, rotary embedding: the arguments LFM2 reads out of
    its own keys; the names are the ones its checkpoints and reference use."""
    from mxnet_tpu.models import lfm2_moe
    cfg = run.load_json(BENCH, "configs", "smoke-lfm2.json")
    net = lfm2_moe._attention(mx.sym.Variable("x"), cfg, "layer1_")
    nodes = json.loads(net.tojson())["nodes"]
    assert [n["name"] for n in nodes if n["op"] == "null"] == [
        "x", "layer1_q_weight", "layer1_q_norm_gamma", "layer1_k_weight",
        "layer1_k_norm_gamma", "layer1_v_weight", "layer1_o_weight"]
    attn = next(n for n in nodes if n["op"] == "_contrib_FlashAttention")
    assert attn["attrs"] == {"causal": "True", "window": "0",
                             "diffusion_block": "0"}
    assert decoder_blocks.grouped_query_attention.__module__ == \
        lfm2_moe.grouped_query_attention.__module__


# --------------------------------------------- the shares add up
WHOLE = dict(num_experts=16, router_num_experts=16, num_experts_per_tok=4,
             expert_offset=0, route_norm=True, route_scale=2.826,
             router_trained=True, num_shared_experts=1)


def _whole_params(d=16, ff=24, e=16):
    return {"moe_router_weight": _rand(e, d, seed=1, scale=0.5),
            "moe_expert_bias": _rand(e, seed=3, scale=0.1),
            "moe_w1_weight": _rand(e, d, ff, seed=4, scale=0.2),
            "moe_w3_weight": _rand(e, d, ff, seed=5, scale=0.2),
            "moe_w2_weight": _rand(e, ff, d, seed=6, scale=0.2),
            "shared_w1_weight": _rand(ff, d, seed=7, scale=0.2),
            "shared_w3_weight": _rand(ff, d, seed=8, scale=0.2),
            "shared_w2_weight": _rand(d, ff, seed=9, scale=0.2)}


def _shares_sum(x, p):
    """4 shares of 4 experts, each without the shared expert, summed, plus the
    shared expert once (every chip computes it alike)."""
    y = 0.0
    for off in range(0, 16, 4):
        y = y + moe.topk_moe(
            x, p["moe_router_weight"], p["moe_expert_bias"],
            *(p["moe_%s_weight" % n][off:off + 4] for n in ("w1", "w3", "w2")),
            WHOLE["num_experts_per_tok"], expert_offset=off,
            norm_topk_prob=WHOLE["route_norm"],
            routed_scaling_factor=WHOLE["route_scale"])[0]
    shared = jax.nn.silu(x @ p["shared_w1_weight"].T) * (x @ p["shared_w3_weight"].T)
    return y + shared @ p["shared_w2_weight"].T


def _uncut(x, p):
    return REF.expert_layer(x, p, WHOLE) + REF.shared_expert(x, p)


@pytest.mark.parametrize("what", ["values", "input_gradients"])
def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(what):
    x, p = _rand(40, 16), _whole_params()
    if what == "values":
        np.testing.assert_allclose(_shares_sum(x, p), _uncut(x, p),
                                   rtol=1e-5, atol=1e-6)
        return
    cot = _rand(40, 16, seed=11)
    got = jax.grad(lambda x: jnp.sum(_shares_sum(x, p) * cot))(x)
    want = jax.grad(lambda x: jnp.sum(_uncut(x, p) * cot))(x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------- the whole model
def _toy_bench():
    bench = dict(run.load_json(run.ROOT, "BENCHMARK.json"))
    bench["configs"] = [{"name": "smoke-trinity",
                         "file": "benchmark/configs/smoke-trinity.json"}]
    bench["workloads"] = [{"name": "smoke-trinity", "config": "smoke-trinity",
                           "traffic": "smoke-s64-b1-chain2", "chips": 1}]
    return bench


SEED = 2 ** 31 + 33


@pytest.fixture(scope="module")
def toy_cell():
    return run.Cell("smoke-trinity", _toy_bench())


@pytest.fixture(scope="module")
def both_sides(toy_cell, _benchmark_modules):
    """One run of the toy cell through the harness (``run.run_cell`` on the
    CPU: the reference's and the program's first 1 + chain steps of 5 layers,
    sliding-dense, sliding, sliding, full, sliding at width 64, window 16,
    sequence 64, float32, 4 of 16 experts held, from the same seeded weights,
    then a short window), with what the harness compared kept.  The
    reference's attention rows are cut so that its blocking is exercised."""
    import check
    from mxnet_tpu.telemetry import spans
    cell, kept = toy_cell, {}
    compare = check.compare

    def keeping(prog, ref, limits, say=print):
        kept.update(prog=prog, ref=ref)
        return compare(prog, ref, limits, say)

    rows, cell.refmod.ATTENTION_ROWS = cell.refmod.ATTENTION_ROWS, 16
    check.compare = keeping
    try:
        result = run.run_cell(cell, seed=SEED, seconds=0.3, trace=0,
                              on_chip=False)
    finally:
        check.compare = compare
        cell.refmod.ATTENTION_ROWS = rows
    init = spans.records("trainer.build.init_params")[-1].attrs
    built = [r.attrs for r in spans.records("model.build")]
    return kept["ref"], kept["prog"], moe.last_plan_summary(), result, init, built


def test_model_symbol_is_built_from_the_configuration(toy_cell):
    net, data, label = toy_cell.cfgmod.build(toy_cell.cfg, toy_cell.mix, 1)
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(**data, **label)[0]))
    del shapes["data"], shapes["softmax_label"]
    assert shapes == toy_cell.refmod.param_shapes(toy_cell.cfg)
    assert net.list_auxiliary_states() == ["layer%d_moe_load" % i
                                           for i in (1, 2, 3, 4)]
    nodes = json.loads(net.tojson())["nodes"]
    ops = [n["op"] for n in nodes]
    assert ops.count("_contrib_FlashAttention") == 5
    assert [n["attrs"]["window"] for n in nodes
            if n["op"] == "_contrib_FlashAttention"] == ["16", "16", "16", "0", "16"]
    assert ops.count("_contrib_RotaryEmbedding") == 8      # q and k, 4 layers
    assert ops.count("_contrib_TopKMoE") == 4
    assert ops.count("RMSNorm") == 5 * 6 + 1       # four a layer, q's, k's
    with pytest.raises(MXNetError, match="does not give 5 layers"):
        afmoe.get_symbol(dict(toy_cell.cfg, layer_types=["conv"] * 5), 64)
    # Module binds such a Symbol too (one layer of it, for the compile's sake)
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


def test_a_model_without_its_windows_does_not_follow_the_reference(
        toy_cell, both_sides):
    """The comparison sees the mechanism: the program built with full causal
    attention on every layer is outside what float noise explains."""
    import check
    import traffic
    cell, ref = toy_cell, both_sides[0]
    hb = traffic.host_batch(cell.cfg, cell.mix, 1, SEED)
    wide = dict(cell.cfg, sliding_window=cell.cfg["max_position_embeddings"])
    session = cell.runner.open(
        wide, cell.cfgmod, cell.mix, jax.devices()[:1], SEED,
        lambda key: cell.refmod.init_params(cell.cfg, key), run.seed_key(SEED), hb)
    values = {n: v for n, v, _ in check.numbers(session.first_steps(), ref)}
    session.close()
    assert values["grad_sample_err"] > 1e-2, values


def test_trainer_records_the_plans_and_the_build_span(both_sides):
    experts, built = both_sides[2], both_sides[5]
    assert experts["expert_layers"] == 4
    assert {(x["buffer_rows"], x["even_rows"]) for x in experts["layers"]} \
        == {(64 * 4, 64.0)}
    assert {"model": "afmoe"} in [{"model": b.get("model")} for b in built]


def test_every_leaf_of_the_model_is_drawn_on_the_device(both_sides):
    """``init_on_host_pct`` 0: each parameter's rule is traceable."""
    init = both_sides[4]
    assert init["host_bytes"] == 0 and init["device_bytes"] > 0


def test_toy_cell_runs_through_the_harness(both_sides):
    result = both_sides[3]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0 and result["device"]["count"] == 1


def test_toy_step_lowered_for_the_tpu_holds_the_windowed_kernels(
        toy_cell, monkeypatch):
    """The toy model's first four layers (sliding, sliding, sliding, full) at
    a sequence of 4096 and a window of 1024, the platform probe patched true,
    the step lowered for the TPU from here: the sliding layers' calls are the
    windowed kernels under ``mxtpu.block.swa``, the full layer's the streamed
    ones, and the plan counts three windowed layers."""
    from mxnet_tpu import context
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    cfg = dict(toy_cell.cfg, num_hidden_layers=4, sliding_window=1024,
               num_attention_heads=2, num_key_value_heads=1, head_dim=32)
    mix = dict(toy_cell.mix, seq=4096)
    t, data, label = _toy_trainer(cfg, mix)
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
    assert text.count('kernel_name = "mxtpu_flash_fwd_window"') == 3
    assert text.count('kernel_name = "mxtpu_flash_bwd_window"') == 3
    assert text.count('kernel_name = "mxtpu_flash_fwd_stream"') == 1
    assert text.count('kernel_name = "mxtpu_flash_bwd_stream"') == 1
    assert "mxtpu.block.swa)/mxtpu_flash_fwd_window/pallas_call" in text
    assert "mxtpu.block.swa)/mxtpu_flash_bwd_window/pallas_call" in text
    plan = pk.last_causal_plan()
    assert plan["window_layers"] == 3
    assert plan["scores_computed_pct"] == pk._scores_computed_pct(
        4096, 128, 2048, pk._causal_plan(128, 2048))
    assert {(k["block_q"], k["block_k"]) for k in plan["kernels"]
            if k["window"]} == {pk._WINDOW_BLOCKS} == {(512, 1024)}
    assert plan["window_scores_computed_pct"] == pk._scores_computed_pct(
        4096, 512, 1024, pk._causal_plan(512, 1024), 1024) \
        < plan["scores_computed_pct"]


def test_new_readers_read_the_plan_and_none_without_it(monkeypatch, toy_cell):
    def read(name):
        return run.load_module("layer_metrics", name).read({"cell": toy_cell})

    names = ("attn_window_layers", "flash_window_scores_computed_pct",
             "flash_scores_computed_pct")
    monkeypatch.setattr(pk, "last_causal_plan", lambda: {
        "kernels": [], "causal_ranges": 4, "scores_computed_pct": 53.125,
        "window_layers": 4, "window_scores_computed_pct": 34.375})
    assert [read(n) for n in names] == [4, 34.375, 53.125]
    # a step without a windowed kernel (LFM2's): a count of 0, no share
    monkeypatch.setattr(pk, "last_causal_plan", lambda: {
        "kernels": [], "causal_ranges": 4, "scores_computed_pct": 53.125,
        "window_layers": 0, "window_scores_computed_pct": None})
    assert [read(n) for n in names] == [0, None, 53.125]
    # the parent's plan (no such keys), no plan, and a program without one
    monkeypatch.setattr(pk, "last_causal_plan", lambda: {
        "kernels": [], "causal_ranges": 4, "scores_computed_pct": 53.125})
    assert [read(n) for n in names] == [None, None, 53.125]
    monkeypatch.setattr(pk, "last_causal_plan", lambda: None)
    assert [read(n) for n in names] == [None, None, None]
    monkeypatch.delattr(pk, "last_causal_plan")
    assert [read(n) for n in names[:2]] == [None, None]


@pytest.mark.parametrize("plan,want", [
    (None, None),
    # the parent's plan: no summary key, each kernel says its block_q
    ({"kernels": [{"block_q": 512}, {"block_q": 128}, {"block_q": 512}],
      "causal_ranges": 4, "scores_computed_pct": 53.125}, 128),
    ({"kernels": [{"block_q": 512}], "causal_ranges": 4}, 512),
    ({"kernels": [], "causal_ranges": 4}, None),
    # the summary key where there is one
    ({"kernels": [{"block_q": 128}], "q_block_rows": 512}, 512)],
    ids=["no_plan", "parent_plan", "parent_plan_512", "no_kernel", "summary"])
def test_q_block_rows_reader(monkeypatch, toy_cell, plan, want):
    reader = run.load_module("layer_metrics", "flash_q_block_rows")
    monkeypatch.setattr(pk, "last_causal_plan", lambda: plan)
    assert reader.read({"cell": toy_cell}) == want
    # a program without the function at all
    monkeypatch.delattr(pk, "last_causal_plan")
    assert reader.read({"cell": toy_cell}) is None


def test_cell_configuration_keeps_every_published_width():
    """``benchmark/configs/trinity-mini.json`` against the catalog's ``config``
    (``model-configs``' ``architectures.jsonl``, quoted here): only the four
    reduced keys differ, each with its published value beside it."""
    cfg = run.load_json(BENCH, "configs", "trinity-mini.json")
    published = dict(
        global_attn_every_n_layers=4, head_dim=128, hidden_act="silu",
        hidden_size=2048, intermediate_size=6144, load_balance_coeff=0.001,
        max_position_embeddings=131072, model_type="afmoe",
        moe_intermediate_size=1024, mup_enabled=True, n_group=1,
        num_attention_heads=32, num_dense_layers=2, num_expert_groups=1,
        num_experts=128, num_experts_per_tok=8, num_hidden_layers=32,
        num_key_value_heads=4, num_limited_groups=1, num_shared_experts=1,
        rms_norm_eps=1e-5, rope_scaling=None, rope_theta=10000,
        route_norm=True, route_scale=2.826, score_func="sigmoid",
        sliding_window=2048, tie_word_embeddings=False, topk_group=1,
        use_grouped_mm=True, vocab_size=200192)
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert {k: published[k] for k in changed} == cfg["published"]
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 8
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"], cfg["router_num_experts"],
            cfg["routed_scaling_factor"]) == (5, 1, 8, 25024, 128, 2.826)
    # the one initial value that is not the family's convention, and why
    assert cfg["qk_norm_gain_init"] == 2.0 and "qk_norm_gain" in cfg["assumed"]
    gains = {n[len("layer0_"):]: float(v[0]) for n, v in REF.init_params(
        dict(cfg, num_hidden_layers=1, vocab_size=64),
        jax.random.PRNGKey(0)).items()
        if n.startswith("layer0_") and n.endswith("_gamma")}
    assert gains == {"op_norm_gamma": 1.0, "q_norm_gamma": 2.0,
                     "k_norm_gamma": 2.0, "post_op_norm_gamma": 1.0,
                     "ffn_norm_gamma": 1.0, "post_ffn_norm_gamma": 1.0}
    cfgmod = run.load_module("configs", "trinity-mini")
    mix = run.load_json(BENCH, "traffic", "s8192-b1-chain2.json")
    shapes = REF.param_shapes(cfg)
    # ISSUE 33's count: 504.1M parameters, 27.26M an attention sub-layer
    assert sum(int(np.prod(s)) for s in shapes.values()) == 504147712
    attention = sum(int(np.prod(shapes["layer3_%s_weight" % n])) for n in "qkvgo")
    assert round(attention / 1e6, 2) == 27.26
    assert round(cfgmod.matmul_params_per_token(cfg) / 1e6, 1) == 263.1
    assert (cfgmod.score_pairs(8192, 2048), cfgmod.score_pairs(8192)) == \
        (14681088, 33558528)
    costs = cfgmod.kernel_costs(cfg, mix)
    assert set(costs) == {"mxtpu_flash_fwd_window", "mxtpu_flash_bwd_window",
                          "mxtpu_flash_fwd_stream", "mxtpu_flash_bwd_stream",
                          "ragged-dot"}
    assert [costs[k]["calls"] for k in sorted(costs)] == [1, 4, 1, 4, 36]
    # attention 4.53 TFLOP of the step's 17.5, the sliding layers by their band
    flash = sum(costs[k]["flops"] for k in costs if k.startswith("mxtpu_flash"))
    assert abs(flash / 1e12 - 4.53) < 0.01
    assert abs(cfgmod.step_flops(cfg, mix, 1) / 1e12 - 17.48) < 0.01
