"""The expert layer's sum of a sorted buffer's rows into token order as
``mxtpu_moe_token_sum`` (``mxnet_tpu/ops/moe_token_sum.py``), run under
Pallas's interpreter, against the ``jax.numpy`` scatter-add it stands in
for on a TPU (``parallel/moe.py`` ``_bounded_products``; PR 46).

The oracle is the parent's form: ``jnp.zeros((T, d), float32).at[token]
.add(rows * weight)`` and autodiff's transposes.  The kernel adds a token's
experts in ascending order in float32, as the scatter-add over the sorted
rows does, so with products that are exact (bf16 rows, gates of eight
significant bits) the two agree to the last bit.  With arbitrary float32
gates they agree to one float32 unit of the largest term: XLA:CPU contracts
the interpreter's multiply and add into one fused operation, which rounds
once where the scatter-add's operands were rounded first.
"""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import moe_token_sum
from mxnet_tpu.parallel import moe

HERE = os.path.dirname(os.path.abspath(__file__))

#: the six expert cells' shapes cut to CPU size: the cell's experts a token,
#: held / E and so ``T k / rows summed``; 256 tokens, 128 wide
#: cell -> (k, held, E, rows summed, T k / rows)
CELLS = {
    "lfm2moe": (4, 8, 32, 512, 2),
    "sdar": (8, 16, 128, 512, 4),
    "trinitymini": (8, 8, 128, 256, 8),
    "nemotron3nano": (6, 8, 128, 192, 8),
    "kimilinear": (8, 8, 256, 256, 8),
    "glm47flash": (4, 8, 64, 256, 4),
}
T, D, FF = 256, 128, 32


@pytest.fixture
def interpreted(monkeypatch):
    """The rule's platform gate says ``"interpret"``; blocks of 64 tokens so
    that a toy has several; nothing traced under another gate is reused."""
    moe._two_sizes.cache_clear()
    monkeypatch.setattr(moe, "_token_sum_lowering", lambda dtype: "interpret")
    monkeypatch.setattr(moe, "TOKEN_SUM_BLOCKS", ((64, 4096),))
    yield
    moe._two_sizes.cache_clear()


def _routing(rng, t, k, held, num_experts):
    """``(local, order, inv, counts)`` as ``topk_moe`` makes them, the first
    ``held`` experts here."""
    idx = np.argsort(-rng.rand(t, num_experts), axis=1)[:, :k]
    local = np.where(idx < held, idx, held).reshape(-1)
    order = np.argsort(local, kind="stable").astype(np.int32)
    inv = np.argsort(order).astype(np.int32)
    counts = np.bincount(local, minlength=held + 1)[:held].astype(np.int32)
    return local.reshape(t, k), order, inv, counts


def _eight_bits(a):
    """Float32 values of eight significant bits: their products with bf16
    rows are exact in float32, fused or not."""
    return np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)


def _scatter_add(rows, token, weight, t):
    """The parent's line, ``parallel/moe.py:239`` at 2cad754."""
    rows = rows.astype(jnp.float32) * weight[:, None]
    return jnp.zeros((t, rows.shape[1]), jnp.float32).at[token].add(rows)


def _kernel_operands(local, inv, counts, gates, n_rows):
    """``pos`` and ``weight`` as ``_bounded_products`` hands them over."""
    t, k = local.shape
    held = len(counts)
    ends = np.minimum(np.cumsum(counts), n_rows)
    starts = np.concatenate([[0], ends[:-1]])
    slot = inv.reshape(t, k)
    hit = (slot[:, :, None] >= starts) & (slot[:, :, None] < ends)
    assert hit.shape == (t, k, held)
    pos = np.where(hit, slot[:, :, None], 0).sum(1) - ~hit.any(1)
    weight = np.where(hit, gates[:, :, None], 0.0).sum(1)
    return jnp.asarray(pos, jnp.int32), jnp.asarray(weight, jnp.float32)


@pytest.mark.parametrize("weights", ["gates", "unit"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_kernel_equals_the_scatter_add_bit_for_bit(cell, weights):
    """At each cell's shape, forward combine (``gates``) and the dispatch
    gather's transpose (``unit``): the rows past the held assignments are
    NaN, as a grouped product may leave them, and add nothing."""
    k, held, num_experts, n_rows, ratio = CELLS[cell]
    assert T * k == ratio * n_rows
    small = moe.small_buffer_rows(T, k, held, num_experts)
    assert n_rows == (small or moe.buffer_rows(T, k, held, num_experts))
    rng = np.random.RandomState(len(cell))
    local, order, inv, counts = _routing(rng, T, k, held, num_experts)
    filled = min(int(counts.sum()), n_rows)
    assert 0 < filled < n_rows
    gates = _eight_bits(rng.rand(T, k)) if weights == "gates" \
        else np.ones((T, k), np.float32)
    rows = jnp.asarray(rng.randn(n_rows, D), jnp.bfloat16)
    head = order[:n_rows]
    weight = np.where(np.arange(n_rows) < filled, gates.reshape(-1)[head], 0.0)
    want = _scatter_add(jnp.where(jnp.arange(n_rows)[:, None] < filled, rows, 0),
                        head // k, jnp.asarray(weight, jnp.float32), T)
    pos, wmat = _kernel_operands(local, inv, counts, gates, n_rows)
    got = moe_token_sum.token_sum(
        rows.at[filled:].set(jnp.nan), pos,
        wmat if weights == "gates" else None, block=64, interpret=True)
    assert got.dtype == jnp.bfloat16 and float(jnp.abs(want).max()) > 1
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want.astype(jnp.bfloat16), np.float32))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_kernel_with_any_gates_is_within_one_unit_of_the_largest_term(cell):
    """Float32 rows and float32 gates: the interpreter's fused multiply-add
    rounds once, so each of a token's ``k`` terms may differ by half a unit
    of itself."""
    k, held, num_experts, n_rows, _ratio = CELLS[cell]
    rng = np.random.RandomState(7 + len(cell))
    local, order, inv, counts = _routing(rng, T, k, held, num_experts)
    gates = rng.rand(T, k).astype(np.float32)
    rows = jnp.asarray(rng.randn(n_rows, D), jnp.float32)
    filled = min(int(counts.sum()), n_rows)
    head = order[:n_rows]
    weight = np.where(np.arange(n_rows) < filled, gates.reshape(-1)[head], 0.0)
    want = _scatter_add(rows, head // k, jnp.asarray(weight, jnp.float32), T)
    pos, wmat = _kernel_operands(local, inv, counts, gates, n_rows)
    got = moe_token_sum.token_sum(rows, pos, wmat, block=128, interpret=True)
    largest = float(jnp.abs(rows).max()) * float(gates.max())
    assert float(jnp.abs(got - want).max()) <= np.spacing(np.float32(largest))


def test_an_expert_with_no_token_and_a_token_with_all_its_experts_held():
    """Expert 2 of the 4 held draws nothing (an empty range at every block);
    the first 40 tokens choose both their experts among the held."""
    t, k, held, n_rows = 128, 2, 4, 192
    rng = np.random.RandomState(5)
    first = np.array([0, 1, 3])[rng.randint(0, 3, t)]
    second = np.where(np.arange(t) < 40, (first + 1) % 2, held)  # 0/1, or away
    second = np.where(second == first, 3 - first, second)
    local = np.stack([first, second], axis=1)
    local[40:, 1] = held
    order = np.argsort(local.reshape(-1), kind="stable").astype(np.int32)
    inv = np.argsort(order).astype(np.int32)
    counts = np.bincount(local.reshape(-1), minlength=held + 1)[:held]
    assert counts[2] == 0 and counts.sum() == t + 40 <= n_rows
    assert all(set(local[i]) <= {0, 1, 3} for i in range(40))
    gates = _eight_bits(rng.rand(t, k))
    rows = jnp.asarray(rng.randn(n_rows, D), jnp.bfloat16)
    head = order[:n_rows]
    weight = np.where(np.arange(n_rows) < counts.sum(), gates.reshape(-1)[head], 0)
    want = _scatter_add(rows, head // k, jnp.asarray(weight, jnp.float32), t)
    pos, wmat = _kernel_operands(local, inv, counts.astype(np.int32), gates, n_rows)
    assert int((pos[:, 2] >= 0).sum()) == 0 and int((pos[:40] >= 0).sum()) == 80
    got = moe_token_sum.token_sum(rows, pos, wmat, block=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want.astype(jnp.bfloat16), np.float32))


def test_token_sum_refuses_tokens_that_are_no_whole_blocks():
    with pytest.raises(ValueError, match="blocks of 64"):
        moe_token_sum.token_sum(jnp.zeros((64, 128)), jnp.zeros((96, 4), jnp.int32),
                                block=64, interpret=True)


# ------------------------------------------------ the rule, from the shapes
@pytest.mark.parametrize("t,held,n_rows,d,dtype,want", [
    (8192, 8, 16384, 2048, jnp.bfloat16, ("kernel", 512)),     # LFM2
    (8192, 16, 16384, 2048, jnp.bfloat16, ("kernel", 512)),    # SDAR
    (8192, 8, 8192, 2048, jnp.bfloat16, ("kernel", 512)),      # Trinity-Mini
    (8192, 8, 6144, 2688, jnp.bfloat16, ("kernel", 256)),      # Nemotron
    (8192, 8, 8192, 2304, jnp.bfloat16, ("kernel", 512)),      # Kimi Linear
    (4096, 8, 4096, 2048, jnp.bfloat16, ("kernel", 512)),      # GLM-4.7-Flash
    (8192, 8, 8192, 4096, jnp.bfloat16, ("kernel", 256)),      # a wider model
    (8320, 8, 8192, 4096, jnp.bfloat16, ("kernel", 128)),      # 65 x 128 tokens
    (8192, 8, 8192, 8192, jnp.bfloat16, ("scatter_add", None)),  # too wide
    (8192, 8, 16384, 2048, jnp.float32, ("scatter_add", 512)),  # not bf16
    (8192, 8, 16384, 2000, jnp.bfloat16, ("scatter_add", None)),  # no lane tile
    (8192, 8, 88, 2048, jnp.bfloat16, ("scatter_add", None)),   # no whole chunk
    (8200, 8, 16384, 2048, jnp.bfloat16, ("scatter_add", None)),  # no whole block
])
def test_the_form_follows_shapes_and_platform(monkeypatch, t, held, n_rows, d,
                                              dtype, want):
    from mxnet_tpu import context
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    assert moe._token_sum_form(t, n_rows, d, dtype) == want
    monkeypatch.setattr(context, "on_tpu", lambda: False)
    assert moe._token_sum_form(t, n_rows, d, dtype)[0] == "scatter_add"


def test_under_a_mesh_of_several_devices_the_scatter_add_stays(monkeypatch):
    from mxnet_tpu import context
    from mxnet_tpu.parallel import mesh
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    monkeypatch.setattr(mesh, "active_kernel_mesh", lambda: object())
    assert moe._token_sum_form(8192, 16384, 2048, jnp.bfloat16) \
        == ("scatter_add", 512)


# ------------------------------------------- topk_moe through the kernel
def _params(k, held, num_experts, gated=True, t=T, seed=3):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    return {"x": f(t, D), "router_w": f(num_experts, D),
            "w1": f(held, D, FF) if gated else f(held, FF, D),
            "w3": f(held, D, FF) if gated else None, "w2": f(held, FF, D)}


def _layer_and_grads(p, k, bias, trained):
    """``(y, load, {argument: gradient}, the layers' plan)``; the router's
    gradient is the gates' cotangent carried back."""
    names = [n for n in ("x", "w1", "w3", "w2", "router_w")
             if p[n] is not None and (n != "router_w" or trained)]
    t = p["x"].shape[0]
    mix = jnp.cos(jnp.arange(t * D, dtype=jnp.float32)).reshape(t, D)

    def loss(args):
        q = dict(p, **args)
        y, load = moe.topk_moe(q["x"], q["router_w"], bias, q["w1"], q["w3"],
                               q["w2"], k, router_trained=trained)
        return jnp.sum(y * mix), (y, load)

    with moe.plan_recording():
        (_, (y, load)), grads = jax.value_and_grad(loss, has_aux=True)(
            {n: p[n] for n in names})
    return y, load, grads, moe.last_plan_summary()


def _parents_form(p, k, bias, trained):
    moe._two_sizes.cache_clear()
    out = _layer_and_grads(p, k, bias, trained)
    assert out[3]["token_sum_layers"] == 0
    return out


def _assert_same(got, want):
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6, atol=1e-6)
    assert sorted(got[2]) == sorted(want[2])
    for n in want[2]:
        assert float(jnp.abs(want[2][n]).max()) > 0, n
        np.testing.assert_allclose(got[2][n], want[2][n], rtol=2e-6, atol=1e-6,
                                   err_msg=n)


#: held / E of a layer with two sizes (a quarter, an eighth) and of one with
#: one bounded size (8 of 256: the bound itself is small)
LAYERS = {"two_sizes_quarter": (4, 8, 32), "two_sizes_eighth": (4, 8, 64),
          "one_size": (8, 8, 256)}


@pytest.mark.parametrize("trained", [True, False], ids=["trained", "frozen"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_topk_moe_through_the_kernel_equals_the_parents_form(
        interpreted, layer, gated, trained):
    """Even routing: the small branch of a layer with two sizes, the one
    bounded size of the other; result, load and the gradients for ``x``,
    ``w1``, ``w3``, ``w2`` and (``trained``) the gates."""
    k, held, num_experts = LAYERS[layer]
    p = _params(k, held, num_experts, gated)
    got = _layer_and_grads(p, k, None, trained)
    small = moe.small_buffer_rows(T, k, held, num_experts)
    assert (small is None) == (layer == "one_size")
    assert 0 < float(got[1][:-1].sum()) <= (small or moe.buffer_rows(
        T, k, held, num_experts))
    assert [la["token_sum"] for la in got[3]["layers"]] == ["interpret"]
    with pytest.MonkeyPatch.context() as m:
        m.setattr(moe, "_token_sum_lowering", lambda dtype: "scatter_add")
        want = _parents_form(p, k, None, trained)
    assert [la["token_sum"] for la in want[3]["layers"]] == ["scatter_add"]
    _assert_same(got, want)


@pytest.mark.parametrize("trained", [True, False], ids=["trained", "frozen"])
@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_topk_moe_at_the_bound_through_the_kernel(interpreted, layer, trained):
    """A bias that sends every assignment to the held experts: the branch at
    ``buffer_rows`` runs.  A quarter held: its buffer holds every assignment
    and is the permutation's gathers, as it was.  Fewer: the bounded products
    over ``buffer_rows`` rows, the kernel again; the held assignments past
    the buffer add nothing, to the result or to a gradient, and ``load``
    still counts them."""
    k, held, num_experts = LAYERS[layer]
    p = _params(k, held, num_experts)
    bias = jnp.where(jnp.arange(num_experts) < held, 10.0, 0.0)
    got = _layer_and_grads(p, k, bias, trained)
    bound = moe.buffer_rows(T, k, held, num_experts)
    assert float(got[1][:-1].sum()) == T * k and float(got[1][-1]) == 0
    assert (bound < T * k) == (layer != "two_sizes_quarter")
    with pytest.MonkeyPatch.context() as m:
        m.setattr(moe, "_token_sum_lowering", lambda dtype: "scatter_add")
        want = _parents_form(p, k, bias, trained)
    _assert_same(got, want)
    if bound < T * k:
        # the buffer is the head of the sorted order: the last held expert's
        # assignments all sort past it and its weights get no gradient
        assert float(jnp.abs(got[2]["w2"][-1]).max()) == 0 \
            < float(jnp.abs(got[2]["w2"][0]).max())


def test_tokens_that_are_no_whole_block_fall_to_jax_numpy(interpreted):
    """96 tokens in blocks of 64: the rule says ``scatter_add`` and the layer
    is the parent's, whatever the platform gate says."""
    k, held, num_experts = LAYERS["two_sizes_quarter"]
    p = _params(k, held, num_experts, t=96)
    got = _layer_and_grads(p, k, None, True)
    assert [la["token_sum"] for la in got[3]["layers"]] == ["scatter_add"]
    assert got[3]["token_sum_layers"] == 0
    assert float(jnp.abs(got[0]).max()) > 0


# -------------------------------------------------- the counter's readers
def _reader():
    path = os.path.join(os.path.dirname(HERE), "benchmark", "layer_metrics",
                        "moe_token_sum_layers.py")
    spec = importlib.util.spec_from_file_location("moe_token_sum_layers", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("forms,count", [
    (["kernel"] * 4, 4), (["kernel", "scatter_add", "gathers"], 1),
    (["scatter_add"] * 5, 0)])
def test_the_summary_counts_the_layers_on_the_kernel(monkeypatch, forms, count):
    from mxnet_tpu.telemetry import plan
    monkeypatch.setattr(plan, "_LAST", {})
    with moe.plan_recording():
        for form in forms:
            moe.note_layer(token_sum=form)
    assert moe.last_plan_summary()["token_sum_layers"] == count
    assert _reader().read(None) == count


def test_the_reader_reads_none_of_a_program_without_the_count(monkeypatch):
    from mxnet_tpu.telemetry import plan
    reader = _reader()
    monkeypatch.setattr(plan, "_LAST", {})
    assert reader.read(None) is None                  # no expert layer traced
    # an older program's summary: the name the benchmark's own test sets
    monkeypatch.setattr(moe, "_LAST_SUMMARY", {"expert_layers": 4, "layers": []})
    assert reader.read(None) is None


def test_a_full_buffers_layer_says_gathers():
    """Half of the experts held: one size, every assignment has a row, the
    permutation's gathers both ways (``_sorted_dispatch``) and no kernel."""
    p = _params(4, 16, 32)
    got = _layer_and_grads(p, 4, None, False)
    assert [la["token_sum"] for la in got[3]["layers"]] == ["gathers"]
    assert got[3]["token_sum_layers"] == 0
