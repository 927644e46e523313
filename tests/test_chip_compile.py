"""The main path's Pallas kernels compile for a TPU v5e at real widths.

The chip's compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2): what it
refuses costs no chip time.  Nothing runs, so these say nothing about
results or speed — ``chip_smoke.py`` checks those on the chip.

This is the only file that describes a topology, and it does so inside
a fixture: only one process may load the TPU's library, so the call
must not happen while any module is imported, and a second such file
could land on another xdist worker where the fixture would skip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu import context
from mxnet_tpu.ops import pallas_kernels as pk
from test_fusion import one_by_one_block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # mxlint: allow-broad-except(whatever keeps the chip's compiler from describing a v5e here — no libtpu, its lock held, an unknown topology name — means these tests cannot run, not that they failed)
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(fn, one_chip, *shapes_dtypes, names):
    """Compile ``fn`` for the described chip.  ``names``: what the
    compiled program's custom calls must be called — the instruction's
    name is what a device trace's "XLA Ops" line shows, and XLA makes
    it from the ``name=`` of the ``pallas_call``."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes_dtypes]
    compiled = jax.jit(fn).lower(*args).compile()
    calls = [line.split("=")[0] for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert bool(calls) == bool(names), calls
    for name in names:
        assert any(name in c for c in calls), (name, calls)
    return compiled


# (batch, seq, heads, head_dim): the transformer bench's shape, its
# longer-sequence cells (T=4096 takes the K/V-streaming kernels), d=128
FLASH_SHAPES = [(8, 1024, 16, 64), (4, 2048, 16, 64), (2, 4096, 16, 64),
                (2, 4096, 16, 128)]


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_forward_compiles_for_v5e(one_chip, shape):
    _compile_for_chip(lambda q, k, v: pk.flash_attention(q, k, v, True),
                      one_chip, *[(shape, jnp.bfloat16)] * 3,
                      names=["mxtpu_flash_fwd_"])


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_forward_backward_compiles_for_v5e(one_chip, shape):
    def loss(q, k, v):
        return pk.flash_attention(q, k, v, True).astype(jnp.float32).sum()

    _compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                      *[(shape, jnp.bfloat16)] * 3,
                      names=["mxtpu_flash_fwd_", "mxtpu_flash_bwd_"])


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("d", [64, 128])
def test_factor_one_streamed_flash_compiles_for_v5e(one_chip, d, causal):
    """One query head a key/value head at 8192 positions: without a mask
    the call keeps its 128-row Q block and asks for no VMEM, as it always
    did, and compiles under the scoped default; the causal rule's 512 rows
    hold 21 MiB, ask for them and compile."""
    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2))
    shapes = [((1, 8192, 8, d), jnp.bfloat16)] * 3
    _compile_for_chip(fn, one_chip, *shapes,
                      names=["mxtpu_flash_fwd_stream", "mxtpu_flash_bwd_stream"])
    lowered = jax.jit(fn).lower(*[
        jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes])
    # a request is the custom call's scoped memory configuration
    assert ("scoped_memory_configs" in lowered.as_text()) == causal


# (batch, seq, query heads, key/value heads, head_dim): LFM2-8B-A1B's
# attention at the cell's 8192 positions (streaming kernels; the backward
# asks for the VMEM of its 4-head dQ accumulator) and at one K/V panel
GROUPED_SHAPES = [(1, 8192, 32, 8, 64), (4, 2048, 32, 8, 64)]


@pytest.mark.parametrize("shape", GROUPED_SHAPES, ids=str)
def test_grouped_query_flash_compiles_for_v5e(one_chip, shape):
    b, t, hq, hk, d = shape

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, True).astype(jnp.float32).sum()

    compiled = _compile_for_chip(
        jax.grad(loss, argnums=(0, 1, 2)), one_chip,
        ((b, t, hq, d), jnp.bfloat16), ((b, t, hk, d), jnp.bfloat16),
        ((b, t, hk, d), jnp.bfloat16),
        names=["mxtpu_flash_fwd_", "mxtpu_flash_bwd_"])
    # K and V go to the kernels as they are: no repeated copy is made
    assert "bf16[%d,%d,%d,%d]" % (b, t, hq, d) not in [
        line.split("=")[1].split()[0] for line in compiled.as_text().splitlines()
        if " broadcast(" in line and "=" in line]


# (batch, seq, query heads, key/value heads, head_dim, window): Trinity-Mini's
# attention at the cell's 8192 positions: 8 query heads of 128 a key/value
# head (the backward asks for the 32 MiB of its dQ accumulator), a sliding
# layer (the windowed kernels), the full layer (window 0: the streamed
# ones), and a sliding layer at one K/V panel (the panel kernels and a mask)
WINDOW_SHAPES = [((1, 8192, 32, 4, 128, 2048), "_window"),
                 ((1, 8192, 32, 4, 128, 0), "_stream"),
                 ((1, 2048, 32, 4, 128, 512), "_panel")]


@pytest.mark.parametrize("shape,route", WINDOW_SHAPES, ids=str)
def test_windowed_grouped_flash_compiles_for_v5e(one_chip, shape, route):
    b, t, hq, hk, d, window = shape

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, True, False, window).astype(
            jnp.float32).sum()

    _compile_for_chip(
        jax.grad(loss, argnums=(0, 1, 2)), one_chip,
        ((b, t, hq, d), jnp.bfloat16), ((b, t, hk, d), jnp.bfloat16),
        ((b, t, hk, d), jnp.bfloat16),
        names=["mxtpu_flash_fwd" + route, "mxtpu_flash_bwd" + route])


def test_sixteen_query_heads_a_key_value_head_compile_for_v5e(one_chip):
    """Nemotron-H's attention at the cell's 8192 positions: 32 query heads
    of 128 over 2 key/value heads.  The forward is one streamed call; the
    backward's dQ accumulator for the whole group would be 64 MiB (74 MiB
    reckoned, 111 asked for, past the 100 a kernel may ask), so it runs as
    two calls of the kernel that 8 heads a key/value head compile, each
    asking for the 73.5 MiB Trinity-Mini's full layer asks for."""
    b, t, hq, hk, d = 1, 8192, 32, 2, 128
    assert pk._group_parts(t, d, hq // hk) == 2
    assert pk._flash_blocks(t, d, d, hq // hk, True) == (512, 2048)

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, True).astype(jnp.float32).sum()

    compiled = _compile_for_chip(
        jax.grad(loss, argnums=(0, 1, 2)), one_chip,
        ((b, t, hq, d), jnp.bfloat16), ((b, t, hk, d), jnp.bfloat16),
        ((b, t, hk, d), jnp.bfloat16),
        names=["mxtpu_flash_fwd_stream", "mxtpu_flash_bwd_stream"])
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert sum("mxtpu_flash_fwd_stream" in c.split("=")[0] for c in calls) == 1
    assert sum("mxtpu_flash_bwd_stream" in c.split("=")[0] for c in calls) == 2


# (batch, seq, heads, query/key width, value width): Kimi Linear's latent
# attention at the cell's 8192 positions (streaming kernels; a 192-wide
# head takes two lane tiles a row, so the backward asks for the VMEM of its
# dQ accumulator) and at one K/V panel; GLM-4.7-Flash's at its cell's 4096
# positions (20 heads, two lane tiles a row on BOTH sides: the backward at
# 256 x 2048 blocks holds 25 MiB by ``_vmem_need`` with a 4096-row
# accumulator and asks for 37.5; the compiler's least is 23, PR 44)
LATENT_SHAPES = [(1, 8192, 32, 192, 128), (1, 2048, 32, 192, 128),
                 (1, 4096, 20, 256, 256)]


@pytest.mark.parametrize("shape", LATENT_SHAPES, ids=str)
def test_latent_width_flash_compiles_for_v5e(one_chip, shape):
    b, t, h, dk, dv = shape

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, True).astype(jnp.float32).sum()

    _compile_for_chip(
        jax.grad(loss, argnums=(0, 1, 2)), one_chip,
        ((b, t, h, dk), jnp.bfloat16), ((b, t, h, dk), jnp.bfloat16),
        ((b, t, h, dv), jnp.bfloat16),
        names=["mxtpu_flash_fwd_", "mxtpu_flash_bwd_"])


def test_latent_attention_block_with_its_options_compiles_for_v5e(
        one_chip, monkeypatch):
    """One GLM-4.7-Flash latent-attention block as the shared builder makes
    it (query latent, the rotary part on slices, 256 / 256 wide heads at 4096
    positions), forward and backward through the graph's own lowering: the
    streamed pair at the rule's blocks (512 x 2048 forward, 256 x 2048
    backward), the backward asking for its 37.5 MiB."""
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.models.decoder_blocks import latent_attention
    from mxnet_tpu.symbol import eval_graph
    cfg = dict(hidden_size=2048, num_attention_heads=20, qk_nope_head_dim=192,
               qk_rope_head_dim=64, v_head_dim=256, kv_lora_rank=512,
               q_lora_rank=768, rms_norm_eps=1e-5, rope_theta=1000000)
    net = latent_attention(sym.Variable("x"), cfg, "l_")
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(x=(1, 4096, 2048))[0]))
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    topo = net._topo()
    nodes = {n.name: n for n in topo if n.is_variable}

    def loss(values):
        out, _aux = eval_graph(topo, net._entries,
                               {id(nodes[k]): v for k, v in values.items()},
                               is_train=True)
        return out[0].astype(jnp.float32).sum()

    args = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for k, s in shapes.items()}
    lowered = jax.jit(jax.grad(loss)).lower(args)
    assert pk._flash_blocks(4096, 256, 256, 1, True) == (512, 2048)
    assert pk._flash_blocks(4096, 256, 256, 1, True, backward=True) == \
        (256, 2048)
    assert "scoped_memory_configs" in lowered.as_text()
    calls = [line.split("=")[0] for line in
             lowered.compile().as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 2 and any("mxtpu_flash_fwd_stream" in c for c in calls) \
        and any("mxtpu_flash_bwd_stream" in c for c in calls), calls


@pytest.mark.parametrize("names", [[], ["mxtpu_kda_fwd", "mxtpu_kda_bwd"]],
                         ids=["jax_numpy_form", "kernels"])
def test_gated_delta_rule_scan_compiles_for_v5e(one_chip, names, monkeypatch):
    """The chunked scan at Kimi Linear's heads and widths, forward and
    backward, two groups of chunks: as plain XLA ops (no custom call: what
    any backend but the TPU runs) and, the platform probe patched true, as
    the two kernels, the forward handing the backward each chunk's
    triangular inverse, a group's eight side by side in whole lane tiles;
    either plan fits a small share of the chip."""
    from mxnet_tpu.ops import delta_rule
    monkeypatch.setattr(context, "on_tpu", lambda: bool(names))
    b, t, h, d = 1, 1024, 32, 128

    def loss(q, k, v, g, beta):
        return delta_rule.gated_delta_rule(
            q, k, v, g, beta, qk_l2norm=True,
            scale=d ** -0.5).astype(jnp.float32).sum()

    wide = ((b, t, h, d), jnp.bfloat16)
    compiled = _compile_for_chip(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip, wide, wide, wide,
        ((b, t, h, d), jnp.float32), ((b, t, h), jnp.bfloat16), names=names)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
    if names:
        lines = compiled.as_text().splitlines()
        forward, backward = (
            next(l for l in lines if "tpu_custom_call" in l
                 and l.split("=")[0].strip().startswith("%" + name))
            for name in names)
        kept = "f32[%d,%d,64,512]{3,2,1,0:T(8,128)}" % (b * h, t // 512)
        assert forward.split(" custom-call(")[0].count(kept) == 1
        # the forward's third result is one of the backward's operands
        part = next(l.split("=")[0].strip() for l in lines
                    if " = " + kept + " get-tuple-element(" in l
                    and "index=2" in l)
        operands = backward.split(" custom-call(")[1].split(")")[0]
        assert part in [x.split("*/")[-1] for x in operands.split(", ")]


def test_grouped_matmul_of_the_expert_layer_compiles_for_v5e(one_chip):
    """``jax.lax.ragged_dot`` at the cell's size, forward and both backward
    products: three Mosaic grouped-matmul custom calls, no dense fall-back."""
    def loss(x, w, sizes):
        return jax.lax.ragged_dot(x, w, sizes).astype(jnp.float32).sum()

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((32768, 2048), jnp.bfloat16), ((8, 2048, 1792), jnp.bfloat16),
        ((8,), jnp.int32))]
    text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(*args).compile().as_text()
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and "ragged-dot-metadata" not in l.split("=")[0]]
    assert len(calls) == 3 and all("ragged-dot" in c.split("=")[0] for c in calls), \
        [c.split("=")[0] for c in calls]


def test_two_size_expert_layer_compiles_for_v5e(one_chip):
    """``topk_moe`` at LFM2's size (8192 tokens, 4 of 32 experts a token, 8
    held): forward and backward are one ``conditional`` each, and each branch
    holds its own Mosaic grouped products, over 16384 and over 32768 rows (the
    sum of ``y`` needs no value of the third forward product: 8 a branch)."""
    from mxnet_tpu.parallel import moe
    t, k, e, held, d, ff = 8192, 4, 32, 8, 2048, 1792

    def loss(x, router_w, bias, w1, w3, w2):
        y, _load = moe.topk_moe(x, router_w, bias, w1, w3, w2, k,
                                router_trained=False)
        return y.astype(jnp.float32).sum()

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((t, d), jnp.bfloat16), ((e, d), jnp.float32), ((e,), jnp.float32),
        ((held, d, ff), jnp.bfloat16), ((held, d, ff), jnp.bfloat16),
        ((held, ff, d), jnp.bfloat16))]
    text = jax.jit(jax.grad(loss, (0, 3, 4, 5))).lower(*args).compile().as_text()
    lines = text.splitlines()
    assert sum(" conditional(" in l for l in lines) == 2
    products = [l.split("=")[1].split("{")[0].strip()
                for l in moe._GROUPED_PRODUCT.findall(text)]
    rows = sorted(int(p.split("[")[1].split(",")[0]) for p in products)
    small, bound = moe.small_buffer_rows(t, k, held, e), moe.buffer_rows(t, k, held, e)
    assert (small, bound) == (16384, 32768)
    # a branch's five products with rows as their first axis; its three for
    # the weights' gradients are (held, ., .)
    assert rows == [held] * 6 + [small] * 5 + [bound] * 5, rows


#: the six expert cells' sums into token order: (tokens, held experts, width,
#: rows of the buffer a fitting step runs over)
TOKEN_SUM_SHAPES = {
    "lfm2moe": (8192, 8, 2048, 16384), "sdar": (8192, 16, 2048, 16384),
    "trinitymini": (8192, 8, 2048, 8192), "nemotron3nano": (8192, 8, 2688, 6144),
    "kimilinear": (8192, 8, 2304, 8192), "glm47flash": (4096, 8, 2048, 4096),
    # the widest rows of the 256- and 128-token blocks (Kimi Linear's are the
    # 512-token block's)
    "wide-256": (8192, 8, 4096, 8192), "wide-128": (8320, 8, 4096, 8192)}


@pytest.mark.parametrize("cell", sorted(TOKEN_SUM_SHAPES))
def test_moe_token_sum_compiles_for_v5e(one_chip, monkeypatch, cell):
    """``mxtpu_moe_token_sum`` at each expert cell's shape, and at each block's
    widest rows, with the block the rule gives it, with the gates' weights (the forward combine) and without
    (the dispatch gather's transpose): it fits the default of VMEM and asks
    for none."""
    from mxnet_tpu.ops import moe_token_sum
    from mxnet_tpu.parallel import moe
    t, held, d, n_rows = TOKEN_SUM_SHAPES[cell]
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    form, block = moe._token_sum_form(t, n_rows, d, jnp.bfloat16)
    assert form == "kernel"
    shapes = [((n_rows, d), jnp.bfloat16), ((t, held), jnp.int32),
              ((t, held), jnp.float32)]
    for operands in (shapes, shapes[:2]):
        compiled = _compile_for_chip(
            lambda *a: moe_token_sum.token_sum(*a, block=block), one_chip,
            *operands, names=[moe_token_sum.MOE_TOKEN_SUM])
        assert "vmem_limit" not in compiled.as_text()


def test_expert_layer_on_the_kernel_holds_no_scatter_for_v5e(one_chip,
                                                             monkeypatch):
    """``topk_moe`` at LFM2's size as the chip traces it: the small branch's
    two sums into token order are two ``mxtpu_moe_token_sum`` calls (the
    ``checkpoint`` does not run the forward's again in the backward), the
    branch at the bound is the permutation's gathers, and no ``scatter`` is
    left under the layer's scope."""
    from mxnet_tpu.ops import moe_token_sum
    from mxnet_tpu.parallel import moe
    t, k, e, held, d, ff = 8192, 4, 32, 8, 2048, 1792
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    moe._two_sizes.cache_clear()         # nothing traced off the chip's path

    def loss(x, router_w, bias, w1, w3, w2):
        y, _load = moe.topk_moe(x, router_w, bias, w1, w3, w2, k,
                                router_trained=False)
        return y.astype(jnp.float32).sum()

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((t, d), jnp.bfloat16), ((e, d), jnp.float32), ((e,), jnp.float32),
        ((held, d, ff), jnp.bfloat16), ((held, d, ff), jnp.bfloat16),
        ((held, ff, d), jnp.bfloat16))]
    try:
        with moe.plan_recording():
            text = jax.jit(jax.value_and_grad(loss, (0, 3, 4, 5))).lower(
                *args).compile().as_text()
    finally:
        moe._two_sizes.cache_clear()
    plan = moe.last_plan_summary()
    assert plan["token_sum_layers"] == 1
    assert plan["layers"][0]["token_sum"] == "kernel"
    lines = text.splitlines()
    sums = [l for l in lines if "tpu_custom_call" in l
            and moe_token_sum.MOE_TOKEN_SUM in l.split("=")[0]]
    assert len(sums) == 2, [l.split("=")[0] for l in sums]
    assert not [l.split("=")[0] for l in lines
                if " scatter(" in l and moe.SCOPE_MOE in l]
    assert sum(" conditional(" in l for l in lines) == 2


# stage 1 and stage 2 of ResNet-50 at batch 128 (the flatten round the
# removed kernel moved the whole activation there), and the shape of
# PR 25's A/B, whose flatten is a bitcast (the kernel lost there too)
@pytest.mark.parametrize("x_shape,nout", [
    ((128, 56, 56, 256), 64), ((128, 28, 28, 512), 128),
    ((128, 32, 32, 128), 128)], ids=str)
def test_bottleneck_block_holds_no_kernel_for_v5e(one_chip, monkeypatch,
                                                  x_shape, nout):
    """The block's compiled forward + backward holds no
    ``mxtpu_matmul_stats`` and no ``reshape`` of the activation."""
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    fn, args = one_by_one_block(x_shape, nout, jnp.bfloat16)
    compiled = _compile_for_chip(fn, one_chip, *args, names=[])
    rows = "[%d," % (x_shape[0] * x_shape[1] * x_shape[2])
    moved = [line for line in compiled.as_text().splitlines()
             if " reshape(" in line and rows in line.split(" reshape(")[0]]
    assert not moved, moved[:2]


def test_block_diffusion_flash_compiles_for_v5e(one_chip):
    """SDAR's attention at the cell's size: a clean and a noised copy of a
    4096-token document (8192 rows) in blocks of 4, 32 query heads over 4
    key/value heads of 128.  Both new kernels compile at the rule's 512 x
    2048 blocks; the backward asks for the VMEM of its 8-head dQ
    accumulator."""
    from mxnet_tpu.ops import flash_blockdiff as bd

    def loss(q, k, v):
        return bd.flash_attention_blockdiff(q, k, v, 4) \
            .astype(jnp.float32).sum()

    assert bd.blocks_for(8192, 4) == (512, 2048)
    fn = jax.grad(loss, argnums=(0, 1, 2))
    shapes = [((1, 8192, 32, 128), jnp.bfloat16)] \
        + [((1, 8192, 4, 128), jnp.bfloat16)] * 2
    _compile_for_chip(fn, one_chip, *shapes,
                      names=["mxtpu_flash_fwd_blockdiff",
                             "mxtpu_flash_bwd_blockdiff"])
    lowered = jax.jit(fn).lower(*[
        jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes])
    assert lowered.as_text().count("scoped_memory_configs") == 1


def test_block_diffusion_cells_chain_program_fits_a_v5e(one_chip, monkeypatch):
    """The cell ``sdar-fused-s4096-bd4`` itself: its 2-step chain program,
    built by the benchmark's own ``build`` at the published widths and
    compiled for the described chip with the platform probe patched true,
    holds the five layers' ten new kernels, and its memory plan (6.6 GB of
    arguments, 7.3 GB of scratch: 13.9 GB, PR 40) fits the chip's 16.9 GB
    with the room the ``cond``'s second branch needs."""
    import importlib.util
    import json
    import os
    import re
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "sdar-30b-a3b-chat.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "traffic", "bd-s4096-b1-chain2.json")) as f:
        mix = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "bench_configs_sdar", os.path.join(bench, "configs",
                                           "sdar-30b-a3b-chat.py"))
    cfgmod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cfgmod)
    net, data, label = cfgmod.build(cfg, mix, 1)
    opt = dict(cfg["optimizer"])
    t = ShardedTrainer(net, build_mesh(devices=jax.devices("cpu")[:1], tp=1),
                       data_shapes=data, label_shapes=label,
                       optimizer=opt.pop("optimizer"), seed=1, **opt,
                       **cfg["trainer"])
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    on_chip = lambda tree: jax.tree.map(                    # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    steps = int(mix["chain"])
    args = (on_chip(t.params), on_chip(t.opt_state), on_chip(t.aux),
            {n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
             for n, s in {**data, **label}.items()},
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
            jax.ShapeDtypeStruct((steps,), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((steps,), jnp.float32, sharding=one_chip))
    step = t._py_step

    def chain(params, opt_state, aux, batch, key, lrs, ts):
        def body(carry, xs):
            p, s, a, ky = carry
            ky, sub = jax.random.split(ky)
            p, s, a, loss = step(p, s, a, batch, sub, *xs)
            return (p, s, a, ky), loss

        (params, opt_state, aux, _), losses = jax.lax.scan(
            body, (params, opt_state, aux, key), (lrs, ts), length=steps)
        return params, opt_state, aux, losses

    compiled = jax.jit(chain, donate_argnums=(0, 1, 2)).lower(*args).compile()
    text = compiled.as_text()
    assert set(re.findall(r"mxtpu_flash_\w+?_blockdiff", text)) == {
        "mxtpu_flash_fwd_blockdiff", "mxtpu_flash_bwd_blockdiff"}
    plan = pk.last_causal_plan()
    assert (plan["diffusion_layers"], plan["diffusion_scores_computed_pct"],
            plan["q_block_rows"]) == (5, 31.25, 512)
    mem = compiled.memory_analysis()
    state = 550984960 * 12
    assert state <= mem.argument_size_in_bytes < state + 2 ** 20
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 13.0e9 < total < 15.0e9, total
