"""The block-diffusion flash kernels (PR 40) in interpret mode on the CPU:
against the plain path and against a dense mask built row by row from the
three sentences of the mask, forward and the three gradients; the closed-form
cases of one block a document and of blocks of one position; the share of the
square the kernels compute against a count made here, and that no product
they run is of a tile the mask leaves empty; the op's dispatch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import flash_blockdiff as bd
from mxnet_tpu.ops import pallas_kernels as pk


def row_by_row(t, block):
    """The mask of ``t = 2L`` rows, clean copy first, one row and key at a
    time from the three sentences."""
    half = t // 2
    seen = np.zeros((t, t), bool)
    for r in range(t):
        for c in range(t):
            i, j = r % half, c % half
            if r < half and c < half:       # c_i sees c_j iff j // B <= i // B
                seen[r, c] = j // block <= i // block
            elif r >= half and c >= half:   # n_i sees n_j iff j // B == i // B
                seen[r, c] = j // block == i // block
            elif r >= half:                 # n_i sees c_j iff j // B < i // B
                seen[r, c] = j // block < i // block
            # no clean row sees a noised row
    return seen


def dense(q, k, v, seen):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def qkvg(t, hq, hk, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(rng.normal(0, 1, (1, t, h, d)), jnp.float32)  # noqa: E731
    return mk(hq), mk(hk), mk(hk), mk(hq)


def test_the_mask_is_the_three_sentences():
    for t, block in ((16, 1), (16, 2), (32, 4), (32, 16), (48, 8)):
        assert np.array_equal(np.asarray(bd.sees(t, block)),
                              row_by_row(t, block)), (t, block)
    # L^2 + L B of the 4 L^2 pairs
    assert row_by_row(64, 4).sum() == 32 * 32 + 32 * 4


# (L, B, block_q, block_k, query heads, key/value heads): B in (1, 4, 32), the
# half spanning 1, 2 and 4 K/V tiles, 8 query heads a key/value head among them
CASES = [(64, 4, 32, 64, 2, 1), (128, 4, 32, 64, 8, 1), (256, 4, 32, 64, 2, 2),
         (64, 1, 16, 32, 2, 1), (128, 1, 32, 32, 2, 1),
         (32, 32, 32, 32, 2, 1), (64, 32, 32, 32, 8, 1), (128, 32, 32, 64, 2, 1),
         (256, 32, 64, 64, 2, 1)]


@pytest.mark.parametrize("half,block,block_q,block_k,hq,hk", CASES, ids=str)
def test_kernels_agree_with_the_row_by_row_mask(half, block, block_q, block_k,
                                                hq, hk):
    t = 2 * half
    q, k, v, g = qkvg(t, hq, hk)
    seen = row_by_row(t, block)
    blocks = (block_q, block_k)

    def kernel(q, k, v):
        return bd.flash_attention_blockdiff(q, k, v, block, True, blocks)

    want, want_vjp = jax.vjp(lambda *a: dense(*a, seen), q, k, v)
    got, got_vjp = jax.vjp(kernel, q, k, v)
    plain, plain_vjp = jax.vjp(
        lambda *a: pk._attention_jnp(*a, False, 0, block), q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(plain, want, atol=2e-5)
    for a, b, c in zip(got_vjp(g), want_vjp(g), plain_vjp(g)):
        np.testing.assert_allclose(a, b, atol=5e-5)
        np.testing.assert_allclose(c, b, atol=5e-5)


def test_one_block_a_document_and_blocks_of_one_position():
    """B = L: the noised copy sees itself only and the clean copy everything
    of itself, two full attentions side by side.  B = 1: the clean copy is
    causal, and a noised row sees its own token and the strict clean prefix."""
    half, hq, hk = 32, 2, 1
    q, k, v, _g = qkvg(2 * half, hq, hk, seed=3)
    got = bd.flash_attention_blockdiff(q, k, v, half, True, (32, 32))
    for part in (slice(0, half), slice(half, 2 * half)):
        np.testing.assert_allclose(
            got[:, part], pk._attention_jnp(q[:, part], k[:, part], v[:, part],
                                            False), atol=2e-5)
    got = bd.flash_attention_blockdiff(q, k, v, 1, True, (16, 32))
    np.testing.assert_allclose(
        got[:, :half], pk._attention_jnp(q[:, :half], k[:, :half], v[:, :half],
                                         True), atol=2e-5)
    kk, vv = jnp.repeat(k, hq // hk, 2), jnp.repeat(v, hq // hk, 2)
    for i in (0, 1, 7, half - 1):
        keys = jnp.concatenate([kk[:, :i], kk[:, half + i:half + i + 1]], 1)
        vals = jnp.concatenate([vv[:, :i], vv[:, half + i:half + i + 1]], 1)
        s = jnp.einsum("bhd,bkhd->bhk", q[:, half + i], keys) / 4.0
        want = jnp.einsum("bhk,bkhd->bhd", jax.nn.softmax(s, -1), vals)
        np.testing.assert_allclose(got[:, half + i], want, atol=2e-5)


@pytest.mark.parametrize("half,block,block_q,block_k", [
    (4096, 4, 512, 2048), (4096, 4, 512, 1024), (4096, 4, 256, 512),
    (2048, 32, 512, 1024), (256, 4, 32, 64), (64, 32, 32, 32), (32, 32, 32, 32)],
    ids=str)
def test_share_computed_is_the_live_tiles_and_no_empty_tile_is_run(
        half, block, block_q, block_k):
    t = 2 * half
    plan = pk._causal_plan(block_q, block_k)
    tiles = bd.tiles_run(t, block_q, block_k, block, plan)
    blk = np.arange(t) % half // block
    noised = np.arange(t) >= half

    def any_seen(r0, r1, c0, c1):
        qb, kb = blk[r0:r1, None], blk[None, c0:c1]
        qn, kn = noised[r0:r1, None], noised[None, c0:c1]
        return np.where(qn, np.where(kn, kb == qb, kb < qb),
                        ~kn & (kb <= qb))

    covered = 0
    for _qpos, row, _ki, col, cols in tiles:
        seen = any_seen(row, row + block_q, col, col + cols)
        assert seen.any(), (row, col, cols)
        covered += int(seen.sum())
    # every pair the mask needs lies in a product that is run, once
    assert covered == half * half + half * block
    assert len({(row, col) for _q, row, _k, col, _c in tiles}) == len(tiles)
    # counted here: a whole tile where every row sees every key, the prefix of
    # the block's place on the diagonal where its position crosses the tile,
    # block_q columns of a noised block's own tile
    m = block_k // block_q
    count = 0
    for qpos in range(t // block_q):
        row = qpos * block_q
        for ki in range(half // block_k):
            seen = any_seen(row, row + block_q, ki * block_k, (ki + 1) * block_k)
            if seen.all():
                count += block_q * block_k
            elif seen.any():
                place = qpos % (half // block_q) % m
                count += block_q * next(
                    c for lo, hi, c in plan[1] if lo <= place < hi)
        count += block_q * block_q * (row >= half)
    pct = bd.scores_computed_pct(t, block_q, block_k, block, plan)
    assert pct == pytest.approx(100.0 * count / (t * t))
    needed = 100.0 * (half * half + half * block) / (t * t)
    assert needed <= pct < 53.125
    if (half, block) == (4096, 4):
        assert needed == pytest.approx(25.02, abs=0.01)
        assert pct == {(512, 2048): 31.25, (512, 1024): 31.25,
                       (256, 512): 28.125}[(block_q, block_k)]


def test_rule_takes_the_measured_blocks_or_none():
    assert bd.blocks_for(8192, 4) == (512, 2048)
    assert bd.blocks_for(4096, 4) == (512, 2048)
    assert bd.blocks_for(2048, 4) == (512, 1024)
    assert bd.blocks_for(2 * 3072, 4) == (512, 1024)
    assert bd.blocks_for(256, 4) == (128, 128)
    assert bd.blocks_for(128, 4) is None            # a half under one tile
    assert bd.blocks_for(8192, 3) is None           # no power of two
    assert bd.blocks_for(8192, 1024) is None        # a Q block is no block
    with pytest.raises(ValueError, match="do not tile"):
        bd.fwd(*qkvg(128, 2, 1)[:3], 4, True, (48, 64))


def test_plan_records_the_diffusion_kernels():
    q = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)

    def loss(q, k, v):
        return bd.flash_attention_blockdiff(q, k, v, 4, True) \
            .astype(jnp.float32).sum()

    with pk.causal_plan_recording():
        text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, kv, kv))
    plan = pk.last_causal_plan()
    assert [k["kernel"] for k in plan["kernels"]] == [
        "flash_attention_fwd_blockdiff", "flash_attention_bwd_blockdiff"]
    assert plan["kernels"][0] == {
        "block_q": 512, "block_k": 2048, "n_k": 4, "causal": False,
        "causal_ranges": 4, "scores_computed_pct": 31.25, "window": 0,
        "group_parts": 1, "tiles_per_q_block": 3, "diffusion_block": 4,
        "kernel": "flash_attention_fwd_blockdiff",
        "shape": (1, 8192, 8, 128), "dk": 128, "dv": 128}
    assert plan["diffusion_layers"] == 1
    assert plan["diffusion_scores_computed_pct"] == 31.25
    assert (plan["scores_computed_pct"], plan["q_block_rows"],
            plan["window_layers"]) == (31.25, 512, 0)
    assert "name=mxtpu_flash_fwd_blockdiff" in text
    assert "name=mxtpu_flash_bwd_blockdiff" in text
    # the backward's dQ accumulator holds the group's rows: it asks for VMEM
    need = pk._vmem_need(128, 512, 2048, 8 * 8192)
    assert "vmem_limit_bytes=%d" % (need * 3 // 2) in text
    # a step without such a kernel says so
    with pk.causal_plan_recording():
        jax.make_jaxpr(lambda q: pk._flash_attention_fwd_pallas(
            q, q, q, True, True)[0])(
                jax.ShapeDtypeStruct((1, 256, 2, 16), jnp.float32))
    plan = pk.last_causal_plan()
    assert (plan["diffusion_layers"],
            plan["diffusion_scores_computed_pct"]) == (0, None)


def _op(**attrs):
    return mx.sym._contrib_FlashAttention(
        mx.sym.Variable("q"), mx.sym.Variable("k"), mx.sym.Variable("v"),
        **attrs)


def test_op_takes_the_plain_path_off_the_chip_and_refuses_other_masks():
    q, k, v, _g = qkvg(64, 4, 2, seed=5)
    ex = _op(diffusion_block=4).bind(
        mx.cpu(), {"q": mx.nd.array(q), "k": mx.nd.array(k),
                   "v": mx.nd.array(v)})
    np.testing.assert_allclose(ex.forward()[0].asnumpy(),
                               dense(q, k, v, row_by_row(64, 4)), atol=2e-5)
    for attrs, says in (
            (dict(diffusion_block=4, causal=True), "a mask of its own"),
            (dict(diffusion_block=5), "whole number of blocks"),
            (dict(diffusion_block=-1), "whole number of blocks")):
        with pytest.raises(MXNetError, match=says):
            _op(**attrs).infer_shape(q=(1, 64, 4, 16), k=(1, 64, 2, 16),
                                     v=(1, 64, 2, 16))
    with pytest.raises(MXNetError, match="whole number of blocks"):
        _op(diffusion_block=4).infer_shape(q=(1, 63, 4, 16), k=(1, 63, 2, 16),
                                           v=(1, 63, 2, 16))


def test_op_lowers_to_the_kernels_for_the_chip(monkeypatch):
    """The platform probe patched true, lowered for the TPU from here: the
    call is the two new kernels under ``mxtpu.block.bda``; a half that no
    K/V tile divides stays on the plain path."""
    from mxnet_tpu import context
    monkeypatch.setattr(context, "on_tpu", lambda: True)

    def loss(q, k, v):
        return pk.flash_attention_op({"causal": False, "window": 0,
                                      "diffusion_block": 4}, None, q, k, v) \
            .astype(jnp.float32).sum()

    def lowered(t):
        q = jax.ShapeDtypeStruct((1, t, 8, 128), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, t, 1, 128), jnp.bfloat16)
        return jax.jit(jax.grad(loss, (0, 1, 2))).trace(q, kv, kv).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)

    text = lowered(2048)
    assert text.count('kernel_name = "mxtpu_flash_fwd_blockdiff"') == 1
    assert text.count('kernel_name = "mxtpu_flash_bwd_blockdiff"') == 1
    assert "mxtpu.block.bda" in text
    assert "mxtpu_flash" not in lowered(2 * 200)
