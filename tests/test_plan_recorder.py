"""The one plan recorder (``mxnet_tpu.telemetry.plan``) and what the four
modules that note into it make of its plans.

The recorder's own rules first (nesting, an exception, a scope without a
note, ``annotate`` without a plan).  Then the six token configurations of
the benchmark at their toy sizes (``benchmark/configs/smoke-*.json``): a
``ShardedTrainer`` is built, one step traced, and every summary the
benchmark's per-layer readers read (``pallas_kernels.last_causal_plan``,
``delta_rule`` / ``ssd`` / ``moe`` ``.last_plan_summary``) compared, entry
for entry, with what the parent of PR 43 (commit 75a636c, four recorders
opened one by one, the flash one alone round the pull) published.  Each is
traced twice: as the CPU takes it (``jax.numpy`` forms, no flash plan) and,
the platform probe patched true and the shapes widened to what the kernels
take, as the chip does (the Pallas calls are traced, never lowered).

The values are data, ``tests/plan_summaries_parent.json``.  A PR that means
to move them records them again with the code it compares against::

    JAX_PLATFORMS=cpu python tests/test_plan_recorder.py <checkout> <out.json>
"""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "plan_summaries_parent.json")

#: toy configuration -> (its traffic mix, what the chip's trace widens in
#: the configuration, and in the mix): sequences the flash kernels tile, a
#: window shorter than the sequence, the delta rule's heads at a lane tile
TOYS = {
    "smoke-opt": ("smoke-s32-b2-chain2",
                  {"max_position_embeddings": 1024}, {"seq": 1024}),
    "smoke-lfm2": ("smoke-s64-b1-chain2",
                   {"max_position_embeddings": 1024}, {"seq": 1024}),
    "smoke-kimi": ("smoke-s64-b1-chain2",
                   {"linear_attn_config.head_dim": 128}, {"seq": 1024}),
    "smoke-trinity": ("smoke-s64-b1-chain2",
                      {"sliding_window": 1024, "head_dim": 32,
                       "num_attention_heads": 2, "num_key_value_heads": 1},
                      {"seq": 4096}),
    "smoke-nemotron": ("smoke-s64-b1-chain2", {}, {"seq": 1024}),
    "smoke-sdar": ("smoke-bd-s64-b1-chain2", {},
                   {"document": 1024, "seq": 2 * 1024 + 1024 // 4}),
}
CASES = [(name, branch) for name in TOYS for branch in ("cpu", "chip")]
#: toys younger than the recorded file: held to values written out below
NEW_TOYS = {
    "smoke-glm47": ("smoke-s64-b1-chain2", {}, {"seq": 1024}),
}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _toy_trainer(name, branch, root):
    import jax
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    bench = os.path.join(root, "benchmark")
    mix_name, cfg_wide, mix_wide = {**TOYS, **NEW_TOYS}[name]
    cfg = _load(os.path.join(bench, "configs", name + ".json"))
    mix = _load(os.path.join(bench, "traffic", mix_name + ".json"))
    if branch == "chip":
        mix.update(mix_wide)
        for key, value in cfg_wide.items():
            *outer, leaf = key.split(".")
            part = cfg
            for k in outer:
                part = part[k]
            part[leaf] = value
    spec = importlib.util.spec_from_file_location(
        "toy_builder", os.path.join(bench, "configs", cfg["code"] + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    net, data, label = mod.build(cfg, mix, 1)
    opt = dict(cfg["optimizer"])
    trainer = ShardedTrainer(
        net, build_mesh(devices=jax.devices()[:1], tp=1), data_shapes=data,
        label_shapes=label, optimizer=opt.pop("optimizer"), seed=0, **opt,
        **cfg["trainer"])
    return trainer, {**data, **label}


def summaries(name, branch, root=os.path.dirname(HERE)):
    """What the readers read after one traced step of the toy trainer, as
    JSON holds it.  The caller has forgotten the plans before."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import context
    from mxnet_tpu.ops import delta_rule, pallas_kernels, ssd
    from mxnet_tpu.parallel import moe
    trainer, inputs = _toy_trainer(name, branch, root)
    spec = lambda tree: jax.tree.map(                       # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    args = (spec(trainer.params), spec(trainer.opt_state), spec(trainer.aux),
            {k: jax.ShapeDtypeStruct(v, jnp.float32)
             for k, v in inputs.items()},
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32))
    probe = context.on_tpu
    context.on_tpu = lambda: branch == "chip"
    try:
        jax.eval_shape(trainer._py_step, *args)
    finally:
        context.on_tpu = probe
    return json.loads(json.dumps({
        "flash": pallas_kernels.last_causal_plan(),
        "kda": delta_rule.last_plan_summary(),
        "ssd": ssd.last_plan_summary(),
        "moe": moe.last_plan_summary()}))


# ---------------------------------------------------- the recorder's rules

@pytest.fixture
def plan(monkeypatch):
    from mxnet_tpu.telemetry import plan
    monkeypatch.setattr(plan, "_LAST", {})
    return plan


def test_nesting_restores_the_outer_recording(plan):
    with plan.recording():
        plan.note("a", n=1)
        with plan.recording():
            plan.note("a", n=2)
            plan.note("b", n=3)
        # the inner one published its own and the outer collects again
        assert plan.last("a") == [{"n": 2}] and plan.last("b") == [{"n": 3}]
        plan.note("a", n=4)
        assert plan.active()
    assert not plan.active()
    assert plan.last("a") == [{"n": 1}, {"n": 4}]
    assert plan.last("b") == [{"n": 3}]


def test_an_exception_publishes_nothing_and_keeps_the_last_plans(plan):
    with plan.recording():
        plan.note("a", n=1)
    plan.annotate("a", compiled=7)
    with pytest.raises(RuntimeError):
        with plan.recording():
            plan.note("a", n=2)
            plan.note("b", n=3)
            raise RuntimeError("the trace failed")
    assert plan.last("a") == [{"n": 1}] and plan.last("b") is None
    assert plan.annotations("a") == {"compiled": 7}
    assert not plan.active()
    # and under an outer recording the outer one is back
    with plan.recording():
        with pytest.raises(RuntimeError):
            with plan.recording():
                raise RuntimeError("the trace failed")
        plan.note("b", n=5)
    assert plan.last("b") == [{"n": 5}]


def test_a_scope_without_a_note_keeps_its_plan_while_anothers_is_replaced(
        plan):
    with plan.recording():
        plan.note("a", n=1)
        plan.note("b", n=1)
    plan.annotate("a", compiled=7)
    plan.annotate("b", compiled=8)
    with plan.recording():
        plan.note("b", n=2)
    assert plan.last("a") == [{"n": 1}]
    assert plan.annotations("a") == {"compiled": 7}
    # a new plan starts without the old one's annotations
    assert plan.last("b") == [{"n": 2}] and plan.annotations("b") == {}
    with plan.recording():
        pass
    assert plan.last("a") == [{"n": 1}] and plan.last("b") == [{"n": 2}]


def test_annotate_and_note_outside_any_plan_do_nothing(plan):
    plan.annotate("a", compiled=7)
    plan.note("a", n=1)
    assert plan.last("a") is None and plan.annotations("a") == {}
    assert not plan.active()
    from mxnet_tpu.parallel import moe

    class Text:
        def as_text(self):
            return "custom-call"
    moe.note_compiled(Text())
    assert moe.last_plan_summary() is None


def test_the_modules_names_are_the_one_recorder(plan):
    from mxnet_tpu.ops import delta_rule, pallas_kernels, ssd
    from mxnet_tpu.parallel import moe
    for module in (delta_rule, ssd, moe):
        assert module.plan_recording is plan.recording
    assert pallas_kernels.causal_plan_recording is plan.recording
    # one recording collects every module's notes, each under its scope
    with moe.plan_recording():
        moe.note_layer(buffer_rows=64)
        plan.note(ssd.SCOPE_SSD, state_bytes=16)
    assert moe.last_plan_summary()["expert_layers"] == 1
    assert ssd.last_plan_summary() == {
        "layers": [{"state_bytes": 16}], "chunked_layers": 1,
        "state_bytes": 16}
    assert delta_rule.last_plan_summary() is None
    assert pallas_kernels.last_causal_plan() is None


def test_auto_layouts_true_is_refused_and_the_removal_named():
    import mxnet_tpu as mx
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    import jax
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4, name="fc"), name="softmax")
    mesh = build_mesh(devices=jax.devices()[:1], tp=1)
    shapes = dict(data_shapes={"data": (2, 8)},
                  label_shapes={"softmax_label": (2,)})
    with pytest.raises(MXNetError, match="auto_layouts.*PR 43"):
        ShardedTrainer(net, mesh, auto_layouts=True, **shapes)
    assert ShardedTrainer(net, mesh, auto_layouts=False, **shapes).params
    with pytest.raises(TypeError, match="native_weight_layout"):
        ShardedTrainer(net, mesh, native_weight_layout=True, **shapes)


def _ssd_tower(layers, vocab=16, width=16, heads=2, head_dim=8, state=4):
    """A decoder of state-space layers alone, small enough to pipeline."""
    from mxnet_tpu import symbol as sym
    x = sym.Embedding(sym.Variable("data"), input_dim=vocab,
                      output_dim=width, name="emb")
    for i in range(layers):
        pre = "l%d_" % i

        def fc(of, n, name):
            return sym.FullyConnected(of, num_hidden=n, flatten=False,
                                      no_bias=True, name=pre + name)

        y = sym._contrib_SSDScan(
            sym.Reshape(fc(x, heads * head_dim, "x"),
                        shape=(0, 0, heads, head_dim)), fc(x, heads, "dt"),
            sym.Reshape(fc(x, state, "b"), shape=(0, 0, 1, state)),
            sym.Reshape(fc(x, state, "c"), shape=(0, 0, 1, state)),
            A_log=sym.Variable(pre + "a_log_bias"),
            D=sym.Variable(pre + "d_gamma"),
            dt_bias=sym.Variable(pre + "dt_bias"), chunk_size=8,
            name=pre + "ssd")
        x = x + fc(sym.Reshape(y, shape=(0, 0, -3)), width, "out")
    x = sym.FullyConnected(sym.Reshape(x, shape=(-3, 0)), num_hidden=vocab,
                           name="head")
    return sym.SoftmaxOutput(x, name="softmax")


@pytest.mark.parametrize("stages", [1, 2])
def test_a_pipelined_step_records_each_layer_once(stages, plan):
    """``_build_pipeline_step`` opens the recording too: its stages are
    traced as branches of one program, and each layer is noted once, at
    the microbatch it runs over."""
    import numpy as np
    from mxnet_tpu.ops import ssd
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    layers, batch, seq = 4, 8, 16
    piped = dict(pipeline_stages=stages, pipeline_microbatches=stages) \
        if stages > 1 else {}
    trainer = ShardedTrainer(
        _ssd_tower(layers),
        build_mesh(n_devices=stages, **({"pp": stages} if stages > 1
                                        else {"tp": 1})),
        data_shapes={"data": (batch, seq)},
        label_shapes={"softmax_label": (batch * seq,)}, seed=3, **piped)
    tokens = np.random.RandomState(0).randint(0, 16, (batch, seq))
    trainer.step({"data": tokens.astype("f"),
                  "softmax_label": tokens.reshape(-1).astype("f")})
    got = ssd.last_plan_summary()
    assert got["chunked_layers"] == layers
    one_layer = 4 * (batch // stages) * 2 * 8 * 4 * (seq // 16)
    assert got["state_bytes"] == layers * one_layer


# ------------------------------------- the summaries against the parent's

@pytest.mark.parametrize("name,branch", CASES)
def test_a_traced_toy_step_publishes_the_parents_summaries(
        name, branch, plan):
    want = _load(RECORDED)[name][branch]
    got = summaries(name, branch)
    assert sorted(got) == sorted(want)
    if got["moe"] is not None:
        # younger than the recorded file (PR 46): what sums each layer's
        # rows into token order.  A toy is 32 wide, no whole lane tile, so
        # either branch keeps the jax.numpy form, or the full buffer's gathers
        forms = [layer.pop("token_sum") for layer in got["moe"]["layers"]]
        assert forms and set(forms) <= {"scatter_add", "gathers"}, forms
        assert got["moe"].pop("token_sum_layers") == 0
    for module in want:
        assert got[module] == want[module], module


@pytest.mark.parametrize("branch", ["cpu", "chip"])
def test_glm_toy_step_publishes_its_blocks_plans(branch, plan):
    """GLM-4.7-Flash's toy (PR 44): two decoder layers and the
    multi-token-prediction module note three latent-attention layers with
    both options and one module, beside the expert layers' and (as the chip
    traces it) the flash kernels' plans."""
    from mxnet_tpu.models import glm4_moe_lite
    got = summaries("smoke-glm47", branch)
    assert got["kda"] is None and got["ssd"] is None
    assert got["moe"]["expert_layers"] == 2
    seq = 1024 if branch == "chip" else 64
    assert [(la["buffer_rows"], la["small_rows"], la["even_rows"])
            for la in got["moe"]["layers"]] == \
        [(seq * 4, seq * 2, seq * 4 * 4 / 16)] * 2
    if branch == "cpu":
        assert got["flash"] is None
    else:
        kernels = got["flash"]["kernels"]
        assert [(k["kernel"], k["shape"], k["dk"], k["dv"], k["block_q"],
                 k["block_k"]) for k in kernels] == \
            [("flash_attention_fwd", [1, 1024, 4, 24], 24, 24, 256, 1024)] * 3 \
            + [("flash_attention_bwd", [1, 1024, 4, 24], 24, 24, 256, 1024)] * 3
    assert glm4_moe_lite.last_plan_summary() == {
        "mla_layers": [{"q_lora_rank": 24, "rope_dims": 8, "dk": 24, "dv": 24,
                        "heads": 4}] * 3,
        "mtp": {"depth": 1, "layer_rows": seq, "head_rows": 2 * seq,
                "loss_weight": 0.3,
                "shared": ["embed_weight", "lm_head_weight"]}}


def test_recorded_summaries_cover_every_case_and_every_module():
    recorded = _load(RECORDED)
    assert sorted((n, b) for n in recorded for b in recorded[n]) \
        == sorted(CASES)
    published = {m for n, b in CASES for m, s in recorded[n][b].items()
                 if s is not None}
    assert published == {"flash", "kda", "ssd", "moe"}
    chip = {n: recorded[n]["chip"] for n in recorded}
    # the chip's traces reach what the cells' readers read
    assert chip["smoke-trinity"]["flash"]["window_layers"] > 0
    assert chip["smoke-sdar"]["flash"]["diffusion_layers"] > 0
    assert chip["smoke-kimi"]["kda"]["kernel_layers"] > 0
    assert chip["smoke-kimi"]["kda"]["bwd_hi_products"] > 0
    assert chip["smoke-nemotron"]["ssd"]["chunked_layers"] > 0
    assert all(chip[n]["moe"]["expert_layers"] > 0
               for n in chip if n != "smoke-opt")


def _forget():
    """No plan from an earlier trace: the recorder's, or at the parent each
    module's own."""
    try:
        from mxnet_tpu.telemetry import plan
        plan._LAST.clear()
    except ImportError:
        from mxnet_tpu.ops import delta_rule, pallas_kernels, ssd
        from mxnet_tpu.parallel import moe
        pallas_kernels._LAST_CAUSAL_PLAN = None
        delta_rule._LAST_SUMMARY = ssd._LAST_SUMMARY = None
        moe._LAST_SUMMARY = None


if __name__ == "__main__":
    checkout, out_path = sys.argv[1:3]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, checkout)
    rec = {}
    for toy, trace in CASES:
        _forget()
        rec.setdefault(toy, {})[trace] = summaries(toy, trace, checkout)
    with open(out_path, "w") as f:
        json.dump(rec, f, sort_keys=True, separators=(",", ":"))
        f.write("\n")
