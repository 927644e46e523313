"""Runner ``fused_trainer``: one ``ShardedTrainer`` driven by ``run_steps`` chains.

The harness calls ``open`` once; the session it returns is the one object that
set-up drives through its first steps and that the window then times.  The
program runs with its defaults: no ``MXNET_TPU_*`` or ``BENCH_*`` variable is
set here.
"""
from __future__ import annotations

import numpy as np


def _norms(tree):
    import jax.numpy as jnp
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


class Session:
    def __init__(self, cfg, cfgmod, mix, devices, seed, init_fn, seeded, host_batch):
        import jax
        from mxnet_tpu.parallel import ShardedTrainer, build_mesh

        self.cfg, self.init_fn = cfg, init_fn
        self.chain = int(mix["chain"])
        net, data_shapes, label_shapes = cfgmod.build(cfg, mix, len(devices))
        mesh = build_mesh(devices=list(devices), **mix.get("mesh", {"tp": 1}))
        opt = dict(cfg["optimizer"])
        # the program's initializer draws from numpy's global generator
        np.random.seed(seed % (2 ** 32))
        self.trainer = ShardedTrainer(
            net, mesh, data_shapes=data_shapes, label_shapes=label_shapes,
            optimizer=opt.pop("optimizer"), seed=seed % (2 ** 31), **opt, **cfg["trainer"])
        self.reseed(seeded)
        self.batch = self.trainer.put_batch(host_batch)
        jax.block_until_ready(self.batch)

    def reseed(self, seeded):
        """Replace the program's own initial weights by the benchmark's seeded ones,
        made on the device in one jitted call, placed as the program placed its own."""
        import jax
        t = self.trainer
        self.key, self.offset = seeded
        key = self.key
        missing = set(t.params) ^ set(jax.eval_shape(self.init_fn, key))
        if missing:
            raise RuntimeError("reference and program disagree on parameters: %s"
                               % sorted(missing)[:8])
        shardings = {n: a.sharding for n, a in t.params.items()}
        t.params = None
        t.params = jax.jit(self.init_fn, out_shardings=shardings)(key)
        jax.block_until_ready(t.params)

    def restart(self, seeded, host_batch):
        """``calibrate.py`` only: put the trainer back to step 0 on another seed's
        weights and batch without compiling again (a run of the benchmark never
        calls this: its trainer is new).  Touches what ``load_checkpoint`` touches."""
        import jax
        import jax.numpy as jnp
        t = self.trainer
        self.batch = None
        self.reseed(seeded)
        t.opt_state = jax.jit(lambda s: jax.tree_util.tree_map(jnp.zeros_like, s),
                              donate_argnums=0)(t.opt_state)
        t.aux = jax.jit(lambda a: {k: (jnp.ones_like(v) if k.endswith("moving_var")
                                       else jnp.zeros_like(v)) for k, v in a.items()},
                        donate_argnums=0)(t.aux)
        t._step_count = 0
        t.optimizer.num_update = t.optimizer.begin_num_update
        self.batch = t.put_batch(host_batch)

    # -- the window's own call and feed -----------------------------------
    def dispatch(self, steps=None):
        """Enqueue one chain; returns the handle whose fetch closes it."""
        return self.trainer.run_steps(self.batch, steps or self.chain)

    @staticmethod
    def fetch(handle):
        return np.asarray(handle, dtype=np.float64)

    # -- the first steps, for the comparison -------------------------------
    def first_steps(self):
        """Drive the trainer through 1 + chain steps from its seeded state.

        One step alone first (``run_steps(batch, 1)``), so that the optimizer's
        state holds the first gradient and nothing else; then the window's own
        chain.  Returns the losses, the first gradient's norm by leaf as the
        optimizer got it (worked out from its state), and the norm of the
        parameters' change by leaf after all of them."""
        import jax
        import common   # the benchmark's own sampling rule, shared with the reference
        t, hp = self.trainer, self.cfg["optimizer"]
        losses = list(self.fetch(self.dispatch(1)))
        lr, wd = float(hp["learning_rate"]), float(hp.get("weight_decay", 0.0))
        decays = {k: (wd if (k.endswith("_weight") or k.endswith("_gamma")) else 0.0)
                  for k in t.params}
        if hp["optimizer"] == "sgd":      # m1 = -lr * (g + wd * w0)
            scale = -1.0 / lr
        elif hp["optimizer"] == "adam":   # m1 = (1 - b1) * (g + wd * w0)
            scale = 1.0 / (1.0 - float(hp.get("beta1", 0.9)))
        else:
            raise ValueError("no rule to read the gradient from %r's state" % hp["optimizer"])

        def first_grad(slots, key, offset):
            w0 = self.init_fn(key)
            g = {k: s[0] * scale - decays[k] * w0[k] for k, s in slots.items()}
            return _norms(g), common.grad_sample(g, offset)

        norms, samples = jax.jit(first_grad)(t.opt_state, self.key, self.offset)
        grad_norms = {k: float(v) for k, v in norms.items()}
        samples = {k: np.asarray(v) for k, v in samples.items()}
        losses += list(self.fetch(self.dispatch()))

        def change(params, key):
            w0 = self.init_fn(key)
            return _norms({k: params[k] - w0[k] for k in params})

        delta = {k: float(v) for k, v in jax.jit(change)(t.params, self.key).items()}
        return {"losses": [float(x) for x in losses], "grad_norms": grad_norms,
                "grad_samples": samples, "delta_norms": delta}

    def counters(self):
        """The program's own counts, read as they are: the fusion plan, and the
        memory plan of the chain program (``telemetry.memory``: bytes from the
        compile's ``memory_analysis()``)."""
        from mxnet_tpu.telemetry import memory
        return {"fusion": self.trainer.fusion_summary(),
                "memory_plan": memory.plans_dict().get("trainer.run_steps")}

    def close(self):
        self.trainer = self.batch = None


def open(cfg, cfgmod, mix, devices, seed, init_fn, seeded, host_batch):
    """``seeded`` is ``(key, offset)``: the key of the weights, the offset of the
    gradient elements compared; both stay arguments of every jitted call."""
    return Session(cfg, cfgmod, mix, devices, seed, init_fn, seeded, host_batch)
