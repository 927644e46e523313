"""What the plain references share: seeded weights, the optimizer rules and
the few-step training loop the comparison follows.

Plain ``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``.
Imports nothing of the program and takes nothing the program has made.

``quant`` selects the precision of every matmul/conv operand:
``None`` is the reference; ``"fp8"`` rounds both operands to float8 e4m3 (the
control: the nearest precision below the bf16 the configurations state).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed, n=2):
    """``n`` 32-bit words from any whole-number seed (the driver's are > 2**31)."""
    return [int(w) for w in np.random.SeedSequence(int(seed)).generate_state(n)]


def q(x, quant):
    """Operand of a matmul or conv in the precision under test (straight-through)."""
    if quant is None:
        return x
    if quant == "fp8":
        lo = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        return x + jax.lax.stop_gradient(lo - x)
    raise ValueError("unknown precision %r" % (quant,))


def softmax_xent(logits, labels):
    """(sum, mean) of -log p(label) over rows; logits (N, C) f32, labels (N,) int."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=1)[:, 0]
    return jnp.sum(nll), jnp.mean(nll)


def decays(name):
    """The reference's rule: weight decay on ``*_weight`` and ``*_gamma`` only."""
    return name.endswith("_weight") or name.endswith("_gamma")


def sgd_momentum(w, g, s, hp, t, wd):
    m = hp["momentum"] * s[0] - hp["learning_rate"] * (g + wd * w)
    return w + m, [m]


def adam(w, g, s, hp, t, wd):
    b1, b2, eps = hp.get("beta1", 0.9), hp.get("beta2", 0.999), hp.get("epsilon", 1e-8)
    g = g + wd * w
    m = b1 * s[0] + (1 - b1) * g
    v = b2 * s[1] + (1 - b2) * jnp.square(g)
    lr_t = hp["learning_rate"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    return w - lr_t * m / (jnp.sqrt(v) + eps), [m, v]


RULES = {"sgd": (1, sgd_momentum), "adam": (2, adam)}


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


SAMPLE = 4096


def grad_sample(tree, offset):
    """Up to ``SAMPLE`` elements of every ``*_weight`` leaf, evenly strided from an
    offset drawn from the seed: what the two sides' first gradients are compared on,
    element by element."""
    out = {}
    for k, v in tree.items():
        if k.endswith("_weight"):
            flat = v.reshape(-1).astype(jnp.float32)
            m = min(flat.shape[0], SAMPLE)
            stride = flat.shape[0] // m
            out[k] = flat[offset % stride + jnp.arange(m) * stride]
    return out


def follow(loss_fn, init_fn, key, offset, batch, hp, steps, grad_rows, quant=None):
    """Train ``steps`` steps on one batch from seeded weights, as the program does.

    ``init_fn(key) -> params``; ``loss_fn(params, batch, quant) -> (sum of
    cross-entropy, mean)``; the gradient is of ``sum / grad_rows`` (the program
    rescales by the batch's first dimension).  Returns host values: the loss of
    every step, the first gradient's norm by leaf and its sampled elements
    (``grad_sample``), the norm of the parameters' change after all the steps by
    leaf, and the bytes the step program needs.
    """
    n_slots, rule = RULES[hp["optimizer"]]
    wd = float(hp.get("weight_decay", 0.0))

    def one(params, slots, batch, t, offset):
        def f(p):
            total, mean = loss_fn(p, batch, quant)
            return total / grad_rows, mean
        (_, mean), grads = jax.value_and_grad(f, has_aux=True)(params)
        new_p, new_s = {}, {}
        for k, w in params.items():
            new_p[k], new_s[k] = rule(w, grads[k], slots[k], hp, t, wd if decays(k) else 0.0)
        return new_p, new_s, mean, leaf_norms(grads), grad_sample(grads, offset)

    def change(params, key):
        return leaf_norms({k: params[k] - w for k, w in init_fn(key).items()})

    with jax.default_matmul_precision("highest"):
        params = jax.jit(init_fn)(key)
        slots = {k: [jnp.zeros_like(v) for _ in range(n_slots)] for k, v in params.items()}
        step = jax.jit(one, donate_argnums=(0, 1)).lower(
            params, slots, batch, jnp.float32(1), offset).compile()
        mem = step.memory_analysis()
        footprint = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes - mem.alias_size_in_bytes) if mem else 0
        losses, first = [], None
        for i in range(steps):
            params, slots, mean, gn, gs = step(params, slots, batch, jnp.float32(i + 1), offset)
            losses.append(float(mean))
            if i == 0:
                first = {k: float(v) for k, v in gn.items()}
                samples = {k: np.asarray(v) for k, v in gs.items()}
        delta = {k: float(v) for k, v in jax.jit(change)(params, key).items()}
    del params, slots
    return {"losses": losses, "grad_norms": first, "grad_samples": samples,
            "delta_norms": delta, "footprint_bytes": footprint}
