"""Reference training: three AdamW steps of the plain model in float32.

The optimizer as the configuration states it (paper §4.1): the gradient
clipped to a global norm of ``grad_clip``, Adam with bias correction, and
decoupled weight decay on matrices (embedding and head included) but not
on norm scales or biases; the learning rate follows a cosine from
``learning_rate`` to ``min_lr`` over ``total_steps`` after ``warmup_steps``
of linear warm-up.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import model as R
from bench.weights import leaves


def lr_at(step: int, o: dict) -> float:
    if step < o["warmup_steps"]:
        return o["learning_rate"] * step / max(o["warmup_steps"], 1)
    prog = min(max((step - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    return o["min_lr"] + 0.5 * (o["learning_rate"] - o["min_lr"]) * (
        1 + math.cos(math.pi * prog))


def leaf_norms(flat: dict, scale=1.0) -> dict:
    """L2 norm of every leaf (a leaf stacks one weight over all layers, as
    the program's parameter tree does)."""
    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) * scale)))
            for n, x in flat.items()}


def make_step(c: dict, precision: str):
    o = c["optimizer"]
    no_decay = {n for n, (_, init) in leaves(c).items()
                if init in ("norm", "bias")}

    def step(params, m, v, batch, lr, count):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(R.loss)(
                params, batch["tokens"], batch["labels"], batch["seg"], c,
                precision)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        g = {n: x * jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gn, 1e-9))
             for n, x in g.items()}
        bc1 = 1.0 - o["b1"] ** count
        bc2 = 1.0 - o["b2"] ** count
        new_p, new_m, new_v = {}, {}, {}
        for n in params:
            new_m[n] = o["b1"] * m[n] + (1 - o["b1"]) * g[n]
            new_v[n] = o["b2"] * v[n] + (1 - o["b2"]) * g[n] * g[n]
            upd = (new_m[n] / bc1) / (jnp.sqrt(new_v[n] / bc2) + o["eps"])
            if n not in no_decay:
                upd = upd + o["weight_decay"] * params[n]
            new_p[n] = params[n] - lr * upd
        return new_p, new_m, new_v, loss, leaf_norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def run(weights_fn, batches, c: dict, precision: str = "fp32"):
    """``weights_fn()`` gives the initial weights (made anew, so that the
    caller need not keep a copy). Returns the loss of each step, the
    per-leaf norms of the first clipped gradient, and the per-leaf norms
    of the change of the weights over all the steps."""
    params = {n: x.astype(jnp.float32) for n, x in weights_fn().items()}
    m = {n: jnp.zeros_like(x) for n, x in params.items()}
    v = {n: jnp.zeros_like(x) for n, x in params.items()}
    step = make_step(c, precision)
    losses, g1 = [], None
    for i, batch in enumerate(batches):
        params, m, v, loss, gnorms = step(
            params, m, v, batch, jnp.float32(lr_at(i, c["optimizer"])),
            jnp.float32(i + 1))
        losses.append(float(loss))
        if g1 is None:
            g1 = {n: float(x) for n, x in gnorms.items()}
    del m, v
    w0 = weights_fn()
    change = jax.jit(_change_norms)(params, w0)
    return losses, g1, {n: float(x) for n, x in change.items()}


def _change_norms(p, w0):
    return leaf_norms({n: p[n] - w0[n].astype(jnp.float32) for n in p})
