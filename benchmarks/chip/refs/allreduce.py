"""Plain reference of the training algorithm of a cell whose traffic has
``method="allreduce"``: the paper's Algorithm 1 baseline with the Nesterov step
of its Algorithm 5. W replicas of one model start from the same weights; each
takes the gradient on its own rows, every replica takes the mean of all W
gradients, and each steps with it:

    g       = mean_i grad loss(theta_i; rows of worker i)
    v_i'    = mu * v_i - lr * g
    theta_i' = theta_i - lr * g + mu * v_i'

It imports nothing of the system under test and takes nothing it made: the
weights come from the reference model's own ``init`` on the seed. The harness
finds it by the traffic's ``method``; another algorithm is another file of
``refs/`` with the same ``train``.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def train(model, cfg: dict, traffic: dict, params0, batches, seed: int, *,
          dtype=jnp.float32, steps: int = 3, fault: str = "", devices=None):
    """Runs ``steps`` steps of the reference from ``params0`` on ``batches``
    (one ``(tokens, labels)`` pair of ``[W, B, S]`` arrays per step).

    Returns ``(losses, grads, thetas)``: the mean loss over workers at each
    step, each worker's first gradient (as its optimizer gets it), and each
    worker's parameters after the last step (float32 trees). ``fault`` plants
    a fault for calibration: ``"half"`` takes the loss over the first half of
    each worker's rows only, ``"nomean"`` leaves the gradient mean out.
    ``seed`` is unused: the algorithm draws nothing.
    """
    del seed
    W = traffic["workers"]
    lr, mu = traffic["lr"], traffic["momentum"]
    dev = (devices or jax.devices()[:1])[0]

    def value_and_grad(theta, tokens, labels):
        if fault == "half":
            half = tokens.shape[0] // 2
            tokens, labels = tokens[:half], labels[:half]
        return jax.value_and_grad(model.loss)(theta, cfg, tokens, labels)

    grad_fn = jax.jit(value_and_grad)
    cast = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(dtype), t))
    theta = [cast(params0) for _ in range(W)]
    vel = [jax.tree.map(jnp.zeros_like, t) for t in theta]

    @jax.jit
    def update(th, v, g):
        v_new = jax.tree.map(lambda v, g: mu * v - lr * g, v, g)
        th_new = jax.tree.map(lambda x, g, v: x - lr * g + mu * v, th, g, v_new)
        return th_new, v_new

    mean = jax.jit(lambda *gs: jax.tree.map(lambda *x: sum(x) / W, *gs))
    losses, first_grads = [], None
    for t in range(steps):
        out = [grad_fn(theta[w], jax.device_put(batches[t][0][w], dev),
                       jax.device_put(batches[t][1][w], dev))
               for w in range(W)]
        losses.append(float(np.mean([float(l) for l, _ in out])))
        grads = [g for _, g in out]
        if fault != "nomean":
            grads = [mean(*grads)] * W
        if t == 0:
            first_grads = [jax.tree.map(lambda g: g.astype(jnp.float32), g)
                           for g in grads]
        new = [update(theta[w], vel[w], grads[w]) for w in range(W)]
        theta = [n[0] for n in new]
        vel = [n[1] for n in new]
    thetas = [jax.tree.map(lambda x: x.astype(jnp.float32), t) for t in theta]
    return losses, first_grads, thetas
