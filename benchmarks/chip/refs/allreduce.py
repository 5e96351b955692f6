"""Plain reference of the training algorithm of a cell whose traffic has
``method="allreduce"``: the paper's Algorithm 1 baseline with the Nesterov step
of its Algorithm 5. W replicas of one model start from the same weights; each
takes the gradient on its own rows, every replica takes the mean of all W
gradients, and each steps with it:

    g       = mean_i grad loss(theta_i; rows of worker i)
    v_i'    = mu * v_i - lr * g
    theta_i' = theta_i - lr * g + mu * v_i'

Since every replica starts from the same weights and steps with the same g,
the W replicas are one tree at every step, exactly; the reference holds that
one tree and one velocity. It takes the W gradients one worker at a time at
the current weights, each added to a running sum in one buffer and dropped
before the next is taken, and divides the sum by W: ``((g_1 + g_2) + ...) / W``,
the order of Python's ``sum(gs) / W``, so the mean is the same number as the
one a W-replica form computes.

Memory: with P the size of one replica in the computing dtype, it holds the
weights, the velocity, the running sum and one worker's gradient, 4 P, and at
most about 6 P with what the runtime keeps, above the weights it is handed,
whatever W is; plus one worker's activations at ``highest`` precision. With the
gradient mean left out (``fault="nomean"``, calibration only) the replicas
differ, and it holds W weights and W velocities, 2 W P, and one gradient.

It imports nothing of the system under test and takes nothing it made: the
weights come from the reference model's own ``init`` on the seed. The harness
finds it by the traffic's ``method``; another algorithm is another file of
``refs/`` with the same ``train``.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from compare import leaf_norms


def train(model, cfg: dict, traffic: dict, params0, batches, seed: int, *,
          dtype=jnp.float32, steps: int = 3, fault: str = "", devices=None):
    """Runs ``steps`` steps of the reference from ``params0`` on ``batches``
    (one ``(tokens, labels)`` pair of ``[W, B, S]`` arrays per step).

    Returns ``(losses, grads, thetas)``: the mean loss over workers at each
    step, the leaf norms (``compare.leaf_norms``, float32 ``[n_leaves]``) of
    each worker's first gradient as its optimizer gets it, and each worker's
    parameters after the last step (float32 trees). Where the mean is taken
    the W entries of ``grads`` and of ``thetas`` are one and the same object.
    ``params0`` is left as it was handed in. ``fault`` plants a fault for
    calibration: ``"half"`` takes the loss over the first half of each
    worker's rows only, ``"nomean"`` leaves the gradient mean out. ``seed`` is
    unused: the algorithm draws nothing.
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

    def grad(theta, t, w):
        loss, g = grad_fn(theta, jax.device_put(batches[t][0][w], dev),
                          jax.device_put(batches[t][1][w], dev))
        return float(loss), g

    # a copy even in float32: the update donates what it steps, and params0
    # stays the caller's
    fresh = jax.jit(lambda t: jax.tree.map(lambda x: jnp.copy(x.astype(dtype)), t))
    add = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g), donate_argnums=0)
    divide = jax.jit(lambda a: jax.tree.map(lambda x: x / W, a), donate_argnums=0)

    @partial(jax.jit, donate_argnums=(0, 1))
    def update(th, v, g):
        v_new = jax.tree.map(lambda v, g: mu * v - lr * g, v, g)
        th_new = jax.tree.map(lambda x, g, v: x - lr * g + mu * v, th, g, v_new)
        return th_new, v_new

    def norms(g):
        return np.asarray(leaf_norms(g))

    # every ``del g`` drops a gradient before the next one is taken
    losses, first = [], []
    if fault == "nomean":
        theta = [fresh(params0) for _ in range(W)]
        vel = [jax.tree.map(jnp.zeros_like, th) for th in theta]
        for t in range(steps):
            step_losses = []
            for w in range(W):
                loss, g = grad(theta[w], t, w)
                step_losses.append(loss)
                if t == 0:
                    first.append(norms(g))
                theta[w], vel[w] = update(theta[w], vel[w], g)
                del g
            losses.append(float(np.mean(step_losses)))
        thetas = [jax.tree.map(lambda x: x.astype(jnp.float32), th) for th in theta]
        return losses, first, thetas

    theta = fresh(params0)
    vel = jax.tree.map(jnp.zeros_like, theta)
    for t in range(steps):
        step_losses, total = [], None
        for w in range(W):
            loss, g = grad(theta, t, w)
            step_losses.append(loss)
            total = g if total is None else add(total, g)
            del g
        losses.append(float(np.mean(step_losses)))
        g = divide(total)
        if t == 0:
            first = [norms(g)] * W
        theta, vel = update(theta, vel, g)
        del g
    return losses, first, [jax.tree.map(lambda x: x.astype(jnp.float32), theta)] * W
