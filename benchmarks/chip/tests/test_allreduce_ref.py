"""CPU tests of the all-reduce reference, ``refs/allreduce.train``: its one
replica gives the numbers of the form that held all W replicas, bit for bit,
and it holds at most 6 P above the weights it is handed, whatever W is."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402

import feed       # noqa: E402
from compare import leaf_norms  # noqa: E402
from refs import allreduce, dense  # noqa: E402
from test_chipbench_run import TINY, TRAFFIC  # noqa: E402


def w_replica_train(model, cfg, traffic, params0, batches, seed, *,
                    dtype=jnp.float32, steps=3, fault="", devices=None):
    """The reference as it held all W replicas, kept as the oracle: W weight
    and velocity trees, W gradients alive at once, their mean in one call."""
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


@pytest.fixture(scope="module")
def params0():
    return jax.jit(lambda k: dense.init(TINY, k))(jax.random.PRNGKey(2**31 + 7))


@pytest.mark.parametrize("case", ["mean", "half", "nomean", "bfloat16"])
@pytest.mark.parametrize("W", [2, 3])
def test_one_replica_gives_the_w_replica_numbers(params0, W, case):
    traffic = {**TRAFFIC, "workers": W}
    batches = [tuple(jnp.asarray(x) for x in b)
               for b in feed.batches(2**31 + 11, traffic, TINY["vocab_size"])[:3]]
    kw = {"steps": 3, "fault": case if case in ("half", "nomean") else "",
          "dtype": jnp.bfloat16 if case == "bfloat16" else jnp.float32}
    with jax.default_matmul_precision("highest"):
        want = w_replica_train(dense, TINY, traffic, params0, batches, 0, **kw)
        got = allreduce.train(dense, TINY, traffic, params0, batches, 0, **kw)
    assert not any(x.is_deleted() for x in jax.tree.leaves(params0))
    assert got[0] == want[0]
    assert len(got[1]) == len(got[2]) == W
    for w in range(W):
        assert np.array_equal(got[1][w], np.asarray(leaf_norms(want[1][w])))
        for a, b in zip(jax.tree.leaves(got[2][w]), jax.tree.leaves(want[2][w])):
            assert a.dtype == b.dtype == jnp.float32
            assert np.array_equal(np.asarray(a), np.asarray(b))
    if case != "nomean":
        # the replicas are one tree, and the gradient mean moved every leaf
        assert got[2][0] is got[2][W - 1]
        assert np.all(got[1][0] > 0)


MEASURE = """
import json, resource, sys
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, sys.argv[2])
from refs import allreduce

N = 50_000_000          # P = 200 MB of float32
W = int(sys.argv[1])


class Toy:
    @staticmethod
    def loss(params, cfg, tokens, labels):
        scale = 1e-3 * (1.0 + jnp.mean(tokens.astype(jnp.float32)))
        return scale * (jnp.sum(params["a"] ** 2) + jnp.sum(params["b"] ** 2))


params0 = jax.jit(lambda: {"a": jnp.full((N // 2,), 0.5, jnp.float32),
                           "b": jnp.full((N // 2,), -0.5, jnp.float32)})()
jax.block_until_ready(params0)
tokens = np.arange(W * 2 * 8, dtype=np.int32).reshape(W, 2, 8)
batches = [(tokens + t, tokens) for t in range(3)]
before = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
losses, grads, thetas = allreduce.train(
    Toy, {}, {"workers": W, "lr": 0.01, "momentum": 0.9}, params0, batches, 0,
    steps=3)
jax.block_until_ready(thetas)
after = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print(json.dumps({"above_kib": after - before, "P_kib": N * 4 / 1024,
                  "moved": float(thetas[0]["a"][0] - 0.5)}))
"""


@pytest.mark.parametrize("W", [2, 4])
def test_reference_holds_at_most_six_replicas_above_its_weights(W):
    """Peak RSS of a process that runs the reference on a 50M-parameter toy,
    over the peak before the call: at most 6 P, whatever W is."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    p = subprocess.run([sys.executable, "-c", MEASURE, str(W), str(CHIP)],
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["moved"] != 0.0
    assert r["above_kib"] <= 6 * r["P_kib"], r["above_kib"] / r["P_kib"]
