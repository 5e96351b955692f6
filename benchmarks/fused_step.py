"""Fused flat-plane step vs per-bucket reference path: steps/sec + modeled
HBM bytes/step on both engines, plus the RESIDENT-vs-reflatten update-phase
micro-benchmark. Writes ``BENCH_fused_step.json`` at the repo root (the bench
trajectory file the roadmap's perf claims anchor to).

What is modeled: the post-gradient *update phase* of one communication-firing
step, in units of the stacked parameter bytes B = W * bytes(one replica).
Gradient computation and (sim engine) the mixing einsum are identical on both
paths and excluded. Streams counted, per path:

  sim  unfused  comm-delta 3B + velocity 3B + param-update 4B + add 3B = 13B
  sim  fused    read theta/theta_comm/v/g, write theta'/v'            =  6B
  dist unfused  exchange-apply 3B + delta 3B + velocity 3B + update 4B
                + add 3B                                              = 16B
  dist fused    exchange-peer 3B + one fused pass 6B                  =  9B

Measured: wall-clock steps/sec through the GossipTrainer facade with
``fused_update`` on/off (elastic gossip, p=1 so every step communicates).
Since the flat-resident FlatState redesign BOTH paths run on the resident
``[W, total]`` buffers — the per-step flatten/unflatten concat copies that
made the PR-2 fused sim path measure SLOWER than unfused on XLA:CPU are
structurally gone, and ``update_phase.resident`` vs
``update_phase.reflatten`` isolates exactly that cost: the same fused update
applied to resident buffers vs through a per-step
flatten -> kernel -> unflatten round trip (the old layout). On this CPU
container the fused path dispatches to the jnp reference oracle; the Pallas
kernel itself is exercised in interpret mode and parity-checked against the
oracle (``kernel_interpret_parity_ok``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(REPO, "BENCH_fused_step.json")

WORKERS = 4

SIM_MODELED = {"fused": 6.0, "unfused": 13.0}     # in units of B, see docstring
DIST_MODELED = {"fused": 9.0, "unfused": 16.0}


def _measure_sim(fused: bool, steps: int, hidden: int):
    from repro.api import GossipTrainer
    from repro.common.config import OptimizerConfig, ProtocolConfig
    from repro.models import simple

    proto = ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                           moving_rate=0.5, topology="uniform")
    params0, _ = simple.init_mlp(jax.random.PRNGKey(0), in_dim=784, hidden=hidden,
                                 depth=3, num_classes=10)

    def loss_fn(p, x, y):
        return simple.xent_loss(simple.mlp_logits(p, x), y)

    trainer = GossipTrainer(engine="sim", protocol=proto,
                            optimizer=OptimizerConfig(name="nag", learning_rate=1e-3,
                                                      momentum=0.99),
                            loss_fn=loss_fn, num_workers=WORKERS,
                            fused_update=fused)
    state = trainer.init_state(0, params=params0)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(WORKERS, 32, 784).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, (WORKERS, 32)))
    for _ in range(3):   # warmup / compile
        state, m = trainer.step(state, (x, y))
    jax.block_until_ready(state.theta)
    t0 = time.time()
    for _ in range(steps):
        state, m = trainer.step(state, (x, y))
    jax.block_until_ready(state.theta)
    pb = trainer.comm_cost().bytes_per_event   # = bytes of one replica
    return steps / (time.time() - t0), int(pb)


def _measure_dist(steps: int):
    """Per-path steps/sec on the 8-worker shard_map engine; runs in a
    subprocess so this process keeps 1 visible device (see tests/conftest)."""
    code = textwrap.dedent("""
        import json, time
        import jax, jax.numpy as jnp, numpy as np
        from repro.api import GossipTrainer
        from repro.common.config import MeshConfig, OptimizerConfig, ProtocolConfig
        from repro.configs import get_reduced
        from repro.launch.mesh import make_worker_mesh

        STEPS = %d
        mcfg = MeshConfig(data=4, model=1, pods=2, workers_per_pod=4)
        mesh = make_worker_mesh(mcfg)
        W = mcfg.num_workers
        model_cfg = get_reduced("tinyllama_1_1b")   # batch axes/shapes only
        V, D = 256, 64

        def init_fn(key):
            k1, k2 = jax.random.split(key)
            return {"emb": 0.1 * jax.random.normal(k1, (V, D)),
                    "out": 0.1 * jax.random.normal(k2, (D, V))}

        axes = {"emb": (None, None), "out": (None, None)}

        def loss_fn(params, batch):
            h = params["emb"][batch["tokens"]].mean(axis=1)
            logits = h @ params["out"]
            lab = batch["labels"][:, 0]
            return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(lab.shape[0]), lab])

        S, pw = 32, 2
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, V, (W, pw, S))),
                 "labels": jnp.asarray(rng.randint(0, V, (W, pw, S)))}
        out = {"stacked_param_bytes": None}
        for fused in (True, False):
            proto = ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                                   moving_rate=0.5)
            tr = GossipTrainer(engine="dist", protocol=proto,
                               optimizer=OptimizerConfig(name="nag",
                                                         learning_rate=1e-3,
                                                         momentum=0.99),
                               mesh=mesh, mesh_cfg=mcfg, model_cfg=model_cfg,
                               init_fn=init_fn, params_axes=axes,
                               global_batch=W * pw, seq_len=S,
                               loss_fn=loss_fn, fused_update=fused)
            state = tr.init_state(0)
            for _ in range(2):   # warmup / compile
                state, m = tr.step(state, batch)
            jax.block_until_ready(state.theta)
            t0 = time.time()
            for _ in range(STEPS):
                state, m = tr.step(state, batch)
            jax.block_until_ready(state.theta)
            out["fused" if fused else "unfused"] = STEPS / (time.time() - t0)
            out["stacked_param_bytes"] = tr.comm_cost().bytes_per_event * W
        print("RESULT " + json.dumps(out))
    """ % steps)
    # eight virtual CPU devices: the child never reaches for an accelerator
    # (this process may already hold it)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def _measure_update_phase(steps: int, hidden: int):
    """Resident vs reflatten, update phase only (satellite of the FlatState
    redesign): the SAME fused elastic-NAG update applied (a) directly to the
    resident flat buffers — the engines' hot path — and (b) through a
    per-step flatten -> update -> unflatten round trip over the parameter
    pytree, i.e. the pre-FlatState layout. Identical math, identical output;
    the difference is purely the per-step concat/slice copies."""
    from repro.common.flat import FlatSpec
    from repro.kernels import ops
    from repro.models import simple

    params, _ = simple.init_mlp(jax.random.PRNGKey(0), in_dim=784, hidden=hidden,
                                depth=3, num_classes=10)
    stack = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (WORKERS,) + a.shape) + 0.0, params)
    spec = FlatSpec.build(stack, leading=1)
    bufs = spec.flatten(stack)
    peer_t = jax.tree.map(lambda a: a + 0.01, stack)
    peer_b = spec.flatten(peer_t)
    coef = jnp.full((WORKERS,), 0.5)

    @jax.jit
    def resident(theta, peer, v, g):
        return ops.fused_bufs_elastic_nag(theta, peer, v, g, coef, 1e-3, 0.9)

    @jax.jit
    def reflatten(theta_tree, peer_tree, v_tree, g_tree):
        # the PR-2 layout: state lives as a pytree, the fused update flattens
        # it per call and unflattens the result
        return ops.fused_tree_elastic_nag(theta_tree, peer_tree, v_tree, g_tree,
                                          coef, eta=1e-3, mu=0.9, spec=spec)

    def time_loop(fn, t0_args):
        args = t0_args
        out = fn(*args)          # warmup/compile
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        return steps / (time.time() - t0)

    zeros_b = jax.tree.map(jnp.zeros_like, bufs)
    ones_b = jax.tree.map(jnp.ones_like, bufs)
    zeros_t = jax.tree.map(jnp.zeros_like, stack)
    ones_t = jax.tree.map(jnp.ones_like, stack)
    return {"resident_steps_per_sec": round(time_loop(resident, (bufs, peer_b, zeros_b, ones_b)), 3),
            "reflatten_steps_per_sec": round(time_loop(reflatten, (stack, peer_t, zeros_t, ones_t)), 3)}


def _kernel_interpret_parity() -> bool:
    """Exercise the fused Pallas kernel in interpret mode vs the jnp oracle
    (what CI's quick profile is for)."""
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    t, p, v, g = (jax.random.normal(k, (WORKERS, 2048)) for k in ks)
    coef = jnp.linspace(0.0, 1.0, WORKERS)
    tk, vk = ops.fused_flat_elastic_nag_update(t, p, v, g, coef, 0.01, 0.9,
                                               use_kernel=True, interpret=True)
    tr_, vr_ = ops.fused_flat_elastic_nag_update(t, p, v, g, coef, 0.01, 0.9,
                                                 use_kernel=False)
    return (bool(jnp.allclose(tk, tr_, rtol=1e-6, atol=1e-6))
            and bool(jnp.allclose(vk, vr_, rtol=1e-6, atol=1e-6)))


def main(quick: bool = True) -> None:
    sim_steps = 60 if quick else 200
    dist_steps = 8 if quick else 50
    hidden = 128 if quick else 512

    result = {"workers": WORKERS, "kernel_interpret_parity_ok": _kernel_interpret_parity()}
    print("path,engine,steps_per_sec,modeled_hbm_bytes_per_step")

    sim = {}
    # two interleaved passes per path, best-of: the first measured path pays
    # one-time process warmup (allocator/page faults), which otherwise biases
    # the fused-vs-unfused comparison by more than the real gap
    for path in ("fused", "unfused"):
        best = 0.0
        for _ in range(2):
            sps, pb = _measure_sim(path == "fused", sim_steps, hidden)
            best = max(best, sps)
        B = pb * WORKERS
        sim[path] = {"steps_per_sec": round(best, 3),
                     "modeled_hbm_bytes_per_step": SIM_MODELED[path] * B}
        result["param_bytes_per_replica"] = pb
        result["stacked_param_bytes"] = B
        print(f"{path},sim,{best:.3f},{SIM_MODELED[path] * B:.0f}")
    result["sim"] = sim

    up = _measure_update_phase(max(50, sim_steps), hidden)
    result["update_phase"] = up
    print(f"resident,update_phase,{up['resident_steps_per_sec']:.3f},-")
    print(f"reflatten,update_phase,{up['reflatten_steps_per_sec']:.3f},-")

    dist_sps = _measure_dist(dist_steps)
    # the dist subprocess trains a small embedding model; modeled bytes stay
    # in units of ITS stacked param bytes, reported by the subprocess itself
    dist_B = dist_sps.pop("stacked_param_bytes")
    result["dist"] = {
        path: {"steps_per_sec": round(dist_sps[path], 3),
               "modeled_hbm_bytes_per_step": DIST_MODELED[path] * dist_B}
        for path in ("fused", "unfused")}
    for path in ("fused", "unfused"):
        print(f"{path},dist,{dist_sps[path]:.3f},{DIST_MODELED[path] * dist_B:.0f}")

    for eng in ("sim", "dist"):
        assert (result[eng]["fused"]["modeled_hbm_bytes_per_step"]
                <= result[eng]["unfused"]["modeled_hbm_bytes_per_step"]), eng
    assert result["kernel_interpret_parity_ok"]
    # the flat-resident acceptance: with the state resident, the fused sim
    # path no longer pays per-step flatten copies, so it must not lose to the
    # per-bucket reference path even on XLA:CPU (the PR-2 regression)
    result["sim_fused_ge_unfused"] = (
        result["sim"]["fused"]["steps_per_sec"]
        >= result["sim"]["unfused"]["steps_per_sec"])
    result["resident_speedup_vs_reflatten"] = round(
        up["resident_steps_per_sec"] / up["reflatten_steps_per_sec"], 3)

    result["modeled_notes"] = (
        "update-phase streams only, units of stacked param bytes B: "
        "sim fused 6B vs unfused 13B; dist fused 9B vs unfused 16B "
        "(gradient compute + sim mixing einsum excluded, identical on both paths)")
    result["measured_notes"] = (
        "flat-RESIDENT FlatState: both engines keep params/velocity as the "
        "[W,total] plane, so neither path re-flattens per step — the old "
        "XLA:CPU regression (fused slower than unfused due to per-step "
        "flatten concat copies) is closed; update_phase isolates that cost "
        "as resident vs reflatten steps/sec on the same fused update")
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"# wrote {OUT_PATH}")


def resident_main(quick: bool = True) -> None:
    """Standalone resident-vs-reflatten update-phase micro-bench (registered
    as ``fused_step_resident`` in benchmarks/run.py): prints both steps/sec
    without touching BENCH_fused_step.json — the full trajectory (incl. this
    section under ``update_phase``) is written by :func:`main`."""
    steps = 100 if quick else 500
    up = _measure_update_phase(steps, 128 if quick else 512)
    print("path,steps_per_sec")
    print(f"resident,{up['resident_steps_per_sec']:.3f}")
    print(f"reflatten,{up['reflatten_steps_per_sec']:.3f}")
    ratio = up["resident_steps_per_sec"] / up["reflatten_steps_per_sec"]
    print(f"# resident/reflatten speedup: {ratio:.2f}x")
    # the CI signal: operating resident must never lose to paying the
    # per-step flatten/unflatten round trip (it wins ~5-10x on this box)
    assert ratio >= 1.0, f"resident slower than reflatten ({ratio:.2f}x)"


if __name__ == "__main__":
    main(quick="--full" not in sys.argv)
