#!/usr/bin/env python3
"""Bring-up smoke test: the gossip trainer on a TPU, end to end.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # one replica per chip on a 2x2 host

One chip — the sim engine with W stacked replicas of full-width xlstm_125m
(12 layers, d_model 768, vocab 50,304: 211,971,880 f32 parameters), driven
through ``repro.launch.train.run``, per-worker batch 4 x seq 1024:

1. train: elastic gossip, 8 steps. Every loss is finite, an exchange fired,
   the wire moved bytes, and the compiled step holds ``tpu_custom_call`` —
   the Pallas kernel ran, not its jnp oracle;
2. kernel: one fused elastic-NAG update of the trained plane through the
   Pallas kernel against ``repro.kernels.ref`` (max |diff| <= ``TOL``);
3. q8: the same run with the q8 codec for 4 steps, then q8 encode/decode of
   the plane through the kernels against the oracles.

Four chips — the dist engine, W = 4, one replica per chip gossiping over
ICI, then the paper's allreduce baseline, a few steps each. Every replica
plane buffer has one ``[1, N]`` shard on each device, the gossip step holds
a ``collective-permute`` (the allreduce step an ``all-reduce``), and every
loss is finite. After the gossip run, one fused elastic-NAG update through
the Pallas kernel on each device's ``[1, N]`` shard (as the dist engine
runs it) against the oracle on the whole plane (max |diff| <= ``TOL``).
The host must hold exactly the chips asked for.

Earlier lines report compile seconds, steady step seconds and the device's
``peak_bytes_in_use``. The last line of stdout is the JSON result. A failed
check exits non-zero before it; without a TPU the script refuses to run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
from repro.comm import codec_seeds                     # noqa: E402
from repro.common.cache import enable_compile_cache    # noqa: E402
from repro.common.config import ProtocolConfig         # noqa: E402
from repro.kernels import ops, ref                     # noqa: E402
from repro.launch import train                         # noqa: E402

ARCH = "xlstm_125m"
# stacked replicas on one 16 GB chip: the compile rehearsal of the sim step
# needs ~20.2 GB at W=4 and ~10.1 GB at W=2 (memory_analysis, v5e)
SIM_WORKERS = 2
PER_WORKER, SEQ = 4, 1024
TOL = 1e-5                  # kernel vs oracle, max abs diff of f32 planes
ETA, MU = 0.01, 0.9         # the kernel-vs-oracle update's lr and momentum
Q8_FLIP_FRAC = 1e-6         # q8 values may differ by one step this rarely
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

compile_s = [0.0]           # backend compile seconds so far, all programs


def _on_duration(event, duration, **kw):
    if event == BACKEND_COMPILE:
        compile_s[0] += duration


def check(cond, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def report(phase: str, **fields) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    fields["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    print(json.dumps({"phase": phase, **fields}), flush=True)


def run_train(phase: str, *, steps: int, workers: int, **kw):
    """One ``repro.launch.train.run``; checks its losses, exchanges and wire
    bytes and reports its times. Returns (trainer, state)."""
    c0, t0 = compile_s[0], time.perf_counter()
    trainer, state, hist = train.run(
        ARCH, reduced=False, steps=steps, tau=0, alpha=0.5,
        workers=workers, global_batch=workers * PER_WORKER, seq=SEQ,
        lr=1e-3, log_every=1, seed=0, **kw)
    wall = time.perf_counter() - t0
    losses = [r["loss"] for r in hist]
    check(len(hist) == steps, f"{phase}: {len(hist)} logged steps of {steps}")
    check(bool(np.all(np.isfinite(losses))), f"{phase}: losses {losses}")
    if kw.get("method") != "allreduce":
        check(any(r["fired"] for r in hist), f"{phase}: no exchange fired")
    check(hist[-1]["comm_mb"] > 0, f"{phase}: no bytes on the wire")
    # steady: every step but the first of each program (fired or not)
    seen, steady = set(), []
    for r in hist:
        if r["fired"] in seen:
            steady.append(r["step_s"])
        seen.add(r["fired"])
    report(phase, workers=workers, steps=steps, wall_s=wall,
           compile_s=compile_s[0] - c0, first_step_s=hist[0]["step_s"],
           steady_step_s=float(np.median(steady)) if steady else None,
           step_s=[r["step_s"] for r in hist], losses=losses,
           fired=[r["fired"] for r in hist], comm_mb=hist[-1]["comm_mb"])
    return trainer, state


def compiled_text(jitted, *args) -> str:
    """HLO of a program the run already compiled (a cache lookup)."""
    t0 = time.perf_counter()
    text = jitted.lower(*args).compile().as_text()
    print(json.dumps({"hlo_lookup_s": time.perf_counter() - t0}), flush=True)
    return text


def sim_step_holds_kernel(phase: str, trainer, state) -> None:
    x = jnp.zeros((SIM_WORKERS, PER_WORKER, SEQ), jnp.int32)
    n = compiled_text(trainer._backend.sim._step_fn, state, x, x).count(
        "tpu_custom_call")
    check(n > 0, f"{phase}: compiled step holds no tpu_custom_call")
    report(phase + "_hlo", tpu_custom_call=n)


def kernel_update(theta, peer, v, g, coef):
    return ops.fused_flat_elastic_nag_update(theta, peer, v, g, coef, ETA, MU,
                                             use_kernel=True)


def kernel_vs_oracle(phase: str, state, update=kernel_update,
                     sharding=None) -> None:
    """One fused elastic-NAG update of the trained plane through ``update``
    (the Pallas kernel) against the oracle; ``sharding`` places the random
    peer and gradient planes like the trained one."""
    theta, v = state.theta["float32"], state.opt.mu["float32"]
    W = theta.shape[0]
    place = (lambda x: x) if sharding is None else (
        lambda x: jax.lax.with_sharding_constraint(x, sharding))

    @jax.jit
    def compare(theta, v, key):
        kp, kg = jax.random.split(key)
        peer = place(theta + 0.01 * jax.random.normal(kp, theta.shape))
        g = place(0.1 * jax.random.normal(kg, theta.shape))
        coef = jnp.linspace(0.5, 0.25, W)
        tk, vk = update(theta, peer, v, g, coef)
        tr, vr = ref.fused_flat_elastic_nag_update(theta, peer, v, g, coef, ETA, MU)
        return (jnp.max(jnp.abs(tk - tr)), jnp.max(jnp.abs(vk - vr)),
                jnp.max(jnp.abs(tr - theta)))

    d_theta, d_v, moved = (float(a) for a in compare(theta, v, jax.random.PRNGKey(1)))
    report(phase, plane=list(theta.shape), tol=TOL,
           max_abs_diff_theta=d_theta, max_abs_diff_v=d_v, max_abs_update=moved)
    check(d_theta <= TOL and d_v <= TOL,
          f"{phase}: fused kernel vs oracle: {d_theta}, {d_v} > {TOL}")
    check(moved > 100 * TOL, f"{phase}: the update moved the plane only {moved}")


def q8_vs_oracle(state) -> None:
    block = ProtocolConfig().codec_block
    theta = state.theta["float32"]
    W, n = theta.shape

    @jax.jit
    def compare(theta):
        seeds = codec_seeds(0, jnp.arange(W))
        vk, sk = ops.q8_encode(theta, seeds, block=block, use_kernel=True)
        vr, sr = ref.q8_encode(theta, seeds, block=block)
        dk = ops.q8_decode(vk, sk, n, block=block, use_kernel=True)
        dr = ref.q8_decode(vk, sk, n, block=block)
        step = jnp.abs(vk.astype(jnp.int32) - vr.astype(jnp.int32))
        return (jnp.max(jnp.abs(sk - sr)), jnp.max(step),
                jnp.mean((step > 0).astype(jnp.float32)),
                jnp.max(jnp.abs(dk - dr)))

    d_scale, d_step, flips, d_dec = (float(a) for a in compare(theta))
    report("q8_vs_oracle", plane=[W, n], block=block, max_abs_diff_scale=d_scale,
           max_value_step=d_step, value_flip_frac=flips, max_abs_diff_decode=d_dec)
    check(d_scale == 0.0, f"q8 scales differ from the oracle by {d_scale}")
    check(d_step <= 1 and flips <= Q8_FLIP_FRAC,
          f"q8 values: max step {d_step}, flip fraction {flips}")
    check(d_dec <= TOL, f"q8 decode vs oracle: {d_dec} > {TOL}")


def dist_kernel_vs_oracle(phase: str, trainer, state) -> None:
    """The fused kernel on each device's local ``[1, N]`` shard, as the dist
    engine runs it, against the oracle on the whole sharded plane."""
    dt = trainer._backend.trainer
    spec = dt.buf_specs["float32"]
    local = jax.shard_map(kernel_update, mesh=dt.mesh,
                          in_specs=(spec,) * 4 + (P(spec[0]),),
                          out_specs=(spec, spec), check_vma=False)
    kernel_vs_oracle(phase, state, local, NamedSharding(dt.mesh, spec))


def dist(phase: str, method: str, collective: str) -> None:
    devices = jax.devices()
    W = len(devices)
    # p = 1: every step of the gossip run is an exchange round, so one
    # program (train + gossip) serves the whole run
    trainer, state = run_train(phase, engine="dist", method=method, p=1.0,
                               workers=W, steps=5)
    if method != "allreduce":
        dist_kernel_vs_oracle(phase + "_kernel_vs_oracle", trainer, state)
    for what, bufs in (("theta", state.theta), ("velocity", state.opt.mu)):
        for name, buf in bufs.items():
            shards = buf.addressable_shards
            check(len(shards) == W
                  and {s.device for s in shards} == set(devices)
                  and all(s.data.shape == (1, buf.shape[1]) for s in shards),
                  f"{phase}: {what}[{name}] shards "
                  f"{[(str(s.device), s.data.shape) for s in shards]}")
    backend = trainer._backend
    batch = {k: jnp.zeros((W, PER_WORKER, SEQ), jnp.int32)
             for k in ("tokens", "labels")}
    if method == "allreduce":
        text = compiled_text(backend.ts, state, batch, jnp.zeros(()))
    else:
        text = compiled_text(backend.tg, state, batch,
                             jnp.ones((W,), jnp.float32), jnp.int32(0))
    n = text.count(collective + "(") + text.count(collective + "-start(")
    check(n > 0, f"{phase}: compiled step holds no {collective}")
    theta = state.theta["float32"]
    report(phase + "_hlo", collective=collective, count=n,
           shard_shape=[1, int(theta.shape[1])],
           devices=[str(s.device) for s in theta.addressable_shards])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX found {devices[0].platform!r} devices")
    check(len(devices) == args.chips,
          f"--chips {args.chips} needs exactly {args.chips} chips, "
          f"found {len(devices)}")
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    if args.chips == 4:
        dist("dist_elastic_gossip", "elastic_gossip", "collective-permute")
        dist("dist_allreduce", "allreduce", "all-reduce")
    else:
        trainer, state = run_train("sim_train", engine="sim",
                                   method="elastic_gossip", p=0.5,
                                   workers=SIM_WORKERS, steps=8)
        sim_step_holds_kernel("sim_train", trainer, state)
        kernel_vs_oracle("kernel_vs_oracle", state)
        del trainer, state
        trainer, state = run_train("sim_q8", engine="sim",
                                   method="elastic_gossip", p=0.5,
                                   workers=SIM_WORKERS, steps=4, codec="q8")
        sim_step_holds_kernel("sim_q8", trainer, state)
        q8_vs_oracle(state)
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {"platform": d[0].platform,
                                             "kind": d[0].device_kind,
                                             "count": len(d)}}), flush=True)


if __name__ == "__main__":
    main()
