"""End-to-end training driver (single-controller), built on ``repro.api``.

The driver is protocol- AND engine-agnostic: it constructs a
:class:`repro.api.GossipTrainer` for any registered engine (``--engine
{sim,dist,async,...}``, resolved via ``repro.api.register_engine``) and calls
ONE method per step — ``trainer.step(state, batch)`` over the flat-resident
:class:`repro.api.FlatState` (params live as flat per-dtype buffers; the
driver's divergence diagnostics read ``state.theta`` directly and checkpoints
are written in the flat v2 format). Scheduling (fire/active/round polling and
the train vs. train+gossip program selection — or, for ``--engine async``,
the virtual-time event loop), communication-byte accounting and
checkpoint/schedule persistence all live inside the facade; protocol names
come from the registry, so a newly registered protocol is immediately
launchable with ``--method <name>``.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1_1b \
        --reduced --steps 50 --method elastic_gossip --p 0.25

    # heterogeneous fleet: 4x straggler under virtual-time async gossip
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1_1b \
        --reduced --steps 50 --engine async --time-model slow_node \
        --slow-factor 4 --workers 4 --p 0.25

    # same run with the telemetry plane armed (repro.obs): Perfetto timeline
    # + metrics JSONL, then `python -m repro.obs.report run.jsonl`
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1_1b \
        --reduced --steps 50 --engine async --workers 4 --p 0.25 \
        --trace run.json --metrics run.jsonl

On this CPU container it is exercised with reduced configs
(examples/quickstart.py, tests); on a real cluster the same driver drives the
production mesh.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import GossipTrainer, available_engines, available_protocols
from repro.comm import available_codecs
from repro.common.cache import enable_compile_cache
from repro.common.config import (FaultConfig, FleetConfig, HeteroConfig,
                                 MeshConfig, ObsConfig, OptimizerConfig,
                                 ProtocolConfig, ShardConfig)
from repro.faults import available_delay_models, available_fault_models
from repro.fleet import available_flow_controls
from repro.hetero import available_time_models
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.core.consensus import divergence_metrics
from repro.launch.mesh import make_host_mesh, make_worker_mesh
from repro.models import transformer as tr


def lm_batches(cfg, num_workers: int, per_worker: int, seq: int, seed: int = 0):
    """Worker-partitioned synthetic token stream (each worker gets a disjoint
    slice, the paper's data-parallel partitioning)."""
    from repro.data.synthetic import make_lm_tokens
    stream = make_lm_tokens(num_workers * 4_000_000 // max(1, num_workers // 8), cfg.vocab_size, seed)
    shard_len = len(stream) // num_workers
    step = 0
    while True:
        xs = []
        for w in range(num_workers):
            base = w * shard_len + (step * per_worker * (seq + 1)) % (shard_len - per_worker * (seq + 1))
            chunk = stream[base: base + per_worker * (seq + 1)].reshape(per_worker, seq + 1)
            xs.append(chunk)
        arr = np.stack(xs)
        batch = {"tokens": jnp.asarray(arr[..., :-1]), "labels": jnp.asarray(arr[..., 1:])}
        if cfg.audio is not None:
            batch["tokens"] = jnp.repeat(batch["tokens"][:, :, None], cfg.audio.num_codebooks, 2)
            batch["labels"] = jnp.repeat(batch["labels"][:, :, None], cfg.audio.num_codebooks, 2)
            batch["cond"] = jnp.zeros((num_workers, per_worker, cfg.audio.num_cond_tokens,
                                       cfg.d_model), jnp.float32)
        elif cfg.vlm is not None:
            batch["cond"] = jnp.zeros((num_workers, per_worker, cfg.vlm.num_image_tokens,
                                       cfg.vlm.image_embed_dim), jnp.float32)
        yield batch
        step += 1


def run(arch: str, *, reduced: bool, steps: int, method: str, p: float, tau: int,
        alpha: float, workers: int, global_batch: int, seq: int, lr: float,
        seed: int = 0, checkpoint_dir: str = "", log_every: int = 10,
        production_mesh: bool = False, multi_pod: bool = False,
        codec: str = "none", engine: str = "dist",
        time_model: str = "constant", mean_step_time: float = 1.0,
        sigma: float = 0.25, slow_worker: int = 0, slow_factor: float = 4.0,
        fault_model: str = "none", fault_rate: float = 0.0,
        fault_frac: float = 0.0, delay_model: str = "none",
        delay: float = 0.0, timeout: float = 0.0,
        partition: int = 1, flow_control: str = "none",
        plane: str = "device", token_capacity: float = 20.0,
        token_rate: float = 1.0, token_threshold: float = 10.0,
        shard: int = 1, trace: str = "", metrics: str = "",
        sample_every: int = 1):
    cfg = get_reduced(arch) if reduced else get_config(arch)
    proto = ProtocolConfig(method=method, moving_rate=alpha,
                           comm_probability=p if not tau else 0.0,
                           comm_period=tau, codec=codec)
    opt = OptimizerConfig(name="nag", learning_rate=lr, momentum=0.9)
    # fault plane (repro.faults): only construct a FaultConfig when something
    # is actually enabled, so the default path keeps the exact no-faults
    # engine behaviour (bit-for-bit — tests/test_faults.py)
    faults = None
    if fault_model != "none" or delay_model != "none" or timeout > 0:
        faults = FaultConfig(fault_model=fault_model, fault_rate=fault_rate,
                             fault_frac=fault_frac, delay_model=delay_model,
                             delay=delay, timeout=timeout, seed=seed)
    # fleet plane (repro.fleet): only construct a FleetConfig when something
    # is enabled — the default path keeps every engine trace bit-identical
    fleet = None
    if partition != 1 or flow_control != "none" or plane != "device":
        fleet = FleetConfig(partition=partition, flow_control=flow_control,
                            plane=plane, token_capacity=token_capacity,
                            token_rate=token_rate,
                            token_threshold=token_threshold, seed=seed)
        if engine == "dist":
            raise ValueError(
                'engine="dist" does not take the fleet plane '
                "(--partition/--flow-control/--plane); use --engine sim or "
                "--engine async")
    # sharded plane (repro.shard): only construct a ShardConfig when the
    # plane is actually split — None keeps every engine trace bit-identical
    shard_cfg = ShardConfig(n_shards=shard) if shard != 1 else None
    # telemetry plane (repro.obs): only construct an ObsConfig when an export
    # path is requested — obs=None keeps every engine bit-identical (the
    # inert-anchor contract shared with faults/fleet/shard above)
    obs_cfg = None
    if trace or metrics:
        obs_cfg = ObsConfig(trace_path=trace, metrics_path=metrics,
                            sample_every=sample_every)

    def init_fn(key):
        params, _ = tr.init_lm(key, cfg)
        return params

    if engine == "dist":
        if faults is not None:
            raise ValueError(
                'engine="dist" does not support fault injection; use '
                '--engine sim or --engine async for --fault-model/'
                '--delay-model runs')
        if production_mesh:
            mesh_cfg = MeshConfig(data=16, model=16, pods=2 if multi_pod else 1,
                                  workers_per_pod=workers)
            mesh = make_worker_mesh(mesh_cfg)
        else:
            mesh_cfg = MeshConfig(data=len(jax.devices()), model=1, pods=1,
                                  workers_per_pod=workers)
            mesh = make_host_mesh(workers)
        _, axes = tr.abstract_lm(cfg)
        trainer = GossipTrainer(
            engine="dist", protocol=proto, optimizer=opt,
            mesh=mesh, mesh_cfg=mesh_cfg, model_cfg=cfg, init_fn=init_fn,
            params_axes=axes, global_batch=global_batch, seq_len=seq, seed=seed,
            shard=shard_cfg, obs=obs_cfg)
        num_workers = mesh_cfg.num_workers
        as_batch = lambda b: b
    else:
        # stacked-replica engines (sim / async) on the same transformer loss;
        # engine="async" additionally takes the heterogeneity config — each
        # facade step then processes one virtual-time event window
        num_workers = workers
        # validate W against available memory UP FRONT (repro.fleet.memory):
        # one clear error here beats an OOM deep inside plane allocation or
        # the first jitted step. The estimate is plane-aware — plane="host"
        # (async) is bounded by host RAM at 2 replica-sizes per worker.
        from repro.fleet import validate_fleet_memory
        abstract, _ = tr.abstract_lm(cfg)
        replica_bytes = sum(
            int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(abstract))
        validate_fleet_memory(num_workers, replica_bytes, plane,
                              what=f"arch {arch!r}", n_shards=shard)
        hetero = HeteroConfig(time_model=time_model, mean_step_time=mean_step_time,
                              sigma=sigma, slow_worker=slow_worker,
                              slow_factor=slow_factor, seed=seed)

        def loss_fn(params, x, y):
            return tr.lm_loss(params, cfg, x, y)[0]   # scalar (drop aux dict)

        trainer = GossipTrainer(
            engine=engine, protocol=proto, optimizer=opt, loss_fn=loss_fn,
            num_workers=num_workers, init_fn=init_fn, seed=seed,
            hetero=hetero if engine == "async" else None, faults=faults,
            fleet=fleet, shard=shard_cfg, obs=obs_cfg)
        as_batch = lambda b: (b["tokens"], b["labels"])
    state = trainer.init_state(seed)
    batches = lm_batches(cfg, num_workers, global_batch // num_workers,
                         seq, seed)
    history = []
    t0 = time.time()
    for i in range(steps):
        batch = as_batch(next(batches))
        t_step = time.perf_counter()
        state, m = trainer.step(state, batch)
        if i % log_every == 0 or i == steps - 1:
            # a logged step is timed to the end of its device work
            jax.block_until_ready(state.theta)
            step_s = time.perf_counter() - t_step
            # diagnostics read the resident flat plane directly (identical
            # numbers to the per-leaf tree: padding is zeros on both sides of
            # the consensus difference) — no pytree views on the log path
            div = divergence_metrics(state.theta)
            rec = {"step": i, "step_s": step_s, "loss": float(m["loss"]),
                   "consensus_rel": float(div["consensus_rel"]),
                   "fired": bool(m["fired"]),
                   "comm_mb": round(float(m["comm_bytes"]) / 1e6, 3)}
            if "virtual_time" in m:
                rec["virtual_time"] = round(float(m["virtual_time"]), 3)
                rec["window_size"] = int(m["window_size"])
            history.append(rec)
            print(json.dumps(rec))
        if checkpoint_dir and (i + 1) % 50 == 0:
            trainer.save_checkpoint(f"{checkpoint_dir}/step_{i+1}.npz", state,
                                    meta={"arch": arch, "step": i + 1})
    print(f"trained {steps} steps in {time.time()-t0:.1f}s; "
          f"final loss {history[-1]['loss']:.4f}")
    exported = trainer.export_obs()
    for kind, path in exported.items():
        print(f"wrote {kind} -> {path}")
    if "metrics" in exported:
        hint = f"python -m repro.obs.report {exported['metrics']}"
        if "trace" in exported:
            hint += f" --trace {exported['trace']}"
        print(f"summarize: {hint}")
    elif "trace" in exported:
        print(f"view: load {exported['trace']} at https://ui.perfetto.dev")
    return trainer, state, history


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--method", default="elastic_gossip",
                    choices=available_protocols())
    ap.add_argument("--engine", default="dist", choices=available_engines(),
                    help="training engine (repro.api engine registry)")
    ap.add_argument("--codec", default="none", choices=available_codecs(),
                    help="gossip-compression codec on the wire (repro.comm)")
    ap.add_argument("--time-model", default="constant",
                    choices=available_time_models(),
                    help='compute-time model for --engine async (repro.hetero)')
    ap.add_argument("--mean-step-time", type=float, default=1.0)
    ap.add_argument("--sigma", type=float, default=0.25,
                    help="lognormal straggler log-space std")
    ap.add_argument("--slow-worker", type=int, default=0)
    ap.add_argument("--slow-factor", type=float, default=4.0)
    # fault-injection plane (repro.faults) — unknown names fail at parse time
    # with the registered list, same contract as --method/--codec
    ap.add_argument("--fault-model", default="none",
                    choices=available_fault_models(),
                    help="message-level fault model on the gossip wire "
                         "(repro.faults registry)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="per-(worker,step) drop/corrupt probability")
    ap.add_argument("--fault-frac", type=float, default=0.0,
                    help="fraction of Byzantine workers (byzantine_* models)")
    ap.add_argument("--delay-model", default="none",
                    choices=available_delay_models(),
                    help='network-delay model for --engine async '
                         '(repro.faults registry)')
    ap.add_argument("--delay", type=float, default=0.0,
                    help="delay-model scale (mean / constant, virtual time)")
    ap.add_argument("--timeout", type=float, default=0.0,
                    help="per-exchange timeout before skip-and-retry "
                         "(0 = wait forever)")
    # mega-fleet plane (repro.fleet) — unknown flow-control names fail at
    # parse time with the registered list, same contract as --method/--codec
    ap.add_argument("--partition", type=int, default=1,
                    help="split each exchange into 1/P of the flat plane "
                         "(hash-scheduled contiguous chunk, repro.fleet)")
    ap.add_argument("--flow-control", default="none",
                    choices=available_flow_controls(),
                    help="token-account initiation throttling "
                         "(repro.fleet registry)")
    ap.add_argument("--plane", default="device", choices=["device", "host"],
                    help='FlatState residency: "host" keeps the [W, total] '
                         "plane in host RAM (async engine only) and streams "
                         "event-window rows to device")
    # sharded flat plane (repro.shard): big-model gossip with 1/N of every
    # buffer (and 1/N of the gossip wire) per device
    ap.add_argument("--shard", type=int, default=1,
                    help="split the flat plane into N device shards "
                         "(repro.shard): per-device plane memory and gossip "
                         "wire bytes scale with 1/N; engine='dist' realizes "
                         "the shards over the ('fsdp','model') mesh axes")
    # telemetry plane (repro.obs): export paths arm the host-side observer;
    # leaving both unset keeps the run bit-identical (inert anchor)
    ap.add_argument("--trace", default="",
                    help="write a Perfetto/Chrome-trace JSON timeline here "
                         "(load at https://ui.perfetto.dev; repro.obs)")
    ap.add_argument("--metrics", default="",
                    help="stream per-step metrics JSONL here (summarize with "
                         "python -m repro.obs.report)")
    ap.add_argument("--sample-every", type=int, default=1,
                    help="record trace events / metrics rows every k-th step")
    ap.add_argument("--token-capacity", type=float, default=20.0)
    ap.add_argument("--token-rate", type=float, default=1.0)
    ap.add_argument("--token-threshold", type=float, default=10.0,
                    help="randomized_token_account aggressiveness threshold")
    ap.add_argument("--p", type=float, default=0.25)
    ap.add_argument("--tau", type=int, default=0)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    a = ap.parse_args()
    enable_compile_cache()
    run(a.arch, reduced=a.reduced, steps=a.steps, method=a.method, p=a.p, tau=a.tau,
        alpha=a.alpha, workers=a.workers, global_batch=a.global_batch, seq=a.seq,
        lr=a.lr, checkpoint_dir=a.checkpoint_dir,
        production_mesh=a.production_mesh, multi_pod=a.multi_pod, codec=a.codec,
        engine=a.engine, time_model=a.time_model,
        mean_step_time=a.mean_step_time, sigma=a.sigma,
        slow_worker=a.slow_worker, slow_factor=a.slow_factor,
        fault_model=a.fault_model, fault_rate=a.fault_rate,
        fault_frac=a.fault_frac, delay_model=a.delay_model,
        delay=a.delay, timeout=a.timeout,
        partition=a.partition, flow_control=a.flow_control, plane=a.plane,
        token_capacity=a.token_capacity, token_rate=a.token_rate,
        token_threshold=a.token_threshold, shard=a.shard,
        trace=a.trace, metrics=a.metrics, sample_every=a.sample_every)


if __name__ == "__main__":
    main()
