"""Train-while-serve driver (single-controller), built on ``repro.serve``.

One process, two interleaved loops over the same model: a ``repro.api``
GossipTrainer (any registered engine) trains W gossip replicas and publishes
consensus snapshots every ``--publish-every`` steps onto a SnapshotBus; a
LiveServer hot-swaps a ServeProgram to each snapshot between decode
boundaries while a ContinuousBatcher serves a hash-seeded Poisson request
stream. Prints per-phase progress and a final latency/swap/staleness summary.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_1_1b \
        --reduced --boundaries 120 --rate 0.3 --publish-every 5
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.api import GossipTrainer, available_engines, make_serve_program
from repro.common.cache import enable_compile_cache
from repro.common.config import MeshConfig, OptimizerConfig, ProtocolConfig
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.launch.mesh import make_host_mesh
from repro.launch.train import lm_batches
from repro.models import transformer as tr
from repro.serve import ContinuousBatcher, LiveServer, TrafficGen, TrainServeLoop


def run(arch: str, *, reduced: bool = True, engine: str = "sim",
        workers: int = 4, method: str = "elastic_gossip", p: float = 0.25,
        alpha: float = 0.5, lr: float = 0.01, seq: int = 32,
        per_worker_batch: int = 2, slots: int = 4, max_len: int = 256,
        boundaries: int = 120, rate: float = 0.3, num_requests: int = 24,
        publish_every: int = 5, train_per_boundary: int = 1,
        traffic_mode: str = "poisson", seed: int = 0) -> dict:
    cfg = get_reduced(arch) if reduced else get_config(arch)
    assert cfg.audio is None and cfg.vlm is None, (
        "the traffic harness serves plain-LM archs")

    # ---- training side: gossip trainer with the snapshot publish hook armed
    def loss_fn(params, x, y):
        loss, _ = tr.lm_loss(params, cfg, x, y)
        return loss

    trainer = GossipTrainer(
        engine=engine,
        protocol=ProtocolConfig(method=method, comm_probability=p,
                                moving_rate=alpha, topology="uniform"),
        optimizer=OptimizerConfig(name="nag", learning_rate=lr, momentum=0.9),
        loss_fn=loss_fn, num_workers=workers,
        init_fn=lambda key: tr.init_lm(key, cfg)[0],
        publish_every=publish_every)
    state = trainer.init_state(seed)
    batches = lm_batches(cfg, workers, per_worker_batch, seq, seed)

    # ---- serving side: LiveServer over the bus the trainer publishes onto
    mesh_cfg = MeshConfig(data=1, model=1, pods=1, workers_per_pod=1)
    prog = make_serve_program(make_host_mesh(1), mesh_cfg, cfg, batch=slots,
                              max_len=max_len, param_dtype=jnp.float32,
                              cache_dtype=jnp.float32)
    server = LiveServer(prog, trainer.snapshot_bus,
                        params=trainer.consensus_params(state))
    gen = TrafficGen(seed + 1, rate=rate, num_requests=num_requests,
                     vocab=cfg.vocab_size, prompt_len=(1, 8), max_new=(4, 16),
                     mode=traffic_mode)
    batcher = ContinuousBatcher(server, gen.requests())

    # ---- interleave
    def train_fn(_boundary: int) -> int:
        nonlocal state
        for _ in range(train_per_boundary):
            b = next(batches)
            state, _ = trainer.step(state, (b["tokens"], b["labels"]))
        return trainer._host_steps

    loop = TrainServeLoop(server, batcher, train_fn)
    loop.run(boundaries)
    batcher.check_invariants()

    out = {"arch": cfg.name, "engine": engine, "workers": workers,
           "slots": slots, "publish_every": publish_every,
           "bus_seq": trainer.snapshot_bus.seq,
           **batcher.latency_summary(), **loop.summary()}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama_1_1b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--engine", default="sim", choices=available_engines())
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--method", default="elastic_gossip")
    ap.add_argument("--p", type=float, default=0.25)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--boundaries", type=int, default=120)
    ap.add_argument("--rate", type=float, default=0.3)
    ap.add_argument("--num-requests", type=int, default=24)
    ap.add_argument("--publish-every", type=int, default=5)
    ap.add_argument("--train-per-boundary", type=int, default=1)
    ap.add_argument("--traffic-mode", default="poisson",
                    choices=["poisson", "staggered"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    out = run(args.arch, reduced=args.reduced, engine=args.engine,
              workers=args.workers, method=args.method, p=args.p,
              alpha=args.alpha, lr=args.lr, slots=args.slots,
              max_len=args.max_len, boundaries=args.boundaries,
              rate=args.rate, num_requests=args.num_requests,
              publish_every=args.publish_every,
              train_per_boundary=args.train_per_boundary,
              traffic_mode=args.traffic_mode, seed=args.seed)
    print(json.dumps(out, indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
