"""Engine-agnostic GossipTrainer facade — the repro.api entry point.

One object, one loop, any engine::

    from repro.api import GossipTrainer

    trainer = GossipTrainer(engine="sim", protocol=proto, optimizer=opt,
                            loss_fn=loss_fn, num_workers=4)
    state = trainer.init_state(seed=0)
    for step in range(steps):
        state, metrics = trainer.step(state, next(batches))

The facade owns everything the old drivers leaked to callers:

- **scheduling** — the host-side ``GossipSchedule`` fire/active/round polling
  and the ``train_step`` vs ``train_gossip_step`` program selection of the
  distributed engine happen inside :meth:`step`;
- **accounting** — every metrics dict carries ``loss``, ``fired`` and the
  cumulative ``comm_bytes`` (expected per-worker egress), live-measuring the
  paper's communication-cost claim;
- **checkpointing** — :meth:`save_checkpoint` / :meth:`load_checkpoint`
  persist the communication-schedule state alongside the trainer state so a
  resumed run reproduces the exact schedule;
- **parity** — :meth:`gossip_exchange` exposes one communication round under
  both engines (ppermute for ``engine="dist"``, the mixing-matrix oracle for
  ``engine="sim"``) over the same matching schedule, so engines are testable
  against each other purely through this facade.

Both engines speak ONE state type — :class:`repro.api.state.FlatState` — the
flat-RESIDENT contract: params/velocity are per-dtype flat buffers on the
wire layout from :meth:`init_state` to :meth:`save_checkpoint`; pytrees
appear only as lazy views (``state.params``) at the boundaries. Backends
implement init_state/step/gossip_exchange/schedule_state against FlatState
natively.

Engines (resolved through ``repro.api.register_engine`` — any registered
backend name works here):

- ``engine="sim"``  exact Alg. 1-6 on stacked replicas
  (:class:`repro.core.gossip_sim.SimTrainer`); scheduling is traced into the
  jitted step from the state's PRNG key.
- ``engine="dist"`` the production shard_map/collective-permute engine
  (:class:`repro.train.step.DistTrainer` + ``repro.core.gossip_dist``);
  scheduling is host-side and replayable.
- ``engine="async"`` the virtual-time heterogeneous-fleet engine
  (:class:`repro.core.gossip_async.AsyncTrainer` + :mod:`repro.hetero`): one
  :meth:`GossipTrainer.step` processes one event window, metrics gain
  ``virtual_time``/``window_size``/staleness, and a constant homogeneous
  compute-time model reproduces ``engine="sim"`` bit-exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import registry
from repro.api.protocols import CommCost, stacked_param_bytes
from repro.common.config import (HeteroConfig, MeshConfig, OptimizerConfig,
                                 ProtocolConfig, TrainConfig)

PyTree = Any


def __getattr__(name: str):
    if name == "ENGINES":
        # deprecated alias: the engine registry is the source of truth
        return registry.available_engines()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _as_key(seed) -> jax.Array:
    if isinstance(seed, (int, np.integer)):
        return jax.random.PRNGKey(int(seed))
    return seed


def _diff_descriptor(name: str, saved: dict, current: dict) -> None:
    """Raise a field-by-field ValueError when a persisted fleet descriptor
    (hetero / fault plane) differs from the live trainer's."""
    diffs = sorted(k for k in set(saved) | set(current)
                   if saved.get(k) != current.get(k))
    if diffs:
        detail = ", ".join(
            f"{k}: saved={saved.get(k)!r} != current={current.get(k)!r}"
            for k in diffs)
        raise ValueError(
            f"checkpoint was written under a different {name} config — "
            f"{detail}. Restore with the matching config (the virtual-time "
            "and fault draws are pure functions of it) or start a fresh run")


def _validate_shard_meta(facade, meta) -> None:
    """Refuse to restore across shard layouts, BEFORE any array is touched:
    the persisted shard descriptor (n_shards / axes / quantum) must match the
    live trainer's — the resident buffer widths, codec block streams and
    device placement are all functions of it. Field-by-field diff via
    :func:`_diff_descriptor`; the bucket totals themselves are additionally
    validated by the FlatSpec manifest check during restore."""
    from repro.shard import shard_descriptor
    meta = meta or {}
    shard = facade.shard
    cur = (shard_descriptor(shard, facade.codec)
           if shard is not None and shard.enabled() else None)
    if "shard" in meta:
        if cur is None:
            raise ValueError(
                "checkpoint was written under a sharded plane "
                f"({meta['shard']!r}) but this trainer is un-sharded — the "
                "resident buffer widths and codec streams depend on the "
                "layout; pass the same ShardConfig (shard=...) to resume")
        _diff_descriptor("shard", meta["shard"], cur)
    elif cur is not None:
        raise ValueError(
            "checkpoint was written WITHOUT a sharded plane but this "
            "trainer configures one — restoring would reinterpret the "
            "un-padded buffers under the sharded layout; drop shard= or "
            "start a fresh run")


class GossipTrainer:
    """Protocol-agnostic, engine-agnostic trainer facade.

    Common arguments:
      engine:     any registered engine name — "sim" | "dist" | "async" |
                  a ``@register_engine`` addition (``available_engines()``)
      protocol:   ProtocolConfig (method name resolved via the registry)
      optimizer:  OptimizerConfig (default NAG, as the paper)
      init_fn:    key -> single-replica params (no worker dim)
      seed:       base seed for the communication schedule
      obs:        ObsConfig (repro.obs) — structured event tracing + metrics
                  recording; None / all-default is inert (bit-exact anchor)

    ``engine="sim"`` additionally takes ``loss_fn(params, x, y)`` and
    ``num_workers`` (``mesh_cfg`` optionally, for a dist-matching gossip
    schedule in :meth:`gossip_exchange`).

    ``engine="async"`` takes the sim arguments plus ``hetero`` (a
    :class:`HeteroConfig` selecting the registered compute-time model); one
    :meth:`step` processes one virtual-time event window (see
    :mod:`repro.core.gossip_async`).

    ``engine="dist"`` takes ``mesh``, ``mesh_cfg``, ``model_cfg``,
    ``params_axes``, ``global_batch``, ``seq_len`` (and optionally
    ``loss_fn(params, batch)``, ``grad_accum``).
    """

    def __init__(self, *, engine: str = "sim",
                 protocol: ProtocolConfig,
                 optimizer: Optional[OptimizerConfig] = None,
                 init_fn: Optional[Callable] = None,
                 loss_fn: Optional[Callable] = None,
                 num_workers: Optional[int] = None,
                 mesh=None, mesh_cfg: Optional[MeshConfig] = None,
                 model_cfg=None, params_axes: Optional[PyTree] = None,
                 global_batch: Optional[int] = None, seq_len: Optional[int] = None,
                 grad_accum: int = 1, seed: int = 0, fused_update: bool = True,
                 codec: Optional[str] = None,
                 hetero: Optional[HeteroConfig] = None,
                 faults=None, fleet=None, shard=None,
                 publish_every: Optional[int] = None,
                 snapshot_bus=None, obs=None):
        backend_cls = registry.get_engine(engine)   # unknown names raise with
        self.engine = engine                        # the registered list
        # gossip-compression codec (repro.comm registry): an explicit
        # ``codec=`` overrides the protocol config's codec for this trainer
        if codec is not None:
            protocol = dataclasses.replace(protocol, codec=codec)
        self.protocol = protocol
        self.impl = registry.resolve(protocol)
        from repro import comm as _comm
        self.codec = _comm.active_codec(protocol) if self.impl.pairwise else None
        self.optimizer = optimizer or OptimizerConfig()
        self.seed = seed
        # flat-plane fused update (repro.common.flat + kernels/fused_update):
        # effective for pairwise protocols on either engine; others keep their
        # per-leaf path regardless (capability-flag gated inside the engines).
        self.fused_update = fused_update
        self.hetero = hetero
        # message-level fault plane (repro.faults): a FaultConfig turns on
        # hash-seeded drop/corrupt/Byzantine injection at the wire boundary
        # (sim + async engines) and, with a delay model, the async engine's
        # pending-wire message mode. None keeps every trace fault-free.
        self.faults = faults
        # mega-fleet plane (repro.fleet): a FleetConfig turns on partitioned
        # exchanges / token-account flow control (sim + async) and the
        # host-resident FlatState plane (async only). None or the all-default
        # config keeps every trace byte-identical to the non-fleet build.
        self.fleet = fleet
        # the host plane streams RAW host rows — a codec would silently ship
        # uncompressed bytes while comm accounting claimed the codec wire.
        # Refuse the composition up front (facade-level, before any backend
        # is built), matching the other refused compositions.
        if (fleet is not None and getattr(fleet, "plane", "device") == "host"
                and self.codec is not None):
            raise ValueError(
                "host wires are raw rows; codecs unsupported on "
                "plane='host' — drop the codec or use plane='device'")
        # sharded flat plane (repro.shard): a ShardConfig with n_shards>1
        # splits every dtype bucket's plane dim into equal device shards
        # (('fsdp','model') mesh axes under engine="dist", semantically under
        # sim/async) so gossip wire bytes and plane memory scale per-device.
        # None or the all-default config is inert: every trace and account is
        # byte-identical to the un-sharded build.
        self.shard = shard
        # train-while-serve hook (repro.serve): every ``publish_every`` facade
        # steps, :meth:`step` publishes an atomic consensus snapshot of the
        # resident flat buffers onto ``snapshot_bus`` (auto-created when only
        # the cadence is given). Engine-agnostic by construction — the hook
        # sits above the backend, on the ONE FlatState contract.
        if publish_every is not None and publish_every <= 0:
            raise ValueError("publish_every must be a positive step count")
        self.publish_every = publish_every
        if snapshot_bus is None and publish_every is not None:
            from repro.serve import SnapshotBus
            snapshot_bus = SnapshotBus()
        self.snapshot_bus = snapshot_bus
        self._host_steps = 0
        # registry-resolved backend: each engine class validates and consumes
        # the kwargs it needs from the shared facade surface
        self._backend = backend_cls.build(self, dict(
            loss_fn=loss_fn, num_workers=num_workers, init_fn=init_fn,
            mesh=mesh, mesh_cfg=mesh_cfg, model_cfg=model_cfg,
            params_axes=params_axes, global_batch=global_batch,
            seq_len=seq_len, grad_accum=grad_accum, seed=seed, hetero=hetero))
        # telemetry plane (repro.obs): an ObsConfig with anything enabled
        # builds the host-side observer and hangs it off the backend's hook.
        # None or the all-default config is INERT — no observer exists, no
        # host hook runs, every engine reproduces the un-observed build
        # bit-exactly (the FleetConfig / ShardConfig anchor pattern).
        self.obs = obs
        self.observer = None
        if obs is not None and obs.enabled():
            from repro.obs import Observer
            self.observer = Observer(obs, engine=engine,
                                     num_workers=self.num_workers)
            attach = getattr(self._backend, "attach_observer", None)
            if attach is not None:
                attach(self.observer)

    # ------------------------------------------------------------------ core
    @property
    def num_workers(self) -> int:
        return self._backend.num_workers

    def init_state(self, seed=0, params: Optional[PyTree] = None):
        """Fresh trainer state. ``params`` (optional): single-replica params
        to broadcast instead of calling ``init_fn``."""
        self._host_steps = 0
        return self._backend.init_state(seed, params)

    def step(self, state, batch):
        """ONE training step: gradient component + (internally scheduled)
        communication component. Returns (state', metrics) where metrics
        always has ``loss``, ``fired`` and cumulative ``comm_bytes``.

        With ``publish_every=k``, every k-th step additionally publishes a
        consensus snapshot of the new state onto :attr:`snapshot_bus` and
        reports its sequence number as ``metrics["published_seq"]``.

        Metrics are normalized to the unified cross-engine schema
        (:data:`repro.obs.schema.CORE_STEP_KEYS`) — additive only, engines'
        own keys are never removed.

        The whole call is the host span ``train_step`` of a ``jax.profiler``
        trace: it lands on the host plane beside the device's ops, on the
        same clock (the span costs one enter and exit when no trace runs)."""
        with jax.profiler.TraceAnnotation("train_step"):
            return self._step(state, batch)

    def _step(self, state, batch):
        from repro.obs import schema as obs_schema
        step_idx = self._host_steps
        state, metrics = self._backend.step(state, batch)
        self._host_steps += 1
        bus = self.snapshot_bus
        if (bus is not None and self.publish_every is not None
                and self._host_steps % self.publish_every == 0):
            snap = bus.publish_state(state, train_step=self._host_steps)
            if snap is not None:
                metrics["published_seq"] = snap.seq
                if self.observer is not None:
                    self.observer.event("publish", self.observer.now(),
                                        step_idx, seq=snap.seq)
            else:
                # validation refused the snapshot (non-finite / bad manifest):
                # serving keeps the last good one (repro.faults degradation)
                metrics["publish_rejected"] = True
                if self.observer is not None:
                    self.observer.event("publish_rejected",
                                        self.observer.now(), step_idx)
        metrics = obs_schema.normalize_step_metrics(metrics, step=step_idx)
        if self.observer is not None:
            self.observer.on_step(step_idx, metrics, state)
        return state, metrics

    def export_obs(self, trace_path: Optional[str] = None,
                   metrics_path: Optional[str] = None) -> dict:
        """Write the recorded telemetry: the Perfetto/Chrome trace JSON and
        the metrics JSONL (paths default to the ObsConfig's). Returns
        {kind: path} of what was written — {} when nothing records."""
        if self.observer is None:
            return {}
        return self.observer.export(trace_path, metrics_path)

    # ------------------------------------------------------- parity / gossip
    def gossip_exchange(self, params_stack: PyTree, active, round_idx: int) -> PyTree:
        """Apply ONE communication round of the pairwise protocol to stacked
        params — identical semantics under both engines (same matching
        schedule), the facade-level parity surface."""
        if not self.impl.pairwise:
            raise ValueError(f"protocol {self.protocol.method!r} has no pairwise "
                             "gossip exchange")
        return self._backend.gossip_exchange(params_stack, active, round_idx)

    def matching_partners(self, round_idx: int) -> np.ndarray:
        """Global partner index per worker for ``round_idx`` (host-side)."""
        return self._backend.matching_partners(round_idx)

    @property
    def num_gossip_rounds(self) -> int:
        return self._backend.num_gossip_rounds

    # ---------------------------------------------------------------- params
    def rank0_params(self, state) -> PyTree:
        """Worker 0's replica (paper 'Rank-0 Accuracy')."""
        return jax.tree.map(lambda x: x[0], state.params)

    def consensus_params(self, state) -> PyTree:
        """Worker-averaged replica (paper 'Aggregate Accuracy') — the
        parameters the serving engine loads. FLAT-NATIVE: the mean runs over
        the resident ``[W, total]`` buffers (one einsum per dtype bucket),
        pytree views appear only on the result."""
        from repro.serving.engine import consensus_params
        return consensus_params(state)

    # aggregate_params: alias kept for SimTrainer-era callers
    aggregate_params = consensus_params

    # ------------------------------------------------------------ accounting
    def comm_cost(self, param_bytes: Optional[int] = None) -> CommCost:
        """Analytic expected egress (bytes/worker/step); ``param_bytes``
        defaults to the live WIRE size per event — the codec-compressed flat
        plane when a codec is active, else the raw parameter size."""
        pb = param_bytes if param_bytes is not None else self._backend.wire_bytes()
        return self.impl.comm_cost(pb, self.num_workers)

    # ------------------------------------------------------------ scheduling
    def schedule_state(self) -> dict:
        """Serializable communication-schedule state ({} for engine="sim",
        whose schedule lives in the jitted state's PRNG key)."""
        return self._backend.schedule_state()

    def restore_schedule(self, sched_state: dict) -> None:
        self._backend.restore_schedule(sched_state)

    # ---------------------------------------------------------- checkpointing
    def save_checkpoint(self, path: str, state, meta: Optional[dict] = None) -> None:
        """Trainer state + schedule state + host accounting + protocol
        config, atomically, in checkpoint format v2: the resident flat
        buffers plus a FlatSpec manifest (schedule rides in the metadata via
        io.save_state)."""
        from repro.checkpoint import io
        meta = dict(meta or {})
        meta.setdefault("protocol", dataclasses.asdict(self.protocol))
        if self.shard is not None and self.shard.enabled():
            from repro.shard import shard_descriptor
            meta.setdefault("shard", shard_descriptor(self.shard, self.codec))
        meta.update(self._backend.checkpoint_extra())
        io.save_state(path, state, meta=meta,
                      schedule=getattr(self._backend, "sched", None))

    def load_checkpoint(self, path: str, state_like):
        """Restore a checkpoint into the FlatState structure of
        ``state_like`` AND rewind the communication schedule / host-side
        accounting to the saved position. Legacy (pre-FlatState) pytree
        checkpoints are converted bit-exactly on load. Returns (state, meta).
        """
        from repro.checkpoint import io
        meta = io.load_meta(path)
        # descriptor checks run BEFORE array restore: a fleet mismatch (e.g.
        # a different partition) would otherwise surface as an opaque
        # chunk_units shape assert instead of the config diff
        validate = getattr(self._backend, "validate_checkpoint_meta", None)
        if validate is not None:
            validate(meta)
        state = io.restore_state(path, state_like, meta=meta)
        sched = getattr(self._backend, "sched", None)
        if sched is not None:
            io.restore_schedule(path, sched)
        self._backend.on_checkpoint_loaded(state, meta)
        return state, meta


# ---------------------------------------------------------------------------
# engine adapters
# ---------------------------------------------------------------------------

class _MatchingScheduleMixin:
    """Shared host-side matching schedule (hypercube / random) so every engine
    exposes the SAME gossip rounds through the facade — routed through the
    protocol's ONE overridable :meth:`~repro.api.protocols.Protocol.
    schedule_partners` hook (time-varying topologies override it in the
    protocol class and every host consumer follows)."""

    def matching_partners(self, round_idx: int) -> np.ndarray:
        mcfg = self._sched_mesh_cfg()
        return self.facade.impl.schedule_partners(round_idx, mcfg.num_workers,
                                                  mesh_cfg=mcfg)

    @property
    def num_gossip_rounds(self) -> int:
        mcfg = self._sched_mesh_cfg()
        return self.facade.impl.schedule_rounds(mcfg.num_workers, mesh_cfg=mcfg)


@registry.register_engine("sim")
class _SimBackend(_MatchingScheduleMixin):
    @classmethod
    def build(cls, facade: GossipTrainer, kw: dict):
        if kw.get("loss_fn") is None or kw.get("num_workers") is None:
            raise ValueError(f'engine="{cls.engine_name}" requires loss_fn '
                             'and num_workers')
        return cls(facade, kw["loss_fn"], kw["num_workers"], kw.get("init_fn"),
                   kw.get("mesh_cfg"))

    def __init__(self, facade: GossipTrainer, loss_fn, num_workers: int,
                 init_fn, mesh_cfg: Optional[MeshConfig]):
        from repro.core.gossip_sim import SimTrainer
        self.facade = facade
        self.init_fn = init_fn
        self.num_workers = num_workers
        self.mesh_cfg = mesh_cfg
        self.sim = SimTrainer(loss_fn, num_workers, facade.protocol, facade.optimizer,
                              fused_update=facade.fused_update,
                              faults=facade.faults, fleet=facade.fleet,
                              shard=facade.shard)
        self._pb = None
        self._wire = None

    def attach_observer(self, observer) -> None:
        self.sim.obs = observer

    def _sched_mesh_cfg(self) -> MeshConfig:
        return self.mesh_cfg or MeshConfig(data=self.num_workers, model=1, pods=1,
                                           workers_per_pod=self.num_workers)

    def init_state(self, seed=0, params=None):
        if params is None:
            if self.init_fn is None:
                raise ValueError("provide init_fn at construction or params here")
            params = self.init_fn(_as_key(seed))
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (self.num_workers,) + x.shape), params)
        self._pb = stacked_param_bytes(stacked)
        self._wire = int(self.facade.impl.wire_stack_bytes(stacked))
        sim_seed = int(seed) if isinstance(seed, (int, np.integer)) else 0
        state = self.sim.init(stacked, sim_seed)
        if self.sim.shard_layout is not None:
            # sharded plane: the facade-level wire account is per-DEVICE
            # egress — exactly the engine's own (padded wire / n_shards)
            self._wire = int(self.sim._wire_bytes(state.spec))
        return state

    def step(self, state, batch):
        x, y = (batch["x"], batch["y"]) if isinstance(batch, dict) else batch
        state, m = self.sim.step(state, x, y)
        metrics = dict(m)
        metrics["loss"] = m["loss_mean"]
        metrics["fired"] = m["comm_active"] > 0
        # unified schema: the engine's round counter — the device-side
        # cumulative fired-round count here (lazy, no host sync; the dist
        # engine reports its schedule's round index instead, see schema.py)
        metrics["comm_round"] = state.proto.comm_rounds
        metrics["comm_bytes"] = state.proto.comm_bytes
        return state, metrics

    def param_bytes(self) -> int:
        if self._pb is None:
            raise ValueError("param size unknown before init_state; pass param_bytes")
        return self._pb

    def wire_bytes(self) -> int:
        if self._wire is None:
            raise ValueError("wire size unknown before init_state; pass param_bytes")
        return self._wire

    def gossip_exchange(self, params_stack, active, round_idx):
        """Mixing-matrix oracle over the shared matching schedule — exactly
        Alg. 3/4/6 restricted to the round's perfect matching. With a codec,
        off-diagonal contributions read the decode(encode(theta))
        reconstruction, seeded by (round, worker) exactly like the dist
        engine's wire — the parity surface stays engine-exact."""
        from repro import comm
        from repro.common.flat import FlatSpec
        from repro.core import topology
        peers = jnp.asarray(self.matching_partners(round_idx))
        gate = jnp.asarray(active) > 0
        mix = self.facade.impl.mix_matrix(peers, gate)
        codec = self.facade.codec
        if codec is None:
            return topology.apply_mix(mix, params_stack)
        spec = FlatSpec.build(params_stack, leading=1)
        W = jax.tree.leaves(params_stack)[0].shape[0]
        bufs = spec.flatten(params_stack)
        layout = self.sim.shard_layout
        shard = self.facade.shard
        if layout is None and shard is not None and shard.enabled():
            # parity surface may run before init_state: derive the layout
            # from the stacked params directly (same spec → same layout)
            from repro import shard as shard_plane
            layout = shard_plane.build_layout(spec, shard, codec)
        if layout is not None:
            # sharded plane: encode per SHARD row, seeded by the dist
            # engine's worker*n_shards+shard coordinate (see
            # SimTrainer._codec_transmit) — the parity surface stays
            # engine-exact under shard ∘ q8/topk too
            from repro import shard as shard_plane
            widths = {k: b.shape[-1] for k, b in bufs.items()}
            rows = layout.shard_rows(shard_plane.pad_bufs(bufs, layout))
            hat, _ = comm.roundtrip_bufs(
                codec, rows,
                comm.codec_seeds(round_idx, jnp.arange(W * layout.n_shards)))
            hat = shard_plane.slice_bufs(layout.unshard_rows(hat), widths)
        else:
            hat, _ = comm.roundtrip_bufs(
                codec, bufs, comm.codec_seeds(round_idx, jnp.arange(W)))
        return topology.apply_mix_split(mix, params_stack, spec.unflatten(hat))

    def schedule_state(self) -> dict:
        return {}

    def restore_schedule(self, sched_state: dict) -> None:
        pass  # sim scheduling lives in FlatState.key, restored with the state

    def checkpoint_extra(self) -> dict:
        return {}  # comm_bytes lives in ProtocolState, saved with the state

    def validate_checkpoint_meta(self, meta) -> None:
        _validate_shard_meta(self.facade, meta)

    def on_checkpoint_loaded(self, state, meta) -> None:
        pass


@registry.register_engine("dist")
class _DistBackend(_MatchingScheduleMixin):
    @classmethod
    def build(cls, facade: GossipTrainer, kw: dict):
        if (kw.get("mesh") is None or kw.get("mesh_cfg") is None
                or kw.get("init_fn") is None or kw.get("params_axes") is None):
            raise ValueError('engine="dist" requires mesh, mesh_cfg, init_fn '
                             'and params_axes')
        if facade.faults is not None:
            raise ValueError(
                'engine="dist" does not support fault injection: the fault '
                'plane rides the single-controller wire boundary (use '
                'engine="sim" or engine="async")')
        return cls(facade, kw["mesh"], kw["mesh_cfg"], kw.get("model_cfg"),
                   kw["init_fn"], kw["params_axes"], kw.get("global_batch"),
                   kw.get("seq_len"), kw.get("loss_fn"),
                   kw.get("grad_accum", 1), kw.get("seed", 0))

    def __init__(self, facade: GossipTrainer, mesh, mesh_cfg: MeshConfig, model_cfg,
                 init_fn, params_axes, global_batch, seq_len, loss_fn,
                 grad_accum: int, seed: int):
        from repro.core.scheduler import GossipSchedule
        from repro.train.step import DistTrainer
        self.facade = facade
        self.mesh_cfg = mesh_cfg
        self.num_workers = mesh_cfg.num_workers
        tcfg = TrainConfig(protocol=facade.protocol, optimizer=facade.optimizer,
                           fused_update=facade.fused_update)
        self.trainer = DistTrainer(mesh, mesh_cfg, model_cfg, tcfg, init_fn,
                                   params_axes, loss_fn=loss_fn,
                                   grad_accum=grad_accum, shard=facade.shard)
        if global_batch is not None:
            self.trainer.set_shape(global_batch, seq_len or 4096)
        self.sched = GossipSchedule(facade.protocol, self.num_workers, seed=seed + 1,
                                    mesh_cfg=mesh_cfg)
        self._ts = self._tg = None
        # host-side (python float64) accumulator: increments stay exact far
        # beyond f32's 2^24 granularity — the traced sim-engine counterpart is
        # ProtocolState.comm_units (see repro.api.protocols)
        self.comm_bytes = 0.0
        # per-step host costs, hoisted out of the hot loop: param_bytes()
        # walked the whole param tree and comm_cost() re-derived the analytic
        # egress EVERY step — both are static per trainer. The cost model uses
        # the WIRE bytes: the codec-compressed flat plane when a codec rides
        # the collective, else the raw parameter bytes.
        self._pb = stacked_param_bytes(self.trainer.param_shapes)
        self._wire = int(facade.impl.wire_stack_bytes(self.trainer.param_shapes))
        if self.trainer.shard_layout is not None:
            # sharded plane: account per-DEVICE egress (each device ships
            # only its local shard of the wire)
            from repro.shard import wire_per_device
            self._wire = int(wire_per_device(self.trainer.shard_layout,
                                             self.trainer.flat_spec,
                                             facade.codec))
        self._cost = facade.impl.comm_cost(self._wire, self.num_workers)
        # host mirror of state.step: polling the schedule with it (instead of
        # int(state.step)) keeps the hot loop free of per-step device syncs.
        # The facade drives ONE sequential training stream; the mirror is
        # re-anchored at init_state / load_checkpoint.
        self._host_step = 0
        self._obs = None

    def attach_observer(self, observer) -> None:
        self._obs = observer

    def _sched_mesh_cfg(self) -> MeshConfig:
        return self.mesh_cfg

    def init_state(self, seed=0, params=None):
        assert params is None, 'engine="dist" initializes from init_fn only'
        self._host_step = 0
        return self.trainer.init_state(_as_key(seed))

    @property
    def ts(self):
        if self._ts is None:
            self._ts = self.trainer.jit_train_step()
        return self._ts

    @property
    def tg(self):
        if self._tg is None:
            self._tg = self.trainer.jit_train_gossip_step()
        return self._tg

    def param_bytes(self) -> int:
        return self._pb

    def wire_bytes(self) -> int:
        return self._wire

    def step(self, state, batch):
        impl = self.facade.impl
        obs = self._obs
        t_start = obs.now() if obs is not None else 0.0
        fire, active, rnd = self.sched.poll(self._host_step)
        step_idx = self._host_step
        self._host_step += 1
        if impl.pairwise and fire:
            state, m = self.tg(state, batch, jnp.asarray(active), jnp.int32(rnd))
        elif impl.uses_center:
            state, m = self.ts(state, batch, jnp.float32(fire))
        else:
            state, m = self.ts(state, batch, jnp.zeros(()))
        cost = self._cost
        if not impl.communicates:
            self.comm_bytes += cost.bytes_per_step   # allreduce: every step; none: 0
        elif fire:
            self.comm_bytes += cost.bytes_per_event * float(np.mean(active))
        metrics = dict(m)
        metrics["fired"] = bool(fire)
        # unified schema: the dist loss is the device-reduced fleet mean —
        # per-worker losses never leave the mesh, so mean == max == loss
        # (documented degeneracy, repro/obs/schema.py); comm_active comes
        # from the host schedule's active mask
        metrics["loss_mean"] = m["loss"]
        metrics["loss_max"] = m["loss"]
        metrics["comm_active"] = (int(np.sum(active))
                                  if fire and active is not None else 0)
        metrics["comm_round"] = rnd
        metrics["comm_bytes"] = self.comm_bytes
        if obs is not None:
            obs.on_dist_step(self, t_start, step_idx, fire, active, rnd)
        return state, metrics

    def gossip_exchange(self, params_stack, active, round_idx):
        # the compiled schedule inside the engine is build_schedule(...) too,
        # so rounds line up 1:1 with the sim oracle's matching_partners
        return self.trainer.gossip_exchange(params_stack, jnp.asarray(active),
                                            jnp.int32(round_idx))

    def schedule_state(self) -> dict:
        return self.sched.state()

    def restore_schedule(self, sched_state: dict) -> None:
        self.sched.restore(sched_state)

    def checkpoint_extra(self) -> dict:
        # dist comm_bytes is host-side accounting; persist it so resumed runs
        # keep the cumulative egress metric instead of restarting at 0
        return {"comm_bytes": float(self.comm_bytes)}

    def validate_checkpoint_meta(self, meta) -> None:
        _validate_shard_meta(self.facade, meta)

    def on_checkpoint_loaded(self, state, meta) -> None:
        self._host_step = int(state.step)   # one sync, at load time only
        if meta and "comm_bytes" in meta:
            self.comm_bytes = float(meta["comm_bytes"])


@registry.register_engine("async")
class _AsyncBackend(_SimBackend):
    """Virtual-time asynchronous engine (repro.core.gossip_async): the sim
    backend surface driven by an event loop — one facade ``step`` is one
    event window, metrics additionally carry ``virtual_time`` /
    ``window_size`` / staleness accumulators, and the host clock mirrors
    persist through the checkpoint metadata."""

    @classmethod
    def build(cls, facade: GossipTrainer, kw: dict):
        if kw.get("loss_fn") is None or kw.get("num_workers") is None:
            raise ValueError('engine="async" requires loss_fn and num_workers')
        return cls(facade, kw["loss_fn"], kw["num_workers"], kw.get("init_fn"),
                   kw.get("mesh_cfg"), kw.get("hetero"))

    def __init__(self, facade: GossipTrainer, loss_fn, num_workers: int,
                 init_fn, mesh_cfg: Optional[MeshConfig],
                 hetero: Optional[HeteroConfig]):
        from repro.core.gossip_async import AsyncTrainer
        self.facade = facade
        self.init_fn = init_fn
        self.num_workers = num_workers
        self.mesh_cfg = mesh_cfg
        # the AsyncTrainer satisfies the SimTrainer surface the inherited
        # backend methods drive (init/step/rank0/aggregate)
        self.sim = AsyncTrainer(loss_fn, num_workers, facade.protocol,
                                facade.optimizer, hetero=hetero,
                                fused_update=facade.fused_update,
                                faults=facade.faults, fleet=facade.fleet,
                                shard=facade.shard)
        self._pb = None
        self._wire = None

    # ------------------------------------------------- virtual-time schedule
    def schedule_state(self) -> dict:
        # unlike engine="sim" (whose whole schedule lives in FlatState.key)
        # the async engine adds the host-side virtual-time position
        return {"hetero_clock": self.sim.clock_state()}

    def restore_schedule(self, sched_state: dict) -> None:
        hc = (sched_state or {}).get("hetero_clock")
        if hc:
            self.sim.anchor(hc["clocks"], hc["steps_done"])

    def checkpoint_extra(self) -> dict:
        # float64 clocks via JSON round-trip exactly; the device-side f32
        # proto.clocks are only a fallback for checkpoints missing this.
        # The hetero/fault descriptors make a resumed run refuse a DIFFERENT
        # fleet: replaying a fail_rejoin schedule or fault seed that doesn't
        # match the saved one silently changes every subsequent draw.
        extra = {"hetero_clock": self.sim.clock_state(),
                 "hetero": dataclasses.asdict(self.sim.hetero)}
        if self.facade.faults is not None:
            from repro.faults import fault_descriptor
            extra["faults"] = fault_descriptor(self.facade.faults)
        if self.facade.fleet is not None and self.facade.fleet.enabled():
            extra["fleet"] = dataclasses.asdict(self.facade.fleet)
        return extra

    def validate_checkpoint_meta(self, meta) -> None:
        self._validate_fleet(meta)
        _validate_shard_meta(self.facade, meta)

    def on_checkpoint_loaded(self, state, meta) -> None:
        hc = (meta or {}).get("hetero_clock")
        if hc:
            self.sim.anchor(hc["clocks"], hc["steps_done"])
        elif state.proto is not None and state.proto.clocks is not None:
            self.sim.anchor(np.asarray(state.proto.clocks, np.float64),
                            np.asarray(state.proto.worker_steps, np.int64))

    def _validate_fleet(self, meta) -> None:
        """Refuse to restore under a different virtual fleet (S2): the saved
        ``hetero`` / ``faults`` descriptors must match the current trainer's.
        Checkpoints written before these keys existed restore unvalidated."""
        from repro.faults import fault_descriptor
        meta = meta or {}
        if "hetero" in meta:
            _diff_descriptor("hetero", meta["hetero"],
                             dataclasses.asdict(self.sim.hetero))
        if "faults" in meta:
            cur = (fault_descriptor(self.facade.faults)
                   if self.facade.faults is not None else None)
            if cur is None:
                raise ValueError(
                    "checkpoint was written with a fault plane "
                    f"({meta['faults']!r}) but this trainer has none — pass "
                    "the same FaultConfig (faults=...) to resume this run")
            _diff_descriptor("faults", meta["faults"], cur)
        elif self.facade.faults is not None:
            raise ValueError(
                "checkpoint was written WITHOUT a fault plane but this "
                "trainer configures one — resuming would inject faults into "
                "a run that never had them; drop faults= or start fresh")
        fleet = self.facade.fleet
        cur_fleet = (dataclasses.asdict(fleet)
                     if fleet is not None and fleet.enabled() else None)
        if "fleet" in meta:
            if cur_fleet is None:
                raise ValueError(
                    "checkpoint was written under a fleet plane "
                    f"({meta['fleet']!r}) but this trainer has none — the "
                    "partition/flow draws are pure functions of it; pass the "
                    "same FleetConfig (fleet=...) to resume this run")
            _diff_descriptor("fleet", meta["fleet"], cur_fleet)
        elif cur_fleet is not None:
            raise ValueError(
                "checkpoint was written WITHOUT a fleet plane but this "
                "trainer configures one — resuming would change every "
                "partition/flow draw; drop fleet= or start fresh")
