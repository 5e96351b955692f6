"""Distributed training step: per-worker replicas on the production mesh.

The trainer state is the engine-agnostic :class:`repro.api.state.FlatState`:
parameters and velocity live RESIDENT on the flat parameter plane
(:mod:`repro.common.flat`) — one lane-aligned ``[W, total]`` buffer per dtype
bucket, sharded on the leading (replica) dim over ('pod','worker'), flattened
once at :meth:`DistTrainer.init_state`. The gossip exchange, the fused Pallas
update and the NAG sweeps all consume the buffers directly (no per-step
flatten/unflatten); the parameter *pytree* exists only as lazy slice views at
the loss boundary (per-worker, inside the gradient vmap) and for
eval/checkpoint via ``state.params``.

Two compiled programs (DESIGN.md §4):

- ``train_step``      gradient-related component only. For ``allreduce`` the
                      gradient mean over the worker axis happens here (Alg. 1);
                      for ``easgd`` the center exchange (psum) happens here,
                      gated by the host-scheduled ``active`` scalar.
- ``train_gossip_step``  gradient + ONE matching-gossip round, composed
                      simultaneously from the step-t state, exactly like the
                      simulation engine (gossip_sim.py). The repro.api
                      GossipTrainer facade selects between the two programs
                      from the host-side schedule; protocol behavior comes
                      from registry capability flags, not method strings.

Keeping them separate keeps gossip collectives out of the steady-state HLO, so
the dry-run roofline can amortize gossip cost by its true expected frequency
(p or 1/tau) instead of baking it into every step.

Sharding contract of the resident plane: the replica dim shards over
('pod','worker'). By default the plane dim is replicated within a replica
group; with a ``ShardConfig`` (repro.shard) the plane dim ALSO shards over
the ('fsdp','model') mesh axes — bucket totals are padded to n_shards equal
codec-block-aligned shards, the buf specs gain the shard axes on the plane
dim, and every shard-mapped program (gossip exchange, fused NAG, fused
gossip) sees only its ``[1, shard_size]`` local shard, so gossip wire bytes
and plane memory scale per-device. The per-leaf ``params_axes`` are still
accepted and used for batch/loss shardings.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import comm
from repro.api import registry
from repro.api.state import FlatState
from repro.common import flat as flat_plane
from repro.common.config import MeshConfig, ModelConfig, ProtocolConfig, TrainConfig
from repro.core import gossip_dist
from repro.kernels import ops
from repro.launch import sharding as shr
from repro.optim.optimizers import OptState
from repro.optim.schedule import lr_at
from repro.train import losses

PyTree = Any

# Deprecated alias: the dist engine's state IS the engine-agnostic FlatState
# (repro.api.state) since the flat-resident redesign.
TrainState = FlatState


class DistTrainer:
    def __init__(self, mesh: Mesh, mesh_cfg: MeshConfig, model_cfg: ModelConfig,
                 train_cfg: TrainConfig, init_fn: Callable, params_axes: PyTree,
                 loss_fn: Optional[Callable] = None, grad_accum: int = 1,
                 shard=None):
        """init_fn(key) -> single-replica params (no W dim)."""
        self.mesh, self.mesh_cfg, self.model_cfg, self.train_cfg = mesh, mesh_cfg, model_cfg, train_cfg
        self.loss_fn = loss_fn or losses.lm_loss_fn(model_cfg)
        self.init_fn = init_fn
        self.grad_accum = grad_accum
        self.W = mesh_cfg.num_workers
        self.opt = train_cfg.optimizer
        # TrainConfig.codec overrides the protocol's codec for this run
        self.protocol = (dataclasses.replace(train_cfg.protocol, codec=train_cfg.codec)
                         if train_cfg.codec else train_cfg.protocol)
        self._impl = registry.resolve(self.protocol)
        self._codec = (comm.active_codec(self.protocol)
                       if self._impl.pairwise else None)
        self._codec_stateful = self._codec is not None and self._codec.stateful
        assert self.opt.name == "nag", "distributed trainer implements the paper's NAG (Alg. 5)"

        single_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        self.param_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((self.W,) + s.shape, s.dtype), single_shapes)
        # per-leaf axes kept for batch/loss shardings; the RESIDENT state is
        # the flat plane, sharded on the replica dim only
        self.params_axes = params_axes
        self.flat_spec = flat_plane.FlatSpec.build(self.param_shapes, leading=1)
        lead_axes = tuple(a for a in ("pod", "worker") if a in mesh.axis_names)
        # sharded plane (repro.shard): pad bucket totals to n_shards equal
        # quantum-aligned shards and put the shard axes on the PLANE dim of
        # the buf specs — inert (spec/jaxpr-identical) at the default config
        self.shard = shard
        self.shard_layout = None
        if shard is not None and shard.enabled():
            if not self._impl.pairwise:
                raise ValueError(
                    f"sharded plane (repro.shard) needs a pairwise protocol; "
                    f"{self.protocol.method!r} is not pairwise")
            got = 1
            for ax in shard.axes:
                if ax not in mesh.shape:
                    raise ValueError(
                        f"shard axis {ax!r} not in mesh axes "
                        f"{tuple(mesh.axis_names)}")
                got *= mesh.shape[ax]
            if got != shard.n_shards:
                raise ValueError(
                    f"ShardConfig(n_shards={shard.n_shards}) needs the mesh "
                    f"product over axes {tuple(shard.axes)} to match, got "
                    f"{got} (mesh shape {dict(mesh.shape)})")
            from repro import shard as shard_plane
            self.shard_layout = shard_plane.build_layout(
                self.flat_spec, shard, self._codec)
            self.flat_spec = shard_plane.padded_spec(self.flat_spec,
                                                     self.shard_layout)
            self.buf_specs = {k: P(lead_axes, tuple(shard.axes))
                              for k in self.flat_spec.buckets}
        else:
            self.buf_specs = {k: P(lead_axes) for k in self.flat_spec.buckets}
        self.center_buf_specs = {k: P() for k in self.flat_spec.buckets}
        self.state_specs = FlatState(
            spec=self.flat_spec,
            theta=self.buf_specs,
            opt=OptState(P(), dict(self.buf_specs), {}),
            center=dict(self.center_buf_specs) if self._impl.uses_center else None,
            comm=comm.CommState(dict(self.buf_specs) if self._codec_stateful else None),
            step=P())
        self._gossip_exchange = None
        self._fused_gossip = None
        self._fused_nag = None
        # fused flat-plane update (TrainConfig.fused_update, default on):
        # pairwise protocols only — allreduce/EASGD keep the per-bucket path
        # (registry capability flags, not method strings).
        self.fused_update = bool(train_cfg.fused_update) and self._impl.pairwise

    # ------------------------------------------------------------------ init
    def _constrain_bufs(self, bufs, specs=None):
        specs = specs or self.buf_specs
        return jax.lax.with_sharding_constraint(
            bufs, {k: NamedSharding(self.mesh, specs[k]) for k in bufs})

    def _stack_plane(self, single):
        """One replica's params -> the sharded ``[W, total]`` plane buffers."""
        stacked = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (self.W,) + x.shape), single)
        theta = self.flat_spec.flatten(stacked)
        if self.shard_layout is not None:
            from repro import shard as shard_plane
            theta = shard_plane.pad_bufs(theta, self.shard_layout)
        return self._constrain_bufs(theta)

    def init_state(self, key) -> FlatState:
        """Flatten ONCE into the resident plane; pytrees do not survive init."""
        single = self.init_fn(key)
        # stacked inside one program: each device builds only its own rows
        # (built eagerly, the whole W-replica stack and its flattened copy
        # would land on the first device before the resharding)
        theta = jax.jit(self._stack_plane)(single)
        vel = jax.tree.map(jnp.zeros_like, theta)
        # replicated leaves carry the step programs' own output sharding, so
        # the first step compiles the program every later step reuses
        rep = NamedSharding(self.mesh, P())
        center = (jax.device_put(self.flat_spec.with_lead(()).flatten(single), rep)
                  if self._impl.uses_center else None)
        comm_state = comm.CommState(None)
        if self._codec_stateful:
            res = {k: jnp.zeros_like(b, jnp.float32) for k, b in theta.items()}
            comm_state = comm.CommState(self._constrain_bufs(res))
        # two counters, two buffers: the step programs donate both
        counter = lambda: jax.device_put(jnp.zeros((), jnp.int32), rep)
        return FlatState(spec=self.flat_spec, theta=theta,
                         opt=OptState(counter(), vel, {}),
                         center=center, comm=comm_state, step=counter())

    def state_shapes(self) -> FlatState:
        """ShapeDtypeStructs for the dry-run (no allocation)."""
        def bufs_sds(dtype=None):
            return {k: jax.ShapeDtypeStruct((self.W, n),
                                            jnp.dtype(dtype or k))
                    for k, n in self.flat_spec.totals.items()}
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        center = ({k: jax.ShapeDtypeStruct((n,), jnp.dtype(k))
                   for k, n in self.flat_spec.totals.items()}
                  if self._impl.uses_center else None)
        comm_state = comm.CommState(
            bufs_sds(jnp.float32) if self._codec_stateful else None)
        return FlatState(spec=self.flat_spec, theta=bufs_sds(),
                         opt=OptState(scalar, bufs_sds(), {}),
                         center=center, comm=comm_state, step=scalar)

    # --------------------------------------------------------------- batches
    def batch_specs(self):
        ax = losses.batch_axes(self.model_cfg)
        ax = {k: (("worker",) + tuple(a)) for k, a in ax.items()}
        shapes = self.batch_shapes()
        return shr.tree_specs(shapes, ax, self.mesh)

    def batch_shapes(self, global_batch: Optional[int] = None, seq_len: int = 4096):
        gb = global_batch or getattr(self, "_gb", None)
        assert gb is not None
        per_worker = gb // self.W
        shapes = losses.batch_shapes(self.model_cfg, per_worker, seq_len)
        return {k: jax.ShapeDtypeStruct((self.W,) + s, dt) for k, (s, dt) in shapes.items()}

    def set_shape(self, global_batch: int, seq_len: int):
        self._gb, self._seq = global_batch, seq_len

    # ------------------------------------------------------- gradient engine
    def _grads_and_loss(self, theta_bufs, batch):
        """Per-worker grads via vmap over the replica dim of the resident
        buffers. The loss reads the single-replica pytree VIEW of its row and
        autodiff through the views lands the gradients directly on the flat
        plane — no per-step flatten. Microbatch accumulation as before
        (jax.checkpoint'ed model already limits live activations)."""
        A = self.grad_accum
        row_spec = self.flat_spec.with_lead(())

        def loss_of(bufs, b):
            return self.loss_fn(row_spec.views(bufs), b)

        def one_worker(bufs, b):
            if A == 1:
                return jax.value_and_grad(loss_of)(bufs, b)

            def micro(carry, mb):
                tot, acc = carry
                l, g = jax.value_and_grad(loss_of)(bufs, mb)
                return (tot + l, jax.tree.map(jnp.add, acc, g)), None

            micro_b = jax.tree.map(
                lambda x: x.reshape((A, x.shape[0] // A) + x.shape[1:]), b)
            zero = {k: jnp.zeros(x.shape, jnp.float32) for k, x in bufs.items()}
            (tot, acc), _ = jax.lax.scan(micro, (jnp.zeros(()), zero), micro_b)
            return tot / A, jax.tree.map(lambda g_: g_ / A, acc)

        return jax.vmap(one_worker)(theta_bufs, batch)

    def _nag(self, theta, velocity, grads, step):
        eta = lr_at(self.opt, step)
        mu = self.opt.momentum
        v_new = jax.tree.map(lambda v, g: mu * v - eta * g.astype(v.dtype), velocity, grads)
        p_new = jax.tree.map(lambda p, g, v: p - eta * g.astype(p.dtype) + mu * v.astype(p.dtype),
                             theta, grads, v_new)
        return p_new, v_new

    # ------------------------------------------------------------- programs
    def _train_step(self, state: FlatState, batch, active):
        loss, grads = self._grads_and_loss(state.theta, batch)
        grads = self._impl.gradient_transform(grads)
        center_new = state.center
        comm_delta = None
        if self._impl.uses_center:
            # center exchange (Alg. 2 lines 5-7), gated by the host scheduler,
            # directly on the resident buffers ([W, N] vs [N] center)
            comm_delta, center_new = self._impl.center_step(
                state.theta, state.center, active)
        if self.fused_update and comm_delta is None:
            # flat-plane fused NAG: velocity + parameter update in ONE pass
            # (5 streams) instead of two per-bucket sweeps
            p_new, v_new = self.fused_nag(
                state.theta, state.opt.mu, grads,
                lr_at(self.opt, state.step), jnp.float32(self.opt.momentum))
        else:
            p_new, v_new = self._nag(state.theta, state.opt.mu, grads, state.step)
            if comm_delta is not None:
                p_new = jax.tree.map(jnp.add, p_new, comm_delta)
        metrics = {"loss": jnp.mean(loss)}
        return state.replace(theta=p_new,
                             opt=OptState(state.opt.step + 1, v_new, {}),
                             center=center_new, step=state.step + 1), metrics

    def _train_gossip_step(self, state: FlatState, batch, active, round_idx):
        """Simultaneous composition: grads and the elastic move both read the
        step-t resident buffers (paper §2.3)."""
        loss, grads = self._grads_and_loss(state.theta, batch)
        comm_new = state.comm
        if self.fused_update:
            # flat-plane path: ONE shard-mapped program does the single
            # ppermute (peer replica + gate in one buffer) AND the fused
            # NAG + elastic displacement (Alg. 5 lines 3/7/9, simultaneous —
            # both read the step-t buffers), with the per-replica gate*coef
            # folded into the kernel's coefficient. Keeping the kernel inside
            # the shard_map is load-bearing: pallas_call has no GSPMD
            # sharding rule, so outside it XLA would all-gather the stacked
            # plane onto every chip.
            eta, mu = lr_at(self.opt, state.step), jnp.float32(self.opt.momentum)
            if self._codec_stateful:
                p_new, v_new, res_new = self.fused_gossip(
                    state.theta, state.opt.mu, grads, state.comm.residual,
                    active, round_idx, eta, mu)
                comm_new = comm.CommState(res_new)
            else:
                p_new, v_new = self.fused_gossip(
                    state.theta, state.opt.mu, grads, active, round_idx, eta, mu)
        else:
            if self._codec_stateful:
                exchanged, res_new = self._apply_gossip(
                    state.theta, state.comm.residual, active, round_idx)
                comm_new = comm.CommState(res_new)
            else:
                exchanged = self._apply_gossip(state.theta, active, round_idx)
            comm_delta = jax.tree.map(lambda a, b: a - b, exchanged, state.theta)
            p_new, v_new = self._nag(state.theta, state.opt.mu, grads, state.step)
            p_new = jax.tree.map(lambda p, d: p + d.astype(p.dtype), p_new, comm_delta)
        metrics = {"loss": jnp.mean(loss)}
        return state.replace(theta=p_new,
                             opt=OptState(state.opt.step + 1, v_new, {}),
                             comm=comm_new, step=state.step + 1), metrics

    def _make_gossip(self, mode: str):
        return gossip_dist.make_gossip_step(
            self.mesh, self.mesh_cfg, self.protocol, self.buf_specs,
            schedule_kind="hypercube" if self.protocol.topology == "matching" else "random",
            mode=mode, shard=self.shard)

    @property
    def _apply_gossip(self):
        """The raw mode="apply" program over flat-plane buffer dicts; with a
        stateful codec its signature is (bufs, residual_bufs, active, round)
        -> (exchanged_bufs, residual_bufs')."""
        if self._gossip_exchange is None:
            self._gossip_exchange = self._make_gossip("apply")
        return self._gossip_exchange

    def gossip_exchange(self, params_stack, active, round_idx):
        """ONE communication round applied to a stacked params PYTREE — the
        facade parity surface (a boundary: flatten in, unflatten out; the
        training loop itself never leaves the resident buffers). Stateful
        codecs run against a zero residual here (the live residual only
        advances inside the training step). With a sharded plane the pytree
        flattens to the UN-padded widths, so pad to the shard-padded totals
        on entry and slice the padding back off before unflattening."""
        spec = flat_plane.FlatSpec.build(params_stack, leading=1)
        bufs = spec.flatten(params_stack)
        widths = {k: b.shape[-1] for k, b in bufs.items()}
        if self.shard_layout is not None:
            from repro import shard as shard_plane
            bufs = shard_plane.pad_bufs(bufs, self.shard_layout)
        if self._codec_stateful:
            zeros = {k: jnp.zeros(b.shape, jnp.float32) for k, b in bufs.items()}
            out, _ = self._apply_gossip(bufs, zeros, active, round_idx)
        else:
            out = self._apply_gossip(bufs, active, round_idx)
        if self.shard_layout is not None:
            out = shard_plane.slice_bufs(out, widths)
        return spec.unflatten(out, like=params_stack)

    @property
    def fused_gossip(self):
        if self._fused_gossip is None:
            self._fused_gossip = self._make_gossip("fused")
        return self._fused_gossip

    @property
    def fused_nag(self):
        """Shard-mapped flat-plane NAG (full-manual: the Pallas kernel must
        only see local shards) — fused_nag(theta_bufs, v_bufs, g_bufs, eta,
        mu) -> (theta'_bufs, v'_bufs)."""
        if self._fused_nag is None:
            bspecs = self.buf_specs
            self._fused_nag = jax.shard_map(
                lambda p, v, g, eta, mu: ops.fused_bufs_nag(p, v, g, eta, mu),
                mesh=self.mesh,
                in_specs=(bspecs, bspecs, bspecs, P(), P()),
                out_specs=(bspecs, bspecs),
                axis_names=frozenset(self.mesh.axis_names), check_vma=False)
        return self._fused_nag

    # jit entry points ------------------------------------------------------
    def _shard(self, tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), tree,
                            is_leaf=lambda x: isinstance(x, P))

    def jit_train_step(self):
        bspec = self.batch_specs()
        return jax.jit(
            self._train_step,
            in_shardings=(self._shard(self.state_specs), self._shard(bspec),
                          NamedSharding(self.mesh, P())),
            out_shardings=(self._shard(self.state_specs), NamedSharding(self.mesh, P())),
            donate_argnums=(0,))

    def jit_train_gossip_step(self):
        bspec = self.batch_specs()
        rep = NamedSharding(self.mesh, P())
        return jax.jit(
            self._train_gossip_step,
            in_shardings=(self._shard(self.state_specs), self._shard(bspec),
                          NamedSharding(self.mesh, P(tuple(a for a in ("pod", "worker")
                                                           if a in self.mesh.axis_names))), rep),
            out_shardings=(self._shard(self.state_specs), rep),
            donate_argnums=(0,))
