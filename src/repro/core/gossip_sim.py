"""Simulation engine: exact Algorithms 1-6 on stacked replicas.

Replicas live RESIDENT on the flat parameter plane (:mod:`repro.common.flat`):
the trainer state is a :class:`repro.api.state.FlatState` whose params and
velocity are ONE lane-aligned ``[W, total]`` buffer per dtype bucket,
flattened once at :meth:`SimTrainer.init` and never re-flattened per step.
One jitted step does: per-worker gradients via vmap — differentiated directly
w.r.t. the resident buffers, so gradient buffers arrive already flat through
the unflatten views at the loss boundary — the protocol's gradient transform,
the NAG velocity update (Alg. 5 line 3), the gated communication-related
component (line 7, a mixing einsum per dtype bucket instead of per leaf), and
the parameter update (line 9) — all computed simultaneously from the step-t
state, exactly as the paper specifies (§2.3). Pytrees appear only at the
boundaries (``state.params`` lazy views for eval/checkpoint).

This is the engine used for the paper-reproduction benchmarks (W in {4, 8},
like the thesis); the distributed shard_map engine (gossip_dist.py) is
validated against it.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import comm
from repro.api import registry
from repro.api.state import FlatState
from repro.common import flat as flat_plane
from repro.common.config import OptimizerConfig, ProtocolConfig
from repro.common.pytree import tree_take_leading
from repro.core import protocols
from repro.kernels import ops
from repro.optim.optimizers import OptState, _clip, make_optimizer, param_update, velocity_update
from repro.optim.schedule import lr_at

PyTree = Any

# Deprecated alias: the sim engine's state IS the engine-agnostic FlatState
# (repro.api.state) since the flat-resident redesign.
SimState = FlatState


class SimTrainer:
    """Single-controller trainer over W simulated workers.

    loss_fn(params, x, y) -> scalar loss for ONE worker's replica/batch
    (``params`` is the single-replica pytree view of the resident plane).
    """

    # host-resident FlatState plane (repro.fleet): only the async engine's
    # event-window execution model can stream window rows from host RAM
    _supports_host_plane = False

    def __init__(self, loss_fn: Callable, num_workers: int,
                 protocol: ProtocolConfig, optimizer: OptimizerConfig,
                 fused_update: bool = True, faults=None, fleet=None,
                 shard=None):
        self.loss_fn = loss_fn
        self.num_workers = num_workers
        self.protocol = protocol
        self.optimizer_cfg = optimizer
        self.optimizer = make_optimizer(optimizer)
        self._impl = registry.resolve(protocol)
        # fused flat-plane path (one pass for Alg. 5 lines 3/7/9): pairwise
        # protocols + NAG only — allreduce/EASGD/none keep the per-bucket path
        # (registry capability flags, not method strings).
        self.fused_update = (fused_update and optimizer.name == "nag"
                             and self._impl.pairwise)
        # gossip-compression codec (repro.comm): pairwise protocols only
        # (enforced by Protocol.__init__); None when cfg.codec == "none"
        self.codec = comm.active_codec(protocol)
        # message-level fault plane (repro.faults): hash-seeded drop/corrupt
        # masks + Byzantine garbling injected at the wire boundary. None (no
        # FaultConfig) keeps the engine's traces byte-identical to the
        # fault-free build.
        self.faults = faults
        self.fault_model = None
        if faults is not None:
            from repro.faults import resolve_fault_model
            self.fault_model = resolve_fault_model(faults)
        # registered THIRD-PARTY protocols may override comm_update with the
        # pre-FlatState signature (no wire_bytes / wire_faults kwargs) —
        # detect once and fall back for them
        try:
            import inspect
            sig = inspect.signature(self._impl.comm_update).parameters.values()
            self._pass_wire_bytes = any(
                p.name == "wire_bytes" or p.kind is inspect.Parameter.VAR_KEYWORD
                for p in sig)
            self._pass_wire_faults = any(
                p.name == "wire_faults" or p.kind is inspect.Parameter.VAR_KEYWORD
                for p in sig)
        except (TypeError, ValueError):
            self._pass_wire_bytes = False
            self._pass_wire_faults = False
        fm = self.fault_model
        if (fm is not None and (fm.injects_drop or fm.injects_corrupt)
                and self._impl.pairwise and not self._pass_wire_faults):
            raise ValueError(
                f"fault model {fm.name!r} discards wires, but protocol "
                f"{protocol.method!r} overrides comm_update without a "
                "wire_faults kwarg — it cannot honor the discard")
        # fleet plane (repro.fleet): partitioned exchanges + token-account
        # flow control + plane residency. The all-default FleetConfig is
        # INERT — no trace ops are added, so the non-fleet step program is
        # reproduced bit-exactly by construction.
        self.fleet = fleet
        self.flow = None
        self.partition = 1
        self._plans: dict = {}
        if fleet is not None and fleet.enabled():
            from repro.fleet import flow as fleet_flow
            self.flow = fleet_flow.resolve_flow_control(fleet)
            self.partition = int(fleet.partition)
            if self.partition < 1:
                raise ValueError(f"partition must be >= 1, got {fleet.partition}")
            if self.partition > 1 and not self._impl.pairwise:
                raise ValueError(
                    f"partitioned exchanges need a pairwise protocol; "
                    f"{protocol.method!r} is not pairwise")
            if fleet.plane == "host" and not self._supports_host_plane:
                raise ValueError(
                    "plane='host' (host-resident FlatState) requires the "
                    "async engine — use GossipTrainer(engine='async') / "
                    "launch.train --engine async")
        # sharded plane (repro.shard): bucket totals split into equal device
        # shards — the sim engine realizes the per-shard wire semantically
        # (shard-rows reshape at the codec boundary + per-device accounting).
        # The all-default ShardConfig is INERT: no layout is built, no trace
        # ops are added, so the un-sharded step program is reproduced
        # bit-exactly by construction.
        self.shard = shard
        self.shard_layout = None
        if shard is not None and shard.enabled():
            if not self._impl.pairwise:
                raise ValueError(
                    f"sharded plane (repro.shard) needs a pairwise protocol; "
                    f"{protocol.method!r} is not pairwise")
            if faults is not None:
                raise ValueError(
                    "the fault plane (repro.faults) garbles/checksums whole "
                    "replica wires; it does not compose with the sharded "
                    "plane (repro.shard) yet")
            if fleet is not None and fleet.enabled() and fleet.plane == "host":
                raise ValueError(
                    "plane='host' streams whole host rows; it does not "
                    "compose with the sharded plane (repro.shard) yet")
        # telemetry plane (repro.obs): attached by the facade AFTER build;
        # None (the default) keeps step() the bare jitted dispatch — zero
        # trace ops, zero host work, the ObsConfig inert anchor
        self.obs = None
        # gate/partner draws re-derived from the pre-step key — pure
        # functions of it, shared by the async clock program and the
        # host-side observer (both replay exactly what the step consumed)
        self._draw_fn = jax.jit(self._draws)
        # donate the resident state so the flat buffers update in place
        # instead of doubling HBM residency every step
        self._step_fn = jax.jit(self._step, donate_argnums=(0,),
                                static_argnames=("defer_comm",))

    def _wire_bytes(self, spec: flat_plane.FlatSpec) -> float:
        """Exact per-replica wire bytes from the STATIC spec (trace-time
        shape math, no cache): the resident buffers carry lane padding, so
        deriving raw bytes from their shapes would over-count — the raw size
        sums the unpadded slot sizes; a codec wire is genuinely the padded
        plane (what actually ships). With a sharded plane the account is
        per-DEVICE egress: each device ships only its local shard, so the
        whole-plane wire divides exactly by n_shards (equal quantum-aligned
        shards; raw per-shard wires sum exactly to the un-sharded wire)."""
        if self.codec is None:
            wire = float(sum(s.size * s.dtype.itemsize for s in spec.slots))
        else:
            wire = float(comm.wire_param_bytes(self.codec, spec))
        if self.shard_layout is not None:
            wire /= self.shard_layout.n_shards
        return wire

    def _fleet_plan(self, spec: flat_plane.FlatSpec):
        """Static PartitionPlan for ``spec`` (cached — spec is hashable).
        Partition chunks are defined on the GLOBAL (shard-padded) totals and
        realized on local shards: with a sharded plane each device ships its
        1/n_shards columns of the scheduled chunk, so the plan's per-chunk
        wire accounts scale by 1/n_shards (mean per-device egress)."""
        plan = self._plans.get(spec)
        if plan is None:
            import dataclasses as _dc

            from repro.fleet.partition import build_plan
            plan = build_plan(spec, self.partition, self.codec)
            if self.shard_layout is not None:
                S = self.shard_layout.n_shards
                plan = _dc.replace(
                    plan, wire_bytes=tuple(w / S for w in plan.wire_bytes))
            self._plans[spec] = plan
        return plan

    def _fleet_proto_seed(self, proto):
        """Seed the fleet-plane ProtocolState fields so the state pytree
        structure is stable across steps (comm updates _replace in place)."""
        if self.flow is not None:
            proto = proto._replace(
                tokens=self.flow.init_tokens(self.num_workers),
                flow_skipped=jnp.zeros((), jnp.int32))
        if self.partition > 1:
            proto = proto._replace(
                chunk_units=jnp.zeros((self.partition,), jnp.int32))
        return proto

    def init(self, params_stack: PyTree, seed: int = 0) -> FlatState:
        """Flatten ONCE: the returned state holds the resident buffers; the
        ``params_stack`` pytree is not referenced again."""
        spec = flat_plane.FlatSpec.build(params_stack, leading=1)
        theta = spec.flatten(params_stack)
        if self.shard is not None and self.shard.enabled():
            # sharded plane: pad every bucket to n_shards equal quantum-
            # aligned shards (tail-only, so leaf views are untouched) and
            # re-bind the spec to the padded totals — the resident state,
            # optimizer/protocol/residual buffers all follow the padded
            # widths from here on.
            from repro import shard as shard_plane
            self.shard_layout = shard_plane.build_layout(
                spec, self.shard, self.codec)
            spec = shard_plane.padded_spec(spec, self.shard_layout)
            theta = shard_plane.pad_bufs(theta, self.shard_layout)
        proto = self._impl.init_state(theta)
        if self.fault_model is not None:
            # seed the fault counters so the state pytree structure is stable
            # across steps (comm_update _replaces them in place)
            proto = proto._replace(wire_dropped=jnp.zeros((), jnp.int32),
                                   wire_corrupt=jnp.zeros((), jnp.int32))
        proto = self._fleet_proto_seed(proto)
        state = FlatState(
            spec=spec,
            theta=theta,
            opt=self.optimizer.init(theta),
            proto=proto,
            comm=comm.init_comm_state(self.codec, theta),
            key=jax.random.PRNGKey(seed),
            step=jnp.zeros((), jnp.int32))
        # commit the state to the device it is on: the step returns committed
        # arrays, so an uncommitted first state would compile the whole step a
        # second time, for its first call alone
        return jax.tree.map(lambda x: jax.device_put(x, x.sharding), state)

    def _codec_transmit(self, state: FlatState, active, publish=None,
                        col_gate=None):
        """decode(encode(theta)) on the resident plane: what peers RECEIVE
        this round, plus the advanced error-feedback residual (already flat
        f32 buffers in ``state.comm``). Seeds derive from (comm round counter,
        worker index) — the same stream the dist engine uses. Wrapped in
        lax.cond so non-firing steps skip the whole encode/decode pass (the
        identity mix would ignore the transmit anyway); inside a firing
        round, a stateful codec's residual advances per worker, gated by that
        worker's OWN participation (matching the dist engine) so wire mass a
        receiver discards is carried forward. ``publish`` (optional) is what
        workers put on the wire instead of ``state.theta`` — the fault
        plane's Byzantine garbling hook. ``col_gate`` (optional,
        ``{bucket: bool[W, N]}``) restricts the residual advance per COLUMN
        too — the partition plane's gate: only the chunk a worker actually
        shipped carries its wire mass forward.

        With a sharded plane (repro.shard) the codec runs per SHARD, not per
        replica: the ``[W, total]`` buffers reshape to ``[W*S, shard_size]``
        rows (contiguous — shard boundaries are codec-block aligned by
        layout construction, so the block layout is IDENTICAL to the
        whole-plane encode) and row ``w*S + s`` seeds from worker-coordinate
        ``w*S + s`` — exactly the stream a sharded dist device uses, which is
        what keeps sim and dist wires bit-identical under shard ∘ q8/topk."""
        codec = self.codec
        layout = self.shard_layout
        if publish is None:
            publish = state.theta

        def fire():
            res = state.comm.residual if codec.stateful else None
            gate = jnp.asarray(active)
            if layout is not None:
                S = layout.n_shards
                publish_w = layout.shard_rows(publish)
                res = layout.shard_rows(res) if res is not None else None
                seeds = comm.codec_seeds(
                    state.proto.comm_rounds,
                    jnp.arange(self.num_workers * S))
                gate = jnp.repeat(gate, S).reshape(-1, 1)
                if col_gate is not None:
                    gate = {k: gate & layout.shard_rows(col_gate)[k]
                            for k in publish_w}
            else:
                publish_w = publish
                seeds = comm.codec_seeds(state.proto.comm_rounds,
                                         jnp.arange(self.num_workers))
                gate = gate.reshape(-1, 1)
                if col_gate is not None:
                    gate = {k: gate & col_gate[k] for k in publish_w}
            hat, new_res = comm.roundtrip_bufs(codec, publish_w, seeds, res,
                                               gate=gate)
            if layout is not None:
                hat = layout.unshard_rows(hat)
                if new_res is not None:
                    new_res = layout.unshard_rows(new_res)
            # decode reconstructs in f32; match the storage dtype so both
            # cond branches agree (and mixing casts exactly like the wire)
            hat = {k: v.astype(state.theta[k].dtype) for k, v in hat.items()}
            comm_new = comm.CommState(new_res) if codec.stateful else state.comm
            return hat, comm_new

        def skip():
            # transmit := theta makes apply_mix_split exactly apply_mix
            return state.theta, state.comm

        return jax.lax.cond(jnp.any(active), fire, skip)

    def _codec_transmit_checked(self, state: FlatState, active, publish,
                                corrupt_mask, col_gate=None):
        """:meth:`_codec_transmit` through the PACKED uint8 wire with a
        checksum tail and in-flight corruption (repro.faults): per bucket,
        encode -> pack -> append checksum -> corrupt -> verify -> decode.
        Returns (transmit, comm_state', ok bool[W]); rows failing
        verification are zeroed (they are discarded at the mix, never
        applied — zeroing keeps NaN bytes out of the einsum)."""
        from repro.faults import wire as fwire
        from repro.faults.models import SALT_BYTE
        codec = self.codec
        if publish is None:
            publish = state.theta
        fseed = self.faults.seed

        def fire():
            seeds = comm.codec_seeds(state.proto.comm_rounds,
                                     jnp.arange(self.num_workers))
            gate = jnp.asarray(active).reshape(-1, 1)
            res_bufs = state.comm.residual if codec.stateful else {}
            res_bufs = res_bufs or {}
            hat, new_res, ok = {}, {}, None
            for i, k in enumerate(sorted(publish)):
                b = publish[k]
                r = res_bufs.get(k)
                if r is None and codec.stateful:
                    r = jnp.zeros(b.shape, jnp.float32)
                wire_arrays, r2 = codec.encode(b, seeds, r)
                packed = fwire.append_checksum(codec.pack(wire_arrays))
                packed = fwire.corrupt_wire(packed, corrupt_mask, fseed,
                                            state.step, SALT_BYTE + i)
                payload, ok_b = fwire.verify_strip(packed)
                dec = codec.decode(codec.unpack(payload, b.shape[1]), b.shape[1])
                dec = jnp.where(ok_b[:, None], dec, jnp.zeros((), dec.dtype))
                hat[k] = dec.astype(state.theta[k].dtype)
                ok = ok_b if ok is None else ok & ok_b
                if codec.stateful:
                    g = gate if col_gate is None else gate & col_gate[k]
                    new_res[k] = jnp.where(g, r2, r)
            comm_new = comm.CommState(new_res) if codec.stateful else state.comm
            return hat, comm_new, ok

        def skip():
            return state.theta, state.comm, jnp.ones((self.num_workers,), bool)

        return jax.lax.cond(jnp.any(active), fire, skip)

    # -- one synchronous step across all workers ---------------------------
    def _step(self, state: FlatState, x, y, worker_mask=None,
              defer_comm: bool = False):
        """One step over the stacked workers. ``worker_mask`` is the
        virtual-time window hook used by the async engine
        (:mod:`repro.core.gossip_async`): ``None`` here (the synchronous
        engine) — a trace-time constant, so the sim jaxpr is unchanged. With a
        mask, only in-window workers may initiate an exchange and commit their
        update (out-of-window rows are kept bit-exactly); the async engine
        dispatches full-fleet windows through the maskless signature, i.e.
        through THIS very program, which is what makes its homogeneous-fleet
        degenerate case bit-exact against the sim engine."""
        cfg = self.protocol
        spec = state.spec
        row_spec = spec.with_lead(())
        key, sel_key, gate_key = jax.random.split(state.key, 3)

        # gradient-related component (Alg. 5 line 2), per worker — the loss
        # reads the single-replica pytree VIEW of its buffer row, and autodiff
        # through the views returns the gradients already on the flat plane
        def one_loss(bufs, xi, yi):
            return self.loss_fn(row_spec.views(bufs), xi, yi)

        losses, grads = jax.vmap(jax.value_and_grad(one_loss))(state.theta, x, y)
        with jax.named_scope("grad_mean"):
            grads = protocols.gradient_transform(cfg, grads)

        # communication-related component (lines 4-8), simultaneous, directly
        # on the resident buffers (one mixing einsum per dtype bucket)
        active = protocols.comm_gate(cfg, gate_key, state.step, self.num_workers)
        if worker_mask is not None:
            # async window: only in-window workers (at a step boundary) may
            # INITIATE an exchange; out-of-window workers still respond
            # passively through the mixing matrix with their last published row
            active = jnp.logical_and(active, worker_mask)

        # token-account flow control (repro.fleet): a worker whose gate fired
        # but whose account cannot cover the spend SKIPS the initiation — the
        # wire never carries it, so it never reaches comm_units/comm_bytes
        # (applied-exchange accounting); skips land in flow_skipped instead.
        proto0 = state.proto
        if self.flow is not None:
            allowed = self.flow.allow(state.step, proto0.tokens)
            skipped = jnp.sum((active & ~allowed).astype(jnp.int32))
            active = jnp.logical_and(active, allowed)
            stepped = (worker_mask if worker_mask is not None
                       else jnp.ones((self.num_workers,), bool))
            proto0 = proto0._replace(
                tokens=self.flow.update(proto0.tokens, stepped, active),
                flow_skipped=proto0.flow_skipped + skipped)

        # partition plane (repro.fleet): hash-scheduled chunk per initiator,
        # pure in (fleet seed, worker, step) — sim and async agree
        part_ids = col_gate = None
        if self.partition > 1:
            from repro.fleet.partition import partition_ids
            part_ids = partition_ids(self.fleet.seed, state.step,
                                     self.num_workers, self.partition)
            if self.codec is not None:
                plan = self._fleet_plan(spec)
                col_gate = {
                    b: part_ids[:, None] == jnp.asarray(
                        plan.col_chunks(b, state.theta[b].shape[1]))[None, :]
                    for b in state.theta}

        if defer_comm:
            # async message mode: exchanges live in the host pending-wire
            # queue (dispatch at this window, apply at arrival) — the step
            # program keeps its PRNG splits and the pure local update, and
            # skips the in-program mixing entirely
            theta_comm, proto_new, comm_new = (state.theta, proto0,
                                               state.comm)
            return self._step_epilogue(state, worker_mask, theta_comm,
                                       proto_new, comm_new, grads, losses,
                                       active, key)

        # message-level fault plane (repro.faults), injected at the WIRE
        # boundary so codecs/kernels are untouched: Byzantine rows garble what
        # they publish; drop/corrupt draws are pure hashes of
        # (fault seed, worker, step); discarding happens inside comm_update.
        fm = self.fault_model
        publish = corrupt_mask = dropped = detected = None
        if fm is not None:
            if fm.injects_byzantine and fm.num_byzantine(self.num_workers) > 0:
                publish = fm.garble_bufs(state.theta, state.step, self.num_workers)
            if fm.injects_corrupt:
                corrupt_mask = fm.corrupt_mask_jnp(state.step, self.num_workers)
            if fm.injects_drop:
                dropped = fm.drop_mask_jnp(state.step, self.num_workers)

        if self.codec is not None:
            with jax.named_scope("codec"):
                if corrupt_mask is not None:
                    transmit, comm_new, ok = self._codec_transmit_checked(
                        state, active, publish, corrupt_mask, col_gate)
                    detected = ~ok
                else:
                    transmit, comm_new = self._codec_transmit(
                        state, active, publish, col_gate)
        elif corrupt_mask is not None:
            # uncompressed wire: bitcast -> checksum -> corrupt -> verify
            from repro.faults import wire as fwire
            transmit, ok = fwire.corrupt_roundtrip_bufs(
                publish if publish is not None else state.theta,
                corrupt_mask, self.faults.seed, state.step)
            detected = ~ok
            comm_new = state.comm
        elif publish is not None:
            # Byzantine garbage rides the (uncompressed) transmit path
            transmit, comm_new = publish, state.comm
        else:
            transmit, comm_new = None, state.comm

        wire_faults = None
        if dropped is not None or detected is not None:
            from repro.api.protocols import WireFaults
            wire_faults = WireFaults(dropped=dropped, corrupt=detected)

        with jax.named_scope("mix"):
            if part_ids is not None:
                from repro.fleet.partition import partitioned_comm_update
                theta_comm, proto_new = partitioned_comm_update(
                    self._impl, sel_key, active, state.theta, proto0,
                    step=state.step, transmit=transmit, wire_faults=wire_faults,
                    part_ids=part_ids, plan=self._fleet_plan(spec))
            else:
                kw = ({"wire_bytes": self._wire_bytes(spec)}
                      if self._pass_wire_bytes else {})
                theta_comm, proto_new = protocols.comm_update(
                    cfg, sel_key, active, state.theta, proto0, step=state.step,
                    transmit=transmit, wire_faults=wire_faults, **kw)
        return self._step_epilogue(state, worker_mask, theta_comm, proto_new,
                                   comm_new, grads, losses, active, key)

    def _step_epilogue(self, state, worker_mask, theta_comm, proto_new,
                       comm_new, grads, losses, active, key):
        """Optimizer update + metrics — the tail of :meth:`_step`, shared by
        the normal path and the async message-mode (``defer_comm``) path."""
        with jax.named_scope("update"):
            if self.fused_update:
                # fused flat-plane path: lines 3, 7 and 9 in ONE pass per dtype
                # bucket, in place (donated buffers alias the kernel outputs).
                # Setting peer := theta_comm and coef := 1 makes the kernel's
                # elastic term exactly the comm displacement theta_comm - theta,
                # for ANY pairwise mixing (incl. fan-in > 1).
                ocfg = self.optimizer_cfg
                grads_c = _clip(ocfg, grads)
                eta = lr_at(ocfg, state.opt.step)
                theta_new, v_new = ops.fused_bufs_elastic_nag(
                    state.theta, theta_comm, state.opt.mu, grads_c,
                    jnp.ones((self.num_workers,), jnp.float32),
                    eta, ocfg.momentum)
                opt_new = OptState(state.opt.step + 1, v_new, {})
            else:
                # per-bucket reference path (the fused path's parity target)
                # elastic/gossip displacement relative to theta_t:
                comm_delta = jax.tree.map(lambda a, b: a - b, theta_comm, state.theta)

                # optimizer update (lines 3 & 9)
                if self.optimizer_cfg.name == "nag":
                    v_new, opt_new = velocity_update(self.optimizer_cfg, state.opt, grads)
                    # clip the -eta*g term too: velocity_update clips internally,
                    # and make_optimizer("nag") uses the clipped grads for BOTH
                    # terms — so must line 9 here (and the fused path does)
                    theta_grad = param_update(self.optimizer_cfg, state.opt.step,
                                              state.theta,
                                              _clip(self.optimizer_cfg, grads), v_new)
                else:
                    theta_grad, opt_new = self.optimizer.update(grads, state.opt, state.theta)

                theta_new = jax.tree.map(lambda tg, d: tg + d.astype(tg.dtype),
                                         theta_grad, comm_delta)

        metrics = {
            "loss_mean": jnp.mean(losses),
            "loss_max": jnp.max(losses),
            "comm_active": jnp.sum(active.astype(jnp.int32)),
        }
        if worker_mask is not None:
            # async only: keep out-of-window rows bit-exactly (defined by
            # AsyncTrainer; clock/staleness bookkeeping runs in a separate
            # micro-program so full windows reuse the maskless trace)
            theta_new, opt_new, metrics = self._finalize_window(
                state, worker_mask, theta_new, opt_new, losses, metrics)
        return state.replace(theta=theta_new, opt=opt_new, proto=proto_new,
                             comm=comm_new, key=key,
                             step=state.step + 1), metrics

    def _draws(self, key0, step0):
        """Gate/partner draws for the step that consumed ``key0`` — pure
        functions of the pre-step key, recomputed host-side by the async
        clock program and the observer (the step program split the same key
        and consumed the same draws)."""
        _, sel_key, gate_key = jax.random.split(key0, 3)
        gate = protocols.comm_gate(self.protocol, gate_key, step0,
                                   self.num_workers)
        peers = self._impl.sample_peers(sel_key, self.num_workers)
        return gate, peers

    def step(self, state: FlatState, x, y):
        if self.obs is None:
            return self._step_fn(state, x, y)
        # observation path: copy the pre-step key/step/tokens BEFORE the
        # donated dispatch (the async engine's capture pattern), then let the
        # observer re-derive this step's draws host-side — the jitted program
        # and its inputs are byte-identical to the unobserved path
        t_start = self.obs.now()
        key0, step0 = jnp.array(state.key), jnp.array(state.step)
        tokens0 = (jnp.array(state.proto.tokens) if self.flow is not None
                   else None)
        state, m = self._step_fn(state, x, y)
        self.obs.on_sim_step(self, t_start, key0, step0, tokens0)
        return state, m

    # -- evaluation helpers (pytree boundary: lazy views) --------------------
    def rank0_params(self, state: FlatState) -> PyTree:
        return tree_take_leading(state.params, 0)

    def aggregate_params(self, state: FlatState) -> PyTree:
        """Parameter average across workers (paper 'Aggregate Accuracy') —
        the shared flat-native consensus reduction (one einsum over the
        resident ``[W, total]`` buffers, no pytree stacking)."""
        from repro.serving.engine import consensus_params
        return consensus_params(state)
