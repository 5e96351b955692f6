"""Distributed gossip engine: shard_map + collective-permute matchings.

TPU-native realization of the communication-related component (DESIGN.md §3).
Replica parameters are stacked on a leading worker dim sharded over the
('pod', 'worker') mesh axes; one gossip round is ONE collective-permute of the
replica shard along a matching, followed by the (fusable) elastic update:

    theta <- theta - coef * gate * (theta - theta_peer)

The exchange runs on the **flat parameter plane** (repro.common.flat): the
replica shard is one lane-aligned buffer per dtype and the participation gate
rides in the tail element of the first buffer, so a round is exactly ONE
ppermute per dtype bucket (ONE total for the usual homogeneous-dtype tree)
instead of one per leaf plus one for the gate. Since the flat-resident
redesign the trainers pass the RESIDENT buffer dicts of
:class:`repro.api.state.FlatState` straight in — the internal
``FlatSpec.build``/``flatten``/``unflatten`` become structural no-ops
(single pre-aligned leaf per bucket: no pad, no concatenate, no copy) — while
plain parameter pytrees (the parity/oracle surface and older callers) still
flatten on entry exactly as before.

Matching schedules decompose over the mesh's gossip axes (hypercube dims on
'worker' then 'pod' — so cross-pod/DCN rounds are a distinct, less frequent
schedule entry, matching the bandwidth hierarchy). The round index and the
per-worker participation mask are *inputs*, so one compiled program serves
every round (lax.switch selects the static ppermute permutation).

Semantics vs. the simulation engine: restricted to perfect matchings, a round
here is EXACTLY Alg. 4 with peers given by the matching (tests assert
bit-equality against gossip_sim fed the same matching).
"""
from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import comm
from repro.api import registry
from repro.common import flat as flat_plane
from repro.common.config import MeshConfig, ProtocolConfig
from repro.core import topology

PyTree = Any

GOSSIP_AXES = ("pod", "worker")


def build_schedule(mesh_cfg: MeshConfig, kind: str = "hypercube", num_random_rounds: int = 16,
                   seed: int = 0) -> List[Tuple[str, List[Tuple[int, int]]]]:
    """List of (mesh_axis, ppermute_pairs) rounds, cycled by round index.

    hypercube: log2(workers_per_pod) rounds on 'worker' + log2(pods) on 'pod'.
    random: precomputed random matchings on 'worker' (+ the pod hypercube
    rounds appended, so cross-pod mixing still happens).
    """
    rounds: List[Tuple[str, List[Tuple[int, int]]]] = []
    if kind == "hypercube":
        if mesh_cfg.workers_per_pod > 1:
            rounds += [("worker", m) for m in topology.hypercube_schedule(mesh_cfg.workers_per_pod)]
        if mesh_cfg.pods > 1:
            rounds += [("pod", m) for m in topology.hypercube_schedule(mesh_cfg.pods)]
    elif kind == "random":
        if mesh_cfg.workers_per_pod > 1:
            rounds += [("worker", m) for m in
                       topology.random_matching_schedule(mesh_cfg.workers_per_pod, num_random_rounds, seed)]
        if mesh_cfg.pods > 1:
            rounds += [("pod", m) for m in topology.hypercube_schedule(mesh_cfg.pods)]
    else:
        raise ValueError(kind)
    assert rounds, "need at least 2 gossip workers"
    return rounds


def _gate_and_coef(cfg: ProtocolConfig, my_active, peer_active):
    """Per-protocol gate/coefficient for a matched pair (DESIGN.md §3) —
    deprecated shim over :meth:`repro.api.protocols.Protocol.pair_gate_coef`."""
    return registry.resolve(cfg).pair_gate_coef(my_active, peer_active)


def make_gossip_step(mesh: Mesh, mesh_cfg: MeshConfig, cfg: ProtocolConfig,
                     param_specs: PyTree, schedule_kind: str = "hypercube",
                     mode: str = "apply", shard=None):
    """Build gossip_step(params_stack, active[Wtot], round_idx).

    params_stack leaves: [Wtot_local..., ...] sharded per param_specs (leading
    dim over ('pod','worker')) — either a parameter pytree or, the trainers'
    hot path, the resident flat-plane buffer dict of a FlatState (for which
    the flatten below is the identity: no per-step copies). active: float32
    [num_workers] participation.

    mode="apply": returns the exchanged params_stack (elastic move applied in
    the exchange program — the facade parity surface and the unfused path).
    mode="peer":  returns (peer_stack, gate*coef [Wtot]) with the elastic move
    NOT applied (composition surface for external fused consumers/tests).
    mode="fused": the trainers' hot path — gossip_step(params_stack, velocity,
    grads, active, round_idx, eta, mu) -> (params', velocity'): the exchange
    AND the whole NAG + elastic update (Alg. 5 lines 3/7/9, simultaneous) in
    one shard-mapped program, so the fused Pallas kernel only ever sees the
    LOCAL replica shard (a pallas_call has no GSPMD sharding rule — outside
    shard_map XLA would all-gather the stacked plane onto every chip).

    In every mode the round's communication is one ppermute per dtype bucket
    of the flat plane (the participation gate rides in the first buffer's
    tail element), not one per leaf.

    When ``cfg.codec`` names a registered compression codec (repro.comm), the
    wire is the codec's PACKED uint8 buffer: each shard encodes its local
    plane before the ppermute (stochastic rounding seeded by (round, worker),
    matching the sim engine's stream) and decodes the peer's wire after — the
    collective moves compressed bytes, still exactly one ppermute per bucket.
    Stateful codecs (topk error feedback) additionally take/return the
    residual tree: every mode's signature gains a ``residual`` argument after
    the params and a residual output at the end.

    ``shard`` (a ShardConfig with ``enabled()``): the plane dim is ALSO
    sharded over ``shard.axes`` — each shard_map instance holds
    ``[1, shard_size]`` of the plane, the ppermute still runs along
    'worker'/'pod' (instances with equal shard coordinates exchange, so the
    wire is exactly the local shard), and the codec's rounding-seed
    coordinate becomes ``worker * n_shards + shard_index`` — the stream the
    sim engine replicates with its shard-rows reshape, keeping the wires
    bit-identical.
    """
    assert mode in ("apply", "peer", "fused"), mode
    schedule = build_schedule(mesh_cfg, schedule_kind)
    n_rounds = len(schedule)
    impl = registry.resolve(cfg)
    codec = comm.active_codec(cfg) if impl.pairwise else None
    stateful = codec is not None and codec.stateful
    gossip_axes = set(GOSSIP_AXES) & set(mesh.axis_names)

    # Full-manual over EVERY mesh axis, all modes (specs stay unfiltered).
    # The body is elementwise + ppermute, hence valid on the fully decomposed
    # shards — and the flat plane REQUIRES it: flattening a leaf whose
    # fsdp/model dims were left auto would make GSPMD all-gather the full
    # replica onto each chip before the concat (and a pallas_call has no
    # GSPMD sharding rule at all). Manual shards keep the exchange moving
    # shard-local bytes only.
    manual = frozenset(mesh.axis_names)

    sharded = shard is not None and shard.enabled()
    if sharded:
        missing = [a for a in shard.axes if a not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"shard axes {missing} not in mesh axes {mesh.axis_names}")

    def _worker_index():
        """Global worker index of the local shard (inside shard_map) — the
        codec's rounding-seed coordinate, matching the sim engine's
        ``jnp.arange(W)``."""
        idx = jnp.int32(0)
        if "pod" in mesh.axis_names:
            idx = jax.lax.axis_index("pod") * mesh_cfg.workers_per_pod
        if "worker" in mesh.axis_names:
            idx = idx + jax.lax.axis_index("worker")
        return idx

    def _seed_index():
        """Codec seed coordinate: the worker index, or — with the sharded
        plane — ``worker * n_shards + shard_index`` with the shard index
        folded row-major over ``shard.axes`` (GSPMD's tuple-axes order), so
        it matches the sim engine's shard-rows ``jnp.arange(W * S)``."""
        if not sharded:
            return _worker_index()
        s_idx = jnp.int32(0)
        for ax in shard.axes:
            s_idx = s_idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        return _worker_index() * shard.n_shards + s_idx

    def switch_exchange(bufs, act, round_idx):
        """ONE ppermute per dtype bucket (gate in the carrier's tail element):
        lax.switch selects the round's static permutation. Returns
        (peer_bufs, peer_act)."""
        buckets = list(bufs)
        carrier = buckets[0]

        def branch(axis_name, pairs):
            def fn(bufs):
                cat = jnp.concatenate(
                    [bufs[carrier],
                     jnp.reshape(act, (1, 1)).astype(bufs[carrier].dtype)], axis=-1)
                peer_cat = jax.lax.ppermute(cat, axis_name, pairs)
                peer = {carrier: peer_cat[:, :-1]}
                for k in buckets[1:]:
                    peer[k] = jax.lax.ppermute(bufs[k], axis_name, pairs)
                return peer, peer_cat[0, -1].astype(jnp.float32)
            return fn

        branches = [branch(ax, pairs) for ax, pairs in schedule]
        return jax.lax.switch(round_idx % n_rounds, branches, bufs)

    def exchange_flat(spec, bufs, residual, act, round_idx):
        """One gossip round over the local flat plane. Returns
        (peer_bufs, peer_act, new_residual_bufs_or_None).

        Uncompressed: the raw buffers ride the collective. With a codec: each
        shard encodes its plane, PACKS the wire into one uint8 buffer per
        bucket (gate in the tail byte) so the ppermute moves compressed bytes,
        and decodes the peer's wire on arrival. A stateful codec's residual
        only advances when THIS worker's own gate fired (mirroring the sim
        engine): mass encoded into a wire the partner discards stays in the
        residual instead of being dropped."""
        if codec is None:
            peer, peer_act = switch_exchange(bufs, act, round_idx)
            return peer, peer_act, None
        seeds = jnp.reshape(comm.codec_seeds(round_idx, _seed_index()), (1,))
        res_bufs = spec.flatten(residual) if stateful else {}
        wires, new_res = {}, {}
        for k, b in bufs.items():
            wire, r2 = codec.encode(b, seeds, residual=res_bufs.get(k))
            wires[k] = codec.pack(wire)
            if stateful:
                new_res[k] = jnp.where(act > 0, r2, res_bufs[k])
        peer_wires, peer_act = switch_exchange(wires, act, round_idx)
        peer = {k: codec.decode_wire(peer_wires[k], spec.totals[k]).astype(b.dtype)
                for k, b in bufs.items()}
        return peer, peer_act, (new_res if stateful else None)

    def local_update(params, residual, active_scalar, round_idx):
        # params: local replica shard, leading dim 1; active_scalar: scalar f32
        spec = flat_plane.FlatSpec.build(params, leading=1)
        bufs = spec.flatten(params)
        peer, peer_act, new_res = exchange_flat(spec, bufs, residual,
                                                active_scalar, round_idx)
        gate, coef = impl.pair_gate_coef(active_scalar, peer_act)
        gc = (gate * coef).astype(jnp.float32)
        if mode == "peer":
            out = (spec.unflatten(peer), jnp.reshape(gc, (1,)))
        else:
            # compute in the storage dtype: f32 upcasts would materialize two
            # full f32 copies of the replica shard (grok: +12 GB/chip). On TPU
            # the fused mode does the f32 math per-tile in VMEM instead.
            new = {k: b - gc.astype(b.dtype) * (b - peer[k]) for k, b in bufs.items()}
            out = (spec.unflatten(new),)
        if stateful:
            out = out + (spec.unflatten(new_res, like=residual),)
        return out[0] if len(out) == 1 else out

    def local_fused(params, velocity, grads, residual, active_scalar,
                    round_idx, eta, mu):
        # exchange + the entire NAG + elastic displacement in one pass over
        # the local flat plane (kernels/ops dispatches to the Pallas kernel on
        # TPU, the jnp oracle elsewhere)
        from repro.kernels import ops as kernel_ops
        spec = flat_plane.FlatSpec.build(params, leading=1)
        bufs = spec.flatten(params)
        vb, gb = spec.flatten(velocity), spec.flatten(grads)
        peer, peer_act, new_res = exchange_flat(spec, bufs, residual,
                                                active_scalar, round_idx)
        gate, coef = impl.pair_gate_coef(active_scalar, peer_act)
        gc = (gate * coef).astype(jnp.float32)
        out_t, out_v = kernel_ops.fused_bufs_elastic_nag(bufs, peer, vb, gb,
                                                         gc, eta, mu)
        outs = (spec.unflatten(out_t), spec.unflatten(out_v, like=velocity))
        if stateful:
            outs = outs + (spec.unflatten(new_res, like=residual),)
        return outs

    active_spec = P(tuple(a for a in GOSSIP_AXES if a in gossip_axes))

    if mode == "fused":
        if stateful:
            @jax.jit
            def gossip_step(params_stack, velocity, grads, residual, active,
                            round_idx, eta, mu):
                fn = jax.shard_map(
                    lambda p, v, g, r, a, e, m: local_fused(p, v, g, r, a[0],
                                                            round_idx, e, m),
                    mesh=mesh,
                    in_specs=(param_specs, param_specs, param_specs, param_specs,
                              active_spec, P(), P()),
                    out_specs=(param_specs, param_specs, param_specs),
                    axis_names=manual, check_vma=False,
                )
                return fn(params_stack, velocity, grads, residual, active, eta, mu)
        else:
            @jax.jit
            def gossip_step(params_stack, velocity, grads, active, round_idx, eta, mu):
                fn = jax.shard_map(
                    lambda p, v, g, a, e, m: local_fused(p, v, g, None, a[0],
                                                         round_idx, e, m),
                    mesh=mesh,
                    in_specs=(param_specs, param_specs, param_specs, active_spec,
                              P(), P()),
                    out_specs=(param_specs, param_specs),
                    axis_names=manual, check_vma=False,
                )
                return fn(params_stack, velocity, grads, active, eta, mu)
    elif stateful:
        out_specs = ((param_specs, param_specs) if mode == "apply"
                     else (param_specs, active_spec, param_specs))

        @jax.jit
        def gossip_step(params_stack, residual, active, round_idx):
            fn = jax.shard_map(
                lambda p, r, a: local_update(p, r, a[0], round_idx),
                mesh=mesh,
                in_specs=(param_specs, param_specs, active_spec),
                out_specs=out_specs,
                axis_names=manual, check_vma=False,
            )
            return fn(params_stack, residual, active)
    else:
        out_specs = param_specs if mode == "apply" else (param_specs, active_spec)

        @jax.jit
        def gossip_step(params_stack, active, round_idx):
            fn = jax.shard_map(
                lambda p, a: local_update(p, None, a[0], round_idx),
                mesh=mesh,
                in_specs=(param_specs, active_spec),
                out_specs=out_specs,
                axis_names=manual, check_vma=False,
            )
            return fn(params_stack, active)

    gossip_step.num_rounds = n_rounds
    gossip_step.schedule = schedule
    gossip_step.stateful_codec = stateful
    return gossip_step


def partner_of(schedule, round_idx: int, worker: int, mesh_cfg: MeshConfig) -> int:
    """Host-side: global worker index of `worker`'s partner in round_idx
    (for logging / parity tests vs. the simulation engine)."""
    axis, pairs = schedule[round_idx % len(schedule)]
    wpp = mesh_cfg.workers_per_pod
    pod, w = divmod(worker, wpp)
    part = dict(pairs)
    if axis == "worker":
        return pod * wpp + part[w]
    return part[pod] * wpp + w
