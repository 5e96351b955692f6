"""Observer — binds an :class:`repro.common.config.ObsConfig` to a
:class:`TraceRecorder` / :class:`MetricsSink` and hangs off the engine hooks.

The cardinal rule (the inert-anchor contract): observation NEVER adds device
ops to a step program. Every event is reconstructed host-side from values the
engines already materialize —

- gate/partner draws are pure functions of the PRE-step PRNG key, re-derived
  through the engine's own ``_draw_fn`` (the async clock program's pattern);
- flow-control admission replays ``FlowControl.allow_np`` on the pre-step
  token balances (bit-identical host mirror of the traced gate);
- fault drop/corrupt draws replay the pure ``(seed, worker, step)`` hashes
  (``FaultModel.drop_mask`` / ``corrupt_mask``);
- partition chunk ids replay ``partition_ids_np``;
- message-mode wire events are emitted by the async pending queue itself,
  which is host code to begin with;
- metrics counters are DELTAS of the engine's ``ProtocolState`` accumulators
  (one batched ``jax.device_get`` per sampled step) — sink totals equal the
  state's totals exactly, by construction.

Timestamps: VIRTUAL seconds on the async engine's worker tracks, host seconds
on the profiler's clock everywhere else (the trainer track mixes in host time
under ``engine="async"`` — a documented, deliberate asymmetry: virtual time is
the async engine's semantic clock). The profiler's host clock is the wall
clock ``time.time_ns()`` reads, the clock ``jax.profiler`` stamps host spans
with (``train_step``) and aligns the device's ops to, so an exported run
overlays a device trace of the same run.

The harvest is PIPELINED one step behind: each hook dispatches its device
reads (the ``_draw_fn`` draws, a jitted donation-safe snapshot of the
``ProtocolState`` accumulators) without blocking and materializes the
PREVIOUS step's reads — by then they are computed, so the ``device_get``
overlaps with the step the engine just dispatched instead of stalling it.
That one-step lag is why the recording overhead stays in the low single
digits; :meth:`flush` (called by :meth:`export`) drains the last pending
step. The snapshot copies are what make the lag safe against the engines'
donated step buffers.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

from repro.obs.metrics import MetricsSink
from repro.obs.trace import TraceRecorder

# ProtocolState scalar accumulators mirrored into the metrics stream (the
# fields are Optional — only the ones the run's planes seeded are read)
PROTO_COUNTERS = (
    "comm_rounds", "comm_units", "comm_bytes",
    "stale_time", "stale_steps", "stale_events",
    "wire_dropped", "wire_corrupt", "exch_timeouts", "exch_retries",
    "flow_skipped",
)
# small per-worker / per-chunk arrays, recorded as lists
PROTO_ARRAYS = ("tokens", "chunk_units")


class Observer:
    """One per recording ``GossipTrainer`` (see module docstring)."""

    def __init__(self, cfg, engine: str, num_workers: int):
        self.cfg = cfg
        self.engine = engine
        self.num_workers = num_workers
        self.trace: Optional[TraceRecorder] = (
            TraceRecorder(cfg.max_events) if cfg.trace_enabled() else None)
        self.sink: Optional[MetricsSink] = (
            MetricsSink(cfg.metrics_path or None)
            if cfg.metrics_enabled() else None)
        self._prev: Dict[str, float] = {}
        self._exported = False
        # one-step-deferred harvest state (see module docstring)
        self._pending_trace = None
        self._pending_row = None
        self._snap_fn = None

    # ------------------------------------------------------------ utilities
    @staticmethod
    def now() -> float:
        """Host seconds on the profiler's clock (see module docstring)."""
        return time.time_ns() * 1e-9

    def want(self, step: int) -> bool:
        return step % max(1, self.cfg.sample_every) == 0

    def event(self, ev: str, t: float, step: int, worker: int = -1,
              **fields) -> None:
        if self.trace is not None:
            self.trace.emit(ev, t, step, worker, **fields)

    # ---------------------------------------------------------- engine hooks
    def on_sim_step(self, trainer, t_start: float, key0, step0,
                    tokens0) -> None:
        """Synchronous engine: one whole-fleet compute span (wall time) plus
        the step's exchange/fault/flow/chunk events re-derived from the
        pre-step key (dispatched now, harvested one step later)."""
        if self.trace is None:
            return
        step = int(step0)   # pre-step scalar copy: already materialized
        if not self.want(step):
            self._flush_trace()
            return
        t = self.now()
        self.event("compute", t_start, step, worker=-1, dur=t - t_start)
        self._defer_exchanges(trainer, t, step, key0, step0, tokens0,
                              mask=None)

    def on_async_window(self, trainer, t: float, mask, nxt, clocks0,
                        key0, step0, tokens0) -> None:
        """Async engine: per-worker compute spans in VIRTUAL time plus (in
        normal mode) the window's exchange events at window time ``t``.
        Message-mode wire events come from the pending queue instead."""
        if self.trace is None:
            return
        step = int(step0)
        if not self.want(step):
            self._flush_trace()
            return
        for w in np.nonzero(mask)[0]:
            w = int(w)
            self.event("compute", float(clocks0[w]), step, worker=w,
                       dur=float(nxt[w]) - float(clocks0[w]))
        if getattr(trainer, "_message_mode", False):
            self._flush_trace()
        else:
            self._defer_exchanges(trainer, t, step, key0, step0, tokens0,
                                  mask=np.array(mask, copy=True))

    def on_dist_step(self, backend, t_start: float, step: int, fire,
                     active, rnd: int) -> None:
        """Distributed engine: everything is already host-side — the schedule
        poll gives fire/active/round, the matching gives the partners, and
        the per-device wire bytes are static. Nothing to defer."""
        if self.trace is None or not self.want(step):
            return
        t = self.now()
        self.event("compute", t_start, step, worker=-1, dur=t - t_start)
        if not fire or active is None:
            return
        partners = np.asarray(backend.matching_partners(rnd))
        act = np.asarray(active).astype(bool)
        wire = float(backend.wire_bytes())
        for i in np.nonzero(act)[0]:
            i = int(i)
            k = int(partners[i])
            if k == i:
                continue
            self.event("exchange", t, step, worker=i, peer=k, round=int(rnd),
                       wire_bytes=wire)

    # -------------------------------------------------- deferred trace harvest
    def _defer_exchanges(self, trainer, t: float, step: int, key0, step0,
                         tokens0, mask) -> None:
        """Dispatch the gate/peer draws for THIS step (no blocking read) and
        harvest the PREVIOUS step's — the device_get then overlaps with the
        engine step that was just dispatched instead of stalling behind it.
        key0/step0/tokens0 are pre-step copies, safe against donation."""
        if not trainer._impl.pairwise:
            self._flush_trace()
            return
        draws = trainer._draw_fn(key0, step0)
        self._flush_trace()
        self._pending_trace = (trainer, t, step, draws, tokens0, mask)

    def _flush_trace(self) -> None:
        """Materialize the deferred step's draws and classify each initiation
        into exchange / drop / corrupt / flow_skip (+ a chunk id under the
        partition plane) — the same precedence the traced step applies."""
        p = self._pending_trace
        if p is None:
            return
        self._pending_trace = None
        trainer, t, step, draws, tokens0, mask = p
        import jax
        gate, peers, balances = jax.device_get((*draws, tokens0))
        gate = np.asarray(gate).astype(bool)
        peers = np.asarray(peers)
        active = gate if mask is None else (gate & np.asarray(mask))
        if trainer.flow is not None and balances is not None:
            balances = np.asarray(balances)
            allowed = np.asarray(
                trainer.flow.allow_np(step, balances)).astype(bool)
            for w in np.nonzero(active & ~allowed)[0]:
                w = int(w)
                self.event("flow_skip", t, step, worker=w,
                           tokens=float(balances[w]))
            active = active & allowed
        part = None
        if trainer.partition > 1:
            from repro.fleet.partition import partition_ids_np
            part = partition_ids_np(trainer.fleet.seed, step,
                                    trainer.num_workers, trainer.partition)
        fm = trainer.fault_model
        for i in np.nonzero(active)[0]:
            i = int(i)
            k = int(peers[i])
            if k == i:
                continue
            if fm is not None and fm.injects_drop and \
                    bool(fm.drop_mask(i, step)):
                self.event("drop", t, step, worker=i)
                continue
            if fm is not None and fm.injects_corrupt and \
                    bool(fm.corrupt_mask(i, step)):
                self.event("corrupt", t, step, worker=i)
                continue
            self.event("exchange", t, step, worker=i, peer=k)
            if part is not None:
                self.event("chunk", t, step, worker=i, chunk=int(part[i]))

    # --------------------------------------------------------- facade metrics
    def on_step(self, step: int, metrics: Dict[str, Any], state) -> None:
        """One sampled metrics row: the normalized step metrics plus a
        donation-safe snapshot of the ``ProtocolState`` accumulators (ONE
        jitted copy dispatch), harvested one step later."""
        if self.sink is None:
            return
        if not self.want(step):
            self._flush_row()
            return
        row: Dict[str, Any] = {"step": step, "t": self.now(),
                               "engine": self.engine}
        for k in ("loss", "loss_mean", "loss_max", "fired", "comm_active",
                  "comm_round", "comm_bytes", "virtual_time", "window_size",
                  "pending_wires", "published_seq", "publish_rejected"):
            if k in metrics:
                row[k] = metrics[k]
        proto = getattr(state, "proto", None)
        snap = None
        if proto is not None:
            import jax
            vals = {k: getattr(proto, k) for k in PROTO_COUNTERS + PROTO_ARRAYS
                    if getattr(proto, k, None) is not None}
            if self._snap_fn is None:
                # x * 1 is a bit-exact copy into FRESH output buffers — the
                # engine donates this state's buffers on its next step, so
                # holding the originals across the lag would read freed memory
                self._snap_fn = jax.jit(
                    lambda d: {k: v * 1 for k, v in d.items()})
            snap = self._snap_fn(vals)
        self._flush_row()
        self._pending_row = (row, snap)

    def _flush_row(self) -> None:
        p = self._pending_row
        if p is None:
            return
        self._pending_row = None
        row, snap = p
        if snap is not None:
            import jax
            host = jax.device_get(snap)
            pr = {}
            for k in PROTO_COUNTERS:
                if k not in host:
                    continue
                v = float(host[k])
                pr[k] = v
                delta = v - self._prev.get(k, 0.0)
                self._prev[k] = v
                if delta:
                    self.sink.counter_add(k, delta)
                if k == "stale_time" and delta:
                    self.sink.observe("stale_time_delta", delta)
            for k in PROTO_ARRAYS:
                if k in host:
                    pr[k] = np.asarray(host[k]).tolist()
            row["proto"] = pr
            # row fields that alias the (now possibly donated) state read
            # their values from the snapshot instead
            if "comm_bytes" in pr:
                row["comm_bytes"] = pr["comm_bytes"]
            if "comm_round" in row and "comm_rounds" in pr:
                row["comm_round"] = int(pr["comm_rounds"])
        elif "comm_bytes" in row:
            # dist without a ProtocolState: the host f64 accumulator is the
            # authoritative comm account; mirror it into the proto block so
            # the report tool reads one shape
            v = float(row["comm_bytes"])
            row["proto"] = {"comm_bytes": v}
            delta = v - self._prev.get("comm_bytes", 0.0)
            self._prev["comm_bytes"] = v
            if delta:
                self.sink.counter_add("comm_bytes", delta)
        for k in ("window_size", "pending_wires"):
            if k in row:
                self.sink.observe(k, int(row[k]))
        self.sink.record(row)

    def flush(self) -> None:
        """Drain the one-step-deferred harvest (call before reading the
        recorder/sink mid-run; :meth:`export` does it for you)."""
        self._flush_trace()
        self._flush_row()

    # ---------------------------------------------------------------- export
    def export(self, trace_path: Optional[str] = None,
               metrics_path: Optional[str] = None) -> Dict[str, str]:
        """Write the trace (Perfetto JSON) and flush/close the metrics JSONL.
        Paths default to the config's; returns {kind: path} for what was
        written. Idempotent for the trace (re-export overwrites)."""
        self.flush()
        out = {}
        tp = trace_path or self.cfg.trace_path
        if self.trace is not None and tp:
            self.trace.save(tp, num_workers=self.num_workers)
            out["trace"] = tp
        mp = metrics_path or self.cfg.metrics_path
        if self.sink is not None:
            if mp and mp != (self.sink.path or ""):
                # late path (CLI --metrics after in-memory recording): dump
                # the buffered rows
                import json
                with open(mp, "w") as f:
                    for r in self.sink.records:
                        f.write(json.dumps(r) + "\n")
                out["metrics"] = mp
            elif self.sink.path:
                out["metrics"] = self.sink.path
            self.sink.close()
        self._exported = True
        return out
