"""repro.faults tests: registry contract, hash-seeded draw purity (property
test + two-fresh-process determinism), wire checksum detection, zero-fault
bit-exactness, drop/corrupt/Byzantine behavior on the sim engine, the robust
Pallas kernel vs its oracle, the async delay/timeout/rendezvous plane,
fail_rejoin edge cases (rejoin-as-partner, full-fleet outage), checkpoint
fleet validation, and serve-layer graceful degradation."""
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.api import GossipTrainer, get_protocol, register_protocol, \
    unregister_protocol
from repro.common.config import (FaultConfig, HeteroConfig, OptimizerConfig,
                                 ProtocolConfig)
from repro.faults import (available_delay_models, available_fault_models,
                          bernoulli_jnp, bernoulli_np, delays_active,
                          get_delay_model, get_fault_model,
                          register_fault_model, resolve_delay_model,
                          resolve_fault_model, unregister_fault_model)
from repro.faults import wire as fwire
from repro.kernels import ops, ref
from repro.models import simple

W = 4
SRC = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))


def _problem(seed=0, n=32, d=10, classes=3, workers=W):
    rng = np.random.RandomState(seed)
    protos = rng.randn(classes, d) * 2
    y = rng.randint(0, classes, (workers, n)).astype(np.int32)
    x = protos[y] + rng.randn(workers, n, d).astype(np.float32)
    return jnp.asarray(x, jnp.float32), jnp.asarray(y)


def _loss(params, x, y):
    return simple.xent_loss(simple.mlp_logits(params, x), y)


def _trainer(engine="sim", faults=None, hetero=None, method="elastic_gossip",
             workers=W, **proto_kw):
    proto_kw.setdefault("comm_probability", 1.0)
    proto_kw.setdefault("moving_rate", 0.5)
    proto_kw.setdefault("topology", "uniform")
    proto = ProtocolConfig(method=method, **proto_kw)
    return GossipTrainer(
        engine=engine, protocol=proto,
        optimizer=OptimizerConfig(name="nag", learning_rate=0.05, momentum=0.9),
        loss_fn=_loss, num_workers=workers, hetero=hetero, faults=faults,
        init_fn=lambda key: simple.init_mlp(key, in_dim=10, hidden=16, depth=2,
                                            num_classes=3)[0])


def _run(trainer, steps, batch, seed=0):
    state = trainer.init_state(seed)
    m = {}
    for _ in range(steps):
        state, m = trainer.step(state, batch)
    return state, m


# ---------------------------------------------------------------------------
# registry contract
# ---------------------------------------------------------------------------

def test_fault_registry_builtins_and_errors():
    assert {"none", "drop", "corrupt", "byzantine_scale",
            "byzantine_noise"} <= set(available_fault_models())
    assert {"none", "constant", "uniform", "lognormal"} <= set(
        available_delay_models())
    with pytest.raises(ValueError, match="unknown fault model.*registered"):
        get_fault_model("gremlins")
    with pytest.raises(ValueError, match="unknown delay model.*registered"):
        get_delay_model("carrier_pigeon")
    # ...and already at resolve time, before any engine is built
    with pytest.raises(ValueError, match="unknown fault model"):
        resolve_fault_model(FaultConfig(fault_model="gremlins"))
    with pytest.raises(ValueError, match="unknown delay model"):
        resolve_delay_model(FaultConfig(delay_model="carrier_pigeon"))


def test_register_fault_model_extension_point():
    from repro.faults.models import FaultModel

    @register_fault_model("_test_null")
    class Null(FaultModel):
        pass
    try:
        assert "_test_null" in available_fault_models()
        fm = resolve_fault_model(FaultConfig(fault_model="_test_null"))
        assert not (fm.injects_drop or fm.injects_corrupt
                    or fm.injects_byzantine)
        with pytest.raises(ValueError, match="already registered"):
            @register_fault_model("_test_null")
            class Clash(FaultModel):
                pass
    finally:
        unregister_fault_model("_test_null")
    assert "_test_null" not in available_fault_models()


# ---------------------------------------------------------------------------
# hash-seeded draw purity (S6)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), worker=st.integers(0, 63),
       step=st.integers(0, 10_000), rate=st.floats(0.0, 1.0),
       salt=st.integers(0, 500))
def test_fault_draws_pure_in_seed_worker_step(seed, worker, step, rate, salt):
    """Every fault/delay draw is a pure function of (seed, worker, step):
    re-evaluating gives the identical bit, the traced (jnp) mirror agrees
    with the host (np) draw exactly, and polluting the host RNG between
    draws changes nothing."""
    a = bernoulli_np(seed, worker, step, rate, salt)
    np.random.seed((seed ^ step) % 2**31)   # host RNG must be irrelevant
    _ = np.random.rand(7)
    b = bernoulli_np(seed, worker, step, rate, salt)
    assert bool(a) == bool(b)
    j = bernoulli_jnp(seed, jnp.arange(worker + 1), jnp.asarray(step),
                      rate, salt)
    assert bool(np.asarray(j)[worker]) == bool(a)


def test_fault_model_draws_recomputable_and_rate_accurate():
    cfg = FaultConfig(fault_model="drop", fault_rate=0.3, seed=11)
    m1, m2 = resolve_fault_model(cfg), resolve_fault_model(cfg)
    w = np.repeat(np.arange(16), 400)
    k = np.tile(np.arange(400), 16)
    d1 = m1.drop_mask(w, k)
    np.testing.assert_array_equal(d1, m2.drop_mask(w, k))
    assert abs(d1.mean() - 0.3) < 0.02
    # rate 0 / 1 are exact, not approximate (integer-threshold Bernoulli)
    assert not resolve_fault_model(
        FaultConfig(fault_model="drop", fault_rate=0.0)).drop_mask(w, k).any()
    assert resolve_fault_model(
        FaultConfig(fault_model="drop", fault_rate=1.0)).drop_mask(w, k).all()


def test_fault_trace_identical_across_fresh_processes():
    """Two fresh interpreters (different host RNG pollution) produce the
    bit-identical fault + delay trace — the restart-exactness contract."""
    script = (
        "import sys, hashlib; import numpy as np; "
        f"sys.path.insert(0, {SRC!r}); "
        "np.random.seed(int(sys.argv[1])); np.random.rand(1000); "
        "from repro.common.config import FaultConfig; "
        "from repro.faults import resolve_fault_model, resolve_delay_model; "
        "cfg = FaultConfig(fault_model='corrupt', fault_rate=0.25, seed=5, "
        "delay_model='lognormal', delay=1.5, delay_sigma=0.4); "
        "fm, dm = resolve_fault_model(cfg), resolve_delay_model(cfg); "
        "w = np.repeat(np.arange(6), 50); k = np.tile(np.arange(50), 6); "
        "trace = np.concatenate([fm.corrupt_mask(w, k).astype(np.float64), "
        "dm.wire_delay(w, k), dm.wire_delay(w, k, attempt=1)]); "
        "print(hashlib.sha256(trace.tobytes()).hexdigest())")
    outs = [subprocess.run([sys.executable, "-c", script, str(pollute)],
                           capture_output=True, text=True, check=True).stdout
            for pollute in (1, 999)]
    assert outs[0] == outs[1]
    assert len(outs[0].strip()) == 64


# ---------------------------------------------------------------------------
# wire checksum
# ---------------------------------------------------------------------------

def test_checksum_detects_every_single_byte_flip():
    rng = np.random.RandomState(0)
    wire = jnp.asarray(rng.randint(0, 256, (3, 64), np.uint8))
    ext = fwire.append_checksum(wire)
    payload, ok = fwire.verify_strip(ext)
    assert bool(np.asarray(ok).all())
    np.testing.assert_array_equal(np.asarray(payload), np.asarray(wire))
    for pos in range(64):              # flip each payload byte in row 1
        bad = np.asarray(ext).copy()
        bad[1, pos] ^= 0x40
        _, ok = fwire.verify_strip(jnp.asarray(bad))
        assert not bool(np.asarray(ok)[1]), f"flip at byte {pos} undetected"
        assert bool(np.asarray(ok)[0]) and bool(np.asarray(ok)[2])


def test_corrupt_roundtrip_identity_when_mask_clear():
    bufs = {"f32": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "i32": jnp.arange(12, dtype=jnp.int32).reshape(3, 4)}
    out, ok = fwire.corrupt_roundtrip_bufs(bufs, jnp.zeros((3,), bool),
                                           seed=7, step=jnp.int32(0))
    assert bool(np.asarray(ok).all())
    for k in bufs:
        assert out[k].dtype == bufs[k].dtype
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(bufs[k]))
    # ...and a set mask is both injected and detected
    out2, ok2 = fwire.corrupt_roundtrip_bufs(
        bufs, jnp.asarray([False, True, False]), seed=7, step=jnp.int32(0))
    assert not bool(np.asarray(ok2)[1])
    assert bool(np.asarray(ok2)[0]) and bool(np.asarray(ok2)[2])


# ---------------------------------------------------------------------------
# sim engine: zero-fault anchor + fault behavior
# ---------------------------------------------------------------------------

def test_zero_fault_config_is_bit_exact_vs_no_faults():
    """FaultConfig with rate 0 runs the full fault wiring yet reproduces the
    fault-free engine bit-for-bit: params, velocity, comm accounting, key."""
    batch = _problem()
    s0, m0 = _run(_trainer(), 6, batch)
    s1, m1 = _run(_trainer(faults=FaultConfig(fault_model="drop",
                                              fault_rate=0.0)), 6, batch)
    for k in s0.theta:
        np.testing.assert_array_equal(np.asarray(s0.theta[k]),
                                      np.asarray(s1.theta[k]))
        np.testing.assert_array_equal(np.asarray(s0.opt.mu[k]),
                                      np.asarray(s1.opt.mu[k]))
    assert int(s0.proto.comm_units) == int(s1.proto.comm_units)
    assert float(s0.proto.comm_bytes) == float(s1.proto.comm_bytes)
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(s0.key)),
        np.asarray(jax.random.key_data(s1.key)))
    assert int(s1.proto.wire_dropped) == 0


def test_drop_faults_counted_and_excluded_from_comm_bytes():
    batch = _problem()
    s0, _ = _run(_trainer(), 8, batch)
    s1, m = _run(_trainer(faults=FaultConfig(fault_model="drop",
                                             fault_rate=0.5, seed=3)),
                 8, batch)
    assert int(s1.proto.wire_dropped) > 0
    # S1: only surviving wires count — units + derived bytes shrink together
    assert int(s1.proto.comm_units) + int(s1.proto.wire_dropped) \
        == int(s0.proto.comm_units)
    assert float(s1.proto.comm_bytes) < float(s0.proto.comm_bytes)
    assert np.isfinite(float(m["loss"]))
    for k in s1.theta:
        assert bool(jnp.all(jnp.isfinite(s1.theta[k])))


@pytest.mark.parametrize("codec", ["none", "q8"])
def test_corrupt_faults_detected_and_discarded(codec):
    faults = FaultConfig(fault_model="corrupt", fault_rate=0.5, seed=2)
    s, m = _run(_trainer(faults=faults, codec=codec), 8, _problem())
    assert int(s.proto.wire_corrupt) > 0
    assert int(s.proto.wire_dropped) == 0
    assert np.isfinite(float(m["loss"]))
    for k in s.theta:
        assert bool(jnp.all(jnp.isfinite(s.theta[k])))


def test_byzantine_noise_clipped_gossip_stays_bounded():
    """Plain elastic gossip is pulled toward the Byzantine noise rows;
    clipped_gossip norm-clips the received displacement and keeps training."""
    batch = _problem()
    faults = FaultConfig(fault_model="byzantine_noise", fault_frac=0.25,
                         noise_std=10.0, seed=1)
    s_plain, m_plain = _run(_trainer(faults=faults), 12, batch)
    s_clip, m_clip = _run(_trainer(faults=faults, method="clipped_gossip",
                                   robust_clip=0.1), 12, batch)
    assert float(m_clip["loss"]) < float(m_plain["loss"])
    # honest rows (the last 3 of 4) stay finite under clipping
    for k in s_clip.theta:
        assert bool(jnp.all(jnp.isfinite(s_clip.theta[k])))


def test_fault_model_requires_wire_faults_capable_protocol():
    """A protocol whose comm_update cannot honor the discard is refused at
    build time, not silently over-counted at run time."""
    Base = get_protocol("elastic_gossip")

    @register_protocol("_test_nofaultkw")
    class NoFaultKw(Base):
        def comm_update(self, key, active, theta_stack, state, step=None,
                        transmit=None, wire_bytes=None):
            return super().comm_update(key, active, theta_stack, state,
                                       step=step, transmit=transmit,
                                       wire_bytes=wire_bytes)
    try:
        with pytest.raises(ValueError, match="wire_faults"):
            _trainer(method="_test_nofaultkw",
                     faults=FaultConfig(fault_model="drop", fault_rate=0.5))
    finally:
        unregister_protocol("_test_nofaultkw")


# ---------------------------------------------------------------------------
# robust kernel vs oracle
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(w=st.integers(1, 5), n=st.integers(1, 700),
       scale_all=st.booleans(), finite_thr=st.booleans())
def test_robust_flat_apply_kernel_matches_oracle(w, n, scale_all, finite_thr):
    rng = np.random.RandomState(w * 1000 + n)
    theta = jnp.asarray(rng.randn(w, n), jnp.float32)
    delta = jnp.asarray(rng.randn(w, n) * 3, jnp.float32)
    scale = jnp.asarray(np.ones(w) if scale_all
                        else rng.uniform(0, 1, w), jnp.float32)
    thr = jnp.asarray(rng.uniform(0.5, 2.0, w) if finite_thr
                      else np.full(w, np.inf), jnp.float32)
    want = ref.robust_flat_apply(theta, delta, scale, thr)
    got = ops.robust_flat_apply(theta, delta, scale, thr,
                                use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# async engine: delay / timeout / rendezvous plane
# ---------------------------------------------------------------------------

def _hetero(**kw):
    kw.setdefault("time_model", "constant")
    kw.setdefault("mean_step_time", 1.0)
    return HeteroConfig(**kw)


def test_zero_delay_fault_config_keeps_in_window_path_bit_exact():
    """A FaultConfig that activates no delay plane must not flip the async
    engine into message mode — the hetero bit-exact anchor is untouched."""
    faults = FaultConfig(fault_model="drop", fault_rate=0.0)
    assert not delays_active(faults)
    batch = _problem()
    s0, _ = _run(_trainer("async", hetero=_hetero()), 5, batch)
    s1, _ = _run(_trainer("async", hetero=_hetero(), faults=faults), 5, batch)
    for k in s0.theta:
        np.testing.assert_array_equal(np.asarray(s0.theta[k]),
                                      np.asarray(s1.theta[k]))
    assert int(s0.proto.comm_units) == int(s1.proto.comm_units)


def test_async_delayed_wires_apply_at_arrival_with_staleness():
    faults = FaultConfig(delay_model="constant", delay=1.5)
    t = _trainer("async", hetero=_hetero(), faults=faults)
    state, m = _run(t, 10, _problem())
    # exchanges happened, delayed: staleness accrues >= delay per event
    assert int(m["stale_events"]) > 0
    assert float(m["stale_time"]) >= 1.5 * int(m["stale_events"])
    # one unit per applied exchange (the initiator), same as the in-window path
    assert int(state.proto.comm_units) == int(m["stale_events"])
    assert np.isfinite(float(m["loss"]))
    for k in state.theta:
        assert bool(jnp.all(jnp.isfinite(state.theta[k])))


def test_async_timeout_skips_and_never_counts_bytes():
    """Wires slower than the timeout are abandoned: retry/timeout counters
    accrue, applied-exchange accounting stays at zero (S1, async side)."""
    faults = FaultConfig(delay_model="constant", delay=100.0, timeout=1.0,
                         max_retries=2)
    t = _trainer("async", hetero=_hetero(), faults=faults)
    state, m = _run(t, 12, _problem())
    assert int(m["exch_timeouts"]) > 0
    assert int(m["exch_retries"]) > 0
    assert int(m["stale_events"]) == 0        # nothing ever applied...
    assert int(state.proto.comm_units) == 0   # ...so nothing is billed
    assert float(state.proto.comm_bytes) == 0.0


def test_async_rendezvous_defers_to_partner_boundary():
    faults = FaultConfig(delay_model="constant", delay=0.25, rendezvous=True)
    hetero = _hetero(time_model="slow_node", slow_worker=0, slow_factor=4.0)
    t = _trainer("async", hetero=hetero, faults=faults)
    state, m = _run(t, 16, _problem())
    assert int(m["stale_events"]) > 0
    # a wire held for the slow partner's boundary waits >> its raw delay
    assert float(m["stale_time"]) > 0.25 * int(m["stale_events"])
    assert np.isfinite(float(m["loss"]))


def test_async_drop_faults_kill_wires_at_dispatch():
    faults = FaultConfig(fault_model="drop", fault_rate=0.6, seed=4,
                         delay_model="constant", delay=0.5)
    t = _trainer("async", hetero=_hetero(), faults=faults)
    state, m = _run(t, 10, _problem())
    assert int(state.proto.wire_dropped) > 0
    assert np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# fail_rejoin edge cases (S3)
# ---------------------------------------------------------------------------

def test_fail_rejoin_worker_rejoins_and_is_drawn_as_partner():
    """Worker 1 drops out mid-run and rejoins; with comm_probability 1 its
    first post-rejoin completion immediately gossips (it is drawn as a
    partner in the same window) — the huge step gap lands in the staleness
    accounting and nothing diverges."""
    hetero = _hetero(time_model="fail_rejoin", slow_worker=1, fail_at=2.5,
                     rejoin_at=8.0)
    t = _trainer("async", hetero=hetero)
    batch = _problem()
    state = t.init_state(0)
    sim = t._backend.sim
    steps_during_outage = None
    m = {}
    for _ in range(40):
        state, m = t.step(state, batch)
        if 3.0 <= float(m["virtual_time"]) < 8.0:
            steps_during_outage = int(sim.steps_done[1])
    assert steps_during_outage == 2            # froze at the outage
    assert int(sim.steps_done[1]) > 2          # ...and resumed after rejoin
    assert int(m["stale_steps"]) > 0           # the gap was accounted
    assert np.isfinite(float(m["loss"]))
    for k in state.theta:
        assert bool(jnp.all(jnp.isfinite(state.theta[k])))


def test_full_fleet_outage_advances_clock_without_device_program():
    """slow_worker=-1 fail_rejoin: EVERY worker is down for the window.
    The engine emits one empty event window (no device step, NaN loss),
    jumps the virtual clock to rejoin_at, then training resumes."""
    hetero = _hetero(time_model="fail_rejoin", slow_worker=-1, fail_at=2.5,
                     rejoin_at=9.0)
    t = _trainer("async", hetero=hetero)
    batch = _problem()
    state = t.init_state(0)
    sim = t._backend.sim
    empty = []
    for _ in range(8):
        before = int(np.sum(sim.steps_done))
        state, m = t.step(state, batch)
        if int(m["window_size"]) == 0:
            empty.append((float(m["virtual_time"]), np.isnan(float(m["loss"])),
                          int(np.sum(sim.steps_done)) - before))
    assert len(empty) == 1
    vt, loss_nan, steps_delta = empty[0]
    assert vt == 9.0 and loss_nan and steps_delta == 0
    assert float(np.min(sim.clocks)) >= 9.0
    # post-outage windows train again
    state, m = t.step(state, batch)
    assert int(m["window_size"]) > 0 and np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# checkpoint fleet validation (S2)
# ---------------------------------------------------------------------------

def test_restore_refuses_different_fleet(tmp_path):
    batch = _problem()
    hetero = _hetero(time_model="fail_rejoin", slow_worker=1, fail_at=3.0,
                     rejoin_at=6.0)
    faults = FaultConfig(fault_model="drop", fault_rate=0.3, seed=7)
    t = _trainer("async", hetero=hetero, faults=faults)
    state, _ = _run(t, 3, batch)
    path = str(tmp_path / "ckpt.npz")
    t.save_checkpoint(path, state)

    # same fleet: restores cleanly
    t_same = _trainer("async", hetero=hetero, faults=faults)
    t_same.load_checkpoint(path, t_same.init_state(0))

    # different fault seed
    t_seed = _trainer("async", hetero=hetero,
                      faults=FaultConfig(fault_model="drop", fault_rate=0.3,
                                         seed=8))
    with pytest.raises(ValueError, match="different faults config.*seed"):
        t_seed.load_checkpoint(path, t_seed.init_state(0))

    # different fail_rejoin schedule
    t_sched = _trainer("async", faults=faults,
                       hetero=_hetero(time_model="fail_rejoin", slow_worker=1,
                                      fail_at=3.0, rejoin_at=20.0))
    with pytest.raises(ValueError, match="different hetero config"):
        t_sched.load_checkpoint(path, t_sched.init_state(0))

    # fault plane present in checkpoint, absent in trainer
    t_none = _trainer("async", hetero=hetero)
    with pytest.raises(ValueError, match="fault plane"):
        t_none.load_checkpoint(path, t_none.init_state(0))

    # ...and the converse: fault-free checkpoint into a faulted trainer
    t_clean = _trainer("async", hetero=hetero)
    state_c, _ = _run(t_clean, 3, batch)
    path_c = str(tmp_path / "clean.npz")
    t_clean.save_checkpoint(path_c, state_c)
    t_faulted = _trainer("async", hetero=hetero, faults=faults)
    with pytest.raises(ValueError, match="WITHOUT a fault plane"):
        t_faulted.load_checkpoint(path_c, t_faulted.init_state(0))


# ---------------------------------------------------------------------------
# serve-layer graceful degradation
# ---------------------------------------------------------------------------

def test_snapshot_bus_rejects_nonfinite_publish():
    from repro.serve import SnapshotBus
    bus = SnapshotBus()
    good = {"w": jnp.ones((3, 4))}
    snap = bus.publish_params(good, train_step=1)
    assert snap is not None and bus.seq == 1
    bad = {"w": jnp.asarray([[1.0, jnp.nan], [0.0, 2.0]])}
    with pytest.warns(RuntimeWarning, match="non-finite"):
        rejected = bus.publish_params(bad, train_step=2)
    assert rejected is None
    assert bus.rejected == 1
    assert bus.latest().seq == 1       # readers keep the last good snapshot
    # a later good publish proceeds normally
    assert bus.publish_params(good, train_step=3).seq == 2


def test_live_server_pins_last_good_on_invalid_snapshot():
    """A bad snapshot that bypassed publish validation (e.g. loaded from
    disk) is refused at swap time: the server pins the last good weights
    and counts the rejection — decode never sees garbage."""
    import dataclasses as dc

    from repro.serve import LiveServer, SnapshotBus
    bus = SnapshotBus()
    good = {"w": jnp.ones((2, 3))}
    snap = bus.publish_params(good, train_step=5)
    server = LiveServer(program=object(), bus=bus)   # program untouched here
    # hand-craft an invalid successor in the bus (simulates a foreign bus)
    bad = dc.replace(snap, seq=snap.seq + 1,
                     bufs={k: v.at[0].set(jnp.inf) for k, v in snap.bufs.items()})
    bus._slots[1 - bus._head] = bad
    bus._head = 1 - bus._head
    bus._seq = bad.seq
    server.seq = snap.seq              # currently serving the good snapshot
    with pytest.warns(RuntimeWarning, match="refused snapshot"):
        assert server.maybe_swap() is False
    assert server.rejected_swaps == 1
    assert server.seq == snap.seq      # still pinned to the last good seq
    assert server.swap_stats()["rejected_swaps"] == 1
    # the refused seq is remembered: no warning storm on every poll
    assert server.maybe_swap() is False
    assert server.rejected_swaps == 1
