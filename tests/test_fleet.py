"""repro.fleet: partitioned exchanges, token-account flow control, and the
host-resident plane (ISSUE 8).

Contract anchors:
- ``FleetConfig(partition=1, flow_control="none", plane="device")`` is INERT —
  the async/sim engines reproduce the non-fleet trajectory bit-exactly
  (params, velocity, comm_bytes, PRNG key);
- the chunk schedule is a pure hash of (seed, worker, step), covers the plane
  exactly, and the host (numpy) mirror agrees with the traced draw bit-for-bit;
- partition composes with q8/topk codecs with sim-vs-async wire parity;
- flow-control balances persist through checkpoints; restoring under a
  different fleet config is refused.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import GossipTrainer
from repro.common.config import (FleetConfig, HeteroConfig, OptimizerConfig,
                                 ProtocolConfig)
from repro.fleet import (FlowControl, available_flow_controls, build_plan,
                         chunk_bounds, get_flow_control, partition_ids,
                         partition_ids_np, register_flow_control,
                         resolve_flow_control, unregister_flow_control,
                         validate_fleet_memory)
from repro.models import simple

W = 8


def _problem(n=24, d=10, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    protos = rng.randn(classes, d) * 2
    y = rng.randint(0, classes, (W, n)).astype(np.int32)
    x = protos[y] + rng.randn(W, n, d).astype(np.float32)
    return jnp.asarray(x, jnp.float32), jnp.asarray(y)


def _loss(params, x, y):
    return simple.xent_loss(simple.mlp_logits(params, x), y)


def _init(key):
    return simple.init_mlp(key, in_dim=10, hidden=16, depth=2,
                           num_classes=3)[0]


def _trainer(engine="sim", fleet=None, codec=None, hetero=None,
             method="elastic_gossip", p=0.5, **kw):
    proto = ProtocolConfig(method=method, comm_probability=p,
                           moving_rate=0.5, topology="uniform")
    return GossipTrainer(
        engine=engine, protocol=proto, fleet=fleet, codec=codec,
        hetero=hetero,
        optimizer=OptimizerConfig(name="nag", learning_rate=0.05, momentum=0.9),
        loss_fn=_loss, num_workers=W, init_fn=_init, **kw)


def _run(trainer, steps=8, seed=0):
    state = trainer.init_state(seed)
    x, y = _problem()
    m = {}
    for _ in range(steps):
        state, m = trainer.step(state, (x, y))
    return state, m


def _assert_states_equal(a, b):
    for k in a.theta:
        np.testing.assert_array_equal(np.asarray(a.theta[k]),
                                      np.asarray(b.theta[k]), err_msg=k)
    for k in a.opt.mu:
        np.testing.assert_array_equal(np.asarray(a.opt.mu[k]),
                                      np.asarray(b.opt.mu[k]), err_msg=k)
    assert float(a.proto.comm_bytes) == float(b.proto.comm_bytes)
    assert int(a.proto.comm_units) == int(b.proto.comm_units)
    np.testing.assert_array_equal(np.asarray(a.key), np.asarray(b.key))


# ---------------------------------------------------------------------------
# chunk schedule: coverage + purity (property test)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(total=st.integers(1, 5000), partition=st.integers(1, 16))
def test_chunk_bounds_cover_exactly(total, partition):
    """The integer split covers [0, total) with no gap and no overlap for ANY
    total (lane-aligned or not), sizes differing by at most one element."""
    bnds = chunk_bounds(total, partition)
    assert len(bnds) == partition
    assert bnds[0][0] == 0 and bnds[-1][1] == total
    sizes = []
    for c, (lo, hi) in enumerate(bnds):
        assert lo <= hi
        if c > 0:
            assert lo == bnds[c - 1][1]
        sizes.append(hi - lo)
    assert max(sizes) - min(sizes) <= 1
    assert sum(sizes) == total


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), step=st.integers(0, 10_000),
       partition=st.integers(1, 16))
def test_partition_ids_pure_and_host_traced_agree(seed, step, partition):
    """Chunk ids are a pure hash of (seed, worker, step): the numpy mirror
    equals the traced draw bit-for-bit and host RNG state is irrelevant."""
    a = partition_ids_np(seed, step, 32, partition)
    np.random.seed((seed ^ step) % 2**31)
    _ = np.random.rand(5)
    b = partition_ids_np(seed, step, 32, partition)
    np.testing.assert_array_equal(a, b)
    j = np.asarray(partition_ids(seed, jnp.asarray(step), 32, partition))
    np.testing.assert_array_equal(a, j)
    assert a.min() >= 0 and a.max() < partition


def test_partition_schedule_uniform_coverage():
    """Over many steps every worker ships every chunk with near-uniform
    frequency (the hash schedule has no stuck chunk)."""
    P, steps = 8, 800
    counts = np.zeros((W, P), np.int64)
    for s in range(steps):
        ids = partition_ids_np(0, s, W, P)
        for w in range(W):
            counts[w, ids[w]] += 1
    freq = counts / steps
    # each (worker, chunk) cell within 35% of the uniform 1/P rate
    assert np.abs(freq - 1.0 / P).max() < 0.35 / P
    # and every chunk is shipped by every worker at least once
    assert counts.min() > 0


def test_build_plan_wire_bytes_sum_to_plane():
    t = _trainer()
    s = t.init_state(0)
    # raw-wire convention: lane-padding columns never ride the wire, so the
    # per-chunk bytes sum EXACTLY to the engines' full-replica raw wire
    raw = sum(sl.size * sl.dtype.itemsize for sl in s.spec.slots)
    padded = sum(int(n) * jnp.dtype(b).itemsize
                 for b, n in s.spec.totals.items())
    assert raw < padded  # this model does carry lane padding
    for P in (1, 3, 8):
        plan = build_plan(s.spec, P)
        assert len(plan.wire_bytes) == P
        assert sum(plan.wire_bytes) == raw
        for b, total in s.spec.totals.items():
            cols = plan.col_chunks(b, int(total))
            for c, (lo, hi) in enumerate(plan.bounds[b]):
                assert (cols[lo:hi] == c).all()


# ---------------------------------------------------------------------------
# inert-config bit-exactness anchor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["sim", "async"])
def test_default_fleet_config_is_bit_exact_inert(engine):
    """partition=1 + flow_control='none' + plane='device' reproduces the
    non-fleet engine bit-exactly: params, velocity, comm accounting, and the
    PRNG key (the engines add ZERO trace ops for the inert config)."""
    s0, _ = _run(_trainer(engine))
    s1, _ = _run(_trainer(engine, fleet=FleetConfig()))
    _assert_states_equal(s0, s1)
    assert s1.proto.tokens is None and s1.proto.chunk_units is None


# ---------------------------------------------------------------------------
# partitioned exchanges
# ---------------------------------------------------------------------------

def test_partition_sim_async_parity_and_exact_accounting():
    """Sim and async (constant fleet) agree bit-exactly under partition, and
    comm_bytes is derived exactly from the per-chunk applied counts."""
    fleet = FleetConfig(partition=4)
    sp, _ = _run(_trainer("sim", fleet=fleet))
    sa, _ = _run(_trainer("async", fleet=fleet))
    _assert_states_equal(sp, sa)
    cu = np.asarray(sp.proto.chunk_units)
    assert cu.sum() == int(sp.proto.comm_units)
    plan = build_plan(sp.spec, 4)
    t = _trainer()
    impl = t.impl
    per = np.array([impl.comm_cost(bc, W).bytes_per_event
                    for bc in plan.wire_bytes])
    assert float(sp.proto.comm_bytes) == pytest.approx(
        float(per @ cu) / W, rel=1e-6)


def test_partition_cuts_wire_bytes_but_still_converges():
    s_full, _ = _run(_trainer("sim"), steps=40)
    s_part, _ = _run(_trainer("sim", fleet=FleetConfig(partition=4)), steps=40)
    # same number of applied exchanges, ~1/4 the bytes
    assert int(s_part.proto.comm_units) == int(s_full.proto.comm_units)
    ratio = float(s_part.proto.comm_bytes) / float(s_full.proto.comm_bytes)
    assert 0.15 < ratio < 0.4
    # partitioned gossip still pulls the fleet together
    th = np.asarray(s_part.theta["float32"])
    spread = np.abs(th - th.mean(0)).max()
    th0 = np.asarray(_run(_trainer("sim", method="none"), steps=40)[0]
                     .theta["float32"])
    spread0 = np.abs(th0 - th0.mean(0)).max()
    assert spread < spread0


@pytest.mark.parametrize("codec", ["q8", "topk"])
def test_partition_composes_with_codec_sim_async_bit_exact(codec):
    """partition ∘ codec wire round-trips bit-exactly between the sim and
    async engines (the constant-fleet parity anchor, with residual state)."""
    fleet = FleetConfig(partition=4)
    sp, _ = _run(_trainer("sim", fleet=fleet, codec=codec), steps=10)
    sa, _ = _run(_trainer("async", fleet=fleet, codec=codec), steps=10)
    _assert_states_equal(sp, sa)
    if sp.comm.residual:
        for k in sp.comm.residual:
            np.testing.assert_array_equal(np.asarray(sp.comm.residual[k]),
                                          np.asarray(sa.comm.residual[k]))
        if codec == "topk":
            # the error-feedback residual is actually alive under partition
            assert sum(float(np.abs(np.asarray(r)).sum())
                       for r in sp.comm.residual.values()) > 0


def test_partitioned_robust_mixing_runs_per_chunk():
    """Robust protocols get PER-CHUNK clip coefficients under partition: the
    run completes, accounts per chunk, and stays finite."""
    for method in ("clipped_gossip", "trimmed_gossip"):
        s, _ = _run(_trainer("sim", fleet=FleetConfig(partition=3),
                             method=method), steps=10)
        cu = np.asarray(s.proto.chunk_units)
        assert cu.shape == (3,) and cu.sum() == int(s.proto.comm_units)
        assert np.isfinite(np.asarray(s.theta["float32"])).all()


def test_partition_requires_pairwise_protocol():
    with pytest.raises(ValueError, match="pairwise"):
        _trainer("sim", fleet=FleetConfig(partition=4), method="allreduce")


# ---------------------------------------------------------------------------
# token-account flow control
# ---------------------------------------------------------------------------

def test_flow_registry_extension_point():
    assert set(available_flow_controls()) >= {
        "none", "token_account", "randomized_token_account"}
    assert resolve_flow_control(FleetConfig()) is None  # trivial -> no ops

    @register_flow_control("_test_every_other")
    class EveryOther(FlowControl):
        def allow(self, step, tokens):
            return jnp.broadcast_to(step % 2 == 0, tokens.shape)

        def allow_np(self, step, tokens):
            return np.broadcast_to(step % 2 == 0, tokens.shape)

    try:
        assert get_flow_control("_test_every_other") is EveryOther
        fleet = FleetConfig(flow_control="_test_every_other")
        s, _ = _run(_trainer("sim", fleet=fleet, p=1.0), steps=4)
        # steps 0 and 2 allowed (W initiations each), 1 and 3 skipped
        assert int(s.proto.comm_units) == 2 * W
        assert int(s.proto.flow_skipped) == 2 * W
        with pytest.raises(ValueError, match="already registered"):
            register_flow_control("_test_every_other")(EveryOther)
    finally:
        unregister_flow_control("_test_every_other")
    assert "_test_every_other" not in available_flow_controls()


def test_unknown_flow_control_raises_with_candidates():
    with pytest.raises(KeyError, match="token_account"):
        resolve_flow_control(FleetConfig(flow_control="nope"))


def test_token_account_semantics():
    """Credit token_rate per completed step (capped), debit 1 per initiation,
    floor at 0; a worker below 1 token cannot initiate."""
    fc = get_flow_control("token_account")(
        FleetConfig(flow_control="token_account", token_capacity=2.0,
                    token_rate=0.5, token_init=1.0))
    tokens = fc.init_tokens(4)
    np.testing.assert_array_equal(np.asarray(tokens), np.ones(4, np.float32))
    allowed = np.asarray(fc.allow(0, tokens))
    assert allowed.all()
    stepped = jnp.ones((4,), bool)
    initiated = jnp.asarray([True, True, False, False])
    t1 = np.asarray(fc.update(tokens, stepped, initiated))
    np.testing.assert_allclose(t1, [0.5, 0.5, 1.5, 1.5])
    assert not np.asarray(fc.allow(1, jnp.asarray(t1)))[:2].any()
    # capacity cap and zero floor
    t2 = np.asarray(fc.update(jnp.asarray([1.9, 0.2, 0.0, 2.0], jnp.float32),
                              stepped, jnp.asarray([False, True, True, False])))
    np.testing.assert_allclose(t2, [2.0, 0.0, 0.0, 2.0])


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), step=st.integers(0, 10_000))
def test_randomized_token_account_host_traced_agree(seed, step):
    """The randomized initiation draw is an exact hash-threshold comparison:
    the numpy (host plane) and jnp (device plane) draws agree bit-for-bit."""
    fc = get_flow_control("randomized_token_account")(
        FleetConfig(flow_control="randomized_token_account",
                    token_threshold=10.0, seed=seed))
    rng = np.random.RandomState(seed % 2**31)
    tokens = rng.uniform(0.0, 20.0, size=(32,)).astype(np.float32)
    host = fc.allow_np(step, tokens)
    traced = np.asarray(fc.allow(jnp.asarray(step), jnp.asarray(tokens)))
    np.testing.assert_array_equal(host, traced)
    # a balance below one token can never cover the spend
    assert not host[tokens < 1.0].any()


def test_randomized_flow_throttles_initiations():
    fleet = FleetConfig(flow_control="randomized_token_account",
                        token_capacity=4.0, token_rate=0.25,
                        token_threshold=4.0)
    s, _ = _run(_trainer("sim", fleet=fleet, p=1.0), steps=20)
    # p=1 would fire 20*W initiations; the account throttles well below that
    assert 0 < int(s.proto.comm_units) < 20 * W // 2
    assert int(s.proto.flow_skipped) > 0
    assert int(s.proto.comm_units) + int(s.proto.flow_skipped) == 20 * W


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_fleet_state_roundtrips_through_checkpoint(tmp_path):
    """tokens / flow_skipped / chunk_units persist through save/load and the
    resumed trajectory continues bit-identically."""
    fleet = FleetConfig(partition=3, flow_control="token_account",
                        token_capacity=5.0, token_rate=0.5)
    t = _trainer("async", fleet=fleet)
    s = t.init_state(0)
    x, y = _problem()
    for _ in range(6):
        s, _ = t.step(s, (x, y))
    path = str(tmp_path / "fleet.npz")
    t.save_checkpoint(path, s, meta={"step": 6})

    t2 = _trainer("async", fleet=fleet)
    restored, meta = t2.load_checkpoint(path, t2.init_state(1))
    np.testing.assert_array_equal(np.asarray(restored.proto.tokens),
                                  np.asarray(s.proto.tokens))
    np.testing.assert_array_equal(np.asarray(restored.proto.chunk_units),
                                  np.asarray(s.proto.chunk_units))
    assert int(restored.proto.flow_skipped) == int(s.proto.flow_skipped)
    sc, _ = t.step(s, (x, y))
    sr, _ = t2.step(restored, (x, y))
    _assert_states_equal(sc, sr)

    # restoring under a DIFFERENT fleet config is refused field-by-field
    t3 = _trainer("async", fleet=FleetConfig(partition=6,
                                             flow_control="token_account",
                                             token_capacity=5.0,
                                             token_rate=0.5))
    with pytest.raises(ValueError, match="partition"):
        t3.load_checkpoint(path, t3.init_state(1))
    t4 = _trainer("async")
    with pytest.raises(ValueError, match="fleet"):
        t4.load_checkpoint(path, t4.init_state(1))


# ---------------------------------------------------------------------------
# host-resident plane
# ---------------------------------------------------------------------------

def test_host_plane_matches_device_plane():
    """plane='host' runs theta/velocity in host numpy with identical
    accounting (bytes, units, staleness, PRNG key) and numerics within float
    rounding of the device plane."""
    sd, _ = _run(_trainer("async"), steps=10)
    sh, mh = _run(_trainer("async", fleet=FleetConfig(plane="host")), steps=10)
    assert isinstance(sh.theta["float32"], np.ndarray)
    assert float(sd.proto.comm_bytes) == float(sh.proto.comm_bytes)
    assert int(sd.proto.comm_units) == int(sh.proto.comm_units)
    assert int(sd.proto.stale_events) == int(sh.proto.stale_events)
    np.testing.assert_array_equal(np.asarray(sd.key), np.asarray(sh.key))
    np.testing.assert_allclose(np.asarray(sd.theta["float32"]),
                               sh.theta["float32"], atol=2e-5)
    np.testing.assert_allclose(np.asarray(sd.opt.mu["float32"]),
                               sh.opt.mu["float32"], atol=2e-5)
    assert np.isfinite(mh["loss_mean"])


def test_host_plane_straggler_windows_only_move_window_rows():
    """Under a lognormal straggler fleet the host plane only updates the
    event window's rows: with every exchange starved by flow control, a row
    outside the window is BIT-frozen (host rows are never rewritten by
    device round-trips)."""
    het = HeteroConfig(time_model="lognormal", sigma=0.5, seed=3)
    # a 0.5-token account with zero refill can never cover an initiation
    t = _trainer("async", hetero=het, p=1.0,
                 fleet=FleetConfig(plane="host", partition=2,
                                   flow_control="token_account",
                                   token_init=0.5, token_rate=0.0))
    s = t.init_state(0)
    x, y = _problem()
    saw_partial = False
    for _ in range(12):
        prev = {b: v.copy() for b, v in s.theta.items()}
        prev_steps = t._backend.sim.steps_done.copy()
        s, m = t.step(s, (x, y))
        stepped = t._backend.sim.steps_done > prev_steps
        assert m["window_size"] == int(stepped.sum())
        moved = np.array([
            not np.array_equal(prev["float32"][w], s.theta["float32"][w])
            for w in range(W)])
        np.testing.assert_array_equal(moved, stepped)
        saw_partial = saw_partial or not stepped.all()
    assert saw_partial  # the straggler model actually produced partial windows
    assert float(s.proto.comm_bytes) == 0.0

    # ...and the full composition (partition + randomized flow + stragglers)
    # completes with consistent per-chunk accounting
    t2 = _trainer("async", hetero=het, p=1.0,
                  fleet=FleetConfig(plane="host", partition=2,
                                    flow_control="randomized_token_account"))
    s2 = t2.init_state(0)
    for _ in range(20):
        s2, _ = t2.step(s2, (x, y))
    assert np.isfinite(s2.theta["float32"]).all()
    assert int(s2.proto.comm_units) == int(
        np.asarray(s2.proto.chunk_units).sum())
    assert int(s2.proto.comm_units) > 0


def test_host_plane_requires_async_engine_and_nag():
    with pytest.raises(ValueError, match="async"):
        _trainer("sim", fleet=FleetConfig(plane="host"))
    proto = ProtocolConfig(method="elastic_gossip", comm_probability=0.5,
                           moving_rate=0.5, topology="uniform")
    with pytest.raises(ValueError, match="NAG"):
        GossipTrainer(engine="async", protocol=proto,
                      fleet=FleetConfig(plane="host"),
                      optimizer=OptimizerConfig(name="sgd", learning_rate=0.05),
                      loss_fn=_loss, num_workers=W, init_fn=_init)
    with pytest.raises(ValueError, match="codec"):
        _trainer("async", fleet=FleetConfig(plane="host"), codec="q8")


# ---------------------------------------------------------------------------
# up-front memory validation
# ---------------------------------------------------------------------------

def test_memory_validation_fails_fast_with_actionable_error(monkeypatch):
    gib = 1024 ** 3
    # 1024 workers x 1 GiB replicas cannot fit an 8 GiB device budget...
    with pytest.raises(ValueError, match="--plane host"):
        validate_fleet_memory(1024, gib, "device", available=8 * gib)
    # ...the host plane fits 3x more W in the same budget but still bounds it...
    with pytest.raises(ValueError, match="reduce --workers"):
        validate_fleet_memory(1024, gib, "host", available=8 * gib)
    need = validate_fleet_memory(2, gib, "host", available=8 * gib)
    assert need == 2 * 2 * gib
    # ...and an unknown platform (no /proc/meminfo) passes best-effort
    import repro.fleet.memory as mem
    monkeypatch.setattr(mem, "available_host_bytes", lambda: None)
    assert validate_fleet_memory(10 ** 6, gib, "device") > 0
