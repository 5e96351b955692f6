"""repro.comm tests: codec registry round-trip, Pallas kernel-vs-oracle
parity (interpret mode), wire packing, wire-byte accounting, the comm_bytes
precision fix, and sim-engine codec behavior (compression ratio, q8
convergence vs uncompressed, error-feedback residual + checkpoint)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import GossipTrainer, resolve
from repro.comm import (Codec, CommState, available_codecs, codec_seeds,
                        get_codec, register_codec, resolve_codec,
                        unregister_codec, wire_param_bytes)
from repro.common.config import OptimizerConfig, ProtocolConfig
from repro.common.flat import FlatSpec
from repro.core.gossip_sim import SimTrainer
from repro.kernels import ops, ref
from repro.models import simple

BUILTIN_CODECS = {"none", "q8", "topk"}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_codec_registry_roundtrip():
    names = available_codecs()
    assert BUILTIN_CODECS <= set(names)
    for name in names:
        cls = get_codec(name)
        assert issubclass(cls, Codec)
        assert cls.name == name


def test_unknown_codec_raises_with_candidates():
    with pytest.raises(ValueError, match="unknown codec"):
        get_codec("carrier_pigeon")
    # ...and already at protocol-resolve time, before any engine is built
    with pytest.raises(ValueError, match="unknown codec"):
        resolve(ProtocolConfig(method="elastic_gossip", comm_probability=0.5,
                               codec="carrier_pigeon"))


def test_register_codec_extension_point():
    @register_codec("_test_half")
    class Half(Codec):
        def wire_bytes(self, n, itemsize):
            return n * itemsize // 2

    try:
        assert "_test_half" in available_codecs()
        impl = resolve_codec(ProtocolConfig(codec="_test_half"))
        assert isinstance(impl, Half)
        with pytest.raises(ValueError, match="already registered"):
            @register_codec("_test_half")
            class Clash(Codec):
                pass
    finally:
        unregister_codec("_test_half")
    assert "_test_half" not in available_codecs()


def test_codec_rejected_for_non_pairwise_protocols():
    for method in ("allreduce", "easgd", "none"):
        kw = dict(comm_period=2) if method == "easgd" else {}
        with pytest.raises(ValueError, match="not pairwise"):
            resolve(ProtocolConfig(method=method, codec="q8", **kw))


# ---------------------------------------------------------------------------
# kernel-vs-oracle parity (interpret mode) — bit-exact, like fused_update's
# ---------------------------------------------------------------------------

def _buf(W=3, N=1000, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (W, N))


@pytest.mark.parametrize("N,block", [(1000, 256), (128, 128), (700, 512),
                                     (70 * 128, 128)])
def test_q8_kernel_matches_oracle(N, block):
    buf = _buf(N=N)
    seeds = codec_seeds(3, jnp.arange(buf.shape[0]))
    vo, so = ref.q8_encode(buf, seeds, block=block)
    vk, sk = ops.q8_encode(buf, seeds, block=block, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(vk))
    np.testing.assert_array_equal(np.asarray(so), np.asarray(sk))
    do = ref.q8_decode(vo, so, N, block=block)
    dk = ops.q8_decode(vk, sk, N, block=block, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(do), np.asarray(dk))
    # reconstruction error is bounded by one quantization step per element
    err = np.abs(np.asarray(do) - np.asarray(buf))
    assert err.max() <= float(so.max()) + 1e-6


def test_q8_rounding_is_seed_deterministic_and_varies_with_seed():
    buf = _buf()
    s0 = codec_seeds(0, jnp.arange(3))
    s1 = codec_seeds(1, jnp.arange(3))
    a0, _ = ref.q8_encode(buf, s0, block=256)
    a0b, _ = ref.q8_encode(buf, s0, block=256)
    a1, _ = ref.q8_encode(buf, s1, block=256)
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a0b))
    assert np.any(np.asarray(a0) != np.asarray(a1))


@pytest.mark.parametrize("N,k,block", [(1000, 13, 256), (512, 1, 512), (300, 8, 128),
                                       (70 * 128, 3, 128)])
def test_topk_kernel_matches_oracle(N, k, block):
    buf = _buf(N=N, seed=4)
    res = 0.1 * _buf(N=N, seed=5)
    vo, io_, ro = ref.topk_encode(buf, res, k=k, block=block)
    vk, ik, rk = ops.topk_encode(buf, res, k=k, block=block,
                                 use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(vk))
    np.testing.assert_array_equal(np.asarray(io_), np.asarray(ik))
    np.testing.assert_array_equal(np.asarray(ro), np.asarray(rk))
    do = ref.topk_decode(vo, io_, N, k=k, block=block)
    dk = ops.topk_decode(vk, ik, N, k=k, block=block, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(do), np.asarray(dk))
    # error feedback invariant: decode + residual' == buf + residual exactly
    np.testing.assert_allclose(np.asarray(do) + np.asarray(ro),
                               np.asarray(buf + res), rtol=1e-6, atol=1e-6)


def test_topk_selects_largest_magnitudes():
    buf = jnp.zeros((1, 256)).at[0, 7].set(5.0).at[0, 200].set(-9.0).at[0, 31].set(1.0)
    vals, idx, res = ref.topk_encode(buf, jnp.zeros_like(buf), k=2, block=256)
    assert set(np.asarray(idx[0]).tolist()) == {7, 200}
    assert float(jnp.abs(res).sum()) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# wire packing + byte accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["q8", "topk"])
def test_pack_unpack_roundtrip_and_wire_len(name):
    cfg = ProtocolConfig(codec=name, codec_block=256, codec_topk_frac=0.05)
    codec = resolve_codec(cfg)
    buf = _buf(N=1000)
    wire, _ = codec.encode(buf, codec_seeds(0, jnp.arange(3)),
                           residual=jnp.zeros_like(buf) if codec.stateful else None)
    packed = codec.pack(wire)
    assert packed.dtype == jnp.uint8
    # the packed buffer IS the accounted wire: lengths must agree exactly
    assert packed.shape[1] == codec.wire_bytes(1000, 4)
    for a, b in zip(wire, codec.unpack(packed, 1000)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(codec.decode(wire, 1000)),
        np.asarray(codec.decode_wire(packed, 1000)))


def test_wire_param_bytes_compression_ratios():
    spec = FlatSpec.build({"w": jnp.zeros((2, 100_000)), "b": jnp.zeros((2, 50))},
                          leading=1)
    raw = spec.num_elements() * 4
    cfg = ProtocolConfig(codec="q8", codec_block=512)
    q8 = wire_param_bytes(resolve_codec(cfg), spec)
    # int8 values + f32 scale per 512 elems: ~3.97x below the padded plane
    assert raw / q8 == pytest.approx(4.0 / (1 + 4 / 512), rel=0.01)
    cfgt = ProtocolConfig(codec="topk", codec_block=512, codec_topk_frac=0.05)
    topk = wire_param_bytes(resolve_codec(cfgt), spec)
    # 8 bytes per kept element, ~5% of each block kept: 2048 raw bytes/block
    # vs 26 * 8 wire bytes/block
    assert raw / topk == pytest.approx(512 * 4 / (8 * 26), rel=0.02)
    none = wire_param_bytes(resolve_codec(ProtocolConfig(codec="none")), spec)
    assert none == raw


# ---------------------------------------------------------------------------
# comm_bytes precision (satellite): exact integer accumulator
# ---------------------------------------------------------------------------

def test_comm_bytes_increments_survive_f32_granularity():
    """Old bug: ``comm_bytes`` accumulated in float32, so once the running
    total passed 2^24 x increment granularity, ``+=`` silently dropped every
    further increment. The accumulator is now the exact int32 ``comm_units``
    (host-side dist accounting is already python float64); ``comm_bytes`` is
    derived from it, so increments keep landing forever."""
    W = 4
    impl = resolve(ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                                  moving_rate=0.5, topology="uniform"))
    theta = {"w": jnp.zeros((W, 256))}
    per_event = impl.comm_cost(impl.wire_stack_bytes(theta), W).bytes_per_event
    assert per_event == 256 * 4
    big = 1 << 26                      # far past f32's 2^24 exact-int range
    state = impl.init_state(theta)._replace(comm_units=jnp.int32(big))
    active = jnp.ones((W,), bool)
    steps = 10

    # the OLD accumulate-in-f32 scheme drops all of these increments
    lost = jnp.float32((per_event / W) * big)
    for _ in range(steps):
        lost = lost + jnp.float32(per_event * 1.0)   # frac = 1
    assert float(lost) == float(jnp.float32((per_event / W) * big))

    key = jax.random.PRNGKey(0)
    for _ in range(steps):
        _, state = impl.comm_update(key, active, theta, state)
    # exact integer accounting...
    assert int(state.comm_units) == big + steps * W
    # ...and the derived f32 report tracks the float64 ground truth
    truth = (per_event / W) * (big + steps * W)
    assert float(state.comm_bytes) == pytest.approx(truth, rel=1e-6)
    assert float(state.comm_bytes) > float(lost)


def test_dropped_exchanges_do_not_count_comm_bytes():
    """repro.faults accounting contract: a dropped or corrupt-discarded wire
    is NOT an applied exchange, so it must not appear in the exact
    ``comm_units`` accumulator (nor in the derived ``comm_bytes``) — only in
    the ``wire_dropped``/``wire_corrupt`` fault counters."""
    from repro.api.protocols import WireFaults
    W = 4
    impl = resolve(ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                                  moving_rate=0.5, topology="uniform"))
    theta = {"w": jnp.zeros((W, 256))}
    per_event = impl.comm_cost(impl.wire_stack_bytes(theta), W).bytes_per_event
    state = impl.init_state(theta)._replace(
        wire_dropped=jnp.int32(0), wire_corrupt=jnp.int32(0))
    active = jnp.ones((W,), bool)
    key = jax.random.PRNGKey(0)

    dropped = jnp.asarray([True, False, True, False])
    _, st = impl.comm_update(key, active, theta, state,
                             wire_faults=WireFaults(dropped=dropped))
    # 2 of 4 senders lost their wire: only the surviving participations count
    assert int(st.comm_units) == W - 2
    assert float(st.comm_bytes) == pytest.approx((per_event / W) * (W - 2))
    assert int(st.wire_dropped) == 2 and int(st.wire_corrupt) == 0

    # corrupt-discarded wires follow the same rule, via the corrupt counter
    corrupt = jnp.asarray([False, True, False, False])
    _, st2 = impl.comm_update(key, active, theta, state,
                              wire_faults=WireFaults(corrupt=corrupt))
    assert int(st2.comm_units) == W - 1
    assert int(st2.wire_corrupt) == 1 and int(st2.wire_dropped) == 0

    # an all-clear fault mask is accounting-identical to no faults at all
    _, st3 = impl.comm_update(key, active, theta, state,
                              wire_faults=WireFaults(
                                  dropped=jnp.zeros((W,), bool)))
    _, st4 = impl.comm_update(key, active, theta, state)
    assert int(st3.comm_units) == int(st4.comm_units) == W
    assert float(st3.comm_bytes) == float(st4.comm_bytes)


def test_flow_skipped_exchanges_do_not_count_comm_bytes():
    """repro.fleet extension of the applied-exchange accounting contract: an
    initiation skipped by token-account flow control never rides the wire, so
    it must not appear in ``comm_units``/``comm_bytes`` — only in the
    ``flow_skipped`` counter. A 3-token non-replenishing account with p=1
    means every worker initiates exactly 3 times, then skips forever."""
    from repro.common.config import FleetConfig
    W, steps = 4, 10
    proto = ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                           moving_rate=0.5, topology="uniform")
    fleet = FleetConfig(flow_control="token_account", token_capacity=3.0,
                        token_rate=0.0, token_init=3.0)
    t = GossipTrainer(
        engine="sim", protocol=proto, fleet=fleet,
        optimizer=OptimizerConfig(name="nag", learning_rate=0.05, momentum=0.9),
        loss_fn=_mlp_loss, num_workers=W,
        init_fn=lambda key: simple.init_mlp(key, in_dim=10, hidden=16, depth=2,
                                            num_classes=3)[0])
    s = t.init_state(0)
    x, y = _problem()
    for _ in range(steps):
        s, m = t.step(s, (x, y))
    assert int(s.proto.comm_units) == 3 * W
    assert int(s.proto.flow_skipped) == (steps - 3) * W
    assert float(s.proto.comm_bytes) == pytest.approx(
        3 * t.comm_cost().bytes_per_event, rel=1e-6)
    np.testing.assert_array_equal(np.asarray(s.proto.tokens),
                                  np.zeros((W,), np.float32))


# ---------------------------------------------------------------------------
# sim engine: codec wiring end-to-end
# ---------------------------------------------------------------------------

def _problem(W=4, n=48, d=10, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    protos = rng.randn(classes, d) * 2
    y = rng.randint(0, classes, (W, n)).astype(np.int32)
    x = protos[y] + rng.randn(W, n, d).astype(np.float32)
    return jnp.asarray(x, jnp.float32), jnp.asarray(y)


def _mlp_loss(params, x, y):
    return simple.xent_loss(simple.mlp_logits(params, x), y)


def _sim_run(codec, W=4, steps=40, hidden=64, fused=True, method="elastic_gossip",
             **proto_kw):
    proto_kw.setdefault("comm_probability", 0.5)
    proto = ProtocolConfig(method=method, moving_rate=0.5, topology="uniform",
                           codec=codec, **proto_kw)
    params, _ = simple.init_mlp(jax.random.PRNGKey(0), in_dim=10, hidden=hidden,
                                depth=2, num_classes=3)
    stack = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (W,) + a.shape) + 0.0,
                         params)
    t = SimTrainer(_mlp_loss, W, proto,
                   OptimizerConfig(name="nag", learning_rate=0.05, momentum=0.9),
                   fused_update=fused)
    st = t.init(stack, 7)
    x, y = _problem(W)
    losses = []
    for _ in range(steps):
        st, m = t.step(st, x, y)
        losses.append(float(m["loss_mean"]))
    return t, st, losses


def test_sim_comm_bytes_shrink_by_compression_ratio():
    # hidden=64 keeps lane padding negligible, so the measured ratio matches
    # the codec's analytic compression ratio
    _, s_none, _ = _sim_run("none", steps=12)
    _, s_q8, _ = _sim_run("q8", steps=12)
    assert int(s_none.proto.comm_units) == int(s_q8.proto.comm_units) > 0
    ratio = float(s_none.proto.comm_bytes) / float(s_q8.proto.comm_bytes)
    # uncompressed accounting counts raw (unpadded) param bytes; the codec
    # wire counts the padded flat plane it actually ships
    from repro.api.protocols import stacked_param_bytes
    spec = FlatSpec.build(s_none.params, leading=1)
    expected = stacked_param_bytes(s_none.params) / wire_param_bytes(
        resolve_codec(ProtocolConfig(codec="q8")), spec)
    assert ratio == pytest.approx(expected, rel=1e-5)
    assert ratio > 3.5


def test_sim_q8_converges_close_to_uncompressed():
    """Acceptance (c), sim engine: a short elastic-gossip run with q8 lands
    within 5% relative final-loss of the uncompressed run."""
    _, s_none, l_none = _sim_run("none", steps=40)
    _, s_q8, l_q8 = _sim_run("q8", steps=40)
    assert l_q8[-1] < l_q8[0] * 0.7                   # it actually trains
    assert abs(l_q8[-1] - l_none[-1]) <= 0.05 * abs(l_none[-1]) + 0.02


@pytest.mark.parametrize("codec", ["q8", "topk"])
def test_sim_fused_matches_per_leaf_path_with_codec(codec):
    """The codec applies on the flat plane BEFORE the update, so fused and
    per-leaf paths must stay numerically identical under compression."""
    tf_, sf, _ = _sim_run(codec, steps=8, fused=True)
    tu, su, _ = _sim_run(codec, steps=8, fused=False)
    assert tf_.fused_update and not tu.fused_update
    for a, b in zip(jax.tree.leaves(sf.params), jax.tree.leaves(su.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sf.proto.comm_bytes),
                               np.asarray(su.proto.comm_bytes), rtol=1e-6)


@pytest.mark.parametrize("method,kw", [
    ("gossiping_pull", dict(comm_probability=0.5)),
    ("gossiping_push", dict(comm_period=2, comm_probability=0.0)),
])
def test_sim_codec_works_for_all_pairwise_protocols(method, kw):
    """ProtocolConfig(method=..., codec="q8") must work for every pairwise
    protocol (elastic_gossip is covered by the convergence test)."""
    _, st, losses = _sim_run("q8", steps=25, method=method, **kw)
    assert losses[-1] < losses[0] * 0.8, (method, losses[0], losses[-1])
    assert int(st.proto.comm_units) > 0
    assert np.isfinite(float(st.proto.comm_bytes))


def test_sim_topk_carries_error_feedback_residual():
    t, st, _ = _sim_run("topk", steps=10, comm_probability=1.0)
    assert t.codec is not None and t.codec.stateful
    res_l1 = sum(float(jnp.abs(r).sum()) for r in jax.tree.leaves(st.comm.residual))
    assert res_l1 > 0
    # stateless codecs keep an empty CommState
    t2, st2, _ = _sim_run("q8", steps=2)
    assert st2.comm == CommState(None)


def test_residual_only_advances_for_participating_workers():
    """Error-feedback bookkeeping: a worker whose own gate did NOT fire must
    carry its residual unchanged through a fired round (its wire may be
    discarded by the receiver — dropping the mass would lose it forever),
    while firing workers' residuals advance."""
    from repro.comm import codec_seeds, roundtrip_bufs
    codec = resolve_codec(ProtocolConfig(codec="topk", codec_block=128,
                                         codec_topk_frac=0.1))
    W, N = 4, 256
    bufs = {"float32": _buf(W=W, N=N, seed=9)}
    res = {"float32": 0.3 * _buf(W=W, N=N, seed=10)}
    gate = jnp.asarray([1.0, 0.0, 1.0, 0.0]).reshape(-1, 1)
    _, new_res = roundtrip_bufs(codec, bufs, codec_seeds(0, jnp.arange(W)),
                                res, gate=gate)
    r0, r1 = np.asarray(res["float32"]), np.asarray(new_res["float32"])
    for w, fired in enumerate([True, False, True, False]):
        if fired:
            assert not np.array_equal(r1[w], r0[w]), w
        else:
            np.testing.assert_array_equal(r1[w], r0[w])


def test_facade_codec_override_and_checkpoint_roundtrip(tmp_path):
    """GossipTrainer(codec=...) overrides the protocol config; CommState
    (the topk residual) round-trips through save/load_checkpoint and the
    resumed run continues it — bit-identical next step."""
    proto = ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                           moving_rate=0.5, topology="uniform")
    trainer = GossipTrainer(
        engine="sim", protocol=proto, codec="topk",
        optimizer=OptimizerConfig(name="nag", learning_rate=0.05, momentum=0.9),
        loss_fn=_mlp_loss, num_workers=4,
        init_fn=lambda key: simple.init_mlp(key, in_dim=10, hidden=16, depth=2,
                                            num_classes=3)[0])
    assert trainer.protocol.codec == "topk"
    state = trainer.init_state(0)
    x, y = _problem()
    for _ in range(5):
        state, m = trainer.step(state, (x, y))
    res_before = [np.asarray(r) for r in jax.tree.leaves(state.comm.residual)]
    assert sum(np.abs(a).sum() for a in res_before) > 0
    path = str(tmp_path / "ck.npz")
    trainer.save_checkpoint(path, state, meta={"step": 5})
    restored, meta = trainer.load_checkpoint(path, trainer.init_state(1))
    for a, b in zip(res_before, jax.tree.leaves(restored.comm.residual)):
        np.testing.assert_array_equal(a, np.asarray(b))
    s_resumed, _ = trainer.step(restored, (x, y))
    s_cont, _ = trainer.step(state, (x, y))
    for a, b in zip(jax.tree.leaves(s_cont.params), jax.tree.leaves(s_resumed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_facade_comm_cost_reports_wire_bytes():
    proto = ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                           moving_rate=0.5, topology="uniform")
    raw_t = GossipTrainer(engine="sim", protocol=proto, loss_fn=_mlp_loss,
                          num_workers=4,
                          init_fn=lambda key: simple.init_mlp(
                              key, in_dim=10, hidden=64, depth=2, num_classes=3)[0])
    q8_t = GossipTrainer(engine="sim", protocol=proto, codec="q8",
                         loss_fn=_mlp_loss, num_workers=4,
                         init_fn=lambda key: simple.init_mlp(
                             key, in_dim=10, hidden=64, depth=2, num_classes=3)[0])
    s_raw, s_q8 = raw_t.init_state(0), q8_t.init_state(0)
    ratio = raw_t.comm_cost().bytes_per_event / q8_t.comm_cost().bytes_per_event
    assert ratio > 3.5
    # live accounting agrees with the analytic wire cost (p=1: every step)
    x, y = _problem()
    for _ in range(3):
        s_q8, m = q8_t.step(s_q8, (x, y))
    assert float(m["comm_bytes"]) == pytest.approx(
        3 * q8_t.comm_cost().bytes_per_event, rel=1e-6)
