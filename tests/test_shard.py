"""repro.shard — sharded flat plane.

Layout properties (hypothesis-style): shards exactly cover every bucket
total for ANY (total, n_shards), including zero-size and scalar leaves, and
the per-shard raw-wire accounting sums exactly to the un-sharded wire (no
lane/shard padding ever charged). Engine semantics: the all-default
ShardConfig is bit-exact on all three engines (the inert anchor),
``comm_bytes`` accounts per-DEVICE egress (exactly wire/n_shards), the
checkpoint shard descriptor refuses cross-layout restores field-by-field,
memory validation admits under sharding what whole-replica refuses, and —
in the multi-device subprocess tests — the sim and dist engines produce the
same exchanged parameters under shard ∘ q8/topk while the dist ppermute
moves only local-shard-sized wires.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro import shard as shard_plane
from repro.api import GossipTrainer
from repro.comm import active_codec
from repro.common.config import (FaultConfig, FleetConfig, OptimizerConfig,
                                 ProtocolConfig, ShardConfig)
from repro.common.flat import FlatSpec
from repro.fleet import validate_fleet_memory

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 4


def _init(key):
    k1, k2 = jax.random.split(key)
    return {"w1": 0.3 * jax.random.normal(k1, (37, 19)),
            "b": jnp.zeros((19,)),
            "w2": 0.3 * jax.random.normal(k2, (19, 3))}


def _loss(p, x, y):
    h = jnp.tanh(x @ p["w1"] + p["b"])
    return jnp.mean((h @ p["w2"] - y) ** 2)


def _trainer(engine="sim", shard=None, codec=None, p=1.0, **kw):
    proto = ProtocolConfig(method="elastic_gossip", comm_probability=p,
                           moving_rate=0.5)
    return GossipTrainer(engine=engine, protocol=proto,
                         optimizer=OptimizerConfig(name="nag",
                                                   learning_rate=0.05,
                                                   momentum=0.9),
                         loss_fn=_loss, num_workers=W, init_fn=_init,
                         shard=shard, codec=codec, **kw)


def _run(trainer, steps=6, seed=0):
    state = trainer.init_state(seed)
    key = jax.random.PRNGKey(123)
    m = {}
    for _ in range(steps):
        key, k = jax.random.split(key)
        x = jax.random.normal(k, (W, 8, 37))
        y = jnp.zeros((W, 8, 3))
        state, m = trainer.step(state, (x, y))
    return state, m


# ---------------------------------------------------------------------------
# layout properties
# ---------------------------------------------------------------------------

def _spec_of(sizes):
    """FlatSpec over one f32 bucket with the given leaf sizes (0 -> a scalar
    () leaf, size 1)."""
    tree = {f"l{i}": jax.ShapeDtypeStruct((1,) + ((n,) if n else ()),
                                          jnp.float32)
            for i, n in enumerate(sizes)}
    return FlatSpec.build(tree, leading=1)


@settings(max_examples=40)
@given(n1=st.integers(min_value=0, max_value=700),
       n2=st.integers(min_value=0, max_value=5000),
       n_shards=st.integers(min_value=1, max_value=9))
def test_shards_exactly_cover_total(n1, n2, n_shards):
    """For ANY (leaf sizes, n_shards): equal quantum-aligned shards tile the
    padded total exactly, the padding stays under one shard-quantum stride,
    and the manifest's per-shard REAL element counts sum to the true
    parameter count — zero-size shards and scalar leaves included."""
    spec = _spec_of([n1, n2, 0])       # 0 -> a scalar () leaf
    layout = shard_plane.build_layout(spec, ShardConfig(n_shards=n_shards))
    for b, total in layout.totals.items():
        assert total == n_shards * layout.shard_sizes[b]
        assert total >= spec.totals[b]
        assert total - spec.totals[b] < n_shards * layout.quantum
        assert layout.shard_sizes[b] % layout.quantum == 0
        lo = 0
        for (a, c) in layout.bounds[b]:
            assert a == lo and c == a + layout.shard_sizes[b]
            lo = c
        assert lo == total
    man = shard_plane.shard_manifest(layout, spec)
    real = sum(sum(v) for v in man["real_elements"].values())
    assert real == sum(s.size for s in spec.slots)
    assert real == (n1 or 1) + (n2 or 1) + 1


@settings(max_examples=25)
@given(n1=st.integers(min_value=0, max_value=900),
       n2=st.integers(min_value=0, max_value=3000),
       n_shards=st.integers(min_value=1, max_value=8),
       codec=st.sampled_from([None, "q8", "topk"]))
def test_per_shard_wire_sums_exactly(n1, n2, n_shards, codec):
    """Raw per-shard wires sum EXACTLY to the un-sharded raw wire (padding is
    never charged), so per-device = raw/n_shards; codec shards are equal,
    block-aligned (the bit-parity precondition), and sum to the whole padded
    plane's wire (codec wires are linear in block count)."""
    spec = _spec_of([n1, n2])
    proto = ProtocolConfig(method="elastic_gossip", codec=codec or "none")
    cd = active_codec(proto)
    if cd is not None and cd.identity:
        cd = None
    layout = shard_plane.build_layout(spec, ShardConfig(n_shards=n_shards),
                                      cd)
    per = shard_plane.shard_wire_bytes(layout, spec, cd)
    assert len(per) == n_shards
    if cd is None:
        raw = sum(s.size * s.dtype.itemsize for s in spec.slots)
        assert sum(per) == raw
        assert shard_plane.wire_per_device(layout, spec, cd) * n_shards == raw
    else:
        for b in layout.shard_sizes:
            assert layout.shard_sizes[b] % cd.block == 0
        assert len(set(per)) == 1   # equal shards -> equal codec wires
        whole = sum(cd.wire_bytes(layout.totals[b], np.dtype(b).itemsize)
                    for b in layout.totals)
        assert sum(per) == whole


def test_pad_slice_shard_rows_roundtrip():
    spec = _spec_of([703, 19, 57])
    layout = shard_plane.build_layout(spec, ShardConfig(n_shards=4))
    bufs = {b: jnp.arange(3 * n, dtype=jnp.float32).reshape(3, n)
            for b, n in spec.totals.items()}
    padded = shard_plane.pad_bufs(bufs, layout)
    rows = layout.shard_rows(padded)
    for b in rows:
        assert rows[b].shape == (3 * 4, layout.shard_sizes[b])
    back = layout.unshard_rows(rows)
    sliced = shard_plane.slice_bufs(back, spec.totals)
    for b in bufs:
        np.testing.assert_array_equal(np.asarray(sliced[b]),
                                      np.asarray(bufs[b]))


# ---------------------------------------------------------------------------
# inert anchor — sim + async engines, bit-exact (dist in the subprocess test)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["sim", "async"])
@pytest.mark.parametrize("codec", [None, "q8"])
def test_default_shard_config_is_bit_exact(engine, codec):
    """ShardConfig() (n_shards=1) must reproduce shard=None bit-exactly:
    params, velocity, comm accounting and the PRNG key."""
    s0, m0 = _run(_trainer(engine, shard=None, codec=codec))
    s1, m1 = _run(_trainer(engine, shard=ShardConfig(), codec=codec))
    for k in s0.theta:
        np.testing.assert_array_equal(np.asarray(s0.theta[k]),
                                      np.asarray(s1.theta[k]))
        np.testing.assert_array_equal(np.asarray(s0.opt.mu[k]),
                                      np.asarray(s1.opt.mu[k]))
    np.testing.assert_array_equal(np.asarray(s0.key), np.asarray(s1.key))
    assert float(m0["comm_bytes"]) == float(m1["comm_bytes"])


# ---------------------------------------------------------------------------
# per-device comm accounting (sim engine, single device)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine,codec", [("sim", None), ("sim", "q8"),
                                          ("sim", "topk"), ("async", "q8")])
def test_comm_bytes_scale_per_device(engine, codec):
    """With n_shards=S each device ships only its local shard: the facade
    wire account and the engine's cumulative comm_bytes both equal exactly
    the un-sharded account divided by S (raw: identical real bytes split S
    ways; codec: equal block-aligned shards). S is chosen so the shard
    padding is a no-op and the division is exact end-to-end."""
    S = 4 if codec is None else 2
    t0 = _trainer(engine, codec=codec)
    tS = _trainer(engine, shard=ShardConfig(n_shards=S), codec=codec)
    s0, m0 = _run(t0)
    sS, mS = _run(tS)
    assert t0._backend.wire_bytes() % S == 0
    assert tS._backend.wire_bytes() == t0._backend.wire_bytes() // S
    assert float(mS["comm_bytes"]) > 0
    assert float(m0["comm_bytes"]) == S * float(mS["comm_bytes"])
    if codec is None:
        # raw wires are lossless and the padding is a no-op here: the sharded
        # run IS the base run
        for k in s0.theta:
            w = s0.theta[k].shape[-1]
            np.testing.assert_array_equal(np.asarray(s0.theta[k]),
                                          np.asarray(sS.theta[k][..., :w]))


def test_shard_refuses_non_pairwise_and_faults_and_host_plane():
    proto = ProtocolConfig(method="allreduce")
    with pytest.raises(ValueError, match="pairwise"):
        GossipTrainer(engine="sim", protocol=proto,
                      optimizer=OptimizerConfig(name="nag",
                                                learning_rate=0.05),
                      loss_fn=_loss, num_workers=W, init_fn=_init,
                      shard=ShardConfig(n_shards=2))
    with pytest.raises(ValueError, match="fault"):
        _trainer("sim", shard=ShardConfig(n_shards=2),
                 faults=FaultConfig(fault_model="drop", fault_rate=0.1))
    with pytest.raises(ValueError, match="shard"):
        _trainer("async", shard=ShardConfig(n_shards=2),
                 fleet=FleetConfig(plane="host"))


def test_host_plane_codec_refused_up_front():
    """Satellite: FleetConfig(plane='host') + codec must refuse at FACADE
    construction (host wires are raw rows), on any engine, before a backend
    is even built."""
    for engine, codec in (("async", "q8"), ("sim", "topk")):
        with pytest.raises(ValueError,
                           match="codecs unsupported on plane='host'"):
            _trainer(engine, fleet=FleetConfig(plane="host"), codec=codec)


# ---------------------------------------------------------------------------
# partition ∘ shard: chunks on the global total, realized on local shards
# ---------------------------------------------------------------------------

def test_partition_composes_with_shard():
    S = 2
    fleet = FleetConfig(partition=2)
    t0 = _trainer(codec="q8", fleet=fleet)
    tS = _trainer(codec="q8", fleet=fleet, shard=ShardConfig(n_shards=S))
    s0, m0 = _run(t0, steps=8)
    sS, mS = _run(tS, steps=8)
    assert int(np.asarray(sS.proto.chunk_units).sum()) > 0
    # same hash-drawn chunk schedule, each chunk accounted per device:
    # exactly 1/S of the whole-replica partitioned run
    assert float(mS["comm_bytes"]) > 0
    assert float(m0["comm_bytes"]) == S * float(mS["comm_bytes"])
    for b in sS.theta.values():
        assert np.isfinite(np.asarray(b)).all()


# ---------------------------------------------------------------------------
# checkpoint v2: shard descriptor validated field-by-field BEFORE arrays
# ---------------------------------------------------------------------------

def test_checkpoint_refuses_cross_shard_layout(tmp_path):
    path = str(tmp_path / "ck.npz")
    t2 = _trainer(shard=ShardConfig(n_shards=2))
    s2, _ = _run(t2, steps=2)
    t2.save_checkpoint(path, s2)

    # different n_shards: field-by-field diff, raised before restore
    t4 = _trainer(shard=ShardConfig(n_shards=4))
    with pytest.raises(ValueError, match="n_shards: saved=2"):
        t4.load_checkpoint(path, t4.init_state(0))

    # un-sharded trainer refuses a sharded checkpoint...
    t0 = _trainer()
    with pytest.raises(ValueError, match="sharded plane"):
        t0.load_checkpoint(path, t0.init_state(0))

    # ...and a sharded trainer refuses an un-sharded checkpoint
    path0 = str(tmp_path / "ck0.npz")
    s0, _ = _run(t0, steps=2)
    t0.save_checkpoint(path0, s0)
    with pytest.raises(ValueError, match="WITHOUT a sharded plane"):
        t2.load_checkpoint(path0, t2.init_state(0))

    # matching layout round-trips bit-exactly
    t2b = _trainer(shard=ShardConfig(n_shards=2))
    restored, _meta = t2b.load_checkpoint(path, t2b.init_state(0))
    for k in s2.theta:
        np.testing.assert_array_equal(np.asarray(restored.theta[k]),
                                      np.asarray(s2.theta[k]))


# ---------------------------------------------------------------------------
# memory validation: per-device shard size admits big-model configs
# ---------------------------------------------------------------------------

def test_memory_validation_uses_per_device_shard_size():
    gib = 1024 ** 3
    # whole-replica: 8 workers x 4 GiB refuses an 8 GiB budget, and the
    # error points at --shard...
    with pytest.raises(ValueError, match="--shard"):
        validate_fleet_memory(8, 4 * gib, "device", available=8 * gib)
    # ...the same config shard-fits at 1/64 of the plane per device...
    need = validate_fleet_memory(8, 4 * gib, "device", available=8 * gib,
                                 n_shards=64)
    assert need == 8 * 4 * gib * 6 // 64
    # ...and an over-subscribed SHARDED config still refuses, with the
    # sharded hint
    with pytest.raises(ValueError, match="raise --shard"):
        validate_fleet_memory(64, 16 * gib, "device", available=8 * gib,
                              n_shards=2)


# ---------------------------------------------------------------------------
# multi-device subprocess tests: dist engine wires
# ---------------------------------------------------------------------------

def run_sub(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


SETUP = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.api import GossipTrainer
    from repro.common.config import (MeshConfig, OptimizerConfig,
                                     ProtocolConfig, ShardConfig)
    from repro.launch.mesh import make_worker_mesh

    # 8 host devices: W=4 replicas x S=2 plane shards over the 'model' axis
    mcfg = MeshConfig(data=4, model=2, pods=1, workers_per_pod=4)
    mesh = make_worker_mesh(mcfg)
    W, S = 4, 2

    def init_fn(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (37, 19)), "b": jnp.zeros((19,)),
                "w2": jax.random.normal(k2, (19, 3))}

    params_axes = {"w1": (None, None), "b": (None,), "w2": (None, None)}

    def sim_loss(p, x, y):
        h = jnp.tanh(x @ p["w1"] + p["b"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    def dist_loss(p, batch):
        return sim_loss(p, batch["x"], batch["y"])

    opt = OptimizerConfig(name="nag", learning_rate=0.05, momentum=0.9)

    single = init_fn(jax.random.PRNGKey(0))
    stack = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (W,) + x.shape)
        + 0.01 * jax.random.normal(jax.random.PRNGKey(7), (W,) + x.shape),
        single)
    active = jnp.ones((W,), jnp.float32)
"""


@pytest.mark.slow
def test_sim_vs_dist_wire_parity_under_shard_codec():
    """Acceptance: under shard ∘ q8/topk the sim and dist engines produce the
    same exchanged parameters round-for-round — the wires (per-shard codec
    blocks + per-(worker,shard) seed streams) are bit-identical; the applied
    mix is compared at the engines' standard fp tolerance. The facades also
    agree on the per-DEVICE wire account."""
    out = run_sub(SETUP + """
    for codec_name in ("q8", "topk"):
        proto = ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                               moving_rate=0.5, codec=codec_name)
        shard = ShardConfig(n_shards=2)
        dist = GossipTrainer(engine="dist", protocol=proto, optimizer=opt,
                             mesh=mesh, mesh_cfg=mcfg, init_fn=init_fn,
                             params_axes=params_axes, shard=shard)
        sim = GossipTrainer(engine="sim", protocol=proto, optimizer=opt,
                            loss_fn=sim_loss, num_workers=W, init_fn=init_fn,
                            mesh_cfg=mcfg, shard=shard)
        sim.init_state(0)
        assert sim._backend.wire_bytes() == dist._backend.wire_bytes(), (
            codec_name, sim._backend.wire_bytes(), dist._backend.wire_bytes())
        for rnd in range(4):
            out_d = dist.gossip_exchange(stack, active, rnd)
            out_s = sim.gossip_exchange(stack, active, rnd)
            for k in out_d:
                np.testing.assert_allclose(np.asarray(out_d[k]),
                                           np.asarray(out_s[k]),
                                           rtol=1e-6, atol=1e-6,
                                           err_msg=codec_name)
        print(codec_name, "wire/device:", dist._backend.wire_bytes())
    print("PARITY-OK")
    """)
    assert "PARITY-OK" in out


@pytest.mark.slow
def test_dist_shard_wire_is_local_shard_sized_and_anchor_bit_exact():
    """Acceptance: (a) the sharded gossip program's uint8 ppermute wires
    shrink vs the whole-replica program by EXACTLY the analytic codec-wire
    difference wire(total) - wire(shard_size) — each exchange ships only the
    local shard; the facade accounts wire_per_device and per-step comm_bytes
    advance by exactly that. (b) The all-default ShardConfig reproduces the
    un-sharded dist run bit-exactly."""
    out = run_sub(SETUP + """
    from repro import shard as shard_layout_mod
    from repro.comm import active_codec

    def collect(jaxpr, out):
        for e in jaxpr.eqns:
            if e.primitive.name == "ppermute":
                out.append(e)
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "jaxpr"):
                        collect(sub.jaxpr, out)
                    elif hasattr(sub, "eqns"):
                        collect(sub, out)
        return out

    proto = ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                           moving_rate=0.5, codec="q8")

    def make(shard, p2=None):
        return GossipTrainer(engine="dist", protocol=p2 or proto,
                             optimizer=opt, mesh=mesh, mesh_cfg=mcfg,
                             init_fn=init_fn, params_axes=params_axes,
                             loss_fn=dist_loss, global_batch=8, seq_len=4,
                             shard=shard)

    def ppermute_bytes(facade):
        tr = facade._backend.trainer
        bufs = {k: jnp.zeros((W, n), jnp.dtype(k))
                for k, n in tr.flat_spec.totals.items()}
        step = tr._apply_gossip
        jaxpr = jax.make_jaxpr(lambda b, a, r: step(b, a, r))(
            bufs, active, jnp.int32(0))
        pp = collect(jaxpr.jaxpr, [])
        assert pp, "no ppermute found"
        assert {str(e.invars[0].aval.dtype) for e in pp} == {"uint8"}
        return len(pp), sum(int(np.prod(e.invars[0].aval.shape))
                            for e in pp)

    whole, sharded = make(None), make(ShardConfig(n_shards=2))
    n0, b0 = ppermute_bytes(whole)
    n1, b1 = ppermute_bytes(sharded)
    cd = active_codec(proto)
    layout = sharded._backend.trainer.shard_layout
    total = layout.totals["float32"]
    # same round structure; each round's wire shrinks by exactly the
    # analytic difference (any fixed per-message framing cancels out)
    assert n0 == n1, (n0, n1)
    assert b0 - b1 == n0 * (cd.wire_bytes(total, 4) - cd.wire_bytes(
        layout.shard_sizes["float32"], 4)), (n0, b0, b1)
    shard_wire = int(shard_layout_mod.wire_per_device(
        layout, sharded._backend.trainer.flat_spec, cd))
    assert sharded._backend.wire_bytes() == shard_wire
    assert whole._backend.wire_bytes() == cd.wire_bytes(total, 4)

    # per-exchange accounting: comm_bytes advance by the local-shard wire
    rng = np.random.RandomState(0)
    batch = {"x": jnp.asarray(rng.normal(size=(W, 8, 37)).astype(np.float32)),
             "y": jnp.zeros((W, 8, 3))}
    sharded._backend.trainer.batch_specs = lambda: {"x": None, "y": None}
    st = sharded.init_state(0)
    for i in range(3):
        st, m = sharded.step(st, batch)
    assert float(m["comm_bytes"]) == 3.0 * shard_wire, m["comm_bytes"]

    # (b) inert anchor on the dist engine: raw wire, default ShardConfig
    p_raw = ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                           moving_rate=0.5)

    def train(shard):
        t = make(shard, p2=p_raw)
        t._backend.trainer.batch_specs = lambda: {"x": None, "y": None}
        s = t.init_state(0)
        for i in range(3):
            s, mm = t.step(s, batch)
        return s, mm

    s0, m0 = train(None)
    s1, m1 = train(ShardConfig())
    for k in s0.theta:
        np.testing.assert_array_equal(np.asarray(s0.theta[k]),
                                      np.asarray(s1.theta[k]))
        np.testing.assert_array_equal(np.asarray(s0.opt.mu[k]),
                                      np.asarray(s1.opt.mu[k]))
    assert float(m0["comm_bytes"]) == float(m1["comm_bytes"])
    print("DIST-SHARD-OK")
    """)
    assert "DIST-SHARD-OK" in out


@pytest.mark.slow
def test_dist_sharded_training_converges_and_mesh_mismatch_refuses():
    """End-to-end sharded dist training: the fused path inside shard_map on
    the (fsdp,model)-sharded plane stays finite, converges and communicates;
    an n_shards that doesn't match the mesh product refuses up front."""
    out = run_sub(SETUP + """
    proto = ProtocolConfig(method="elastic_gossip", comm_probability=1.0,
                           moving_rate=0.5, codec="q8")

    def make(shard):
        return GossipTrainer(engine="dist", protocol=proto, optimizer=opt,
                             mesh=mesh, mesh_cfg=mcfg, init_fn=init_fn,
                             params_axes=params_axes, loss_fn=dist_loss,
                             global_batch=8, seq_len=4, shard=shard)

    tr = make(ShardConfig(n_shards=2))
    tr._backend.trainer.batch_specs = lambda: {"x": None, "y": None}
    st = tr.init_state(0)
    rng = np.random.RandomState(1)
    losses = []
    for i in range(6):
        x = jnp.asarray(rng.normal(size=(W, 8, 37)).astype(np.float32))
        y = jnp.zeros((W, 8, 3))
        st, m = tr.step(st, {"x": x, "y": y})
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    assert float(m["comm_bytes"]) > 0

    # wrong mesh product for n_shards refuses with the mesh shape in the
    # error
    try:
        make(ShardConfig(n_shards=4))
        raise SystemExit("expected ValueError")
    except ValueError as e:
        assert "n_shards=4" in str(e) and "mesh" in str(e), str(e)
    print("DIST-TRAIN-OK")
    """)
    assert "DIST-TRAIN-OK" in out
