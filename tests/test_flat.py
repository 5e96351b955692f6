"""Flat parameter plane (repro.common.flat) + flat fused kernels: round-trip,
lane alignment, mixed dtypes, and interpret-mode kernel parity vs the ref
oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.flat import LANE, FlatSpec
from repro.kernels import fused_update as fu
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(11)


def mixed_tree(W=4):
    ks = jax.random.split(KEY, 4)
    return {"w": jax.random.normal(ks[0], (W, 16, 8)),
            "b": jax.random.normal(ks[1], (W, 7)),
            "h": jax.random.normal(ks[2], (W, 33)).astype(jnp.bfloat16),
            "s": jax.random.normal(ks[3], (W,))}


# ---------------------------------------------------------------------------
# FlatSpec
# ---------------------------------------------------------------------------

def test_flatten_unflatten_roundtrip_identity():
    tree = mixed_tree()
    spec = FlatSpec.build(tree, leading=1)
    back = spec.unflatten(spec.flatten(tree))
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(tree[k]), np.asarray(back[k]))


def test_roundtrip_without_leading_dims():
    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((130,))}
    spec = FlatSpec.build(tree, leading=0)
    back = spec.unflatten(spec.flatten(tree))
    for k in tree:
        np.testing.assert_array_equal(np.asarray(tree[k]), np.asarray(back[k]))


def test_offsets_lane_aligned_and_buckets_by_dtype():
    tree = mixed_tree()
    spec = FlatSpec.build(tree, leading=1)
    assert all(s.offset % LANE == 0 for s in spec.slots)
    assert set(spec.buckets) == {"float32", "bfloat16"}
    bufs = spec.flatten(tree)
    for k, b in bufs.items():
        assert b.shape == (4, spec.totals[k])
        assert spec.totals[k] % LANE == 0
    # three f32 leaves of sizes 128, 7, 1 -> aligned offsets 0/128/256
    f32 = sorted(s.offset for s in spec.slots if s.bucket == "float32")
    assert f32 == [0, 128, 256]


def test_flatten_foreign_dtype_tree_into_param_layout():
    """A float32 gradient tree flattens into a bfloat16 parameter spec's
    layout bucket-for-bucket (what the fused update relies on)."""
    theta = jax.tree.map(lambda x: x.astype(jnp.bfloat16), mixed_tree())
    grads = jax.tree.map(lambda x: jnp.ones_like(x, jnp.float32), theta)
    spec = FlatSpec.build(theta, leading=1)
    gb = spec.flatten(grads)
    assert set(gb) == {"bfloat16"} and gb["bfloat16"].dtype == jnp.float32
    back = spec.unflatten(gb, like=grads)
    for k in grads:
        assert back[k].dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(grads[k]), np.asarray(back[k]))


def test_build_from_shape_structs_matches_concrete():
    tree = mixed_tree()
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    a, b = FlatSpec.build(tree, leading=1), FlatSpec.build(shapes, leading=1)
    assert a.slots == b.slots and a.totals == b.totals


def test_leaves_must_share_leading_dims():
    with pytest.raises(AssertionError):
        FlatSpec.build({"a": jnp.ones((4, 3)), "b": jnp.ones((5, 3))}, leading=1)


def test_with_lead_rebinds_leading_dims_only():
    tree = mixed_tree()
    spec = FlatSpec.build(tree, leading=1)
    row = spec.with_lead(())
    assert row.slots == spec.slots and row.totals == spec.totals
    assert row.leading == 0 and row.lead_shape == ()
    one = row.unflatten({k: b[2] for k, b in spec.flatten(tree).items()})
    for k in tree:
        np.testing.assert_array_equal(np.asarray(tree[k][2]), np.asarray(one[k]))
    # specs are hashable + comparable (FlatState carries them as pytree aux)
    assert hash(spec) == hash(FlatSpec.build(tree, leading=1))
    assert spec == FlatSpec.build(tree, leading=1) and spec != row


def test_views_match_unflatten_and_grads_land_flat():
    """FlatSpec.views == unflatten in value, and its scatter VJP returns the
    cotangent already on the plane — identical to flatten(tree grads), with
    zero pad/concatenate per leaf."""
    tree = {k: v for k, v in mixed_tree().items() if k != "h"}  # f32 bucket
    spec = FlatSpec.build(tree, leading=1)
    bufs = spec.flatten(tree)
    out = spec.views(bufs)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(tree[k]), np.asarray(out[k]))

    def f_bufs(b):
        return sum(jnp.sum(jnp.sin(l)) for l in jax.tree.leaves(spec.views(b)))

    def f_tree(t):
        return sum(jnp.sum(jnp.sin(l)) for l in jax.tree.leaves(t))

    g_bufs = jax.grad(f_bufs)(bufs)
    g_ref = spec.flatten(jax.grad(f_tree)(tree))
    for k in g_bufs:
        np.testing.assert_allclose(np.asarray(g_bufs[k]), np.asarray(g_ref[k]),
                                   rtol=1e-6, atol=0)
    jaxpr = jax.make_jaxpr(jax.grad(f_bufs))(bufs)

    def count(jx, name):
        n = sum(1 for e in jx.eqns if e.primitive.name == name)
        for e in jx.eqns:
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "jaxpr"):
                        n += count(sub.jaxpr, name)
        return n

    assert count(jaxpr.jaxpr, "concatenate") == 0
    assert count(jaxpr.jaxpr, "pad") == 0


def test_degenerate_leaves_zero_size_and_scalar_roundtrip():
    """Zero-size and scalar leaves must round-trip: a zero-size leaf occupies
    a zero-width slot (offset unchanged — two leaves may share an offset) and
    a scalar leaf occupies one lane-padded slot. Guards the codec kernels'
    block-index math against degenerate offsets."""
    W = 4
    tree = {"empty": jnp.zeros((W, 0), jnp.float32),           # zero-size
            "scalar": jnp.arange(W, dtype=jnp.float32),        # per-item ()
            "mat": jnp.arange(W * 6, dtype=jnp.float32).reshape(W, 2, 3),
            "empty2": jnp.zeros((W, 3, 0), jnp.float32)}
    spec = FlatSpec.build(tree, leading=1)
    slot = {jax.tree_util.tree_flatten_with_path(tree)[0][i][0][0].key: s
            for i, s in enumerate(spec.slots)}
    assert slot["empty"].size == 0 and slot["empty2"].size == 0
    assert slot["scalar"].size == 1 and slot["scalar"].shape == ()
    # zero-size slots consume no plane: offsets stay lane-aligned and the
    # total is exactly the two real slots
    assert all(s.offset % LANE == 0 for s in spec.slots)
    assert spec.num_elements() == 2 * LANE
    bufs = spec.flatten(tree)
    assert bufs["float32"].shape == (W, 2 * LANE)
    back = spec.unflatten(bufs)
    for k in tree:
        assert back[k].shape == tree[k].shape and back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(tree[k]), np.asarray(back[k]))


def test_degenerate_leaves_survive_codec_roundtrip():
    """The codec kernels tile the [W, total] plane into blocks: degenerate
    slots (zero-size, scalar) must not corrupt neighbors through a
    quantize/sparsify round-trip."""
    from repro.comm import codec_seeds, resolve_codec
    from repro.common.config import ProtocolConfig
    W = 2
    tree = {"empty": jnp.zeros((W, 0), jnp.float32),
            "scalar": 100.0 + jnp.arange(W, dtype=jnp.float32),
            "mat": jax.random.normal(jax.random.PRNGKey(0), (W, 40))}
    spec = FlatSpec.build(tree, leading=1)
    bufs = spec.flatten(tree)
    seeds = codec_seeds(0, jnp.arange(W))
    for name in ("q8", "topk"):
        codec = resolve_codec(ProtocolConfig(codec=name, codec_block=128,
                                             codec_topk_frac=0.5))
        hat = {}
        for k, b in bufs.items():
            res = jnp.zeros(b.shape, jnp.float32) if codec.stateful else None
            hat[k], _ = codec.roundtrip(b, seeds, residual=res)
        back = spec.unflatten(hat)
        assert back["empty"].shape == (W, 0)
        # the large scalar dominates its block's scale/selection; it must
        # reconstruct to within one quantization step
        np.testing.assert_allclose(np.asarray(back["scalar"]),
                                   np.asarray(tree["scalar"]), rtol=0.02,
                                   err_msg=name)


# ---------------------------------------------------------------------------
# flat fused kernels (interpret mode) vs ref oracles
# ---------------------------------------------------------------------------

def flat_inputs(W=3, N=1000, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (W, N)) for k in ks)


@pytest.mark.parametrize("coef", [0.0, 0.5, [0.0, 0.37, 1.0]])
def test_flat_kernel_matches_ref(coef):
    t, p, v, g = flat_inputs()
    c = jnp.asarray(coef)
    tk, vk = fu.fused_flat_elastic_nag_update(t, p, v, g, c, 0.01, 0.9,
                                              block=256, interpret=True)
    tr_, vr_ = ref.fused_flat_elastic_nag_update(t, p, v, g, c, 0.01, 0.9)
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tr_), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vr_), rtol=1e-6, atol=1e-6)


def test_flat_nag_kernel_matches_ref():
    t, _, v, g = flat_inputs(seed=5)
    tk, vk = fu.fused_flat_nag_update(t, v, g, 0.05, 0.99, block=512, interpret=True)
    tr_, vr_ = ref.fused_flat_nag_update(t, v, g, 0.05, 0.99)
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tr_), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vr_), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("W,N,block", [
    (1, 3 * 8192, 8192),      # one replica: (1, cols) tiles of the [1, N] row
    (16, 2048, 512),          # W a multiple of 8: 8-row tiles, 2 row groups
    (3, 925 * 128, fu.BLOCK),  # divisor tiling (185-lane tiles), all rows
])
def test_flat_kernels_every_tile_layout(W, N, block):
    """Each tile layout of kernels.fused_update.plane_call matches the
    oracles (to the fused tests' f32 tolerance) and keeps the in-place
    aliasing."""
    from repro.kernels import robust
    t, p, v, g = flat_inputs(W=W, N=N, seed=W)
    close = lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)
    coef = jnp.linspace(0.0, 1.0, W)
    tk, vk = fu.fused_flat_elastic_nag_update(t, p, v, g, coef, 0.01, 0.9,
                                              block=block, interpret=True)
    tr_, vr_ = ref.fused_flat_elastic_nag_update(t, p, v, g, coef, 0.01, 0.9)
    close(tk, tr_)
    close(vk, vr_)
    tk, vk = fu.fused_flat_nag_update(t, v, g, 0.05, 0.99, block=block,
                                      interpret=True)
    tr_, vr_ = ref.fused_flat_nag_update(t, v, g, 0.05, 0.99)
    close(tk, tr_)
    close(vk, vr_)
    thr = jnp.full((W,), 1.5)
    ok = robust.robust_flat_apply(t, p, coef, thr, block=block, interpret=True)
    close(ok, ref.robust_flat_apply(t, p, coef, thr))
    jx = jax.make_jaxpr(lambda a, b, c: fu.fused_flat_nag_update(
        a, b, c, 0.01, 0.9, block=block, interpret=True))(t, v, g)
    (eq,) = _pallas_eqns(jx.jaxpr)
    assert dict(eq.params["input_output_aliases"]) == {0: 0, 1: 1}


def _pallas_eqns(jaxpr):
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr"):
                    out += _pallas_eqns(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    out += _pallas_eqns(sub)
    return out


def test_flat_kernel_traced_eta_single_compile():
    """eta/mu ride in the scalar operand: a traced learning rate must work
    (lr schedules don't retrigger compilation)."""
    t, p, v, g = flat_inputs(W=2, N=300)

    @jax.jit
    def f(eta):
        return fu.fused_flat_elastic_nag_update(t, p, v, g, jnp.ones((2,)),
                                                eta, 0.9, block=128, interpret=True)
    for eta in (0.1, 0.01):
        tk, _ = f(jnp.float32(eta))
        tr_, _ = ref.fused_flat_elastic_nag_update(t, p, v, g, 1.0, eta, 0.9)
        np.testing.assert_allclose(np.asarray(tk), np.asarray(tr_), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# tree-level entry points (ops) — the per-leaf oracle is the target
# ---------------------------------------------------------------------------

def per_leaf_oracle(theta, peer, v, g, coef, eta, mu):
    W = jax.tree.leaves(theta)[0].shape[0]
    c = jnp.broadcast_to(jnp.asarray(coef, jnp.float32).reshape(-1), (W,))

    def one(t, p, vv, gg):
        cc = c.reshape((W,) + (1,) * (t.ndim - 1))
        tf, pf = t.astype(jnp.float32), p.astype(jnp.float32)
        vf, gf = vv.astype(jnp.float32), gg.astype(jnp.float32)
        vn = mu * vf - eta * gf
        tn = tf - cc * (tf - pf) - eta * gf + mu * vn
        return tn.astype(t.dtype), vn.astype(vv.dtype)

    pairs = jax.tree.map(one, theta, peer, v, g)
    t_new = jax.tree.map(lambda x: x[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    v_new = jax.tree.map(lambda x: x[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return t_new, v_new


@pytest.mark.parametrize("use_kernel", [False, True])
def test_tree_elastic_nag_matches_per_leaf(use_kernel):
    theta = mixed_tree()
    peer = jax.tree.map(lambda x: x + 0.1, theta)
    v = jax.tree.map(lambda x: jnp.zeros_like(x) + 0.01, theta)
    g = jax.tree.map(lambda x: jnp.ones_like(x, jnp.float32), theta)
    coef = jnp.asarray([0.0, 0.25, 0.5, 1.0])
    t2, v2 = ops.fused_tree_elastic_nag(theta, peer, v, g, coef, eta=0.01, mu=0.9,
                                        use_kernel=use_kernel, interpret=True)
    tr_, vr_ = per_leaf_oracle(theta, peer, v, g, coef, 0.01, 0.9)
    for k in theta:
        assert t2[k].dtype == theta[k].dtype and t2[k].shape == theta[k].shape
        tol = 1e-6 if theta[k].dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(t2[k], np.float32),
                                   np.asarray(tr_[k], np.float32), rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(v2[k], np.float32),
                                   np.asarray(vr_[k], np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_tree_nag_matches_per_leaf(use_kernel):
    theta = mixed_tree()
    v = jax.tree.map(lambda x: jnp.zeros_like(x) + 0.5, theta)
    g = jax.tree.map(lambda x: jnp.ones_like(x, jnp.float32), theta)
    t2, v2 = ops.fused_tree_nag(theta, v, g, eta=0.05, mu=0.9,
                                use_kernel=use_kernel, interpret=True)
    # coef=0 elastic == pure NAG (the peer stream must not matter)
    tr_, vr_ = per_leaf_oracle(theta, theta, v, g, 0.0, 0.05, 0.9)
    for k in theta:
        tol = 1e-6 if theta[k].dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(t2[k], np.float32),
                                   np.asarray(tr_[k], np.float32), rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(v2[k], np.float32),
                                   np.asarray(vr_[k], np.float32), rtol=tol, atol=tol)
