"""Unit + property tests for the paper's protocol math (Algorithms 1-6)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.common.config import ProtocolConfig
from repro.core import consensus, protocols, topology

KEY = jax.random.PRNGKey(0)


def stacked_params(key, W, scale=1.0):
    k1, k2 = jax.random.split(key)
    return {"w": scale * jax.random.normal(k1, (W, 6, 5)),
            "b": scale * jax.random.normal(k2, (W, 7))}


# ---------------------------------------------------------------------------
# topology / mixing matrices
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("W", [2, 3, 4, 8, 16])
def test_uniform_peers_never_self(W):
    for s in range(5):
        peers = topology.sample_uniform_peers(jax.random.PRNGKey(s), W)
        assert not bool((peers == jnp.arange(W)).any())
        assert peers.min() >= 0 and peers.max() < W


@pytest.mark.parametrize("W", [2, 4, 8, 7])
def test_matching_is_involution(W):
    for s in range(5):
        m = np.asarray(topology.sample_matching(jax.random.PRNGKey(s), W))
        assert (m[m] == np.arange(W)).all()          # partner of partner = self
        if W % 2 == 0:
            assert (m != np.arange(W)).all()          # no self-pairs at even W


@pytest.mark.parametrize("W", [3, 4, 8])
def test_elastic_mix_rows_sum_to_one_and_symmetric(W):
    peers = topology.sample_uniform_peers(KEY, W)
    active = jnp.array([True] * (W - 1) + [False])
    mix = topology.elastic_gossip_mix(peers, active, 0.37)
    assert np.allclose(np.asarray(mix).sum(1), 1.0, atol=1e-6)
    assert np.allclose(np.asarray(mix), np.asarray(mix).T, atol=1e-6)


def test_pull_mix_row_stochastic_not_symmetric():
    W = 8
    peers = topology.sample_uniform_peers(KEY, W)
    active = jnp.ones(W, bool)
    mix = np.asarray(topology.gossip_pull_mix(peers, active))
    assert np.allclose(mix.sum(1), 1.0, atol=1e-6)
    assert not np.allclose(mix, mix.T)


def test_push_mix_row_stochastic():
    W = 8
    peers = topology.sample_uniform_peers(KEY, W)
    active = jnp.ones(W, bool)
    mix = np.asarray(topology.gossip_push_mix(peers, active))
    assert np.allclose(mix.sum(1), 1.0, atol=1e-6)


def test_inactive_workers_still_respond_to_selection():
    """Alg. 4: K_i includes workers that selected i even if i itself did not
    draw communication — passive peers respond."""
    W = 4
    peers = jnp.array([1, 0, 0, 0])
    active = jnp.array([True, False, False, False])  # only worker 0 gossips
    mix = np.asarray(topology.elastic_gossip_mix(peers, active, 0.5))
    # workers 0 and 1 average; 2, 3 untouched
    assert np.allclose(mix[0], [0.5, 0.5, 0, 0])
    assert np.allclose(mix[1], [0.5, 0.5, 0, 0])
    assert np.allclose(mix[2], [0, 0, 1, 0])
    assert np.allclose(mix[3], [0, 0, 0, 1])


def test_fan_in_set_semantics():
    """Two workers selecting the same target: target moves toward both."""
    W = 3
    peers = jnp.array([2, 2, 0])
    active = jnp.array([True, True, False])
    mix = np.asarray(topology.elastic_gossip_mix(peers, active, 0.25))
    # A = edges (0,2), (1,2); L row 2 has degree 2
    assert np.allclose(mix[2], [0.25, 0.25, 0.5])


# ---------------------------------------------------------------------------
# conservation (elastic symmetry) — the paper's key structural property
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), W=st.sampled_from([2, 4, 8]),
       alpha=st.floats(0.05, 0.95), p=st.floats(0.1, 1.0),
       matching=st.booleans())
def test_elastic_gossip_conserves_global_sum(seed, W, alpha, p, matching):
    key = jax.random.PRNGKey(seed)
    theta = stacked_params(key, W)
    cfg = ProtocolConfig(method="elastic_gossip", moving_rate=alpha,
                         comm_probability=p,
                         topology="matching" if matching else "uniform")
    state = protocols.init_state(cfg, theta)
    k1, k2 = jax.random.split(key)
    active = protocols.comm_gate(cfg, k2, jnp.zeros((), jnp.int32), W)
    new, _ = protocols.comm_update(cfg, k1, active, theta, state)
    assert np.allclose(float(consensus.total_sum(new)),
                       float(consensus.total_sum(theta)), rtol=1e-5, atol=1e-4)


def test_gossip_pull_does_not_conserve_sum_in_general():
    """Gossiping SGD pull lacks elastic symmetry — the contrast the paper
    draws: a one-sided pull changes the global parameter sum."""
    peers = jnp.array([1, 0, 0])
    active = jnp.array([True, False, False])     # only worker 0 pulls
    mix = np.asarray(topology.gossip_pull_mix(peers, active))
    assert not np.allclose(mix, mix.T)
    theta = {"w": jnp.array([[2.0], [10.0], [0.0]])}
    out = topology.apply_mix(jnp.asarray(mix), theta)["w"]
    assert not np.isclose(float(out.sum()), float(theta["w"].sum()))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), alpha=st.floats(0.05, 0.95))
def test_easgd_conserves_center_plus_workers(seed, alpha):
    """EASGD elastic symmetry: sum(theta_i) + center is conserved when the
    center absorbs the symmetric counter-updates (Alg. 2 lines 5-7)."""
    W = 4
    key = jax.random.PRNGKey(seed)
    theta = stacked_params(key, W)
    cfg = ProtocolConfig(method="easgd", moving_rate=alpha, comm_period=1)
    state = protocols.init_state(cfg, theta)
    total0 = float(consensus.total_sum(theta)) + float(consensus.total_sum(
        jax.tree.map(lambda x: x[None], state.center)))
    new, st2 = protocols.comm_update(cfg, key, jnp.ones(W, bool), theta, state)
    total1 = float(consensus.total_sum(new)) + float(consensus.total_sum(
        jax.tree.map(lambda x: x[None], st2.center)))
    assert np.isclose(total0, total1, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# moving-rate semantics (paper Eq. 3.9)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alpha,expect", [(0.0, "same"), (0.5, "average"), (1.0, "swap")])
def test_moving_rate_extremes(alpha, expect):
    W = 2
    theta = {"w": jnp.array([[1.0, 2.0], [5.0, 10.0]])}
    peers = jnp.array([1, 0])
    mix = topology.elastic_gossip_mix(peers, jnp.ones(W, bool), alpha)
    out = topology.apply_mix(mix, theta)["w"]
    if expect == "same":
        assert np.allclose(out, theta["w"])
    elif expect == "average":
        assert np.allclose(out, jnp.array([[3.0, 6.0], [3.0, 6.0]]))
    else:
        assert np.allclose(out, theta["w"][::-1])


def test_comm_gate_period_vs_probability():
    cfg_tau = ProtocolConfig(method="elastic_gossip", comm_period=4)
    for step, expect in [(0, True), (1, False), (4, True)]:
        g = protocols.comm_gate(cfg_tau, KEY, jnp.int32(step), 4)
        assert bool(g.all()) == expect and bool(g.any()) == expect
    cfg_p = ProtocolConfig(method="elastic_gossip", comm_probability=0.5)
    draws = np.stack([np.asarray(protocols.comm_gate(cfg_p, jax.random.PRNGKey(s),
                                                     jnp.int32(0), 64)) for s in range(40)])
    rate = draws.mean()
    assert 0.4 < rate < 0.6          # Bernoulli(0.5) per worker


def test_allreduce_gradient_transform_averages():
    g = {"w": jnp.arange(8.0).reshape(4, 2)}
    cfg = ProtocolConfig(method="allreduce")
    out = protocols.gradient_transform(cfg, g)["w"]
    assert np.allclose(out, np.tile(np.asarray(g["w"]).mean(0), (4, 1)))


# ---------------------------------------------------------------------------
# communication-cost accounting — the paper's headline claim quantified
# ---------------------------------------------------------------------------

def test_comm_cost_gossip_much_cheaper_than_allreduce():
    P = 4 * 1.1e9   # tinyllama f32 bytes
    ar = protocols.comm_cost(ProtocolConfig(method="allreduce"), P, 8)
    eg = protocols.comm_cost(
        ProtocolConfig(method="elastic_gossip", comm_probability=1 / 32), P, 8)
    assert ar.bytes_per_step > 50 * eg.bytes_per_step
    nc = protocols.comm_cost(ProtocolConfig(method="none"), P, 8)
    assert nc.bytes_per_step == 0.0


def test_alpha_schedule_annealing():
    """Beyond-paper alpha schedule (thesis §4.1.3)."""
    cfg = ProtocolConfig(method="elastic_gossip", comm_probability=0.5,
                         moving_rate=0.9, moving_rate_final=0.1, alpha_decay_steps=100)
    assert float(protocols.alpha_at(cfg, 0)) == pytest.approx(0.9)
    assert float(protocols.alpha_at(cfg, 50)) == pytest.approx(0.5)
    assert float(protocols.alpha_at(cfg, 100)) == pytest.approx(0.1)
    assert float(protocols.alpha_at(cfg, 1000)) == pytest.approx(0.1)
    const = ProtocolConfig(method="elastic_gossip", comm_probability=0.5, moving_rate=0.5)
    assert float(protocols.alpha_at(const, 12345)) == pytest.approx(0.5)
