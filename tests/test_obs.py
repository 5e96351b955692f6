"""repro.obs: unified telemetry plane (ISSUE 10).

Contract anchors:
- ``ObsConfig()`` (all defaults) is INERT — no observer is built and every
  engine reproduces the obs=None trajectory bit-exactly (params, velocity,
  comm accounting, PRNG key);
- a RECORDING run is also bit-exact: observation is host-side only, events
  are re-derived from values the engines already materialize, never from
  extra device ops;
- every engine's facade step returns the unified metrics schema —
  ``CORE_STEP_KEYS`` everywhere, plus the documented per-engine extensions;
- the exported Perfetto trace validates against the event schema, and
  ``repro.obs.report`` totals (read from the metrics JSONL) equal the
  engine's own ``ProtocolState`` accumulators EXACTLY (never re-derived).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

from repro.api import GossipTrainer
from repro.common.config import (FaultConfig, FleetConfig, HeteroConfig,
                                 ObsConfig, OptimizerConfig, ProtocolConfig)
from repro.models import simple
from repro.obs import MetricsSink, TraceRecorder, report, schema

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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


def _trainer(engine="sim", obs=None, p=0.5, **kw):
    if engine == "async":
        kw.setdefault("hetero", HeteroConfig(time_model="constant",
                                             mean_step_time=1.0))
    proto = ProtocolConfig(method="elastic_gossip", comm_probability=p,
                           moving_rate=0.5, topology="uniform")
    return GossipTrainer(
        engine=engine, protocol=proto, obs=obs,
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


_RECORDING = ObsConfig(trace=True, metrics=True)


# ---------------------------------------------------------------------------
# inert anchor: ObsConfig() adds nothing, recording changes nothing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["sim", "async"])
def test_default_obsconfig_is_inert(engine):
    """All-default ObsConfig builds NO observer and the trajectory is
    bit-exact vs obs=None (params, velocity, comm accounting, PRNG key)."""
    plain = _trainer(engine)
    anchored = _trainer(engine, obs=ObsConfig())
    assert not ObsConfig().enabled()
    assert anchored.observer is None
    assert getattr(anchored._backend.sim, "obs", None) is None
    s0, _ = _run(plain)
    s1, _ = _run(anchored)
    _assert_states_equal(s0, s1)


@pytest.mark.parametrize("engine", ["sim", "async"])
def test_recording_run_is_bit_exact(engine):
    """Observation is host-side only: a run with trace + metrics armed
    reproduces the non-recording trajectory bit-for-bit."""
    s0, _ = _run(_trainer(engine))
    rec = _trainer(engine, obs=_RECORDING)
    assert rec.observer is not None and rec.observer.trace is not None
    s1, _ = _run(rec)
    _assert_states_equal(s0, s1)
    rec.observer.flush()   # drain the one-step-deferred harvest
    evs = rec.observer.trace.events
    assert any(e["ev"] == "compute" for e in evs)
    assert any(e["ev"] == "exchange" for e in evs)  # p=0.5: rounds fired
    for e in evs:
        assert schema.validate_event(e) == [], e


# ---------------------------------------------------------------------------
# unified metrics schema: engine key-set parity
# ---------------------------------------------------------------------------

def test_metrics_keyset_parity_sim_vs_async():
    """Equivalent configs return the documented key sets: CORE on sim,
    CORE + the async window extension on async — nothing more, nothing
    undocumented."""
    _, m_sim = _run(_trainer("sim"))
    _, m_async = _run(_trainer("async"))
    assert set(m_sim) == schema.CORE_STEP_KEYS
    assert set(m_async) == schema.CORE_STEP_KEYS | schema.ASYNC_STEP_KEYS


def test_metrics_keyset_async_message_mode():
    """Message mode (delay models) adds exactly the pending-wire keys."""
    faults = FaultConfig(delay_model="constant", delay=1.5)
    _, m = _run(_trainer("async", faults=faults), steps=6)
    assert set(m) == (schema.CORE_STEP_KEYS | schema.ASYNC_STEP_KEYS
                      | schema.ASYNC_MESSAGE_KEYS)


def test_normalize_step_metrics_is_additive():
    """Normalization fills missing CORE keys and never removes engine keys."""
    m = schema.normalize_step_metrics({"loss": 1.5, "my_extra": 7}, step=3)
    assert schema.CORE_STEP_KEYS <= set(m)
    assert m["my_extra"] == 7 and m["step"] == 3
    assert m["loss_mean"] == m["loss_max"] == 1.5
    assert m["fired"] is False and m["comm_active"] == 0
    # engine-provided values win over defaults
    m2 = schema.normalize_step_metrics({"loss_mean": 2.0, "comm_active": 3},
                                       step=0)
    assert m2["loss"] == 2.0 and m2["fired"] is True


# ---------------------------------------------------------------------------
# acceptance: W=8 async + faults + flow control -> valid trace, exact totals
# ---------------------------------------------------------------------------

def test_async_w8_faults_flow_trace_and_exact_totals(tmp_path):
    """The issue's acceptance scenario: a W=8 async run with drop faults and
    token-account flow control exports (a) a schema-valid Perfetto trace with
    per-worker tracks, exchange arrows and fault/skip markers, and (b) a
    metrics JSONL from which the report tool reproduces comm_bytes and
    staleness totals EXACTLY matching the engine's ProtocolState."""
    trace_path = str(tmp_path / "run.json")
    metrics_path = str(tmp_path / "run.jsonl")
    obs = ObsConfig(trace_path=trace_path, metrics_path=metrics_path)
    faults = FaultConfig(fault_model="drop", fault_rate=0.3, seed=3)
    fleet = FleetConfig(flow_control="token_account", token_capacity=3.0,
                        token_rate=0.5, seed=0)
    t = _trainer("async", obs=obs, faults=faults, fleet=fleet)
    state, m = _run(t, steps=20)
    # recording must not have changed the trajectory
    s0, _ = _run(_trainer("async", faults=faults, fleet=fleet), steps=20)
    _assert_states_equal(s0, state)
    out = t.export_obs()
    assert out == {"trace": trace_path, "metrics": metrics_path}

    with open(trace_path) as f:
        doc = json.load(f)
    assert schema.validate_trace(doc) == []
    kinds = {e["ev"] for e in doc["reproEvents"]}
    assert {"compute", "exchange", "drop", "flow_skip"} <= kinds
    # one named track per worker (tid w+1) plus the trainer track (tid 0)
    tids = {e["tid"] for e in doc["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "thread_name"}
    assert {w + 1 for w in range(W)} <= tids
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "i", "s", "f"} <= phases   # spans, markers, exchange arrows

    rows = report.load_jsonl(metrics_path)
    assert len(rows) == 20
    tot = report.totals(rows)
    proto = state.proto
    assert tot["comm_bytes"] == float(proto.comm_bytes)
    assert tot["comm_units"] == float(proto.comm_units)
    assert tot["stale_time"] == float(proto.stale_time)
    assert tot["wire_dropped"] == float(proto.wire_dropped)
    assert tot["flow_skipped"] == float(proto.flow_skipped)
    np.testing.assert_array_equal(np.asarray(tot["tokens"]),
                                  np.asarray(proto.tokens))
    # the sink's counter registry carries the same totals (sum of deltas)
    sink = t.observer.sink
    assert sink.counters["comm_bytes"] == float(proto.comm_bytes)
    # frontier is monotone in step and ends at the final budget
    fr = report.frontier(rows)
    assert [p["step"] for p in fr] == sorted(p["step"] for p in fr)
    assert fr[-1]["comm_bytes"] == float(proto.comm_bytes)
    # and the report CLI agrees end to end (schema VALID, exit 0)
    assert report.main([metrics_path, "--trace", trace_path]) == 0


def test_sample_every_thins_rows_and_events():
    """sample_every=3 records rows/events only on steps 0, 3, 6, ..."""
    obs = ObsConfig(trace=True, metrics=True, sample_every=3)
    t = _trainer("sim", obs=obs)
    _run(t, steps=9)
    t.observer.flush()
    rows = t.observer.sink.records
    assert [r["step"] for r in rows] == [0, 3, 6]
    assert {e["step"] for e in t.observer.trace.events} <= {0, 3, 6}


# ---------------------------------------------------------------------------
# components: sink round-trip, bounded recorder, schema validation
# ---------------------------------------------------------------------------

def test_metrics_sink_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    sink = MetricsSink(path)
    sink.counter_add("c", 2.0)
    sink.counter_add("c", 3.0)
    sink.gauge_set("g", 7)
    sink.observe("h", 1.0)
    sink.observe("h", 3.0)
    sink.record({"step": 0, "loss": float(np.float32(1.25)),
                 "n": jnp.int32(4)})
    sink.record({"step": 1, "loss": 1.0})
    sink.close()
    rows = report.load_jsonl(path)
    assert [r["step"] for r in rows] == [0, 1]
    assert rows[0]["loss"] == 1.25 and rows[0]["n"] == 4   # jsonable scalars
    assert sink.counters["c"] == 5.0
    s = sink.summary()
    assert s["g"] == 7
    assert s["h_count"] == 2 and s["h_max"] == 3.0
    # samples() is a LIVE view — mutations hit the sink (the serve plane
    # relies on this for its thin compatibility properties)
    sink.samples("h").clear()
    assert sink.summary()["h_count"] == 0


def test_trace_recorder_bounded():
    rec = TraceRecorder(max_events=5)
    for i in range(9):
        rec.emit("exchange", float(i), i, worker=0, peer=1)
    assert len(rec.events) == 5
    assert rec.dropped == 4
    doc = rec.perfetto(num_workers=2)
    assert schema.validate_trace(doc) == []


def test_schema_validation_catches_errors():
    assert schema.validate_event({"ev": "nope", "t": 0.0, "step": 0})
    assert schema.validate_event({"ev": "exchange", "t": 0.0, "step": 0,
                                  "worker": 1})  # missing peer
    assert schema.validate_event(
        {"ev": "exchange", "t": 0.0, "step": 0, "worker": 1, "peer": 2}) == []
    bad = {"traceEvents": [{"ph": "X", "ts": 0, "tid": 9, "name": "x"}],
           "reproEvents": []}
    errs = schema.validate_trace(bad)
    assert any("without dur" in e for e in errs)
    assert any("thread_name" in e for e in errs)


# ---------------------------------------------------------------------------
# serve plane rides the sink (satellite: no more private lists)
# ---------------------------------------------------------------------------

def test_serve_telemetry_rides_metrics_sink():
    """LiveServer/TrainServeLoop keep their old read surfaces
    (swap_pauses/rejected_swaps/staleness/swap_stats) as thin LIVE views
    over one shared MetricsSink."""
    from repro.serve import LiveServer, TrainServeLoop

    class _Bus:
        def latest(self):
            return None

    sink = MetricsSink()
    server = LiveServer(program=None, bus=_Bus(), metrics=sink)
    assert server.metrics is sink
    assert server.maybe_swap() is False          # empty bus: no-op
    sink.observe("swap_pause_s", 0.25)
    sink.counter_add("swaps", 1)
    sink.counter_add("rejected_swaps", 2)
    assert server.swap_pauses == [0.25]          # live view over the sink
    assert server.rejected_swaps == 2
    st = server.swap_stats()
    assert st["swaps"] == 1 and st["swap_pause_max_s"] == 0.25
    assert st["rejected_swaps"] == 2

    class _Batcher:
        pos, max_len, boundaries_run = 0, 100, 0

        def step(self, t):
            self.boundaries_run += 1

    loop = TrainServeLoop(server, _Batcher(), train_fn=lambda t: 10)
    assert loop.metrics is sink                  # ONE sink for both halves
    server.train_step = 7
    loop.run(3)
    assert loop.staleness == [3, 3, 3]           # 10 - 7, via the sink
    assert len(loop.boundary_times) == 3
    summ = loop.summary()
    assert summ["boundaries"] == 3
    assert summ["staleness_max_steps"] == 3
    assert summ["swaps"] == 1                    # merged server stats


# ---------------------------------------------------------------------------
# dist engine (multi-device subprocess)
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


@pytest.mark.slow
def test_dist_recording_bit_exact_and_core_keyset():
    """The dist engine under a recording ObsConfig: bit-exact trajectory,
    exactly the CORE key set, schedule-derived exchange events with static
    per-device wire bytes, and report totals equal to the host comm account."""
    out = run_sub("""
        import json, numpy as np, jax, jax.numpy as jnp
        from repro.api import GossipTrainer
        from repro.common.config import (MeshConfig, ObsConfig,
                                         OptimizerConfig, ProtocolConfig)
        from repro.launch.mesh import make_worker_mesh
        from repro.obs import report, schema

        mcfg = MeshConfig(data=4, model=1, pods=2, workers_per_pod=4)
        mesh = make_worker_mesh(mcfg)
        W = mcfg.num_workers

        def init_fn(key):
            k1, k2 = jax.random.split(key)
            return {"w1": jax.random.normal(k1, (37, 19)),
                    "b": jnp.zeros((19,)),
                    "w2": jax.random.normal(k2, (19, 3))}

        def dist_loss(p, batch):
            h = jnp.tanh(batch["x"] @ p["w1"] + p["b"])
            return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

        proto = ProtocolConfig(method="elastic_gossip", comm_probability=0.5,
                               moving_rate=0.5)
        opt = OptimizerConfig(name="nag", learning_rate=0.05, momentum=0.9)

        def make(obs):
            t = GossipTrainer(engine="dist", protocol=proto, optimizer=opt,
                              mesh=mesh, mesh_cfg=mcfg, init_fn=init_fn,
                              params_axes={"w1": (None, None), "b": (None,),
                                           "w2": (None, None)},
                              loss_fn=dist_loss, global_batch=8, seq_len=4,
                              obs=obs)
            t._backend.trainer.batch_specs = lambda: {"x": None, "y": None}
            return t

        def run(t, steps=10):
            st = t.init_state(0)
            rng = np.random.RandomState(1)
            for _ in range(steps):
                x = jnp.asarray(rng.normal(size=(W, 8, 37)).astype(np.float32))
                y = jnp.zeros((W, 8, 3))
                st, m = t.step(st, {"x": x, "y": y})
            return st, m

        s0, m0 = run(make(None))
        rec = make(ObsConfig(trace=True, metrics=True))
        s1, m1 = run(rec)
        for k in s0.theta:
            np.testing.assert_array_equal(np.asarray(s0.theta[k]),
                                          np.asarray(s1.theta[k]))
        assert float(m0["comm_bytes"]) == float(m1["comm_bytes"])
        assert set(m1) == schema.CORE_STEP_KEYS, sorted(m1)
        assert isinstance(m1["comm_round"], int)   # schedule round index

        rec.observer.flush()
        evs = rec.observer.trace.events
        ex = [e for e in evs if e["ev"] == "exchange"]
        assert ex and all(e["wire_bytes"] ==
                          rec._backend.wire_bytes() for e in ex)
        assert all(e["peer"] != e["worker"] for e in ex)
        doc = rec.observer.trace.perfetto(W)
        assert schema.validate_trace(doc) == []
        # report totals == the backend's host f64 comm account, exactly
        rows = rec.observer.sink.records
        assert report.totals(rows)["comm_bytes"] == float(m1["comm_bytes"])
        print("DIST-OBS-OK")
    """)
    assert "DIST-OBS-OK" in out


# ---------------------------------------------------------------------------
# launch CLI: --trace/--metrics end to end
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_launch_cli_trace_metrics_end_to_end(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    tr_path, m_path = str(tmp_path / "r.json"), str(tmp_path / "r.jsonl")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "xlstm_125m",
         "--reduced", "--steps", "8", "--engine", "async", "--workers", "4",
         "--p", "0.5", "--global-batch", "8", "--seq", "32",
         "--fault-model", "drop", "--fault-rate", "0.3",
         "--trace", tr_path, "--metrics", m_path],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "wrote trace" in r.stdout and "wrote metrics" in r.stdout
    with open(tr_path) as f:
        assert schema.validate_trace(json.load(f)) == []
    rows = report.load_jsonl(m_path)
    assert len(rows) == 8
    assert report.totals(rows)["comm_bytes"] > 0
    # the report CLI runs clean over the artifacts
    rep = subprocess.run(
        [sys.executable, "-m", "repro.obs.report", m_path, "--trace", tr_path],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "schema: VALID" in rep.stdout
