"""The train step names its layers inside the program, the same program
traced or not, and the facade's host span sits on the profiler's clock.

- every ``jax.named_scope`` of the step reaches the compiled HLO's ``op_name``
  metadata, forward and backward, on the sim and the dist engine;
- an active ``jax.profiler`` trace leaves the lowered program unchanged;
- each ``GossipTrainer.step`` is one ``train_step`` span on the host plane
  of a profiler trace, inside ``time.time_ns()`` readings taken around the
  call, and ``Observer.now()`` reads that clock too.
"""
from __future__ import annotations

import glob
import os
import re
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from repro.api import GossipTrainer
from repro.common.config import ModelConfig, OptimizerConfig, ProtocolConfig
from repro.models import transformer as tr
from repro.obs import Observer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=96, vocab_size=256,
                  activation="swiglu", tie_embeddings=True)
MODEL = ("embed", "layer_scan", "attention", "ffn", "head_loss", "flat_views")
UNWRAP = re.compile(r"^(?:[\w-]+\()*([^()]*)\)*$")


def _trainer(method="allreduce", codec="none"):
    return GossipTrainer(
        engine="sim", protocol=ProtocolConfig(method=method, comm_probability=1.0,
                                              codec=codec),
        optimizer=OptimizerConfig(name="nag", learning_rate=1e-3),
        loss_fn=lambda p, x, y: tr.lm_loss(p, CFG, x, y)[0],
        init_fn=lambda k: tr.init_lm(k, CFG)[0], num_workers=2)


def _lowered(trainer):
    state = trainer.init_state(0)
    x = jnp.zeros((2, 2, 32), jnp.int32)
    return trainer._backend.sim._step_fn.lower(state, x, x)


def scopes_of(hlo_text: str) -> dict:
    """{scope: {"fwd", "bwd"}} over the op_name metadata of an HLO text; an
    op is backward when its path runs through ``transpose(``."""
    out = {}
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        way = "bwd" if "transpose(" in name else "fwd"
        for part in name.split("/"):
            m = UNWRAP.match(part)
            if m and m.group(1):
                out.setdefault(m.group(1), set()).add(way)
    return out


@pytest.mark.parametrize("method,codec,step_scopes", [
    ("allreduce", "none", ("grad_mean", "mix", "update")),
    ("elastic_gossip", "q8", ("codec", "mix", "update")),
])
def test_sim_step_names_every_layer(method, codec, step_scopes):
    found = scopes_of(_lowered(_trainer(method, codec)).compile().as_text())
    for s in MODEL:
        assert found.get(s) == {"fwd", "bwd"}, (s, found.get(s))
    for s in step_scopes:
        assert s in found, (s, sorted(found))


def test_dist_step_names_the_exchange():
    """engine="dist" on four host devices: the gossip program names the
    ppermute ``exchange``, the codec and the fused update; the train step
    names the gradient mean."""
    code = textwrap.dedent("""
        import re
        import jax.numpy as jnp
        from repro.launch.train import run
        for method, codec in (("elastic_gossip", "q8"), ("allreduce", "none")):
            t, st, _ = run("tinyllama_1_1b", reduced=True, steps=1, method=method,
                           p=1.0, tau=0, alpha=0.5, workers=4, global_batch=8,
                           seq=32, lr=1e-3, engine="dist", codec=codec,
                           log_every=100)
            d = t._backend
            shapes = d.trainer.batch_shapes()
            low = (d.tg.lower(st, shapes, jnp.ones((4,), bool), jnp.int32(0))
                   if method != "allreduce" else d.ts.lower(st, shapes, jnp.zeros(())))
            names = set()
            for n in re.findall(r'op_name="([^"]*)"', low.compile().as_text()):
                names.update(re.split(r"[/()]", n))
            print(method, sorted(names & {"exchange", "codec", "update", "mix",
                                          "grad_mean", "flat_views", "attention"}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in r.stdout.splitlines()
                 if line.startswith(("elastic_gossip ", "allreduce ")))
    assert lines["elastic_gossip"] == str(["attention", "codec", "exchange",
                                           "flat_views", "update"])
    assert lines["allreduce"] == str(["attention", "flat_views", "grad_mean",
                                      "update"])


def test_tracing_leaves_the_program_unchanged(tmp_path):
    trainer = _trainer()
    plain = _lowered(trainer).as_text(debug_info=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _lowered(trainer).as_text(debug_info=True)
    finally:
        jax.profiler.stop_trace()
    assert "attention" in plain
    assert traced == plain


def test_train_step_span_is_on_the_profiler_clock(tmp_path):
    from jax.profiler import ProfileData
    trainer = _trainer()
    state = trainer.init_state(0)
    x = jnp.zeros((2, 2, 32), jnp.int32)
    state, _ = trainer.step(state, (x, x))          # compile outside the trace
    jax.block_until_ready(state.theta)
    jax.profiler.start_trace(str(tmp_path))
    brackets, now = [], []
    for _ in range(3):
        t0 = time.time_ns()
        state, m = trainer.step(state, (x, x))
        jax.block_until_ready(m["loss"])
        now.append(Observer.now())
        brackets.append((t0, time.time_ns()))
    jax.profiler.stop_trace()
    data = ProfileData.from_file(
        glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    start = next(int(v) for p in data.planes if p.name == "Task Environment"
                 for k, v in p.stats if k == "profile_start_time")
    spans = sorted((start + e.start_ns, start + e.end_ns)
                   for p in data.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name == "train_step")
    assert len(spans) == 3
    for (a, b), (t0, t1), t_obs in zip(spans, brackets, now):
        assert t0 <= a < b <= t1 + 1_000          # the trace keeps ns floats
        assert b / 1e9 <= t_obs <= t1 / 1e9
