#!/usr/bin/env python3
"""Records ``data/scoped_tpu_trace.xplane.pb``: a ``jax.profiler`` trace, on a
TPU, of three steps of the sim engine's all-reduce step on a tiny tied dense
model, inside a ``window`` span as the harness traces one. The readers' tests
(``test_scopes.py``) reduce it.

    python3 benchmarks/chip/tests/make_scoped_trace.py
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))

import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402

from repro.api import GossipTrainer            # noqa: E402
from repro.common.cache import enable_compile_cache   # noqa: E402
from repro.common.config import ModelConfig, OptimizerConfig, ProtocolConfig  # noqa: E402
from repro.models import transformer as tr     # noqa: E402

OUT = HERE / "data" / "scoped_tpu_trace.xplane.pb"
CFG = ModelConfig(name="tiny", arch_type="dense", num_layers=1, d_model=256,
                  num_heads=2, num_kv_heads=1, d_ff=512, vocab_size=512,
                  activation="swiglu", tie_embeddings=True)


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("make_scoped_trace: needs a TPU")
    enable_compile_cache()
    t = GossipTrainer(engine="sim", protocol=ProtocolConfig(method="allreduce"),
                      optimizer=OptimizerConfig(name="nag", learning_rate=1e-3),
                      loss_fn=lambda p, x, y: tr.lm_loss(p, CFG, x, y)[0],
                      init_fn=lambda k: tr.init_lm(k, CFG)[0], num_workers=2)
    state = t.init_state(0)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 256), 0, CFG.vocab_size)
    for _ in range(2):
        state, _ = t.step(state, (x, x))
    jax.block_until_ready(state.theta)
    d = tempfile.mkdtemp(prefix="scoped_trace_")
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            state, _ = t.step(state, (x, x))
        jax.block_until_ready(state.theta)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, OUT)
    shutil.rmtree(d, ignore_errors=True)
    print(f"{OUT} {OUT.stat().st_size} bytes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
