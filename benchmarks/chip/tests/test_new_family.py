"""A model family joins the benchmark as new files only.

A copy of ``benchmarks/chip`` in a temporary checkout gains a made-up family
(``refs/toy.py`` with ``init``, ``loss`` and ``forward_flops``), a
configuration, a traffic mix, the limits of a cell and a reader that reads the
cell's configuration, and a ``BENCHMARK.json`` that names them. In a process of
its own, the copy's harness resolves the cell, its count and its readers, and
no file of the copy differs from the original.
"""
from __future__ import annotations

import filecmp
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]

TOY_REF = '''"""A made-up family: an embedding, layers of tanh(x @ m) and an untied
head."""
import jax.numpy as jnp

from refs.plain import Init, cross_entropy, stack_layers


def init(cfg, key):
    d, V = cfg["d_model"], cfg["vocab_size"]
    rng = Init(key)
    return {"embed": rng.normal((V, d), d),
            "mix": stack_layers([rng.normal((d, d), d)
                                 for _ in range(cfg["num_layers"])]),
            "head": rng.normal((d, V), d)}


def loss(params, cfg, tokens, labels):
    x = params["embed"][tokens]
    for j in range(cfg["num_layers"]):
        x = jnp.tanh(x @ params["mix"][j])
    return cross_entropy(x, params["head"], labels)


def forward_flops(cfg, seq):
    d, V = cfg["d_model"], cfg["vocab_size"]
    return {"mixer": cfg["num_layers"] * 2.0 * d * d, "head": 2.0 * d * V}
'''
TOY_READER = '''"""toy_mixer_gflop: the made-up family's mixer operations in one step."""
import flops


def read(ctx):
    t = ctx.traffic
    tokens = t["workers"] * t["per_worker_batch"] * t["seq"]
    return 3 * flops.forward_terms(ctx.config, t["seq"])["mixer"] * tokens / 1e9
'''
TOY = {"reference": "toy", "num_layers": 2, "d_model": 16, "vocab_size": 32}
TRAFFIC = {"engine": "sim", "workers": 2, "per_worker_batch": 4, "seq": 8,
           "method": "allreduce"}
PROBE = '''
import json, sys, types
sys.path.insert(0, "benchmarks/chip")
import jax, jax.numpy as jnp
import flops, harness
cell = harness.load_cell("toy.mix")
cfg, t = cell.config, cell.traffic
model = harness.reference_model(cfg)
params = model.init(cfg, jax.random.PRNGKey(0))
rows = jnp.arange(2 * t["seq"]).reshape(2, t["seq"]) % cfg["vocab_size"]
count = flops.train_flops_per_token(cfg, t["seq"])
ctx = types.SimpleNamespace(config=cfg, traffic=t, window_s=1.0, tokens=64,
                            chips=1, peak={"bf16_flops_per_s": 1e9},
                            flops_per_token=count)
print(json.dumps({"ref": model.__file__, "count": count,
                  "loss": float(model.loss(params, cfg, rows, rows)),
                  "limits": cell.limits,
                  "metrics": {e["name"]: m.read(ctx) for e, m in cell.metrics}}))
'''


def test_a_family_joins_with_new_files_only(tmp_path):
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p.relative_to(chip) for p in chip.rglob("*") if p.is_file()}
    (chip / "refs" / "toy.py").write_text(TOY_REF)
    (chip / "configs" / "toy.json").write_text(json.dumps(TOY))
    (chip / "traffic" / "mix.json").write_text(json.dumps(TRAFFIC))
    (chip / "limits" / "toy.mix.json").write_text(json.dumps({"loss_gap": 1e-3}))
    (chip / "metrics" / "toy_mixer_gflop.py").write_text(TOY_READER)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmarks/chip/run_cell.py"],
        "paths": ["benchmarks/chip"], "run_seconds": 10,
        "configs": [{"name": "toy", "source": "x", "reduced": [], "why": "x",
                     "file": "benchmarks/chip/configs/toy.json"}],
        "workloads": [{"name": "toy.mix", "config": "toy", "traffic": "mix",
                       "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s",
                        "better": "higher", "bound": 0.01, "source": "host_clock"}],
        "per_layer": [
            {"name": "step_mfu", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "x", "moves": "train_tokens_per_s"},
            {"name": "toy_mixer_gflop", "unit": "GFLOP", "better": "lower",
             "source": "device_trace", "layer": "x", "moves": "train_tokens_per_s",
             "workloads": ["toy.mix"]}]}))

    p = subprocess.run([sys.executable, "-c", PROBE], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.splitlines()[-1])
    assert Path(got["ref"]) == chip / "refs" / "toy.py"
    # (mixer 2 x 2 x 16 x 16 + head 2 x 16 x 32) per token, x3 for training
    assert got["count"] == 3 * (2 * 2 * 16 * 16 + 2 * 16 * 32)
    assert got["loss"] > 0 and got["limits"] == {"loss_gap": 1e-3}
    assert got["metrics"] == pytest.approx({"step_mfu": 100 * got["count"] * 64 / 1e9,
                                            "toy_mixer_gflop": 3 * 1024 * 64 / 1e9})
    after = {p.relative_to(chip) for p in chip.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert after - before == {Path("refs/toy.py"), Path("configs/toy.json"),
                              Path("traffic/mix.json"), Path("limits/toy.mix.json"),
                              Path("metrics/toy_mixer_gflop.py")}
    assert before <= after
    edited = [str(f) for f in before if not filecmp.cmp(chip / f, CHIP / f, shallow=False)]
    assert not edited
