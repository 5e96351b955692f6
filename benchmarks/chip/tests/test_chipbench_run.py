"""CPU tests that drive whole benchmark runs at a tiny size.

The harness's look for a chip is replaced by the CPU device; everything else
runs as on the chip: a cell, its configuration, traffic, limits and a metric
written only into a temporary directory are found by name; a sound run is
correct; the control (the reference in bfloat16 in the program's place) and
each fault planted under the timed path come out not correct.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(1, str(ROOT / "src"))

import compare   # noqa: E402
import harness   # noqa: E402

# a gap of 1e-3 is 100x what float32 on the CPU reads at this size (~1e-5)
LIMITS = {"loss_gap": 1e-3, "grad_gap": 1e-3, "update_gap": 1e-3}
TINY = {"reference": "dense", "name": "tiny", "arch_type": "dense", "num_layers": 2,
        "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "d_ff": 96,
        "vocab_size": 256, "activation": "swiglu", "rope_theta": 10000.0,
        "norm_eps": 1e-5, "tie_embeddings": True}
TRAFFIC = {"engine": "sim", "workers": 2, "per_worker_batch": 2, "seq": 32,
           "method": "allreduce", "p": 0.0, "alpha": 0.5, "codec": "none",
           "lr": 0.01, "momentum": 0.9, "copy_prob": 0.5, "shift": 7, "pool": 4,
           "trace_seconds": 1}
METRIC = '''
def read(ctx):
    return float(ctx.steps)
'''


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A benchmark that exists only in a temporary directory: one cell."""
    root = tmp_path_factory.mktemp("bench")
    data = root / "bench_data"
    for sub in ("configs", "traffic", "metrics", "limits"):
        (data / sub).mkdir(parents=True)
    (data / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (data / "traffic" / "ar.json").write_text(json.dumps(TRAFFIC))
    (data / "limits" / "tiny.ar.json").write_text(json.dumps(LIMITS))
    (data / "metrics" / "steps_seen.py").write_text(METRIC)
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "run.py"], "paths": ["bench_data"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "x", "reduced": [], "why": "x",
                     "file": "bench_data/configs/tiny.json"}],
        "workloads": [{"name": "tiny.ar", "config": "tiny", "traffic": "ar",
                       "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s",
                        "better": "higher", "bound": 0.03, "source": "host_clock"},
                       {"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "steps_seen", "unit": "steps", "better": "higher",
                       "source": "program_counter", "layer": "x",
                       "moves": "train_tokens_per_s"}]}))
    return root


@pytest.fixture(autouse=True)
def cpu_as_chip(monkeypatch):
    import jax
    monkeypatch.setattr(harness, "require_chips", lambda cell: (
        jax.devices()[:1], {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}))
    # leave the process's compilation cache settings as the suite has them
    monkeypatch.setattr(harness, "enable_cache", lambda: "")


def _run(root, seed=2**31 + 99):
    args = types.SimpleNamespace(workload="tiny.ar", seed=seed, seconds=0.3, trace=0)
    return harness.run(args, root=root)


def test_files_in_a_temporary_directory_are_found_by_name(bench):
    cell = harness.load_cell("tiny.ar", bench)
    assert cell.config["d_model"] == 64 and cell.traffic["method"] == "allreduce"
    assert [e["name"] for e, _ in cell.metrics] == ["steps_seen"]
    assert cell.metrics[0][1].read(types.SimpleNamespace(steps=3)) == 3.0


def test_sound_run_is_correct(bench):
    r = _run(bench)
    assert r["correct"], r["checks"]
    assert list(r)[:3] == ["correct", "attempted", "failed"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert all(c["value"] < 1e-4 for c in r["checks"].values())


def test_control_in_bfloat16_is_not_correct(bench):
    """The reference computed in bfloat16, in the program's place, fails."""
    import jax
    import jax.numpy as jnp
    cell = harness.load_cell("tiny.ar", bench)
    run = harness.prepare(cell, 5, jax.devices()[:1])
    ref = harness.reference(cell, run, jax.devices()[:1])
    control = harness.reference(cell, run, jax.devices()[:1], dtype=jnp.bfloat16)
    correct, checks = compare.verdict(compare.gaps(control, ref), cell.limits)
    assert not correct, checks


def _break_step(monkeypatch, breaker):
    build = harness.build_trainer

    def broken(*a, **kw):
        trainer, state, as_batch = build(*a, **kw)
        sim = trainer._backend.sim
        sim._step_fn = breaker(sim._step_fn)
        return trainer, state, as_batch

    monkeypatch.setattr(harness, "build_trainer", broken)


def test_state_left_unchanged_is_not_correct(bench, monkeypatch):
    def breaker(step):
        def unchanged(state, x, y):
            import jax
            import jax.numpy as jnp
            # the step donates its state: keep copies of what it returns
            theta, opt = jax.tree.map(jnp.copy, (state.theta, state.opt))
            new, m = step(state, x, y)
            return new.replace(theta=theta, opt=opt), m
        return unchanged
    _break_step(monkeypatch, breaker)
    r = _run(bench)
    assert not r["correct"] and r["checks"]["update_gap"]["value"] > 0.5


def test_half_the_batch_left_out_is_not_correct(bench, monkeypatch):
    def breaker(step):
        def half(state, x, y):
            n = x.shape[1] // 2
            return step(state, x[:, :n], y[:, :n])
        return half
    _break_step(monkeypatch, breaker)
    assert not _run(bench)["correct"]


def test_gradient_mean_left_out_is_not_correct(bench, monkeypatch):
    """The exchange of this traffic is the gradient mean: each replica steps
    on its own gradient instead."""
    from repro.api import protocols
    monkeypatch.setattr(protocols.AllReduceSGD, "gradient_transform",
                        lambda self, grads: grads)
    r = _run(bench)
    assert not r["correct"] and r["checks"]["grad_gap"]["value"] > 1e-3


def test_checkout_without_the_program_gives_no_result(tmp_path):
    """BENCHMARK.json and the benchmark's files alone: non-zero, no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    p = subprocess.run(
        bench["command"] + ["--workload", bench["workloads"][0]["name"],
                            "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
