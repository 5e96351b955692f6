"""CPU tests of a kernel's roofline share: ``roofline.share`` by hand, the
work ``attention_kernel_roofline`` counts for the granite cell and for a
made-up cell, the reader by hand on a made-up trace, and its silence on the
recorded v5e trace of a program without the training flash kernel
(``data/scoped_tpu_trace.xplane.pb``)."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import devtrace  # noqa: E402
import harness   # noqa: E402
import roofline  # noqa: E402
import scopes    # noqa: E402

SCOPED = CHIP / "tests" / "data" / "scoped_tpu_trace.xplane.pb"
READER = harness._module(CHIP / "metrics" / "attention_kernel_roofline.py",
                         "metric_attention_kernel_roofline")
STEP = "jit(_step)/transpose(jvp(vmap(jvp(layer_scan))))/while/body/checkpoint/"
PEAK = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}
# head_dim 2; seq 5 -> mean causal context 3
SMALL = {"reference": "dense", "num_layers": 1, "d_model": 8, "num_heads": 4,
         "num_kv_heads": 2, "d_ff": 12, "vocab_size": 10}
SMALL_TRAFFIC = {"workers": 2, "per_worker_batch": 3, "seq": 5}


@pytest.mark.parametrize("ops,nbytes,want", [
    (4e9, 1e8, 40.0),     # compute bound: 4 s of work in 10 s
    (1e9, 5e8, 50.0),     # memory bound: 5 s of traffic in 10 s
])
def test_share_by_hand(ops, nbytes, want):
    ctx = types.SimpleNamespace(peak=PEAK)
    assert roofline.share(ctx, 10e9, ops, nbytes) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("ns", [None, 0.0])
def test_share_says_nothing_without_a_time(ns):
    assert roofline.share(types.SimpleNamespace(peak=PEAK), ns, 1.0, 1.0) is None


def test_work_of_a_small_cell_by_hand():
    ops, nbytes = READER.work_per_step(SMALL, SMALL_TRAFFIC)
    # q.k and p.v: 2 x 2 x heads 4 x head_dim 2 x context 3 per token, x3
    # for the backward, over W x B x S = 30 tokens
    assert ops == 3 * (2 * 2 * 4 * 2 * 3) * 30
    q = 3 * 5 * 4 * 2 * 2          # B x S x heads x head_dim x 2 bytes
    kv = 3 * 5 * 2 * 2 * 2
    assert nbytes == 2 * ((2 * q + 2 * kv) + (4 * q + 4 * kv))


def test_work_of_the_granite_cell():
    """206.4 GFLOP a step (1.048 ms at 197 TFLOP/s) and 0.503 GB (0.615 ms at
    819 GB/s): the kernels are bound by compute."""
    cell = harness.load_cell("granite_3_8b.cut1.sim_w2.allreduce", CHIP.parents[1])
    ops, nbytes = READER.work_per_step(cell.config, cell.traffic)
    assert ops == 206_359_756_800
    assert nbytes == 503_316_480
    peak = json.loads((CHIP / "peaks.json").read_text())["TPU v5 lite"]
    assert ops / peak["bf16_flops_per_s"] > nbytes / peak["hbm_bytes_per_s"]


def test_reads_the_flash_scopes_by_hand(monkeypatch):
    tr = devtrace.Trace()
    tr.host = [("window", 0, 1_000_000)]
    tr.ops = {0: [
        (STEP + "rematted_computation/attention/flash_fwd/pallas_call", 0, 200_000),
        (STEP + "attention/flash_bwd/pallas_call", 200_000, 500_000),
        (STEP + "attention/flash_bwd/reduce_sum", 500_000, 550_000),
        (STEP + "attention/bsd,dhk->bshk/dot_general", 550_000, 900_000),
    ]}
    monkeypatch.setattr(scopes, "trace", lambda ctx: tr)
    ctx = types.SimpleNamespace(ops={0: []}, steps=2, chips=1, peak=PEAK,
                                config=SMALL, traffic=SMALL_TRAFFIC)
    # 0.55 ms over 2 steps; memory bound: 4,320 B at 1e8 B/s is 43.2 us
    assert READER.read(ctx) == pytest.approx(100 * 43.2e-6 / 0.275e-3, rel=1e-12)


def test_silent_on_a_program_without_the_kernel():
    tr = scopes.load(str(SCOPED))
    lo, hi = scopes.window(tr)
    ctx = types.SimpleNamespace(trace_path=str(SCOPED), ops={d: [] for d in tr.ops},
                                window_s=(hi - lo) / 1e9, steps=3, chips=1,
                                peak=PEAK, config=SMALL, traffic=SMALL_TRAFFIC)
    assert READER.read(ctx) is None
