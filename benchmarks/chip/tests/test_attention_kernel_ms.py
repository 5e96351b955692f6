"""CPU tests of the ``attention_kernel_ms`` reader: by hand on a made-up
trace, and silent on the recorded v5e trace of a program without the
training flash kernel (``data/scoped_tpu_trace.xplane.pb``)."""
from __future__ import annotations

import sys
import types
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import devtrace  # noqa: E402
import harness   # noqa: E402
import scopes    # noqa: E402

SCOPED = CHIP / "tests" / "data" / "scoped_tpu_trace.xplane.pb"
READER = harness._module(CHIP / "metrics" / "attention_kernel_ms.py",
                         "metric_attention_kernel_ms")
STEP = "jit(_step)/transpose(jvp(vmap(jvp(layer_scan))))/while/body/checkpoint/"


def test_reads_the_flash_scopes_by_hand(monkeypatch):
    tr = devtrace.Trace()
    tr.host = [("window", 0, 1_000_000)]
    tr.ops = {0: [
        (STEP + "rematted_computation/attention/flash_fwd/pallas_call", 0, 200_000),
        (STEP + "attention/flash_bwd/pallas_call", 200_000, 500_000),
        (STEP + "attention/flash_bwd/reduce_sum", 500_000, 550_000),
        (STEP + "attention/bsd,dhk->bshk/dot_general", 550_000, 900_000),
        ("jit(_step)/vmap(transpose(jvp(flash_bwd_like)))/mul", 900_000, 950_000),
        ("jit(_step)/update/add", 950_000, 1_200_000),           # past the window
    ]}
    monkeypatch.setattr(scopes, "trace", lambda ctx: tr)
    ctx = types.SimpleNamespace(ops={0: []}, steps=2)
    # 0.2 + 0.3 + 0.05 ms over 2 steps
    assert abs(READER.read(ctx) - 0.275) < 1e-12
    assert scopes.reduce(tr, 2).ns["attention"] >= READER.read(ctx) * 1e6


def test_silent_on_a_program_without_the_kernel():
    tr = scopes.load(str(SCOPED))
    lo, hi = scopes.window(tr)
    ctx = types.SimpleNamespace(trace_path=str(SCOPED), ops={d: [] for d in tr.ops},
                                window_s=(hi - lo) / 1e9, steps=3)
    assert READER.read(ctx) is None
