"""CPU tests of the chip benchmark's yardstick: the trace reduction, the FLOP
and byte counts, the cells of BENCHMARK.json and the refusal of devices."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(1, str(ROOT / "src"))

import compare   # noqa: E402
import devtrace  # noqa: E402
import flops     # noqa: E402
import harness   # noqa: E402

RECORDED = CHIP / "tests" / "data" / "small_tpu_trace.xplane.pb"


# ----------------------------------------------------------- trace reduction
def _trace():
    """Device 0 ops and host spans in ns, by hand:

    window [0, 100); ops a [10, 30), b [20, 40) (overlapping), c [60, 70);
    host: dispatch [0, 15), block [40, 100)."""
    tr = devtrace.Trace()
    tr.ops[0] = [("a", 10, 30), ("b", 20, 40), ("c", 60, 70)]
    tr.host = [("window", 0, 100), ("dispatch", 0, 15), ("block", 40, 100)]
    return tr


def test_busy_union_counts_overlap_once():
    tr = _trace()
    assert devtrace.busy_ns(tr.ops[0]) == 30 + 10


def test_clip_to_window():
    ops = [("x", -5, 5), ("y", 95, 120), ("z", 200, 210)]
    assert devtrace.clip(ops, 0, 100) == [("x", 0, 5), ("y", 95, 100)]


def test_idle_gaps_and_their_host_activity():
    tr = _trace()
    lo, hi = devtrace.window(tr)
    assert devtrace.gaps(tr.ops[0], lo, hi) == [(0, 10), (40, 60), (70, 100)]
    gaps = devtrace.longest_gaps(tr, tr.ops[0], lo, hi, n=2)
    assert gaps == [["block", 30e-9], ["block", 20e-9]]
    assert devtrace.host_activity(tr, 5) == "dispatch"
    assert devtrace.host_activity(tr, 20) == "none"


def test_kernel_event_sums_and_top_ops():
    ops = {0: [("fusion.1", 0, 10), ("_flat_kernel", 10, 40), ("fusion.1", 40, 45)],
           1: [("_flat_kernel", 0, 20)]}
    assert devtrace.sum_ns(ops[0], r"_flat_kernel") == 30
    assert devtrace.sum_ns(ops[0], r"^fusion") == 15
    top = devtrace.top_ops(ops, 1)
    assert top == [["_flat_kernel", 25e-9]]


def test_recorded_tpu_trace_reduces():
    """A trace recorded on a TPU v5e: three steps of a small jitted program
    under the harness's ``window`` / ``dispatch`` / ``block`` spans."""
    tr = devtrace.load(str(RECORDED))
    assert 0 in tr.ops and tr.ops[0], "no TPU ops in the recorded trace"
    lo, hi = devtrace.window(tr)
    ops = devtrace.clip(tr.ops[0], lo, hi)
    busy = devtrace.busy_ns(ops)
    assert 0 < busy <= hi - lo
    idle = sum(b - a for a, b in devtrace.gaps(ops, lo, hi))
    assert busy + idle == hi - lo
    names = {n for n, _, _ in ops}
    assert devtrace.sum_ns(ops, ".") == sum(b - a for _, a, b in ops)
    assert names


# ------------------------------------------------------------------- flops
def test_dense_flops_by_hand():
    """The dense family's count lives with its reference, by term."""
    cfg = {"reference": "dense", "num_layers": 2, "d_model": 8, "num_heads": 4,
           "num_kv_heads": 2, "d_ff": 12, "vocab_size": 10}
    # head_dim 2; seq 5 -> mean context 3
    weights = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 12             # 480
    attention = 2 * 2 * 4 * 2 * 3
    terms = {"weights": 2 * 2 * weights, "attention": 2 * attention,
             "head": 2 * 8 * 10}
    assert harness.reference_model(cfg).forward_flops(cfg, 5) == terms
    assert flops.forward_terms(cfg, 5) == terms
    assert flops.train_flops_per_token(cfg, 5) == 3 * sum(terms.values())


def test_published_config_flops():
    """The configuration at its cell's sequence: 1,371,561,984 operations per
    token trained, the count ``step_mfu`` has read since the benchmark began."""
    g = json.loads((CHIP / "configs" / "granite_3_8b.cut1.json").read_text())
    assert flops.train_flops_per_token(g, 1024) == 1_371_561_984
    assert flops.forward_terms(g, 1024) == {
        "weights": 398_458_880, "attention": 8_396_800, "head": 50_331_648}


def test_flops_names_no_family():
    """A family's count is found through its reference alone."""
    source = (CHIP / "flops.py").read_text()
    assert "dense" not in source and "FORWARD" not in source


# ------------------------------------------------------------------- cells
def test_every_workload_resolves_to_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        assert cell.chips == w["chips"]
        assert cell.limits and set(cell.limits) <= set(compare.NUMBERS)
        assert cell.traffic["engine"] == "sim"
        assert (CHIP / "refs" / f"{cell.config['reference']}.py").is_file()
        assert (CHIP / "refs" / f"{cell.traffic['method']}.py").is_file()
        assert all(hasattr(mod, "read") for _, mod in cell.metrics)
        assert [m["name"] for m in cell.end_to_end] == [
            m["name"] for m in bench["end_to_end"]]
        assert harness.model_config(cell.config).d_model == cell.config["d_model"]


def test_program_layout_matches_reference_initialiser():
    """The reference initialiser builds the program's parameter tree for every
    configuration (shapes only; nothing is allocated)."""
    import jax

    from repro.models import transformer as tr
    for path in sorted((CHIP / "configs").glob("*.json")):
        cfg = json.loads(path.read_text())
        model = harness.reference_model(cfg)
        got = jax.eval_shape(lambda k: model.init(cfg, k), jax.random.PRNGKey(0))
        want, _ = tr.abstract_lm(harness.model_config(cfg))
        assert jax.tree.map(lambda s: (s.shape, s.dtype), got) == \
            jax.tree.map(lambda s: (s.shape, s.dtype), want)


def test_non_tpu_device_is_refused():
    cell = harness.load_cell(
        json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"], ROOT)
    with pytest.raises(harness.Refused, match="no TPU"):
        harness.require_chips(cell)


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax
    cell = types.SimpleNamespace(chips=1, peaks={"TPU v5 lite": {}})
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(harness.Refused, match="not in peaks.json"):
        harness.require_chips(cell)
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")])
    with pytest.raises(harness.Refused, match="needs 4 chips"):
        harness.require_chips(types.SimpleNamespace(chips=4, peaks=cell.peaks))


def test_seeds_take_any_size():
    a, b = harness.seeds(2**31 + 12345), harness.seeds(2**31 + 12345)
    assert a == b and 0 <= a.trainer < 2**31
    assert harness.seeds(7) != a


# ------------------------------------------------------------ metric readers
def _reader(name):
    return harness._module(CHIP / "metrics" / f"{name}.py", f"metric_{name}")


def _ctx(ops, **kw):
    base = dict(ops=ops, window_s=1.0, busy_s=[0.75], steps=2, tokens=1000,
                chips=1, flops_per_token=1e9, peak={"bf16_flops_per_s": 2e12,
                                                    "hbm_bytes_per_s": 1e9})
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_metric_readers_by_hand():
    ops = {0: [("%fusion.1 = f32[8] fusion(f32[8] %x)", 0, 200_000)]}
    assert _reader("device_idle_share").read(_ctx(ops)) == pytest.approx(25.0)
    # 1e9 FLOP/token x 1000 tokens / 1 s over 2e12 FLOP/s
    assert _reader("step_mfu").read(_ctx(ops)) == pytest.approx(50.0)


@pytest.mark.parametrize("name", ["device_idle_share", "step_mfu"])
def test_metric_readers_leave_out_what_they_cannot_read(name):
    empty = _ctx({}, busy_s=[], window_s=0.0, tokens=0)
    assert _reader(name).read(empty) is None
