"""CPU tests of the per-layer readers (``scopes.py`` and the eight metrics
that read it): the protobuf decoding against ``jax.profiler.ProfileData``,
the self-time partition and the scope of an op by hand, and every reader on
a trace recorded on a TPU v5e from the scoped program
(``data/scoped_tpu_trace.xplane.pb``, made by ``make_scoped_trace.py``)
against sums of the same events computed here by brute force, and every
reader of the granite cell alike whether the harness gives it the trace's
path or it searches for the trace."""
from __future__ import annotations

import shutil
import sys
import types
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

import devtrace  # noqa: E402
import flops     # noqa: E402
import harness   # noqa: E402
import scopes    # noqa: E402
# the temporary one-cell benchmark and the CPU standing in for the chip
from test_chipbench_run import bench, cpu_as_chip  # noqa: E402,F401

SCOPED = CHIP / "tests" / "data" / "scoped_tpu_trace.xplane.pb"
UNSCOPED = CHIP / "tests" / "data" / "small_tpu_trace.xplane.pb"
STEPS = 3          # the steps make_scoped_trace.py runs inside its window
LAYERS = {"attention_ms": ("attention",), "ffn_ms": ("ffn",),
          "embed_head_ms": ("embed", "layer_scan", "head_loss"),
          "flat_views_ms": ("flat_views",), "grad_mean_ms": ("grad_mean",),
          "update_ms": ("update",)}
METRICS = tuple(LAYERS) + ("unscoped_share", "host_step_ms")


def _reader(name):
    return harness._module(CHIP / "metrics" / f"{name}.py", f"metric_{name}")


def _ctx(path, steps=STEPS, **kw):
    tr = scopes.load(str(path))
    lo, hi = scopes.window(tr)
    base = dict(trace_path=str(path), ops={d: [] for d in tr.ops},
                window_s=(hi - lo) / 1e9, steps=steps)
    base.update(kw)
    return types.SimpleNamespace(**base)


# ------------------------------------------------------------- by hand
@pytest.mark.parametrize("op_name,scope", [
    ("jit(_step)/vmap(jvp())/while/body/closed_call/layer_scan/attention/"
     "bsd,dhk->bshk/dot_general", "attention"),
    ("jit(_step)/vmap(transpose(jvp(layer_scan)))/while/body/squeeze", "layer_scan"),
    ("jit(_step)/vmap(transpose(vmap(jvp(flat_views))))/vmap()/scatter", "flat_views"),
    ("jit(_step)/update/add", "update"),
    ("jit(update)/add", ""),
    ("jit(_step)/vmap(jvp())/dynamic_update_slice", ""),
    ("", ""),
])
def test_scope_of_an_op_name(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_self_times_partition_the_busy_union():
    """while [0, 100) encloses a [10, 30) and b [40, 60); c [90, 120) overlaps
    its end; d [200, 210) stands alone; the window is [0, 205)."""
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 40, 60), ("c", 90, 120),
           ("d", 200, 210)]
    got = scopes.self_times(ops, 0, 205)
    assert got == {"while": 50, "a": 20, "b": 20, "c": 30, "d": 5}
    assert sum(got.values()) == devtrace.busy_ns(devtrace.clip(ops, 0, 205))


def _varint(x):
    out = b""
    while True:
        b, x = x & 0x7F, x >> 7
        out += bytes([b | (0x80 if x else 0)])
        if not x:
            return out


def _f(num, value):
    """One protobuf field: an int as a varint, bytes/str length-delimited,
    a list of ints packed."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, list):
        value = b"".join(_varint(v) for v in value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _instr(iid, name, opcode, op_name="", operands=(), called=()):
    return _f(2, _f(1, name) + _f(2, opcode)
              + (_f(7, _f(2, op_name)) if op_name else b"") + _f(35, iid)
              + (_f(36, list(operands)) if operands else b"")
              + (_f(38, list(called)) if called else b""))


def test_op_names_from_the_hlo_by_hand():
    """A fusion takes its root's op_name; an instruction without metadata
    takes the nearest named one, readers before operands."""
    fused = (_f(1, "fused") + _instr(10, "param_0", "parameter")
             + _instr(11, "add.1", "add", "jit(f)/transpose(jvp(attention))/add", [10])
             + _f(5, 2) + _f(6, 11))
    bare = (_f(1, "bare") + _instr(20, "param_0.1", "parameter")
            + _instr(21, "bitcast.1", "bitcast", "", [20]) + _f(5, 3) + _f(6, 21))
    entry = (_f(1, "main") + _instr(1, "p0", "parameter")
             + _instr(2, "dot.1", "dot", "jit(f)/ffn/dot_general", [1])
             + _instr(3, "copy.1", "copy", "", [2])
             + _instr(4, "fusion.1", "fusion", "", [3], [2])
             + _instr(5, "fusion.2", "fusion", "", [1], [3])
             + _instr(6, "mul.1", "multiply", "jit(f)/embed/mul", [5, 2])
             + _instr(7, "copy.2", "copy", "", [2, 5])
             + _f(5, 1) + _f(6, 7))
    proto = _f(1, _f(3, fused) + _f(3, bare) + _f(3, entry))
    names = scopes._op_names(proto)
    assert names["dot.1"] == "jit(f)/ffn/dot_general"
    assert names["fusion.1"] == "jit(f)/transpose(jvp(attention))/add"
    assert names["copy.1"] == "jit(f)/transpose(jvp(attention))/add"  # its reader
    assert names["fusion.2"] == "jit(f)/embed/mul"                   # its reader
    assert names["copy.2"] == "jit(f)/ffn/dot_general"               # no reader


# ------------------------------------------------------ recorded on a TPU
def test_decoding_agrees_with_profile_data():
    ours = scopes.load(str(SCOPED))
    ref = devtrace.load(str(SCOPED))
    assert ours.ops and set(ours.ops) == set(ref.ops)
    for d in ref.ops:
        a = sorted((t0, t1) for _, t0, t1 in ours.ops[d])
        b = sorted((t0, t1) for _, t0, t1 in ref.ops[d])
        assert len(a) == len(b)
        # ProfileData truncates to whole ns; this decoding keeps the ps
        assert max(max(abs(x[0] - y[0]), abs(x[1] - y[1])) for x, y in zip(a, b)) < 3
    assert [round(t) for t in scopes.window(ours)] == list(devtrace.window(ref))


def _by_brute_force(tr):
    """{scope: ns per step}, the busy ns per step and the train_step spans,
    from the events alone: every elementary interval of the window goes to
    the latest-started op covering it."""
    lo, hi = scopes.window(tr)
    ops = [(n, max(a, lo), min(b, hi)) for n, a, b in tr.ops[0] if b > lo and a < hi]
    cuts = sorted({t for _, a, b in ops for t in (a, b)})
    tot = {}
    for t0, t1 in zip(cuts, cuts[1:]):
        live = [(a, -b, n) for n, a, b in ops if a <= t0 and b >= t1]
        if live:
            scope = scopes.scope_of(max(live)[2])
            tot[scope] = tot.get(scope, 0.0) + (t1 - t0)
    spans = [b - a for n, a, b in tr.host
             if n == "train_step" and a >= lo and b <= hi]
    return ({k: v / STEPS for k, v in tot.items()}, sum(tot.values()) / STEPS,
            spans)


def test_every_reader_matches_a_hand_sum():
    tr = scopes.load(str(SCOPED))
    assert set(tr.ops) == {0}
    per_scope, busy, spans = _by_brute_force(tr)
    ctx = _ctx(SCOPED)
    for name, names in LAYERS.items():
        want = sum(per_scope.get(s, 0.0) for s in names) / 1e6
        assert _reader(name).read(ctx) == pytest.approx(want, rel=1e-9), name
    assert _reader("unscoped_share").read(ctx) == pytest.approx(
        100 * per_scope.get("", 0.0) / busy, rel=1e-9)
    assert len(spans) == STEPS
    assert _reader("host_step_ms").read(ctx) == pytest.approx(
        sum(spans) / len(spans) / 1e6, rel=1e-9)
    # every scope of the all-reduce step is there, and little is left out
    assert all(per_scope.get(s, 0) > 0 for names in LAYERS.values() for s in names)
    assert _reader("unscoped_share").read(ctx) < 10


def test_readers_say_nothing_of_a_program_without_scopes():
    ctx = _ctx(UNSCOPED, steps=3)
    for name in METRICS:
        assert _reader(name).read(ctx) is None, name


def test_trace_is_found_where_the_harness_writes_it(tmp_path, monkeypatch):
    """Without ``ctx.trace_path`` the readers take the newest trace under a
    ``chip_trace_*`` directory of the temporary directory whose window is as
    long as the run's."""
    monkeypatch.setattr(scopes.tempfile, "tempdir", str(tmp_path))
    where = tmp_path / "chip_trace_x" / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(SCOPED, where / "host.xplane.pb")
    ctx = _ctx(SCOPED)
    want = _reader("ffn_ms").read(ctx)
    del ctx.trace_path
    assert _reader("ffn_ms").read(ctx) == want
    ctx.window_s += 1e-3
    assert _reader("ffn_ms").read(ctx) is None


def test_traced_run_finds_its_trace_for_the_readers(bench, monkeypatch):
    """The harness gives the readers the trace it wrote, the cell's
    configuration and its traffic: a reader of ``scopes.py`` reads the
    ``train_step`` spans of the window (the CPU trace has no TPU plane, so no
    layer reads)."""
    load = harness.load_cell
    seen = []

    def with_readers(*a, **kw):
        cell = load(*a, **kw)
        for name in ("host_step_ms", "ffn_ms"):
            cell.metrics.append(({"name": name, "unit": "ms"}, harness._module(
                CHIP / "metrics" / f"{name}.py", f"metric_{name}")))
        cell.metrics.append(({"name": "seen", "unit": "x"},
                             types.SimpleNamespace(read=seen.append)))
        return cell

    monkeypatch.setattr(harness, "load_cell", with_readers)
    args = types.SimpleNamespace(workload="tiny.ar", seed=2**31 + 7, seconds=0.3,
                                 trace=1)
    r = harness.run(args, root=bench)
    assert r["correct"], r["checks"]
    assert 0 < r["metrics"]["host_step_ms"]["value"] < 1e3
    assert "ffn_ms" not in r["metrics"] and "seen" not in r["metrics"]
    ctx, = seen
    assert ctx.trace_path.endswith(".xplane.pb") and "chip_trace_" in ctx.trace_path
    assert ctx.config["d_model"] == 64 and ctx.traffic["seq"] == 32
    assert ctx.flops_per_token == flops.train_flops_per_token(ctx.config, 32)


GRANITE = "granite_3_8b.cut1.sim_w2.allreduce"
EXISTING = ("device_idle_share", "step_mfu", "attention_kernel_ms") + METRICS


@pytest.mark.parametrize("name", EXISTING + ("attention_kernel_roofline",))
@pytest.mark.parametrize("path", [SCOPED, UNSCOPED], ids=["scoped", "unscoped"])
def test_every_reader_reads_alike_with_and_without_the_path(name, path, tmp_path,
                                                            monkeypatch):
    """The granite cell's readers on a recorded trace, through the harness's
    own ``reader_context``: the given path and the search of the temporary
    directory find the same trace, so each reader reads the same."""
    cell = harness.load_cell(GRANITE, ROOT)
    _, ctx = harness.reader_context(cell, str(path), STEPS, 1,
                                    cell.peaks["TPU v5 lite"])
    want = _reader(name).read(ctx)
    monkeypatch.setattr(scopes.tempfile, "tempdir", str(tmp_path))
    where = tmp_path / "chip_trace_x" / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(path, where / "host.xplane.pb")
    del ctx.trace_path
    assert _reader(name).read(ctx) == want
    if name in LAYERS or name == "unscoped_share":
        assert (want is None) == (path == UNSCOPED)
