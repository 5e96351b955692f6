"""Reduction of a ``jax.profiler`` trace to device time.

A trace holds one plane per TPU (``/device:TPU:<n>``) whose ``XLA Ops`` line
lists every operation the device ran, with its start and duration, and the
host plane (``/host:CPU``), where the harness's ``TraceAnnotation`` spans
(``window``, ``dispatch``, ``block``, ``batch``) say what the host was doing.
Both are on one clock. Everything below works on those intervals, clipped to
the ``window`` span.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

HOST_SPANS = ("window", "dispatch", "block", "batch")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
SHORT = re.compile(r"^%?([^\s=]+) = ")


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)    # device -> [(name, t0, t1)] ns
    host: list = field(default_factory=list)   # [(name, t0, t1)] ns


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Device operations and host spans of one ``.xplane.pb`` file (or the
    newest one under a trace directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = xplane_file(path)
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events]
            tr.ops[int(m.group(1))] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host += [(e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events if e.name in HOST_SPANS]
    tr.host.sort(key=lambda s: s[1])
    return tr


def window(tr: Trace, name: str = "window") -> tuple:
    """(start, end) ns of the host span ``name`` (the first one)."""
    for n, t0, t1 in tr.host:
        if n == name:
            return t0, t1
    raise ValueError(f"no {name!r} span in the trace")


def clip(ops: list, lo: int, hi: int) -> list:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in ops if b > lo and a < hi]


def merged(ops: list) -> list:
    """Union of the op intervals as sorted disjoint [(t0, t1)]."""
    out = []
    for _, a, b in sorted(ops, key=lambda o: o[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_ns(ops: list) -> int:
    return sum(b - a for a, b in merged(ops))


def gaps(ops: list, lo: int, hi: int) -> list:
    """Idle intervals [(t0, t1)] of the device between ``lo`` and ``hi``."""
    out, t = [], lo
    for a, b in merged(ops):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def host_activity(tr: Trace, t: int) -> str:
    """Innermost harness span (other than ``window``) open at time ``t``."""
    best = None
    for n, a, b in tr.host:
        if n != "window" and a <= t < b and (best is None or a >= best[1]):
            best = (n, a)
    return best[0] if best else "none"


def sum_ns(ops: list, pattern: str) -> int:
    """Summed duration of the ops whose name matches the regex ``pattern``."""
    rx = re.compile(pattern)
    return sum(b - a for n, a, b in ops if rx.search(n))


def short_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text, ``%fusion.12 = f32[..]
    fusion(..), ..``: the instruction's name is what precedes `` = ``."""
    m = SHORT.match(text)
    return m.group(1) if m else text[:80]


def top_ops(ops_by_device: dict, n: int = 10) -> list:
    """The ``n`` HLO instructions that took most device time, [name, seconds]
    averaged over the devices."""
    tot = {}
    for ops in ops_by_device.values():
        for text, a, b in ops:
            name = short_name(text)
            tot[name] = tot.get(name, 0) + (b - a)
    k = max(len(ops_by_device), 1)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in top]


def longest_gaps(tr: Trace, ops: list, lo: int, hi: int, n: int = 10) -> list:
    """The ``n`` longest idle gaps of one device as [host activity, seconds]."""
    gs = sorted(gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[host_activity(tr, (a + b) // 2), (b - a) / 1e9] for a, b in gs]
