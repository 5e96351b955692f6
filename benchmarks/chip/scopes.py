"""Device time per named layer of the train step, from a ``jax.profiler`` trace.

The program names each layer of its step with ``jax.named_scope``
(``attention``, ``ffn``, ``embed``, ``layer_scan``, ``head_loss``,
``flat_views``, ``grad_mean``, ``mix``, ``codec``, ``update``, ``exchange``)
and each call of ``GossipTrainer.step`` with the host span ``train_step``.
XLA keeps the scope path in the ``op_name`` metadata of every HLO
instruction, and the TPU profiler copies it into the trace: as the ``tf_op``
stat of an op's event metadata, and in the HLO of each program (the
``/host:metadata`` plane). The op's event name, its HLO text, carries none
of it. A fusion is named by its root; an instruction XLA made without
metadata by the nearest named instruction in the dataflow graph.

An op's time is its SELF time: the device timeline of the ``window`` span is
cut at every op boundary and each piece goes to the innermost op running
(the latest started), so a control-flow op that encloses its body's ops
(``while``) keeps only the time no body op covers. The pieces partition the
interval union that ``devtrace.busy_ns`` measures. An op belongs to the
innermost scope of its path; under a transform the name is wrapped
(``transpose(jvp(flat_views))``), and forward and backward add together.

``jax.profiler.ProfileData`` does not expose event metadata stats, so the
``.xplane.pb`` protobuf is decoded here, reading only the fields named below
(tensorflow/tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto).
"""
from __future__ import annotations

import glob
import heapq
import os
import re
import tempfile
from dataclasses import dataclass

from devtrace import DEVICE_PLANE, OPS_LINE, SHORT, Trace, window

SCOPES = ("attention", "ffn", "embed", "layer_scan", "head_loss", "flat_views",
          "grad_mean", "mix", "codec", "update", "exchange")
HOST_SPANS = ("window", "train_step")
UNWRAP = re.compile(r"^((?:[\w-]+\()*)([^()]*)\)*$")


# ------------------------------------------------------------------ protobuf
def _varint(b: bytes, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes):
    """(field number, wire type, value) of one message; a length-delimited
    value is its bytes, a fixed-width one its raw bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield f, wt, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stats(raw: list, stat_names: dict) -> dict:
    """{stat name: value} of XStats: a string, an integer or bytes."""
    out = {}
    for stat in raw:
        mid, val = 0, None
        for f, wt, v in _fields(stat):
            if f == 1:
                mid = v
            elif f in (3, 4):            # uint64, int64 (a program id
                val = v                  # keys /host:metadata unsigned)
            elif f == 5:
                val = v.decode("utf-8", "replace")
            elif f == 6:
                val = v
            elif f == 7:                 # ref_value: a string kept once
                val = stat_names.get(v)
        out[stat_names.get(mid)] = val
    return out


def _plane(b: bytes):
    """(name, {event metadata id: (name, {stat: value})}, [(line name,
    ts_ns, [(metadata id, offset_ps, duration_ps)])]) of one XPlane."""
    name, lines, md_raw, stat_names = "", [], [], {}
    for f, _, v in _fields(b):
        if f == 2:
            name = v.decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            md_raw.append(v)
        elif f == 5:                     # map<int64, XStatMetadata>
            for g, _, w in _fields(v):
                if g == 2:
                    sid, sname = 0, ""
                    for h, _, x in _fields(w):
                        if h == 1:
                            sid = x
                        elif h == 2:
                            sname = x.decode("utf-8", "replace")
                    stat_names[sid] = sname
    meta = {}
    for entry in md_raw:                 # map<int64, XEventMetadata>
        for g, _, w in _fields(entry):
            if g != 2:
                continue
            mid, mname, raw = 0, "", []
            for h, _, x in _fields(w):
                if h == 1:
                    mid = x
                elif h == 2:
                    mname = x.decode("utf-8", "replace")
                elif h == 5:
                    raw.append(x)
            meta[mid] = (mname, _stats(raw, stat_names))
    out = []
    for lb in lines:
        lname, ts, evs = "", 0, []
        for f, _, v in _fields(lb):
            if f == 2:
                lname = v.decode("utf-8", "replace")
            elif f == 3:
                ts = _signed(v)
            elif f == 4:
                mid = off = dur = 0
                for g, _, w in _fields(v):
                    if g == 1:
                        mid = w
                    elif g == 2:
                        off = _signed(w)
                    elif g == 3:
                        dur = _signed(w)
                evs.append((mid, off, dur))
        out.append((lname, ts, evs))
    return name, meta, out


def _ids(wt: int, x) -> list:
    """A repeated int64 field, packed or not."""
    if wt != 2:
        return [x]
    out, i = [], 0
    while i < len(x):
        c, i = _varint(x, i)
        out.append(c)
    return out


def _op_names(hlo_proto: bytes) -> dict:
    """{instruction name: the op_name it is attributed by} of one HloProto.

    An instruction's own ``op_name``; a fusion's is its root's (through
    nested fusions), else its own. XLA creates some instructions without
    metadata (layout copies, some fusions): such an instruction takes the
    op_name of the nearest named instruction in the dataflow graph, those
    that read it before those it reads at each distance. Fields read
    (xla/service/hlo.proto): HloProto.hlo_module, HloModuleProto.computations,
    HloComputationProto.instructions/id/root_id, HloInstructionProto.name/
    opcode/metadata/id/operand_ids/called_computation_ids, OpMetadata.op_name.
    """
    module = b"".join(v for f, _, v in _fields(hlo_proto) if f == 1)
    ins, roots = {}, {}
    for f, _, comp in _fields(module):
        if f != 3:
            continue
        cid = root = 0
        for g, _, v in _fields(comp):
            if g == 2:
                d = {"name": "", "opcode": "", "op_name": "", "id": 0,
                     "operands": [], "called": []}
                for h, wt, x in _fields(v):
                    if h == 1:
                        d["name"] = x.decode()
                    elif h == 2:
                        d["opcode"] = x.decode()
                    elif h == 7:
                        d["op_name"] = next((y.decode("utf-8", "replace")
                                             for k, _, y in _fields(x) if k == 2), "")
                    elif h == 35:
                        d["id"] = x
                    elif h == 36:
                        d["operands"] += _ids(wt, x)
                    elif h == 38:
                        d["called"] += _ids(wt, x)
                ins[d["id"]] = d
            elif g == 5:
                cid = v
            elif g == 6:
                root = v
        roots[cid] = root

    def own(d):
        node, hops = d, 0
        while node["opcode"] == "fusion" and node["called"] and hops < 8:
            node = ins.get(roots.get(node["called"][0]), node)
            hops += 1
            if node["op_name"]:
                return node["op_name"]
        return d["op_name"]

    name = {i: own(d) for i, d in ins.items()}
    users = {}
    for i, d in ins.items():
        for o in d["operands"]:
            users.setdefault(o, []).append(i)
    out = {}
    for i, d in ins.items():
        found, seen, ring = name[i], {i}, [i]
        while not found and ring and len(seen) < 64:
            nxt = []
            for j in ring:
                nxt += [k for k in users.get(j, []) if k not in seen]
            for j in ring:
                nxt += [k for k in ins[j]["operands"] if k in ins and k not in seen]
            seen.update(nxt)
            found = next((name[k] for k in nxt if name[k]), "")
            ring = nxt
        out[d["name"]] = found
    return out


def load(path: str) -> Trace:
    """A ``devtrace.Trace`` whose device ops are named by their ``op_name``
    (a fusion's that of its root, from the HLO the trace carries) and whose
    host spans are those of HOST_SPANS, in ns on the trace's one clock."""
    with open(path, "rb") as fh:
        data = fh.read()
    planes = [_plane(pb) for f, _, pb in _fields(data) if f == 1]
    hlo = {}                             # program id -> {instruction: op_name}
    for name, meta, _ in planes:
        if name == "/host:metadata":
            for mid, (_, st) in meta.items():
                if isinstance(st.get("Hlo Proto"), bytes):
                    hlo[mid] = _op_names(st["Hlo Proto"])
    tr = Trace()
    for name, meta, lines in planes:
        m = DEVICE_PLANE.match(name)
        if m:
            op_names = {}
            for mid, (text, st) in meta.items():
                short = SHORT.match(text)
                program = hlo.get(st.get("program_id"), {})
                op_names[mid] = ((short and program.get(short.group(1)))
                                 or st.get("tf_op") or "")
            ops = []
            for lname, ts, evs in lines:
                if lname == OPS_LINE:
                    base = ts * 1000
                    ops += [(op_names.get(mid, ""), (base + off) / 1000,
                             (base + off + dur) / 1000) for mid, off, dur in evs]
            tr.ops[int(m.group(1))] = sorted(ops, key=lambda o: o[1])
        elif name.startswith("/host:"):
            for _, ts, evs in lines:
                base = ts * 1000
                for mid, off, dur in evs:
                    if meta.get(mid, ("",))[0] in HOST_SPANS:
                        tr.host.append((meta[mid][0], (base + off) / 1000,
                                        (base + off + dur) / 1000))
    tr.host.sort(key=lambda s: s[1])
    return tr


# ----------------------------------------------------------------- reduction
def scope_of(op_name: str) -> str:
    """The innermost scope of SCOPES on ``op_name``'s path, or ""."""
    found = ""
    for part in op_name.split("/"):
        m = UNWRAP.match(part)
        if m and m.group(2) in SCOPES and not m.group(1).startswith(("jit(", "pjit(")):
            found = m.group(2)
    return found


def self_times(ops: list, lo: float, hi: float) -> dict:
    """op_name -> ns of the window [lo, hi] during which that op was the
    innermost one running. The values sum to the union of the op intervals."""
    evs = sorted((max(a, lo), min(b, hi), n) for n, a, b in ops if b > lo and a < hi)
    out, heap, i, t = {}, [], 0, None
    while i < len(evs) or heap:
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        nxt = evs[i][0] if i < len(evs) else float("inf")
        if heap:
            _, end, name = heap[0]
            stop = min(end, nxt)
            out[name] = out.get(name, 0.0) + stop - t
            t = stop
        else:
            t = nxt
        while i < len(evs) and evs[i][0] <= t:
            a, b, n = evs[i]
            heapq.heappush(heap, (-a, b, n))
            i += 1
    return out


@dataclass
class Layers:
    ns: dict          # scope ("" for none) -> ns per step, averaged over devices
    busy_ns: float    # device busy ns per step, averaged over devices

    @property
    def scoped(self) -> bool:
        return any(k and v > 0 for k, v in self.ns.items())


def reduce(tr: Trace, steps: int, devices=None) -> Layers:
    """Per-step device time of each scope over the ``window`` span."""
    lo, hi = window(tr)
    devs = [d for d in sorted(tr.ops) if devices is None or d in devices]
    tot = {}
    for d in devs:
        for name, ns in self_times(tr.ops[d], lo, hi).items():
            s = scope_of(name)
            tot[s] = tot.get(s, 0.0) + ns
    k = max(len(devs), 1) * max(steps, 1)
    return Layers({s: v / k for s, v in tot.items()}, sum(tot.values()) / k)


def host_steps(tr: Trace) -> list:
    """ns of each ``train_step`` span inside the ``window`` span."""
    lo, hi = window(tr)
    return [b - a for n, a, b in tr.host if n == "train_step" and a >= lo and b <= hi]


# ----------------------------------------------------------------- readers
def trace(ctx):
    """The :class:`Trace` of this run's traced window, or None: the file
    ``ctx.trace_path`` when the harness gives it, else the newest
    ``.xplane.pb`` under a ``chip_trace_*`` directory of the temporary
    directory (where the harness writes it) whose ``window`` span is as long
    as ``ctx.window_s``."""
    given = getattr(ctx, "trace_path", None)
    paths = [given] if given else sorted(
        glob.glob(os.path.join(tempfile.gettempdir(), "chip_trace_*", "**",
                               "*.xplane.pb"), recursive=True),
        key=os.path.getmtime, reverse=True)
    for path in paths:
        try:
            tr = load(path)
            lo, hi = window(tr)
        except (OSError, ValueError, IndexError):
            continue
        if given or abs((hi - lo) / 1e9 - ctx.window_s) < 1e-6:
            return tr
    return None


def layers(ctx):
    """The :class:`Layers` of the run behind ``ctx``, or None where the trace
    cannot be found or names no scope (a program without the scopes)."""
    tr = trace(ctx)
    if tr is None:
        return None
    lay = reduce(tr, ctx.steps, set(ctx.ops) if getattr(ctx, "ops", None) else None)
    return lay if lay.scoped else None


def scope_ms(ctx, *names):
    """Device ms per step under the scopes ``names``, or None."""
    lay = layers(ctx)
    if lay is None:
        return None
    return sum(lay.ns.get(n, 0.0) for n in names) / 1e6
