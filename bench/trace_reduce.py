"""Reduce a JAX profiler trace to device busy time, idle gaps and layers.

Two stages.  ``load`` reads an ``.xplane.pb`` with nothing but JAX and
keeps, per device op executed on a TPU, its interval, its name, the HLO
module it ran in and its frames (the ``jit(...)`` names of its op
metadata, outermost first); and the benchmark's own host spans
(``bench.*``).  Everything after that works on those plain lists, so the
tests check it on a small recorded trace.

Times are in nanoseconds of the trace's clock; the functions that return
seconds say so.
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re
from pathlib import Path

_JIT = re.compile(r"jit\(([^)]*)\)")
TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(tdir: str) -> str:
    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {tdir}: {paths}")
    return paths[0]


def frames_of(op_name: str) -> list[str]:
    """The jit frames of an op's metadata name, outermost first:
    'jit(_rgetrf_jit)/jit(getf2)/while/body/mul' -> ['_rgetrf_jit',
    'getf2']."""
    return _JIT.findall(op_name or "")


def _varint(b: bytes, i: int) -> tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, value) of one protobuf message, wire format only:
    varints as ints, length-delimited fields as bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wt in (1, 5):
            size = 8 if wt == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wt}")
        yield key >> 3, v


def _first(b: bytes, field: int, default=b""):
    return next((v for f, v in _fields(b) if f == field), default)


def op_names(xspace: bytes) -> dict:
    """{program id: {HLO instruction name: op metadata name}} from the
    HLO protos the profiler keeps in the "/host:metadata" plane.

    Field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map
    entry: value 2); XEventMetadata.id 1, .stats 5; XStat.bytes_value 6;
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    out: dict = {}
    for f, plane in _fields(xspace):
        if f != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        for g, entry in _fields(plane):
            if g != 4:
                continue
            meta = _first(entry, 2)
            names = out.setdefault(_first(meta, 1, 0), {})
            for h, stat in _fields(meta):
                if h != 5:
                    continue
                module = _first(_first(stat, 6), 1)
                for k, comp in _fields(module):
                    if k != 3:
                        continue
                    for m, ins in _fields(comp):
                        if m == 2:
                            got = {f: v for f, v in _fields(ins)
                                   if f in (1, 7)}
                            names[got.get(1, b"").decode()] = _first(
                                got.get(7, b""), 2).decode()
    return out


_INSTR = re.compile(r"^%?([^ ]+) = ")
_PROGRAM = re.compile(r"\((\d+)\)$")


def load(path: str) -> dict:
    """Device ops and bench host spans of one trace file.

    {"ops": [[start, end, name, module, op_name], ...] sorted by start,
     "devices": number of TPU planes with ops,
     "spans": [[start, end, name], ...]}

    An op's ``name`` is its HLO instruction, ``module`` the XLA module it
    ran in (from the device's "XLA Modules" line, by time), ``op_name``
    the JAX op metadata of the instruction (from the module's HLO proto)."""
    import jax
    raw = Path(path).read_bytes()
    names = op_names(raw)
    pd = jax.profiler.ProfileData.from_file(path)
    ops, spans, devices = [], [], 0
    for plane in pd.planes:
        if TPU_PLANE.match(plane.name):
            lines = {line.name: sorted((int(e.start_ns),
                                        int(e.start_ns + e.duration_ns),
                                        e.name) for e in line.events)
                     for line in plane.lines}
            mods, j = lines.get(MODULES_LINE, []), 0
            known: dict = {}                   # (module, text) -> op fields
            for s, e, text in lines.get(OPS_LINE, []):
                while j < len(mods) and mods[j][1] <= s:
                    j += 1
                module = mods[j][2] if j < len(mods) and mods[j][0] <= s \
                    else ""
                if (module, text) not in known:
                    m, pid = _INSTR.match(text), _PROGRAM.search(module)
                    instr = m.group(1) if m else text
                    known[module, text] = [
                        instr, module.split("(")[0],
                        names.get(int(pid.group(1)) if pid else -1,
                                  {}).get(instr, "")]
                ops.append([s, e, *known[module, text]])
            devices += bool(lines.get(OPS_LINE))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        start = int(ev.start_ns)
                        spans.append([start, start + int(ev.duration_ns),
                                      ev.name])
    ops.sort(key=lambda o: (o[0], -o[1]))      # a parent before its body
    spans.sort()
    return {"ops": ops, "devices": devices, "spans": spans}


def _host_window(trace: dict) -> tuple[int, int]:
    wins = [s for s in trace["spans"] if s[2] == "bench.window"]
    if len(wins) != 1:
        raise RuntimeError(f"expected one bench.window span, got {len(wins)}")
    return wins[0][0], wins[0][1]


def lost_tail_s(trace: dict) -> float:
    """Seconds between the device's last recorded op and the end of the
    traced calls.  Each call waits for its results, so in a whole trace
    this is the host's wake-up after the last op, well under 10 ms; the
    profiler keeps a bounded number of device events (about 4.3 million
    on a v5 lite, measured) and drops the rest, and then it is large."""
    lo, hi = _host_window(trace)
    last = max((o[1] for o in trace["ops"]), default=lo)
    return max(hi - last, 0) / 1e9


def complete(trace: dict) -> bool:
    """Whether the device's events cover the traced calls to their end:
    the lost tail is under 10 ms plus 1% of the window."""
    lo, hi = _host_window(trace)
    return bool(trace["ops"]) and lost_tail_s(trace) < 0.01 + 0.01 * (
        hi - lo) / 1e9


def window_of(trace: dict) -> tuple[int, int]:
    """The traced window: the benchmark's ``bench.window`` host span,
    widened to hold every device op of the trace (the trace holds only
    the traced calls, each waited for, and the host's and the device's
    clocks in it agree to about a millisecond, not exactly), and cut at
    the last recorded op where the trace is not complete."""
    lo, hi = _host_window(trace)
    if trace["ops"]:
        last = max(o[1] for o in trace["ops"])
        lo = min(lo, trace["ops"][0][0])
        hi = max(hi, last) if complete(trace) else last
    return lo, hi


def merged(intervals) -> list[list[int]]:
    """Union of [start, end] intervals, in order."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def gaps(busy: list[list[int]], lo: int, hi: int) -> list[list[int]]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if hi > t:
        out.append([t, hi])
    return out


def busy_and_window(trace: dict) -> tuple[float, float]:
    """(busy seconds averaged over the devices with ops, window seconds).
    Busy is the union of the device-op intervals."""
    lo, hi = window_of(trace)
    busy = sum(e - s for s, e in merged((o[0], o[1]) for o in trace["ops"]))
    return busy / max(trace["devices"], 1) / 1e9, (hi - lo) / 1e9


def load_layers(metrics_dir) -> dict:
    """The frame-to-metric table kept beside the readers."""
    return json.loads((Path(metrics_dir) / "layers.json").read_text())


@functools.lru_cache(maxsize=None)
def _layer_of(op_name: str, table: str) -> str | None:
    return layer_of(op_name, json.loads(table))


def layer_of(op_name: str, table: dict) -> str | None:
    """The metric an op's time goes to by its own metadata: its innermost
    frame that the table names; None where no frame is named."""
    for frame in reversed(frames_of(op_name)):
        for metric, names in table.items():
            if frame in names:
                return metric
    return None


def nesting(ops) -> tuple[list[int], list[int]]:
    """Each op's own time and the index of the op it is nested in (-1 for
    none).  A loop's event spans its body's ops on the same line, so its
    own time is its duration less theirs.  ``ops`` sorted by start,
    parents first."""
    own = [o[1] - o[0] for o in ops]
    parent = [-1] * len(ops)
    stack: list[int] = []
    for i, (s, e, *_) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            parent[i] = stack[-1]
            own[stack[-1]] -= e - s
        stack.append(i)
    return own, parent


def attributed(trace: dict, table: dict):
    """(op, own ns, metric) per op.  An op whose own metadata names no
    frame of the table (XLA drops the metadata of many fused loop ops)
    takes the metric of the op it is nested in."""
    own, parent = nesting(trace["ops"])
    layers: list = []
    key_of = functools.partial(_layer_of, table=json.dumps(table))
    for i, op in enumerate(trace["ops"]):
        key = key_of(op[4])
        if key is None and parent[i] >= 0:
            key = layers[parent[i]]
        layers.append(key)
    return zip(trace["ops"], own, layers)


def seconds_by_layer(trace: dict, table: dict) -> dict:
    """Device seconds per metric of ``table``; ops that no named frame
    claims are under None."""
    out: dict = {}
    for _, own, key in attributed(trace, table):
        out[key] = out.get(key, 0.0) + own / 1e9
    return out


def kernel_seconds(trace: dict, pattern: str) -> float:
    """Device seconds of the ops whose op metadata name matches
    ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    own, _ = nesting(trace["ops"])
    return sum(t for op, t in zip(trace["ops"], own)
               if rx.search(op[4])) / 1e9


def _host_doing(trace: dict, t: int) -> str:
    """The innermost bench span around time t, or 'outside'."""
    inner = None
    for s, e, name in trace["spans"]:
        if s <= t < e and (inner is None or s >= inner[0]):
            inner = (s, name)
    return inner[1] if inner else "outside"


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device ops that took most of their own time (by instruction
    within its module) and the longest idle gaps, each named by what the
    host was doing and the device op that ran before it."""
    lo, hi = window_of(trace)
    own, _ = nesting(trace["ops"])
    by_op: dict = {}
    for op, t in zip(trace["ops"], own):
        key = f"{op[3]}/{op[2]}"
        by_op[key] = by_op.get(key, 0) + t
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    busy = merged((o[0], o[1]) for o in trace["ops"])
    idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    starts = [o[0] for o in trace["ops"]]
    named = []
    for s, e in idle:
        i = bisect.bisect_left(starts, s) - 1    # the op whose end opens it
        while i >= 0 and trace["ops"][i][1] != s:
            i -= 1
        prev = (f"{trace['ops'][i][3]}/{trace['ops'][i][2]}" if i >= 0
                else "start")
        named.append([f"{_host_doing(trace, (s + e) // 2)} after {prev}",
                      (e - s) / 1e9])
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": named}
