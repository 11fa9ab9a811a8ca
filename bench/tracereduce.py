"""Reduce a profiler trace of one measured window to the benchmark's numbers.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain arrays; ``reduce(trace)`` turns them into device busy time, program
launches, the device operations that took most time, and the device's
idle gaps named by what the host was doing meanwhile.  The window is the
host span that the harness names ``bench.window``.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

WINDOW_SPAN = "bench.window"
BENCH_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP_N = 10


@dataclass
class Line:
    names: List[str]
    start: np.ndarray          # ns
    end: np.ndarray            # ns

    @staticmethod
    def of(events) -> "Line":
        names, start, dur = [], [], []
        for e in events:
            names.append(e.name)
            start.append(e.start_ns)
            dur.append(e.duration_ns)
        s = np.asarray(start, np.float64)
        return Line(names, s, s + np.asarray(dur, np.float64))


@dataclass
class Trace:
    devices: Dict[str, Dict[str, Line]] = field(default_factory=dict)
    host: Optional[Line] = None      # the thread that opened the window


def load(path: str) -> Trace:
    """Read the device planes' op and module lines, and the host thread
    that holds the window span."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: Line.of(ln.events) for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            if lines.get(OPS_LINE) is not None and lines[OPS_LINE].names:
                trace.devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                line = Line.of(ln.events)
                if WINDOW_SPAN in line.names:
                    trace.host = line
    return trace


def _union(start: np.ndarray, end: np.ndarray):
    """Disjoint sorted intervals covering the given ones."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, s.size - 1)
    return s[idx], e[last]


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


_OPCODE = re.compile(r"=\s*(?:\([^=]*?\)|\S+)\s+([a-z][\w-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_key(hlo: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` → ``fusion``; a custom call
    adds its target (``custom-call:tpu_custom_call``)."""
    m = _OPCODE.search(hlo)
    key = m.group(1) if m else hlo.split(" ", 1)[0]
    t = _TARGET.search(hlo)
    return f"{key}:{t.group(1)}" if t else key


def _codes(names: List[str]):
    """Integer code of each name, and the distinct names."""
    index: Dict[str, int] = {}
    codes = np.fromiter((index.setdefault(n, len(index)) for n in names),
                        np.int64, len(names))
    return codes, list(index)


def _add_op_time(op_time, ops: Line, mods: Optional[Line],
                 op_mod: np.ndarray, inside: np.ndarray,
                 dur: np.ndarray) -> None:
    """Sum clipped op time per ``module/opcode``."""
    name_code, names = _codes(ops.names)
    if mods is not None and mods.names:
        mcode, mnames = _codes(mods.names)
        mod_code = np.where(op_mod >= 0, mcode[np.maximum(op_mod, 0)],
                            len(mnames))
    else:
        mod_code, mnames = np.zeros_like(name_code), []
    mnames = [_module_name(m) for m in mnames] + ["?"]
    combined = mod_code[inside] * len(names) + name_code[inside]
    uniq, inv = np.unique(combined, return_inverse=True)
    sums = np.bincount(inv, weights=dur[inside])
    keys = {}
    for u, t in zip(uniq, sums):
        m, n = divmod(int(u), len(names))
        name = names[n]
        if name not in keys:
            keys[name] = op_key(name)
        op_time[f"{mnames[m]}/{keys[name]}"] += float(t)


class _HostSpans:
    """What the host thread was doing at a time: the innermost benchmark
    span (``bench.*``, never nested in one another inside the window) and
    the innermost other host event."""

    def __init__(self, host: Line, max_scan: int = 256):
        bench = np.array([n.startswith(BENCH_PREFIX) and n != WINDOW_SPAN
                          for n in host.names], bool)
        self.host, self.max_scan = host, max_scan
        self.b_idx = np.flatnonzero(bench)
        self.b_idx = self.b_idx[np.argsort(host.start[self.b_idx],
                                           kind="stable")]
        self.o_idx = np.flatnonzero(~bench & np.array(
            [n != WINDOW_SPAN for n in host.names], bool))
        self.o_idx = self.o_idx[np.argsort(host.start[self.o_idx],
                                           kind="stable")]
        self.b_start = host.start[self.b_idx]
        self.o_start = host.start[self.o_idx]

    def at(self, t: float) -> str:
        h = self.host
        i = int(np.searchsorted(self.b_start, t, side="right")) - 1
        bench = (h.names[self.b_idx[i]]
                 if i >= 0 and h.end[self.b_idx[i]] > t
                 else "outside bench spans")
        j = int(np.searchsorted(self.o_start, t, side="right")) - 1
        for k in self.o_idx[max(0, j - self.max_scan + 1):j + 1][::-1]:
            if h.end[k] > t:
                return f"{bench} > {h.names[k]}"
        return bench


def reduce(trace: Trace) -> dict:
    """Busy and idle time, launches and top operations in the window.

    Busy is the union of the intervals in which an operation ran on a
    device, averaged over the devices that ran any; launches are program
    executions, averaged the same way.  ``idle_gaps`` attributes each gap
    in the first device's busy time to the host activity at its midpoint.
    """
    if trace.host is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w = trace.host.names.index(WINDOW_SPAN)
    ws, we = float(trace.host.start[w]), float(trace.host.end[w])
    busy, launches, gaps = [], [], None
    op_time: Dict[str, float] = defaultdict(float)
    for dev in sorted(trace.devices):
        ops = trace.devices[dev][OPS_LINE]
        s = np.clip(ops.start, ws, we)
        e = np.clip(ops.end, ws, we)
        inside = e > s
        if not inside.any():
            continue
        us, ue = _union(s[inside], e[inside])
        busy.append(float(np.sum(ue - us)))
        mods = trace.devices[dev].get(MODULES_LINE)
        n_launch = 0
        op_mod = np.full(ops.start.shape[0], -1)
        if mods is not None and mods.names:
            n_launch = int(np.sum((mods.start >= ws) & (mods.start < we)))
            morder = np.argsort(mods.start, kind="stable")
            mi = np.searchsorted(mods.start[morder], ops.start, "right") - 1
            op_mod = np.where(mi >= 0, morder[np.maximum(mi, 0)], -1)
        launches.append(n_launch)
        _add_op_time(op_time, ops, mods, op_mod, inside, e - s)
        if gaps is None:
            gs = np.concatenate([[ws], ue])
            ge = np.concatenate([us, [we]])
            keep = ge > gs
            gaps = (gs[keep], ge[keep])
    if not busy:
        return dict(window_s=(we - ws) / 1e9, busy_s=0.0, chips=0,
                    launches=0, device_ops=[], idle_gaps=[])
    idle: Dict[str, float] = defaultdict(float)
    spans = _HostSpans(trace.host)
    for gs, ge in zip(*gaps):
        idle[spans.at((gs + ge) / 2)] += float(ge - gs)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP_N]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP_N]
    return dict(window_s=(we - ws) / 1e9,
                busy_s=float(np.mean(busy)) / 1e9,
                chips=len(busy),
                launches=float(np.mean(launches)),
                device_ops=[[k, float(v) / 1e9] for k, v in top],
                idle_gaps=[[k, float(v) / 1e9] for k, v in top_idle])
