"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e (``data/small.xplane.pb``, one shrunk CG cell run
with ``--trace 1``)."""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from bench import tracereduce as T

RECORDED = Path(__file__).parent / "data" / "small.xplane.pb"


def _line(events):
    names = [e[0] for e in events]
    s = np.array([e[1] for e in events], float)
    return T.Line(names, s, s + np.array([e[2] for e in events], float))


def _synthetic():
    ops = _line([("%a = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 10, 10),
                 ("%b = f32[8]{0} custom-call(f32[8]{0} %p), "
                  'custom_call_target="tpu_custom_call"', 15, 10),
                 ("%c = (f32[2]{0}, s32[]) while(%t), body=%w", 40, 5),
                 ("%a = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 90, 30)])
    mods = _line([("jit_step(123)", 10, 15), ("jit_other(9)", 40, 5),
                  ("jit_step(123)", 90, 30)])
    host = _line([(T.WINDOW_SPAN, 0, 100),
                  ("bench.run", 0, 50), ("PjitFunction(step)", 26, 10),
                  ("bench.result", 50, 40), ("np.asarray", 55, 5)])
    return T.Trace(devices={"/device:TPU:0": {T.OPS_LINE: ops,
                                              T.MODULES_LINE: mods}},
                   host=host)


def test_reduce_synthetic_window():
    r = T.reduce(_synthetic())
    # busy: [10, 25) and [40, 45) and [90, 100) clipped to the window
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["launches"] == 3 and r["chips"] == 1
    ops = dict(r["device_ops"])
    assert ops["jit_step/fusion"] == pytest.approx(20e-9)
    assert ops["jit_step/custom-call:tpu_custom_call"] == pytest.approx(10e-9)
    assert ops["jit_other/while"] == pytest.approx(5e-9)
    gaps = dict(r["idle_gaps"])
    # [0,10) and [25,40) under bench.run (the second inside the pjit call),
    # [45,90) under bench.result
    assert gaps["bench.run"] == pytest.approx(10e-9)
    assert gaps["bench.run > PjitFunction(step)"] == pytest.approx(15e-9)
    assert gaps["bench.result"] == pytest.approx(45e-9)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_reduce_without_window_span_is_an_error():
    t = _synthetic()
    t.host = _line([("bench.run", 0, 10)])
    with pytest.raises(ValueError):
        T.reduce(t)


def test_op_key():
    assert T.op_key("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %x)") == "fusion"
    assert T.op_key('%cc = u32[1]{0} custom-call(s64[1]{0} %i), '
                    'custom_call_target="X64SplitLow"') \
        == "custom-call:X64SplitLow"
    assert T.op_key("%t = (f32[2]{0}, s32[]) tuple(%a, %b)") == "tuple"


def _brute_force(path):
    """Busy time and launches in the window by a plain loop over events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    window = None
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == T.WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
    ws, we = window
    busy = launches = 0.0
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == T.MODULES_LINE:
                launches += sum(ws <= e.start_ns < we for e in line.events)
            if line.name != T.OPS_LINE:
                continue
            spans = sorted((max(e.start_ns, ws),
                            min(e.start_ns + e.duration_ns, we))
                           for e in line.events)
            end = ws
            for s, e in spans:
                if e <= s:
                    continue
                if s > end:
                    busy += e - s
                elif e > end:
                    busy += e - end
                end = max(end, e)
    return busy / 1e9, launches, (we - ws) / 1e9


def test_reduce_recorded_tpu_trace():
    r = T.reduce(T.load(str(RECORDED)))
    busy, launches, window = _brute_force(RECORDED)
    assert r["chips"] == 1
    assert r["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert r["launches"] == launches > 0
    assert r["window_s"] == pytest.approx(window, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert len(r["device_ops"]) <= T.TOP_N
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert sum(t for _, t in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] \
        + 1e-12
    assert all(name.startswith("bench.") for name, _ in r["idle_gaps"])
