"""Trace reduction on a hand-made trace and on one recorded on the chip."""
import json
from pathlib import Path

import pytest

import tracing

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    # two steps of 100 ns; device ops [10, 30) and [20, 40) overlap,
    # [150, 190) in the second step; host spans nest
    return {
        "devices": {"/device:TPU:0": [["packed_matmul.1", 10, 20],
                                      ["fusion.2", 20, 20],
                                      ["stream_attention", 150, 40],
                                      ["outside", 250, 10]]},
        "host": [["bench.step", 0, 100], ["bench.adapter.step", 6, 90],
                 ["PjitFunction(x)", 45, 10],
                 ["bench.step", 100, 100], ["bench.retire", 190, 10]],
    }


def test_busy_union_idle_and_kernels():
    r = tracing.reduce(synthetic(), {"packed_matmul": r"packed_matmul",
                                     "stream_attention": r"stream_attention",
                                     "stream_matmul": r"stream_matmul"})
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx(70e-9)        # 30 + 40, overlap once
    assert r["kernels"]["packed_matmul"] == {"s": pytest.approx(20e-9),
                                             "events": 1}
    assert r["kernels"]["stream_matmul"]["events"] == 0
    idle = r["idle_by_span"]
    # [0,10): mid 5 before the adapter span; [40,150): mid 95 inside the
    # adapter span [6, 96); [190,200): bench.retire
    assert idle["bench.step"] == pytest.approx(10e-9)
    assert idle["bench.adapter.step"] == pytest.approx(110e-9)
    assert idle["bench.retire"] == pytest.approx(10e-9)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert "outside" not in r["ops"]


def test_innermost_span():
    spans = sorted(synthetic()["host"], key=lambda e: (e[1], -e[2]))
    assert tracing._innermost(spans, [(44, 56), (60, 70), (97, 99)]) == [
        "PjitFunction(x)", "bench.adapter.step", "bench.step"]


def test_top():
    assert tracing.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                              ["c", 2.0]]


RECORDED = sorted(DATA.glob("trace-*.json"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace(path):
    rec = json.loads(path.read_text())
    r = tracing.reduce(rec["trace"], rec["kernels"])
    exp = rec["expected"]
    assert r["steps"] == exp["steps"]
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] == pytest.approx(exp["window_s"])
    assert r["busy_s"] == pytest.approx(exp["busy_s"])
    for k, n in exp["kernel_events"].items():
        assert r["kernels"][k]["events"] == n
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
