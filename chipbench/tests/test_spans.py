"""The readers of the program's spans on a hand-made trace and on one
recorded on the chip."""
import json
import types
from pathlib import Path

import pytest

import spec

DATA = Path(__file__).resolve().parent / "data"
READERS = ("decode_launches", "logits_wait_ms", "engine_idle_share",
           "stream_wait_ms")


def _launch(name, s, d):
    """A JAX launch: its host event and the nested twin of the same name."""
    return [[name, s, d], [name, s + 1, d - 2]]


def synthetic():
    # two engine steps of 1000 ns; the decode call [200, 850) launches
    # a (with a put inside), b and a standalone put, waits 50 ns on a
    # stream and 50 ns on the logits; c launches in retire, outside it;
    # the second decode call [1200, 1800) launches d
    host = [["bench.step", 0, 1000], ["repro.engine.step", 0, 1000],
            ["repro.engine.admit", 0, 100], ["repro.engine.prefill", 100, 50],
            ["repro.engine.decode", 150, 750],
            ["repro.adapter.step", 200, 650],
            *_launch("PjitFunction(a)", 210, 50), ["DevicePut", 220, 10],
            *_launch("PjitFunction(b)", 300, 20),
            ["DevicePutWithSharding", 330, 10],
            ["repro.stream.wait", 400, 50],
            ["repro.adapter.logits", 800, 50],
            ["repro.engine.retire", 900, 100],
            *_launch("PjitFunction(c)", 950, 10),
            ["bench.step", 1000, 1000], ["repro.engine.step", 1000, 1000],
            ["repro.engine.decode", 1100, 800],
            ["repro.adapter.step", 1200, 600],
            ["repro.stream.wait", 1250, 10],
            *_launch("PjitFunction(d)", 1300, 10),
            ["repro.adapter.logits", 1700, 80]]
    return {"devices": {"/device:TPU:0": [["fusion.1", 240, 260],
                                          ["fusion.2", 1750, 40],
                                          ["outside", 2500, 10]]},
            "modules": {}, "host": host}


def _run(trace):
    return types.SimpleNamespace(trace_events=trace)


def read(name, trace):
    return spec.metric(name).read(_run(trace))


def test_known_answers():
    t = synthetic()
    # a, the put inside it, b and the standalone put; then d: 5 / 2
    assert read("decode_launches", t) == 2.5
    assert read("logits_wait_ms", t) == pytest.approx((50 + 80) / 2 * 1e-6)
    assert read("stream_wait_ms", t) == pytest.approx((50 + 10) / 2 * 1e-6)
    # device busy or host in the decode call: [200, 850) and [1200, 1800)
    # of [0, 2000)
    assert read("engine_idle_share", t) == pytest.approx(
        100 * (2000 - 650 - 600) / 2000)


def test_two_devices_average():
    t = synthetic()
    t["devices"]["/device:TPU:1"] = [["fusion.3", 0, 2000]]
    assert read("engine_idle_share", t) == pytest.approx(
        100 * (2000 - 650 - 600) / 2000 / 2)


@pytest.mark.parametrize("name", READERS)
def test_no_program_spans_no_reading(name):
    t = synthetic()
    bare = {**t, "host": [e for e in t["host"]
                          if e[0] != "repro.engine.step"]}
    assert read(name, bare) is None
    assert read(name, None) is None


def test_no_stream_wait_reads_zero():
    t = synthetic()
    t["host"] = [e for e in t["host"] if e[0] != "repro.stream.wait"]
    assert read("stream_wait_ms", t) == 0.0


def test_recorded_trace_b():
    """A chip trace that carries program spans (its expected readings
    were taken by these readers when it was recorded)."""
    rec = json.loads((DATA / "trace-b.json").read_text())
    want = rec["expected"]["spans"]
    assert set(want) == set(READERS)
    for name in READERS:
        got = read(name, rec["trace"])
        assert got == pytest.approx(want[name]), name
    assert want["decode_launches"] > 0
    assert 0 < want["engine_idle_share"] < 100
    assert 0 < want["logits_wait_ms"]
