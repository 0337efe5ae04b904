"""The result line: strict JSON within ``run.LINE_BYTES``, of one size
whatever the number of steps, requests or traced events."""
import json
import math

import pytest

import check
import run
import spec
import tracing
from window import Step

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
#: the deepest line before the window was bounded: result ->
#: breakdown -> device_ops -> [name, seconds]
DEPTH = 4
LIST_MAX = 16
STEPS = 20_000
STALLS = (1_000, 9_999, 17_777)


def _refuse(constant):
    raise ValueError(f"{constant} is not strict JSON")


def _once(pairs):
    keys = [k for k, _ in pairs]
    if len(keys) != len(set(keys)):
        raise ValueError(f"a key given twice among {keys}")
    return dict(pairs)


def depth(x) -> int:
    if isinstance(x, dict):
        return 1 + max(map(depth, x.values()), default=0)
    if isinstance(x, list):
        return 1 + max(map(depth, x), default=0)
    return 0


def lists(x):
    if isinstance(x, dict):
        for v in x.values():
            yield from lists(v)
    elif isinstance(x, list):
        yield x
        for v in x:
            yield from lists(v)


def check_line(line: str, traced: bool) -> dict:
    """The driver's reading of a result line; returns the object."""
    assert len(line.encode()) < run.LINE_BYTES
    r = json.loads(line, parse_constant=_refuse, object_pairs_hook=_once)
    assert isinstance(r, dict)
    assert depth(r) <= DEPTH
    assert all(len(x) <= LIST_MAX for x in lists(r))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(r)
    assert list(r)[-1] == "checks"
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], (int, float))
    dev = r["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if traced:
        assert 0 < dev["busy_s"] <= dev["window_s"]
    return r


def fast_window(n=STEPS, stalls=STALLS) -> run.Run:
    """``n`` steps of 12-13 ms (decode 5-6 ms) with 2 s stalls at
    ``stalls``, and a garbage-collection pause every 40 steps."""
    steps, t = [], 0.0
    for i in range(n):
        s = 2.0 if i in stalls else 0.012 + 1e-3 * (i % 7) / 7
        steps.append(Step(start=t, end=t + s, decode_s=s - 0.007))
        t += s
    return run.Run(steps=steps, compiles=0,
                   gc_pauses=[(i % 3, 1e-4) for i in range(n // 40)])


def fast_result(cell: str, traced: bool, value=123.45678901234567) -> dict:
    """A result as ``run_cell`` builds it, over :func:`fast_window`: every
    metric of the cell, and a breakdown from thousands of long names."""
    bench = spec.benchmark()
    names = spec.per_layer(bench, cell) if traced \
        else spec.end_to_end(bench, cell)
    result = {"correct": True, "attempted": 25_000, "failed": 0,
              "metrics": {m["name"]: {"value": value, "unit": m["unit"]}
                          for m in names}}
    info = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
            "memory_peak_bytes": 1_182_140_928}
    if traced:
        info["busy_s"], info["window_s"] = 0.0785123456789, 6.6012345678
        many = {f"PjitFunction({'x' * 300}_{i})": 1e-3 / (i + 1)
                for i in range(5_000)}
        result["breakdown"] = {"device_ops": tracing.top(many),
                               "idle_gaps": tracing.top(many)}
    result["device"] = info
    result["window"] = run.window_summary(fast_window())
    numbers = {"max_logit_gap": 0.03979206085205078,
               "served_tokens_compared": 1240, "valid_ids": True}
    result["checks"] = check.judge(numbers, check.limits(cell))[1]
    return result


def test_window_summary_is_bounded_and_finds_the_stalls():
    w = run.window_summary(fast_window())
    assert w["steps"] == STEPS
    assert all(len(x) <= LIST_MAX for x in lists(w))
    assert w["slow_steps"] == len(STALLS)
    slowest = [i for i, _ms, _dec in w["slowest_steps"]]
    assert len(slowest) == run.SLOWEST
    assert set(STALLS) == set(slowest[:len(STALLS)])
    assert w["slowest_step"] in STALLS
    assert w["step_ms_max"] == pytest.approx(2000.0)
    assert w["slowest_steps"][0] == [w["slowest_step"], w["step_ms_max"],
                                     w["decode_ms_of_slowest"]]
    assert len(w["first_step_ms"]) == run.FIRST_STEPS
    assert 12 <= w["step_ms_median"] <= w["step_ms_p90"] \
        <= w["step_ms_p99"] < 13
    assert w["gc_pauses"] == STEPS // 40


def test_window_summary_of_a_short_window():
    w = run.window_summary(fast_window(n=3, stalls=(1,)))
    assert w["steps"] == 3 and w["slowest_step"] == 1
    assert len(w["slowest_steps"]) == 3 and len(w["first_step_ms"]) == 3


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_main_prints_a_bounded_strict_line(cell, traced, monkeypatch,
                                           capsys):
    result = fast_result(cell, traced)
    monkeypatch.setattr(run, "run_cell", lambda *a: result)
    assert run.main(["--workload", cell, "--seed", str(2 ** 33 + 5),
                     "--seconds", "51", "--trace", str(int(traced))]) == 0
    out, err = capsys.readouterr()
    r = check_line(out.splitlines()[-1], traced)
    assert r == result
    window = [x for x in err.splitlines() if x.startswith("window: ")]
    assert [json.loads(x[len("window: "):]) for x in window] \
        == [result["window"]]


def test_names_in_the_breakdown_are_cut():
    r = fast_result(CELLS[0], traced=True)
    names = [n for part in r["breakdown"].values() for n, _s in part]
    assert len(names) == 20
    assert all(len(n) == tracing.NAME_CHARS for n in names)


@pytest.mark.parametrize("bad", [math.nan, math.inf])
def test_a_result_that_is_not_strict_json_prints_no_line(bad, monkeypatch,
                                                         capsys):
    result = fast_result(CELLS[0], traced=False, value=bad)
    monkeypatch.setattr(run, "run_cell", lambda *a: result)
    with pytest.raises(ValueError):
        run.main(["--workload", CELLS[0], "--seed", "3", "--seconds", "51",
                  "--trace", "0"])
    assert capsys.readouterr().out == ""
