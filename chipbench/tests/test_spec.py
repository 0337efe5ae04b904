"""Every entry of BENCHMARK.json is found by name in its own file, and
the file keeps to the benchmark's format."""
import json
import re

import pytest

import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_found_by_name(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    conf = spec.config(BENCH, entry["name"])
    assert conf["name"] == entry["name"]
    assert conf["source"] == entry["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert key in conf
        assert not re.search(r"(size|_dim|_rank|heads|experts)", key), key
    spec.reference(conf["reference"])
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda e: e["name"])
def test_workload_found_by_name(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(wl["name"]) and wl["chips"] == 1
    assert len(wl["why"]) <= 200
    spec.config(BENCH, wl["config"])
    mix = spec.traffic(wl["traffic"])
    assert mix["name"] == wl["traffic"]
    lim = json.loads((spec.HERE / "limits" / f"{wl['name']}.json")
                     .read_text())
    assert "max_logit_gap" in lim
    assert spec.per_layer(BENCH, wl["name"])
    reported = {m["name"] for m in spec.end_to_end(BENCH, wl["name"])}
    assert "setup_s" in reported and len(reported) >= 2
    for m in spec.per_layer(BENCH, wl["name"]):
        assert m["moves"] in reported


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda e: e["name"])
def test_every_request_fits_the_cache(wl):
    """No request reaches the cache's end, where the engine would cut
    it short: the longest prompt plus the longest output fits."""
    conf = spec.config(BENCH, wl["config"])
    mix = spec.traffic(wl["traffic"])
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest <= conf["serving"]["max_seq"]
    assert conf["serving"]["max_seq"] == conf["max_position_embeddings"]


def test_cells_are_unique_pairs():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) <= {"name", "unit", "better", "bound", "source",
                      "workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_found_by_name(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["source"] in SOURCES and m["better"] in ("lower", "higher")
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert callable(spec.metric(m["name"]).read)
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"
    if m["name"].endswith("_roofline"):
        work = spec.work(m["name"][:-len("_roofline")])
        assert re.compile(work.TRACE_NAME) and callable(work.step)
    for cell in m.get("workloads", []):
        spec.workload(BENCH, cell)


def test_layers_named_alike():
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_unknown_device_has_no_peaks():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")
