"""CPU rehearsal of every cell: run.py's own functions at a tiny size,
Pallas in interpret mode, the profiler's CPU planes standing in for the
device's.  Also the faults the check has to catch, planted under a
sound run: a token altered where it is produced, and a step that
returns its state unchanged."""
import json
import re

import numpy as np
import pytest

import program
import run
import spec
import tracing
from test_result_line import check_line

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "num_hidden_layers": 2, "vocab_size": 512,
        "max_position_embeddings": 64, "serving": {"slots": 4, "max_seq": 64}}
MIX = {"clients": 4,
       "prompt_tokens": {"dist": "log_uniform", "min": 2, "max": 4},
       "output_tokens": {"dist": "log_uniform", "min": 2, "max": 8}}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
SEED = 2 ** 33 + 17


def tiny_cell(workload):
    """The cell at the tiny size, with the rehearsal's traffic."""
    bench = spec.benchmark()
    wl = spec.workload(bench, workload)
    conf = spec.config(bench, wl["config"])
    conf = {**conf, **TINY, "serving": {**conf["serving"], **TINY["serving"]}}
    return bench, wl, conf, {**spec.traffic(wl["traffic"]), **MIX}


def cpu_devices(chips):
    import jax

    return jax.devices()


@pytest.fixture(autouse=True)
def tiny(monkeypatch):
    """Every run of this module at the tiny size on the CPU, with no
    compile cache."""
    monkeypatch.setattr(run, "cell", tiny_cell)
    monkeypatch.setattr(run, "device", cpu_devices)
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    monkeypatch.setattr(spec, "peaks", lambda kind: PEAKS)


def rehearse(cell, trace=False, seconds=2.0):
    return run.run_cell(cell, SEED, seconds, trace)


@pytest.fixture
def cpu_planes(monkeypatch):
    """The CPU backend's operations run on host threads."""
    monkeypatch.setattr(tracing, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    monkeypatch.setattr(tracing, "DEVICE_LINE", re.compile(r"^tf_XLA"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    r = rehearse(cell)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {m["name"] for m in
                                 spec.end_to_end(spec.benchmark(), cell)}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["attempted"] >= MIX["clients"] and r["failed"] == 0
    assert list(r)[-1] == "checks"
    check_line(json.dumps(r, allow_nan=False), traced=False)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_cell_reports_its_layer_metrics(cell, cpu_planes):
    r = rehearse(cell, trace=True, seconds=run.TRACE_SECONDS + 1)
    assert r["correct"], r["checks"]
    want = {m["name"] for m in spec.per_layer(spec.benchmark(), cell)}
    # interpret mode runs no kernel under its own name: no roofline here
    assert set(r["metrics"]) == {m for m in want
                                 if not m.endswith("_roofline")}
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert r["metrics"]["window_compiles"]["value"] == 0
    assert len(r["breakdown"]["device_ops"]) <= 10
    check_line(json.dumps(r, allow_nan=False), traced=True)


def _faulty(monkeypatch, fault):
    make = program.fresh_engine

    def fresh(adapter, config):
        eng = make(adapter, config)
        if fault == "token":
            # the sampled token is altered where it is produced
            eng.sampler = lambda row, req: (int(np.argmax(row)) + 1) \
                % len(row)
        else:
            step = adapter.step

            def unchanged(state, tokens, active):
                logits, _ = step(state, tokens, active)
                return logits, state
            # the step returns its state unchanged
            eng.adapter = type("Stuck", (), {
                "step": staticmethod(unchanged),
                "init_state": adapter.init_state,
                "reset_slot": adapter.reset_slot,
                "uploader": getattr(adapter, "uploader", None)})()
        return eng

    monkeypatch.setattr(program, "fresh_engine", fresh)


@pytest.mark.parametrize("fault", ["token", "state"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    _faulty(monkeypatch, fault)
    r = rehearse(cell)
    assert not r["correct"], r["checks"]


def test_no_chip_no_result(capsys, monkeypatch):
    monkeypatch.undo()                  # the real entry, on the CPU
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"]) == 1
    assert capsys.readouterr().out == ""
