"""Find the benchmark's parts by name.

``BENCHMARK.json`` names cells, configurations, traffic mixes and
metrics; each lives in a file of its own under this directory:

* a configuration: the file its entry names (``configs/<name>.json``),
  whose ``reference`` key names its plain reference
  (``references/<reference>.py``);
* a traffic mix: ``traffic/<mix>.json``;
* a per-layer metric: ``metrics/<metric>.py``, a ``read(run)`` function;
* a kernel's operations and bytes: ``work/<kernel>.py``;
* the device peaks: ``peaks.json``, keyed by ``device_kind``.

Adding a cell, a mix, a metric or a kernel adds files and entries; no
code here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

#: this directory, and the checkout that holds it
HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(path: Path | None = None) -> dict:
    return _json(path or CHECKOUT / "BENCHMARK.json")


def _entry(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _entry(bench["workloads"], name, "workload")


def config(bench: dict, name: str) -> dict:
    entry = _entry(bench["configs"], name, "configuration")
    return _json(CHECKOUT / entry["file"])


def traffic(name: str) -> dict:
    return _json(HERE / "traffic" / f"{name}.json")


def _module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "chipbench_" + path.parent.name + "_" + \
        path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric(name: str):
    """The reader of per-layer metric ``name``: ``read(run) -> float |
    None``."""
    return _module(HERE / "metrics" / f"{name}.py")


def work(kernel: str):
    """Operations and needed bytes of ``kernel`` per engine step."""
    return _module(HERE / "work" / f"{kernel}.py")


def reference(name: str):
    return _module(HERE / "references" / f"{name}.py")


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in peaks.json; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def per_layer(bench: dict, workload_name: str) -> list[dict]:
    """The per-layer metrics that cell ``workload_name`` reports."""
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None or workload_name in cells:
            out.append(m)
    return out


def end_to_end(bench: dict, workload_name: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if workload_name in m.get("workloads", [workload_name])]
