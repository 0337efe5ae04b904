"""Reduce a ``jax.profiler`` trace to device busy time, kernel time and
idle gaps attributed to what the host was doing.

:func:`extract` reads the ``.xplane.pb`` file of a trace into plain
lists (``[name, start_ns, duration_ns]``): the operations of each
device (the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane, one
event per HLO instruction, named by its text, e.g. ``%packed_matmul_call.1
= f32[64,576] custom-call(...)``), the programs each device ran (its
``XLA Modules`` line, e.g. ``jit_scatter(<hash>)``), and the host events
of the thread that carries the benchmark's ``bench.*`` annotations.  :func:`reduce` works on those lists alone, so a small
recorded trace can stand in for a chip in the tests.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINE = re.compile(r"^XLA Ops$")
MODULE_LINE = re.compile(r"^XLA Modules$")
HOST_PLANE = "/host:CPU"
STEP = "bench.step"
#: the longest operation or span name that :func:`top` gives
NAME_CHARS = 100


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def extract(path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "modules": {plane: [...]}, "host": [[name, start_ns, dur_ns], ...]}``
    of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    modules: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                for pattern, out in ((DEVICE_LINE, devices),
                                     (MODULE_LINE, modules)):
                    if pattern.match(line.name):
                        out.setdefault(plane.name, []).extend(
                            [e.name, e.start_ns, e.duration_ns]
                            for e in line.events)
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events]
                if any(e[0] == STEP for e in events):
                    host.extend(events)
    return {"devices": devices, "modules": modules, "host": host}


def _program(name: str) -> str:
    """``jit_scatter(10863513692808818614)`` -> ``jit_scatter``."""
    return re.sub(r"\(\d+\)$", "", name)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _innermost(spans: list, gaps: list) -> list[str]:
    """For each gap (in time order), the name of the innermost host span
    open at its middle; host spans of one thread nest."""
    out, stack, i = [], [], 0
    for gs, ge in gaps:
        mid = (gs + ge) / 2
        while i < len(spans) and spans[i][1] <= mid:
            while stack and stack[-1][1] + stack[-1][2] < spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] + stack[-1][2] < mid:
            stack.pop()
        out.append(stack[-1][0] if stack else "(no host span)")
    return out


def reduce(trace: dict, kernels: dict[str, str] | None = None) -> dict:
    """Busy time, kernel time and idle gaps over the traced window.

    The window runs from the start of the first ``bench.step`` host span
    to the end of the last.  Device time is clipped to it.  Returns
    seconds: ``window_s``; ``busy_s`` (union of operation intervals,
    averaged over devices); ``ops`` (device time per operation name,
    all devices); ``kernels`` (device time and event count of every
    operation whose name matches each pattern of ``kernels``);
    ``idle_by_span`` (device idle time of each gap, attributed to the
    innermost host span that covers the gap's middle, by span name)."""
    steps = [e for e in trace["host"] if e[0] == STEP]
    if not steps or not trace["devices"]:
        raise ValueError("trace holds no bench.step span or no device")
    t0 = min(e[1] for e in steps)
    t1 = max(e[1] + e[2] for e in steps)
    window = t1 - t0
    spans = sorted(trace["host"], key=lambda e: (e[1], -e[2]))
    busy_total = 0.0
    ops: dict[str, float] = {}
    for events in trace.get("modules", {}).values():
        for name, s, d in events:
            s2, e2 = max(s, t0), min(s + d, t1)
            if e2 > s2:
                key = _program(name)
                ops[key] = ops.get(key, 0.0) + (e2 - s2)
    idle: dict[str, float] = {}
    compiled = {k: re.compile(p) for k, p in (kernels or {}).items()}
    kern = {k: [0.0, 0] for k in compiled}
    for events in trace["devices"].values():
        clipped = []
        for name, s, d in events:
            s2, e2 = max(s, t0), min(s + d, t1)
            if e2 <= s2:
                continue
            clipped.append((s2, e2))
            for k, pat in compiled.items():
                if pat.search(name):
                    kern[k][0] += e2 - s2
                    kern[k][1] += 1
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                if ge > gs]
        for (gs, ge), label in zip(gaps, _innermost(spans, gaps)):
            idle[label] = idle.get(label, 0.0) + (ge - gs)
    n_dev = len(trace["devices"])
    ns = 1e-9
    return {
        "window_s": window * ns,
        "busy_s": busy_total / n_dev * ns,
        "ops": {k: v * ns for k, v in ops.items()},
        "kernels": {k: {"s": v[0] * ns, "events": v[1]}
                    for k, v in kern.items()},
        "idle_by_span": {k: v * ns for k, v in idle.items()},
        "steps": len(steps),
    }


def top(d: dict[str, float], n: int = 10) -> list[list]:
    """The ``n`` largest entries of ``d`` as ``[name, value]``, largest
    first, each name cut to ``NAME_CHARS``."""
    return [[k[:NAME_CHARS], v]
            for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
