"""The program's own spans in a traced run's host events.

``repro.engine.trace`` writes ``repro.*`` spans into the profiler trace
(``repro.engine.step`` around every engine step, ``repro.adapter.step``
around its decode call, and so on); ``tracing.extract`` keeps them with
the other events of the engine's thread.  The per-layer metrics that
read them share this module.  Each reading is None when the trace holds
no ``repro.engine.step`` span: a program that writes no such spans
reports nothing.
"""
from __future__ import annotations

import bisect

from tracing import _union

STEP = "repro.engine.step"
ADAPTER = "repro.adapter.step"
#: the host event of every jitted program JAX launches; each launch
#: holds a nested event of the same name, which is not counted again
LAUNCH = "PjitFunction("
#: the host events of a host->device put (``DevicePut``,
#: ``DevicePutWithSharding``)
PUT = "DevicePut"


def _host(run) -> list | None:
    """The traced run's host events, or None without program spans."""
    trace = run.trace_events
    if trace is None or not any(e[0] == STEP for e in trace["host"]):
        return None
    return trace["host"]


def mean_ms(run, name: str) -> float | None:
    """Host milliseconds in spans named ``name``, per traced engine
    step."""
    host = _host(run)
    if host is None:
        return None
    n_steps = sum(1 for e in host if e[0] == STEP)
    return 1e-6 * sum(d for n, _s, d in host if n == name) / n_steps


def launches(run) -> float | None:
    """Programs launched and host->device puts that start inside a
    ``repro.adapter.step`` span, per traced engine step."""
    host = _host(run)
    if host is None:
        return None
    # one thread's adapter spans follow one another: the one that can
    # hold a start is the last that begins at or before it
    adapter = sorted((s, s + d) for n, s, d in host if n == ADAPTER)
    starts = [a for a, _b in adapter]
    count, launch_end = 0, float("-inf")
    for name, s, d in sorted(host, key=lambda e: (e[1], -e[2])):
        if name.startswith(LAUNCH):
            if s < launch_end:
                continue                # the launch's nested twin
            launch_end = s + d
        elif not name.startswith(PUT):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < adapter[i][1]:
            count += 1
    return count / sum(1 for e in host if e[0] == STEP)


def engine_idle_share(run) -> float | None:
    """% of the window, the first ``repro.engine.step`` start to the
    last one's end, in which no operation runs on the device and the
    host is outside every ``repro.adapter.step`` span (averaged over
    the devices)."""
    host = _host(run)
    devices = run.trace_events["devices"] if host is not None else None
    if not devices:
        return None
    steps = [(s, s + d) for n, s, d in host if n == STEP]
    t0, t1 = min(s for s, _ in steps), max(e for _, e in steps)

    def clip(spans):
        return [(max(s, t0), min(e, t1)) for s, e in spans
                if min(e, t1) > max(s, t0)]

    adapter = clip((s, s + d) for n, s, d in host if n == ADAPTER)
    idle = 0.0
    for events in devices.values():
        busy = clip((s, s + d) for _n, s, d in events)
        idle += (t1 - t0) - sum(e - s for s, e in _union(busy + adapter))
    return 100.0 * idle / len(devices) / (t1 - t0)
