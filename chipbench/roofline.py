"""A kernel's share of its roofline over the traced steps.

For every call the least time the chip could take is the larger of its
FLOPs over the peak FLOP/s and its needed bytes over the HBM bandwidth
(``work/<kernel>.py``, ``peaks.json``).  The share is the sum of those
least times over the device time of the kernel's events in the trace.
A kernel with no event in the trace has no share.
"""
from __future__ import annotations

import spec


def share(run, kernel: str) -> float | None:
    if run.trace is None:
        return None
    seen = run.trace["kernels"].get(kernel)
    if not seen or seen["events"] == 0 or seen["s"] <= 0:
        return None
    work = spec.work(kernel)
    least = sum(max(f / run.peaks["bf16_flops"],
                    b / run.peaks["hbm_bytes_per_s"])
                for st in run.traced_steps for f, b in work.step(run.conf, st))
    return 100.0 * least / seen["s"]
