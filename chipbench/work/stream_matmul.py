"""stream_matmul (``repro/kernels/stream_matmul.py``): the work of the
stream-direct weight matmuls of one engine step.

The same work as the lane-packed matmul: the weights at their element
width plus their scales, activations in and out.  The per-element
offset tables the kernel reads today are not work and are not counted.
"""
from __future__ import annotations

import spec

_LANE_PACKED = spec.work("packed_matmul")
matmul = _LANE_PACKED.matmul
step = _LANE_PACKED.step

TRACE_NAME = r"^%stream_matmul_call(\.\d+)? = .*custom-call"
