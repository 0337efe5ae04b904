"""packed_matmul (``repro/kernels/packed_matmul.py``): the work of the
lane-packed weight matmuls of one engine step.

Per layer, each of the seven matrices is multiplied once by the step's
``M`` rows.  Needed bytes are the work, whatever implements it: the
weights at their element width, one bfloat16 scale per group of
``group_size`` rows, and the activations in and out at bfloat16 (the
configuration's activation type).
"""
from __future__ import annotations

from weights import shapes

#: the kernel's HLO instruction in the trace's device operations: the
#: custom call named after the jitted launch, e.g. ``%packed_matmul_call.1
#: = f32[64,576] custom-call(...)``
TRACE_NAME = r"^%packed_matmul_call(\.\d+)? = .*custom-call"


def matmul(m: int, k: int, n: int, bits: int, group: int
           ) -> tuple[float, float]:
    """(FLOPs, needed bytes) of ``(m, k) @ (k, n)`` with ``bits``-wide
    weights and ``group``-row bfloat16 scales."""
    flops = 2.0 * m * k * n
    needed = k * n * bits / 8 + (k // group) * n * 2 + (m * k + m * n) * 2
    return flops, needed


def step(conf: dict, st) -> list[tuple[float, float]]:
    """(FLOPs, needed bytes) of every weight matmul of one engine step
    with ``st.rows`` rows."""
    sv = conf["serving"]
    per_layer = [matmul(st.rows, k, n, sv["weight_bits"], sv["group_size"])
                 for k, n in shapes(conf).values()]
    return per_layer * conf["num_hidden_layers"]
