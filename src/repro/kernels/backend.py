"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

This is the one place the package decides interpret mode.  Every kernel
entry point asks :func:`interpret_mode` when it is called and hands the
answer to its jitted body as a static argument, so a trace made for one
mode is never reused for the other.  On a TPU backend every kernel
lowers through Mosaic; on the CPU (the test suite, tiny-size rehearsals)
the same kernels run in Pallas interpret mode.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


__all__ = ["interpret_mode"]
