"""Program spans on the profiler's clock.

The serving path marks where its host time goes with ``jax.profiler``
annotations (TraceMe events): one :func:`step_span` per engine step and
one :func:`span` around each part of it.  They cost a sub-microsecond
check while no profiler session is active, and under
``jax.profiler.trace`` they land on the host line of the device trace,
so an idle gap on the device can be named by the part of the program
that was running.

Span names are a fixed vocabulary prefixed ``repro.``:

==========================  ==============================================
``repro.engine.step``       one admit -> retire cycle (:meth:`Engine.step`)
``repro.engine.<stage>``    each stage and its hooks (admit, prefill,
                            decode, retire)
``repro.adapter.step``      an adapter's decode call
``repro.adapter.logits``    the pull of the step's logits to the host
``repro.model.embed``       the embedding and the rows' positions
``repro.layer.qkv``         norm1, the q/k/v matmuls and rope
``repro.layer.kv_write``    the cache write (scatter or packed append)
``repro.layer.attend``      attention, ``wo`` and the residual
``repro.layer.mlp``         norm2, the MLP and the residual
``repro.model.head``        the final norm and the logits matmul
``repro.model.state``       the cache re-stack and the clock update
``repro.stream.wait``       the decode step blocked on a weight upload
``repro.stream.upload``     one layer's upload (the uploader's thread)
==========================  ==============================================

A layer index is a stat of the span (``layer=3``), never part of its
name.  No ``jax.named_scope`` here: on the eager path its name stack
could enter the cache keys of eager operations.
"""
from __future__ import annotations

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["span", "step_span"]


def span(name: str, **stats) -> TraceAnnotation:
    """A host span named ``name`` carrying ``stats`` (``with span(...)``)."""
    return TraceAnnotation(name, **stats)


def step_span(step_num: int) -> StepTraceAnnotation:
    """The ``repro.engine.step`` span of engine step ``step_num``."""
    return StepTraceAnnotation("repro.engine.step", step_num=step_num)
