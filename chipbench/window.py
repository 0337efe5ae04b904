"""The measured window: host timestamps of every engine stage, the
decode call, and the compilations and garbage-collection pauses inside
the window.

:class:`StepLog` wraps the stages of one :class:`repro.engine.Engine`
and its adapter's ``step`` from outside (instance attributes; nothing of
the program is edited).  Each wrapper writes a ``jax.profiler``
``TraceAnnotation`` named ``bench.<stage>`` (``bench.adapter.step``
around the decode call, ``bench.step`` around a whole engine step), so
that a traced run puts the host's spans on the device trace's clock.
"""
from __future__ import annotations

import dataclasses
import gc
import time

#: the event JAX records around every XLA compilation (a compile or a
#: load from the persistent cache)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
STAGES = ("admit", "prefill", "decode", "retire")


@dataclasses.dataclass
class Step:
    start: float
    end: float = 0.0
    decode_s: float = 0.0
    #: positions written by this step, one per active slot
    positions: tuple[int, ...] = ()

    @property
    def rows(self) -> int:
        return len(self.positions)


class CompileCounter:
    """Counts XLA compilations while active."""

    def __init__(self) -> None:
        self.count = 0
        self.active = False

    def _listen(self, event: str, duration: float, **_kw) -> None:
        if self.active and event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self.active = True
        return self

    def __exit__(self, *exc) -> None:
        import jax

        self.active = False
        jax.monitoring.unregister_event_duration_listener(self._listen)


class GcPauses:
    """The interpreter's garbage-collection pauses while active:
    ``pauses`` holds (generation, seconds) of each."""

    def __init__(self, clock=time.perf_counter) -> None:
        self.clock = clock
        self.pauses: list[tuple[int, float]] = []
        self._start = 0.0

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = self.clock()
        else:
            self.pauses.append((info["generation"],
                                self.clock() - self._start))

    def __enter__(self) -> "GcPauses":
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._callback)


class StepLog:
    """Times every stage of ``engine``'s steps; ``steps`` holds one
    :class:`Step` per engine step taken while installed."""

    def __init__(self, engine, clock=time.perf_counter) -> None:
        self.engine = engine
        self.clock = clock
        self.steps: list[Step] = []
        self._adapter = engine.adapter
        self._adapter_step = engine.adapter.step
        for stage in STAGES:
            setattr(engine, f"_stage_{stage}",
                    self._wrap_stage(stage, getattr(engine,
                                                    f"_stage_{stage}")))
        self._adapter.step = self._wrapped_adapter_step

    def _wrap_stage(self, stage: str, fn):
        from jax.profiler import TraceAnnotation

        def stage_fn(ctx):
            if stage == "admit":
                self.steps.append(Step(start=self.clock()))
            if stage == "decode":
                eng = self.engine
                self.steps[-1].positions = tuple(
                    int(eng.slot_pos[i]) for i in ctx["active"])
            with TraceAnnotation(f"bench.{stage}"):
                return fn(ctx)

        return stage_fn

    def _wrapped_adapter_step(self, *args, **kw):
        from jax.profiler import TraceAnnotation

        t0 = self.clock()
        with TraceAnnotation("bench.adapter.step"):
            out = self._adapter_step(*args, **kw)
        self.steps[-1].decode_s += self.clock() - t0
        return out

    def step(self) -> Step:
        """One engine step, annotated as ``bench.step``."""
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.step"):
            self.engine.step()
        st = self.steps[-1]
        st.end = self.clock()
        return st

    def remove(self) -> None:
        """Uninstall the adapter wrapper (the adapter outlives the
        engine)."""
        del self._adapter.step
