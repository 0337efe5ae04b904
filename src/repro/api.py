"""One front door for the Iris layout pipeline.

The paper's pitch is that Iris *automates* the layout workflow; this
module is that workflow as a single call.  :func:`plan` turns a
:class:`~repro.core.task.LayoutProblem` into a lazy :class:`Plan` that
carries the schedule, metrics, decode program and packed buffers behind
one uniform surface:

    import repro.api as iris

    p = iris.plan(iris.PAPER_EXAMPLE)            # strategy="iris"
    p.metrics.row()                              # C_max / L_max / B_eff
    buf = p.pack(codes)                          # host-side organization
    out = p.decode(buf, backend="pallas")        # accelerator-side read
    src = p.emit(target="c")                     # HLS read_data module

Two registries make the pipeline pluggable:

* **strategies** (:data:`STRATEGIES`) map a problem to a
  :class:`~repro.core.layout.Layout` — ``"iris"`` (the scheduler) plus
  the paper's baselines ``"naive"``, ``"homogeneous"``,
  ``"hls_padded"``.  Sweeps and comparisons iterate the registry
  (:func:`compare`) instead of importing one function per family.
* **backends** (:data:`BACKENDS`) execute a plan — ``"numpy"`` is the
  reference bit-gatherer, ``"pallas"`` the TPU kernel path (Pallas
  interpret mode on a non-TPU backend, :mod:`repro.kernels.backend`),
  ``"c"`` emits the paper's Listing 1/2 HLS source.
  ``plan.decode`` normalizes every backend's output to uint64 numpy
  arrays, so cross-backend equivalence is plain ``np.array_equal``.

Scheduling routes through the content-addressed
:class:`~repro.core.iris.LayoutCache` (the process-wide
``DEFAULT_CACHE``) by default: repeated problems — every layer of a
uniform stack, every repeated serving request — never re-run the
scheduler.  Only the ``"iris"`` strategy consults the cache; baselines
are closed-form and cheaper than a lookup.

Everything here is importable without JAX; the ``"pallas"`` backend
loads the kernel package on first use.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import numpy as np

from .core.baselines import ALL_BASELINES
from .core.codegen import (
    DecodePlan,
    decode_plan,
    emit_c_decode,
    emit_c_pack,
    pack_arrays,
    random_codes,
    unpack_arrays,
)
from .core.exec_plan import (
    ExecProgram,
    StreamTables,
    lower_exec,
    pack_compiled,
    stream_matmul_tables,
    unpack_compiled,
)
from .core.iris import DEFAULT_CACHE, LayoutCache, schedule, schedule_many
from .core.layout import Layout, LayoutMetrics
from .core.registry import Registry
from .core.task import (
    INV_HELMHOLTZ,
    PAPER_EXAMPLE,
    ArraySpec,
    LayoutProblem,
    make_problem,
    matmul_problem,
)

__all__ = [
    "ArraySpec", "LayoutProblem", "make_problem", "random_codes",
    "PAPER_EXAMPLE", "INV_HELMHOLTZ", "matmul_problem",
    "Backend", "Plan", "LayerStackPlan",
    "STRATEGIES", "BACKENDS", "strategies", "backends",
    "plan", "plan_many", "compare", "plan_layer_stack",
    "ExecProgram", "lower_exec", "pack_compiled", "unpack_compiled",
    "StreamTables", "stream_matmul_tables",
    # pytree-level front door (loads JAX lazily on first access)
    "PackedTree", "pack_tree", "unpack_streams", "LayoutManifest",
]

#: attributes served lazily from repro.tree so that ``import repro.api``
#: stays numpy-only; the PackedTree machinery needs JAX (pytree
#: registration, device placement)
_TREE_EXPORTS = ("PackedTree", "pack_tree", "unpack_streams",
                 "LayoutManifest")


def __getattr__(name: str):
    if name in _TREE_EXPORTS:
        from . import tree as _tree
        return getattr(_tree, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ----------------------------------------------------------------------
# strategy registry: name -> (problem, **knobs) -> Layout
# ----------------------------------------------------------------------
#: Layout strategies.  A strategy is ``fn(problem, *, mode,
#: fill_residual, cache) -> Layout``; closed-form baselines ignore the
#: scheduling knobs.
STRATEGIES: Registry[Callable[..., Layout]] = Registry("strategy")


def _register_baseline(name: str, fn: Callable[[LayoutProblem], Layout]):
    def run(problem: LayoutProblem, *, mode: str = "auto",
            fill_residual: bool = False,
            cache: LayoutCache | None = None) -> Layout:
        # closed-form baseline: the scheduling knobs don't apply, and it
        # is cheaper than a cache lookup
        return fn(problem)

    run.__name__ = f"strategy_{name}"
    run.__doc__ = fn.__doc__
    STRATEGIES.register(name, run)


for _name, _fn in ALL_BASELINES.items():
    _register_baseline(_name, _fn)
STRATEGIES.register("iris", schedule)


# ----------------------------------------------------------------------
# backend registry: execution targets for a Plan
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Backend:
    """One execution target for a :class:`Plan`.

    ``decode(plan, buf, **kw)`` reverses the packed buffer into per-array
    code streams; ``emit(plan, **kw)`` renders source code.  A backend
    may support either or both; unset capabilities raise
    ``NotImplementedError`` with the backends that do support them.
    """

    name: str
    decode: Callable[..., dict[str, np.ndarray]] | None = None
    emit: Callable[..., str] | None = None


def _as_u64(out: dict[str, Any]) -> dict[str, np.ndarray]:
    """Normalize backend output to uint64 numpy arrays (cross-backend
    equality is then plain ``np.array_equal``)."""
    return {k: np.asarray(v).astype(np.uint64) for k, v in out.items()}


# backend callables take explicit keywords only — a misspelled option
# must raise TypeError, not silently fall back to a default
def _decode_numpy(pl: "Plan", buf: np.ndarray, *,
                  compiled: bool = True) -> dict[str, np.ndarray]:
    if compiled:
        return _as_u64(unpack_compiled(pl.layout, np.asarray(buf),
                                       program=pl.exec_program))
    return _as_u64(unpack_arrays(pl.layout, np.asarray(buf)))


def _decode_pallas(pl: "Plan", buf: np.ndarray, *,
                   fused: bool = True) -> dict[str, np.ndarray]:
    from .kernels.ops import decode_layout  # lazy: pulls in JAX

    if fused:
        return _as_u64(decode_layout(pl.layout, buf, fused=True,
                                     program=pl.exec_program))
    return _as_u64(decode_layout(pl.layout, buf, fused=False,
                                 plan=pl.decode_plan))


def _emit_c(pl: "Plan", *, artifact: str = "decode",
            word_bits: int = 64) -> str:
    # no **kw passthrough: a misspelled option must fail, not silently
    # emit default-width source
    if artifact == "decode":
        return emit_c_decode(pl.layout)
    if artifact == "pack":
        return emit_c_pack(pl.layout, word_bits=word_bits)
    if artifact == "both":
        return (emit_c_pack(pl.layout, word_bits=word_bits)
                + "\n\n" + emit_c_decode(pl.layout))
    raise ValueError(
        f"unknown C artifact {artifact!r}; expected 'pack', 'decode' or 'both'"
    )


#: Execution backends.
BACKENDS: Registry[Backend] = Registry("backend")
BACKENDS.register("numpy", Backend("numpy", decode=_decode_numpy))
BACKENDS.register("pallas", Backend("pallas", decode=_decode_pallas))
BACKENDS.register("c", Backend("c", emit=_emit_c))


def strategies() -> list[str]:
    """Registered strategy names, registration order (iris last)."""
    return STRATEGIES.names()


def backends() -> list[str]:
    """Registered backend names."""
    return BACKENDS.names()


# ----------------------------------------------------------------------
# the Plan object
# ----------------------------------------------------------------------
class Plan:
    """Lazy handle over one (problem, strategy) layout pipeline.

    Nothing is scheduled at construction (the strategy name is validated
    eagerly so typos fail fast); the layout materializes on first access
    to :attr:`layout` / :attr:`metrics` / :attr:`decode_plan` and is
    memoized, as are the derived artifacts.  ``cache`` defaults to the
    process-wide :data:`~repro.core.iris.DEFAULT_CACHE`, so identical
    problems across Plans share one scheduler run.
    """

    def __init__(self, problem: LayoutProblem, strategy: str = "iris", *,
                 mode: str = "auto", fill_residual: bool = False,
                 cache: LayoutCache | None = DEFAULT_CACHE) -> None:
        self._strategy_fn = STRATEGIES.get(strategy)   # fail fast on typos
        self.problem = problem
        self.strategy = strategy
        self.mode = mode
        self.fill_residual = fill_residual
        self.cache = cache
        self._layout: Layout | None = None
        self._metrics: LayoutMetrics | None = None
        self._decode_plan: DecodePlan | None = None
        self._exec_program: ExecProgram | None = None
        self._provenance: str | None = None
        self._stream_tables: dict = {}

    # -- lazy pipeline stages ------------------------------------------
    @property
    def layout(self) -> Layout:
        """The scheduled :class:`Layout` (computed on first access)."""
        if self._layout is None:
            hits0 = self.cache.hits if self.cache is not None else 0
            self._layout = self._strategy_fn(
                self.problem, mode=self.mode,
                fill_residual=self.fill_residual, cache=self.cache,
            )
            if self.strategy != "iris":
                self._provenance = "closed-form"
            elif self.cache is not None and self.cache.hits > hits0:
                self._provenance = "cache-hit"
            else:
                self._provenance = "scheduled"
        return self._layout

    @property
    def provenance(self) -> str:
        """Where the layout came from: ``"scheduled"``, ``"cache-hit"``
        or ``"closed-form"`` (``"unscheduled"`` before first access)."""
        return self._provenance or "unscheduled"

    @property
    def metrics(self) -> LayoutMetrics:
        """Paper metrics (C_max, L_max, B_eff, FIFO depths) of the layout."""
        if self._metrics is None:
            self._metrics = self.layout.metrics()
        return self._metrics

    @property
    def decode_plan(self) -> DecodePlan:
        """Static decode program (paper Listing 2 as a table)."""
        if self._decode_plan is None:
            self._decode_plan = decode_plan(self.layout)
        return self._decode_plan

    @property
    def exec_program(self) -> ExecProgram:
        """Compiled execution plan (flat pack/unpack tables + the fused
        Pallas kernel's slot table).  Lowered once per layout signature:
        the program cache lives on the layout and is shared across
        :class:`~repro.core.iris.LayoutCache` rebinds, so a cache hit
        returns a plan whose program is already built."""
        if self._exec_program is None:
            self._exec_program = lower_exec(self.layout)
        return self._exec_program

    @property
    def c_max(self) -> int:
        return self.layout.c_max

    @property
    def stream_bytes(self) -> int:
        """Size of the packed unified buffer in bytes."""
        return self.layout.c_max * self.problem.m // 8

    # -- uniform execution surface -------------------------------------
    def pack(self, arrays: dict[str, np.ndarray], *,
             compiled: bool = True, backend: str = "numpy") -> np.ndarray:
        """Pack per-array codes into the unified ``(c_max, m/8)`` buffer
        (paper Listing 1).

        ``backend="numpy"`` (default) packs host-side: the vectorized
        :class:`~repro.core.exec_plan.ExecProgram` when ``compiled=True``,
        the legacy per-slot reference path otherwise.
        ``backend="pallas"`` runs the fused device pack kernel
        (:func:`~repro.kernels.layout_pack.pack_layout_fused`, imported
        lazily so this module stays importable without JAX).  All paths
        are bit-identical.
        """
        if backend == "pallas":
            from repro.kernels.layout_pack import pack_layout_fused

            return pack_layout_fused(self.layout, arrays,
                                     program=self.exec_program)
        if backend != "numpy":
            raise NotImplementedError(
                f"backend {backend!r} cannot pack; use 'numpy' or 'pallas'"
            )
        if compiled:
            return pack_compiled(self.layout, arrays,
                                 program=self.exec_program)
        return pack_arrays(self.layout, arrays)

    def decode(self, buf: np.ndarray, backend: str = "numpy",
               **kw: Any) -> dict[str, np.ndarray]:
        """Decode a packed buffer through a registered backend.

        Returns ``{name: uint64 ndarray}`` regardless of backend, so
        outputs compare bit-for-bit across backends.
        """
        b = BACKENDS.get(backend)
        if b.decode is None:
            can = [n for n in BACKENDS if BACKENDS.get(n).decode is not None]
            raise NotImplementedError(
                f"backend {backend!r} cannot decode; use one of {can}"
            )
        return b.decode(self, buf, **kw)

    def emit(self, target: str = "c", **kw: Any) -> str:
        """Emit source for a registered backend (e.g. the HLS C module).

        ``target="c"`` accepts ``artifact="decode" | "pack" | "both"``.
        """
        b = BACKENDS.get(target)
        if b.emit is None:
            can = [n for n in BACKENDS if BACKENDS.get(n).emit is not None]
            raise NotImplementedError(
                f"backend {target!r} cannot emit source; use one of {can}"
            )
        return b.emit(self, **kw)

    # -- stream-direct execution ----------------------------------------
    def stream_tables(self, weights: int | str, shape: tuple[int, int], *,
                      scales: int | str, group_size: int,
                      elem_widths: tuple[int, ...] | None = None,
                      ) -> StreamTables:
        """Bit-offset tables for one ``(K, N)`` stream-direct matmul.

        Memoized per (operands, shape, granularity) — serving calls hit
        the table once per weight matrix, not per token.
        """
        key = (weights, scales, shape, group_size, elem_widths)
        tabs = self._stream_tables.get(key)
        if tabs is None:
            prog = self.exec_program if elem_widths is None \
                else lower_exec(self.layout, elem_widths=elem_widths)
            tabs = stream_matmul_tables(
                self.layout, weights, shape, scales=scales,
                group_size=group_size, program=prog)
            self._stream_tables[key] = tabs
        return tabs

    def matmul_direct(self, x, buf, weights: int | str,
                      shape: tuple[int, int], *, scales: int | str,
                      group_size: int,
                      elem_widths: tuple[int, ...] | None = None,
                      **block_kw):
        """``x @ dequant(weights)`` straight out of the packed stream.

        The stream-direct exec surface: no dense intermediate ever
        materializes — the Pallas matmul prologue gathers packed words
        from ``buf`` against this plan's slot tables
        (:mod:`repro.kernels.stream_matmul`).  ``buf`` is the packed
        ``(c_max, m/8)`` uint8 buffer (or a precomputed uint32 stream
        from :func:`repro.kernels.stream_matmul.stream_words`).
        """
        from .kernels.stream_matmul import (  # lazy: pulls in JAX
            stream_matmul,
            stream_words,
        )

        tabs = self.stream_tables(weights, shape, scales=scales,
                                  group_size=group_size,
                                  elem_widths=elem_widths)
        buf = np.asarray(buf) if not hasattr(buf, "dtype") else buf
        if buf.dtype == np.uint8:
            prog = self.exec_program if elem_widths is None \
                else lower_exec(self.layout, elem_widths=elem_widths)
            buf = stream_words(prog, np.asarray(buf))
        return stream_matmul(x, buf, tabs, **block_kw)

    # -- conveniences ---------------------------------------------------
    def validate(self) -> "Plan":
        """Validate the layout (legal, complete transfer plan); chainable."""
        self.layout.validate()
        return self

    def verify(self, *, raise_on_error: bool = True, passes=None):
        """Run the static layout analyzer over this plan's layout and
        lowered tables (:mod:`repro.analysis`).

        Returns the :class:`~repro.analysis.Report`; with
        ``raise_on_error=True`` (default) any error-severity finding
        raises :class:`~repro.analysis.AnalysisError` naming the rule —
        "verify before you serve".
        """
        from .analysis import verify_layout  # lazy: keep api import lean

        report = verify_layout(
            self.layout, program=self.exec_program, passes=passes,
            subject=f"Plan[{self.strategy}]")
        return report.raise_if_errors() if raise_on_error else report

    def render(self, max_cycles: int = 64) -> str:
        """ASCII rendering in the style of the paper's Figs. 3-5."""
        return self.layout.render(max_cycles=max_cycles)

    def summary(self) -> str:
        """One-line report: strategy, size, B_eff, buffer bytes and cache
        provenance (forces scheduling).  Used by serve.py's reporting."""
        m = self.metrics
        return (
            f"Plan[{self.strategy}] m={self.problem.m}"
            f" arrays={len(self.problem.arrays)}"
            f" C_max={m.c_max} B_eff={m.efficiency:.4f}"
            f" stream={self.stream_bytes / 2**10:.1f} KiB"
            f" cache={self.provenance}"
        )

    def __repr__(self) -> str:
        if self._layout is None:
            return (
                f"Plan({self.strategy!r}, m={self.problem.m}, "
                f"n_arrays={len(self.problem.arrays)}, unscheduled)"
            )
        return f"<{self.summary()}>"


def plan(problem: LayoutProblem, strategy: str = "iris", *,
         mode: str = "auto", fill_residual: bool = False,
         cache: LayoutCache | None = DEFAULT_CACHE) -> Plan:
    """Build a lazy :class:`Plan` for ``problem`` under ``strategy``.

    The one front door: every consumer — examples, sweeps, serving,
    benchmarks — goes through here.  Unknown strategies raise a
    ``KeyError`` listing the registered names.
    """
    return Plan(problem, strategy, mode=mode, fill_residual=fill_residual,
                cache=cache)


def plan_many(problems: Sequence[LayoutProblem], strategy: str = "iris", *,
              mode: str = "auto", fill_residual: bool = False,
              cache: LayoutCache | None = DEFAULT_CACHE) -> list[Plan]:
    """Batch :func:`plan`: problems sharing a canonical signature are
    scheduled once (``cache=None`` still dedupes within the batch via an
    ephemeral cache, mirroring :func:`~repro.core.iris.schedule_many`)."""
    if cache is None:
        cache = LayoutCache(maxsize=max(1, len(problems)))
    return [
        Plan(p, strategy, mode=mode, fill_residual=fill_residual, cache=cache)
        for p in problems
    ]


def compare(problem: LayoutProblem,
            strategies: Sequence[str] | None = None, *,
            mode: str = "auto", fill_residual: bool = False,
            cache: LayoutCache | None = DEFAULT_CACHE,
            ) -> dict[str, LayoutMetrics]:
    """Metrics per strategy — the paper's Figs. 3-5 / Tables 6-7 columns.

    Iterates the whole strategy registry unless ``strategies`` narrows it.
    """
    names = list(strategies) if strategies is not None else STRATEGIES.names()
    return {
        name: plan(problem, name, mode=mode, fill_residual=fill_residual,
                   cache=cache).metrics
        for name in names
    }


# ----------------------------------------------------------------------
# layer-stack planning (the serving hot path)
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LayerStackPlan:
    """Per-layer Iris stream plans for a uniform decoder stack.

    Every layer of a uniform stack poses the same scheduling instance, so
    the scheduler runs at most once; further layers are cache rebinds.
    ``scheduler_runs`` / ``cache_hits`` are the deltas incurred by this
    call (a warm cache yields ``scheduler_runs == 0``).
    """

    problem: LayoutProblem          # one layer's bundle problem
    bundle: tuple                   # the BundleTensors the problem encodes
    plans: tuple[Plan, ...]         # one resolved Plan per layer

    scheduler_runs: int
    cache_hits: int

    @property
    def n_layers(self) -> int:
        return len(self.plans)

    @property
    def c_max_per_layer(self) -> int:
        return self.plans[0].c_max

    @property
    def b_eff(self) -> float:
        return self.plans[0].metrics.efficiency

    @property
    def stream_bytes_per_layer(self) -> int:
        return self.plans[0].stream_bytes

    def exec_program(self) -> ExecProgram:
        """Compiled execution plan at *bundle-element* granularity.

        Lowered with each tensor's ``width_bits`` as the piece width, so
        bundle data packs/decodes at element granularity even when the
        scheduled unit width exceeds 64 bits.  All layers share one
        layout signature, hence one program (cached on the layout)."""
        ew = tuple(b.width_bits for b in self.bundle)
        return lower_exec(self.plans[0].layout, elem_widths=ew)

    def stream_tables(self, name: str,
                      shape: tuple[int, int]) -> StreamTables:
        """Stream-direct matmul tables for bundle tensor ``name``.

        Resolves the paired ``{name}_scales`` tensor and derives the
        quantization group size from the bundle element counts, so
        callers hand in only the weight name and its ``(K, N)`` shape.
        All layers share the tables (one layout signature).
        """
        by_name = {b.name: b for b in self.bundle}
        if name not in by_name:
            raise KeyError(f"no bundle tensor named {name!r}")
        sname = f"{name}_scales"
        if sname not in by_name:
            raise KeyError(f"bundle tensor {name!r} has no paired scales")
        w, s = by_name[name], by_name[sname]
        k, n = shape
        if k * n != w.n_elems:
            raise ValueError(
                f"{name}: shape {shape} has {k * n} elements, bundle "
                f"holds {w.n_elems}"
            )
        if w.n_elems % s.n_elems:
            raise ValueError(
                f"{name}: scale count {s.n_elems} does not divide "
                f"weight count {w.n_elems}"
            )
        group_size = w.n_elems // s.n_elems
        ew = tuple(b.width_bits for b in self.bundle)
        return self.plans[0].stream_tables(
            name, shape, scales=sname, group_size=group_size,
            elem_widths=ew)

    def matmul_direct(self, x, buf, name: str, shape: tuple[int, int],
                      **block_kw):
        """Stream-direct ``x @ dequant(name)`` against one layer's buffer.

        ``buf`` is that layer's packed stream (uint8 rows or a
        precomputed uint32 word stream).  Any bundle element width <= 32
        works — including the widths ``packed_matmul`` cannot lane-pack.
        """
        tabs = self.stream_tables(name, shape)
        group_size = tabs.group_size
        ew = tuple(b.width_bits for b in self.bundle)
        return self.plans[0].matmul_direct(
            x, buf, name, shape, scales=f"{name}_scales",
            group_size=group_size, elem_widths=ew, **block_kw)


def plan_layer_stack(cfg, qspec, *, m: int = 4096,
                     n_layers: int | None = None, mode: str = "auto",
                     strategy: str = "iris",
                     cache: LayoutCache | None = DEFAULT_CACHE,
                     bundle=None,
                     ) -> LayerStackPlan:
    """Plan the per-layer weight-stream layouts for a model config.

    ``cfg`` is any object with ``d_model / d_ff / n_heads / n_kv_heads /
    head_dim`` (and ``n_layers`` unless passed explicitly); ``qspec`` is
    the weight :class:`~repro.quant.qtypes.QuantSpec`.  The internal
    engine of :func:`pack_tree`, and shared by
    ``repro.launch.serve --packed`` and
    :func:`repro.core.packing.serving_stream_report`.  Every layer of a
    uniform stack poses the same scheduling instance: ``"iris"`` costs
    one scheduler run (or zero on a warm cache) plus N-1 rebinds;
    baseline strategies are closed-form and computed once outright.

    ``bundle`` overrides the scheduled tensor set: any sequence of
    :class:`~repro.core.packing.BundleTensor` replaces the default
    per-layer weight bundle while keeping the shared planning/cache
    path — how ``repro.kvcache`` plans its per-page KV stream once and
    rebinds it across every layer's pages.
    """
    from .core.packing import bundle_problem, layer_bundle_spec  # lazy

    if bundle is None:
        bundle = layer_bundle_spec(cfg.d_model, cfg.d_ff, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.head_dim, qspec)
    prob = bundle_problem(bundle, m=m)
    n = int(cfg.n_layers if n_layers is None else n_layers)
    if n <= 0:
        raise ValueError(f"n_layers must be positive, got {n}")
    local = cache if cache is not None else LayoutCache(maxsize=1)
    hits0, misses0 = local.hits, local.misses
    if strategy == "iris":
        layouts = schedule_many([prob] * n, mode=mode, cache=local)
    else:
        lay0 = plan(prob, strategy, mode=mode, cache=None).layout
        layouts = [lay0] * n
    plans = []
    for i, lay in enumerate(layouts):
        pl = Plan(prob, strategy, mode=mode, cache=local)
        pl._layout = lay
        if strategy != "iris":
            pl._provenance = "closed-form"
        else:
            pl._provenance = "cache-hit" if (i or local.misses == misses0) \
                else "scheduled"
        plans.append(pl)
    # every layer shares the first layout's count runs; validating one
    # validates the stack (and catches scheduler regressions before any
    # consumer reports metrics off an illegal plan)
    plans[0].validate()
    return LayerStackPlan(
        problem=prob,
        bundle=tuple(bundle),
        plans=tuple(plans),
        scheduler_runs=local.misses - misses0,
        cache_hits=local.hits - hits0,
    )
