"""The `repro.api` façade: registries, Plan laziness, cross-backend
equivalence, layer-stack planning, and the compatibility re-export
policy (every pre-façade import path must keep resolving).
"""
import numpy as np
import pytest

import repro
from repro import api
from repro.api import make_problem
from repro.core import LayoutCache

# The three acceptance problems: the paper §4 worked example, a
# non-power-of-two-width problem, and a lane-capped bundle-style problem.
PROBLEMS = {
    "paper_example": api.PAPER_EXAMPLE,
    "non_pow2": make_problem(
        64, [("a", 3, 40, 4), ("b", 5, 24, 8), ("c", 6, 16, 12),
             ("d", 11, 9, 2)]),
    "lane_capped_bundle": make_problem(
        64, [("w", 4, 96, 6), ("s", 16, 24, 6), ("n", 8, 16, 2)],
        max_lanes=2),
}


# ----------------------------------------------------------------------
# cross-backend equivalence: every strategy x every decode backend
# ----------------------------------------------------------------------
@pytest.mark.parametrize("prob_name", sorted(PROBLEMS))
@pytest.mark.parametrize("strategy", api.strategies())
def test_cross_backend_equivalence(strategy, prob_name):
    """pack -> decode roundtrips bit-for-bit on both backends."""
    prob = PROBLEMS[prob_name]
    pl = api.plan(prob, strategy, cache=None).validate()
    codes = api.random_codes(prob, seed=7)
    buf = pl.pack(codes)
    out_np = pl.decode(buf, backend="numpy")
    out_pl = pl.decode(buf, backend="pallas")
    for name, want in codes.items():
        assert np.array_equal(out_np[name], want), (strategy, name)
        assert np.array_equal(out_pl[name], out_np[name]), (strategy, name)
        assert out_np[name].dtype == out_pl[name].dtype == np.uint64


def test_c_backend_emits_both_listings():
    pl = api.plan(api.PAPER_EXAMPLE)
    src = pl.emit(target="c", artifact="both")
    assert "void pack(" in src          # paper Listing 1
    assert "void read_data(" in src     # paper Listing 2
    assert pl.emit(target="c") == pl.emit(target="c", artifact="decode")
    with pytest.raises(ValueError, match="artifact"):
        pl.emit(target="c", artifact="verilog")


# ----------------------------------------------------------------------
# registries
# ----------------------------------------------------------------------
def test_unknown_strategy_lists_registered_names():
    with pytest.raises(KeyError) as ei:
        api.plan(api.PAPER_EXAMPLE, "irsi")
    msg = str(ei.value)
    for name in api.strategies():
        assert name in msg


def test_unknown_backend_lists_registered_names():
    pl = api.plan(api.PAPER_EXAMPLE)
    with pytest.raises(KeyError) as ei:
        pl.decode(np.zeros((9, 1), np.uint8), backend="cuda")
    msg = str(ei.value)
    for name in api.backends():
        assert name in msg


def test_backend_capability_errors_name_alternatives():
    pl = api.plan(api.PAPER_EXAMPLE)
    with pytest.raises(NotImplementedError, match="numpy"):
        pl.decode(np.zeros((9, 1), np.uint8), backend="c")
    with pytest.raises(NotImplementedError, match="'c'"):
        pl.emit(target="numpy")


def test_duplicate_registration_rejected():
    with pytest.raises(KeyError, match="already registered"):
        api.STRATEGIES.register("iris", lambda p, **kw: None)


def test_custom_strategy_registers_and_plans():
    from repro.core.baselines import naive_layout

    api.STRATEGIES.register(
        "reversed_naive",
        lambda p, **kw: naive_layout(p), overwrite=True)
    try:
        m = api.plan(api.PAPER_EXAMPLE, "reversed_naive").metrics
        assert m.c_max == 19
        assert "reversed_naive" in api.strategies()
        assert api.compare(api.PAPER_EXAMPLE)["reversed_naive"].c_max == 19
    finally:
        del api.STRATEGIES._entries["reversed_naive"]


# ----------------------------------------------------------------------
# Plan semantics
# ----------------------------------------------------------------------
def test_plan_is_lazy_and_memoized():
    cache = LayoutCache()
    pl = api.plan(api.PAPER_EXAMPLE, cache=cache)
    assert cache.misses == 0            # nothing scheduled yet
    lay = pl.layout
    assert cache.misses == 1
    assert pl.layout is lay             # memoized, no second run
    assert pl.metrics is pl.metrics
    assert pl.decode_plan is pl.decode_plan
    assert cache.misses == 1


def test_plan_routes_through_shared_cache_by_default():
    p = make_problem(32, [("x", 3, 50, 5), ("y", 7, 30, 9)])
    from repro.core.iris import DEFAULT_CACHE

    api.plan(p).layout
    h0 = DEFAULT_CACHE.hits
    api.plan(p).layout                  # identical problem: cache hit
    assert DEFAULT_CACHE.hits == h0 + 1


def test_plan_many_dedupes_without_shared_cache():
    p = make_problem(32, [("x", 3, 50, 5), ("y", 7, 30, 9)])
    plans = api.plan_many([p, p, p], cache=None)
    layouts = [pl.layout for pl in plans]
    cache = plans[0].cache
    assert cache.misses == 1 and cache.hits == 2
    assert all(lay.count_intervals == layouts[0].count_intervals
               for lay in layouts)


def test_plan_stream_bytes_matches_buffer():
    pl = api.plan(api.PAPER_EXAMPLE)
    buf = pl.pack(api.random_codes(pl.problem))
    assert pl.stream_bytes == buf.size == pl.c_max * pl.problem.m // 8


def test_compare_covers_whole_registry():
    out = api.compare(api.PAPER_EXAMPLE)
    assert list(out) == api.strategies()
    assert out["iris"].c_max == 9 and out["naive"].c_max == 19


# ----------------------------------------------------------------------
# layer-stack planning (shared by serve --packed and packing reports)
# ----------------------------------------------------------------------
class _Cfg:
    name = "toy"
    d_model, d_ff = 64, 128
    n_heads, n_kv_heads, head_dim = 4, 2, 16
    n_layers = 5


def test_plan_layer_stack_schedules_once():
    from repro.quant import QuantSpec

    stack = api.plan_layer_stack(_Cfg, QuantSpec(bits=4, group_size=32),
                                 m=512, cache=LayoutCache())
    assert stack.n_layers == _Cfg.n_layers
    assert stack.scheduler_runs == 1
    assert stack.cache_hits == _Cfg.n_layers - 1
    first = stack.plans[0].layout
    assert all(pl.layout.count_intervals == first.count_intervals
               for pl in stack.plans)
    assert stack.stream_bytes_per_layer == stack.c_max_per_layer * 512 // 8
    assert 0 < stack.b_eff <= 1


def test_plan_layer_stack_agrees_with_serving_report():
    from repro.core.packing import serving_stream_report
    from repro.quant import QuantSpec

    qspec = QuantSpec(bits=4, group_size=32)
    cache = LayoutCache()
    stack = api.plan_layer_stack(_Cfg, qspec, m=512, n_layers=1, cache=cache)
    rep = serving_stream_report(_Cfg, qspec, m=512, cache=cache)
    assert rep["iris_MiB_per_layer"] == pytest.approx(
        stack.stream_bytes_per_layer / 2**20)
    assert rep["n_decode_units"] == stack.plans[0].decode_plan.n_units


# ----------------------------------------------------------------------
# compatibility: every pre-façade import path keeps resolving
# ----------------------------------------------------------------------
def test_old_import_paths_still_resolve():
    from repro.core.baselines import (       # noqa: F401
        ALL_BASELINES,
        hls_padded_layout,
        homogeneous_layout,
        naive_layout,
    )
    from repro.core.codegen import (         # noqa: F401
        decode_plan,
        emit_c_decode,
        emit_c_pack,
        pack_arrays,
        random_codes,
        unpack_arrays,
    )
    from repro.core.dse import sweep_max_lanes, sweep_widths  # noqa: F401
    from repro.core.iris import (            # noqa: F401
        DEFAULT_CACHE,
        LayoutCache,
        schedule,
        schedule_many,
    )
    from repro.core.layout import Layout, LayoutMetrics  # noqa: F401
    from repro.core.packing import (         # noqa: F401
        bundle_problem,
        layer_bundle_spec,
        pack_bundle,
        serving_stream_report,
    )
    from repro.core.task import (            # noqa: F401
        INV_HELMHOLTZ,
        PAPER_EXAMPLE,
        ArraySpec,
        LayoutProblem,
        make_problem,
        matmul_problem,
    )

    # curated exports alias the originals, not copies — and the
    # pre-façade compat aliases now warn, naming the repro.api
    # replacement, while still resolving to the same object
    with pytest.deprecated_call(match="repro.api"):
        assert repro.core.schedule is schedule
    with pytest.deprecated_call(match="repro.api"):
        assert repro.schedule is schedule
    with pytest.deprecated_call(match="repro.api.PAPER_EXAMPLE"):
        assert repro.core.PAPER_EXAMPLE is PAPER_EXAMPLE


def test_deprecated_packed_params_alias():
    """`PackedParams` warns, names the replacement, and still works."""
    with pytest.deprecated_call(match="repro.api.PackedTree"):
        from repro.models.quantized import PackedParams
    assert PackedParams is api.PackedTree


def test_curated_all_exports_resolve():
    import warnings

    with warnings.catch_warnings():
        # the compat aliases in __all__ warn by design; they must still
        # all resolve
        warnings.simplefilter("ignore", DeprecationWarning)
        for name in repro.core.__all__:
            assert getattr(repro.core, name) is not None
        for name in repro.__all__:
            assert getattr(repro, name) is not None
        for name in api.__all__:
            assert getattr(api, name) is not None


def test_version_sourced_from_pyproject():
    import pathlib
    import re

    assert re.fullmatch(r"\d+\.\d+.*", repro.__version__)
    pyproject = (pathlib.Path(repro.__file__).resolve().parents[2]
                 / "pyproject.toml")
    m = re.search(r'^version\s*=\s*"([^"]+)"', pyproject.read_text(),
                  re.MULTILINE)
    assert m is not None
    assert repro.__version__ == m.group(1)
