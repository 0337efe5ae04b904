"""Property tests: stream-direct matmul over randomized bundles,
widths and layout strategies agrees with the float host reference, and
is bit-invariant to the layout strategy.

Skipped gracefully where hypothesis is not installed (the deterministic
equivalence suite in test_stream_matmul.py always runs).  Under
``HYPOTHESIS_PROFILE=ci`` (see conftest) the sweep is derandomized so
CI failures reproduce exactly.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings

from conftest import build_stream_case, stream_matmul_cases
from repro.core.baselines import homogeneous_layout
from repro.kernels.ref import stream_matmul_ref
from repro.kernels.stream_matmul import stream_matmul, stream_words


def _run_case(case, x):
    import jax.numpy as jnp

    _, _, _, prog, buf, tabs = case
    sw = stream_words(prog, buf)
    got = stream_matmul(jnp.asarray(x), sw, tabs)
    return np.asarray(got), np.asarray(sw), tabs


@given(stream_matmul_cases())
@settings(max_examples=10, deadline=None)
def test_matches_host_reference(case_params):
    """pack -> stream-direct matmul == float reference (any bits,
    ragged M/K/N, both bus widths, both strategies)."""
    bits, g, k, n, m, bus, strategy = case_params
    layout_fn = None if strategy == "iris" else homogeneous_layout
    case = build_stream_case(bits, g, k, n, m=bus, layout_fn=layout_fn)
    rng = np.random.default_rng(bits * 31 + k + n + m)
    x = rng.standard_normal((m, k)).astype(np.float32)
    got, sw, tabs = _run_case(case, x)
    want = np.asarray(stream_matmul_ref(
        x, sw, tabs.w_tab, tabs.s_tab, bits=bits, group_size=g))
    assert got.shape == (m, n)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@given(stream_matmul_cases())
@settings(max_examples=6, deadline=None)
def test_layout_strategy_invariance(case_params):
    """The same codes through two different layouts produce *bit
    identical* matmul outputs — the slot tables fully absorb the
    placement."""
    bits, g, k, n, m, bus, _ = case_params
    rng = np.random.default_rng(k * 7 + n)
    x = rng.standard_normal((m, k)).astype(np.float32)
    iris, _, _ = _run_case(build_stream_case(bits, g, k, n, m=bus), x)
    homo, _, _ = _run_case(
        build_stream_case(bits, g, k, n, m=bus,
                          layout_fn=homogeneous_layout), x)
    np.testing.assert_array_equal(iris, homo)
